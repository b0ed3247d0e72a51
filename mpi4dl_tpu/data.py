"""Datasets for the benchmark entry points.

Reference parity (``benchmark_amoebanet_sp.py:264-306``): ``--app`` selects
1 = real medical images via ImageFolder at ``--datapath``, 2 = CIFAR-10,
3 = synthetic fake data. The reference uses torchvision loaders; here the
synthetic path is pure numpy (the benchmarks' hot path — every reference
benchmark defaults to it), and the torchvision-backed paths are used when
torchvision + data are actually present, else fall back to synthetic with a
warning (the benchmark cluster has no egress).
"""

from __future__ import annotations

import sys

import numpy as np


class SyntheticImages:
    """Deterministic fake-data stream (ref ``torchvision.datasets.FakeData``
    with ``transforms.ToTensor``: uniform [0,1) pixels). NHWC float32.

    Batch synthesis runs in the native multithreaded runtime when built
    (:mod:`mpi4dl_tpu.native`; counter-based RNG, thread-count independent),
    with a one-batch-deep background prefetch thread so host synthesis
    overlaps device compute — the role of the reference's DataLoader
    ``--num-workers``. Falls back to single-threaded numpy.
    """

    def __init__(
        self,
        batch_size,
        image_size,
        num_classes,
        length=60000,
        seed=0,
        prefetch=True,
    ):
        self.batch_size = batch_size
        self.image_size = image_size
        self.num_classes = num_classes
        self.length = length
        self.seed = seed
        self.prefetch = prefetch

    def __len__(self):
        return max(self.length // self.batch_size, 1)

    def _make_batch(self, i):
        from mpi4dl_tpu import native

        x = native.fill_uniform(
            (self.batch_size, self.image_size, self.image_size, 3),
            seed=self.seed * 1_000_003 + i,
        )
        y = native.fill_labels(
            self.batch_size, self.num_classes, seed=self.seed * 7_000_003 + i
        )
        return x, y

    def __iter__(self):
        return _batches(self._make_batch, len(self), self.prefetch)


def _batches(make_batch, n: int, prefetch: bool):
    """``make_batch(0) .. make_batch(n - 1)``, with ``prefetch`` made one
    batch ahead by a background thread so that host synthesis overlaps
    device compute."""
    if not prefetch:
        for i in range(n):
            yield make_batch(i)
        return

    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=2)
    stop = threading.Event()

    def producer():
        try:
            for i in range(n):
                item = (None, make_batch(i))
                # Bounded put so an abandoned consumer (early break in the
                # epoch loop) doesn't pin this thread + batches forever.
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as e:  # propagate instead of hanging q.get
            q.put((e, None))
            return
        q.put(None)

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is None:
                break
            err, batch = item
            if err is not None:
                raise err
            yield batch
    finally:
        stop.set()  # runs on generator close/GC too — unblocks producer


class SyntheticTokens:
    """Deterministic token stream for a sequence model: each row draws
    ``sequence_length + 1`` ids uniformly over ``vocab_size`` from
    ``(seed, batch index)``; the input is the first ``sequence_length`` of
    them and the labels are the next token at every position. One document
    a sequence: no padding, no segment mask. ``[batch, sequence_length]``
    int32 both. Prefetched like :class:`SyntheticImages`."""

    def __init__(self, batch_size, sequence_length, vocab_size, length=60000,
                 seed=0, prefetch=True):
        self.batch_size = batch_size
        self.sequence_length = sequence_length
        self.vocab_size = vocab_size
        self.length = length
        self.seed = seed
        self.prefetch = prefetch

    def __len__(self):
        return max(self.length // self.batch_size, 1)

    def _make_batch(self, i):
        rng = np.random.default_rng(np.random.SeedSequence((self.seed, i)))
        ids = rng.integers(
            0, self.vocab_size, (self.batch_size, self.sequence_length + 1),
            dtype=np.int32)
        return ids[:, :-1], ids[:, 1:]

    def __iter__(self):
        return _batches(self._make_batch, len(self), self.prefetch)


class BlockDiffusionTokens:
    """Deterministic stream for block-diffusion training of a token model
    (the BD3-LM recipe: a noisy copy of every sequence beside the clean one).
    From ``(seed, batch index)`` each row draws ``sequence_length`` ids ``x0``
    uniformly over the vocabulary less ``mask_id`` (default: the last id,
    never drawn as data), a noise level ``t`` uniform over ``[t_min, 1]``
    (the linear schedule: a position is masked with probability ``t``) and
    the positions masked; a row that masked none masks one drawn position,
    so that every sequence carries loss. One document a sequence.

    ``x`` int32 ``[batch, 2 sequence_length]``: ``x0`` with ``mask_id`` at
    the masked positions, then ``x0`` itself. ``y`` int32 ``[batch,
    sequence_length, 2]``: at every position the token to predict (``x0``:
    no shift) and the BITS of its float32 weight, ``1 / t`` where the
    position is masked and 0 where it is not (``y[..., 1].view(float32)``;
    labels travel as one integer array and a weight must arrive exactly).
    Prefetched like :class:`SyntheticImages`."""

    def __init__(self, batch_size, sequence_length, vocab_size, mask_id=None,
                 t_min=1e-3, length=60000, seed=0, prefetch=True):
        self.batch_size = batch_size
        self.sequence_length = sequence_length
        self.vocab_size = vocab_size
        self.mask_id = vocab_size - 1 if mask_id is None else mask_id
        self.t_min = t_min
        self.length = length
        self.seed = seed
        self.prefetch = prefetch

    def __len__(self):
        return max(self.length // self.batch_size, 1)

    def _make_batch(self, i):
        rng = np.random.default_rng(np.random.SeedSequence((self.seed, i)))
        shape = (self.batch_size, self.sequence_length)
        x0 = rng.integers(0, self.vocab_size - 1, shape, dtype=np.int32)
        x0 += x0 >= self.mask_id  # every id but the mask's
        t = self.t_min + (1.0 - self.t_min) * rng.random(self.batch_size)
        t = t.astype(np.float32)
        masked = rng.random(shape) < t[:, None]
        one = rng.integers(0, self.sequence_length, self.batch_size)
        bare = ~masked.any(axis=1)
        masked[bare, one[bare]] = True
        weight = np.where(masked, 1 / t[:, None], 0).astype(np.float32)
        x = np.concatenate([np.where(masked, np.int32(self.mask_id), x0), x0], axis=1)
        return x, np.stack([x0, weight.view(np.int32)], axis=-1)

    def __iter__(self):
        return _batches(self._make_batch, len(self), self.prefetch)


class ClassPatternImages:
    """Learnable deterministic dataset: each class has a fixed smooth
    pattern template, each sample = its class template + Gaussian noise.

    This exists for convergence evidence (the reference's ``--app 2``
    CIFAR-10 path, ``benchmark_amoebanet_sp.py:264-306``, plays this role
    on a cluster with data; the benchmark machine has no egress, so the
    learnable signal is synthesized): a model that learns ANYTHING drives
    loss below ln(num_classes) and accuracy above 1/num_classes within a
    few hundred SGD steps, and a resumed run must continue the same curve.
    Pure numpy, fully determined by ``seed`` — two processes construct
    bit-identical streams, which is what makes kill/resume curves
    comparable across process boundaries.
    """

    def __init__(
        self,
        batch_size,
        image_size,
        num_classes,
        length=60000,
        seed=0,
        noise=0.25,
    ):
        self.batch_size = batch_size
        self.image_size = image_size
        self.num_classes = num_classes
        self.length = length
        self.seed = seed
        self.noise = noise
        # Low-frequency templates: random coarse grids upsampled to the
        # image size, so the signal survives pooling/striding.
        rng = np.random.default_rng(seed ^ 0x5EED)
        coarse = rng.standard_normal((num_classes, 4, 4, 3)).astype(np.float32)
        reps = (image_size + 3) // 4
        up = np.repeat(np.repeat(coarse, reps, axis=1), reps, axis=2)
        self._templates = up[:, :image_size, :image_size, :]

    def __len__(self):
        return max(self.length // self.batch_size, 1)

    def batch(self, i):
        # SeedSequence over the (seed, batch) pair: genuinely independent
        # per-pair streams. The old ``seed * 1_000_003 + i`` mix collided
        # across seeds ((0, 1000003) == (1, 0)) and degenerated to
        # ``default_rng(i)`` at seed 0 (ADVICE r5).
        rng = np.random.default_rng(np.random.SeedSequence((self.seed, i)))
        y = rng.integers(0, self.num_classes, size=(self.batch_size,))
        x = self._templates[y] + self.noise * rng.standard_normal(
            (self.batch_size, self.image_size, self.image_size, 3)
        ).astype(np.float32)
        return x.astype(np.float32), y.astype(np.int32)

    def __iter__(self):
        for i in range(len(self)):
            yield self.batch(i)


def _torchvision_loader(kind, args, batch_size, shard_id=0, num_shards=1):
    import torch
    import torchvision
    from torchvision import transforms

    transform = transforms.Compose(
        [
            transforms.Resize((args.image_size, args.image_size)),
            transforms.ToTensor(),
        ]
    )
    if kind == "imagefolder":
        ds = torchvision.datasets.ImageFolder(args.datapath, transform=transform)
    else:
        ds = torchvision.datasets.CIFAR10(
            root=args.datapath, train=True, transform=transform, download=False
        )
    sampler = None
    if num_shards > 1:
        # Multi-host: each data shard reads a disjoint subset (hosts at the
        # same data coordinate pass the same shard_id and stay identical).
        sampler = torch.utils.data.distributed.DistributedSampler(
            ds,
            num_replicas=num_shards,
            rank=shard_id,
            shuffle=False,
            # Without drop_last the sampler pads by wrapping, handing the
            # same leading samples to several shards — shards must stay
            # disjoint.
            drop_last=True,
        )
    loader = torch.utils.data.DataLoader(
        ds,
        batch_size=batch_size,
        shuffle=False,
        sampler=sampler,
        num_workers=args.num_workers,
        drop_last=True,
    )

    def gen():
        for xb, yb in loader:
            # torch NCHW -> NHWC numpy
            yield (
                np.ascontiguousarray(xb.numpy().transpose(0, 2, 3, 1)),
                yb.numpy().astype(np.int32),
            )

    class _Wrap:
        def __len__(self):
            return len(loader)

        def __iter__(self):
            return gen()

    return _Wrap()


def get_dataset(args, batch_size, num_classes, shard_id=0, num_shards=1):
    """Dataset iterable of (x NHWC f32, y i32) host batches.

    ``shard_id``/``num_shards`` shard the stream for multi-process runs
    along the batch axis (``run_training`` passes ``multihost.data_shard``,
    which keeps model-parallel co-hosts — same data coordinates — on the
    SAME shard). A token model (``--sequence-length``) reads
    :class:`SyntheticTokens`, ``num_classes`` being its vocabulary."""
    if getattr(args, "sequence_length", 0):
        return SyntheticTokens(
            batch_size, args.sequence_length, num_classes, seed=shard_id)
    if args.app in (1, 2):
        kind = "imagefolder" if args.app == 1 else "cifar"
        try:
            return _torchvision_loader(
                kind, args, batch_size, shard_id=shard_id, num_shards=num_shards
            )
        except Exception as e:  # no torchvision / no data on this machine
            print(
                f"app={args.app} dataset unavailable ({e}); using synthetic",
                file=sys.stderr,
            )
    return SyntheticImages(batch_size, args.image_size, num_classes, seed=shard_id)
