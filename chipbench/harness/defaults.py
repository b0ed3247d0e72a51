"""What a configuration's files need not say: the image-classifier family.

A configuration brings its family as files. Its reference module may define
``input_spec``, ``loss`` and ``train_flops_per_sample`` beside ``cells`` and
``kinds``, and its ``entry_point`` may name a ``build_trainer`` and an
``input_stream`` of the program (``chipbench/README.md``, "Adding things").
Where a file is silent the harness takes what is here, the family of the
first configurations: square float32 images, one label an image, the
program's ``--app 3`` stream. This is the one file of the harness that
names a family's input keys; ``tests/test_benchmark_json.py`` holds the
others to that.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def input_spec(model: dict, traffic: dict):
    """``(shape without the batch, dtype)`` of one sample."""
    size = int(model["image_size"])
    return (size, size, int(model["image_channels"])), jnp.float32


def loss(logits, labels):
    """Mean softmax cross-entropy over every leading axis of ``labels``:
    over the batch for ``[N, C]`` logits, over batch and positions for
    ``[N, S, V]``."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)


def input_stream(cfg, traffic: dict, seed: int):
    """The program's synthetic image pipeline (native fill, one batch
    prefetched by a thread), seeded by the run: host ``(x, y)`` batches."""
    from mpi4dl_tpu.data import SyntheticImages

    return SyntheticImages(
        int(traffic["batch_size"]), cfg.image_size, cfg.num_classes, seed=seed,
        prefetch=bool(traffic["prefetch"]),
    )
