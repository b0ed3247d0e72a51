"""The traced step of every trainer, remat policy and evaluator, pinned.

Each program is traced on the CPU mesh at a tiny size (nothing runs) and the
sha256 of its jaxpr's text is compared with what ``c0a7bc1`` traced: a change
to ``ops/``, ``parallel/halo.py`` or a step's prologue that is meant to leave
the programs alone shows here that it did. A change that means to alter a
program replaces that program's hash, in the same PR and by name. A file of
its own so that ``--dist loadfile`` runs its ~100 s beside ``test_lfm2.py``'s,
not after them.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

import test_lfm2  # the tiny token models of these three files
import test_nemotron_h
import test_qwen3_next

from mpi4dl_tpu.config import ParallelConfig
from mpi4dl_tpu.train import Trainer


def _image_step(model, remat, spatial=False):
    """``Trainer._train_step`` of a tiny AmoebaNet-D or ResNet-v2 on one
    device, or with every cell but the head on 2x2 tiles (where AmoebaNet's
    last cells need 256 px to keep a tile wider than their halo)."""
    from mpi4dl_tpu.models.amoebanet import amoebanetd
    from mpi4dl_tpu.models.resnet import get_resnet_v2
    from mpi4dl_tpu.utils import get_depth

    size = 256 if spatial and model == "amoebanet" else 64
    cfg = ParallelConfig(
        batch_size=2, split_size=1, spatial_size=int(spatial), image_size=size,
        num_classes=10, **(dict(num_spatial_parts=(4,), slice_method="square")
                           if spatial else {}))
    if model == "amoebanet":
        build, kw = amoebanetd, dict(num_classes=10, num_layers=3, num_filters=32)
    else:
        build, kw = get_resnet_v2, dict(
            depth=get_depth(2, 2), num_classes=10, pool_kernel=size // 4)
    plain = build(dtype=jnp.float32, **kw)
    n_sp = len(plain) - 1 if spatial else 0
    cells = build(dtype=jnp.bfloat16, **(dict(spatial_cells=n_sp) if spatial else {}), **kw)
    trainer = Trainer(cells, n_sp, cfg, plain_cells=plain, remat=remat)
    state = jax.eval_shape(lambda: trainer.init(jax.random.PRNGKey(0), (2, size, size, 3)))
    return trainer._train_step, (
        state, jax.ShapeDtypeStruct((2, size, size, 3), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.int32))


def _token_step(tests=test_lfm2):
    """``Trainer._train_step`` of the tiny float32 model of a token model's
    test file, under "cell" remat."""
    trainer = tests._trainer(tests.MODEL, tests.LENGTH)
    state = jax.eval_shape(lambda: trainer.init(
        jax.random.PRNGKey(0), (tests.BATCH, tests.LENGTH), jnp.int32))
    ids = jax.ShapeDtypeStruct((tests.BATCH, tests.LENGTH), jnp.int32)
    return trainer._train_step, (state, ids, ids)


def _pipeline_step(kind):
    """Two stages of a ResNet-v1: a spatial front on 2x2 tiles ahead of the
    fill-drain schedule (the front is traced under ``vmap``), the 1F1B ring,
    and the two-directional GEMS schedule."""
    from mpi4dl_tpu.models.resnet import get_resnet_v1
    from mpi4dl_tpu.parallel.pipeline import GemsMasterTrainer, PipelineTrainer

    spatial = kind == "gpipe"
    cfg = ParallelConfig(
        batch_size=2 if spatial else 4, parts=2, split_size=2, image_size=32,
        spatial_size=int(spatial), **(dict(num_spatial_parts=(4,), slice_method="square")
                                      if spatial else {}))
    plain = get_resnet_v1(depth=8)
    if kind == "gpipe":
        n_sp = PipelineTrainer.spatial_cell_count(len(plain), cfg)
        trainer = PipelineTrainer(
            get_resnet_v1(depth=8, spatial_cells=n_sp), cfg, plain_cells=plain)
    elif kind == "1f1b":
        trainer = PipelineTrainer(plain, cfg, schedule="1f1b", virtual_stages=2)
    else:  # "gems"
        trainer = GemsMasterTrainer(plain, cfg)
    state = trainer.init(jax.random.PRNGKey(0))
    batch = getattr(trainer, "chunks", 1) * cfg.batch_size
    x, y = trainer.shard_batch(
        jnp.zeros((batch, 32, 32, 3), jnp.float32), jnp.zeros((batch,), jnp.int32))
    return trainer._train_step, (state, x, y)


def _eval_step(spatial):
    """``evaluate``'s frozen-statistics step on a tiny ResNet-v2: the plain
    cells on one device, or a spatial ``Trainer``'s cells on 2x2 tiles."""
    from mpi4dl_tpu import evaluate
    from mpi4dl_tpu.models.resnet import get_resnet_v2
    from mpi4dl_tpu.parallel.partition import init_cells
    from mpi4dl_tpu.utils import get_depth

    kw = dict(depth=get_depth(2, 1), num_classes=10, pool_kernel=8)
    plain = get_resnet_v2(**kw)
    x = jnp.zeros((4, 32, 32, 3), jnp.float32)
    y = jnp.zeros((4,), jnp.int32)
    params = init_cells(plain, jax.random.PRNGKey(3), x)
    stats = evaluate.collect_batch_stats(plain, params, [x])
    if not spatial:
        return evaluate.make_eval_step(plain), (params, stats, x, y)
    cfg = ParallelConfig(
        batch_size=4, split_size=1, spatial_size=1, num_spatial_parts=(4,),
        slice_method="square", image_size=32)
    trainer = Trainer(get_resnet_v2(spatial_cells=len(plain) - 1, **kw),
                      num_spatial_cells=len(plain) - 1, config=cfg, plain_cells=plain)
    return evaluate.make_spatial_eval_step(trainer), (params, stats, x, y)


# Each program with the sha256 of its jaxpr as ``c0a7bc1`` traced it: every
# trainer, remat policy and evaluator that shares ``ops/``, ``parallel/halo.py``
# and the step's prologue, so that a change there which is meant to leave the
# programs alone can show that it did. The two AmoebaNet hashes under False and
# "cell" date from fc8bfe1 (before ``Trainer`` learned the token family);
# "lfm2-cell" is PR 37's.
#
# The five programs under "cell" remat ("amoebanet-cell", "resnet-cell",
# "lfm2-cell", "qwen3_next-cell", "nemotron_h-cell") were replaced by PR 44,
# which meant to alter one parameter of one equation in them: each per-cell
# ``checkpoint`` equation prints ``policy=<function save_only_these_names.
# <locals>.policy>`` (``train._cell_ckpt``: what the fused kernels' forwards
# wrote is kept by name) where it printed ``policy=None``: 9, 8, 6, 6 and 11
# lines. Checked on the parent 4e657b6 and on PR 44's tree: with the lines
# that hold ``policy=`` taken out the two texts of each program are equal
# (the CPU traces dispatch no kernel, so no ``name`` equation appears; the
# plain paths' own checkpoints still print ``policy=None``). Their hashes at
# the parent: 44145fc81dac99cf..., 6b2484188b6745ac..., 3f717aa8fd99b954...,
# d1ceef15601fc414..., 32369203498bf3f6...
#
# The three token programs under "cell" were replaced again by PR 46, which
# meant to alter one thing in them: the expert layer's forward names what it
# chose, sorted, gathered and multiplied (``ops/sequence._kept``) and the
# cell's checkpoint keeps the name, so each gains ``name`` equations (60, 88
# and 77 of them) and a ``reduce_precision`` on every kept float the forward
# reads on (jax's own guard against the two uses being merged: 7, 12, 12),
# and its replays lose what only led to a kept value (per program ``top_k``
# 6 -> 3, 8 -> 4, 8 -> 4; ``sort`` 14 -> 9, 18 -> 12, 18 -> 12;
# ``ragged_dot_general`` 36 -> 33, 48 -> 45, 32 -> 30; ``dot_general`` 75 ->
# 70, 316 -> 304, 146 -> 138). With the names off the three trace to the
# parent's hashes, which ``WITHOUT_THE_NAMES`` holds them to from now on.
#
# "qwen3_next-cell" and "nemotron_h-cell" were replaced by PR 47, which meant
# to alter one thing in them: ``GatedDeltaNet`` and ``Mamba2`` multiply a
# product of their input projection's columns for each array the causal
# convolution reads (q, k, v and the gate z; z, x, B, C and dt) where they
# split one wide product, and put each of q, k, v / x, B, C through the
# convolution as a whole array of its own (on a TPU the kernels of
# ``ops/causal_conv_pallas.py``, which take and give whole arrays; here, on
# the CPU, the plain function, three times over a third of the channels each).
# Per program, parent 20f01ba -> PR 47: ``dot_general`` 304 -> 340 and 138 ->
# 186 (a product a piece, forward, replay and two gradients), ``split`` 22 ->
# 10 and 16 -> 0, ``concatenate`` 16 -> 10 and 10 -> 2, ``slice`` 164 -> 303
# and 184 -> 384, ``pad`` 91 -> 163 and 97 -> 197 (the weights' columns, and
# the plain convolution's pads and taps a piece), ``mul`` 567 -> 669 and 371 ->
# 499, ``add_any`` 131 -> 192 and 89 -> 177; 15,076 -> 17,046 and 13,079 ->
# 15,903 lines. Nothing else of the two programs moved: with the two mixers'
# ``__call__`` as at the parent they trace to the parent's hashes
# (9d648b03a174d532..., 9cc3536d14a2feeb...), and "lfm2-cell" (``ShortConv``
# keeps the plain function) is untouched. ``WITHOUT_THE_NAMES`` moves with
# them for the same reason (5db85c6dd34a26a5..., a381ebc6b3f49670... before).
TRACED_AT_C0A7BC1 = {
    "amoebanet-False": (lambda: _image_step("amoebanet", False),
        "eb4431aae24c350019f855dfaac178d4cda883b9657eacc6eb69e7a5f24b0cb7"),
    "amoebanet-cell": (lambda: _image_step("amoebanet", "cell"),
        "9cc8b1ee56fdff0f15787bacac16806a266907590a22ba4dd3e22e4cf13cf359"),
    "amoebanet-scan": (lambda: _image_step("amoebanet", "scan"),
        "6e1f31140a639a956a961a6f3c76f938569a4c791bdab3d7d8687ebe12f1ba7d"),
    "amoebanet-scanlog": (lambda: _image_step("amoebanet", "scanlog"),
        "ce42cdfe204422a00795ceb17a1a14a6893e4d152c03db61cbe3945b105e310e"),
    "amoebanet-scanq": (lambda: _image_step("amoebanet", "scanq"),
        "6e1f31140a639a956a961a6f3c76f938569a4c791bdab3d7d8687ebe12f1ba7d"),
    "resnet-False": (lambda: _image_step("resnet", False),
        "ff3be412231a80692827220172917bdea737cf551f89eb98edeb2fef26a38a08"),
    "resnet-cell": (lambda: _image_step("resnet", "cell"),
        "da6aa9feba6a220c3ed5acd057d1576afd0ae079a3ecf73fc095c0205f631bd7"),
    "resnet-scan": (lambda: _image_step("resnet", "scan"),
        "dc77ab9f667588033eed0c7198c0f9c10e6973c5fc0be584d15cc197a9816abb"),
    "resnet-scanlog": (lambda: _image_step("resnet", "scanlog"),
        "e09e10e2cac45b1d860ada0bf126b3c84240ee996d701f10138819cd74ab6182"),
    "resnet-scanq": (lambda: _image_step("resnet", "scanq"),
        "dc77ab9f667588033eed0c7198c0f9c10e6973c5fc0be584d15cc197a9816abb"),
    "amoebanet_sp2x2-False": (lambda: _image_step("amoebanet", False, spatial=True),
        "d72ad564bf7a3dc4ee4ff31b066f7876ebc5b26a538c807f00851d2247a5f345"),
    "amoebanet_sp2x2-scan": (lambda: _image_step("amoebanet", "scan", spatial=True),
        "07da054167a4e273e675c41edab3470747abe833ff223bb2df4e7618019d5ef9"),
    "resnet_sp2x2-False": (lambda: _image_step("resnet", False, spatial=True),
        "582955a93c82f69620d9339ee98fcab6e0697e852dd188397155f592c01fe753"),
    # replaced by PR 37, which meant to alter the expert layer and nothing
    # else of this program: the combine (and the row gather's backward) run at
    # the width of a range's rows and not of all the pairs, and the rows past
    # the prefix in ranges of the prefix's width (one range here, as before).
    # LFM2's embedding, convolution, attention, dense SwiGLU and head trace to
    # the byte-same jaxpr as at 4ea348d in float32 and bfloat16 (checked on a
    # model of dense layers alone). PR 36 traced b56dc89de99b9a74..., c0a7bc1
    # 9510765a4ddc7ee2...
    "lfm2-cell": (_token_step,
        "4504f1ee561c08386c502bdb6799c3cd4b9e35a9bc19e16e247a900b1d335abf"),
    # added by PR 39: Qwen3-Next's step as the parent 15786f9 traced it (the
    # hash was taken on that tree before PR 39 touched ``Attention`` and
    # ``ExpertFFN``, and holds after), and Nemotron-H's, new in PR 39
    "qwen3_next-cell": (lambda: _token_step(test_qwen3_next),
        "9013be8a0da54ff4b560b22111b7233fe4335bf6033ee1b27e28a496db19e6b3"),
    "nemotron_h-cell": (lambda: _token_step(test_nemotron_h),
        "7e60dbb71306d1f1d31a1d5bd788e44f81c8d92d91c1bd3f93bd5c5b1e4ad0df"),
    "pipeline-gpipe": (lambda: _pipeline_step("gpipe"),
        "278d206dbf04f5ddd34d0b3bfb01274ea8b7e5d47f0c870c9caa6d9ee90b5b01"),
    "pipeline-1f1b": (lambda: _pipeline_step("1f1b"),
        "eb0590081d6cd25c36861a6550941d0a79e3afdb28c5ea0f124b2a032aecfe40"),
    "gems_master": (lambda: _pipeline_step("gems"),
        "fe1513d07782fc05b84f98e676b4383dbe716e295493c0ca7743f39ccf2c37c6"),
    "eval_step": (lambda: _eval_step(spatial=False),
        "32555030fa4bbf6a01fb3fc1dfc0416b55e85e8e48c266ae61717de40ba3d949"),
    "spatial_eval_step": (lambda: _eval_step(spatial=True),
        "8ccde965a604441acfe0e67f5f2a51f8d0705b743dea73a1c2c752b4e4d49a67"),
}


# The three token programs as 3d49de2 (PR 45) pinned them, before the expert
# layer named anything: what they trace to with ``ops/sequence._kept`` the
# identity (Qwen3-Next's and Nemotron-H's with PR 47's products and
# convolutions a piece, above). A name changes which values a replay makes
# again and no value: whoever names more (or fewer) values leaves these three
# as they are.
WITHOUT_THE_NAMES = {
    "lfm2-cell": "4f7e32c3a0c26bdd030a74218ead9322b2849a21733daa3ee924c373cf7b007b",
    "qwen3_next-cell": "4bf59de71f1b7abf8a53c6d203925ae443eb39f28c41e9b5dd398ebdfa396fec",
    "nemotron_h-cell": "d67e2a8d40f6f9369efd6db795fc940446e65ab029e17ae5935a19590a00a711",
}


def _traced_sha256(build):
    # from empty trace caches: whether two ``jit`` equations print one shared
    # body or two depends on what the process traced before, and one pin read
    # another hash in a long-lived worker once in some whole runs (PERF.md
    # section 7, from PR 44)
    jax.clear_caches()
    fn, args = build()
    text = str(jax.make_jaxpr(fn)(*args))
    # a frozenset prints in hash order, which differs from process to process
    text = re.sub(
        r"frozenset\(\{([^}]*)\}\)",
        lambda m: "frozenset({" + ", ".join(sorted(
            s.strip() for s in m.group(1).split(","))) + "})", text)
    # a checkpoint's policy prints with the function's address, likewise
    text = re.sub(r"(policy=<function \S+) at 0x[0-9a-f]+>", r"\1>", text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("case", list(TRACED_AT_C0A7BC1))
def test_the_traced_step_is_what_it_was(case):
    build, sha256 = TRACED_AT_C0A7BC1[case]
    assert _traced_sha256(build) == sha256


@pytest.fixture
def names_off(monkeypatch):
    """``ops/sequence._kept`` the identity; ``jit``'s traces of the expert
    layer's ranges are dropped before the trace (``_traced_sha256``) and
    after it, so that neither program is made from the other's."""
    from mpi4dl_tpu.ops import sequence

    monkeypatch.setattr(sequence, "_kept", lambda x: x)
    yield
    jax.clear_caches()


@pytest.mark.parametrize("case", list(WITHOUT_THE_NAMES))
def test_the_token_step_without_its_names_is_what_it_was(case, names_off):
    assert _traced_sha256(TRACED_AT_C0A7BC1[case][0]) == WITHOUT_THE_NAMES[case]
