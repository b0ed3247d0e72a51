"""Every Pallas kernel, compiled for a described v5e chip at the shapes the
full-width models dispatch — without the chip.

Interpreter-mode tests cannot see what the TPU's compiler refuses (a slice
not aligned to the tiling, more VMEM than a kernel may use). The compiler
is installed here and compiles for a chip that is described, not attached
(``jax.experimental.topologies``), so the kernels' shape gates are held to
it at no chip time: a shape ``supported()`` admits and this compiler
refuses is a bug in ``supported()``. There is no in-program trial compile
to hide it any more.

The shapes are not guessed: the train step of each model is traced
abstractly (``jax.eval_shape``) with the dispatch gates steered to their
TPU branch, and every kernel call is recorded. Models: AmoebaNet-D 18/416
@1024 bs2 bf16 on one chip (what ``chip_smoke.py`` runs), the same under
SP 2x2 (its ``--chips 4`` path, 512 px tiles plus halos), and ResNet-110
@1024 bs2 bf16.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
The whole step of the one-chip model compiles in ~200 s for the described
chip (PR 24), outside the tier-1 budget; it stays a scratch script.
"""

import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mpi4dl_tpu.config import ParallelConfig
from mpi4dl_tpu.ops import pool_pallas, ssd_scan_pallas
from mpi4dl_tpu.train import Trainer, default_remat

MODELS = ("amoebanet", "amoebanet_sp2x2", "resnet110")
KERNELS = ("pool",)
# (model, kernel) pairs whose gates admit nothing: ResNet has no max pool.
EMPTY = {("resnet110", "pool")}


@pytest.fixture(scope="module")
def topo():
    """Four described v5e chips. Skipped only where no TPU compiler is
    installed; where one is, failing to describe the chip fails the tests —
    a guard that skipped on any error could vanish without anyone seeing."""
    import importlib.util

    from jax.experimental import topologies

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler to hold the gates to")
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def cache_off():
    """The compile cache off around described-chip compiles: an executable
    for a chip that is not attached is written but can never be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _trainer(model, devices):
    """The model as the benchmark entry points build it (their parser,
    their builders, their remat rule), on a mesh of ``devices``."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks.common import build_amoebanet, build_resnet
    from mpi4dl_tpu.parallel.pipeline import PipelineTrainer
    from mpi4dl_tpu.parser import get_parser

    spatial = model.endswith("sp2x2")
    args = get_parser().parse_args(
        ["--batch-size", "2", "--image-size", "1024", "--split-size", "1"]
    )
    cfg = ParallelConfig(
        batch_size=2, split_size=1, spatial_size=1 if spatial else 0,
        num_spatial_parts=(4,), slice_method="square", image_size=1024,
    )
    build = build_resnet if model == "resnet110" else build_amoebanet
    n_sp = (
        PipelineTrainer.spatial_cell_count(len(build(args, cfg)[1]), cfg)
        if spatial else 0
    )
    cells, plain = build(args, cfg, spatial_cells=n_sp)[:2]
    return Trainer(
        cells, num_spatial_cells=n_sp, config=cfg, plain_cells=plain,
        mesh=cfg.make_mesh(devices), remat=default_remat(cfg.image_size),
    )


@pytest.fixture(scope="module")
def dispatched(topo):
    """{model: {kernel: {call signature: calls}}} from one abstract trace of
    each model's train step. The gates ask ``jax.default_backend()``; here
    the test answers "tpu" for them, and records instead of lowering."""
    seen = {}
    current = {}

    def record(kernel, sig, out):
        calls = current.setdefault(kernel, {})
        calls[sig] = calls.get(sig, 0) + 1
        return out

    def pool(xp, dy, *, kh, kw, interpret=False):
        sig = (xp.shape, dy.shape, xp.dtype.name, kh, kw)
        return record("pool", sig, jnp.zeros(xp.shape, dy.dtype))

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        mp.setenv("MPI4DL_TPU_CONV_IMPL", "auto")  # conftest pins "xla"
        mp.setattr(pool_pallas, "_bwd_padded", pool)
        for model in MODELS:
            current = seen[model] = {}
            n = 4 if model.endswith("sp2x2") else 1
            tr = _trainer(model, list(topo.devices)[:n])
            state = jax.eval_shape(
                lambda: tr.init(jax.random.PRNGKey(0), (2, 1024, 1024, 3))
            )
            x = jax.ShapeDtypeStruct((2, 1024, 1024, 3), jnp.float32)
            y = jax.ShapeDtypeStruct((2,), jnp.int32)
            jax.eval_shape(tr._jit_step, state, x, y)
    return seen


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile_pool(one_chip, sig):
    xp, dy, dtype, kh, kw = sig
    assert pool_pallas._plan(
        xp[-1], dy[1], dy[2], kh, kw, jnp.dtype(dtype).itemsize
    ), sig
    fn = functools.partial(pool_pallas._bwd_padded, kh=kh, kw=kw)
    jax.jit(fn).lower(_on(one_chip, xp, dtype), _on(one_chip, dy, dtype)).compile()


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("model", MODELS)
def test_admitted_shapes_compile_for_v5e(topo, cache_off, dispatched, model, kernel):
    """Every shape the kernel's gate admitted while the model's train step
    was traced compiles for one described v5e chip. A refusal is the
    compiler's own error, with the shape in the assertion's note."""
    sigs = sorted(dispatched[model].get(kernel, ()))
    if (model, kernel) in EMPTY:
        assert not sigs
        return
    assert sigs, f"{model}: no {kernel} call was traced — the gate moved?"
    one_chip = SingleDeviceSharding(topo.devices[0])
    for sig in sigs:
        try:
            _compile_pool(one_chip, sig)
        except Exception as e:
            e.add_note(f"{kernel} kernel, {model}, admitted signature {sig}")
            raise


@pytest.mark.parametrize(
    "model,padded_inputs",
    [
        ("amoebanet", [(130, 416), (66, 832), (34, 1664), (258, 208)]),
        ("amoebanet_sp2x2", [(66, 416), (34, 832), (18, 1664), (130, 208)]),
    ],
)
def test_the_step_dispatches_its_forty_pool_backwards(dispatched, model, padded_inputs):
    """A step runs the stride-1 3x3 max-pool backward 40 times, at four
    shapes: 13 each in the three groups of normal cells and one in the stem.
    A gate that turns one of them down sends it to the tree path in silence;
    here it is seen as a missing signature, not later as a missing gain."""
    want = {
        ((2, s, s, c), (2, s - 2, s - 2, c), "bfloat16", 3, 3): calls
        for (s, c), calls in zip(padded_inputs, (13, 13, 13, 1))
    }
    assert dispatched[model]["pool"] == want


def test_default_on_kernels_are_the_ones_the_chip_smoke_expects(monkeypatch):
    """The pool kernel's gate opens on a TPU at a shape of the model (what
    ``chip_smoke.py`` then demands of the compiled step), stays shut on the
    CPU, and shuts where a caller declares a batched trace."""
    from mpi4dl_tpu.parallel.halo import batched_trace

    x = jax.ShapeDtypeStruct((2, 256, 256, 208), jnp.bfloat16)
    assert not pool_pallas.dispatchable(x, 3, 3, 0, 0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pool_pallas.dispatchable(x, 3, 3, 0, 0)
    with batched_trace():
        assert not pool_pallas.dispatchable(x, 3, 3, 0, 0)
    assert pool_pallas.dispatchable(x, 3, 3, 0, 0)


def _compiled_grad(fn, one_chip, *shapes):
    """``fn``'s value and gradient (all arguments) compiled for the described
    chip on ``(shape, dtype)`` arguments."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32))  # noqa: E731
    grad = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(len(args)))))
    return grad.lower(*args).compile()


_ATTENTION_SHAPES = (((1, 8192, 8, 4, 64), jnp.bfloat16), ((1, 8192, 8, 64), jnp.bfloat16),
                     ((1, 8192, 8, 64), jnp.bfloat16))


def test_blocked_attention_holds_one_block_of_scores_at_full_width(topo, cache_off):
    """LFM2-8B-A1B's attention at 8,192 positions (8 key-value heads x 4
    query heads of 64, bf16), forward and its own backward, for one
    described chip: all 16 blocks' float32 scores together would be 4.3 GB
    a pass; the barriers between blocks keep it to one block's."""
    from mpi4dl_tpu.ops.sequence import blocked_causal_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = _compiled_grad(
        functools.partial(blocked_causal_attention, block=512), one_chip, *_ATTENTION_SHAPES)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30


def test_full_width_attention_dispatches_the_fused_kernels(topo, cache_off, monkeypatch):
    """The same shape through ``causal_attention`` with the gate steered to
    its TPU branch: the compiled value and gradient hold the forward kernel
    (the gradient's own forward) and the backward kernel by name, each with
    the ``lfm2_attention``-style name stack a scope would give it (what
    ``attn_ms`` joins the trace with), no ``[.., 512, N]`` float32 score
    fusion of the plain path, and under 1 GiB of temporaries. A fall-back to
    the plain path at this shape fails here, loudly."""
    from mpi4dl_tpu.ops import attention_pallas
    from mpi4dl_tpu.ops.sequence import causal_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def attend(q, k, v):
        with jax.named_scope("lfm2_attention"):
            return causal_attention(q, k, v, 512)

    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = _compiled_grad(attend, one_chip, *_ATTENTION_SHAPES)
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in (attention_pallas.FWD_NAME, attention_pallas.BWD_NAME):
        mine = [line for line in calls if name in line.split(" = ")[0]]
        assert len(mine) == 1, (name, [line[:80] for line in calls])
        assert "lfm2_attention" in mine[0].split("op_name=")[1].split('"')[1]
    assert not re.search(r"f32\[[\d,]*,512,\d+\]", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_the_expert_layers_grouped_products_are_the_chips_ragged_dot(topo, cache_off):
    """The share of LFM2-8B-A1B's expert layer (8 of 32 experts, 4 a token,
    widths 2048 / 1792) on 8,192 tokens, forward and backward: each
    ``jax.lax.ragged_dot`` must reach the chip as its grouped-matmul custom
    call (which skips the rows past the last group); expanded into one dense
    product an expert it would multiply every row by all 8 experts. The
    layer computes its 32,768 sorted pair rows as two ranges of 16,384 (PR
    36): the first always, the second under a conditional. The step that
    stays on the first must not pay for the second: the conditional's other
    branch hands its operands through (no zero-filled gradient the size of
    the expert arrays), and the layer's temporaries stay within a fifth of
    the one-range layer's."""
    from mpi4dl_tpu.ops.sequence import ExpertFFN

    layer = ExpertFFN(2048, 1792, 32, 8, 0, 4)
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.float32)
    variables = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(x.shape)))
    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), (variables, x))

    def products(text):
        calls = [line for line in text.splitlines()
                 if "custom-call(" in line and "%ragged-dot" in line.split(" = ")[0]
                 and "metadata" not in line.split(" = ")[0]]
        assert all('custom_call_target="tpu_custom_call"' in line for line in calls)
        return [re.search(r"= \w+\[([\d,]+)\]", line).group(1) for line in calls]

    def value(v, x_):
        return jnp.sum(layer.apply(v, x_).astype(jnp.float32))

    # forward: three products a range, every one over 16,384 rows
    forward = jax.jit(value).lower(*shapes).compile().as_text()
    assert sorted(products(forward)) == 2 * ["16384,1792"] * 2 + 2 * ["16384,2048"]
    assert forward.count(" conditional(") == 1
    # the gradient (whose value nothing reads: the forward's conditional goes).
    # First range: three forward, an input and a weight gradient for each.
    # Second range, all inside the backward's one conditional: its forward
    # once more and the same six.
    compiled = jax.jit(jax.grad(value, argnums=(0, 1))).lower(*shapes).compile()
    text = compiled.as_text()
    shapes_of = products(text)
    assert len(shapes_of) == 18
    assert len([s for s in shapes_of if s.startswith("8,")]) == 6  # weight gradients
    assert all(s.startswith(("8,", "16384,")) for s in shapes_of)
    assert text.count(" conditional(") == 1
    # zeros for the gradients of a range that did not run would be
    # broadcasts the size of the expert arrays (3 x 59 MB a layer, and as
    # much again to add them). Temporaries: the one-range layer of c32c8c2
    # compiled to 818,508,288 bytes, this one to 935,816,704 (877,847,040
    # without the ``jit`` around the two ranges: the compiler's prefetches
    # fall otherwise); the branch that runs the second range holds the first
    # range's gradients beside its own rows. A zero-filled residual set
    # reads 2.59e9.
    assert not re.search(r"bf16\[8,(2048,1792|1792,2048)\]\S* broadcast\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2 * 818_508_288


@pytest.mark.parametrize("why, q_shape, plan", [
    ("LFM2's heads at twice the length: two of the group's four a step",
     (1, 16384, 8, 4, 64), (512, 2)),
    ("Nemotron-H's head dim at twice the length: one head a step",
     (1, 16384, 2, 4, 128), (512, 1)),
    ("32k positions at head dim 64: one head a step, 64 blocks resident",
     (1, 32768, 2, 2, 64), (512, 1)),
    ("a short sequence in blocks of 128 at head dim 256", (2, 384, 2, 8, 256), (128, 1)),
])
def test_what_the_attention_plan_admits_the_chips_compiler_takes(
        topo, cache_off, why, q_shape, plan):
    """Shapes no cell runs that ``attention_pallas.plan_for`` admits by the
    same rule (a step's heads shrink until the backward's residents fit):
    the kernels' value and gradient compile for one described chip. A shape
    the plan admits and this compiler refuses is a bug in the plan."""
    from mpi4dl_tpu.ops import attention_pallas

    k_shape = q_shape[:3] + q_shape[4:]
    got = attention_pallas.plan_for(q_shape, k_shape, jnp.bfloat16)
    assert got == plan, why
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = _compiled_grad(
        lambda q, k, v: attention_pallas.attention(q, k, v, got), one_chip,
        (q_shape, jnp.bfloat16), (k_shape, jnp.bfloat16), (k_shape, jnp.bfloat16))
    for name in (attention_pallas.FWD_NAME, attention_pallas.BWD_NAME):
        assert name in compiled.as_text(), why


@pytest.mark.parametrize("why, q_shape, length, unit, plan", [
    ("the SDAR cell's: a noisy copy beside the clean one, 8,192 positions each, "
     "8 heads of 128 a group, diffusion blocks of 4", (1, 16384, 4, 8, 128), 8192, 4, (512, 1)),
    ("a short pair of copies at head dim 64, whole kernel blocks as diffusion blocks",
     (2, 512, 2, 4, 64), 256, 256, (256, 4)),
])
def test_the_attention_kernels_under_the_block_diffusion_mask_compile(
        topo, cache_off, monkeypatch, why, q_shape, length, unit, plan):
    """``block_diffusion_attention`` with the gate steered to its TPU branch:
    the plan under the mask, and the compiled value and gradient hold the
    forward and the backward kernel under their own names (not the causal
    ones'), each under the scope the layer's reader joins the trace with."""
    from mpi4dl_tpu.ops import attention_pallas
    from mpi4dl_tpu.ops.sequence import BlockMask, block_diffusion_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mask = BlockMask(length, unit)
    k_shape = q_shape[:3] + q_shape[4:]
    assert attention_pallas.plan_for(q_shape, k_shape, jnp.bfloat16, mask) == plan, why

    def attend(q, k, v):
        with jax.named_scope("blockdiff_attention"):
            return block_diffusion_attention(q, k, v, 512, mask)

    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = _compiled_grad(
        attend, one_chip,
        (q_shape, jnp.bfloat16), (k_shape, jnp.bfloat16), (k_shape, jnp.bfloat16))
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in (attention_pallas.BLOCKDIFF_FWD_NAME, attention_pallas.BLOCKDIFF_BWD_NAME):
        mine = [line for line in calls if name in line.split(" = ")[0]]
        assert len(mine) == 1, (why, name, [line[:80] for line in calls])
        assert "blockdiff_attention" in mine[0].split("op_name=")[1].split('"')[1]
    assert not any(attention_pallas.FWD_NAME in line.split(" = ")[0] for line in calls)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


# -- Qwen3-Next's layers at full width (PR 37) --------------------------------


def _layer_shapes(layer, x, one_chip):
    """``(variables, x)`` as shapes on the described chip."""
    variables = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), (variables, x))


def _layer_grad(layer, x, one_chip):
    """``(compiled value, compiled gradient over parameters and input)`` of
    ``sum(layer(x))`` for the described chip, and the shapes compiled on."""
    shapes = _layer_shapes(layer, x, one_chip)

    def value(v, x_):
        return jnp.sum(layer.apply(v, x_).astype(jnp.float32))

    return (jax.jit(value).lower(*shapes).compile(),
            jax.jit(jax.grad(value, argnums=(0, 1))).lower(*shapes).compile())


def _op_names(compiled):
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


def _assert_scope_in_both_passes(forward, gradient, scope):
    """What the per-layer readers join the chip's trace with: the scope in
    the name stack of forward instructions and of transposed ones."""
    assert sum(scope in name for name in _op_names(forward)) > 3, scope
    backward = [name for name in _op_names(gradient) if "transpose(" in name]
    assert sum(scope in name for name in backward) > 3, scope


def _kernel_calls(compiled, start, names):
    """``{kernel name: [op_name of each of its custom calls]}`` for the kernels
    whose names begin with ``start`` in a compiled program's text."""
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and start in line.split(" = ")[0]]
    return {name: [re.search(r'op_name="([^"]*)"', line).group(1)
                   for line in calls if name in line.split(" = ")[0]]
            for name in names}


def _assert_the_convolution_dispatched(forward, gradient):
    """The causal convolution's kernels (``ops/causal_conv_pallas.py``, PR 47)
    in a mixer's compiled passes: a forward call for each of the three
    arrays the convolution takes (q, k, v; x, B, C) in both, a backward call
    each in the gradient's, every one under ``mpi4dl_part_conv`` (what
    ``tok_conv_ms`` reads), and what a forward call reads is a product's own
    result: no slice of a wider array is copied out for it."""
    from mpi4dl_tpu.ops import causal_conv_pallas as ccp

    names = (ccp.FWD_NAME, ccp.BWD_NAME)
    found = _kernel_calls(forward, "mpi4dl_causal_conv", names)
    assert {k: len(v) for k, v in found.items()} == {ccp.FWD_NAME: 3, ccp.BWD_NAME: 0}
    found = _kernel_calls(gradient, "mpi4dl_causal_conv", names)
    assert {k: len(v) for k, v in found.items()} == {ccp.FWD_NAME: 3, ccp.BWD_NAME: 3}
    assert all("mpi4dl_part_conv" in op for ops in found.values() for op in ops), found
    assert all("transpose(" in op for op in found[ccp.BWD_NAME])
    for compiled in (forward, gradient):
        lines = compiled.as_text().splitlines()
        calls = [line for line in lines
                 if "custom-call(" in line and ccp.FWD_NAME in line.split(" = ")[0]]
        for call in calls:
            operand = re.search(r"custom-call\((%[\w.\-]+)", call).group(1)
            made = next(line for line in lines if line.strip().startswith(operand + " = "))
            assert " fusion(" in made and " slice(" not in made, made


def _rule_kernels(compiled):
    """The gated delta rule's kernels' custom calls (``_kernel_calls``)."""
    from mpi4dl_tpu.ops import delta_rule_pallas

    return _kernel_calls(compiled, "mpi4dl_delta_rule",
                         (delta_rule_pallas.FWD_NAME, delta_rule_pallas.BWD_NAME))


def test_the_gated_delta_layer_compiles_at_full_width_under_its_scopes(
        topo, cache_off, monkeypatch):
    """Qwen3-Next-80B-A3B's Gated DeltaNet mixer (16 key / 32 value heads of
    128, a convolution of 4 taps over 8,192 channels) on two sequences of
    8,192 positions in bfloat16, forward and backward, for one described
    chip, the rule's gate steered to its TPU branch: the cell's shape takes
    the kernels of ``ops/delta_rule_pallas.py`` (128 chunks of 64, a chunk's
    float32 squares and the carried state in VMEM), ``gated_delta`` and
    ``gated_delta_rule`` reach the compiled text of both passes, every
    ``mpi4dl_delta_rule*`` custom call stands under ``gated_delta_rule``
    (the scope the benchmark's ``delta_rule_ms`` reads), the state's hand-on
    is no ``while`` any more, and the backward keeps the rule's inputs, the
    chunks' start states and the systems' inverses: 3.14 GiB of temporaries
    as compiled here (4.64 on the plain path, which recomputes the rule and
    its chunk terms; 7.92 before it did)."""
    from mpi4dl_tpu.ops.sequence import GatedDeltaNet

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])
    forward, gradient = _layer_grad(
        GatedDeltaNet(2048, 16, 32, 128, 128, 4, 1e-6),
        jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16), one_chip)
    for scope in ("gated_delta", "gated_delta_rule"):
        _assert_scope_in_both_passes(forward, gradient, scope)
    found = _rule_kernels(forward)
    assert len(found["mpi4dl_delta_rule_fwd"]) == 1 and not found["mpi4dl_delta_rule_bwd"]
    found = {**found, **{k: v + found[k] for k, v in _rule_kernels(gradient).items()}}
    assert len(found["mpi4dl_delta_rule_fwd"]) == 2 and len(found["mpi4dl_delta_rule_bwd"]) == 1
    assert all("gated_delta_rule" in name for names in found.values() for name in names), found
    assert "transpose(" in found["mpi4dl_delta_rule_bwd"][0]
    _assert_the_convolution_dispatched(forward, gradient)
    assert " while(" not in gradient.as_text()  # the hand-on is the kernels' own
    assert gradient.memory_analysis().temp_size_in_bytes < 3.5 * 2**30


def test_the_tiny_cuts_gated_delta_layer_takes_the_plain_path(topo, cache_off, monkeypatch):
    """The tiny cut's DeltaNet mixer (``chipbench/tests/tiny/qwen3_next_80b_
    a3b_share16.json``: hidden 64, 2 key / 4 value heads of 16, 160
    positions) with the gate steered to its TPU branch: key dim 16 is not
    whole lanes and 160 positions are not whole chunks, so no
    ``mpi4dl_delta_rule*`` name is in the compiled text of either pass and
    the plain chunked rule runs (its hand-on a ``while``) under the same
    scopes."""
    from mpi4dl_tpu.ops.sequence import GatedDeltaNet

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])
    forward, gradient = _layer_grad(
        GatedDeltaNet(64, 2, 4, 16, 16, 4, 1e-6),
        jax.ShapeDtypeStruct((2, 160, 64), jnp.bfloat16), one_chip)
    for scope in ("gated_delta", "gated_delta_rule"):
        _assert_scope_in_both_passes(forward, gradient, scope)
    for compiled in (forward, gradient):
        assert "mpi4dl_delta_rule" not in compiled.as_text()


def _attention_kernels(compiled):
    """The fused attention kernels' custom calls (``_kernel_calls``)."""
    from mpi4dl_tpu.ops import attention_pallas

    return _kernel_calls(compiled, "mpi4dl_attention",
                         (attention_pallas.FWD_NAME, attention_pallas.BWD_NAME))


def _assert_attention_dispatched(forward, gradient):
    """One forward kernel in the forward's text; in the gradient's its own
    forward and the one backward kernel, each under ``lfm2_attention`` (what
    the scope readers join the trace with). That no block of the plain
    path's float32 scores is left is the callers' limit on the temporaries."""
    from mpi4dl_tpu.ops import attention_pallas

    _assert_scope_in_both_passes(forward, gradient, "lfm2_attention")
    fwd, bwd = attention_pallas.FWD_NAME, attention_pallas.BWD_NAME
    assert {k: len(v) for k, v in _attention_kernels(forward).items()} == {fwd: 1, bwd: 0}
    kernels = _attention_kernels(gradient)
    assert {k: len(v) for k, v in kernels.items()} == {fwd: 1, bwd: 1}
    assert all("lfm2_attention" in op for ops in kernels.values() for op in ops), kernels
    assert "transpose(" in kernels[bwd][0]


def test_the_gated_attention_layer_at_head_dim_256_dispatches_the_fused_kernels(
        topo, cache_off, monkeypatch):
    """Qwen3-Next's attention layer (2 key-value heads x 8 query heads of
    256, rotary embedding on 64 dims, the output gate) at 2 x 8,192
    positions with the kernels' gate steered to its TPU branch: the plan for
    head dim 256 takes one query head a grid step (PR 40), the chip's
    compiler takes it, and both kernels are in the compiled text under
    ``lfm2_attention`` in both passes. Temporaries 1.60 GiB as compiled here
    (under 4.5 on the blocked plain path, before the plan): the projections' and
    the gate's activations, no block of scores."""
    from mpi4dl_tpu.ops.sequence import Attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])
    layer = Attention(2048, 16, 2, 1e-6, 1e7, head_dim=256, rotary_dim=64,
                      output_gate=True, zero_centred_norms=True)
    forward, gradient = _layer_grad(
        layer, jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16), one_chip)
    _assert_attention_dispatched(forward, gradient)
    temp = gradient.memory_analysis().temp_size_in_bytes
    print("gated attention temp GiB", temp / 2**30)
    assert temp < 2.0 * 2**30


def test_the_share_of_the_512_expert_layer_keeps_the_width_of_its_prefix(topo, cache_off):
    """32 of 512 experts, 10 a token, widths 2048 / 512, with the shared
    expert, on 16,384 tokens: 163,840 sorted pair rows of which the prefix of
    20,480 always runs. Every grouped product is over 20,480 rows (the rows
    past the prefix run in a loop of ranges of that width, compiled once);
    nothing but a column of weights is as wide as all the pairs, so the
    layer's backward holds 1.31 GiB of temporaries as compiled here (3.23
    with one range for all the rest, most of it in a branch no step takes);
    ``shared_expert`` and ``lfm2_moe`` reach both passes."""
    from mpi4dl_tpu.ops.sequence import ExpertFFN

    one_chip = SingleDeviceSharding(topo.devices[0])
    layer = ExpertFFN(2048, 512, 512, 32, 0, 10, True, expert_bias=False,
                      scoring="softmax", shared_width=512)
    forward, gradient = _layer_grad(
        layer, jax.ShapeDtypeStruct((2, 8192, 2048), jnp.float32), one_chip)
    for scope in ("lfm2_moe", "shared_expert"):
        _assert_scope_in_both_passes(forward, gradient, scope)
    text = gradient.as_text()
    products = [line for line in text.splitlines()
                if "custom-call(" in line and "%ragged-dot-none" in line.split(" = ")[0]]
    rows = {re.search(r"= \w+\[(\d+),", line).group(1) for line in products}
    assert products and rows == {"20480", "32"}, rows  # 32: the weight gradients
    assert " while(" in text and not re.search(r"\w+\[163840,(2048|512)\]", text)
    assert gradient.memory_analysis().temp_size_in_bytes < 1.6 * 2**30


# -- Nemotron-H's three mixers at full width (PR 39) --------------------------


def _nemotron_mixer(kind):
    from mpi4dl_tpu.ops.sequence import Attention, ExpertFFN, Mamba2

    return {
        "mamba": lambda: Mamba2(2688, 64, 64, 8, 128, 4, 128, 1e-5),
        "attention": lambda: Attention(2688, 32, 2, 1e-5, 0.0, block=256, head_dim=128,
                                       rotary_dim=0, qk_norm=False),
        "moe": lambda: ExpertFFN(2688, 1856, 128, 8, 0, 6, True, 2.5, shared_width=3712,
                                 activation="relu2", shared_gate=False),
    }[kind]()


def _scan_kernels(compiled):
    """Mamba-2's scan's kernels' custom calls (``_kernel_calls``)."""
    return _kernel_calls(compiled, "mpi4dl_ssd_scan",
                         (ssd_scan_pallas.FWD_NAME, ssd_scan_pallas.BWD_NAME))


@pytest.mark.parametrize("kind,scopes,temp_gib", [
    ("mamba", ("mamba2", "ssd_scan"), 3.6),
    ("attention", ("lfm2_attention",), 1.5),
    ("moe", ("lfm2_moe", "shared_expert"), 1.2),
])
def test_a_nemotron_h_mixer_compiles_at_full_width_under_its_scopes(
        topo, cache_off, monkeypatch, kind, scopes, temp_gib):
    """The tower's three mixers (``chipbench/configs/nemotron_twotower_30b_
    a3b_share16.json``) on two sequences of 8,192 positions, forward and
    backward, for one described chip, every kernel's gate steered to its TPU
    branch. Attention at head dim 128 and 16 heads a group dispatches the
    fused kernels, two query heads a grid step (PR 40): both are in the
    compiled text under ``lfm2_attention``. Mamba-2's scan takes the kernels
    of ``ops/ssd_scan_pallas.py`` (PR 42; 8 groups of 8 heads of 64, 64
    chunks of 128 a sequence, positions along the lanes, a chunk's float32
    squares and the group's carried state in VMEM): ``mpi4dl_ssd_scan_fwd`` is in the forward's text
    once, the gradient's holds it and ``mpi4dl_ssd_scan_bwd``, each under
    ``ssd_scan`` (the scope the benchmark's ``ssd_scan_ms`` reads), the
    state's hand-on is no ``while`` any more and no chunk's squares are a
    buffer. The expert layer has no kernel: no ``mpi4dl_*`` custom call is in
    either pass of it and what runs is plain JAX under the scopes the
    benchmark's readers join the trace with; its share of 8 of 128 at 6 a
    token: 98,304 sorted pair rows, every grouped product over the prefix of
    12,288, two products an expert and pass. Temporaries as compiled here,
    GiB: 3.33 / 1.13 / 0.87 (Mamba-2 3.42 on the plain path, which kept a
    sequence's squares and states; attention 4.95 on the blocked plain path,
    before the plan)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])
    dtype = jnp.float32 if kind == "moe" else jnp.bfloat16
    forward, gradient = _layer_grad(
        _nemotron_mixer(kind), jax.ShapeDtypeStruct((2, 8192, 2688), dtype), one_chip)
    for scope in scopes:
        _assert_scope_in_both_passes(forward, gradient, scope)
    text = gradient.as_text()
    if kind == "attention":
        _assert_attention_dispatched(forward, gradient)
    elif kind == "mamba":
        fwd, bwd = ssd_scan_pallas.FWD_NAME, ssd_scan_pallas.BWD_NAME
        assert {k: len(v) for k, v in _scan_kernels(forward).items()} == {fwd: 1, bwd: 0}
        kernels = _scan_kernels(gradient)
        assert {k: len(v) for k, v in kernels.items()} == {fwd: 1, bwd: 1}
        assert all("ssd_scan" in op for ops in kernels.values() for op in ops), kernels
        assert "transpose(" in kernels[bwd][0]
        _assert_the_convolution_dispatched(forward, gradient)
    else:
        for compiled in (forward, gradient):  # a kernel's custom call is named after it
            assert not re.search(r"%mpi4dl_\w+ = [^\n]*custom-call\(", compiled.as_text())
    temp = gradient.memory_analysis().temp_size_in_bytes
    print(kind, "temp GiB", temp / 2**30)
    assert temp < temp_gib * 2**30
    if kind == "mamba":
        assert " while(" not in text  # the hand-on is the kernels' own
        assert not re.search(r"f32\[2,64,128,128,8,8\]", text)
    if kind == "moe":
        products = [line for line in text.splitlines()
                    if "custom-call(" in line and "%ragged-dot-none" in line.split(" = ")[0]]
        rows = {re.search(r"= \w+\[(\d+),", line).group(1) for line in products}
        # 2 forward + 4 backward, in the prefix and again in the loop of further ranges
        assert len(products) == 12 and rows == {"12288", "8"}, rows  # 8: weight gradients


def test_the_tiny_cuts_mamba_layer_takes_the_plain_path(topo, cache_off, monkeypatch):
    """The tiny cut's Mamba-2 mixer (``chipbench/tests/tiny/nemotron_twotower_
    30b_a3b_share16.json``: hidden 64, 2 groups of 4 heads of 8, a state of
    16, chunks of 32, 80 positions) with the gate steered to its TPU branch:
    a head's 8 channels are no whole bfloat16 tile, a state of 16 and a chunk
    of 32 are not whole lanes and 80 positions are not whole chunks, so no
    ``mpi4dl_ssd_scan*`` name is in the compiled text of either pass and the
    plain chunked scan runs (its hand-on a ``while``) under the same scopes."""
    from mpi4dl_tpu.ops.sequence import Mamba2

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])
    forward, gradient = _layer_grad(
        Mamba2(64, 8, 8, 2, 16, 4, 32, 1e-5),
        jax.ShapeDtypeStruct((2, 80, 64), jnp.bfloat16), one_chip)
    for scope in ("mamba2", "ssd_scan"):
        _assert_scope_in_both_passes(forward, gradient, scope)
    for compiled in (forward, gradient):
        assert "mpi4dl_ssd_scan" not in compiled.as_text()
    assert " while(" in gradient.as_text()


# -- what "cell" remat keeps of a kernel (PR 44) -------------------------------


def _sdar_config():
    from mpi4dl_tpu.models.sdar import SDARConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs", "sdar_30b_a3b_share8.json")) as f:
        return SDARConfig.from_dict(json.load(f))


def _sdar_attention_cell():
    """The SDAR cell's attention cell (``chipbench/configs/sdar_30b_a3b_
    share8.json``: hidden 2048, 32 query heads of 128 over 4 key-value heads,
    diffusion blocks of 4) on a noisy copy beside a clean one of 8,192."""
    from mpi4dl_tpu.models.sdar import SDARAttention

    config = _sdar_config()
    return SDARAttention(config), (1, 16384, config.hidden_size)


def _gated_delta_layer():
    """Qwen3-Next's Gated DeltaNet mixer on the cell's two sequences."""
    from mpi4dl_tpu.ops.sequence import GatedDeltaNet

    return GatedDeltaNet(2048, 16, 32, 128, 128, 4, 1e-6), (2, 8192, 2048)


def _mamba_layer():
    """Nemotron-H's Mamba-2 mixer on the cell's two sequences."""
    return _nemotron_mixer("mamba"), (2, 8192, 2688)


@pytest.mark.parametrize("build, start", [
    (_sdar_attention_cell, "mpi4dl_blockdiff_attention"),
    (_gated_delta_layer, "mpi4dl_delta_rule"),
    (_gated_delta_layer, "mpi4dl_causal_conv"),
    (_mamba_layer, "mpi4dl_causal_conv"),
], ids=["sdar_attention_cell", "gated_delta_layer", "gated_delta_layers_convolution",
        "mamba_layers_convolution"])
def test_a_kernels_forward_runs_once_under_the_cell_checkpoint(
        topo, cache_off, monkeypatch, build, start):
    """A cell's value and gradient under ``train._cell_ckpt`` at the cell's
    width, the gates steered to their TPU branch, for one described chip: the
    compiled text holds the kernel's forward once and its backward once. What
    the forward wrote is kept by name (``config.KERNEL_RESIDUAL``), so the
    cell's replay has no use for a second call; under a bare
    ``jax.checkpoint`` there are two (``tests/test_kernel_residuals.py``
    holds that, and the gradients' bits, in the interpreter)."""
    from mpi4dl_tpu.train import _cell_ckpt

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])
    layer, x_shape = build()
    shapes = _layer_shapes(layer, jax.ShapeDtypeStruct(x_shape, jnp.bfloat16), one_chip)

    def value(v, x_):
        return jnp.sum(_cell_ckpt()(layer.apply)(v, x_).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(value, argnums=(0, 1))).lower(*shapes).compile()
    found = _kernel_calls(compiled, start, (start + "_fwd", start + "_bwd"))
    each = 3 if start == "mpi4dl_causal_conv" else 1  # a call each for q, k, v / x, B, C
    assert {name: len(calls) for name, calls in found.items()} == {
        start + "_fwd": each, start + "_bwd": each}, found
    assert "checkpoint" in found[start + "_bwd"][0]  # the replay holds the backward alone


# -- what "cell" remat keeps of the expert layer (PR 46) ----------------------


def _sdar_expert_cell():
    """The SDAR cell's expert cell (16 of 128 experts, 8 a row, widths 2048 /
    768) on both copies' 16,384 rows: 131,072 sorted pair rows, a prefix of
    32,768."""
    from mpi4dl_tpu.models.sdar import SDARExperts

    config = _sdar_config()
    return SDARExperts(config), (1, 16384, config.hidden_size), jnp.bfloat16


def _qwen3_next_expert_layer():
    """Qwen3-Next's expert layer with its gated shared expert (32 of 512
    experts, 10 a token, widths 2048 / 512) on the cell's two sequences: a
    prefix of 20,480 of 163,840 sorted pair rows."""
    from mpi4dl_tpu.ops.sequence import ExpertFFN

    return (ExpertFFN(2048, 512, 512, 32, 0, 10, True, expert_bias=False,
                      scoring="softmax", shared_width=512), (2, 8192, 2048), jnp.float32)


@pytest.mark.parametrize("build, rows, held, sorts", [
    (_sdar_expert_cell, 32768, 16, 6), (_qwen3_next_expert_layer, 20480, 32, 7),
], ids=["sdar_expert_cell", "qwen3_next_expert_layer"])
def test_the_expert_layers_forward_runs_once_under_the_cell_checkpoint(
        topo, cache_off, build, rows, held, sorts):
    """An expert layer's value and gradient at its cell's widths for one
    described chip under ``train._cell_ckpt``, and SDAR's under a bare
    ``jax.checkpoint`` beside it (the file is tier-1's longest: one bare
    compile, not two). Outside the conditionals' branches (the entry
    computation: the prefix range, the one every measured step runs) the
    compiled text holds nine grouped products a gated layer under the cell's
    checkpoint, three forward and six gradients, where the bare one gives
    twelve, and four sorts fewer in all (``top_k``, which the chip's compiler
    lowers to a sort, ``sort_key_val``, ``argsort`` and ``_by_token``'s: each
    once, and ``_by_token``'s in the conditionals' branches, two in SDAR's
    text and three in Qwen3-Next's, whose further ranges are a loop): what the
    forward chose, sorted, gathered and multiplied is kept by name
    (``ops/sequence._kept``), the two hidden-wide arrays among it, so no
    product is in the replay. Every product is over the prefix's rows. The
    temporaries as compiled here, GiB (cell / bare): SDAR's cell 1.43 / 1.30,
    Qwen3-Next's layer 1.42 / 1.18."""
    from mpi4dl_tpu.train import _cell_ckpt

    one_chip = SingleDeviceSharding(topo.devices[0])
    layer, x_shape, dtype = build()
    shapes = _layer_shapes(layer, jax.ShapeDtypeStruct(x_shape, dtype), one_chip)
    mutable = [layer.counters] if hasattr(layer, "counters") else False

    def compiled(ckpt):
        def value(v, x_):
            out = ckpt(lambda v, x_: layer.apply(v, x_, mutable=mutable))(v, x_)
            return jnp.sum((out[0] if mutable else out).astype(jnp.float32))

        return jax.jit(jax.value_and_grad(value, argnums=(0, 1))).lower(*shapes).compile()

    def entry_products(text):
        entry = text[text.index("\nENTRY "):]
        return [re.search(r"= \w+\[(\d+),", line).group(1)
                for line in entry[:entry.index("\n}")].splitlines()
                if "custom-call(" in line and "%ragged-dot-none" in line.split(" = ")[0]]

    kept = compiled(_cell_ckpt())
    products = entry_products(kept.as_text())
    # over the prefix's rows; a weight gradient's leading axis counts the held experts
    assert sorted(products) == sorted(6 * [str(rows)] + 3 * [str(held)]), products
    assert kept.as_text().count(" sort(") == sorts
    if build is _sdar_expert_cell:
        bare = compiled(jax.checkpoint).as_text()
        assert len(entry_products(bare)) == 12 and bare.count(" sort(") == sorts + 4
    temp = kept.memory_analysis().temp_size_in_bytes
    print(build.__name__, "temp GiB", temp / 2**30)
    assert temp < 1.65 * 2**30
