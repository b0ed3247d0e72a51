"""The causal depthwise convolution's kernels (``ops/causal_conv_pallas.py``)
in the Pallas interpreter, at shapes the kernels take (channels of whole
lanes, a length of whole loop trips): held to the plain function they stand
in for (``sequence.causal_depthwise_conv1d`` with its bias and ``nn.silu``),
value and every gradient, to what a position sees and what it does not, to
the rows handed across chunks, grid steps and lane columns and to the zeros
before every sequence's start; and the dispatch rule of
``ops/sequence.causal_conv_silu``.

``tests/test_qwen3_next.py`` and ``tests/test_nemotron_h.py`` run the mixers
at tiny widths in float32 and so hold the plain path; what the chip's compiler
makes of the kernels at full width is ``tests/test_tpu_compile.py``'s.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.ops import causal_conv_pallas as ccp
from mpi4dl_tpu.ops import sequence

B = 2
R = ccp.ROWS  # the positions of a loop trip ("a chunk" below)
# (positions, channels, the block of a grid step): one chunk in one step; three
# chunks a step, two steps, two lane columns a step; four steps of one chunk
# and three blocks of channels
SHAPES = {"one_chunk": (R, 128, ccp.Plan(R, 128)),
          "chunks_steps_and_columns": (6 * R, 256, ccp.Plan(3 * R, 256)),
          "a_chunk_a_step": (4 * R, 384, ccp.Plan(R, 128))}


def plain(x, kernel, bias=None):
    y = sequence.causal_depthwise_conv1d(x, kernel)
    return nn.silu(y if bias is None else y + bias)


def _inputs(shape, taps, bias, dtype, seed=0):
    """``((x, kernel[, bias]), a cotangent for the output, the plan)``: a
    projection's output, a fresh model's taps (LeCun normal over the taps),
    a bias of a tenth."""
    length, channels, plan = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (B, length, channels)).astype(dtype)
    args = (x, (jax.random.normal(keys[1], (taps, channels)) * taps ** -0.5).astype(dtype))
    if bias:
        args += ((0.1 * jax.random.normal(keys[2], (channels,))).astype(dtype),)
    return args, jax.random.normal(keys[3], x.shape).astype(dtype), plan


def _kernels(plan):
    return lambda *args: ccp.conv_silu(*args, plan=plan, interpret=True)


def _out_and_grads(conv, args, ct):
    out, pull = jax.vjp(conv, *args)
    return (out, *pull(ct.astype(out.dtype)))


def _gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


CASES = pytest.mark.parametrize("taps, bias", [(3, False), (3, True), (4, False), (4, True)],
                                ids=["3_taps", "3_taps_bias", "4_taps", "4_taps_bias"])


@CASES
@pytest.mark.parametrize("shape", SHAPES)
def test_in_float32_the_kernels_are_the_plain_function(shape, taps, bias):
    """The algorithm (the shifted views, the rows handed on, the rebuilt
    pre-activation, ``dx`` from the rows after, the taps' and bias' sums)
    without the rounding: float32 through the interpreter against the plain
    function differentiated by JAX. The value's sum runs in the plain
    function's order (a rounding or two apart: the interpreter's ``exp``); the
    taps' and the bias' gradients add the positions in another order."""
    args, ct, plan = _inputs(shape, taps, bias, jnp.float32)
    got = _out_and_grads(_kernels(plan), args, ct)
    want = _out_and_grads(plain, args, ct)
    for name, one, other in zip(("out", "dx", "dw", "db"), got, want):
        assert one.shape == other.shape and one.dtype == jnp.float32, name
        assert _gap(one, other) < 2e-6, (name, _gap(one, other))


# In bfloat16 the kernels take the same operands as the plain function (x, the
# taps and the bias rounded to bfloat16) and do every product, sum and the
# SiLU in float32, rounding once where a result is written; the plain function
# rounds after every product and add. Against the float32 function on the same
# bfloat16 operands a result's one rounding is what is left: a relative L2 of
# 2^-9 / sqrt(3) = 0.0011 were every number rounded at its own magnitude,
# 0.0016-0.0017 read here (value, dx, and the taps' and bias' gradients, whose
# float32 sums over the positions are rounded once); the limit is 0.004, where
# the plain bfloat16 path reads 0.0044-0.0050 (value, dx) and 0.010-0.011 (the
# taps' and bias' gradients). A lost tap or row reads 0.1 and more.
@CASES
@pytest.mark.parametrize("shape", ["chunks_steps_and_columns", "a_chunk_a_step"])
def test_in_bfloat16_value_and_cotangents_are_within_a_rounding_of_float32(shape, taps, bias):
    args, ct, plan = _inputs(shape, taps, bias, jnp.bfloat16)
    got = _out_and_grads(_kernels(plan), args, ct)
    rounded = _out_and_grads(plain, args, ct)
    want = _out_and_grads(plain, [a.astype(jnp.float32) for a in args], ct.astype(jnp.float32))
    for name, one, coarse, other in zip(("out", "dx", "dw", "db"), got, rounded, want):
        assert one.shape == other.shape and one.dtype == jnp.bfloat16, name
        assert _gap(one, other) < 0.004, (name, _gap(one, other))
        # at least the plain path's precision
        assert _gap(one, other) <= _gap(coarse, other) * 1.05, (name, _gap(coarse, other))


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("at", [0, R - 1, R, 3 * R - 1, 3 * R, 6 * R - 1],
                         ids=["first", "a_chunks_last", "a_chunks_first", "a_steps_last",
                              "a_steps_first", "last"])
def test_a_position_is_seen_by_itself_and_the_taps_after_it_and_by_nothing_else(taps, at):
    """``x`` changed at one position: the outputs at that position and the
    ``taps - 1`` after it move and every other is the same bits, across a
    chunk's edge, a grid step's and the sequence's end;
    the cotangent changed at one position moves ``dx`` there and at the
    ``taps - 1`` positions before it and nowhere else."""
    (x, kernel), ct, plan = _inputs("chunks_steps_and_columns", taps, False, jnp.bfloat16)
    conv = _kernels(plan)
    length = x.shape[1]

    def moved(a, b):
        return np.flatnonzero(np.any(np.asarray(a != b), axis=(0, 2)))

    out, pull = jax.vjp(conv, x, kernel)
    seen = list(range(at, min(at + taps, length)))
    assert list(moved(conv(x.at[:, at].add(1.0), kernel), out)) == seen
    reached = list(range(max(at - taps + 1, 0), at + 1))
    assert list(moved(pull(ct.at[:, at].add(1.0))[0], pull(ct)[0])) == reached


def test_every_sequence_of_the_batch_starts_from_zeros():
    """The second sequence's output and cotangent are those of a call that
    holds it alone: the rows handed on stop at a sequence's end, both ways."""
    (x, kernel, bias), ct, plan = _inputs("chunks_steps_and_columns", 4, True, jnp.bfloat16)
    conv = _kernels(plan)
    whole = _out_and_grads(conv, (x, kernel, bias), ct)
    alone = _out_and_grads(conv, (x[1:], kernel, bias), ct[1:])
    np.testing.assert_array_equal(whole[0][1:], alone[0])
    np.testing.assert_array_equal(whole[1][1:], alone[1])
    # and the first rows of a sequence are the taps on zeros before it
    first = plain(x[:, :1].astype(jnp.float32), kernel[-1:].astype(jnp.float32),
                  bias.astype(jnp.float32))
    assert _gap(whole[0][:, :1], first) < 0.004


def test_the_block_of_a_grid_step_changes_no_bit():
    """Blocks of one, two, three and six chunks of positions by 128 and 256
    channels: the same numbers in the same order whatever the grid."""
    args, ct, _ = _inputs("chunks_steps_and_columns", 4, True, jnp.bfloat16)
    want = _out_and_grads(_kernels(ccp.Plan(6 * R, 256)), args, ct)
    for plan in (ccp.Plan(R, 128), ccp.Plan(3 * R, 128), ccp.Plan(2 * R, 256)):
        got = _out_and_grads(_kernels(plan), args, ct)
        for one, other in zip(got[:2], want[:2]):
            np.testing.assert_array_equal(one, other)
        for one, other in zip(got[2:], want[2:]):  # the sums' order is the grid's
            assert _gap(one, other) < 0.004


# -- dispatch ----------------------------------------------------------------


def _shapes(length=8192, channels=8192, taps=4, dtype=jnp.bfloat16, kernel_dtype=None):
    """The Qwen3-Next cell's convolution: two sequences, 8,192 channels."""
    return (jax.ShapeDtypeStruct((2, length, channels), dtype),
            jax.ShapeDtypeStruct((taps, channels), kernel_dtype or dtype))


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch gate steered to its TPU branch (nothing is run there)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_on_the_cpu_the_plain_function_runs():
    assert not ccp.dispatchable(*_shapes())
    (x, kernel, bias), _, _ = _inputs("chunks_steps_and_columns", 4, True, jnp.bfloat16)
    np.testing.assert_array_equal(
        sequence.causal_conv_silu(x, kernel.astype(jnp.float32), bias.astype(jnp.float32)),
        plain(x, kernel, bias))
    np.testing.assert_array_equal(sequence.causal_conv_silu(x, kernel), plain(x, kernel))


def test_the_cells_shapes_take_the_kernels_on_a_tpu(on_tpu):
    assert ccp.dispatchable(*_shapes())                 # Qwen3-Next: 2 x 2048 + 4096 channels
    assert ccp.dispatchable(*_shapes(channels=6144))    # Nemotron-H: 4096 + 2 x 1024
    assert ccp.plan_for((2, 8192, 8192)) == ccp.plan_for((2, 8192, 6144)) == ccp.Plan(
        ccp.BLOCK_ROWS[0], ccp.BLOCK_LANES[0])
    assert ccp.plan_for((2, 3 * R, 384)) == ccp.Plan(R, 128)


@pytest.mark.parametrize("why, shapes", [
    ("float32, the CPU tests' precision", _shapes(dtype=jnp.float32)),
    ("taps that were not cast to the activations' dtype", _shapes(kernel_dtype=jnp.float32)),
    ("the tiny Qwen3-Next cut's 96 channels: no whole lanes", _shapes(length=160, channels=96)),
    ("the tiny Nemotron-H cut's 128 channels at 80 positions: no whole loop trip",
     _shapes(length=80, channels=128)),
    ("a length of 8,200: not whole loop trips", _shapes(length=8200)),
    ("one tap: no convolution", _shapes(taps=1)),
    ("eight taps: the taps' rows and the bias' are more than a tile", _shapes(taps=8)),
    ("other channels under the taps than under x", (
        _shapes()[0], jax.ShapeDtypeStruct((4, 4096), jnp.bfloat16))),
])
def test_shapes_the_kernels_do_not_take_go_the_plain_way(on_tpu, why, shapes):
    assert not ccp.dispatchable(*shapes), why


def test_under_vmap_the_plain_function_runs(on_tpu):
    """A batched ``pallas_call`` is not what the gate vouches for."""
    seen = []

    def conv(x, kernel):
        seen.append(ccp.dispatchable(x, kernel))
        return x

    x, kernel = (jnp.zeros((3,) + s.shape, s.dtype) for s in _shapes(length=128, channels=128))
    jax.vmap(conv)(x, kernel)
    assert seen == [False]
    assert ccp.dispatchable(x[0], kernel[0])


def test_the_two_mixers_ask_and_the_short_convolution_never_does(on_tpu, monkeypatch):
    """``GatedDeltaNet`` and ``Mamba2`` go through ``causal_conv_silu`` and
    so through the gate; ``ShortConv`` (two gates around three taps, no SiLU:
    the compiler fuses it into its projections) calls the plain function and
    the gate never hears of it, whatever its shape."""
    asked = []
    monkeypatch.setattr(ccp, "dispatchable", lambda x, kernel: asked.append(x.shape) or False)
    x = jax.ShapeDtypeStruct((2, 128, 256), jnp.bfloat16)

    def trace(layer):
        del asked[:]
        jax.eval_shape(lambda: layer.init_with_output(
            jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype))[0])
        return list(asked)

    assert trace(sequence.ShortConv(256, 3)) == []
    # a call each for q, k, v and for x, B, C: whole arrays in, whole arrays out
    assert trace(sequence.GatedDeltaNet(256, 1, 2, 128, 128, 4, 1e-6)) == [
        (2, 128, 128), (2, 128, 128), (2, 128, 256)]
    assert trace(sequence.Mamba2(256, 4, 64, 1, 128, 4, 128, 1e-5)) == [
        (2, 128, 256), (2, 128, 128), (2, 128, 128)]
