"""The fused causal-attention kernels (``ops/attention_pallas.py``) in the
Pallas interpreter, at lengths the kernels take (whole blocks of 128) and at
their three head dims (64 with the group in one grid step, 128 and 256 with
the group in sub-groups of two heads and of one, as the token cells' plans
have them): held to the plain blocked path they stand in for and to
full-matrix attention in float32, to causality and to the grouping of query
heads; the plan a shape gets; and the dispatch rule of
``ops/sequence.causal_attention``.

``tests/test_lfm2.py::test_position_t_does_not_see_t_plus_1`` runs 40
positions and so holds the plain path; the kernels' causality is held here.
What the chip's compiler makes of the kernels at full width is
``tests/test_tpu_compile.py``'s.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.ops import attention_pallas
from mpi4dl_tpu.ops.sequence import blocked_causal_attention, causal_attention

KV, G, D, BLOCK = 2, 4, 64, 128
# (head dim, query heads a grid step): LFM2's form (the whole group of four,
# D^-0.5 folded into the keys), Nemotron-H's (sub-groups of two, the float32
# scores scaled) and Qwen3-Next's (one head a step, folded), each with the
# group of four a CPU test affords; and the unfolded scale with the group whole
WIDE = [(128, 2), (256, 1)]
FORMS = [(64, 4)] + WIDE + [(128, 4)]


def _kernel(heads=G):
    return functools.partial(
        attention_pallas.attention, plan=attention_pallas.Plan(BLOCK, heads), interpret=True)


kernel = _kernel()


def _inputs(length, seed=0, d=D):
    """``q, k, v`` and a cotangent ``w`` for the output, bfloat16."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = ((1, length, KV, G, d), (1, length, KV, d), (1, length, KV, d), (1, length, KV, G, d))
    return [jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
            for key, shape in zip(keys, shapes)]


def _out_and_grads(attend, q, k, v, w):
    """The output and the cotangents of ``q, k, v`` under the output's ``w``."""
    out, vjp = jax.vjp(attend, q, k, v)
    return (out,) + vjp(w.astype(out.dtype))


def _full_matrix(q, k, v):
    """Causal grouped-query attention on the whole score matrix, float32."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    scores = jnp.einsum("bqkgd,bnkd->bkgqn", q, k, precision="highest") * q.shape[-1] ** -0.5
    length = q.shape[1]
    scores = jnp.where(jnp.tril(jnp.ones((length, length), bool)), scores, -jnp.inf)
    return jnp.einsum("bkgqn,bnkd->bqkgd", jax.nn.softmax(scores, axis=-1), v, precision="highest")


def _gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# Against the plain path: the same arithmetic (bfloat16 operands, float32
# accumulation and statistics), but the online softmax rounds a probability
# to bfloat16 against the running maximum, the plain path against the row's,
# and each result is rounded to bfloat16 once (2^-9 an element): relative L2
# 0.0004-0.0010 at these lengths, so 0.003 holds it to the rounding.
# Against float32 full-matrix attention: the probabilities and ``ds`` are
# bfloat16 operands of the next product, as the configuration states: 0.002-
# 0.004 read here, 0.01 allowed; a missing block or a wrong mask reads 0.1+.
# The wider head dims read the same (0.0004-0.0010 and 0.002-0.004): a head's
# sum over more dims is still one float32 accumulation.
@pytest.mark.parametrize("length", [256, 512])
@pytest.mark.parametrize("oracle, limit", [("plain", 0.003), ("float32", 0.01)])
@pytest.mark.parametrize("d, heads", FORMS)
def test_output_and_cotangents_match(d, heads, length, oracle, limit):
    q, k, v, w = _inputs(length, d=d)
    attend = (functools.partial(blocked_causal_attention, block=BLOCK)
              if oracle == "plain" else _full_matrix)
    got = _out_and_grads(_kernel(heads), q, k, v, w)
    want = _out_and_grads(attend, q, k, v, w)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == jnp.bfloat16, name
        assert _gap(a, b) < limit, (name, _gap(a, b))


@pytest.mark.parametrize("d, heads", [(D, G)] + WIDE)
def test_log_sum_exp_is_the_rows_own(d, heads):
    q, k, v, _ = _inputs(256, d=d)
    _, lse = attention_pallas.forward(
        q, k, v, attention_pallas.Plan(BLOCK, heads), interpret=True)
    scores = jnp.einsum("bqkgd,bnkd->bkgqn", q.astype(jnp.float32), k.astype(jnp.float32),
                        precision="highest") * d ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), scores, -jnp.inf)
    assert lse.shape == (1, KV, G, 256) and lse.dtype == jnp.float32
    np.testing.assert_allclose(lse, jax.nn.logsumexp(scores, axis=-1), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d, heads, t", [(D, G, t) for t in (60, 127, 128, 200)]
                         + [(128, 2, 127), (128, 2, 128), (256, 1, 200)])
def test_position_t_does_not_see_later_positions(d, heads, t):
    """Everything after position ``t`` changed (inside a diagonal block, at a
    block's last row, at the next block's first): the output rows and ``dq``
    rows up to ``t`` are the same bits, the masked entries being exact
    zeros and the blocks above the diagonal never read."""
    q, k, v, w = _inputs(256, d=d)
    other = _inputs(256, seed=1, d=d)
    later = jnp.arange(256) > t
    changed = [jnp.where(later.reshape(1, -1, *(1,) * (a.ndim - 2)), b, a)
               for a, b in zip((q, k, v), other)]
    kernel = _kernel(heads)
    out, dq, _, _ = _out_and_grads(kernel, q, k, v, w)
    out2, dq2, _, _ = _out_and_grads(kernel, *changed, w)
    np.testing.assert_array_equal(out[:, :t + 1], out2[:, :t + 1])
    np.testing.assert_array_equal(dq[:, :t + 1], dq2[:, :t + 1])
    assert not np.array_equal(out[:, t + 1:], out2[:, t + 1:])


@pytest.mark.parametrize("d, heads", [(D, G)] + WIDE)
def test_a_key_value_head_serves_its_own_group_alone(d, heads):
    """Key-value head 1 changed: the four query heads of group 0 give the
    same bits, those of group 1 move; ``dk`` / ``dv`` of head 0 are the same
    bits (a group's sum takes nothing from the other group, whether the
    kernel makes it or XLA adds up the sub-groups' partial sums)."""
    q, k, v, w = _inputs(256, d=d)
    k2, v2 = (a.at[:, :, 1].set(b[:, :, 1])
              for a, b in zip((k, v), _inputs(256, seed=1, d=d)[1:3]))
    kernel = _kernel(heads)
    out, dq, dk, dv = _out_and_grads(kernel, q, k, v, w)
    out2, dq2, dk2, dv2 = _out_and_grads(kernel, q, k2, v2, w)
    for a, b in ((out, out2), (dq, dq2)):
        np.testing.assert_array_equal(a[:, :, 0], b[:, :, 0])
        assert all(not np.array_equal(a[:, :, 1, g], b[:, :, 1, g]) for g in range(G))
    for a, b in ((dk, dk2), (dv, dv2)):
        np.testing.assert_array_equal(a[:, :, 0], b[:, :, 0])
        assert not np.array_equal(a[:, :, 1], b[:, :, 1])


# -- dispatch ----------------------------------------------------------------


def _shapes(length, dtype=jnp.bfloat16, kv=8, group=4, d=64):
    return (jax.ShapeDtypeStruct((1, length, kv, group, d), dtype),
            jax.ShapeDtypeStruct((1, length, kv, d), dtype))


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch gate steered to its TPU branch (nothing is run there)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_on_the_cpu_the_plain_path_runs():
    assert not attention_pallas.dispatchable(*_shapes(8192))
    q, k, v, _ = _inputs(256)
    np.testing.assert_array_equal(
        causal_attention(q, k, v, BLOCK), blocked_causal_attention(q, k, v, BLOCK))


def test_the_full_width_shape_takes_the_kernel_on_a_tpu(on_tpu):
    assert attention_pallas.dispatchable(*_shapes(8192))
    assert attention_pallas.block_for(8192) == attention_pallas.BLOCKS[0]


def _cell(batch, kv, group, d, length=8192):
    return (batch, length, kv, group, d), (batch, length, kv, d)


# A grid step's query heads fill 256 lanes of head dims where the group has
# them, and their whole-sequence queries, cotangents and float32 dq, buffered
# twice, stay within half of the kernels' 64 MiB: 2 x heads x S x D x 8 bytes.
@pytest.mark.parametrize("why, shapes, plan", [
    ("LFM2's cell: the plan it has had since PR 34", _cell(1, 8, 4, 64), (512, 4)),
    ("Qwen3-Next's cell: 8 heads of 256 a group, one a step", _cell(2, 2, 8, 256), (512, 1)),
    ("Nemotron-H's cell: 16 heads of 128 a group, two a step", _cell(2, 2, 16, 128), (512, 2)),
    ("a short sequence: smaller blocks, the step no wider", _cell(1, 2, 16, 128, 384), (128, 2)),
    ("a group of six at head dim 64: threes, not fours", _cell(1, 2, 6, 64, 1024), (512, 3)),
    ("twice the length: half the heads a step", _cell(1, 8, 4, 64, 16384), (512, 2)),
    ("and at head dim 256 not even one", _cell(1, 2, 8, 256, 16384), None),
    ("one head's queries, cotangents and dq past VMEM", _cell(1, 8, 4, 64, 65536), None),
    ("a head dim the kernels were not written for", _cell(1, 8, 4, 96), None),
    ("not whole blocks", _cell(1, 8, 4, 64, 8192 + 64), None),
])
def test_the_plan_comes_from_the_shape(why, shapes, plan):
    got = attention_pallas.plan_for(*shapes, jnp.bfloat16)
    assert got == plan, why
    assert attention_pallas.plan_for(*shapes, jnp.float32) is None
    assert attention_pallas.supported(*shapes, jnp.bfloat16) == (plan is not None)


@pytest.mark.parametrize("d, folded", [(64, True), (128, False), (256, True)])
def test_the_scale_goes_into_the_keys_only_where_that_is_exact(d, folded):
    """``D^-0.5`` is a power of two at 64 and 256: the bfloat16 keys take it
    without rounding. At 128 it is not, and the keys stay as they are (the
    float32 scores take it in the kernels, as on the plain path)."""
    k = _inputs(128, d=d)[1]
    scaled = attention_pallas._scaled(k)
    if folded:
        np.testing.assert_array_equal(
            scaled.astype(jnp.float32), k.astype(jnp.float32) * np.float32(d ** -0.5))
    else:
        assert scaled is k


@pytest.mark.parametrize("why, shapes", [
    ("the tiny cut's 64 positions", _shapes(64)),
    ("an odd length", _shapes(8191)),
    ("not whole blocks", _shapes(8192 + 64)),
    ("float32, the CPU tests' precision", _shapes(8192, jnp.float32)),
    ("a head dim the kernels were not written for", _shapes(8192, d=96)),
    ("one head's queries, cotangents and dq past VMEM", _shapes(65536)),
])
def test_shapes_the_kernels_do_not_take_go_the_plain_way(on_tpu, why, shapes):
    assert not attention_pallas.dispatchable(*shapes), why


def test_under_vmap_the_plain_path_runs(on_tpu):
    """A batched ``pallas_call`` is not what the gate vouches for."""
    seen = []

    def attend(q, k):
        seen.append(attention_pallas.dispatchable(q, k))
        return q

    q, k = (jnp.zeros((2,) + s.shape, s.dtype) for s in _shapes(1024))
    jax.vmap(attend)(q, k)
    assert seen == [False]
    assert attention_pallas.dispatchable(q[0], k[0])
