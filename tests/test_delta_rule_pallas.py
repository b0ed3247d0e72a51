"""The gated delta rule's kernels (``ops/delta_rule_pallas.py``) in the
Pallas interpreter, at shapes the kernels take (key and value dim 128, whole
chunks of 64): held to the plain chunked path they stand in for
(``sequence._chunked_rule``) and to the reference's position-by-position
recurrence, to the state's hand-on across chunks and grid steps, to the
pairing of value heads with their key head; the kernels' route to a chunk's
inverse against ``jnp.linalg.inv``; and the dispatch rule of
``ops/sequence.gated_delta_rule``.

``tests/test_qwen3_next.py`` runs the rule at key dim 16 in float32 and so
holds the plain path; what the chip's compiler makes of the kernels at full
width is ``tests/test_tpu_compile.py``'s.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import qwen3_next as ref
from mpi4dl_tpu.ops import delta_rule_pallas, sequence

B, H, R, D, E = 2, 2, 2, 128, 128
CHUNK = sequence.RULE_CHUNK
kernel = functools.partial(delta_rule_pallas.rule, chunk=CHUNK, interpret=True)


def _inputs(length, decay, beta_shift, dtype, seed=0):
    """``q, k`` of unit length (``q`` scaled), ``v``, ``g = -decay *
    softplus(.)``, ``beta = sigmoid(. + beta_shift)`` and a cotangent for
    the output, for 2 key heads of 2 value heads each."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q = (unit(jax.random.normal(keys[0], (B, length, H, D))) * D ** -0.5).astype(dtype)
    k = unit(jax.random.normal(keys[1], (B, length, H, D))).astype(dtype)
    v = jax.random.normal(keys[2], (B, length, H, R, E)).astype(dtype)
    g = -decay * jax.nn.softplus(jax.random.normal(keys[3], (B, length, H, R)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, length, H, R)) + beta_shift)
    return (q, k, v, g, beta), jax.random.normal(keys[5], v.shape).astype(dtype)


def _recurrence(q, k, v, g, beta):
    """The reference's position-by-position rule on the program's layout,
    float32."""
    b, s, h, r, e = v.shape
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    out = ref.delta_rule(
        jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v.reshape(b, s, h * r, e),
        g.reshape(b, s, h * r), beta.reshape(b, s, h * r))
    return out.reshape(v.shape)


def _out_and_grads(rule, args, ct):
    out, pull = jax.vjp(rule, *args)
    return (out, *pull(ct.astype(out.dtype)))


def _gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


SHAPES = pytest.mark.parametrize("length,decay,beta_shift", [
    (64, 0.1, 0.0), (256, 0.1, 0.0), (768, 0.1, 0.0), (192, 20.0, 6.0), (192, 0.01, 6.0)],
    ids=["one_chunk", "four_chunks", "many_chunks_two_grid_steps", "strong_decay_beta_near_1",
         "hardly_any_decay_beta_near_1"])


@SHAPES
def test_in_float32_the_kernels_are_the_recurrence(length, decay, beta_shift):
    """The algorithm (the WY form a chunk, the state handed on, the reverse
    sweep that carries ``dS``) without the rounding: float32 through the
    interpreter against the definition, value and all five gradients. ``g``
    near -20 a position underflows a chunk's decay to the 0 it is; a
    ``beta`` near 1 with hardly any decay fills the triangular system."""
    args, ct = _inputs(length, decay, beta_shift, jnp.float32)
    got, want = _out_and_grads(kernel, args, ct), _out_and_grads(_recurrence, args, ct)
    assert got[0].shape == want[0].shape and np.all(np.isfinite(np.asarray(got[0])))
    assert _gap(got[0], want[0]) < 1e-5
    for name, a, b in zip("q k v g beta".split(), got[1:], want[1:]):
        assert a.shape == b.shape and _gap(a, b) < 1e-4, (name, _gap(a, b))


# In bfloat16 the kernels and the plain path round at the same places
# (operands of every product in bfloat16, float32 accumulation, float32
# squares, inverse and state), so the outputs agree to the last bit or nearly;
# the cotangents differ where the kernels round a float32 cotangent to
# bfloat16 before a product (the chip's default precision does the same to
# the plain path's; the CPU's does not): 0.003-0.006 read here, a lost chunk
# or a state not handed on reads 0.1 and more. Against the float32
# recurrence the bfloat16 operands themselves show: 0.004-0.02.
@SHAPES
@pytest.mark.parametrize("oracle, limit", [("plain", 0.012), ("recurrence", 0.04)])
def test_in_bfloat16_value_and_cotangents_match(length, decay, beta_shift, oracle, limit):
    args, ct = _inputs(length, decay, beta_shift, jnp.bfloat16)
    got = _out_and_grads(kernel, args, ct)
    want = _out_and_grads(sequence._chunked_rule if oracle == "plain" else _recurrence, args, ct)
    for name, a, b in zip("out q k v g beta".split(), got, want):
        assert a.shape == b.shape and a.dtype == (jnp.float32 if name in ("g", "beta")
                                                  else jnp.bfloat16), name
        assert _gap(a, b) < limit, (name, _gap(a, b))


def test_the_state_is_handed_from_chunk_to_chunk():
    """With hardly any decay a change at position 3 reaches position 190,
    three chunks on, and nothing before position 3 moves."""
    (q, k, v, g, beta), _ = _inputs(192, 0.01, 0.0, jnp.bfloat16)
    moved = v.at[:, 3].add(1.0)
    delta = np.abs(np.asarray(
        kernel(q, k, moved, g, beta).astype(jnp.float32)
        - kernel(q, k, v, g, beta).astype(jnp.float32))).max(axis=(0, 2, 3, 4))
    assert np.all(delta[:3] == 0.0) and delta[3] > 0 and delta[190] > 1e-4


def test_the_state_crosses_grid_steps_and_starts_from_zero_for_every_head():
    """768 positions are twelve chunks: a grid step takes four, so the state
    crosses two block borders in VMEM scratch; the second sequence of the
    batch and the second key head start from zero again (their outputs are
    those of a call that holds them alone)."""
    (q, k, v, g, beta), _ = _inputs(768, 0.01, 0.0, jnp.bfloat16)
    assert delta_rule_pallas.step_chunks(768 // CHUNK) == 4
    whole = kernel(q, k, v, g, beta)
    alone = kernel(q[1:, :, 1:], k[1:, :, 1:], v[1:, :, 1:], g[1:, :, 1:], beta[1:, :, 1:])
    np.testing.assert_array_equal(whole[1:, :, 1:], alone)
    moved = kernel(q, k, v.at[:, 3].add(1.0), g, beta)
    assert float(jnp.max(jnp.abs((moved - whole)[:, 700:].astype(jnp.float32)))) > 1e-4


def test_a_value_head_moves_its_own_output_alone():
    """``v`` of value head 1 of key head 0 changed: that head's output moves
    and the other three value heads' are the same bits; ``dv`` is the same
    bits everywhere (the rule is linear in ``v``); ``dq`` / ``dk`` move for
    key head 0 alone (a key head's cotangents are the sum over its own
    value heads)."""
    args, ct = _inputs(128, 0.1, 0.0, jnp.bfloat16)
    other = _inputs(128, 0.1, 0.0, jnp.bfloat16, seed=1)[0][2]
    changed = list(args)
    changed[2] = args[2].at[:, :, 0, 1].set(other[:, :, 0, 1])
    out, dq, dk, dv, _, _ = _out_and_grads(kernel, args, ct)
    out2, dq2, dk2, dv2, _, _ = _out_and_grads(kernel, changed, ct)
    assert not np.array_equal(out[:, :, 0, 1], out2[:, :, 0, 1])
    for h, r in ((0, 0), (1, 0), (1, 1)):
        np.testing.assert_array_equal(out[:, :, h, r], out2[:, :, h, r])
    np.testing.assert_array_equal(dv, dv2)
    for a, b in ((dq, dq2), (dk, dk2)):
        assert not np.array_equal(a[:, :, 0], b[:, :, 0])
        np.testing.assert_array_equal(a[:, :, 1], b[:, :, 1])


@pytest.mark.parametrize("scale", [0.3, 0.6])
def test_the_kernels_route_inverts_a_unit_triangular_matrix(scale):
    """``tests/test_qwen3_next.py`` holds the plain path's series to
    ``jnp.linalg.inv`` at 1e-5; this is its twin for the route the kernels
    take (``(I + A)^-1``: the sign differs). At scale 0.6 the inverse's
    entries reach the hundreds and ``jnp.linalg.inv`` itself is off by 2e-4
    in float32: there the route is held to float64."""
    lower = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (64, 64)), -1) * scale
    got, twice = (delta_rule_pallas.unit_lower_inverse(a) for a in (lower, -lower))
    exact = np.linalg.inv(np.eye(64) + np.asarray(lower, np.float64))
    assert _gap(got, exact) < 1e-6
    assert _gap(twice, np.linalg.inv(np.eye(64) - np.asarray(lower, np.float64))) < 1e-6
    if scale == 0.3:
        assert _gap(got, jnp.linalg.inv(jnp.eye(64) + lower)) < 1e-5
    assert float(jnp.max(jnp.abs(jnp.triu(got, 1)))) == 0.0


# -- dispatch ----------------------------------------------------------------


def _shapes(length=8192, dtype=jnp.bfloat16, key_dim=128, value_dim=128, batch=2):
    """The Qwen3-Next cell's rule: 16 key heads of two value heads each."""
    head = (batch, length, 16)
    return (jax.ShapeDtypeStruct(head + (key_dim,), dtype),
            jax.ShapeDtypeStruct(head + (key_dim,), dtype),
            jax.ShapeDtypeStruct(head + (2, value_dim), dtype),
            jax.ShapeDtypeStruct(head + (2,), jnp.float32),
            jax.ShapeDtypeStruct(head + (2,), jnp.float32))


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch gate steered to its TPU branch (nothing is run there)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_on_the_cpu_the_plain_path_runs():
    assert not delta_rule_pallas.dispatchable(*_shapes(), CHUNK)
    args, _ = _inputs(128, 0.1, 0.0, jnp.bfloat16)
    np.testing.assert_array_equal(
        sequence.gated_delta_rule(*args), sequence._chunked_rule(*args))


def test_the_cells_shape_takes_the_kernels_on_a_tpu(on_tpu):
    assert delta_rule_pallas.dispatchable(*_shapes(), CHUNK)
    assert delta_rule_pallas.supported((2, 8192, 16, 128), (2, 8192, 16, 2, 128),
                                       jnp.bfloat16, CHUNK)
    assert delta_rule_pallas.step_chunks(8192 // CHUNK) == delta_rule_pallas.STEP_CHUNKS[0]


@pytest.mark.parametrize("why, shapes", [
    ("float32, the CPU tests' precision", _shapes(dtype=jnp.float32)),
    ("the tiny cut's key dim 16", _shapes(key_dim=16)),
    ("a value dim that is not whole lanes", _shapes(value_dim=64)),
    ("a length of 200: not whole chunks", _shapes(length=200)),
    ("the tiny cut's 160 positions", _shapes(length=160)),
    ("a grid step's states and blocks past the kernels' VMEM", _shapes(key_dim=1024, value_dim=1024)),
])
def test_shapes_the_kernels_do_not_take_go_the_plain_way(on_tpu, why, shapes):
    assert not delta_rule_pallas.dispatchable(*shapes, CHUNK), why


def test_under_vmap_the_plain_path_runs(on_tpu):
    """A batched ``pallas_call`` is not what the gate vouches for."""
    seen = []

    def rule(*args):
        seen.append(delta_rule_pallas.dispatchable(*args, CHUNK))
        return args[2]

    args = [jnp.zeros((3,) + s.shape, s.dtype) for s in _shapes(length=128, batch=1)]
    jax.vmap(rule)(*args)
    assert seen == [False]
    assert delta_rule_pallas.dispatchable(*(a[0] for a in args), CHUNK)
