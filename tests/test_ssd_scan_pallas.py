"""Mamba-2's scan kernels (``ops/ssd_scan_pallas.py``) in the Pallas
interpreter, at shapes the kernels take (heads of 64 or 128 channels, a state
of 128, whole chunks of 128): held to the plain chunked path they stand in
for (``sequence._chunked_scan`` under ``lax.map``) and to the reference's
position-by-position recurrence, to the state's hand-on across chunks and
grid steps, to what a group's heads share and what they do not; and the
dispatch rule of ``ops/sequence.ssd_scan``.

``tests/test_nemotron_h.py`` runs the scan at head dim 8 in float32 and so
holds the plain path; what the chip's compiler makes of the kernels at full
width is ``tests/test_tpu_compile.py``'s.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chipbench.reference import nemotron_h as ref
from mpi4dl_tpu.ops import sequence, ssd_scan_pallas

B, G, R, P, N, CHUNK = 2, 2, 2, 64, 128, 128
kernel = functools.partial(ssd_scan_pallas.scan, chunk=CHUNK, interpret=True)

# ``A`` of a group's two heads: log-normal as a fresh model's, or a head that
# forgets within a position (exp(-40 dt)) beside one whose state lasts
# hundreds of positions, or two of those.
DECAYS = {"mixed": None, "strong_beside_weak": (-40.0, -0.003), "weak": (-0.003, -0.001)}


def plain(x, g, b, c):
    return lax.map(lambda row: sequence._chunked_scan(*row, CHUNK), (x, g, b, c))


def _inputs(length, decay, dtype, seed=0, heads=R, width=P):
    """``x`` before its ``dt``, ``dt = softplus(.)``, ``B, C``, a head's
    ``A`` and a cotangent for the output, for 2 groups of ``heads`` heads."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (B, length, G, heads, width)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, length, G, heads)))
    b = (jax.random.normal(keys[2], (B, length, G, N)) * N ** -0.5).astype(dtype)
    c = jax.random.normal(keys[3], (B, length, G, N)).astype(dtype)
    if DECAYS[decay] is None:
        a = -jnp.exp(2.0 * jax.random.normal(keys[4], (G, heads)))
    else:
        a = jnp.tile(jnp.asarray(DECAYS[decay], jnp.float32), (G, heads // 2))
    return (x, dt, b, c), a, jax.random.normal(keys[5], x.shape).astype(dtype)


def _scan_args(args, a):
    """What ``Mamba2`` hands the scan: ``dt x`` in the activations' dtype,
    ``g = dt A``."""
    x, dt, b, c = args
    return (x * dt[..., None]).astype(x.dtype), dt * a, b, c


def _through(scan, a):
    """``scan`` as a function of ``(x, dt, B, C)``: ``dt``'s gradient holds
    ``g``'s and ``x``'s cotangents both."""
    return lambda *args: scan(*_scan_args(args, a))


def _recurrence(a):
    """The reference's position-by-position recurrence on the program's
    layout, float32, a group's ``B, C`` repeated for its heads."""
    def scan(x, dt, b, c):
        batch, length, groups, heads, width = x.shape
        flat = (batch, length, groups * heads)
        out = ref.recurrence(
            x.astype(jnp.float32).reshape(*flat, width), dt.reshape(flat), a.reshape(-1),
            *(jnp.repeat(t.astype(jnp.float32), heads, axis=2) for t in (b, c)))
        return out.reshape(x.shape)
    return scan


def _out_and_grads(scan, args, ct):
    out, pull = jax.vjp(scan, *args)
    return (out, *pull(ct.astype(out.dtype)))


def _gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


SHAPES = pytest.mark.parametrize("length,decay", [
    (128, "mixed"), (512, "mixed"), (1536, "mixed"), (384, "strong_beside_weak"), (384, "weak")],
    ids=["one_chunk", "four_chunks", "many_chunks_three_grid_steps", "strong_beside_weak_decay",
         "hardly_any_decay"])


@SHAPES
def test_in_float32_the_kernels_are_the_recurrence(length, decay):
    """The algorithm (a chunk's masked square, the state handed on, what the
    start state adds, the reverse sweep that carries ``dS``) without the
    rounding: float32 through the interpreter against the definition, value
    and all four gradients (``dt``'s holds ``g``'s). A head whose ``dt A`` is
    near -40 a position underflows its chunk's decays to the 0 they are; one
    with hardly any decay reads the state of every chunk before it."""
    args, a, ct = _inputs(length, decay, jnp.float32)
    got = _out_and_grads(_through(kernel, a), args, ct)
    want = _out_and_grads(_recurrence(a), args, ct)
    assert got[0].shape == want[0].shape and np.all(np.isfinite(np.asarray(got[0])))
    assert _gap(got[0], want[0]) < 1e-5
    for name, one, other in zip("x dt b c".split(), got[1:], want[1:]):
        assert one.shape == other.shape and _gap(one, other) < 1e-4, (name, _gap(one, other))


# In bfloat16 the kernels and the plain path round at the same places
# (operands of every product in bfloat16, float32 accumulation, float32
# running sums, decays and state; ``(C B^T) * L``, ``x * to_end`` and the
# start state rounded where ``_chunked_scan`` rounds them), so the outputs
# agree to the last bit or nearly (0 to 4e-5 read here); the cotangents
# differ where the kernels round a float32 cotangent to bfloat16 before a
# product (the chip's default precision does the same to the plain path's;
# the CPU's does not) and where ``b``'s and ``c``'s are summed over a
# group's heads in float32 and rounded once: 0.001-0.004 read here, a lost
# chunk or a state not handed on reads 0.1 and more. Against the float32
# recurrence the bfloat16 operands themselves show: 0.003-0.008.
@SHAPES
@pytest.mark.parametrize("oracle, limit", [("plain", 0.012), ("recurrence", 0.03)])
def test_in_bfloat16_value_and_cotangents_match(length, decay, oracle, limit):
    args, a, ct = _inputs(length, decay, jnp.bfloat16)
    if oracle == "plain":
        args = _scan_args(args, a)
        got, want = _out_and_grads(kernel, args, ct), _out_and_grads(plain, args, ct)
    else:
        got = _out_and_grads(_through(kernel, a), args, ct)
        want = _out_and_grads(_recurrence(a), args, ct)
    for name, one, other in zip("out x g b c".split(), got, want):
        assert one.shape == other.shape, name
        assert one.dtype == (jnp.float32 if name == "g" else jnp.bfloat16), name
        assert _gap(one, other) < limit, (name, _gap(one, other))


@pytest.mark.parametrize("heads, width", [(2, 128), (3, 32)])
def test_other_heads_than_the_cells_take_the_same_arithmetic(heads, width):
    """A head's channels are rows of the kernels' blocks, so any width of
    whole bfloat16 tiles and any count of heads a group goes: heads of 128,
    and three of 32 (a group that is no whole lanes wide), held to the plain
    path."""
    args, a, ct = _inputs(256, "mixed", jnp.bfloat16, heads=heads, width=width)
    args = _scan_args(args, a)
    got, want = _out_and_grads(kernel, args, ct), _out_and_grads(plain, args, ct)
    for name, one, other in zip("out x g b c".split(), got, want):
        assert _gap(one, other) < 0.012, (name, _gap(one, other))


def test_the_state_is_handed_from_chunk_to_chunk():
    """With hardly any decay a change at position 3 reaches position 380,
    two chunks on, and nothing before position 3 moves."""
    args, a, _ = _inputs(384, "weak", jnp.bfloat16)
    x, g, b, c = _scan_args(args, a)
    delta = np.abs(np.asarray(
        kernel(x.at[:, 3].add(1.0), g, b, c).astype(jnp.float32)
        - kernel(x, g, b, c).astype(jnp.float32))).max(axis=(0, 2, 3, 4))
    assert np.all(delta[:3] == 0.0) and delta[3] > 0 and delta[380] > 1e-4


def test_the_state_crosses_grid_steps_and_starts_from_zero_for_every_sequence_and_group():
    """1,536 positions are twelve chunks: a grid step takes four, so the
    state crosses two block borders in VMEM scratch; the second sequence of
    the batch and the second group start from zero again (their outputs are
    those of a call that holds them alone)."""
    args, a, _ = _inputs(1536, "weak", jnp.bfloat16)
    x, g, b, c = _scan_args(args, a)
    assert ssd_scan_pallas.step_chunks(1536 // CHUNK) == 4
    whole = kernel(x, g, b, c)
    alone = kernel(x[1:, :, 1:], g[1:, :, 1:], b[1:, :, 1:], c[1:, :, 1:])
    np.testing.assert_array_equal(whole[1:, :, 1:], alone)
    moved = kernel(x.at[:, 3].add(1.0), g, b, c)
    assert float(jnp.max(jnp.abs((moved - whole)[:, 1400:].astype(jnp.float32)))) > 1e-4


def test_a_head_moves_its_own_output_alone_and_a_groups_b_and_c_reach_all_its_heads():
    """``x`` of head 1 of group 0 changed: that head's output moves and the
    other three heads' are the same bits (heads 0 and 1 of a group share a
    group's products with the start states and with ``B``); ``dx`` is the same bits everywhere
    (the scan is linear in ``x``); ``db`` / ``dc`` move for group 0 alone (a
    group's cotangents are the sum over its own heads). ``B`` of group 0
    changed: both its heads' outputs move, group 1's are the same bits."""
    args, a, ct = _inputs(256, "mixed", jnp.bfloat16)
    args = _scan_args(args, a)
    other = _scan_args(_inputs(256, "mixed", jnp.bfloat16, seed=1)[0], a)
    changed = list(args)
    changed[0] = args[0].at[:, :, 0, 1].set(other[0][:, :, 0, 1])
    out, dx, _, db, dc = _out_and_grads(kernel, args, ct)
    out2, dx2, _, db2, dc2 = _out_and_grads(kernel, changed, ct)
    assert not np.array_equal(out[:, :, 0, 1], out2[:, :, 0, 1])
    for group, head in ((0, 0), (1, 0), (1, 1)):
        np.testing.assert_array_equal(out[:, :, group, head], out2[:, :, group, head])
    np.testing.assert_array_equal(dx, dx2)
    for one, two in ((db, db2), (dc, dc2)):
        assert not np.array_equal(one[:, :, 0], two[:, :, 0])
        np.testing.assert_array_equal(one[:, :, 1], two[:, :, 1])
    shared = kernel(args[0], args[1], args[2].at[:, :, 0].set(other[2][:, :, 0]), args[3])
    for head in range(R):
        assert not np.array_equal(out[:, :, 0, head], shared[:, :, 0, head])
    np.testing.assert_array_equal(out[:, :, 1], shared[:, :, 1])


# -- dispatch ----------------------------------------------------------------


def _shapes(length=8192, dtype=jnp.bfloat16, heads=8, width=64, state=128, batch=2,
            decay=jnp.float32):
    """The Nemotron-H cell's scan: 8 groups of 8 heads of 64, a state of 128."""
    return (jax.ShapeDtypeStruct((batch, length, 8, heads, width), dtype),
            jax.ShapeDtypeStruct((batch, length, 8, heads), decay),
            jax.ShapeDtypeStruct((batch, length, 8, state), dtype),
            jax.ShapeDtypeStruct((batch, length, 8, state), dtype))


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch gate steered to its TPU branch (nothing is run there)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_on_the_cpu_the_plain_path_runs():
    assert not ssd_scan_pallas.dispatchable(*_shapes(), CHUNK)
    args, a, _ = _inputs(256, "mixed", jnp.bfloat16)
    args = _scan_args(args, a)
    np.testing.assert_array_equal(sequence.ssd_scan(*args, CHUNK), plain(*args))


def test_the_cells_shape_takes_the_kernels_on_a_tpu(on_tpu):
    assert ssd_scan_pallas.dispatchable(*_shapes(), CHUNK)
    assert ssd_scan_pallas.supported((2, 8192, 8, 8, 64), 128, jnp.bfloat16, CHUNK)
    assert ssd_scan_pallas.step_chunks(8192 // CHUNK) == ssd_scan_pallas.STEP_CHUNKS[0]


@pytest.mark.parametrize("why, shapes, chunk", [
    ("float32, the CPU tests' precision", _shapes(dtype=jnp.float32), CHUNK),
    ("decays that are not float32", _shapes(decay=jnp.bfloat16), CHUNK),
    ("the tiny cut's heads of 8 channels: no whole bfloat16 tile", _shapes(heads=4, width=8), CHUNK),
    ("heads of 24 channels", _shapes(width=24), CHUNK),
    ("the tiny cut's state of 16", _shapes(state=16), CHUNK),
    ("the tiny cut's chunks of 32", _shapes(), 32),
    ("a length of 200: not whole chunks", _shapes(length=200), CHUNK),
    ("the tiny cut's 160 positions", _shapes(length=160), CHUNK),
    ("a grid step's states and blocks past the kernels' VMEM",
     _shapes(heads=64, width=128, state=1024), CHUNK),
])
def test_shapes_the_kernels_do_not_take_go_the_plain_way(on_tpu, why, shapes, chunk):
    assert not ssd_scan_pallas.dispatchable(*shapes, chunk), why


def test_under_vmap_the_plain_path_runs(on_tpu):
    """A batched ``pallas_call`` is not what the gate vouches for."""
    seen = []

    def scan(*args):
        seen.append(ssd_scan_pallas.dispatchable(*args, CHUNK))
        return args[0]

    args = [jnp.zeros((3,) + s.shape, s.dtype) for s in _shapes(length=128, batch=1)]
    jax.vmap(scan)(*args)
    assert seen == [False]
    assert ssd_scan_pallas.dispatchable(*(a[0] for a in args), CHUNK)
