"""Pallas one-pass max-pool backward vs XLA's reduce_window gradient
(interpreter mode — same math on CPU; the TPU lowering is exercised by
the compile probe + bench runs).

The kernel's tie rule is row-major first-max-wins == XLA's
``select_and_scatter``, so with integer-valued cotangents (float sums
exact regardless of accumulation order) the comparison is bit-exact even
on tie-heavy integer inputs.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.ops import pool_pallas


def _kernel_dx(x, dy, kh, kw, ph, pw):
    neg = jnp.asarray(float("-inf"), x.dtype)
    xp = jax.lax.pad(
        x, neg, ((0, 0, 0), (ph, ph, 0), (pw, pw, 0), (0, 0, 0))
    )
    dxp = pool_pallas._bwd_padded(xp, dy, kh=kh, kw=kw, interpret=True)
    h, w = x.shape[1], x.shape[2]
    return dxp[:, ph : ph + h, pw : pw + w, :]


def _xla_dx(x, dy, kh, kw, ph, pw):
    f = functools.partial(pool_pallas._fwd_val, kh=kh, kw=kw, ph=ph, pw=pw)
    _, vjp = jax.vjp(f, x)
    (dx,) = vjp(dy)
    return dx


@pytest.mark.parametrize(
    "shape,kh,kw,p,tie_heavy",
    [
        ((2, 16, 16, 8), 3, 3, 1, True),  # normal-cell 3x3 s1 pool
        ((2, 16, 16, 8), 3, 3, 1, False),
        ((1, 18, 18, 8), 3, 3, 0, True),  # pre-padded VALID form
        ((1, 8, 32, 16), 3, 3, 1, True),  # rectangular
        ((1, 32, 8, 128), 3, 3, 1, False),
        ((2, 16, 16, 8), 1, 3, 0, True),  # one window row: no tail blocks
        ((1, 64, 16, 8), 5, 3, 2, True),  # 5x3 window: 4 overlap rows
    ],
)
def test_bwd_matches_select_and_scatter(shape, kh, kw, p, tie_heavy):
    rng = np.random.default_rng(0)
    if tie_heavy:
        x = jnp.asarray(rng.integers(0, 3, size=shape), jnp.float32)
    else:
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    ho = shape[1] + 2 * p - kh + 1
    wo = shape[2] + 2 * p - kw + 1
    dy = jnp.asarray(
        rng.integers(-64, 64, size=(shape[0], ho, wo, shape[3])), jnp.float32
    )
    assert pool_pallas.supported(shape, kh, kw, p, p, 4)
    got = _kernel_dx(x, dy, kh, kw, p, p)
    want = _xla_dx(x, dy, kh, kw, p, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _brute_force_dx(x, dy, kh, kw):
    """Every window's row-major first maximum (``np.argmax`` returns the
    first), its cotangent scattered there: the definition, tap by tap."""
    x, dy = np.asarray(x, np.float32), np.asarray(dy, np.float32)
    b, hp, wp, c = x.shape
    ho, wo = hp - kh + 1, wp - kw + 1
    dx = np.zeros_like(x)
    for a in range(ho):
        for j in range(wo):
            win = x[:, a : a + kh, j : j + kw, :].reshape(b, kh * kw, c)
            first = np.argmax(win, axis=1)
            for t in range(kh * kw):
                dx[:, a + t // kw, j + t % kw, :] += np.where(
                    first == t, dy[:, a, j, :], 0.0
                )
    return dx


def _inputs(shape, kh, kw, fill, dtype, seed=0):
    """A pre-padded input and an integer cotangent small enough that a
    window's sum is exact in ``dtype``."""
    rng = np.random.default_rng(seed)
    if fill == "equal":
        x = np.ones(shape)
    elif fill == "two-valued":
        x = rng.integers(0, 2, size=shape)
    elif fill == "ties":
        x = rng.integers(0, 3, size=shape)
    else:
        x = rng.standard_normal(shape)
    dy_shape = (shape[0], shape[1] - kh + 1, shape[2] - kw + 1, shape[3])
    dy = rng.integers(-8, 8, size=dy_shape)
    return jnp.asarray(x, dtype), jnp.asarray(dy, dtype)


@pytest.mark.parametrize("fill", ["ties", "normal"])
@pytest.mark.parametrize(
    "shape",
    [(1, 18, 18, 416), (1, 18, 18, 832), (1, 10, 10, 1664), (1, 34, 34, 208)],
)
def test_bwd_at_the_models_channel_widths(shape, fill):
    """The dispatched widths, bf16, pre-padded, the spatial size cut: 208,
    416 and 832 end in a ragged 128-lane block, 1664 fills thirteen."""
    x, dy = _inputs(shape, 3, 3, fill, jnp.bfloat16)
    got = pool_pallas._bwd_padded(x, dy, kh=3, kw=3, interpret=True)
    want = _xla_dx(x, dy, 3, 3, 0, 0)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


@pytest.mark.parametrize("kh,kw", [(3, 3), (5, 3)])
def test_ties_across_every_row_chunk_and_row(monkeypatch, kh, kw):
    """Several row chunks (the VMEM budget cut so that ``_plan`` takes four
    window rows a grid step: the rows carried from one chunk to the next are
    exercised eight times) of several rows each, on an input that is equal
    down every column: each window's first maximum lies in its first row,
    across every boundary between rows and between chunks."""
    shape = (2, 32 + kh - 1, 18, 8)
    monkeypatch.setattr(pool_pallas, "_VMEM_BUDGET", 900 * 1024)
    assert pool_pallas._plan(8, 32, 16, kh, kw, 4)[0] == 4
    rng = np.random.default_rng(3)
    column = rng.integers(0, 3, size=(shape[0], 1, shape[2], shape[3]))
    x = jnp.asarray(np.broadcast_to(column, shape), jnp.float32)
    _, dy = _inputs(shape, kh, kw, "equal", jnp.float32)
    got = pool_pallas._bwd_padded(x, dy, kh=kh, kw=kw, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), _brute_force_dx(x, dy, kh, kw))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_xla_dx(x, dy, kh, kw, 0, 0))
    )
    # all of it lands in rows a window starts in: none in the last kh-1
    assert not np.asarray(got)[:, -(kh - 1) :].any()


@pytest.mark.parametrize("fill", ["equal", "two-valued"])
@pytest.mark.parametrize("kh,kw", [(3, 3), (2, 4)])
def test_separable_winner_is_the_row_major_first_maximum(kh, kw, fill):
    """First maximum over the column taps inside each row, then over the
    rows, against the definition taken tap by tap in row-major order."""
    x, dy = _inputs((2, 12, 11, 8), kh, kw, fill, jnp.float32, seed=5)
    got = pool_pallas._bwd_padded(x, dy, kh=kh, kw=kw, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), _brute_force_dx(x, dy, kh, kw))


def test_forward_matches_tree():
    from mpi4dl_tpu.ops.layers import max_pool_s1_valid

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 18, 18, 8)), jnp.float32)
    y_tree = max_pool_s1_valid(x, 3, 3)  # CPU: tree path (pallas not usable)
    y_pool = pool_pallas._fwd_val(x, 3, 3, 0, 0)
    np.testing.assert_array_equal(np.asarray(y_tree), np.asarray(y_pool))


def test_gates(monkeypatch):
    # a 1x1 window is the identity: nothing to schedule
    assert not pool_pallas.supported((2, 16, 16, 8), 1, 1, 0, 0)
    # more padded input than the chip's compiler takes (see supported())
    assert not pool_pallas.supported((2, 1024, 1024, 64), 3, 3, 1, 1)
    # CPU backend: usable() is False even for supported shapes
    x = jnp.zeros((2, 16, 16, 8), jnp.float32)
    if jax.default_backend() != "tpu":
        assert not pool_pallas.usable(x, 3, 3, 1, 1)
    # env off-switch
    monkeypatch.setenv("MPI4DL_TPU_POOL_PALLAS", "off")
    assert not pool_pallas.usable(x, 3, 3, 1, 1)
    monkeypatch.setenv("MPI4DL_TPU_POOL_PALLAS", "bogus")
    with pytest.raises(ValueError):
        pool_pallas.pool_pallas_mode()


def test_disable_context():
    """Trainer arms pool_pallas.disable() for >=2048px traces: injecting
    the kernel's VMEM-stack-allocated results into a program compiled
    against the HBM ceiling fails the compile (round-4 incident:
    AmoebaNet@2048 bs1 compiled with the kernels off, failed with them on).
    The context must gate dispatchable() regardless of backend."""
    x = jnp.zeros((2, 18, 18, 8), jnp.float32)
    with pool_pallas.disable():
        assert not pool_pallas.dispatchable(x, 3, 3, 0, 0)
        with pool_pallas.disable():  # re-entrant
            assert not pool_pallas.dispatchable(x, 3, 3, 0, 0)
        assert not pool_pallas.dispatchable(x, 3, 3, 0, 0)
    assert not pool_pallas._DISABLED[0]
