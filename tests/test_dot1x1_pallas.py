"""Fused one-pass 1x1-conv backward kernel (ops/dot1x1_pallas.py):
interpreter-mode equality against the stock two-dot backward it
replaces (``fastconv._conv2d_s1_bwd``'s 1x1 branch)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.ops import dot1x1_pallas


@pytest.mark.parametrize(
    "b,h,w,c,o",
    [
        (2, 16, 16, 104, 208),  # AmoebaNet-class widths
        (1, 8, 8, 128, 128),
        (2, 4, 8, 416, 104),  # c > o reduce
    ],
)
def test_fused_1x1_bwd_matches_two_dots(b, h, w, c, o):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((b, h, w, c)), jnp.float32)
    dy = jnp.asarray(rng.standard_normal((b, h, w, o)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((c, o)), jnp.float32)

    dx, dw = dot1x1_pallas.bwd_1x1(x, dy, w2, interpret=True)

    want_dx = jax.lax.dot_general(dy, w2, (((3,), (1,)), ((), ())))
    want_dw = jax.lax.dot_general(
        x, dy, (((0, 1, 2), (0, 1, 2)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    np.testing.assert_allclose(np.asarray(dx), np.asarray(want_dx), rtol=2e-5)
    # dw accumulates across grid steps: f32 reduction order differs from
    # the single fused dot.
    np.testing.assert_allclose(
        np.asarray(dw), np.asarray(want_dw), rtol=1e-4, atol=1e-4
    )
    assert dw.dtype == jnp.float32


def test_conv2d_grad_with_fused_kernel_matches_stock(monkeypatch):
    """End-to-end VJP through fastconv.conv2d with the fused kernel forced
    on (interpreter): gradients must match the stock two-dot backward."""
    from mpi4dl_tpu.ops import fastconv

    monkeypatch.setattr(
        dot1x1_pallas, "dispatchable", lambda x, dy: True
    )
    monkeypatch.setattr(
        dot1x1_pallas, "bwd_1x1",
        lambda x, dy, w2: dot1x1_pallas._bwd_impl(x, dy, w2, interpret=True),
    )
    monkeypatch.setattr(fastconv, "_on_tpu", lambda: True)

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 104)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((1, 1, 104, 128)) * 0.1, jnp.float32)

    def loss(x, w):
        return jnp.sum(fastconv.conv2d(x, w) ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)

    monkeypatch.setattr(
        dot1x1_pallas, "dispatchable", lambda x, dy: False
    )
    gx0, gw0 = jax.grad(loss, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx0), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(gw), np.asarray(gw0), rtol=1e-4, atol=1e-4
    )


def test_dispatch_is_the_shape_gate_alone(monkeypatch):
    """On a TPU backend, with the kernel switched on, dispatch is exactly
    ``supported()``: no trial compile stands between the gate and the
    kernel, so a shape the chip's compiler refuses surfaces as its error
    (tests/test_tpu_compile.py holds the gate to that compiler)."""
    import jax as jax_mod

    monkeypatch.setenv("MPI4DL_TPU_DOT1X1", "auto")
    monkeypatch.setattr(jax_mod, "default_backend", lambda: "tpu")
    x = jnp.zeros((2, 64, 64, 208), jnp.float32)
    dy = jnp.zeros((2, 64, 64, 208), jnp.float32)
    assert dot1x1_pallas.dispatchable(x, dy)
    narrow = jnp.zeros((2, 64, 64, 16), jnp.float32)
    assert not dot1x1_pallas.dispatchable(narrow, narrow)
    monkeypatch.setattr(jax_mod, "default_backend", lambda: "cpu")
    assert not dot1x1_pallas.dispatchable(x, dy)


def test_plan_respects_vmem_budget():
    # Huge rows force smaller chunks; an impossible shape returns None.
    assert dot1x1_pallas._plan(1, 256, 256, 208, 208, 2) is not None
    assert dot1x1_pallas._plan(1, 1, 512 * 512, 1664, 1664, 2) is None


def test_supported_gates():
    # narrow channels are rejected (lane-waste regime)
    assert not dot1x1_pallas.supported((2, 16, 16, 64), 104)
    assert not dot1x1_pallas.supported((2, 16, 16, 104), 64)
    # dx-result-size guard (VMEM stack wall)
    assert not dot1x1_pallas.supported((2, 1024, 1024, 208), 208)
    assert dot1x1_pallas.supported((2, 64, 64, 208), 208)
