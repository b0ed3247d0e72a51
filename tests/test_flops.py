"""FLOP accounting tests: analytic counts on known-cost layers."""

import jax.numpy as jnp
import numpy as np

from mpi4dl_tpu.flops import forward_flops, mfu, train_flops_per_image
from mpi4dl_tpu.ops.fastconv import FastConv


def test_conv_flops_analytic():
    # 3x3 SAME conv, 8->16ch @ 32x32: 2 * H*W*O * KH*KW*Cin MACs-as-FLOPs.
    cell = FastConv(features=16, kernel_size=(3, 3), use_bias=False)
    got = forward_flops([cell], (1, 32, 32, 8))
    want = 2 * 32 * 32 * 16 * 3 * 3 * 8
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_flops_scale_with_batch_and_resolution():
    cell = FastConv(features=16, kernel_size=(3, 3), use_bias=False)
    f1 = forward_flops([cell], (1, 32, 32, 8))
    f2 = forward_flops([cell], (4, 32, 32, 8))
    f3 = forward_flops([cell], (1, 64, 64, 8))
    np.testing.assert_allclose(f2, 4 * f1, rtol=1e-6)
    np.testing.assert_allclose(f3, 4 * f1, rtol=1e-6)


def test_resnet_train_flops_sane():
    from mpi4dl_tpu.models.resnet import get_resnet_v2

    # depth 9n+2 → BOTTLENECK v2 blocks (3 convs, 4x expansion): much more
    # FLOPs than the classic basic-block CIFAR ResNet of the same depth.
    cells = get_resnet_v2(depth=20, num_classes=10, pool_kernel=8)
    fwd = train_flops_per_image(cells, 32) / 3
    assert 150e6 < fwd < 400e6, fwd
    # Quadratic in resolution.
    fwd2 = train_flops_per_image(cells, 64) / 3
    np.testing.assert_allclose(fwd2 / fwd, 4.0, rtol=0.05)


def test_mfu_none_off_tpu():
    assert mfu(10.0, 1e12) is None  # CPU test process: no peak


def test_peak_flops_unknown_tpu_is_an_error():
    """None is for CPU only: a TPU the table does not list must not
    quietly drop the MFU figure."""
    import types

    import pytest

    from mpi4dl_tpu.flops import peak_flops

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert peak_flops(v5e) == 197e12
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        peak_flops(unknown)
    with pytest.raises(ValueError):
        mfu(10.0, 1e12, device=unknown)


def test_flops_counted_inside_cond_branches():
    """FLOPs inside lax.cond branches must be counted (ADVICE r2: the
    recursion previously skipped the 'branches' tuple-of-jaxprs param,
    silently deflating the MFU denominator)."""
    import flax.linen as nn
    import jax

    class CondCell(nn.Module):
        @nn.compact
        def __call__(self, x):
            w = self.param(
                "w", nn.initializers.ones_init(), (x.shape[-1], 16), jnp.float32
            )
            return jax.lax.cond(
                x.sum() > 0, lambda: x @ w, lambda: (x * 2) @ w
            )

    got = forward_flops([CondCell()], (1, 4, 4, 8))
    want = 2 * 4 * 4 * 16 * 8  # one branch's matmul (max over branches)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_forward_flops_rejects_packed_cells():
    """MFU must be counted on the logical model: packed cells execute
    inflated scattered-kernel FLOPs and are rejected at trace time."""
    import pytest

    from mpi4dl_tpu.models.resnet import get_resnet_v2

    packed = get_resnet_v2(depth=20, layout="packed")
    with pytest.raises(ValueError, match="logical"):
        forward_flops(packed, (1, 32, 32, 3))
