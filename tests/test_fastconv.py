"""MXU-packed conv (ops/fastconv.py): exactness vs stock XLA conv.

The packed formulation is a layout identity — same products, same sums
(modulo f32 accumulation order) — so forward values and both gradients must
match ``lax.conv_general_dilated`` to tight f32 tolerances for every
(kernel, padding, factor) combination, including the VALID convs the
spatial/D2 paths use.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flax.linen as nn
from jax import lax

from mpi4dl_tpu.ops import fastconv


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _ref_conv(x, w, strides, padding):
    return lax.conv_general_dilated(
        x, w, strides, padding, dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


@pytest.mark.parametrize(
    "k,pad,f",
    [
        (3, 1, (2, 2)),
        (3, 1, (4, 4)),
        (3, 1, (1, 8)),
        (3, 0, (2, 2)),  # VALID conv (D2 shrink style)
        (5, 2, (2, 4)),
        (1, 0, (2, 2)),  # 1x1: packing never selected, but math must hold
        (3, 2, (2, 2)),  # overwide padding (D2 wide-halo style)
    ],
)
def test_packed_conv_matches_plain(k, pad, f):
    x = _rand((2, 16, 24, 5))
    w = _rand((k, k, 5, 7), seed=1) * 0.3
    padding = ((pad, pad), (pad, pad))
    got = fastconv._conv_packed(x, w, padding, *f)
    want = _ref_conv(x, w, (1, 1), padding)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_scatter_kernel_shape_and_content():
    w = _rand((3, 3, 2, 4))
    wp = fastconv._scatter_kernel(w, 2, 2)
    assert wp.shape == (4, 4, 2, 16)
    # group (0,0) = kernel at offset (0,0), zeros in the last row/col
    blk = wp[:, :, :, 0:4]
    np.testing.assert_array_equal(blk[:3, :3], w)
    assert float(jnp.abs(blk[3]).max()) == 0.0
    # group (1,1) = kernel shifted by one
    blk = wp[:, :, :, 12:16]
    np.testing.assert_array_equal(blk[1:, 1:], w)


def test_unknown_impl_rejected(monkeypatch):
    monkeypatch.setenv("MPI4DL_TPU_CONV_IMPL", "PACKED")
    x = _rand((1, 4, 4, 2))
    w = _rand((1, 1, 2, 2))
    with pytest.raises(ValueError, match="auto|packed|xla"):
        fastconv.conv2d(x, w, (1, 1), ((0, 0), (0, 0)))


@pytest.mark.parametrize(
    "x_shape,o,k,pad",
    [
        ((2, 8, 16, 5), 7, 3, 1),
        ((2, 8, 16, 5), 7, 3, 0),
        ((2, 8, 16, 5), 7, 1, 0),
        ((2, 8, 16, 5), 7, 5, 2),
        ((2, 8, 16, 5), 7, 3, 3),
        # k x k weight gradients: wide, batch 1, and 5x5 on a tall image
        ((2, 18, 18, 5), 7, 3, 0),
        ((1, 10, 26, 4), 4, 3, 0),
        ((2, 36, 12, 3), 5, 5, 0),
        # 1x1 at AmoebaNet-class widths: the two plain dots of the backward
        ((2, 16, 16, 104), 208, 1, 0),
        ((1, 8, 8, 128), 128, 1, 0),
        ((2, 4, 8, 416), 104, 1, 0),  # more inputs than outputs
    ],
)
def test_custom_vjp_grads_match(x_shape, o, k, pad, monkeypatch):
    monkeypatch.setenv("MPI4DL_TPU_CONV_IMPL", "packed")
    b, h, wd, c = x_shape
    x = _rand(x_shape)
    w = _rand((k, k, c, o), seed=1) * 0.3
    padding = ((pad, pad), (pad, pad))
    cot = _rand((b, h + 2 * pad - k + 1, wd + 2 * pad - k + 1, o), seed=2)

    def loss_fast(x, w):
        return jnp.sum(fastconv.conv2d(x, w, (1, 1), padding) * cot)

    def loss_ref(x, w):
        return jnp.sum(_ref_conv(x, w, (1, 1), padding) * cot)

    gx, gw = jax.grad(loss_fast, (0, 1))(x, w)
    rx, rw = jax.grad(loss_ref, (0, 1))(x, w)
    np.testing.assert_allclose(gx, rx, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(gw, rw, rtol=2e-4, atol=2e-4)


def test_strided_conv_falls_back_and_matches(monkeypatch):
    monkeypatch.setenv("MPI4DL_TPU_CONV_IMPL", "packed")
    x = _rand((2, 16, 16, 4))
    w = _rand((3, 3, 4, 6), seed=1) * 0.3
    padding = ((1, 1), (1, 1))
    got = fastconv.conv2d(x, w, (2, 2), padding)
    want = _ref_conv(x, w, (2, 2), padding)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("strides", [(1, 1), (2, 2)])
def test_taps_wgrad_grads_match(strides, monkeypatch):
    """The big-size per-tap wgrad (and the strided custom VJP around it)
    must equal stock XLA AD. The production gate needs >=256 MB operands;
    MIN_MB=0 forces the taps branch on small shapes so the path is
    exercised in CI (it is otherwise dead below 2048px)."""
    monkeypatch.setenv("MPI4DL_TPU_CONV_IMPL", "packed")
    monkeypatch.setenv("MPI4DL_TPU_WGRAD_TAPS_MIN_MB", "0")
    x = _rand((1, 16, 16, 4))
    w = _rand((3, 3, 4, 6), seed=1) * 0.3
    padding = ((1, 1), (1, 1))

    def loss_fast(x, w):
        return jnp.sum(jnp.square(fastconv.conv2d(x, w, strides, padding)))

    def loss_ref(x, w):
        return jnp.sum(jnp.square(_ref_conv(x, w, strides, padding)))

    gx, gw = jax.grad(loss_fast, (0, 1))(x, w)
    rx, rw = jax.grad(loss_ref, (0, 1))(x, w)
    np.testing.assert_allclose(gx, rx, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(gw, rw, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("k,s", [(3, 1), (3, 2), (1, 1)])
def test_packed_core_taps_grads_match(k, s, monkeypatch):
    """The packed-layout core conv's taps backward (bs=1 engages the
    batch<=2 gate with MIN_MB=0) must equal stock AD of the plain conv
    through the pack/unpack round trip."""
    monkeypatch.setenv("MPI4DL_TPU_WGRAD_TAPS_MIN_MB", "0")
    from mpi4dl_tpu.ops.packed import conv2d_packed, pack, pack_factor, unpack

    c = o = 8  # equal c/o keeps f_in == f_out valid for every stride here
    f_in, f_out = pack_factor(c, 32), pack_factor(o, 32 // s)
    x = _rand((1, 16, 32, c))
    w = _rand((k, k, c, o), seed=1) * 0.3
    p = (k - 1) // 2
    padding = ((p, p), (p, p))

    def loss_packed(x, w):
        y = conv2d_packed(pack(x, f_in), w, f_in, f_out, (s, s), padding)
        return jnp.sum(jnp.square(unpack(y, f_out)))

    def loss_ref(x, w):
        return jnp.sum(jnp.square(_ref_conv(x, w, (s, s), padding)))

    gx, gw = jax.grad(loss_packed, (0, 1))(x, w)
    rx, rw = jax.grad(loss_ref, (0, 1))(x, w)
    np.testing.assert_allclose(gx, rx, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(gw, rw, rtol=2e-4, atol=2e-4)


def test_pack_factors_policy():
    # 1x1 never packs
    assert fastconv.pack_factors(1, 1, 16, 64) == (1, 1)
    # >=128 output channels never packs
    assert fastconv.pack_factors(3, 3, 128, 64) == (1, 1)
    # small-N 3x3 packs along W only, factor divides the output extent
    fh, fw = fastconv.pack_factors(3, 3, 16, 64)
    assert fh == 1 and fw > 1 and 64 % fw == 0
    # indivisible output extent: no packing
    assert fastconv.pack_factors(3, 3, 16, 7) == (1, 1)


def test_fastconv_module_params_match_nn_conv(monkeypatch):
    monkeypatch.setenv("MPI4DL_TPU_CONV_IMPL", "packed")
    x = _rand((2, 8, 8, 4))
    ref = nn.Conv(
        features=6, kernel_size=(3, 3), strides=(1, 1),
        padding=((1, 1), (1, 1)), name="conv",
    )
    fast = fastconv.FastConv(
        features=6, kernel_size=(3, 3), strides=(1, 1),
        padding=((1, 1), (1, 1)), name="conv",
    )
    vref = ref.init(jax.random.PRNGKey(0), x)
    vfast = fast.init(jax.random.PRNGKey(0), x)
    assert jax.tree.structure(vref) == jax.tree.structure(vfast)
    for a, b in zip(jax.tree.leaves(vref), jax.tree.leaves(vfast)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(
        fast.apply(vref, x), ref.apply(vref, x), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("strides", [(1, 1), (2, 2)])
def test_fastconv_same_padding_string(strides, monkeypatch):
    monkeypatch.setenv("MPI4DL_TPU_CONV_IMPL", "packed")
    x = _rand((1, 12, 16, 3))
    fast = fastconv.FastConv(
        features=5, kernel_size=(3, 3), strides=strides, padding="SAME",
        name="conv",
    )
    v = fast.init(jax.random.PRNGKey(0), x)
    ref = nn.Conv(
        features=5, kernel_size=(3, 3), strides=strides, padding="SAME",
        name="conv",
    )
    np.testing.assert_allclose(
        fast.apply(v, x), ref.apply(v, x), rtol=2e-5, atol=2e-5
    )


def test_fastconv_valid_padding_string(monkeypatch):
    monkeypatch.setenv("MPI4DL_TPU_CONV_IMPL", "packed")
    x = _rand((1, 10, 12, 3))
    fast = fastconv.FastConv(
        features=5, kernel_size=(3, 3), padding="VALID", name="conv"
    )
    v = fast.init(jax.random.PRNGKey(0), x)
    ref = nn.Conv(
        features=5, kernel_size=(3, 3), padding="VALID", name="conv"
    )
    np.testing.assert_allclose(
        fast.apply(v, x), ref.apply(v, x), rtol=2e-5, atol=2e-5
    )


def test_packed_spatial_conv_matches_golden(monkeypatch):
    """The production TPU shape: Conv2d(spatial=True) under shard_map with
    the packed impl, forward AND gradient vs the full-image plain golden."""
    monkeypatch.setenv("MPI4DL_TPU_CONV_IMPL", "packed")
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.ops.layers import Conv2d

    cfg = ParallelConfig(
        batch_size=2,
        split_size=1,
        spatial_size=1,
        num_spatial_parts=(4,),
        slice_method="square",
        image_size=16,
    )
    mesh = cfg.make_mesh()
    x = _rand((2, 16, 16, 4))
    cot = _rand((2, 16, 16, 6), seed=3)

    plain = Conv2d(features=6, kernel_size=3)
    spatial = Conv2d(features=6, kernel_size=3, spatial=True)
    v = plain.init(jax.random.PRNGKey(0), x)

    def golden(v, x):
        return jnp.sum(plain.apply(v, x) * cot)

    def local(v, x, cot):
        return jax.lax.psum(
            jnp.sum(spatial.apply(v, x) * cot), ("tile_h", "tile_w")
        )

    dist = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(None, "tile_h", "tile_w", None),
                  P(None, "tile_h", "tile_w", None)),
        out_specs=P(),
        check_vma=False,
    )
    np.testing.assert_allclose(dist(v, x, cot), golden(v, x), rtol=2e-5)
    gd = jax.grad(lambda v: dist(v, x, cot))(v)
    gg = jax.grad(lambda v: golden(v, x))(v)
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gg)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
