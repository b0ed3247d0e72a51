"""Distributed-vs-sequential layer equivalence tests.

TPU rebuild of the reference's conv validation benchmarks
(``benchmark_sp_halo_exchange_with_compute_val.py:704-780``,
``benchmark_sp_halo_exchange_conv.py:940-1092``): a spatially-partitioned
conv/pool over the tile mesh must produce exactly the tiles of the
single-device ("sequential") op on the full image. Unlike the reference we
don't need to force weights to 1.0 — CPU simulation is deterministic — but we
keep one ones-weight case for parity with the reference harness.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi4dl_tpu.config import tile_grid
from mpi4dl_tpu.ops.layers import Conv2d, Pool

SPEC = P(None, "tile_h", "tile_w", None)


def _mesh(th, tw):
    dev = np.asarray(jax.devices()[: th * tw]).reshape(th, tw)
    return Mesh(dev, ("tile_h", "tile_w"))


def _run_distributed(module_spatial, module_plain, x, mesh, params=None):
    """Init plain module single-device, run spatial module under shard_map
    with the same params, return (distributed_out, golden_out)."""
    key = jax.random.PRNGKey(0)
    if params is None:
        params = module_plain.init(key, x)
    golden = module_plain.apply(params, x)

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), SPEC),
        out_specs=SPEC,
        check_vma=False,
    )
    def dist_apply(p, tile):
        return module_spatial.apply(p, tile)

    xs = jax.device_put(x, NamedSharding(mesh, SPEC))
    out = dist_apply(params, xs)
    return np.asarray(out), np.asarray(golden)


@pytest.mark.parametrize("slice_method,parts", [("square", 4), ("vertical", 4), ("horizontal", 4)])
@pytest.mark.parametrize("kernel,stride", [(3, 1), (3, 2), (1, 1), (5, 1)])
def test_spatial_conv_matches_sequential(slice_method, parts, kernel, stride):
    th, tw = tile_grid(parts, slice_method)
    mesh = _mesh(th, tw)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 16, 16, 3)), dtype=jnp.float32)

    plain = Conv2d(features=8, kernel_size=kernel, strides=stride, spatial=False)
    spatial = Conv2d(features=8, kernel_size=kernel, strides=stride, spatial=True)
    out, golden = _run_distributed(spatial, plain, x, mesh)
    np.testing.assert_allclose(out, golden, rtol=1e-5, atol=1e-5)


def test_spatial_conv_ones_weights_integer_exact():
    """Reference-parity case: weights/bias forced to 1.0 on an arange image
    (``benchmark_sp_halo_exchange_with_compute_val.py:704-706``)."""
    mesh = _mesh(2, 2)
    x = jnp.arange(1 * 8 * 8 * 2, dtype=jnp.float32).reshape(1, 8, 8, 2)
    plain = Conv2d(features=4, kernel_size=3, spatial=False)
    spatial = Conv2d(features=4, kernel_size=3, spatial=True)
    params = plain.init(jax.random.PRNGKey(0), x)
    params = jax.tree.map(lambda a: jnp.ones_like(a), params)
    out, golden = _run_distributed(spatial, plain, x, mesh, params=params)
    np.testing.assert_array_equal(out, golden)


@pytest.mark.parametrize("kind", ["max", "avg"])
@pytest.mark.parametrize("kernel,stride,padding", [(2, 2, 0), (3, 2, 1), (3, 1, 1)])
def test_spatial_pool_matches_sequential(kind, kernel, stride, padding):
    mesh = _mesh(2, 2)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 16, 16, 3)), dtype=jnp.float32)
    plain = Pool(kind=kind, kernel_size=kernel, strides=stride, padding=padding)
    spatial = Pool(
        kind=kind, kernel_size=kernel, strides=stride, padding=padding, spatial=True
    )
    out, golden = _run_distributed(spatial, plain, x, mesh)
    np.testing.assert_allclose(out, golden, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize(
    "kernel,stride,padding,shape",
    [
        ((3, 3), (2, 2), (1, 1), (2, 16, 16, 3)),
        ((2, 2), (2, 2), (0, 0), (2, 16, 16, 3)),
        ((3, 3), (2, 2), (1, 1), (1, 15, 17, 5)),  # odd extents
        ((3, 2), (2, 3), (1, 0), (2, 12, 18, 4)),  # rectangular
    ],
)
def test_max_pool_strided_backward_matches_select_and_scatter(
    kernel, stride, padding, shape
):
    """The ``Pool`` module's strided max pool (its own -inf edge padding
    ahead of the window op) against ``flax.linen.max_pool``, whose backward
    is XLA's ``select_and_scatter``: the first max in row-major window order
    wins the gradient. On tie-HEAVY data (small integers, so most windows
    hold duplicated maxima) any other tie-breaking shows at once. This is
    the oracle a kernel for the strided pools' backward has to meet."""
    import flax.linen as nn

    rng = np.random.default_rng(7)
    # Integer values 0..3: ties everywhere.
    x = jnp.asarray(rng.integers(0, 4, size=shape), jnp.float32)
    pool = Pool(kind="max", kernel_size=kernel, strides=stride, padding=padding)
    (ph, pw) = padding

    def weighted(y):
        return jnp.sum(y * jnp.cos(jnp.arange(y.size, dtype=y.dtype)).reshape(y.shape))

    def via_module(x):
        return weighted(pool.apply({}, x))

    def via_xla(x):
        return weighted(nn.max_pool(
            x, kernel, strides=stride, padding=((ph, ph), (pw, pw))))

    v1, g1 = jax.value_and_grad(via_module)(x)
    v2, g2 = jax.value_and_grad(via_xla)(x)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    # Gradient ROUTING must be identical; the only tolerated difference is
    # f32 summation order where several windows hit one input element
    # (~1e-7). A tie-breaking divergence would misroute whole dy values
    # (magnitude ~1) and fail this bound by 6 orders.
    np.testing.assert_allclose(
        np.asarray(g1), np.asarray(g2), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("strides", [2, 1])
def test_spatial_max_pool_value_and_gradient_match_plain(strides):
    """The spatial ``Pool`` on 2x2 tiles (halo exchange with the -inf fill,
    the window op, the trim) against the plain ``Pool`` on the whole image,
    on the same tie-heavy integers: value AND input gradient, strided
    (``select_and_scatter``) and stride 1 (the tree of maxima)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 4, size=(2, 16, 16, 3)), jnp.float32)
    pool_kw = dict(kind="max", kernel_size=3, strides=strides, padding=1)
    plain, sp = Pool(**pool_kw), Pool(**pool_kw, spatial=True)
    mesh = _mesh(2, 2)
    out = (2, 16 // strides, 16 // strides, 3)
    # Position-dependent weights of the WHOLE output, cut into tiles like
    # it: a mis-padded or mis-trimmed backward would route gradient to the
    # wrong inputs and diverge from the plain module at once.
    w = jnp.cos(jnp.arange(np.prod(out), dtype=jnp.float32)).reshape(out)

    def loss_plain(x):
        return jnp.sum(plain.apply({}, x) * w)

    @jax.jit
    def loss_spatial(x):
        def local(xt, wt):
            return jax.lax.psum(
                jnp.sum(sp.apply({}, xt) * wt), ("tile_h", "tile_w"))

        return shard_map(
            local, mesh=mesh, in_specs=(SPEC, SPEC), out_specs=P(),
            check_vma=False,
        )(x, w)

    v_sp, g_sp = jax.value_and_grad(loss_spatial)(x)
    v_pl, g_pl = jax.value_and_grad(loss_plain)(x)
    # the sum is taken tile by tile: f32 summation order, nothing more
    np.testing.assert_allclose(float(v_sp), float(v_pl), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(g_sp), np.asarray(g_pl), rtol=1e-6, atol=1e-6
    )


def test_batchnorm_gradients_match_the_closed_form():
    """``TrainBatchNorm``'s input, scale and bias gradients (through
    ``bn_moments``, as every model reaches it) against the closed form of
    batch normalization's backward, worked in float64 NumPy."""
    from mpi4dl_tpu.ops.layers import TrainBatchNorm

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 5)) * 2.0 + 0.5, jnp.float32)
    bn = TrainBatchNorm()
    params = {"params": {
        "scale": jnp.asarray(rng.uniform(0.5, 1.5, 5), jnp.float32),
        "bias": jnp.asarray(rng.standard_normal(5), jnp.float32),
    }}
    w = np.cos(np.arange(x.size, dtype=np.float64)).reshape(x.shape)

    def loss(params, x):
        return jnp.sum(bn.apply(params, x) * jnp.asarray(w, jnp.float32))

    gp, gx = jax.grad(loss, argnums=(0, 1))(params, x)

    x64 = np.asarray(x, np.float64)
    scale = np.asarray(params["params"]["scale"], np.float64)
    mean = x64.mean((0, 1, 2))
    inv = 1.0 / np.sqrt(x64.var((0, 1, 2)) + bn.eps)
    xhat = (x64 - mean) * inv
    want_x = scale * inv * (
        w - w.mean((0, 1, 2)) - xhat * (w * xhat).mean((0, 1, 2)))
    np.testing.assert_allclose(np.asarray(gx), want_x, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gp["params"]["scale"]), (w * xhat).sum((0, 1, 2)),
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(gp["params"]["bias"]), w.sum((0, 1, 2)), rtol=1e-4, atol=1e-4)


def test_spatial_window_coverage_check():
    """Spatial windowed ops whose halo can't cover cross-boundary windows
    must fail loudly instead of silently dropping windows."""
    mesh = _mesh(2, 2)
    x = jnp.zeros((1, 8, 8, 2), jnp.float32)
    for mod in (
        Conv2d(features=2, kernel_size=3, padding=0, spatial=True),
        Pool(kind="max", kernel_size=3, strides=2, padding=0, spatial=True),
    ):
        with pytest.raises(ValueError, match="cover tile-boundary windows"):
            fn = shard_map(
                lambda t, m=mod: m.apply({"params": {}}, t),
                mesh=mesh,
                in_specs=(SPEC,),
                out_specs=SPEC,
                check_vma=False,
            )
            jax.eval_shape(fn, jax.ShapeDtypeStruct(x.shape, x.dtype))


# -- decomposed halo/compute-overlap impl (ISSUE 9 tentpole) ------------------
# MPI4DL_TPU_CONV_OVERLAP=decomposed splits each spatial windowed op into
# an interior op (no halo dependency — overlappable with the ppermutes)
# plus boundary-strip ops on the exchanged tile (layers.overlap_decompose).
# The contract these tests pin: the stitched output is BIT-IDENTICAL to
# the monolithic exchange form on the CPU mesh (every output window sees
# exactly the same bytes and XLA's per-window reduction order does not
# change with the outer slicing here), so flipping the flag is a pure
# scheduling A/B, never a numerics A/B.


def _strip_bounds_ref(n, k, s, p):
    """Brute-force reference: which trimmed output rows have windows that
    stay inside the local tile."""
    n_out = n // s
    lo = sum(1 for i in range(n_out) if i * s - p < 0)
    hi = sum(1 for i in range(n_out) if i * s - p + k - 1 > n - 1)
    return lo, hi, n_out


@pytest.mark.parametrize(
    "n,k,s,p",
    [(8, 3, 1, 1), (8, 3, 2, 1), (8, 5, 1, 2), (16, 3, 2, 1),
     (4, 3, 1, 1), (2, 3, 1, 1), (8, 1, 1, 0), (8, 2, 2, 0)],
)
def test_strip_bounds_match_bruteforce(n, k, s, p):
    from mpi4dl_tpu.ops.layers import _strip_bounds

    assert _strip_bounds(n, k, s, p) == _strip_bounds_ref(n, k, s, p)


@pytest.mark.parametrize("th,tw", [(2, 2), (1, 4)])
@pytest.mark.parametrize("kernel,stride", [(3, 1), (3, 2), (5, 1)])
def test_decomposed_conv_bit_identical_to_monolithic(th, tw, kernel, stride):
    """Tier-1 equivalence (ISSUE satellite): interior+boundary stitching
    equals the monolithic halo_exchange+conv path bit-for-bit on the CPU
    mesh — square AND vertical grids, stride>1, global-boundary tiles
    (every tile of these grids touches the image boundary)."""
    mesh = _mesh(th, tw)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((2, 16, 16, 3)), dtype=jnp.float32)
    plain = Conv2d(features=8, kernel_size=kernel, strides=stride,
                   spatial=False)
    mono = Conv2d(features=8, kernel_size=kernel, strides=stride,
                  spatial=True, overlap="monolithic")
    dec = Conv2d(features=8, kernel_size=kernel, strides=stride,
                 spatial=True, overlap="decomposed")
    params = plain.init(jax.random.PRNGKey(0), x)
    out_m, golden = _run_distributed(mono, plain, x, mesh, params=params)
    out_d, _ = _run_distributed(dec, plain, x, mesh, params=params)
    np.testing.assert_array_equal(out_d, out_m)
    # And both equal the single-device golden (documented f32 tolerance —
    # the tiled conv may legally differ from the full-image one in
    # accumulation order, decomposed or not).
    np.testing.assert_allclose(out_d, golden, rtol=1e-5, atol=1e-5)


def test_decomposed_conv_env_selected_and_ones_exact(monkeypatch):
    """MPI4DL_TPU_CONV_OVERLAP=decomposed (the process-wide selector,
    overlap=None) on the reference-parity ones-weight integer case:
    exact integer equality against the plain golden."""
    monkeypatch.setenv("MPI4DL_TPU_CONV_OVERLAP", "decomposed")
    mesh = _mesh(2, 2)
    x = jnp.arange(1 * 8 * 8 * 2, dtype=jnp.float32).reshape(1, 8, 8, 2)
    plain = Conv2d(features=4, kernel_size=3, spatial=False)
    spatial = Conv2d(features=4, kernel_size=3, spatial=True)
    params = plain.init(jax.random.PRNGKey(0), x)
    params = jax.tree.map(lambda a: jnp.ones_like(a), params)
    out, golden = _run_distributed(spatial, plain, x, mesh, params=params)
    np.testing.assert_array_equal(out, golden)


def test_decomposed_conv_small_tile_falls_back_to_monolithic():
    """A tile too small for a non-empty interior (here 4x4 under a 5x5
    kernel: every output row needs the halo) must fall back to the
    monolithic path, not emit a degenerate stitch."""
    mesh = _mesh(2, 2)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 2)), dtype=jnp.float32)
    plain = Conv2d(features=4, kernel_size=5, spatial=False)
    dec = Conv2d(features=4, kernel_size=5, spatial=True,
                 overlap="decomposed")
    out, golden = _run_distributed(dec, plain, x, mesh)
    np.testing.assert_allclose(out, golden, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "kind,kernel,stride,padding",
    [("max", 3, 2, 1), ("max", 3, 1, 1), ("avg", 3, 2, 1)],
)
def test_decomposed_pool_bit_identical_to_monolithic(
    kind, kernel, stride, padding
):
    """Pooling variant of the decomposition, on ALL-NEGATIVE data so the
    -inf boundary fill is load-bearing at the global-boundary tiles (a
    zero-fill bug would win every boundary max)."""
    mesh = _mesh(2, 2)
    rng = np.random.default_rng(7)
    x = jnp.asarray(
        -np.abs(rng.standard_normal((2, 16, 16, 3))) - 1.0, jnp.float32
    )
    plain = Pool(kind=kind, kernel_size=kernel, strides=stride,
                 padding=padding)
    mono = Pool(kind=kind, kernel_size=kernel, strides=stride,
                padding=padding, spatial=True, overlap="monolithic")
    dec = Pool(kind=kind, kernel_size=kernel, strides=stride,
               padding=padding, spatial=True, overlap="decomposed")
    out_m, golden = _run_distributed(mono, plain, x, mesh)
    out_d, _ = _run_distributed(dec, plain, x, mesh)
    np.testing.assert_array_equal(out_d, out_m)
    np.testing.assert_allclose(out_d, golden, rtol=1e-6, atol=1e-6)


def test_decomposed_conv_gradients_match_monolithic():
    """The decomposition must be transparent to AD: parameter and input
    gradients through the stitched form match the monolithic form (the
    train step consumes this path, not just the forward)."""
    import functools as _ft

    mesh = _mesh(2, 2)
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 3)), dtype=jnp.float32)
    plain = Conv2d(features=4, kernel_size=3, strides=1, spatial=False)
    params = plain.init(jax.random.PRNGKey(0), x)

    def loss_fn(mod):
        @jax.jit
        @_ft.partial(
            shard_map, mesh=mesh, in_specs=(P(), SPEC), out_specs=(P(), SPEC),
            check_vma=False,
        )
        def run(p, t):
            def local(p, t):
                return jnp.sum(jnp.square(mod.apply(p, t)))

            (l, gp), gt = (
                jax.value_and_grad(local)(p, t),
                jax.grad(local, argnums=1)(p, t),
            )
            import jax.lax as _lax

            l = _lax.psum(l, ("tile_h", "tile_w"))
            gp = jax.tree.map(
                lambda a: _lax.psum(a, ("tile_h", "tile_w")), gp
            )
            return (l, gp), gt

        xs = jax.device_put(x, NamedSharding(mesh, SPEC))
        (l, gp), gt = run(params, xs)
        return float(l), gp, np.asarray(gt)

    from jax.sharding import NamedSharding

    l_m, gp_m, gt_m = loss_fn(
        Conv2d(features=4, kernel_size=3, strides=1, spatial=True,
               overlap="monolithic")
    )
    l_d, gp_d, gt_d = loss_fn(
        Conv2d(features=4, kernel_size=3, strides=1, spatial=True,
               overlap="decomposed")
    )
    np.testing.assert_allclose(l_d, l_m, rtol=1e-6)
    # Input gradients: the stitch's transpose accumulates halo-overlap
    # contributions (slice-transpose scatter-adds) in a different order
    # than the monolithic conv transpose — documented f32 tolerance, not
    # bit equality (the FORWARD is bit-identical; see the tests above).
    np.testing.assert_allclose(gt_d, gt_m, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        gp_d, gp_m,
    )
