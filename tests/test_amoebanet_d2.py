"""AmoebaNet-D D2 (fused-halo) tests: one wide exchange per cell input state
plus VALID ops with per-op crops (``AmoebaCellD2``) must reproduce the plain
single-device model bit-for-bit — the property the reference's
``amoebanet_d2.py`` asserts only by construction.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi4dl_tpu.models.amoebanet import (
    NORMAL_OPERATIONS,
    _plan_state_halos,
    amoebanetd,
)
from mpi4dl_tpu.parallel.partition import init_cells


def _forward(cells, params, x):
    h = x
    for c, p in zip(cells, params):
        h = c.apply(p, h)
    return h


def test_halo_plan_for_normal_genotype():
    """State 0 (s1) needs halo 3 (its 1x7-7x1 chains), state 1 (s2) needs
    halo 2 (max-pool chain through state 2); state 2 carries halo 1; concat
    states end at halo 0. The derived plan reproduces exactly the reference's
    hand-chosen exchange widths (s3_layer halo=3, s4_layer halo=2,
    ``amoebanet_d2.py:569-632``) — derived, not tabled."""
    halos = _plan_state_halos(NORMAL_OPERATIONS)
    assert halos[0] == 3 and halos[1] == 2
    assert halos[2] == 1
    assert halos[3:] == [0, 0, 0, 0]


@pytest.mark.parametrize("n_spatial", [4])
def test_amoebanet_d2_forward_matches_plain(n_spatial):
    """D2 spatial front (stem + 2 reduction cells D1 + 1 fused-halo normal
    cell) == plain model activations on 2x2 tiles. Covers wide exchange,
    VALID 1x7/7x1 chains, crops, boundary-ring refill, interior-masked BN,
    and the D2 max/avg pools."""
    d2_cells = amoebanetd(
        num_layers=3, num_filters=32, spatial_cells=n_spatial, halo_d2=True
    )
    plain_cells = amoebanetd(num_layers=3, num_filters=32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 128, 128, 3)), jnp.float32)
    params = init_cells(plain_cells, jax.random.PRNGKey(0), x)

    golden = _forward(plain_cells[:n_spatial], params[:n_spatial], x)

    dev = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(dev, ("tile_h", "tile_w"))
    spec = P(None, "tile_h", "tile_w", None)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(), spec), out_specs=spec, check_vma=False
    )
    def dist(p, tile):
        return _forward(d2_cells[:n_spatial], p, tile)

    xs = jax.device_put(x, NamedSharding(mesh, spec))
    out = dist(params[:n_spatial], xs)
    # Tolerance: interior-masked BN statistics sum in a different order than
    # the plain model's full-tile reduction; the residue is pure float
    # accumulation noise (observed max ~8e-5), far below any structural
    # halo/boundary error (order 1).
    jax.tree.map(
        lambda u, v: np.testing.assert_allclose(
            np.asarray(u), np.asarray(v), rtol=1e-3, atol=3e-4
        ),
        out,
        golden,
    )


@pytest.mark.slow
def test_amoebanet_d2_gradients_match_plain():
    """Gradient parity through the D2 cell (crops, custom boundary fills and
    interior-masked BN all under AD)."""
    n_spatial = 4
    d2_cells = amoebanetd(
        num_layers=3, num_filters=16, spatial_cells=n_spatial, halo_d2=True
    )
    plain_cells = amoebanetd(num_layers=3, num_filters=16)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((1, 128, 128, 3)), jnp.float32)
    params = init_cells(plain_cells, jax.random.PRNGKey(1), x)
    front_params = params[:n_spatial]

    def loss_plain(p):
        out = _forward(plain_cells[:n_spatial], p, x)
        return sum(jnp.sum(l * l) for l in jax.tree.leaves(out))

    g_plain = jax.jit(jax.grad(loss_plain))(front_params)

    dev = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(dev, ("tile_h", "tile_w"))
    spec = P(None, "tile_h", "tile_w", None)

    @jax.jit
    @jax.grad
    def g_d2_fn(p):
        from jax import lax

        def local(p, tile):
            out = _forward(d2_cells[:n_spatial], p, tile)
            return lax.psum(
                sum(jnp.sum(l * l) for l in jax.tree.leaves(out)),
                ("tile_h", "tile_w"),
            )

        fn = shard_map(
            local, mesh=mesh, in_specs=(P(), spec), out_specs=P(), check_vma=False
        )
        return fn(p, jax.device_put(x, NamedSharding(mesh, spec)))

    g_d2 = g_d2_fn(front_params)

    # Tolerance scaled to the global gradient magnitude: the sum-of-squares
    # loss routes ~1e2-magnitude cotangents everywhere, so leaves whose true
    # gradient is a near-cancelled sum (BN biases: sum of zero-mean
    # cotangents) have float noise set by the cotangent scale, not their own
    # value — per-element rtol there flags pure noise. Structural halo bugs
    # diverge at the cotangent scale and are still caught.
    global_scale = max(
        float(np.max(np.abs(np.asarray(l)))) for l in jax.tree.leaves(g_plain)
    )

    def check(u, v):
        np.testing.assert_allclose(
            np.asarray(u), np.asarray(v), rtol=2e-3, atol=2e-4 * global_scale
        )

    jax.tree.map(check, g_d2, g_plain)
