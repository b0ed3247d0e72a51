"""AmoebaNet-D model family tests: architecture shape fixtures, spatial
forward parity, and tuple-valued ("MULTIPLE_INPUT/OUTPUT") stage interfaces
through the partitioner.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi4dl_tpu.models.amoebanet import amoebanetd
from mpi4dl_tpu.parallel.partition import init_cells, trace_shapes


def _forward(cells, params, x):
    h = x
    for c, p in zip(cells, params):
        h = c.apply(p, h)
    return h


def test_amoebanet_structure_and_shapes():
    """Cell count = 3r+6 + classify is num_layers//3 normal-cell triples with
    reductions between (ref builder ``amoebanet.py:535-615``); channel widths
    double at each reduction; final state concat width = channels * len(concat)."""
    cells = amoebanetd(num_classes=10, num_layers=3, num_filters=32)
    assert len(cells) == 9  # stem + 2 red + 3x(1 normal) + 2 red + classify
    shapes = trace_shapes(cells, split_size=1, input_shape=(2, 64, 64, 3))
    assert shapes[-1] == (2, 10)

    # Two-stage split produces a tuple wire (concat, skip) at the boundary.
    shapes2 = trace_shapes(cells, split_size=2, input_shape=(2, 64, 64, 3))
    boundary = shapes2[0]
    assert isinstance(boundary, tuple) and len(boundary) == 2
    assert all(len(s) == 4 for s in boundary)


def test_amoebanet_deeper_variant():
    cells = amoebanetd(num_classes=100, num_layers=6, num_filters=64)
    assert len(cells) == 12
    shapes = trace_shapes(cells, split_size=1, input_shape=(1, 64, 64, 3))
    assert shapes[-1] == (1, 100)


@pytest.mark.slow
@pytest.mark.parametrize("n_spatial", [3])
def test_amoebanet_spatial_forward_matches_plain(n_spatial):
    """Spatial cells (halo-exchange convs/pools, incl. the
    count_include_pad=False distributed avg pool and FactorizedReduce) must
    reproduce the plain model's activations on 2x2 tiles."""
    spatial_cells = amoebanetd(num_layers=3, num_filters=32, spatial_cells=n_spatial)
    plain_cells = amoebanetd(num_layers=3, num_filters=32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 64, 64, 3)), jnp.float32)
    params = init_cells(plain_cells, jax.random.PRNGKey(0), x)

    golden = _forward(plain_cells[:n_spatial], params[:n_spatial], x)

    dev = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(dev, ("tile_h", "tile_w"))
    spec = P(None, "tile_h", "tile_w", None)

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), spec),
        out_specs=spec,
        check_vma=False,
    )
    def dist(p, tile):
        return _forward(spatial_cells[:n_spatial], p, tile)

    xs = jax.device_put(x, NamedSharding(mesh, spec))
    out = dist(params[:n_spatial], xs)
    # Spatial cells emit (concat, skip) tuples — compare leaf-wise.
    jax.tree.map(
        lambda u, v: np.testing.assert_allclose(
            np.asarray(u), np.asarray(v), rtol=2e-5, atol=2e-5
        ),
        out,
        golden,
    )
