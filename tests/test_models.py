"""Model zoo tests: shape parity and distributed-vs-sequential equivalence.

The reference's only model-level check is a runtime shape print
(``resnet_spatial.py:494-497``); here a spatially-partitioned ResNet running
on a virtual tile mesh must reproduce the plain single-device model's output
(cross-tile BN makes the distributed model bit-compatible with the golden)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi4dl_tpu.models.resnet import get_resnet_v1, get_resnet_v2
from mpi4dl_tpu.ops.layers import Sequential
from mpi4dl_tpu.utils import get_depth

SPEC = P(None, "tile_h", "tile_w", None)


def _mesh(th, tw):
    dev = np.asarray(jax.devices()[: th * tw]).reshape(th, tw)
    return Mesh(dev, ("tile_h", "tile_w"))


def test_get_depth_parity():
    # ref utils.py:26-30
    assert get_depth(1, 3) == 20
    assert get_depth(2, 6) == 56


@pytest.mark.parametrize(
    "version,n",
    [pytest.param(1, 2, marks=pytest.mark.slow),
     pytest.param(2, 2, marks=pytest.mark.slow)],
)
def test_resnet_shapes(version, n):
    depth = get_depth(version, n)
    cells = (get_resnet_v1 if version == 1 else get_resnet_v2)(depth, num_classes=10)
    model = Sequential(layers=cells)
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(params, x)
    assert out.shape == (2, 10)


@pytest.mark.parametrize("version", [1, 2])
def test_spatial_resnet_matches_plain(version):
    """All cells spatial, on a 2x2 tile mesh, vs plain golden (logits)."""
    builder = get_resnet_v1 if version == 1 else get_resnet_v2
    depth = get_depth(version, 2)
    plain_cells = builder(depth, num_classes=10, spatial_cells=0)
    n_cells = len(plain_cells)
    # spatial until the head (head is never spatial)
    spatial_cells = builder(depth, num_classes=10, spatial_cells=n_cells - 1)

    mesh = _mesh(2, 2)
    x = jnp.asarray(
        np.random.default_rng(3).standard_normal((2, 32, 32, 3)), jnp.float32
    )
    plain = Sequential(layers=plain_cells)
    params = plain.init(jax.random.PRNGKey(1), x)
    golden = plain.apply(params, x)

    spatial_model = Sequential(layers=spatial_cells[:-1])
    head = Sequential(layers=spatial_cells[-1:])

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), SPEC),
        out_specs=SPEC,
        check_vma=False,
    )
    def spatial_body(p, tile):
        # run the spatial trunk on the local tile
        return spatial_model.apply(p, tile)

    # param tree of Sequential is keyed layers_<i>; split trunk/head params
    # (head re-keyed to layers_0 since it's wrapped in its own Sequential)
    head_params = {
        "params": {"layers_0": params["params"][f"layers_{n_cells-1}"]}
    }
    trunk_params = {
        "params": {
            f"layers_{i}": params["params"][f"layers_{i}"] for i in range(n_cells - 1)
        }
    }

    xs = jax.device_put(x, NamedSharding(mesh, SPEC))
    feats = spatial_body(trunk_params, xs)  # sharded feature map
    # join: gather tiles (the reference's join-rank torch.cat merge,
    # train_spatial.py:1083-1188) — here just a resharding to replicated.
    feats_full = jax.device_get(feats)
    out = head.apply(head_params, jnp.asarray(feats_full))
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-4, atol=2e-4)
