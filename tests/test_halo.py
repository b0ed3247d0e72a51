"""Halo-exchange golden tests.

TPU rebuild of the reference's de-facto integration test: a deterministic
``arange`` image is tiled across ranks, halos are exchanged, and each tile is
compared for integer equality against ``np.pad`` ground truth computed from
the full image (``benchmarks/communication/halo/benchmark_sp_halo_exchange.py:417-584``).
Here the "ranks" are virtual CPU mesh devices and comparison is exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from mpi4dl_tpu.parallel.halo import halo_exchange


def _mesh(th, tw):
    dev = np.asarray(jax.devices()[: th * tw]).reshape(th, tw)
    return Mesh(dev, ("tile_h", "tile_w"))


def _golden_tiles(image, th, tw, halo_h, halo_w, fill=0.0):
    """Expected halo'd tile per grid cell, from np.pad on the full image."""
    b, h, w, c = image.shape
    padded = np.pad(
        image, ((0, 0), (halo_h, halo_h), (halo_w, halo_w), (0, 0)),
        constant_values=fill,
    )
    hh, ww = h // th, w // tw
    out = {}
    for i in range(th):
        for j in range(tw):
            out[(i, j)] = padded[
                :,
                i * hh : i * hh + hh + 2 * halo_h,
                j * ww : j * ww + ww + 2 * halo_w,
                :,
            ]
    return out


@pytest.mark.parametrize(
    "th,tw,halo_h,halo_w,fill",
    [
        (2, 2, 1, 1, 0.0),  # square slicing, 3x3-kernel halo
        (2, 2, 3, 3, 0.0),  # square, halo_len=3 (7x7 kernel / D2 fused halo)
        (1, 4, 0, 2, 0.0),  # vertical slicing
        (4, 1, 2, 0, 0.0),  # horizontal slicing
        (2, 4, 1, 2, 0.0),  # rectangular grid, asymmetric halo
        (4, 1, 3, 0, 0.0),  # horizontal, wide halo
        (2, 2, 2, 2, -np.inf),  # the max pools' fill, corners via two hops
        (1, 4, 0, 2, -np.inf),  # vertical slicing under the pools' fill
    ],
)
def test_halo_exchange_matches_np_pad(th, tw, halo_h, halo_w, fill):
    rng = np.random.default_rng(0)
    b, h, w, c = 2, 16, 16, 3
    image = rng.integers(0, 1000, size=(b, h, w, c)).astype(np.float32)

    mesh = _mesh(th, tw)
    spec = P(None, "tile_h", "tile_w", None)

    fn = shard_map(
        lambda x: halo_exchange(x, halo_h, halo_w, fill_value=fill),
        mesh=mesh,
        in_specs=(spec,),
        out_specs=spec,
    )
    # Output tiles overlap, so gather per-tile results along a stacked axis
    # instead: run with out spec stacking tiles is awkward — instead fetch
    # the per-device shards directly.
    x = jax.device_put(jnp.asarray(image), NamedSharding(mesh, spec))
    y = jax.jit(fn)(x)

    golden = _golden_tiles(image, th, tw, halo_h, halo_w, fill)
    hh, ww = h // th, w // tw
    for shard in y.addressable_shards:
        # shard.index is the slice into the (overlapping) global result; use
        # device mesh position instead.
        pos = np.argwhere(mesh.devices == shard.device)
        assert pos.shape == (1, 2)
        i, j = map(int, pos[0])
        np.testing.assert_array_equal(np.asarray(shard.data), golden[(i, j)])


def test_halo_exchange_zero_halo_is_identity():
    mesh = _mesh(2, 2)
    spec = P(None, "tile_h", "tile_w", None)
    x = jnp.arange(2 * 8 * 8 * 1, dtype=jnp.float32).reshape(2, 8, 8, 1)
    fn = shard_map(
        lambda t: halo_exchange(t, 0, 0),
        mesh=mesh,
        in_specs=(spec,),
        out_specs=spec,
    )
    xs = jax.device_put(x, NamedSharding(mesh, spec))
    np.testing.assert_array_equal(np.asarray(jax.jit(fn)(xs)), np.asarray(x))
