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


def _golden_tiles(image, th, tw, halo_h, halo_w):
    """Expected halo'd tile per grid cell, from np.pad on the full image."""
    b, h, w, c = image.shape
    padded = np.pad(
        image, ((0, 0), (halo_h, halo_h), (halo_w, halo_w), (0, 0))
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
    "th,tw,halo_h,halo_w",
    [
        (2, 2, 1, 1),  # square slicing, 3x3-kernel halo
        (2, 2, 3, 3),  # square, halo_len=3 (7x7 kernel / D2 fused halo)
        (1, 4, 0, 2),  # vertical slicing
        (4, 1, 2, 0),  # horizontal slicing
        (2, 4, 1, 2),  # rectangular grid, asymmetric halo
    ],
)
def test_halo_exchange_matches_np_pad(th, tw, halo_h, halo_w):
    rng = np.random.default_rng(0)
    b, h, w, c = 2, 16, 16, 3
    image = rng.integers(0, 1000, size=(b, h, w, c)).astype(np.float32)

    mesh = _mesh(th, tw)
    spec = P(None, "tile_h", "tile_w", None)

    fn = shard_map(
        lambda x: halo_exchange(x, halo_h, halo_w),
        mesh=mesh,
        in_specs=(spec,),
        out_specs=spec,
    )
    # Output tiles overlap, so gather per-tile results along a stacked axis
    # instead: run with out spec stacking tiles is awkward — instead fetch
    # the per-device shards directly.
    x = jax.device_put(jnp.asarray(image), NamedSharding(mesh, spec))
    y = jax.jit(fn)(x)

    golden = _golden_tiles(image, th, tw, halo_h, halo_w)
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


# -- Pallas->XLA downgrade warning (ISSUE satellite) --------------------------


def _exchange(x, mesh, **kw):
    spec = P(None, "tile_h", "tile_w", None)
    fn = jax.jit(shard_map(
        lambda t: halo_exchange(t, 1, 1, **kw),
        mesh=mesh, in_specs=(spec,), out_specs=spec,
    ))
    xs = jax.device_put(x, NamedSharding(mesh, spec))
    return np.asarray(fn(xs))


def test_explicit_pallas_under_xla_only_warns_once_and_is_correct():
    """ISSUE satellite: explicit ``impl="pallas"`` while the XLA-only
    guard is active downgrades with EXACTLY ONE warning per process — a
    54-cell model must not emit one warning per traced layer — and the
    downgraded output equals the XLA path's."""
    import warnings

    from mpi4dl_tpu.parallel import halo

    mesh = _mesh(2, 2)
    x = jnp.arange(2 * 8 * 8 * 2, dtype=jnp.float32).reshape(2, 8, 8, 2)
    halo._reset_pallas_downgrade_warning()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        with halo.xla_halo_only():
            got1 = _exchange(x, mesh, impl="pallas")
            # A second fresh trace in the same process: no second warning.
            got2 = _exchange(x + 1.0, mesh, impl="pallas")
    downgrades = [w for w in rec if "downgraded" in str(w.message)]
    assert len(downgrades) == 1, [str(w.message) for w in rec]
    ref = _exchange(x, mesh, impl="xla")
    np.testing.assert_array_equal(got1, ref)
    np.testing.assert_array_equal(
        got2, _exchange(x + 1.0, mesh, impl="xla")
    )


def test_env_selected_pallas_downgrades_silently(monkeypatch):
    """ISSUE satellite: MPI4DL_TPU_HALO_IMPL=pallas (no explicit impl=)
    under the XLA-only guard downgrades with NO warning — the env default
    is a preference, not a per-callsite promise — and stays correct."""
    import warnings

    from mpi4dl_tpu.parallel import halo

    monkeypatch.setenv("MPI4DL_TPU_HALO_IMPL", "pallas")
    mesh = _mesh(2, 2)
    x = jnp.arange(1 * 8 * 8 * 1, dtype=jnp.float32).reshape(1, 8, 8, 1)
    halo._reset_pallas_downgrade_warning()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        with halo.xla_halo_only():
            got = _exchange(x, mesh)
    assert [w for w in rec if "downgraded" in str(w.message)] == []
    monkeypatch.delenv("MPI4DL_TPU_HALO_IMPL")
    np.testing.assert_array_equal(got, _exchange(x, mesh, impl="xla"))
