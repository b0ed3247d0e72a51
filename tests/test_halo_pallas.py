"""Pallas halo-exchange kernel vs the XLA (ppermute) path.

The kernel (``mpi4dl_tpu/ops/halo_pallas.py``) runs under the Pallas TPU
interpreter on the CPU test mesh; forward output and input gradients must be
bit-identical to the XLA implementation (which the golden ``np.pad`` suite in
``test_halo.py`` already pins to single-device semantics).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from mpi4dl_tpu.ops import halo_pallas
from mpi4dl_tpu.parallel.halo import halo_exchange

SPEC = P(None, "tile_h", "tile_w", None)


def _mesh(th, tw):
    dev = np.asarray(jax.devices()[: th * tw]).reshape(th, tw)
    return Mesh(dev, ("tile_h", "tile_w"))


def _run(mesh, image, halo_h, halo_w, impl, fill=0.0):
    fn = shard_map(
        lambda x: halo_exchange(x, halo_h, halo_w, fill_value=fill, impl=impl),
        mesh=mesh,
        in_specs=(SPEC,),
        out_specs=SPEC,
        check_vma=False,
    )
    x = jax.device_put(jnp.asarray(image), NamedSharding(mesh, SPEC))
    y = jax.jit(fn)(x)
    return {
        tuple(map(int, np.argwhere(mesh.devices == s.device)[0])): np.asarray(s.data)
        for s in y.addressable_shards
    }


@pytest.mark.parametrize(
    "th,tw,halo_h,halo_w,fill",
    [
        (2, 2, 1, 1, 0.0),  # square slicing, corners via two-phase
        (2, 2, 2, 2, -np.inf),  # max-pool fill value
        (1, 4, 0, 2, 0.0),  # vertical slicing
        (4, 1, 3, 0, 0.0),  # horizontal, wide halo
    ],
)
def test_pallas_matches_xla_forward(th, tw, halo_h, halo_w, fill):
    rng = np.random.default_rng(1)
    image = rng.integers(0, 1000, size=(2, 16, 16, 3)).astype(np.float32)
    mesh = _mesh(th, tw)
    ref = _run(mesh, image, halo_h, halo_w, "xla", fill)
    got = _run(mesh, image, halo_h, halo_w, "pallas", fill)
    assert ref.keys() == got.keys()
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k])


@pytest.mark.parametrize("th,tw,halo_h,halo_w", [(2, 2, 1, 1), (1, 4, 0, 2)])
def test_pallas_gradient_matches_xla(th, tw, halo_h, halo_w):
    """custom_vjp of the strip-swap kernel == AD of the ppermute path."""
    rng = np.random.default_rng(2)
    image = rng.standard_normal((1, 8, 8, 2)).astype(np.float32)
    mesh = _mesh(th, tw)

    def make_loss(impl):
        def local(x):
            ext = halo_exchange(x, halo_h, halo_w, impl=impl)
            # Nontrivial reduction touching halo and interior differently.
            w = jnp.arange(ext.size, dtype=jnp.float32).reshape(ext.shape)
            from jax import lax

            return lax.psum(jnp.sum(ext * w), ("tile_h", "tile_w"))

        fn = shard_map(
            local,
            mesh=mesh,
            in_specs=(SPEC,),
            out_specs=P(),
            check_vma=False,
        )
        return lambda x: fn(x)

    x = jax.device_put(jnp.asarray(image), NamedSharding(mesh, SPEC))
    g_ref = jax.jit(jax.grad(make_loss("xla")))(x)
    g_pal = jax.jit(jax.grad(make_loss("pallas")))(x)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref), rtol=0, atol=0)
