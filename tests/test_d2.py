"""D2 (fused-halo) design tests: one wide halo exchange amortized over
``fused_layers`` shrink-conv cells must be bit-equivalent to the per-cell
(D1) exchange and to the plain single-device model — the property the
reference asserts only by construction (``resnet_spatial_d2.py``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi4dl_tpu.config import ParallelConfig
from mpi4dl_tpu.models.resnet import get_resnet_v2_d2
from mpi4dl_tpu.parallel.partition import init_cells
from mpi4dl_tpu.train import Trainer, TrainState, single_device_step


def _forward(cells, params, x):
    for c, p in zip(cells, params):
        x = c.apply(p, x)
    return x


@pytest.mark.parametrize("fused_layers", [2, 3])
def test_d2_front_matches_plain_forward(fused_layers):
    cells, plain, nsp = get_resnet_v2_d2(
        depth=20, spatial_cells=4, fused_layers=fused_layers
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
    params = init_cells(plain, jax.random.PRNGKey(0), x)
    golden = _forward(plain[:nsp], params[:nsp], x)

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("tile_h", "tile_w"))
    spec = P(None, "tile_h", "tile_w", None)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(), spec), out_specs=spec, check_vma=False
    )
    def dist(p, tile):
        return _forward(cells[:nsp], p, tile)

    out = dist(params[:nsp], jax.device_put(x, NamedSharding(mesh, spec)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(golden), rtol=2e-5, atol=2e-5)


def test_d2_trainer_step_matches_golden():
    """Full D2 training step (loss + grads via updated params) against the
    plain golden — covers the wide exchange, shrink convs, interior-masked
    cross-tile BN, and skip trimming under AD."""
    cfg = ParallelConfig(
        batch_size=2,
        split_size=1,
        spatial_size=1,
        num_spatial_parts=(4,),
        slice_method="square",
        image_size=32,
        halo_d2=True,
        fused_layers=2,
    )
    cells, plain, nsp = get_resnet_v2_d2(depth=20, spatial_cells=4, fused_layers=2)
    trainer = Trainer(cells, num_spatial_cells=nsp, config=cfg, plain_cells=plain)
    state = trainer.init(jax.random.PRNGKey(0), (2, 32, 32, 3))
    _, golden_step = single_device_step(plain)
    gp = jax.tree.map(jnp.copy, state.params)
    golden_state = TrainState(
        params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
    )
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((2, 32, 32, 3)), jnp.float32
    )
    y = jnp.asarray(np.random.default_rng(2).integers(0, 10, size=(2,)), jnp.int32)
    xs, ys = trainer.shard_batch(x, y)
    state, metrics = trainer.train_step(state, xs, ys)
    golden_state, golden_metrics = golden_step(golden_state, x, y)
    np.testing.assert_allclose(
        float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-5
    )
    jax.tree.map(
        lambda u, v: np.testing.assert_allclose(
            np.asarray(u), np.asarray(v), rtol=2e-4, atol=1e-5
        ),
        state.params,
        golden_state.params,
    )
