"""End-to-end trainer parity: distributed SP(+DP) training step must match
the single-device golden step bit-for-bit (up to f32 reduction order).

This covers what the reference can only check by eyeballing loss curves on a
real GPU+MPI cluster: loss value, gradient correctness (via updated params),
and optimizer semantics under spatial tiling + data parallelism.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.config import ParallelConfig
from mpi4dl_tpu.models.resnet import get_resnet_v1
from mpi4dl_tpu.ops.layers import Conv2d, Dense, Pool
from mpi4dl_tpu.train import Trainer, TrainState, single_device_step


def _batch(b=4, size=32, classes=10, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((b, size, size, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, classes, size=(b,)), jnp.int32)
    return x, y


def _assert_tree_close(a, b, **kw):
    jax.tree.map(
        lambda u, v: np.testing.assert_allclose(np.asarray(u), np.asarray(v), **kw),
        a,
        b,
    )


@pytest.mark.parametrize(
    "slice_method,parts",
    [("square", 4), pytest.param("vertical", 4, marks=pytest.mark.slow)],
)
def test_resnet_spatial_trainer_matches_single_device(slice_method, parts):
    cfg = ParallelConfig(
        batch_size=4,
        split_size=1,
        spatial_size=1,
        num_spatial_parts=(parts,),
        slice_method=slice_method,
        image_size=32,
        data_parallel=1,
    )
    spatial = get_resnet_v1(depth=8, spatial_cells=3, cross_tile_bn=True)
    plain = get_resnet_v1(depth=8, spatial_cells=0)
    trainer = Trainer(spatial, num_spatial_cells=3, config=cfg, plain_cells=plain)

    state = trainer.init(jax.random.PRNGKey(0), (4, 32, 32, 3))
    _, golden_step = single_device_step(plain)
    gp = jax.tree.map(jnp.copy, state.params)  # trainer donates its state
    golden_state = TrainState(
        params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
    )

    x, y = _batch()
    for seed in (1, 2):
        xs, ys = trainer.shard_batch(x, y)
        state, metrics = trainer.train_step(state, xs, ys)
        golden_state, golden_metrics = golden_step(golden_state, x, y)
        np.testing.assert_allclose(
            float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-5
        )
        np.testing.assert_allclose(
            float(metrics["accuracy"]), float(golden_metrics["accuracy"]), rtol=1e-6
        )
        x, y = _batch(seed=seed + 10)
    _assert_tree_close(state.params, golden_state.params, rtol=2e-4, atol=1e-5)


@pytest.mark.slow
def test_dp_plus_sp_trainer_matches_golden():
    """DP=2 × 2×2 tiles (all 8 virtual devices). BN-free cells so per-shard
    batch statistics can't mask a gradient-reduction bug."""
    cfg = ParallelConfig(
        batch_size=8,
        split_size=1,
        spatial_size=1,
        num_spatial_parts=(4,),
        slice_method="square",
        image_size=16,
        num_classes=10,
        data_parallel=2,
    )

    def build(spatial):
        return [
            Conv2d(features=8, kernel_size=3, spatial=spatial),
            Pool(kind="max", kernel_size=2, spatial=spatial),
            Conv2d(features=16, kernel_size=3, strides=2, spatial=spatial),
            Dense(10),
        ]

    spatial_cells, plain_cells = build(True), build(False)
    trainer = Trainer(spatial_cells, num_spatial_cells=3, config=cfg, plain_cells=plain_cells)
    state = trainer.init(jax.random.PRNGKey(1), (8, 16, 16, 3))
    _, golden_step = single_device_step(plain_cells)
    gp = jax.tree.map(jnp.copy, state.params)
    golden_state = TrainState(
        params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
    )

    x, y = _batch(b=8, size=16)
    xs, ys = trainer.shard_batch(x, y)
    state, metrics = trainer.train_step(state, xs, ys)
    golden_state, golden_metrics = golden_step(golden_state, x, y)
    np.testing.assert_allclose(
        float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-5
    )
    _assert_tree_close(state.params, golden_state.params, rtol=1e-4, atol=1e-6)


def test_pure_dp_no_spatial():
    """spatial_size=0 → batch-sharded only; mesh tile axes collapse to 1."""
    cfg = ParallelConfig(batch_size=8, split_size=1, spatial_size=0, data_parallel=4)
    cells = [Conv2d(features=4, kernel_size=3), Dense(10)]
    trainer = Trainer(cells, num_spatial_cells=0, config=cfg)
    state = trainer.init(jax.random.PRNGKey(2), (8, 8, 8, 3))
    _, golden_step = single_device_step(cells)
    gp = jax.tree.map(jnp.copy, state.params)
    golden_state = TrainState(
        params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
    )
    x, y = _batch(b=8, size=8)
    xs, ys = trainer.shard_batch(x, y)
    state, metrics = trainer.train_step(state, xs, ys)
    golden_state, golden_metrics = golden_step(golden_state, x, y)
    np.testing.assert_allclose(
        float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-5
    )
    _assert_tree_close(state.params, golden_state.params, rtol=1e-4, atol=1e-6)


def test_scan2_nested_remat_matches_golden(remat="scan2"):
    """The "scan2" policy (two-level checkpointing inside scan runs) and
    the "scanlog" policy (whole-model logarithmic recursion — the deepest-
    memory tier, ≥3072px) are pure scheduling choices: depth-44 gives
    7-cell runs, exercising BOTH scan2's chunked outer scan (g=3, m=2) and
    its remainder head-chunk path (rem=1), and odd left/right splits in
    scanlog's recursion; depth-20's 3-cell runs (below scan2's nesting
    threshold) are covered by the "scan" parametrization below."""
    cells = get_resnet_v1(depth=44)
    cfg = ParallelConfig(batch_size=2, split_size=1, spatial_size=0, image_size=32)
    trainer = Trainer(cells, num_spatial_cells=0, config=cfg, remat=remat)
    state = trainer.init(jax.random.PRNGKey(3), (2, 32, 32, 3))
    _, golden_step = single_device_step(cells)
    gp = jax.tree.map(jnp.copy, state.params)
    golden_state = TrainState(
        params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
    )
    x, y = _batch(b=2, size=32)
    for seed in (1, 2):
        xs, ys = trainer.shard_batch(x, y)
        state, metrics = trainer.train_step(state, xs, ys)
        golden_state, golden_metrics = golden_step(golden_state, x, y)
        np.testing.assert_allclose(
            float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-5
        )
        x, y = _batch(b=2, size=32, seed=seed + 20)
    _assert_tree_close(state.params, golden_state.params, rtol=2e-4, atol=1e-5)


def test_scanlog_matches_golden():
    test_scan2_nested_remat_matches_golden(remat="scanlog")


def test_scanq_matches_golden():
    """"scanq" (anchored-quadratic run backward, chain_quadratic): pure
    scheduling — depth-44's 7-cell runs exercise the masked recompute
    sweep and the per-cell vjp accumulation. The n=3 gate edge (depth-20's
    3-cell runs) is covered by the slow-tier
    ``test_remat_policies_match_golden[scanq]``."""
    test_scan2_nested_remat_matches_golden(remat="scanq")


def test_scanq_store_budget_matches_golden(monkeypatch):
    """MPI4DL_TPU_SCANQ_STORE_MB grants runs the plain stored-carry scan
    BACK-TO-FRONT until the budget runs out (the late stages free their
    carries before the early stages' backward runs — the safe grants);
    the rest stay anchored — a storage-placement choice only: numerics
    must equal the golden step. Re-pinned for ISSUE 10's grant-order fix
    (was front-to-back, the opposite of the docstring's own rationale):
    a 1 MB budget now covers depth-44's LATER stage runs (per-stage
    compact-carry bytes roughly halve stage over stage) and denies the
    ~0.92 MB first run, still exercising BOTH paths in one trace."""
    monkeypatch.setenv("MPI4DL_TPU_SCANQ_STORE_MB", "1")
    test_scan2_nested_remat_matches_golden(remat="scanq")


def test_scanq_store_budget_grants_back_to_front(monkeypatch):
    """ISSUE 10 satellite (ADVICE-r5): the store budget must go to the
    LATEST fitting runs — they free their carries before the early runs'
    backward executes — not be consumed front-to-back. Pure unit: a
    stub plan of three equal-size eligible runs and a budget that covers
    exactly two must grant the LAST TWO and deny the first. (The golden
    tests can't pin this: grant order is numerics-neutral.)"""
    import types

    monkeypatch.setenv("MPI4DL_TPU_SCANQ_STORE_MB", "0.0024")  # 2400 B

    ident = types.SimpleNamespace(apply=lambda p, h: h)
    stub = types.SimpleNamespace(
        _scan_plan=[[0, 1, 2], [3, 4, 5], [6, 7, 8]],
        _scan_plan_key=("k",),
        _at_join=lambda i, h: h,
        cells={i: ident for i in range(9)},
    )
    x = jnp.zeros((100,), jnp.float32)  # 400 B carry; 1200 B per run
    params = {i: {} for i in range(9)}
    granted = {
        run[0]: Trainer._scanq_store_granted(stub, run, params, x)
        for run in stub._scan_plan
    }
    assert granted == {0: False, 3: True, 6: True}
    # Grant bytes recorded for the remat-effectiveness rule, per run.
    assert stub._scanq_grant_bytes == {3: 1200, 6: 1200}
    assert stub._scanq_budget_left == pytest.approx(0.0)


def test_scan2_offload_matches_golden(monkeypatch):
    """MPI4DL_TPU_SCAN2_OFFLOAD=1 moves scan2's outer chunk boundaries to
    pinned host memory between forward and backward (the ≥4096px HBM
    lever) — a pure storage-placement choice: numerics must equal the
    on-device scan2 run and the golden step."""
    monkeypatch.setenv("MPI4DL_TPU_SCAN2_OFFLOAD", "1")
    test_scan2_nested_remat_matches_golden()


@pytest.mark.slow
@pytest.mark.parametrize(
    "remat",
    ["cell", "sqrt", "scan", "scan2", "scanlog", "scanq", "scan_save",
     "group_save"],
)
def test_remat_policies_match_golden(remat):
    """Every remat policy is a pure scheduling choice: losses, metrics, and
    updated parameters must be identical to the no-remat golden step. "scan"
    additionally rewrites repeated cells into a stacked-parameter lax.scan
    with compact [B, H, W*C] carries — still bit-equivalent."""
    cells = get_resnet_v1(depth=20)  # 3 stages x 3 repeated blocks → scannable runs
    cfg = ParallelConfig(batch_size=4, split_size=1, spatial_size=0, image_size=32)
    trainer = Trainer(cells, num_spatial_cells=0, config=cfg, remat=remat)
    state = trainer.init(jax.random.PRNGKey(3), (4, 32, 32, 3))
    _, golden_step = single_device_step(cells)
    gp = jax.tree.map(jnp.copy, state.params)
    golden_state = TrainState(
        params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
    )
    x, y = _batch(b=4, size=32)
    for seed in (1, 2):
        xs, ys = trainer.shard_batch(x, y)
        state, metrics = trainer.train_step(state, xs, ys)
        golden_state, golden_metrics = golden_step(golden_state, x, y)
        np.testing.assert_allclose(
            float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-5
        )
        x, y = _batch(b=4, size=32, seed=seed + 20)
    _assert_tree_close(state.params, golden_state.params, rtol=2e-4, atol=1e-5)


@pytest.mark.slow
def test_scan_unroll_matches_golden(monkeypatch):
    """MPI4DL_TPU_SCAN_UNROLL amortizes scan machinery without changing
    numerics: an unrolled scan run must equal the no-remat golden exactly
    like unroll=1 does (unroll=2 on a 3-cell run also covers the remainder
    handling)."""
    monkeypatch.setenv("MPI4DL_TPU_SCAN_UNROLL", "2")
    test_remat_policies_match_golden("scan_save")


@pytest.mark.slow
def test_scan_remat_spatial_matches_golden():
    """The "scan" policy composes with a spatial front: runs never span the
    SP→LP join and spatial (halo-exchanging) repeated cells scan inside
    shard_map."""
    cfg = ParallelConfig(
        batch_size=4,
        split_size=1,
        spatial_size=1,
        num_spatial_parts=(4,),
        slice_method="square",
        image_size=32,
    )
    spatial = get_resnet_v1(depth=14, spatial_cells=5, cross_tile_bn=True)
    plain = get_resnet_v1(depth=14, spatial_cells=0)
    trainer = Trainer(
        spatial, num_spatial_cells=5, config=cfg, plain_cells=plain, remat="scan"
    )
    state = trainer.init(jax.random.PRNGKey(4), (4, 32, 32, 3))
    _, golden_step = single_device_step(plain)
    gp = jax.tree.map(jnp.copy, state.params)
    golden_state = TrainState(
        params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
    )
    x, y = _batch(b=4, size=32)
    xs, ys = trainer.shard_batch(x, y)
    state, metrics = trainer.train_step(state, xs, ys)
    golden_state, golden_metrics = golden_step(golden_state, x, y)
    np.testing.assert_allclose(
        float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-5
    )
    _assert_tree_close(state.params, golden_state.params, rtol=2e-4, atol=1e-5)


def test_local_dp_without_lp_stage_rejected():
    """--local-DP configs with no LP stage after the spatial front used to
    route to the non-pipeline Trainer, which silently ignored the flag
    (round-1 VERDICT weak #6). The config must now fail loudly."""
    import pytest

    from mpi4dl_tpu.config import ParallelConfig

    with pytest.raises(ValueError, match="LP stage"):
        ParallelConfig(
            batch_size=8,
            split_size=1,
            spatial_size=1,
            num_spatial_parts=(4,),
            image_size=32,
            local_dp=4,
        )


@pytest.mark.slow
@pytest.mark.parametrize(
    "num_filters",
    [32, pytest.param(288, marks=pytest.mark.slow)],  # 288F: ~4 min on CPU
)
def test_scan_remat_amoebanet_tuple_state_matches_golden(num_filters):
    """The "scan" planner accepts pytree (tuple-state) fixed points: an
    AmoebaNet run of identical normal cells rewrites into one stacked-param
    lax.scan whose carry is the ``(concat, skip)`` tuple — round-1 VERDICT
    weak: the planner only accepted single tensors, so AmoebaNet degenerated
    to per-cell checkpointing.

    num_filters=288 puts every carry leaf past the 64-channel pad-tax
    boundary, so the scan runs with 4-D (un-flattened) carries — the
    branch of ``Trainer._compact`` that real AmoebaNet-D (416F) takes by
    default since the round-4 conditional flatten (review finding: the
    32F case flattens every leaf, leaving the pass-through path covered
    only by on-TPU benches).

    Comparison is loss + one-step GRADIENTS at relative tolerance, not
    multi-step parameters: an untrained AmoebaNet's input-side gradients
    reach ~1e7 (measured), so the f32 reassociation noise between the
    scanned and per-cell schedules amplifies chaotically across update
    steps and makes multi-step bitwise-style comparison meaningless for
    this model. 64px keeps the last stage at 2x2 spatial — at 32px it
    degenerates to 1x1 (every windowed op all-padding), where the
    conditioning makes even same-math program pairs diverge visibly."""
    from mpi4dl_tpu.models.amoebanet import amoebanetd

    cells = amoebanetd(num_classes=10, num_layers=12, num_filters=num_filters)
    cfg = ParallelConfig(batch_size=2, split_size=1, spatial_size=0, image_size=64)
    trainer = Trainer(cells, num_spatial_cells=0, config=cfg, remat="scan")
    state = trainer.init(jax.random.PRNGKey(5), (2, 32, 32, 3))
    # The plan must contain at least one multi-cell (scanned) run.
    plan = trainer._plan_scan_runs(state.params, jnp.zeros((2, 32, 32, 3)))
    assert any(len(r) > 1 for r in plan), plan

    golden = Trainer(cells, num_spatial_cells=0, config=cfg, remat=False)
    x, y = _batch(b=2, size=64)
    xs, ys = trainer.shard_batch(x, y)

    def loss_and_grad(tr):
        val, g = jax.jit(
            jax.value_and_grad(lambda p: tr._sharded_loss(p, xs, ys)[0])
        )(state.params)
        return float(val), jax.tree.map(np.asarray, g)

    loss_s, grad_s = loss_and_grad(trainer)
    loss_g, grad_g = loss_and_grad(golden)
    np.testing.assert_allclose(loss_s, loss_g, rtol=1e-6)
    for gs, gg in zip(grad_s, grad_g):
        for u, v in zip(jax.tree.leaves(gs), jax.tree.leaves(gg)):
            scale = max(float(np.max(np.abs(v))), 1e-6)
            np.testing.assert_allclose(u / scale, v / scale, atol=3e-4)


@pytest.mark.slow
@pytest.mark.parametrize("remat", [False, "scan_save"])
def test_packed_layout_matches_golden(remat):
    """The persistently-packed activation layout (ops/packed.py) is a pure
    layout change: same parameter tree, same math (mod f32 accumulation
    order) — train steps must match the stock NHWC golden."""

    from mpi4dl_tpu.models.resnet import get_resnet_v2

    # depth 29 → 3 blocks/stage → the 2 trailing identical cells form a
    # scannable run (depth 20 has only 2 blocks: block0 differs, no runs).
    kw = dict(depth=29 if remat == "scan_save" else 20, num_classes=10, pool_kernel=8)
    packed = get_resnet_v2(layout="packed", **kw)
    stock = get_resnet_v2(**kw)
    cfg = ParallelConfig(batch_size=4, split_size=1, spatial_size=0, image_size=32)
    trainer = Trainer(packed, num_spatial_cells=0, config=cfg, remat=remat)
    state = trainer.init(jax.random.PRNGKey(7), (4, 32, 32, 3))
    if remat == "scan_save":
        plan = trainer._plan_scan_runs(state.params, jnp.zeros((4, 32, 32, 3)))
        assert any(len(r) > 1 for r in plan), plan  # packed cells still scan
    _, golden_step = single_device_step(stock)
    gp = jax.tree.map(jnp.copy, state.params)
    golden_state = TrainState(
        params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
    )
    x, y = _batch(b=4, size=32)
    for seed in (1, 2):
        xs, ys = trainer.shard_batch(x, y)
        state, metrics = trainer.train_step(state, xs, ys)
        golden_state, golden_metrics = golden_step(golden_state, x, y)
        np.testing.assert_allclose(
            float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-4
        )
        x, y = _batch(b=4, size=32, seed=seed + 30)
    _assert_tree_close(state.params, golden_state.params, rtol=5e-3, atol=1e-4)


@pytest.mark.slow
def test_packed_spatial_matches_golden():
    """Packed layout under spatial partitioning (round-2 VERDICT #4): the
    packed conv's zero-pad columns become halo-exchanged packed columns
    (``conv2d_packed`` spatial mode) — the distributed packed train step
    must match the single-device stock-NHWC golden like the plain spatial
    trainer does."""

    from mpi4dl_tpu.models.resnet import get_resnet_v2

    kw = dict(depth=20, num_classes=10, pool_kernel=8)
    plain = get_resnet_v2(**kw)
    n_sp = len(plain) - 1  # every cell but the head runs on 2x2 tiles
    packed_sp = get_resnet_v2(layout="packed", spatial_cells=n_sp, **kw)
    cfg = ParallelConfig(
        batch_size=4,
        split_size=1,
        spatial_size=1,
        num_spatial_parts=(4,),
        slice_method="square",
        image_size=32,
    )
    trainer = Trainer(
        packed_sp, num_spatial_cells=n_sp, config=cfg, plain_cells=plain
    )
    state = trainer.init(jax.random.PRNGKey(7), (4, 32, 32, 3))
    _, golden_step = single_device_step(plain)
    gp = jax.tree.map(jnp.copy, state.params)
    golden_state = TrainState(
        params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
    )
    x, y = _batch(b=4, size=32)
    for seed in (1, 2):
        xs, ys = trainer.shard_batch(x, y)
        state, metrics = trainer.train_step(state, xs, ys)
        golden_state, golden_metrics = golden_step(golden_state, x, y)
        np.testing.assert_allclose(
            float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-4
        )
        x, y = _batch(b=4, size=32, seed=seed + 30)
    _assert_tree_close(state.params, golden_state.params, rtol=5e-3, atol=1e-4)


@pytest.mark.parametrize(
    "accum", [pytest.param(2, marks=pytest.mark.slow), 4]
)
def test_grad_accum_matches_golden(accum):
    """grad_accum=k applies the MEAN of k per-chunk gradients in one
    update, each chunk a batch-of-B/k forward (own BN statistics — the
    reference's GEMS --times chunk semantics, gems_master.py:72-103).
    Golden: explicit per-chunk value_and_grad + one SGD-momentum update."""
    import optax

    from mpi4dl_tpu.train import apply_cells, cross_entropy_sum, make_optimizer

    cells = get_resnet_v1(depth=8)
    cfg = ParallelConfig(batch_size=4, split_size=1, spatial_size=0, image_size=32)
    trainer = Trainer(
        cells, num_spatial_cells=0, config=cfg, grad_accum=accum
    )
    state = trainer.init(jax.random.PRNGKey(5), (4, 32, 32, 3))
    params0 = jax.tree.map(jnp.copy, state.params)
    x, y = _batch(b=4, size=32)
    xs, ys = trainer.shard_batch(x, y)
    state, metrics = trainer.train_step(state, xs, ys)

    def chunk_loss(params, xc, yc):
        logits = apply_cells(cells, params, xc)
        return cross_entropy_sum(logits, yc) / xc.shape[0]

    b = 4 // accum
    losses, grads = [], []
    for i in range(accum):
        l, g = jax.value_and_grad(chunk_loss)(
            params0, x[i * b : (i + 1) * b], y[i * b : (i + 1) * b]
        )
        losses.append(l)
        grads.append(g)
    mean_grads = jax.tree.map(lambda *gs: sum(gs) / accum, *grads)
    tx = make_optimizer()
    updates, _ = tx.update(mean_grads, tx.init(params0), params0)
    want_params = optax.apply_updates(params0, updates)

    np.testing.assert_allclose(
        float(metrics["loss"]), float(sum(losses) / accum), rtol=1e-5
    )
    _assert_tree_close(state.params, want_params, rtol=1e-4, atol=1e-6)


def test_dp_times_grad_accum_matches_unchunked_dp():
    """DP=2 × grad_accum=2 == unchunked DP=2 (parameter equality; BN-free
    cells so per-chunk batch statistics can't mask a reduction bug — with
    linear loss normalization, mean-of-chunk-grads equals the full-batch
    gradient exactly). Also pins what the chunk reshape EMITS on a
    DP-sharded batch (train.py ``_accum_grads`` caveat): each contiguous
    chunk lives on one device, so feeding it back through the
    batch-sharded loss inserts exactly one resharding ``all-to-all`` per
    input (x and y — 2 total), and the unchunked step has none. A change
    that doubles the resharding traffic fails here. Measured cost note in
    docs/PERF.md round 5."""
    from mpi4dl_tpu.analysis import collective_inventory as _inventory

    def build():
        return [
            Conv2d(features=8, kernel_size=3),
            Pool(kind="max", kernel_size=2),
            Conv2d(features=16, kernel_size=3, strides=2),
            Dense(10),
        ]

    cfg = ParallelConfig(
        batch_size=8, split_size=1, spatial_size=0, image_size=16,
        data_parallel=2,
    )
    x, y = _batch(b=8, size=16)
    states, hlos = [], []
    for accum in (1, 2):
        trainer = Trainer(
            build(), num_spatial_cells=0, config=cfg, grad_accum=accum
        )
        state = trainer.init(jax.random.PRNGKey(3), (8, 16, 16, 3))
        xs, ys = trainer.shard_batch(x, y)
        hlos.append(trainer._jit_step.lower(state, xs, ys).compile().as_text())
        state, metrics = trainer.train_step(state, xs, ys)
        states.append((jax.device_get(state.params), float(metrics["loss"])))

    (p1, l1), (p2, l2) = states
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    _assert_tree_close(p1, p2, rtol=1e-4, atol=1e-6)

    inv1, inv2 = _inventory(hlos[0]), _inventory(hlos[1])
    assert inv1["all-to-all"] == 0
    assert inv2["all-to-all"] == 2, (
        "DP x grad_accum chunk resharding changed: expected one all-to-all "
        f"per input (x, y), got {inv2}"
    )
    # Both steps reduce gradients the same way (psum-of-contributions);
    # chunking must not multiply gradient reductions.
    assert inv1["all-reduce"] == inv2["all-reduce"]


def test_grad_accum_rejects_indivisible_batch():
    cells = [Dense(10)]
    cfg = ParallelConfig(batch_size=3, split_size=1, spatial_size=0, image_size=8)
    trainer = Trainer(cells, num_spatial_cells=0, config=cfg, grad_accum=2)
    state = trainer.init(jax.random.PRNGKey(0), (3, 8, 8, 3))
    x, y = _batch(b=3, size=8)
    xs, ys = trainer.shard_batch(x, y)
    with pytest.raises(ValueError, match="not divisible"):
        trainer.train_step(state, xs, ys)


@pytest.mark.slow
def test_save_budget_matches_golden(monkeypatch):
    """MPI4DL_TPU_SAVE_BUDGET_MB only changes which runs save conv outputs
    (a scheduling choice) — params/metrics must match the no-remat golden
    exactly, even with a budget so small nothing gets saved."""
    monkeypatch.setenv("MPI4DL_TPU_SAVE_BUDGET_MB", "0.001")
    cells = get_resnet_v1(depth=20)
    cfg = ParallelConfig(batch_size=4, split_size=1, spatial_size=0, image_size=32)
    trainer = Trainer(cells, num_spatial_cells=0, config=cfg, remat="scan_save")
    state = trainer.init(jax.random.PRNGKey(3), (4, 32, 32, 3))
    _, golden_step = single_device_step(cells)
    gp = jax.tree.map(jnp.copy, state.params)
    golden_state = TrainState(
        params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
    )
    x, y = _batch(b=4, size=32)
    xs, ys = trainer.shard_batch(x, y)
    state, metrics = trainer.train_step(state, xs, ys)
    golden_state, golden_metrics = golden_step(golden_state, x, y)
    np.testing.assert_allclose(
        float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-5
    )
    _assert_tree_close(state.params, golden_state.params, rtol=2e-4, atol=1e-5)


def test_nockpt_budget_matches_golden(monkeypatch):
    """MPI4DL_TPU_NOCKPT_BUDGET_MB grants the cheapest runs a no-checkpoint
    tier (residuals stored, nothing replayed in backward) — a pure
    scheduling choice: params/metrics must match the no-remat golden. The
    10 MB budget covers some-but-not-all depth-20 runs at 32px, exercising
    the mixed grant path on both the saving and plain scan policies."""
    monkeypatch.setenv("MPI4DL_TPU_NOCKPT_BUDGET_MB", "10")
    for remat in ("scan_save", "scan"):
        cells = get_resnet_v1(depth=20)
        cfg = ParallelConfig(
            batch_size=4, split_size=1, spatial_size=0, image_size=32
        )
        trainer = Trainer(cells, num_spatial_cells=0, config=cfg, remat=remat)
        state = trainer.init(jax.random.PRNGKey(3), (4, 32, 32, 3))
        _, golden_step = single_device_step(cells)
        gp = jax.tree.map(jnp.copy, state.params)
        golden_state = TrainState(
            params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
        )
        x, y = _batch(b=4, size=32)
        xs, ys = trainer.shard_batch(x, y)
        state, metrics = trainer.train_step(state, xs, ys)
        golden_state, golden_metrics = golden_step(golden_state, x, y)
        np.testing.assert_allclose(
            float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-5
        )
        _assert_tree_close(
            state.params, golden_state.params, rtol=2e-4, atol=1e-5
        )


@pytest.mark.slow
def test_save_budget_spatial_matches_golden(monkeypatch):
    """The save-budget estimator must account for the SP→LP tile merge
    (join shapes are 4x the per-tile walk on a 2x2 grid) and still produce
    golden-exact numerics for a spatial scan_save trainer."""
    monkeypatch.setenv("MPI4DL_TPU_SAVE_BUDGET_MB", "2")
    cfg = ParallelConfig(
        batch_size=4,
        split_size=1,
        spatial_size=1,
        num_spatial_parts=(4,),
        slice_method="square",
        image_size=32,
    )
    spatial = get_resnet_v1(depth=14, spatial_cells=5, cross_tile_bn=True)
    plain = get_resnet_v1(depth=14, spatial_cells=0)
    trainer = Trainer(
        spatial, num_spatial_cells=5, config=cfg, plain_cells=plain,
        remat="scan_save",
    )
    state = trainer.init(jax.random.PRNGKey(4), (4, 32, 32, 3))
    _, golden_step = single_device_step(plain)
    gp = jax.tree.map(jnp.copy, state.params)
    golden_state = TrainState(
        params=gp, opt_state=trainer.tx.init(gp), step=jnp.zeros((), jnp.int32)
    )
    x, y = _batch(b=4, size=32)
    xs, ys = trainer.shard_batch(x, y)
    state, metrics = trainer.train_step(state, xs, ys)
    golden_state, golden_metrics = golden_step(golden_state, x, y)
    np.testing.assert_allclose(
        float(metrics["loss"]), float(golden_metrics["loss"]), rtol=1e-5
    )
    _assert_tree_close(state.params, golden_state.params, rtol=2e-4, atol=1e-5)


def test_compact_restore_mixed_tree_roundtrip():
    """_compact flattens only leaves whose lane-pad factor is >= 2; a
    mixed tree (C=16 flattens, C=72 passes through 4-D) must round-trip
    exactly through _restore (round-4 conditional flatten)."""
    rng = np.random.default_rng(0)
    tree = {
        "narrow": jnp.asarray(rng.standard_normal((2, 4, 4, 16)), jnp.float32),
        "wide": jnp.asarray(rng.standard_normal((2, 4, 4, 72)), jnp.float32),
        "vec": jnp.asarray(rng.standard_normal((7,)), jnp.float32),
    }
    compact, meta = Trainer._compact(tree)
    assert compact["narrow"].shape == (2, 4, 4 * 16)   # tax 8x: flattened
    assert compact["wide"].shape == (2, 4, 4, 72)      # tax 1.78x: kept 4-D
    assert compact["vec"].shape == (7,)
    restored = Trainer._restore(compact, meta)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(restored[k]), np.asarray(tree[k]))


@pytest.mark.parametrize(
    "image_size,policy",
    [
        (32, False), (1024, False), (2047, False), (2048, "scan"),
        (3071, "scan"), (3072, "scanlog"), (4095, "scanlog"),
        (4096, "scanq"), (8192, "scanq"),
    ],
)
def test_default_remat_is_one_fixed_rule_on_image_size(image_size, policy):
    """The entry points' remat policy (benchmarks/common.make_trainer,
    bench.py) is a fixed choice, not a ladder of attempts: nothing below
    2048 px — both 1024 px reference configurations fit a 16 GB v5e chip
    with everything stored (PR 24's described-chip compiles) — then
    scan / scanlog / scanq."""
    from mpi4dl_tpu.train import default_remat

    assert default_remat(image_size) == policy
    Trainer(  # whatever the rule names, the Trainer accepts
        get_resnet_v1(depth=8), num_spatial_cells=0, remat=policy,
        config=ParallelConfig(batch_size=2, split_size=1, spatial_size=0),
    )


def test_init_places_state_on_mesh_so_step_two_does_not_recompile():
    """A state left on the default device reaches step 2 with other input
    shardings than step 1 had (train_step returns it mesh-replicated) and
    the whole step compiles twice — minutes on the chip at full width."""
    from jax.sharding import NamedSharding

    cfg = ParallelConfig(
        batch_size=4, split_size=1, spatial_size=0, image_size=32,
        data_parallel=2,
    )
    trainer = Trainer(get_resnet_v1(depth=8), num_spatial_cells=0, config=cfg)
    state = trainer.init(jax.random.PRNGKey(0), (4, 32, 32, 3))
    for leaf in jax.tree.leaves(state):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.mesh == trainer.mesh
        assert leaf.sharding.is_fully_replicated
    xs, ys = trainer.shard_batch(*_batch(4, 32))
    for _ in range(2):
        state, metrics = trainer.train_step(state, xs, ys)
        float(metrics["loss"])
    assert trainer._jit_step._cache_size() == 1


def test_entry_point_sp_with_every_stage_spatial_matches_one_device(monkeypatch):
    """``--spatial-size == --split-size`` through benchmarks/common.py's
    builders: every stage is spatial, yet the head cell never is, so the
    tile merge must come before it (``spatial_cell_count`` stops one cell
    short). Before PR 24 each tile classified its own quarter and the
    first-step loss was off by 3.8% in f32; now it equals the one-device
    program's. This is the comparison ``chip_smoke.py --chips 4`` makes on
    the chips at full width."""
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(repo)
    monkeypatch.setenv("MPI4DL_TPU_RESNET_N", "1")
    from benchmarks.common import build_resnet, make_trainer
    from mpi4dl_tpu.parallel.pipeline import PipelineTrainer
    from mpi4dl_tpu.parser import get_parser

    base = ["--batch-size", "2", "--image-size", "64", "--split-size", "1",
            "--precision", "fp32"]
    sp = ["--num-spatial-parts", "4", "--slice-method", "square",
          "--spatial-size", "1"]
    x, y = _batch(2, 64)
    losses = {}
    for tag, argv, spatial in (("one", base, 0), ("sp", base + sp, 1)):
        args = get_parser().parse_args(argv)
        cfg = ParallelConfig(
            batch_size=2, split_size=1, spatial_size=spatial,
            num_spatial_parts=(4,), slice_method="square", image_size=64,
        )
        n_cells = len(build_resnet(args, cfg)[1])
        n_sp = PipelineTrainer.spatial_cell_count(n_cells, cfg) if spatial else 0
        assert n_sp == (n_cells - 1 if spatial else 0)
        cells, plain = build_resnet(args, cfg, spatial_cells=n_sp)
        trainer, n_out = make_trainer(args, cfg, cells, plain)
        assert n_out == n_sp and trainer.remat is False
        state = trainer.init(jax.random.PRNGKey(0), (2, 64, 64, 3))
        _, metrics = trainer.train_step(state, *trainer.shard_batch(x, y))
        losses[tag] = float(metrics["loss"])
    np.testing.assert_allclose(losses["sp"], losses["one"], rtol=2e-4)
