"""Collective-inventory regression tests over compiled train-step HLO.

Multi-chip *performance* is unmeasurable on this runtime (one real chip),
but the communication *structure* is checkable: these tests compile the
distributed train step on the 8-virtual-CPU mesh and pin the exact count
of each collective op in the optimized HLO (VERDICT r4 next #7). A change
that, say, doubles per-layer halo traffic or adds a stray resharding
all-to-all fails here instead of silently shipping — the discipline the
reference enforces by construction with its per-layer explicit
isend/irecv pairs (``spatial.py:336-413``).

Counting rides the shared static analyzer (:mod:`mpi4dl_tpu.analysis`) —
the same inventory the ``python -m mpi4dl_tpu.analyze`` CLI and the bench
hook report, so the pin semantics cannot drift from the lint rules. On top
of the exact pins, each config runs the full rule engine and asserts no
error-severity findings (the tier-1 lint gate; the rules themselves are
unit-tested on canned HLO in ``tests/test_hlolint.py``).

If a test fails after an INTENTIONAL engine change: re-derive the counts
(the probe is just ``trainer._jit_step.lower(...).compile().as_text()``
through ``collective_inventory``), check the delta is explained by the
change, and update the pins in the same commit. NOTE: the all-reduce
count is combiner-dependent. The pins are the installed runtime's (jax /
jaxlib 0.9.0), whose all-reduce combiner merges every all-reduce that is
independent of the others into one tuple all-reduce: all per-parameter
gradients travel in ONE op, and what stays separate is what is ordered by
data dependence (each cross-tile BatchNorm's statistics feed the next
layer). The structural ops (permute / gather / all-to-all /
reduce-scatter) have been stable across compiler versions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.analysis import (
    analyze_compiled,
    collective_inventory,
    compose,
)
from mpi4dl_tpu.config import ParallelConfig
from mpi4dl_tpu.models.resnet import get_resnet_v1
from mpi4dl_tpu.train import Trainer

OPS = (
    "collective-permute",
    "all-gather",
    "all-reduce",
    "all-to-all",
    "reduce-scatter",
)


def _batch(b, size):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((b, size, size, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(b,)), jnp.int32)
    return x, y


def _no_errors(report):
    errors = [f for f in report.findings if f["severity"] == "error"]
    assert not errors, errors


def test_pure_dp_inventory():
    """DP=2, no spatial: gradient/metrics all-reduces only — any permute,
    gather, or all-to-all means input/param sharding regressed. The same
    property is what the analyzer's pure-DP stray-resharding rule lints."""
    cfg = ParallelConfig(
        batch_size=4, split_size=1, spatial_size=0, image_size=32,
        data_parallel=2,
    )
    cells = get_resnet_v1(depth=8)
    tr = Trainer(cells, num_spatial_cells=0, config=cfg)
    state = tr.init(jax.random.PRNGKey(0), (4, 32, 32, 3))
    xs, ys = tr.shard_batch(*_batch(4, 32))
    compiled = tr._jit_step.lower(state, xs, ys).compile()
    inv = collective_inventory(compiled.as_text(), ops=OPS)
    assert inv == {
        "collective-permute": 0,
        "all-gather": 0,
        # 2 on jax 0.9.0: every parameter gradient in one combined
        # all-reduce, plus the loss/accuracy psum the update waits on.
        "all-reduce": 2,
        "all-to-all": 0,
        "reduce-scatter": 0,
    }, inv
    _no_errors(analyze_compiled(
        compiled,
        expected=compose(tr.collective_deltas(state.params, (4, 32, 32, 3))),
    ))


def test_spatial_trainer_inventory():
    """SP 2×2 tiles, 3 spatial cells (5 halo-exchanged 3×3 convs: stem +
    2 CellV1 × 2). Halo traffic rides collective-permutes (4 shift
    ppermutes per exchange forward, partially deduped with the backward's
    transposed shifts by XLA); the SP→LP join is the tiled all_gather
    pair (value + the backward's re-gather)."""
    cfg = ParallelConfig(
        batch_size=4, split_size=1, spatial_size=1, num_spatial_parts=(4,),
        slice_method="square", image_size=32, data_parallel=1,
    )
    plain = get_resnet_v1(depth=8)
    cells = get_resnet_v1(depth=8, spatial_cells=3)
    tr = Trainer(cells, num_spatial_cells=3, config=cfg, plain_cells=plain)
    state = tr.init(jax.random.PRNGKey(0), (4, 32, 32, 3))
    xs, ys = tr.shard_batch(*_batch(4, 32))
    compiled = tr._jit_step.lower(state, xs, ys).compile()
    inv = collective_inventory(compiled.as_text(), ops=OPS)
    assert inv == {
        "collective-permute": 36,  # ~4/exchange fwd + bwd over 5 conv layers
        "all-gather": 2,  # tile join (fwd) + its backward re-gather
        # 11 on jax 0.9.0: the five cross-tile BN layers' statistics (mean
        # and mean-of-squares paired in one op each; the last also carries
        # the loss scalar) — a chain, each feeding the next layer, so the
        # combiner cannot merge them — five more for their transposes in
        # the backward pass, and ONE combined op for all parameter
        # gradients.
        "all-reduce": 11,
        "all-to-all": 0,
        "reduce-scatter": 2,
    }, inv

    # Partition-math derivation (no hand pin): one un-scanned forward
    # traces 20 shift ppermutes (5 exchanges x 4 shifts on the 2x2 grid),
    # so the compiled count must land in [20, 40] — and the full rule set
    # must be clean on the real program. The gate is the COMPOSED spatial
    # delta, not a hand-built Expectations.
    shifts = tr.halo_shift_count(state.params, (4, 32, 32, 3))
    assert shifts == 20, shifts
    (delta,) = tr.collective_deltas(state.params, (4, 32, 32, 3))
    assert delta.layer == "spatial" and delta.halo_shifts == shifts
    report = analyze_compiled(compiled, expected=compose(delta))
    _no_errors(report)
    # The report carries per-collective bytes for every record.
    assert report.overlap["total_bytes"] > 0
    assert all(r["bytes_moved"] > 0 for r in report.collectives)


@pytest.mark.slow
def test_sp_plus_lp_pipeline_inventory():
    """SP front (2×2 tiles) + LP stage, parts=2 micro-batches: the
    pipeline's stage ppermutes ride the same collective-permute class as
    the halo shifts; the join all_gather pair and grad reductions must
    not multiply with the schedule."""
    from mpi4dl_tpu.parallel.pipeline import PipelineTrainer

    cfg = ParallelConfig(
        batch_size=4, parts=2, split_size=2, spatial_size=1,
        num_spatial_parts=(4,), slice_method="square", image_size=32,
        data_parallel=1,
    )
    plain = get_resnet_v1(depth=8)
    n_sp = PipelineTrainer.spatial_cell_count(len(plain), cfg)
    cells = get_resnet_v1(depth=8, spatial_cells=n_sp)
    tr = PipelineTrainer(cells, cfg, plain_cells=plain)
    state = tr.init(jax.random.PRNGKey(0))
    xs, ys = tr.shard_batch(*_batch(4, 32))
    compiled = tr._jit_step.lower(state, xs, ys).compile()
    inv = collective_inventory(compiled.as_text(), ops=OPS)
    assert inv == {
        "collective-permute": 20,
        "all-gather": 2,
        "all-reduce": 7,  # jax 0.9.0: parameter gradients combined, as above
        "all-to-all": 0,
        "reduce-scatter": 2,
    }, inv

    # The STACKED gate (the ROADMAP's composition item): the pipeline
    # trainer contributes a spatial front delta (traced front halo
    # shifts), the SP->LP join gather claim, and the exact stage-permute
    # budget; compose() folds them into one window the full rule set is
    # clean under — no hand-summed constants anywhere.
    deltas = tr.collective_deltas(state, (4, 32, 32, 3))
    assert [d.layer for d in deltas] == ["spatial", "spatial_join", "pipeline"]
    front_shifts = tr.halo_shift_count(state, (4, 32, 32, 3))
    assert front_shifts > 0
    expected = compose(deltas)
    assert expected.halo_shifts == front_shifts
    assert expected.extra_permutes == tr.stage_permute_count()
    assert expected.join_gathers == 2
    _no_errors(analyze_compiled(compiled, expected=expected))


def test_spatial_trainer_decomposed_overlap_keeps_permute_window(monkeypatch):
    """ISSUE 9 acceptance: under MPI4DL_TPU_CONV_OVERLAP=decomposed the
    SAME SP 2×2 program decomposes each spatial conv into interior +
    boundary strips, but halo_exchange still runs exactly once per conv —
    so the counted forward shifts are unchanged (20) and the compiled
    permute inventory must stay inside the partition-math window
    [shifts, 2*shifts]; the full rule set (halo-window included) must be
    clean on the decomposed program."""
    monkeypatch.setenv("MPI4DL_TPU_CONV_OVERLAP", "decomposed")
    cfg = ParallelConfig(
        batch_size=4, split_size=1, spatial_size=1, num_spatial_parts=(4,),
        slice_method="square", image_size=32, data_parallel=1,
    )
    plain = get_resnet_v1(depth=8)
    cells = get_resnet_v1(depth=8, spatial_cells=3)
    tr = Trainer(cells, num_spatial_cells=3, config=cfg, plain_cells=plain)
    state = tr.init(jax.random.PRNGKey(0), (4, 32, 32, 3))
    xs, ys = tr.shard_batch(*_batch(4, 32))

    shifts = tr.halo_shift_count(state.params, (4, 32, 32, 3))
    assert shifts == 20, shifts  # identical to the monolithic derivation

    compiled = tr._jit_step.lower(state, xs, ys).compile()
    inv = collective_inventory(compiled.as_text(), ops=OPS)
    assert shifts <= inv["collective-permute"] <= 2 * shifts, inv
    assert inv["all-to-all"] == 0
    assert inv["all-gather"] == 2  # tile join pair, unchanged

    report = analyze_compiled(
        compiled,
        expected=compose(tr.collective_deltas(state.params, (4, 32, 32, 3))),
    )
    _no_errors(report)
