"""Runtime trace attribution (:mod:`mpi4dl_tpu.analysis.trace`): canned
Chrome-trace fixtures with known category times, the degradation paths
(missing/empty dir, no step annotations), the static<->measured overlap
cross-check, and the live CPU acceptance — ``profiling.capture`` over ≥3
annotated steps whose attribution buckets sum to the measured step wall
time and whose measured-overlap verdict agrees with hlolint's static
finding on the same executable. CPU-only, tier-1.
"""

import gzip
import json
import os

import numpy as np
import pytest

from mpi4dl_tpu import profiling, telemetry
from mpi4dl_tpu.analysis.trace import (
    TraceError,
    analyze_events,
    analyze_trace_dir,
    categorize,
    crosscheck_overlap,
    publish_attribution,
    static_overlap_verdict,
)

# -- canned fixture -----------------------------------------------------------

# Two annotated 1000us steps on a host thread; device ops on two XLA
# executor threads. All times in microseconds (the Chrome trace unit).
_META = [
    {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/host:CPU"}},
    {"ph": "M", "pid": 1, "tid": 10, "name": "thread_name",
     "args": {"name": "python"}},
    {"ph": "M", "pid": 1, "tid": 20, "name": "thread_name",
     "args": {"name": "tf_XLAPjRtCpuClient/111"}},
    {"ph": "M", "pid": 1, "tid": 21, "name": "thread_name",
     "args": {"name": "tf_XLAPjRtCpuClient/222"}},
]

_STEPS = [
    {"ph": "X", "pid": 1, "tid": 10, "ts": 0, "dur": 1000,
     "name": "mpi4dl_capture", "args": {"step_num": "0"}},
    {"ph": "X", "pid": 1, "tid": 10, "ts": 1000, "dur": 1000,
     "name": "mpi4dl_capture", "args": {"step_num": "1"}},
]

_DEVICE = [
    # step 0: 400us compute, then a 200us collective with 100us of
    # concurrent compute on the OTHER executor thread, then 100us d2d.
    {"ph": "X", "pid": 1, "tid": 20, "ts": 100, "dur": 400, "name": "fusion.1"},
    {"ph": "X", "pid": 1, "tid": 20, "ts": 500, "dur": 200,
     "name": "collective-permute.3"},
    {"ph": "X", "pid": 1, "tid": 21, "ts": 550, "dur": 100, "name": "dot.7"},
    {"ph": "X", "pid": 1, "tid": 20, "ts": 700, "dur": 100,
     "name": "D2D Dispatch"},
    # step 1: compute only.
    {"ph": "X", "pid": 1, "tid": 20, "ts": 1200, "dur": 300,
     "name": "convolution.2"},
    # runtime bookkeeping that must NOT count as device busy time — the
    # ExecuteHelper wrapper spans the whole step and would double it.
    {"ph": "X", "pid": 1, "tid": 20, "ts": 0, "dur": 1000,
     "name": "PjRtCpuExecutable::ExecuteHelper"},
    {"ph": "X", "pid": 1, "tid": 20, "ts": 0, "dur": 50,
     "name": "ThreadpoolListener::StartRegion"},
    {"ph": "X", "pid": 1, "tid": 20, "ts": 600, "dur": 300,
     "name": "ThunkExecutor::Execute (wait for completion)"},
]

CANNED = _META + _STEPS + _DEVICE


def _write_trace(root, events, gz=True):
    run = os.path.join(str(root), "plugins", "profile", "2026_01_01_00_00_00")
    os.makedirs(run, exist_ok=True)
    payload = json.dumps({"displayTimeUnit": "ms", "traceEvents": events})
    if gz:
        with gzip.open(os.path.join(run, "host.trace.json.gz"), "wb") as f:
            f.write(payload.encode())
    else:
        with open(os.path.join(run, "host.trace.json"), "w") as f:
            f.write(payload)
    return str(root)


def test_canned_attribution_known_category_times(tmp_path):
    """ISSUE satellite: a canned .trace.json.gz with known per-category
    times parses to exactly those times, wrapper/bookkeeping excluded,
    and the four buckets sum to each step's wall time."""
    summary = analyze_trace_dir(_write_trace(tmp_path, CANNED))
    assert summary["n_steps"] == 2
    s0, s1 = summary["steps"]
    assert s0["wall_s"] == pytest.approx(1000e-6)
    assert s0["compute_s"] == pytest.approx(400e-6)  # dot.7 is inside the
    # collective window on another thread -> overlap, not extra compute
    assert s0["collective_s"] == pytest.approx(200e-6)
    assert s0["transfer_s"] == pytest.approx(100e-6)
    assert s0["host_gap_s"] == pytest.approx(300e-6)
    assert s1["compute_s"] == pytest.approx(300e-6)
    assert s1["collective_s"] == 0.0
    assert s1["host_gap_s"] == pytest.approx(700e-6)
    for s in (s0, s1):
        total = (s["compute_s"] + s["collective_s"] + s["transfer_s"]
                 + s["host_gap_s"])
        assert total == pytest.approx(s["wall_s"], abs=1e-12)
    # Measured overlap: 100us of the 200us collective had concurrent
    # compute on the other executor thread.
    coll = summary["collective"]
    assert coll["total_s"] == pytest.approx(200e-6)
    assert coll["overlapped_s"] == pytest.approx(100e-6)
    assert coll["overlap_ratio"] == pytest.approx(0.5)
    assert coll["verdict"] == "overlapped"
    assert coll["by_op"]["collective-permute"]["n"] == 1


def test_canned_attribution_uncompressed_trace(tmp_path):
    summary = analyze_trace_dir(_write_trace(tmp_path, CANNED, gz=False))
    assert summary["n_steps"] == 2


def test_missing_and_empty_trace_dir_raise(tmp_path):
    """ISSUE satellite degradation: missing dir, dir without profiler
    runs, and a run without trace files all raise TraceError at the
    reader — not a KeyError three layers down."""
    with pytest.raises(TraceError, match="does not exist"):
        analyze_trace_dir(str(tmp_path / "nope"))
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(TraceError, match="no profiler runs"):
        analyze_trace_dir(str(empty))
    run = tmp_path / "norun" / "plugins" / "profile" / "r1"
    run.mkdir(parents=True)
    with pytest.raises(TraceError, match="no .*trace.json"):
        analyze_trace_dir(str(tmp_path / "norun"))


def test_trace_without_step_annotations_degrades_to_range(tmp_path):
    """ISSUE satellite degradation: no StepTraceAnnotation events ->
    n_steps == 0, but the whole-range bucket still answers where device
    time went."""
    summary = analyze_trace_dir(_write_trace(tmp_path, _META + _DEVICE))
    assert summary["n_steps"] == 0
    assert summary["per_step_mean"] is None
    rng = summary["range"]
    assert rng["compute_s"] == pytest.approx(400e-6 + 300e-6)
    assert rng["collective_s"] == pytest.approx(200e-6)
    assert rng["transfer_s"] == pytest.approx(100e-6)
    # Publishing falls back to range totals and must not raise.
    reg = telemetry.MetricsRegistry()
    publish_attribution(summary, reg, program="rangetest")
    attr = reg.get("trace_attribution_seconds")
    assert attr.value(program="rangetest", category="compute") == (
        pytest.approx(700e-6)
    )
    assert reg.get("trace_step_wall_seconds") is None  # no steps -> no wall


def test_categorize_noise_filter():
    assert categorize("collective-permute.12") == "collective"
    assert categorize("all-reduce-start.1") == "collective"
    assert categorize("all_reduce_fusion") == "compute"  # fusion kernel
    assert categorize("D2D Dispatch") == "transfer"
    assert categorize("TransferToDeviceStream") == "transfer"
    assert categorize("fusion.3") == "compute"
    assert categorize("PjRtCpuExecutable::ExecuteHelper") is None
    assert categorize("ThunkExecutor::Execute (wait for completion)") is None
    assert categorize("$profiling.py:141 annotate_step") is None


# -- static <-> measured cross-check ------------------------------------------


def _summary_with(total_s, ratio):
    verdict = (
        "no-collectives" if total_s == 0
        else ("overlapped" if ratio >= 0.5 else "exposed")
    )
    return {"collective": {
        "total_s": total_s,
        "overlapped_s": total_s * ratio if total_s else 0.0,
        "overlap_ratio": ratio if total_s else None,
        "by_op": {},
        "verdict": verdict,
    }}


def test_static_overlap_verdicts():
    assert static_overlap_verdict(
        {"n_collectives": 0, "async_pairs": 0, "zero_overlap": []}
    ) == "no-collectives"
    assert static_overlap_verdict(
        {"n_collectives": 4, "async_pairs": 0, "zero_overlap": []}
    ) == "sync"
    assert static_overlap_verdict(
        {"n_collectives": 4, "async_pairs": 2, "zero_overlap": ["a"]}
    ) == "exposed"
    assert static_overlap_verdict(
        {"n_collectives": 4, "async_pairs": 2, "zero_overlap": []}
    ) == "overlapped"


def test_crosscheck_disagreements_are_findings():
    overlapped_static = {"overlap": {
        "n_collectives": 2, "async_pairs": 2, "zero_overlap": [],
    }}
    # Static promises overlap, trace measured exposed latency: the T3
    # lost-overlap signature the static rule cannot see.
    (f,) = crosscheck_overlap(overlapped_static, _summary_with(1e-3, 0.1))
    assert f.rule == "trace-overlap-crosscheck" and f.severity == "warn"
    # Agreement in both directions -> no findings.
    assert crosscheck_overlap(overlapped_static, _summary_with(1e-3, 0.9)) == []
    none_static = {"overlap": {
        "n_collectives": 0, "async_pairs": 0, "zero_overlap": [],
    }}
    assert crosscheck_overlap(none_static, _summary_with(0.0, 0.0)) == []
    # Static saw nothing, trace recorded collectives (wrong program).
    (f,) = crosscheck_overlap(none_static, _summary_with(1e-3, 0.9))
    assert f.severity == "warn"
    # Static flagged exposed, runtime overlapped anyway: informational.
    exposed_static = {"overlap": {
        "n_collectives": 2, "async_pairs": 2, "zero_overlap": ["x"],
    }}
    (f,) = crosscheck_overlap(exposed_static, _summary_with(1e-3, 0.9))
    assert f.severity == "info"
    # "sync" schedules make no overlap claim: nothing to disagree with.
    sync_static = {"overlap": {
        "n_collectives": 2, "async_pairs": 0, "zero_overlap": [],
    }}
    assert crosscheck_overlap(sync_static, _summary_with(1e-3, 0.1)) == []


# -- live capture (the ISSUE acceptance) --------------------------------------


def test_capture_live_attribution_sums_and_crosscheck(tmp_path):
    """ISSUE acceptance: capture() over >=3 annotated steps on a live
    multi-device CPU program (halo-style ppermute ring + compute) yields
    an attribution whose category times sum to within 10% of the
    host-measured step wall time, and whose measured-overlap verdict is
    consistent with hlolint's static finding on the same executable."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from mpi4dl_tpu.analysis import analyze_compiled
    from jax import shard_map

    n = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("x",))

    def body(v):
        w = jax.lax.ppermute(v, "x", [(i, (i + 1) % n) for i in range(n)])
        m = v[0]
        return v * (m @ m.T).sum() + w

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
    ))
    x = jnp.ones((n, 256, 256), jnp.float32)
    f(x).block_until_ready()  # compile outside the capture

    cap = profiling.capture(lambda i: f(x), steps=3, logdir=str(tmp_path))
    summary = cap.attribution()
    assert summary["n_steps"] >= 3
    assert summary["n_device_slices"] > 0

    # Buckets sum to the annotation wall exactly (construction), and the
    # annotation wall matches the independent host clock within 10%.
    for step, host_dt in zip(summary["steps"], cap.step_times_s):
        parts = (step["compute_s"] + step["collective_s"]
                 + step["transfer_s"] + step["host_gap_s"])
        assert parts == pytest.approx(step["wall_s"], rel=1e-9)
        assert step["wall_s"] == pytest.approx(host_dt, rel=0.10)
    assert summary["per_step_mean"]["compute_s"] > 0
    assert summary["collective"]["total_s"] > 0  # the ppermutes

    # Static analysis of the SAME executable: CPU emits sync collectives
    # (no -start/-done pairs), so the schedule makes no overlap promise
    # and any measured verdict is consistent -> zero crosscheck findings.
    report = analyze_compiled(f.lower(x).compile(), platform="cpu")
    assert report.overlap["n_collectives"] > 0
    assert crosscheck_overlap(report, summary) == []


def test_capture_single_chip_consistent_with_static_no_collectives(tmp_path):
    """The serving-shaped case: a one-device program has zero collectives
    statically AND in the trace — verdicts agree, no findings."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.analysis import analyze_compiled

    f = jax.jit(lambda v: (v @ v.T).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    cap = profiling.capture(lambda i: f(x), steps=3, logdir=str(tmp_path))
    summary = cap.attribution()
    assert summary["collective"]["verdict"] == "no-collectives"
    report = analyze_compiled(f.lower(x).compile(), platform="cpu")
    assert static_overlap_verdict(report.overlap) == "no-collectives"
    assert crosscheck_overlap(report, summary) == []


def test_analyze_events_empty_is_graceful():
    summary = analyze_events([], step_name="mpi4dl_capture")
    assert summary["n_steps"] == 0
    assert summary["range"]["span_s"] == 0.0
    assert summary["collective"]["verdict"] == "no-collectives"
