"""Memory observability (`telemetry/memory.py` + `analysis/memory_plan.py`,
docs/OBSERVABILITY.md "Memory"): OOM-forensics goldens on canned real XLA
messages (the docs/PERF.md round-4 shapes), CPU-backend degradation of the
live monitor (absent-not-wrong), the footprint ledger, the feasibility
planner's exactness against the engine's actually-compiled executables,
the opt-in admission guard, the injected-OOM drill (schema-valid
oom.report in both the JSONL log and the flight dump, naming the
offending program's largest buffer), and the memory_headroom_low alert.
"""

import glob
import json
import os

import numpy as np
import pytest

from mpi4dl_tpu import telemetry
from mpi4dl_tpu.telemetry import memory as memobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Canned real-shape XLA messages. The HBM table is the docs/PERF.md
# round-4 incident: the compile dying at buffer assignment with
# the full breakdown — including the 16x-padded wgrad copy of
# f32[1,3072,3072,16] that PERF.md's whack-a-mole ledger names.
HBM_OOM = """\
RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of memory in \
memory space hbm. Used 18.95G of 15.48G hbm. Exceeded hbm capacity by 3.46G.

Total hbm usage >= 19.46G:
    reserved        530.00M
    program          18.95G
    arguments       unknown size

Output size unknown.

Program hbm requirement 18.95G:
    global            276.0K
    scoped            253.0K
    HLO temp         18.94G (33.0% utilization: Unpadded (6.26G) \
Padded (18.94G), 0.0% fragmentation (1.60M))

  Largest program allocations in hbm:

  1. Size: 4.50G
     Operator: op_name="jit(train_step)/jit(main)/transpose[permutation=(3, 1, 2, 0)]"
     Shape: f32[1,3072,3072,16]{2,1,3,0:T(8,128)}
     Unpadded size: 288.00M
     Extra memory due to padding: 4.22G (16.0x expansion)
     XLA label: %copy.1234 = f32[1,3072,3072,16]{2,1,3,0:T(8,128)} copy(%transpose.56)
     Allocation type: HLO temp
     ==========================

  2. Size: 1.12G
     Operator: op_name="jit(train_step)/while/body/dynamic-update-slice"
     Shape: f32[11,1,768,768,64]{4,3,2,1,0:T(8,128)}
     Unpadded size: 1.12G
     XLA label: %fusion.789 = f32[11,1,768,768,64]{4,3,2,1,0:T(8,128)} fusion(...)
     Allocation type: HLO temp
     ==========================
"""

ALLOCATOR_OOM = (
    "RESOURCE_EXHAUSTED: Out of memory allocating 25769803776 bytes."
)

# The exact shape BENCH_r05.json recorded raw — the string this PR's
# forensics exists to stop losing information on.
BARE_OOM = "ValueError: RESOURCE_EXHAUSTED: TPU backend error (ResourceExhausted)."


# -- size + message parsing (goldens) -----------------------------------------


def test_parse_size_units():
    assert memobs.parse_size("18.95G") == int(18.95 * 2**30)
    assert memobs.parse_size("288.00M") == int(288.0 * 2**20)
    assert memobs.parse_size("276.0K") == int(276.0 * 2**10)
    assert memobs.parse_size("123456") == 123456
    assert memobs.parse_size("1.5GiB") == int(1.5 * 2**30)
    assert memobs.parse_size("530.00MB") == int(530.0 * 2**20)
    assert memobs.parse_size("nonsense") is None


def test_parse_hbm_table_golden():
    p = memobs.parse_resource_exhausted(HBM_OOM)
    assert p["kind"] == "hbm_oom"
    assert p["memory_space"] == "hbm"
    assert p["used_bytes"] == int(18.95 * 2**30)
    assert p["limit_bytes"] == int(15.48 * 2**30)
    assert p["exceeded_bytes"] == int(3.46 * 2**30)
    assert p["program_bytes"] == int(18.95 * 2**30)
    assert p["total_bytes"] == int(19.46 * 2**30)
    a1, a2 = p["largest_allocations"]
    assert a1["rank"] == 1
    assert a1["size_bytes"] == int(4.50 * 2**30)
    # The layout/tiling suffix is stripped; the logical shape survives.
    assert a1["shape"] == "f32[1,3072,3072,16]"
    assert a1["unpadded_bytes"] == int(288.0 * 2**20)
    assert a1["padding_expansion"] == 16.0
    assert a1["allocation_type"] == "HLO temp"
    assert "%copy.1234" in a1["xla_label"]
    assert a2["rank"] == 2
    assert a2["shape"] == "f32[11,1,768,768,64]"
    assert "padding_expansion" not in a2
    # The postmortem one-liner names the biggest buffer.
    lb = memobs.largest_buffer(p)
    assert "4.50G" in lb and "f32[1,3072,3072,16]" in lb
    assert "16x padding" in lb and "%copy.1234" in lb


def test_parse_allocator_and_bare_messages():
    p = memobs.parse_resource_exhausted(ALLOCATOR_OOM)
    assert p["kind"] == "allocator_oom"
    assert p["requested_bytes"] == 25769803776
    p = memobs.parse_resource_exhausted(BARE_OOM)
    assert p["kind"] == "unclassified"
    assert memobs.largest_buffer(p) is None
    assert memobs.parse_resource_exhausted("a perfectly fine message") is None


def test_is_oom_error_walks_exception_chain():
    try:
        try:
            raise RuntimeError(HBM_OOM)
        except RuntimeError as inner:
            raise ValueError("compile died") from inner
    except ValueError as e:
        wrapped = e
    assert memobs.is_oom_error(wrapped)
    # The chain text carries the table, so the parse works on it too.
    p = memobs.parse_resource_exhausted(memobs.exception_chain_text(wrapped))
    assert p["kind"] == "hbm_oom"
    assert not memobs.is_oom_error(ValueError("shape mismatch"))


def test_oom_report_event_is_schema_valid():
    ev = memobs.oom_report(HBM_OOM, program="serve_predict", bucket=32)
    telemetry.validate_event(ev)  # raises on drift
    assert ev["name"] == "oom.report"
    assert ev["attrs"]["program"] == "serve_predict"
    assert ev["attrs"]["bucket"] == 32
    assert ev["attrs"]["parsed"]["kind"] == "hbm_oom"
    assert "f32[1,3072,3072,16]" in ev["attrs"]["largest_buffer"]
    assert "Ran out of memory" in ev["attrs"]["raw"]


def test_emit_oom_report_fans_out(tmp_path):
    reg = telemetry.MetricsRegistry()
    events = telemetry.JsonlWriter(str(tmp_path))
    flight = telemetry.FlightRecorder(capacity=16, directory=str(tmp_path))
    memobs.emit_oom_report(
        HBM_OOM, program="train_step", registry=reg, events=events,
        flight=flight, dump=True,
    )
    events.close()
    assert reg.get("oom_reports_total").value(program="train_step") == 1
    logged = [
        e for e in telemetry.read_events(events.path)
        if e["name"] == "oom.report"
    ]
    assert len(logged) == 1
    (dump,) = glob.glob(str(tmp_path / "flight-*-oom.jsonl"))
    dumped = [
        e for e in telemetry.read_events(dump) if e.get("name") == "oom.report"
    ]
    assert dumped[0]["attrs"]["largest_buffer"] == logged[0]["attrs"]["largest_buffer"]


# -- live monitor: CPU degradation + stub-device publishing -------------------


class _StubDevice:
    platform = "stubtpu"

    def __init__(self, i, used, limit):
        self.id = i
        self._stats = {"bytes_in_use": used, "bytes_limit": limit,
                       "peak_bytes_in_use": used}

    def memory_stats(self):
        return self._stats


def test_monitor_cpu_backend_publishes_nothing():
    """ISSUE satellite: memory_stats() absent (the real CPU devices
    return None) → the gauge NAMES are declared (catalog pin) but no
    series exists, and nothing can trip on a fabricated zero."""
    import jax

    reg = telemetry.MetricsRegistry()
    mon = telemetry.MemoryMonitor(reg, devices=jax.devices())
    assert mon.sample_once() is None
    assert mon.supported is False
    for name in ("device_hbm_used_bytes", "device_hbm_limit_bytes",
                 "device_hbm_headroom_ratio"):
        assert name in reg.names()
        assert reg.get(name).snapshot_series() == []
    # The headroom alert cannot activate without data.
    from mpi4dl_tpu.telemetry.alerts import SLOEvaluator

    ev = SLOEvaluator(
        reg, [], telemetry.SLOConfig(headroom_alert_ratio=0.5),
    )
    ev.evaluate_once(now=1.0)
    ev.evaluate_once(now=2.0)
    assert ev.alerts["memory_headroom_low"].state == "inactive"


def test_monitor_publishes_per_device_gauges():
    reg = telemetry.MetricsRegistry()
    devs = [_StubDevice(0, used=12 << 30, limit=16 << 30),
            _StubDevice(1, used=4 << 30, limit=16 << 30)]
    mon = telemetry.MemoryMonitor(reg, devices=devs)
    out = mon.sample_once()
    assert mon.supported is True
    assert set(out) == {"stubtpu:0", "stubtpu:1"}
    assert reg.get("device_hbm_used_bytes").value(device="stubtpu:0") == 12 << 30
    assert reg.get("device_hbm_limit_bytes").value(device="stubtpu:1") == 16 << 30
    assert reg.get("device_hbm_headroom_ratio").value(
        device="stubtpu:0"
    ) == pytest.approx(0.25)
    assert reg.get("device_hbm_headroom_ratio").value(
        device="stubtpu:1"
    ) == pytest.approx(0.75)


def test_headroom_alert_fires_and_resolves(tmp_path):
    """memory_headroom_low rides the existing alert machinery: AlertState
    lifecycle, alert_active gauge, transition events into the flight
    ring — and the transition names the offending device."""
    from mpi4dl_tpu.telemetry.alerts import SLOEvaluator

    reg = telemetry.MetricsRegistry()
    devs = [_StubDevice(0, used=2 << 30, limit=16 << 30)]
    mon = telemetry.MemoryMonitor(reg, devices=devs)
    flight = telemetry.FlightRecorder(capacity=32, directory=str(tmp_path))
    ev = SLOEvaluator(
        reg, [], telemetry.SLOConfig(headroom_alert_ratio=0.1),
        flight=flight,
    )
    mon.sample_once()
    ev.evaluate_once(now=1.0)
    st = ev.alerts["memory_headroom_low"]
    assert st.state == "inactive"  # 87.5% headroom

    devs[0]._stats["bytes_in_use"] = 15 << 30  # 6.25% headroom < 10%
    mon.sample_once()
    ev.evaluate_once(now=2.0)
    assert st.state == "firing"
    assert reg.get("alert_active").value(
        alert="memory_headroom_low", severity="page"
    ) == 1.0
    trans = [
        t for t in ev.transitions
        if t["attrs"]["alert"] == "memory_headroom_low"
    ]
    assert trans[-1]["attrs"]["to"] == "firing"
    assert trans[-1]["attrs"]["device"] == "stubtpu:0"
    assert trans[-1]["attrs"]["headroom_min"] == pytest.approx(0.0625)
    telemetry.validate_event(trans[-1])
    assert any(
        t.get("name") == "alert.transition"
        and t["attrs"]["alert"] == "memory_headroom_low"
        for t in flight.tail(32)
    )
    # /alertz surface + verdict: a page that fired is a failed verdict.
    assert any(
        a["name"] == "memory_headroom_low" for a in ev.state()["alerts"]
    )
    assert ev.verdict()["ok"] is False

    devs[0]._stats["bytes_in_use"] = 2 << 30
    mon.sample_once()
    ev.evaluate_once(now=3.0)
    assert st.state == "inactive"
    assert reg.get("alert_active").value(
        alert="memory_headroom_low", severity="page"
    ) == 0.0


# -- footprint ledger ---------------------------------------------------------


def test_footprint_ledger_records_and_publishes(tmp_path):
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.analysis.memory import memory_summary

    reg = telemetry.MetricsRegistry()
    ledger = telemetry.FootprintLedger(registry=reg)
    # Declared up front, before any record (catalog-pin behavior).
    assert "serve_bucket_peak_hbm_bytes" in reg.names()
    assert "program_peak_hbm_bytes" in reg.names()

    fn = jax.jit(lambda x: (x @ x).sum())
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = fn.lower(x).compile()
    want = memory_summary(compiled)["peak_bytes"]

    # record_lowered: compile-only (abstract input), no execution.
    entry = ledger.record_lowered("unit_prog", fn, x)
    assert entry["peak_bytes"] == want
    assert reg.get("program_peak_hbm_bytes").value(program="unit_prog") == want

    entry = ledger.record_compiled("serve_predict", compiled, bucket=4)
    assert reg.get("serve_bucket_peak_hbm_bytes").value(bucket=4) == want
    assert ledger.get("serve_predict", bucket=4)["peak_bytes"] == want

    # dump → the planner's --ledger artifact mode reads it, pure JSON.
    path = ledger.dump(str(tmp_path / "ledger.json"))
    from mpi4dl_tpu.analysis.cli import main

    rc = main([
        "memory-plan", "--ledger", path,
        "--limit-bytes", str(want + 1), "--json",
        str(tmp_path / "plan.json"),
    ])
    assert rc == 0
    plan = json.load(open(tmp_path / "plan.json"))
    assert all(e["fits"] for e in plan["entries"])
    assert {e["key"] for e in plan["entries"]} == {
        "unit_prog", "serve_predict[4]"
    }
    assert main([
        "memory-plan", "--ledger", path, "--limit-bytes", str(want - 1),
    ]) == 1


# -- the serving engine + planner on a real model -----------------------------


@pytest.fixture(scope="module")
def tiny_serving_model():
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.evaluate import collect_batch_stats
    from mpi4dl_tpu.models.resnet import get_resnet_v2
    from mpi4dl_tpu.parallel.partition import init_cells

    size = 16
    cells = get_resnet_v2(depth=11, num_classes=10, pool_kernel=size // 4)
    rng = np.random.default_rng(0)
    params = init_cells(
        cells, jax.random.PRNGKey(0), jnp.zeros((1, size, size, 3))
    )
    stats = collect_batch_stats(
        cells, params,
        [jnp.asarray(rng.standard_normal((4, size, size, 3)), jnp.float32)],
    )
    return size, cells, params, stats


def _make_engine(tiny_serving_model, **kw):
    from mpi4dl_tpu.serve import ServingEngine

    size, cells, params, stats = tiny_serving_model
    kw.setdefault("example_shape", (size, size, 3))
    kw.setdefault("default_deadline_s", 30.0)
    return ServingEngine(cells, params, stats, **kw)


def test_planner_matches_engine_compiled_exactly(tiny_serving_model):
    """ISSUE acceptance: memory-plan's predicted peak equals
    memory_analysis() of the executable the engine actually compiles for
    the same config — exactly, not approximately. The planner lowered
    abstractly (no params materialized, nothing executed); the engine
    warmed real device arrays; same program, same buffer assignment."""
    from mpi4dl_tpu.analysis.memory import memory_summary
    from mpi4dl_tpu.analysis.memory_plan import predict_serve_peak

    size, cells, params, stats = tiny_serving_model
    engine = _make_engine(tiny_serving_model, buckets=(1, 4))
    try:
        for b in (1, 4):
            engine_summary = memory_summary(engine._compiled[b])
            planned = predict_serve_peak(cells, size, b)
            assert planned == engine_summary, f"bucket {b}"
            # And the ledger recorded the same number at warm-up.
            assert engine.memory_ledger.get("serve_predict", bucket=b)[
                "peak_bytes"
            ] == engine_summary["peak_bytes"]
    finally:
        engine.stop()


def test_engine_memory_surface_and_bucket_gauges(tiny_serving_model):
    engine = _make_engine(tiny_serving_model, buckets=(1, 4))
    try:
        mem = engine.stats()["memory"]
        assert set(mem["bucket_peak_hbm_bytes"]) == {"1", "4"}
        assert all(v > 0 for v in mem["bucket_peak_hbm_bytes"].values())
        assert mem["refused_buckets"] == {}
        # CPU: no device limit, monitor unsupported — absent, not zero.
        assert mem["limit_bytes"] is None
        for b in (1, 4):
            assert engine.registry.get("serve_bucket_peak_hbm_bytes").value(
                bucket=b
            ) == mem["bucket_peak_hbm_bytes"][str(b)]
    finally:
        engine.stop()


def test_admission_guard_refuses_unfit_bucket(tiny_serving_model):
    """ISSUE tentpole: with the guard on and a limit between the small
    and large buckets' predicted peaks, the large bucket is refused at
    warm-up and the engine serves with what fits — graceful degradation
    instead of a crash."""
    probe = _make_engine(tiny_serving_model, buckets=(1, 8))
    peaks = {
        e["bucket"]: e["peak_bytes"]
        for e in probe.memory_ledger.entries()
    }
    probe.stop()
    limit = (peaks[1] + peaks[8]) // 2

    engine = _make_engine(
        tiny_serving_model, buckets=(1, 8),
        memory_guard=True, memory_limit_bytes=limit,
    )
    try:
        assert engine.buckets == (1,)
        refused = engine.stats()["memory"]["refused_buckets"]["8"]
        assert refused["reason"] == "predicted_peak_exceeds_limit"
        assert refused["peak_bytes"] == peaks[8]
        assert refused["limit_bytes"] == limit
        # It still serves.
        engine.start()
        size = tiny_serving_model[0]
        out = engine.submit(np.zeros((size, size, 3), np.float32)).result(
            timeout=30
        )
        assert out.shape == (10,)
    finally:
        engine.stop()

    # Nothing fits → a loud construction-time error, not a wedged engine.
    with pytest.raises(RuntimeError, match="no serving bucket fits"):
        _make_engine(
            tiny_serving_model, buckets=(1, 8),
            memory_guard=True, memory_limit_bytes=1,
        )


def test_injected_oom_drill(tiny_serving_model, tmp_path):
    """ISSUE acceptance: an injected RESOURCE_EXHAUSTED on a live batch
    produces a schema-valid oom.report in BOTH the JSONL log and the
    flight dump, naming the program, bucket, and the offending program's
    largest buffer — and the batcher survives (only that batch's
    requests fail)."""
    import jax

    size = tiny_serving_model[0]
    engine = _make_engine(
        tiny_serving_model, buckets=(1,),
        telemetry_dir=str(tmp_path), flight_dir=str(tmp_path),
        watchdog_factor=None,
    )
    orig = dict(engine._compiled)
    calls = {"n": 0}

    def boom(p, s, batch):
        calls["n"] += 1
        if calls["n"] == 1:
            raise jax.errors.JaxRuntimeError(HBM_OOM)
        return orig[1](p, s, batch)

    engine._compiled[1] = boom
    engine.start()
    try:
        x = np.zeros((size, size, 3), np.float32)
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            engine.submit(x).result(timeout=30)
        # The loop survived: the next request is served normally.
        assert engine.submit(x).result(timeout=30).shape == (10,)
    finally:
        engine.stop()

    assert engine.registry.get("oom_reports_total").value(
        program="serve_predict"
    ) == 1
    logged = [
        e for e in telemetry.read_events(engine._events.path)
        if e.get("name") == "oom.report"
    ]
    assert len(logged) == 1
    attrs = logged[0]["attrs"]
    assert attrs["program"] == "serve_predict"
    assert attrs["bucket"] == 1
    assert attrs["parsed"]["kind"] == "hbm_oom"
    assert "f32[1,3072,3072,16]" in attrs["largest_buffer"]

    (dump,) = glob.glob(str(tmp_path / "flight-*-oom.jsonl"))
    dumped = [
        e for e in telemetry.read_events(dump)  # read_events validates
        if e.get("name") == "oom.report"
    ]
    assert dumped and dumped[0]["attrs"]["largest_buffer"] == attrs["largest_buffer"]
    assert engine.registry.get("flight_recorder_dumps_total").value(
        reason="oom"
    ) == 1


def test_planner_answers_without_device_limit(tiny_serving_model):
    """ISSUE satellite (CPU degradation): with no device limit (CPU
    reports none) the planner still answers from memory_analysis()
    alone — peak reported, verdict None, exit 0 — instead of inventing
    a limit or failing."""
    from mpi4dl_tpu.analysis.memory import feasibility
    from mpi4dl_tpu.analysis.memory_plan import predict_serve_peak

    size, cells, _, _ = tiny_serving_model
    summary = predict_serve_peak(cells, size, 2)
    assert summary["peak_bytes"] > 0
    v = feasibility(summary["peak_bytes"], memobs.device_memory_limit())
    assert v["fits"] is None and v["peak_bytes"] == summary["peak_bytes"]


def test_trainer_record_memory_footprint_and_oom_wiring(tmp_path, monkeypatch):
    """The trainer side: record_memory_footprint lands the compiled
    step's peak in the ledger/gauge, and an OOM raised by the step
    emits oom.report into the env-gated JSONL log before surfacing."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.models.resnet import get_resnet_v2
    from mpi4dl_tpu.train import Trainer
    from mpi4dl_tpu.utils import get_depth

    size = 16
    cells = get_resnet_v2(
        depth=get_depth(2, 1), num_classes=10, pool_kernel=size // 4
    )
    trainer = Trainer(
        cells, num_spatial_cells=0,
        config=ParallelConfig(
            batch_size=2, split_size=1, spatial_size=0, image_size=size
        ),
    )
    state = trainer.init(jax.random.PRNGKey(0), (2, size, size, 3))
    x = jnp.zeros((2, size, size, 3), jnp.float32)
    y = jnp.zeros((2,), jnp.int32)
    xs, ys = trainer.shard_batch(x, y)

    reg = telemetry.MetricsRegistry()
    entry = trainer.record_memory_footprint(state, xs, ys, registry=reg)
    assert entry["peak_bytes"] > 0
    assert reg.get("program_peak_hbm_bytes").value(
        program="train_step"
    ) == entry["peak_bytes"]

    # OOM forensics: force the dispatch to raise an OOM-shaped error.
    monkeypatch.setenv(telemetry.ENV_DIR, str(tmp_path))
    monkeypatch.setattr(
        trainer, "_jit_step",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError(HBM_OOM)),
    )
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        trainer.train_step(state, xs, ys)
    (log,) = glob.glob(str(tmp_path / "telemetry-*.jsonl"))
    reports = [
        e for e in telemetry.read_events(log) if e.get("name") == "oom.report"
    ]
    assert len(reports) == 1
    assert reports[0]["attrs"]["program"] == "train_step"
    assert reports[0]["attrs"]["image_size"] == size
    assert reports[0]["attrs"]["parsed"]["used_bytes"] == int(18.95 * 2**30)
