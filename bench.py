"""Headline benchmark: training throughput vs the reference's published numbers.

Headline metric (the JSON ``value``): AmoebaNet-D (18 layers / 416 filters,
the reference benchmark defaults — its headline model; BASELINE.json configs
are AmoebaNet-centric) @1024px bs=2, vs the reference's best published
AmoebaNet@1024 number ~3.0 img/s (multi-GPU MVAPICH2-GDR cluster; read off
``docs/assets/images/AmeobaNet_img_size_1024.png`` — BASELINE.md).
``BENCH_MODEL=resnet`` switches the headline to ResNet-110(v2) @1024 bs2
(ref best ~3.1, ``ResNet_img_size_1024.png``).

``extras`` carries the other published chart points:

- ResNet 1024px bs=2: ref best ≈3.1 img/s (ResNet_img_size_1024.png)
- ResNet 2048px bs=1: ref best ≈1.0 img/s (ResNet_img_size_2048.png)
- AmoebaNet 2048px bs=2: ref best ≈5.1 img/s (AmeobaNet_img_size_2048.png)
- AmoebaNet 2048px bs=1: ref best ≈2.9 img/s (same chart)

Every entry also reports MFU (model-FLOPs utilization, analytic conv+dot
count — see mpi4dl_tpu/flops.py); the north star is ≥45% (BASELINE.json).
Train entries carry p50/p90/p99 step-time tails (``step_time_s``), and a
``serving_*`` extra measures the online serving engine (mpi4dl_tpu/serve):
dynamic micro-batching throughput vs the batch-size-1 serial baseline with
request-latency percentiles (``BENCH_SERVING=0`` disables). The
``sp2x2_overlap`` extra runs the spatial-parallel train step's
monolithic-vs-decomposed conv A/B on a CPU-mesh subprocess and embeds both
arms' measured ``trace_overlap_ratio`` (``BENCH_SP_OVERLAP=0`` disables);
``serving_sharded`` runs the same A/B on the serving hot path — a
2×2-sharded engine under closed-loop load per arm, ratio + per-request
p99 per arm (``BENCH_SERVING_SHARDED=0`` disables); ``pipeline`` runs the
LP pipeline's schedule A/B — gpipe vs interleaved 1f1b — embedding both
arms' measured bubble fraction + img/s (``BENCH_PIPELINE=0`` disables);
``tiled_gigapixel`` walks the largest image ONE chip serves through the
halo-correct tile stream (serve/tiled.py) and measures fixed-size request
latency + the tile/stitch split (``BENCH_TILED=0`` disables;
``BENCH_TILED_PX``/``BENCH_TILED_TILE``/``BENCH_TILED_WALK`` scale it);
``numerics`` measures the canary sentinel's ON/OFF rps tax and times a
live bit-flip corrupt drill's corruption→fence detection latency
(``BENCH_NUMERICS=0`` disables); ``incident`` reruns the kill drill under
the incident engine and scores it — MTTD (page→open), MTTR (open→close),
and whether the auto-postmortem blames the injected chaos op
(``BENCH_INCIDENT=0`` disables).

Output protocol (timeout-proof by design): a full JSON result line is
printed AND FLUSHED the moment the headline measurement lands, and an
updated full line (a superset: same headline + one more extra) after each
extra completes.  Every printed line is a complete, valid result — a driver
that keeps either the first or the last JSON line gets a usable record even
if this process is killed mid-extra.  SIGTERM/SIGINT re-emit the latest
result before exiting.  All extras run under a wall-clock budget
(``BENCH_TIME_BUDGET`` seconds, default 1800): an extra is skipped — with a
"skipped" marker — rather than started if the budget is exhausted.

Line shape:
    {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N,
     "mfu": ..., "extras": {...}}
If NOTHING produced a throughput the single line carries an explicit
top-level "error" and the process exits nonzero (a null value must never
masquerade as a measurement).
"""

from __future__ import annotations

import functools
import json
import os
import signal
import sys
import tempfile
import time

import numpy as np

RESNET_BASELINE = 3.1  # img/s, ResNet@1024 bs2, best SP config (BASELINE.md)
RESNET_2048_BASELINE = 1.0  # img/s bs=1 (bs=2 OOMs every published scheme)
AMOEBA_BASELINE = {  # img/s (BASELINE.md chart reads)
    (1024, 2): 3.0,
    (2048, 2): 5.1,
    (2048, 1): 2.9,
}

_T0 = time.monotonic()
_RESULT: dict = {}  # latest complete result; emitted incrementally
_LAST_RUN: dict = {}  # trainer/state/batch of the last successful measurement
_REGISTRY = None  # telemetry.MetricsRegistry, created in main()
_TELEMETRY_LOG = None  # telemetry.JsonlWriter (MPI4DL_TPU_TELEMETRY_DIR)


@functools.lru_cache(maxsize=1)
def _git_rev() -> str:
    """HEAD revision (keys the known-fatal sentinel: a cached failure
    verdict is only trusted while the code that produced it is unchanged).
    "unknown" — e.g. no git — never equals a stored rev, so it fails open
    (retry) rather than hiding a fix behind a stale verdict."""
    import subprocess

    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001
        return "unknown"


def sentinel_skip_reason(
    ent, now_rev: str, remaining_s: float, force_retry: bool
) -> "str | None":
    """Decide whether a known-fatal sentinel entry should skip the attempt.

    Returns a reason string to skip, or None to (re)run. Rules (VERDICT r3
    weak #6 + ADVICE r3 medium):

    - ``force_retry`` (BENCH_RETRY_FATAL=1) always reruns;
    - legacy string entries (pre-revision-keying) rerun — the code has
      certainly changed since they were written;
    - entries from a different (or unknowable) git revision rerun — a code
      change invalidates the verdict, so a fix can't be hidden by a stale
      cache;
    - "confirmed" entries at the current revision skip (the attempt
      genuinely raised, and nothing has changed);
    - "provisional" entries (attempt started, never concluded — a driver
      kill mid-compile) rerun ONCE when the budget still allows a full
      attempt including a possible fatal compile (~600 s); a second
      provisional marker at the same revision (``tries >= 2``) skips —
      a compile that outlives the driver's kill window twice would
      otherwise burn the tail of every future run (the repeated-doomed-
      compile loop the pre-mark exists to prevent). With a thinner budget
      they also skip, since starting a doomed compile would only re-create
      the same provisional marker.
    """
    if force_retry:
        return None
    if not isinstance(ent, dict):
        return None
    if ent.get("rev") != now_rev or now_rev == "unknown":
        return None
    if ent.get("status") == "confirmed":
        return (
            f"known-fatal (cached @{str(ent.get('rev', '?'))[:8]}): "
            + str(ent.get("msg", ""))[:80]
        )
    if int(ent.get("tries", 1)) >= 2:
        return (
            "provisional marker retried and never concluded twice at this "
            "revision — treating as fatal (BENCH_RETRY_FATAL=1 overrides)"
        )
    if remaining_s >= 600:
        return None
    return (
        "provisional marker (prior attempt never concluded); "
        "budget too thin to retry"
    )


def _emit():
    """Print the current result as one flushed JSON line (see module doc).
    Each line carries a ``telemetry`` snapshot in the JSONL metrics-event
    schema (mpi4dl_tpu.telemetry.jsonl), so BENCH_*.json records and the
    MPI4DL_TPU_TELEMETRY_DIR event log stay one schema."""
    if not _RESULT:
        return
    if _REGISTRY is not None and _REGISTRY.names():
        from mpi4dl_tpu import telemetry

        ev = telemetry.metrics_event(_REGISTRY)
        _RESULT["telemetry"] = ev
        if _TELEMETRY_LOG is not None:
            _TELEMETRY_LOG.write(ev)
    print(json.dumps(_RESULT), flush=True)


def _on_signal(signum, frame):  # noqa: ARG001
    # Re-emit what we have and exit hard: XLA teardown can hang, and the
    # driver only needs the stdout line.  Exit 0 only if a real value landed.
    if _RESULT.get("value") is not None:
        _RESULT.setdefault("note", f"interrupted by signal {signum}")
        _emit()
        os._exit(0)
    out = {
        "metric": "bench_interrupted",
        "value": None,
        "unit": "images/sec",
        "vs_baseline": None,
        "error": f"signal {signum} before any successful measurement",
    }
    for key in ("extras", "headline_error"):
        if _RESULT.get(key):
            out[key] = _RESULT[key]
    print(json.dumps(out), flush=True)
    os._exit(1)


def _budget() -> float:
    return float(os.environ.get("BENCH_TIME_BUDGET", "1800"))


def _remaining() -> float:
    return _budget() - (time.monotonic() - _T0)


def _train_throughput(
    cells, image_size, batch, steps, warmup, dtype, remat, grad_accum=1
):
    """img/s for a Trainer over the cell list under ONE remat policy (the
    fixed rule ``train.default_remat`` unless BENCH_REMAT names another):
    a compile that fails, fails the entry.
    grad_accum>1 runs the batch as scanned chunks (Trainer._accum_grads) —
    the full published batch size with a chunk-sized program/working set."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.train import Trainer

    cfg = ParallelConfig(
        batch_size=batch, split_size=1, spatial_size=0, image_size=image_size
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((batch, image_size, image_size, 3)), dtype
    )
    y = jnp.asarray(rng.integers(0, 10, size=(batch,)), jnp.int32)

    from mpi4dl_tpu.profiling import StepTimer

    trainer = Trainer(
        cells, num_spatial_cells=0, config=cfg, remat=remat,
        grad_accum=grad_accum,
    )
    xs, ys = trainer.shard_batch(x, y)
    state = trainer.init(jax.random.PRNGKey(0), x.shape, dtype=dtype)
    for _ in range(warmup):
        state, metrics = trainer.train_step(state, xs, ys)
    # A device-to-host READ (not just block_until_ready) forces the
    # dispatched chain to fully execute: the final loss value transitively
    # depends on every step in the chain, so one scalar read times the
    # real work.
    float(metrics["loss"])

    # Per-step timing (StepTimer): each step ends on the same forced
    # device READ as the warm-up (the readiness-without-execution guard
    # above), so the recorded times carry real per-step boundaries and the
    # summary's p50/p90/p99 are genuine step-latency tails — the statistic
    # the serving work needs result lines to carry. The per-step scalar
    # read costs one D2H round trip per multi-second step (<1% here) and
    # only tightens the measurement: dispatch pipelining can no longer
    # smear one slow step across its neighbors.
    timer = StepTimer(batch_size=batch, warmup=0, registry=_REGISTRY)
    for _ in range(steps):
        with timer.step():
            state, metrics = trainer.train_step(state, xs, ys)
            float(metrics["loss"])
    dt = sum(timer.times)
    if _REGISTRY is not None:
        trainer.publish_telemetry(_REGISTRY)
    # Stash the measured program for the post-headline static analysis
    # (mpi4dl_tpu.analysis): re-lowering it is a warm-cache no-op.
    _LAST_RUN.update(trainer=trainer, state=state, xs=xs, ys=ys)
    return batch * steps / dt, trainer.remat, timer.summary()


def _step_percentiles(steps_summary: dict) -> dict:
    """p50/p90/p99 step-time tails from a StepTimer summary — serving-grade
    tail statistics in every train result line, not just means."""
    return {
        p: round(steps_summary[f"step_time_{p}_s"], 4)
        for p in ("p50", "p90", "p99")
        if f"step_time_{p}_s" in steps_summary
    }


def _measure_serving() -> dict:
    """Online-serving extra: dynamic micro-batching throughput vs the
    batch-size-1 serial baseline (mpi4dl_tpu/serve, docs/SERVING.md) on a
    small calibrated AmoebaNet — many small ops per cell, the op-overhead-
    bound shape the per-call dispatch floor (~23 ms on the TPU runtime,
    PERF.md) penalizes hardest, i.e. where batching IS the serving story.
    The result line carries the tail percentiles serving is judged by."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.evaluate import collect_batch_stats
    from mpi4dl_tpu.models.amoebanet import amoebanetd
    from mpi4dl_tpu.parallel.partition import init_cells
    from mpi4dl_tpu.serve import ServingEngine
    from mpi4dl_tpu.serve.loadgen import run_closed_loop, serial_throughput

    size = 32
    cells = amoebanetd(num_classes=10, num_layers=3, num_filters=16)
    rng = np.random.default_rng(0)
    params = init_cells(
        cells, jax.random.PRNGKey(0), jnp.zeros((1, size, size, 3))
    )
    stats = collect_batch_stats(
        cells, params,
        [jnp.asarray(rng.standard_normal((4, size, size, 3)), jnp.float32)],
    )
    from mpi4dl_tpu.telemetry import SLOConfig

    engine = ServingEngine(
        cells, params, stats, example_shape=(size, size, 3),
        buckets=(1, 32), max_wait_s=0.003, max_queue=512,
        default_deadline_s=30.0, registry=_REGISTRY,
        # SLO evaluation on so every serving result line carries a
        # verdict (docs/OBSERVABILITY.md "SLOs & alerting"); interval
        # shortened because the whole load run lasts ~a second. A tight
        # availability objective with a loose latency threshold: the CPU
        # bench must flag dropped/rejected requests, not page on a slow
        # shared box.
        slo=SLOConfig(
            availability=0.999, latency_threshold_s=2.5,
            latency_target=0.99, interval_s=0.25,
        ),
    )
    serial = serial_throughput(engine, 32)
    attribute = os.environ.get("BENCH_ATTRIBUTION", "1") != "0"
    trace_dir = (
        tempfile.mkdtemp(prefix="mpi4dl-bench-serve-trace-")
        if attribute else None
    )
    engine.start()
    try:
        from contextlib import nullcontext

        from mpi4dl_tpu.profiling import trace as profiler_trace

        with profiler_trace(trace_dir) if attribute else nullcontext():
            rep = run_closed_loop(
                engine, 384, concurrency=96, deadline_s=30.0
            )
    finally:
        engine.stop()
    lint = engine.lint_report()
    attribution = _serving_attribution(trace_dir, lint) if attribute else None
    entry = {
        "value": round(rep["throughput_rps"], 1),
        "serial_bs1_rps": round(serial["throughput_rps"], 1),
        "speedup_vs_serial": round(
            rep["throughput_rps"] / serial["throughput_rps"], 2
        ),
        "latency_ms": {
            k: round(v * 1e3, 2)
            for k, v in rep["latency_s"].items()
            if v is not None
        },
        "mean_batch_size": round(rep["engine"]["mean_batch_size"], 1),
        "deadline_misses": rep["deadline_misses"],
        "rejected": rep["rejected_queue_full"],
        "lint_ok": lint.ok,
        "slo": engine.slo.verdict(),
        # Footprint ledger (docs/OBSERVABILITY.md "Memory"): each warmed
        # bucket's compile-time predicted peak, so BENCH_*.json records
        # the serving memory trajectory next to the throughput one
        # (bench-history trends it with an inverted regression sign).
        "peak_hbm_bytes_by_bucket": {
            str(b): e["peak_bytes"]
            for b in engine.buckets
            for e in [engine.memory_ledger.get("serve_predict", bucket=b)]
            if e is not None and e.get("peak_bytes") is not None
        },
    }
    # Phase mix + client-hop cost (docs/OBSERVABILITY.md "Federation &
    # distributed tracing"): the per-round trajectory of WHERE served
    # latency goes, next to the throughput it costs.
    if rep.get("client_overhead_s"):
        entry["client_overhead_ms"] = {
            k: round(v * 1e3, 3) for k, v in rep["client_overhead_s"].items()
        }
    # Tail forensics (docs/OBSERVABILITY.md "Tail forensics"): the
    # p99/p50 latency ratio — the tail's SHAPE, independent of the
    # box's absolute speed — trended by bench-history with the
    # regression sign inverted (a growing tail fails CI), plus how many
    # tail.samples the watcher captured this round.
    lat_p = rep.get("latency_s") or {}
    if lat_p.get("p50") and lat_p.get("p99"):
        entry["tail"] = {
            "p99_p50_ratio": round(lat_p["p99"] / lat_p["p50"], 3),
            "samples": engine.tail.captured,
            "threshold_ms": round(engine.tail.threshold() * 1e3, 3),
        }
    shares = engine.registry.get("serve_phase_share")
    if shares is not None:
        entry["phase_shares"] = {
            s["labels"]["phase"]: round(s["value"], 4)
            for s in shares.snapshot_series()
        }
    if attribution is not None:
        entry["attribution"] = attribution
    if not lint.ok:
        entry["lint_findings"] = [
            f for f in lint.findings if f["severity"] == "error"
        ]
    # Scheduler A/B (docs/SERVING.md "Scheduling"): the continuous EDF
    # scheduler vs the PR-2 FIFO windowed former, interleaved, under a
    # fixed mixed tight/bulk class load on the same model/config — the
    # per-arm tight-class p99 (and aggregate rps) land in the result
    # line so bench-history trends the EDF tail claim round over round
    # (growing tight p99 fails CI; BENCH_SCHED_AB=0 disables).
    if os.environ.get("BENCH_SCHED_AB", "1") != "0":
        entry["sched_ab"] = _measure_sched_ab(cells, params, stats)
    return entry


def _measure_sched_ab(cells, params, stats) -> dict:
    """Interleaved EDF-vs-FIFO A/B on the PR-2 serving config (32px
    AmoebaNet, buckets (1, 32)) under a fixed 1:3 tight:bulk class mix —
    tight requests carry a 10 s deadline, bulk 60 s, so EDF order lets
    tight jump the bulk backlog while FIFO serves arrival order. Both
    arms run the SAME deterministic mix (ClassMix is RNG-free); per-arm
    per-trial p99s are reduced by median across trials."""
    from mpi4dl_tpu.profiling import percentiles as _pct
    from mpi4dl_tpu.serve import ServingEngine
    from mpi4dl_tpu.serve.loadgen import run_closed_loop

    size = 32
    classes = "tight=250ms:99@10s,bulk=2.5s:99@60s"
    mix = {"tight": (1.0, 10.0), "bulk": (3.0, 60.0)}
    trials, requests = 3, 256
    engines = {
        arm: ServingEngine(
            cells, params, stats, example_shape=(size, size, 3),
            buckets=(1, 32), max_wait_s=0.003, max_queue=512,
            default_deadline_s=30.0, slo_classes=classes, scheduler=arm,
        )
        for arm in ("edf", "fifo")
    }
    samples = {
        arm: {"tight_p99": [], "bulk_p99": [], "rps": [], "misses": 0}
        for arm in engines
    }
    try:
        for eng in engines.values():
            eng.start()
        for _ in range(trials):
            for arm, eng in engines.items():
                rep = run_closed_loop(
                    eng, requests, concurrency=64, deadline_s=30.0,
                    class_mix=dict(mix),
                )
                by = rep["by_class"] or {}
                for cls, key in (("tight", "tight_p99"),
                                 ("bulk", "bulk_p99")):
                    p99 = (by.get(cls) or {}).get("latency_s", {}).get("p99")
                    if p99 is not None:
                        samples[arm][key].append(p99)
                samples[arm]["rps"].append(rep["throughput_rps"])
                samples[arm]["misses"] += rep["deadline_misses"]
    finally:
        for eng in engines.values():
            eng.stop()

    def _median(vals):
        return _pct(vals, (50,))["p50"] if vals else None

    arms = {
        arm: {
            "tight_p99_ms": (
                round(_median(s["tight_p99"]) * 1e3, 2)
                if s["tight_p99"] else None
            ),
            "bulk_p99_ms": (
                round(_median(s["bulk_p99"]) * 1e3, 2)
                if s["bulk_p99"] else None
            ),
            "rps": round(_median(s["rps"]), 1) if s["rps"] else None,
            "deadline_misses": s["misses"],
        }
        for arm, s in samples.items()
    }
    out = {
        "classes": classes,
        "mix": "tight:1:10s,bulk:3:60s",
        "trials": trials,
        "requests_per_trial": requests,
        "arms": arms,
    }
    edf, fifo = arms["edf"], arms["fifo"]
    if edf["tight_p99_ms"] and fifo["tight_p99_ms"]:
        out["tight_p99_improved"] = edf["tight_p99_ms"] < fifo["tight_p99_ms"]
        out["tight_p99_ratio"] = round(
            edf["tight_p99_ms"] / fifo["tight_p99_ms"], 3
        )
    if edf["rps"] and fifo["rps"]:
        out["rps_delta_pct"] = round(
            (edf["rps"] - fifo["rps"]) / fifo["rps"] * 100.0, 2
        )
    return out


def _measure_fleet() -> dict:
    """Fleet recovery extra (docs/FLEET.md): ONE fleet — 2 replica
    subprocesses + 1 warm-pool standby behind 2 front-door router
    processes — put through BOTH kill drills under closed-loop load:

    - arm ``replica``: ``kill -9`` a serving replica mid-run; with the
      warm pool on, recovery is a standby promotion (routing flip), so
      ``recovery_s.replica`` is handshake-bound (< 2 s target on CPU vs
      ~7 s warm-up-compile cold), and the pool backfills afterward;
    - arm ``router``: ``kill -9`` a router process mid-run; the client
      fails over (``router_failovers``), the supervisor respawns the
      slot, and the successor replays the journal
      (``journal_replays`` > 0); ``recovery_s.router`` is the router's
      death-to-ready time.

    bench-history trends ``recovery_s.replica`` and
    ``recovery_s.router`` with the regression sign inverted. The
    workers are pinned to the CPU backend: this bench process owns the
    accelerator, and the mechanics under measurement — dispatch,
    failover, journal replay, promotion — are host-side."""
    import signal as _signal
    import threading

    from mpi4dl_tpu.fleet.__main__ import _journal_replays
    from mpi4dl_tpu.fleet.frontdoor import RouterSetClient
    from mpi4dl_tpu.fleet.supervisor import FleetSupervisor
    from mpi4dl_tpu.serve.loadgen import run_closed_loop

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    n_requests = 600
    sup = FleetSupervisor(
        ["--image-size", "16", "--max-batch", "2"],
        router=None, registry=_REGISTRY,
        replicas=2, max_replicas=2, warm_pool=1,
        routers=2,
        router_args=["--image-size", "16", "--max-attempts", "4",
                     "--inflight-per-replica", "4",
                     "--health-interval", "0.1"],
        env=env,
        reconcile_interval_s=0.1, backoff_base_s=0.1,
        backoff_max_s=0.5, spawn_timeout_s=420.0,
    )
    client = None
    try:
        t0 = time.monotonic()
        sup.start()
        sup.wait_ready(timeout_s=420)
        startup_s = time.monotonic() - t0
        client = RouterSetClient(
            sup.router_submit_urls(), example_shape=(16, 16, 3),
            default_deadline_s=120.0,
        )

        def drill(kill) -> dict:
            rep: dict = {}

            def load():
                rep.update(run_closed_loop(
                    client, n_requests, concurrency=12, deadline_s=120.0,
                ))

            t = threading.Thread(target=load, name="fleet-drill-load")
            t.start()
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if client.stats()["submitted"] >= n_requests // 10:
                    break
                time.sleep(0.01)
            kill()
            t.join(timeout=300)
            return rep

        # Arm 1 — replica kill with the warm pool on: recovery is a
        # promotion, and the pool backfills (cold) afterward.
        rep_a = drill(lambda: os.kill(
            sup.slot_by_index(1).pid, _signal.SIGKILL
        ))
        recovery_replica = sup.last_recovery_s
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if (sup.running_count() == 2 and sup.standby_count() == 1):
                break
            time.sleep(0.2)
        backfilled = sup.standby_count() == 1

        # Arm 2 — router kill: client failover + journal replay on the
        # respawned slot.
        rep_b = drill(lambda: os.kill(
            sup.router_slot_by_index(1).pid, _signal.SIGKILL
        ))
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if sup.running_router_count() == 2:
                break
            time.sleep(0.2)
        recovery_router = sup.last_router_recovery_s
        replays = _journal_replays(sup)
        return {
            "value": round(rep_a["throughput_rps"], 1),
            "unit": "requests/sec through a kill -9 replica drill "
                    "(HTTP front door, warm pool on)",
            "served": rep_a["served"] + rep_b["served"],
            "offered": 2 * n_requests,
            "errors": rep_a["errors"] + rep_b["errors"],
            "router_kill_rps": round(rep_b["throughput_rps"], 1),
            "router_failovers": rep_b.get("router_failovers", 0),
            "journal_replays": replays,
            "promotions": sup.promotions,
            "pool_backfilled": backfilled,
            "restarts": sup.restarts,
            "recovery_s": {
                "replica": (
                    round(recovery_replica, 2)
                    if recovery_replica is not None else None
                ),
                "router": (
                    round(recovery_router, 2)
                    if recovery_router is not None else None
                ),
            },
            "startup_s": round(startup_s, 2),
            "latency_ms": {
                k: round(v * 1e3, 2)
                for k, v in rep_a["latency_s"].items() if v is not None
            },
        }
    finally:
        sup.close()
        if client is not None:
            client.close()


def _measure_incident() -> dict:
    """Incident-engine drill extra (docs/OBSERVABILITY.md "Incidents"):
    the replica kill drill again, but SCORED by the incident engine —
    a standalone :class:`FederatedAggregator` (0.1 s scrape tick, the
    stock :class:`IncidentManager` riding its alert surface) watches
    both replicas while ``chaos.inject("kill:1")`` lands the fault.

    Recorded per ISSUE: ``mttd_s`` (page→incident-open, the open
    record's MTTA), ``mttr_s`` (open→close), and ``blame_correct`` —
    whether the auto-postmortem's first cause names the injected chaos
    op. bench-history trends ``incident.mttd_s`` / ``incident.mttr_s``
    with the regression sign INVERTED (slower detection or recovery
    regresses); rounds that never detect/close omit the field
    (absent-not-zero). Throughput through the fault rides ``value``."""
    import threading

    from mpi4dl_tpu import telemetry
    from mpi4dl_tpu.fleet.chaos import inject, parse_chaos_spec
    from mpi4dl_tpu.fleet.frontdoor import RouterSetClient
    from mpi4dl_tpu.fleet.supervisor import FleetSupervisor
    from mpi4dl_tpu.serve.loadgen import run_closed_loop

    repo = os.path.dirname(os.path.abspath(__file__))
    tele = tempfile.mkdtemp(prefix="mpi4dl-bench-incident-")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
        MPI4DL_TPU_TELEMETRY_DIR=tele,
    )
    n_requests = 400
    events = telemetry.JsonlWriter(tele, filename="fleet-events.jsonl")
    sup = FleetSupervisor(
        ["--image-size", "16", "--max-batch", "2"],
        router=None, registry=_REGISTRY,
        replicas=2, max_replicas=2, warm_pool=1,
        routers=2,
        router_args=["--image-size", "16", "--max-attempts", "4",
                     "--inflight-per-replica", "4",
                     "--health-interval", "0.1"],
        env=env, events=events,
        reconcile_interval_s=0.1, backoff_base_s=0.1,
        backoff_max_s=0.5, spawn_timeout_s=420.0,
    )
    agg = None
    client = None
    try:
        sup.start()
        sup.wait_ready(timeout_s=420)

        def serving_urls() -> dict:
            urls = {}
            for i in range(3):
                s = sup.slot_by_index(i)
                if (s is not None and s.state == "running"
                        and s.role == "serving" and s.ports
                        and s.ports.get("metrics_port")):
                    urls[s.name] = (
                        f"http://127.0.0.1:{s.ports['metrics_port']}"
                    )
            return urls

        # The watcher: its own aggregator so the drill controls target
        # membership (the supervisor-integrated one deregisters a slot
        # on confirmed death, which this drill reproduces by hand after
        # recovery). Shares the fleet's event log so incident lifecycle
        # events interleave with chaos.injected / elastic.restart.
        agg = telemetry.FederatedAggregator(
            replicas=serving_urls(), events=events,
            interval_s=0.1, timeout_s=0.5,
        )
        agg.incidents.telemetry_dir = tele
        agg.start()

        client = RouterSetClient(
            sup.router_submit_urls(), example_shape=(16, 16, 3),
            default_deadline_s=120.0,
        )
        rep: dict = {}

        def load():
            rep.update(run_closed_loop(
                client, n_requests, concurrency=12, deadline_s=120.0,
            ))

        t = threading.Thread(target=load, name="incident-drill-load")
        t.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if client.stats()["submitted"] >= n_requests // 10:
                break
            time.sleep(0.01)
        t_kill = time.monotonic()
        inject(parse_chaos_spec("kill:1"), sup)

        # Detection: injected fault → replica_unreachable page → open.
        kill_to_open = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if agg.incidents.opened_total > 0:
                kill_to_open = time.monotonic() - t_kill
                break
            time.sleep(0.02)
        mttd = None
        inc = agg.incidents.open_incident
        if inc is not None and isinstance(inc.get("mtta_s"), (int, float)):
            mttd = inc["mtta_s"]
        t.join(timeout=300)

        # Recovery: wait for the promotion/backfill, then swap the
        # scrape set to the post-recovery serving slots — the target
        # swap the supervisor performs on confirmed death + handshake.
        # The next clean scrape resolves the page and closes the
        # incident.
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if sup.running_count() == 2 and serving_urls():
                break
            time.sleep(0.1)
        live = serving_urls()
        for tgt in list(agg.replicas()):
            if tgt.name not in live:
                agg.remove_replica(tgt.name)
        for name, url in live.items():
            agg.add_replica(name, url)
        mttr = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if agg.incidents.closed_total > 0:
                break
            time.sleep(0.02)
        state = agg.incidents.state()
        pm = (state["closed"] or state["open"] or [None])[-1]
        if state["closed"]:
            v = pm["incident"].get("mttr_s")
            if isinstance(v, (int, float)):
                mttr = v
            if mttd is None and isinstance(
                pm["incident"].get("mtta_s"), (int, float)
            ):
                mttd = pm["incident"]["mtta_s"]
        cause = (pm or {}).get("first_cause") or {}
        blame_correct = bool(
            cause.get("event") == "chaos.injected"
            and str((cause.get("attrs") or {}).get("op", "")).startswith(
                "kill"
            )
        )
        out = {
            "value": round(rep.get("throughput_rps", 0.0), 1),
            "unit": "requests/sec through a chaos kill drill scored by "
                    "the incident engine",
            "served": rep.get("served"),
            "errors": rep.get("errors"),
            "incidents_opened": agg.incidents.opened_total,
            "incidents_closed": agg.incidents.closed_total,
            "blame_correct": blame_correct,
            "first_cause": cause.get("label"),
        }
        # Absent-not-zero: a round that never detected (or never
        # closed) records NO latency rather than a flattering 0.
        if mttd is not None:
            out["mttd_s"] = round(mttd, 3)
        if kill_to_open is not None:
            out["kill_to_open_s"] = round(kill_to_open, 3)
        if mttr is not None:
            out["mttr_s"] = round(mttr, 3)
        return out
    finally:
        if agg is not None:
            agg.close()
        sup.close()
        if client is not None:
            client.close()


def _measure_coldstart() -> dict:
    """Cold-start decomposition extra (docs/OBSERVABILITY.md "Cold
    start"): two single-replica fleets, one ``kill -9`` each —

    - arm ``cold``: no warm pool — recovery is a full respawn, and the
      worker's ready handshake attributes every second of it across
      ``spawn/import/construct/compile/warm/ready``;
    - arm ``promote``: warm pool of 1 — recovery is a standby
      promotion, attributed honestly as all ``ready`` (routing flip)
      with ``compile == 0``: the phase evidence the pool's idle RAM
      buys the skipped phases.

    bench-history trends ``recovery_s.{cold,promote}`` and every
    ``phase_s.{arm}.{phase}`` with the INVERTED sign; the headline
    ``value`` is the promotion speedup (cold / promote recovery, normal
    sign). The worker ledger dumps collected before teardown feed
    ``analyze coldstart`` — the top executables by compile seconds land
    in ``manifest``."""
    import signal as _signal

    from mpi4dl_tpu.analysis.coldstart import build_manifest
    from mpi4dl_tpu.fleet.supervisor import FleetSupervisor

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )

    def drill(warm_pool: int) -> dict:
        # --max-batch 4 → three serve buckets (1, 2, 4): the manifest's
        # top-3 ranking has three real executables to name.
        sup = FleetSupervisor(
            ["--image-size", "16", "--max-batch", "4"],
            router=None, registry=_REGISTRY,
            replicas=1, max_replicas=1, warm_pool=warm_pool,
            env=env,
            reconcile_interval_s=0.1, backoff_base_s=0.1,
            backoff_max_s=0.5, spawn_timeout_s=420.0,
        )
        try:
            sup.start()
            sup.wait_ready(timeout_s=420)
            os.kill(sup.slot_by_index(0).pid, _signal.SIGKILL)
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                if sup.last_recovery_s is not None and sup.running_count() >= 1:
                    break
                time.sleep(0.05)
            # The replacement's ledger dump (written next to its ready
            # file) must be read BEFORE close() tears the run dir down.
            ledgers = []
            for i in range(2):
                slot = sup.slot_by_index(i)
                path = (slot.ports or {}).get("ledger") if slot else None
                if path and os.path.exists(path):
                    ledgers.append(path)
            manifest = (
                build_manifest(ledgers, top=3) if ledgers else None
            )
            return {
                "recovery_s": sup.last_recovery_s,
                "phases": dict(sup.last_recovery_phases or {}),
                "promotions": sup.promotions,
                "manifest": manifest,
            }
        finally:
            sup.close()

    cold = drill(0)
    promote = drill(1)
    manifest = promote["manifest"] or cold["manifest"]
    speedup = None
    if cold["recovery_s"] and promote["recovery_s"]:
        speedup = round(cold["recovery_s"] / promote["recovery_s"], 1)
    return {
        "value": speedup,
        "unit": "x promotion speedup (cold respawn s / warm-pool "
                "promote s, kill -9 to routable)",
        "recovery_s": {
            "cold": (
                round(cold["recovery_s"], 2)
                if cold["recovery_s"] is not None else None
            ),
            "promote": (
                round(promote["recovery_s"], 2)
                if promote["recovery_s"] is not None else None
            ),
        },
        "phases": {
            "cold": {k: round(v, 3) for k, v in cold["phases"].items()},
            "promote": {
                k: round(v, 3) for k, v in promote["phases"].items()
            },
        },
        "promotions": promote["promotions"],
        "top_executables": [
            {
                "executable": g["executable"],
                "fingerprint": g["fingerprint"],
                "compile_s": g["compile_s"],
            }
            for g in (manifest or {}).get("executables", [])
        ],
    }


def _measure_multitenant() -> dict:
    """Multi-tenant QoS extra (docs/SERVING.md "Multi-tenancy"): one
    small engine, three closed-loop rounds —

    - ``off``: tenancy disabled — the zero-overhead baseline;
    - ``solo``: tenancy on, the victim tenant alone — its clean p99;
    - ``flood``: a 10:1 bully:victim noisy-neighbor flood through the
      deficit-weighted-round-robin batch fill.

    bench-history trends ``victim_p99_ratio`` (flood p99 / solo p99,
    INVERTED sign — a growing ratio means tenant isolation regressed)
    and ``fairness_index`` (Jain's index over per-tenant served/offered,
    normal sign — falling fairness regresses); ``overhead_pct`` records
    the tenancy-on tax vs the off baseline (docs target: within 2%)."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.evaluate import collect_batch_stats
    from mpi4dl_tpu.models.resnet import get_resnet_v2
    from mpi4dl_tpu.parallel.partition import init_cells
    from mpi4dl_tpu.serve import ServingEngine
    from mpi4dl_tpu.serve.loadgen import run_closed_loop
    from mpi4dl_tpu.utils import get_depth

    size = 16
    cells = get_resnet_v2(
        depth=get_depth(2, 1), num_classes=10, pool_kernel=size // 4
    )
    rng = np.random.default_rng(0)
    params = init_cells(
        cells, jax.random.PRNGKey(0), jnp.zeros((1, size, size, 3))
    )
    stats = collect_batch_stats(
        cells, params,
        [jnp.asarray(rng.standard_normal((4, size, size, 3)), jnp.float32)],
    )

    def mk_engine(**kw):
        return ServingEngine(
            cells, params, stats, example_shape=(size, size, 3),
            max_batch=8, max_queue=512, default_deadline_s=60.0, **kw
        )

    n = 512
    eng_off = mk_engine()
    eng_off.start()
    try:
        # Warm-up pass first: bucket compiles and allocator churn must
        # not land inside either arm of the ON/OFF overhead comparison.
        run_closed_loop(eng_off, 64, concurrency=32, deadline_s=60.0)
        off = run_closed_loop(eng_off, n, concurrency=32, deadline_s=60.0)
    finally:
        eng_off.stop()

    eng = mk_engine(tenants="victim=none,bully=none", registry=_REGISTRY)
    eng.start()
    try:
        run_closed_loop(
            eng, 64, concurrency=32, deadline_s=60.0,
            tenant_mix={"victim": 1.0},
        )
        solo = run_closed_loop(
            eng, n, concurrency=32, deadline_s=60.0,
            tenant_mix={"victim": 1.0},
        )
        flood = run_closed_loop(
            eng, n, concurrency=32, deadline_s=60.0,
            tenant_mix={"bully": 10.0, "victim": 1.0},
        )
    finally:
        eng.stop()

    solo_p99 = solo["by_tenant"]["victim"]["latency_s"]["p99"]
    flood_p99 = flood["by_tenant"]["victim"]["latency_s"]["p99"]
    served = {t: rec["served"] for t, rec in flood["by_tenant"].items()}
    offered = {"bully": 10.0, "victim": 1.0}
    xs = [served[t] / offered[t] for t in served if t in offered]
    jain = (
        sum(xs) ** 2 / (len(xs) * sum(x * x for x in xs)) if any(xs) else 0.0
    )
    on_rps = solo["throughput_rps"]
    off_rps = off["throughput_rps"]
    return {
        "value": round(on_rps, 1),
        "unit": "requests/sec with tenancy on (single tenant)",
        "off_rps": round(off_rps, 1),
        "overhead_pct": round((off_rps - on_rps) / off_rps * 100.0, 2),
        # Noisy-neighbor isolation: how much the 10:1 flood inflates the
        # victim's p99 over its solo baseline (1.0 == perfect isolation).
        "victim_p99_ratio": round(flood_p99 / max(solo_p99, 1e-9), 3),
        "victim_p99_ms": {
            "solo": round(solo_p99 * 1e3, 2),
            "flood": round(flood_p99 * 1e3, 2),
        },
        "fairness_index": round(jain, 4),
        "served_by_tenant": served,
        "deadline_misses": flood["deadline_misses"],
        "rejected_quota": flood["rejected_quota"],
    }


def _measure_numerics() -> dict:
    """Numerics sentinel extra (docs/OBSERVABILITY.md "Numerics"): one
    small engine, two closed-loop arms plus a corrupt drill —

    - ``off``: no canary sentinel — the zero-overhead baseline;
    - ``on``: sentinel probing every 0.2s through the real dispatch
      path (the deployment posture; docs target: within 2% rps);
    - the drill: flip 3 bits in the live param buffer and time
      corruption → fence (``canary.failure`` callback).

    bench-history trends ``rps_overhead_pct`` and ``detect_s``, both
    INVERTED — a grown canary tax or a slower detection regresses."""
    import threading

    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.evaluate import collect_batch_stats
    from mpi4dl_tpu.models.resnet import get_resnet_v2
    from mpi4dl_tpu.parallel.partition import init_cells
    from mpi4dl_tpu.serve import ServingEngine
    from mpi4dl_tpu.serve.loadgen import run_closed_loop
    from mpi4dl_tpu.utils import get_depth

    size = 16
    cells = get_resnet_v2(
        depth=get_depth(2, 1), num_classes=10, pool_kernel=size // 4
    )
    rng = np.random.default_rng(0)
    params = init_cells(
        cells, jax.random.PRNGKey(0), jnp.zeros((1, size, size, 3))
    )
    stats = collect_batch_stats(
        cells, params,
        [jnp.asarray(rng.standard_normal((4, size, size, 3)), jnp.float32)],
    )

    def mk_engine(**kw):
        return ServingEngine(
            cells, params, stats, example_shape=(size, size, 3),
            max_batch=8, max_queue=512, default_deadline_s=60.0, **kw
        )

    n = 512
    eng_off = mk_engine()
    eng_off.start()
    try:
        # Warm-up pass first (same discipline as the multitenant A/B):
        # compiles and allocator churn stay out of both arms.
        run_closed_loop(eng_off, 64, concurrency=32, deadline_s=60.0)
        off = run_closed_loop(eng_off, n, concurrency=32, deadline_s=60.0)
    finally:
        eng_off.stop()

    interval = 0.2
    eng = mk_engine(canary_interval_s=interval, registry=_REGISTRY)
    fence_at: dict = {}
    fenced = threading.Event()

    def _on_failure(attrs):
        fence_at.setdefault("t", time.perf_counter())
        fence_at.setdefault("check", attrs.get("check"))
        fenced.set()

    eng.canary.on_failure(_on_failure)
    eng.start()
    try:
        run_closed_loop(eng, 64, concurrency=32, deadline_s=60.0)
        on = run_closed_loop(eng, n, concurrency=32, deadline_s=60.0)
        # Corrupt drill AFTER the measured arm: detection latency is
        # the metric here, the fenced engine's rps is not.
        t0 = time.perf_counter()
        forensics = eng.corrupt_params(bits=3)
        detected = fenced.wait(timeout=max(10.0, 20 * interval))
        view = eng.canary.view()
    finally:
        eng.stop()

    on_rps = on["throughput_rps"]
    off_rps = off["throughput_rps"]
    entry = {
        "value": round(on_rps, 1),
        "unit": "requests/sec with canary sentinel on",
        "off_rps": round(off_rps, 1),
        "rps_overhead_pct": round((off_rps - on_rps) / off_rps * 100.0, 2),
        "canary_interval_s": interval,
        "detected": bool(detected),
        "detect_check": fence_at.get("check"),
        "corrupt": {"bits": 3, "leaf": forensics.get("leaf")},
        "canary_checks": view.get("checks"),
        "canary_failures": view.get("failures"),
    }
    if detected:
        entry["detect_s"] = round(fence_at["t"] - t0, 3)
    return entry


def _measure_sp_overlap() -> dict:
    """SP 2×2 halo/compute-overlap A/B extra: run the spatially-
    partitioned train step with the monolithic AND the decomposed conv
    impl (``MPI4DL_TPU_CONV_OVERLAP``) and embed both arms' measured
    ``trace_overlap_ratio`` + step time in the result line — the number
    ``analyze bench-history`` trends (a falling ratio regresses). Runs as
    a subprocess on a 4-virtual-device CPU mesh: this bench process owns
    the accelerator (one chip — no 2×2 tile mesh exists on it), and the
    property under measurement is the compiled program's schedule freedom,
    which the CPU thunk executor exhibits the same way."""
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    # Each arm pins its own impl; an inherited process-wide override
    # would silently collapse the A/B into one arm measured twice.
    env.pop("MPI4DL_TPU_CONV_OVERLAP", None)
    proc = subprocess.run(
        [sys.executable, "-m", "mpi4dl_tpu.analyze", "sp-overlap",
         "--size", "64", "--steps", "4", "--trials", "3", "--json", "-"],
        env=env, capture_output=True, text=True, timeout=900, cwd=repo,
    )
    line = next(
        (ln for ln in reversed(proc.stdout.splitlines())
         if ln.startswith("{")), None,
    )
    if line is None:
        raise RuntimeError(
            f"sp-overlap emitted no JSON (rc={proc.returncode}): "
            f"{proc.stderr[-300:]}"
        )
    out = json.loads(line)
    out["rc"] = proc.returncode
    return out


def _measure_serving_sharded() -> dict:
    """Sharded-serving overlap A/B extra: a 2×2 spatially-sharded engine
    under closed-loop load with the monolithic AND decomposed conv impl,
    embedding both arms' measured ``trace_overlap_ratio`` + per-request
    latency (``analyze bench-history`` trends the ratio normal-sign and
    p99 inverted). Same subprocess rationale as ``_measure_sp_overlap``:
    the 4-virtual-device CPU tile mesh must exist regardless of the
    bench headline's backend, and the property under measurement is the
    compiled schedule's freedom, not CPU wall-clock."""
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    env.pop("MPI4DL_TPU_CONV_OVERLAP", None)
    proc = subprocess.run(
        [sys.executable, "-m", "mpi4dl_tpu.analyze", "serving-sharded",
         "--size", "32", "--requests", "64", "--trials", "2",
         "--json", "-"],
        env=env, capture_output=True, text=True, timeout=900, cwd=repo,
    )
    line = next(
        (ln for ln in reversed(proc.stdout.splitlines())
         if ln.startswith("{")), None,
    )
    if line is None:
        raise RuntimeError(
            f"serving-sharded emitted no JSON (rc={proc.returncode}): "
            f"{proc.stderr[-300:]}"
        )
    out = json.loads(line)
    out["rc"] = proc.returncode
    return out


def _measure_pipeline() -> dict:
    """Pipeline schedule A/B extra: the LP pipeline train step under the
    gpipe AND interleaved-1f1b schedules (``analyze pipeline``), embedding
    both arms' measured ``pipeline_bubble_fraction`` + img/s in the result
    line — bench-history trends the bubble per arm with the INVERTED sign
    (a grown bubble regresses) and img/s with the normal sign. Same
    subprocess rationale as ``_measure_sp_overlap``: the pipe mesh must
    exist regardless of the bench headline's backend, and the property
    under measurement — which stage-switch slots the compiled schedule
    executes — is backend-independent."""
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    # trials=1: the bubble is slot-counted off the compiled schedule's
    # branch executions — deterministic, unlike the wall-clock ratios the
    # overlap A/Bs pool across interleaved trials — so extra trials only
    # buy img/s averaging at real CPU cost.
    proc = subprocess.run(
        [sys.executable, "-m", "mpi4dl_tpu.analyze", "pipeline",
         "--steps", "3", "--trials", "1", "--require-improvement",
         "--json", "-"],
        env=env, capture_output=True, text=True, timeout=900, cwd=repo,
    )
    line = next(
        (ln for ln in reversed(proc.stdout.splitlines())
         if ln.startswith("{")), None,
    )
    if line is None:
        raise RuntimeError(
            f"analyze pipeline emitted no JSON (rc={proc.returncode}): "
            f"{proc.stderr[-300:]}"
        )
    out = json.loads(line)
    out["rc"] = proc.returncode
    return out


def _measure_tiled_gigapixel() -> dict:
    """Gigapixel tiled-inference extra (serve/tiled.py): (a) a peak
    feasible px WALK — the largest square image one chip serves through
    the halo-correct tile stream, each success recorded with the tile
    executable's compile-time peak so the round file shows bounded-not-
    full-image memory; (b) per-request latency at a FIXED large size
    under a small closed loop, with the tile-count/stitch breakdown.
    bench-history trends ``tiled_gigapixel.peak_px`` (normal sign — a
    shrunk capability regresses) and ``tiled_gigapixel.latency_p99_ms``
    (INVERTED — slower gigapixel requests regress). Sizes scale by
    backend: CPU walks 256→512 so the extra stays in budget; a TPU round
    starts at 8192 (past the single-chip monolithic wall) by default.
    ``BENCH_TILED_PX``/``BENCH_TILED_TILE``/``BENCH_TILED_WALK``
    override."""
    import jax
    import numpy as np

    from mpi4dl_tpu.serve.loadgen import run_closed_loop
    from mpi4dl_tpu.serve.tiled import synthetic_tiled_engine

    on_cpu = jax.default_backend() == "cpu"
    fixed_px = int(
        os.environ.get("BENCH_TILED_PX", "256" if on_cpu else "8192")
    )
    tile = int(
        os.environ.get("BENCH_TILED_TILE", str(max(64, fixed_px // 4)))
    )
    walk_steps = int(os.environ.get("BENCH_TILED_WALK", "1"))
    engine_kw = dict(
        tile=tile, max_queue=8, calib_batches=1,
        default_deadline_s=1200.0,
    )
    entry = {
        "unit": "square image side, one chip, tiled stream",
        "tile": tile,
        "walk": [],
        "peak_px": None,
    }

    # (a) Peak feasible px walk: double from the fixed size; each
    # success is recorded immediately (the next, larger, attempt is
    # expected to eventually fail — on TPU with RESOURCE_EXHAUSTED at
    # the head, on CPU only by budget).
    px = fixed_px
    for _ in range(walk_steps + 1):
        t0 = time.time()
        step = {"px": px}
        try:
            eng = synthetic_tiled_engine(px, **engine_kw)
            try:
                eng.start()
                fut = eng.submit(
                    np.zeros((px, px, 3), np.float32), deadline_s=1200.0
                )
                fut.result(timeout=1200.0)
                tile_e = eng.memory_ledger.get("serve_tiled", bucket=1)
                head_e = eng.memory_ledger.get("serve_tiled_head")
                step.update(
                    serve_s=round(time.time() - t0, 2),
                    tile_peak_hbm_bytes=(
                        tile_e.get("peak_bytes") if tile_e else None
                    ),
                    head_peak_hbm_bytes=(
                        head_e.get("peak_bytes") if head_e else None
                    ),
                )
                entry["peak_px"] = px
            finally:
                eng.stop()
        except Exception as e:  # noqa: BLE001 — the walk's whole point
            # is to find the failure edge without losing the peak
            step["error"] = f"{type(e).__name__}: {str(e)[:160]}"
            entry["walk"].append(step)
            break
        entry["walk"].append(step)
        px *= 2

    # (b) Latency at the fixed size: a small closed loop (gigapixel
    # traffic is low-rps by nature; the tail percentiles and the
    # tile/stitch split are the serving numbers that matter).
    eng = synthetic_tiled_engine(fixed_px, **engine_kw)
    try:
        eng.start()
        rep = run_closed_loop(
            eng, 6 if on_cpu else 4, concurrency=2, deadline_s=1200.0
        )
    finally:
        eng.stop()
    lint = eng.lint_report()
    entry.update(
        image_px=fixed_px,
        latency_ms={
            k: round(v * 1e3, 1)
            for k, v in rep["latency_s"].items() if v is not None
        },
        served=rep["served"],
        errors=rep["errors"],
        deadline_misses=rep["deadline_misses"],
        tiled=rep["engine"].get("tiled"),
        lint_ok=lint.ok,
    )
    return entry


def _serving_attribution(trace_dir, lint_report) -> "dict | None":
    """Measured device-time attribution of the serving load run
    (analysis/trace.py over the engine's own ``mpi4dl_serve_batch``
    annotations), cross-checked against the single-chip static lint.
    Advisory: failures degrade to an error note. ``BENCH_ATTRIBUTION=0``
    disables (checked by the caller, which then skips the trace too)."""
    import shutil

    try:
        from mpi4dl_tpu.analysis.trace import (
            analyze_trace_dir,
            crosscheck_overlap,
            publish_attribution,
        )

        summary = analyze_trace_dir(
            trace_dir, step_name="mpi4dl_serve_batch"
        )
        if _REGISTRY is not None:
            publish_attribution(summary, _REGISTRY, program="serve_batch")
        checks = crosscheck_overlap(lint_report, summary)
        return {
            "n_steps": summary["n_steps"],
            "per_step_mean": summary["per_step_mean"],
            "range": summary["range"],
            "overlap": summary["collective"],
            "crosscheck": [f.as_dict() for f in checks],
        }
    except Exception as e:  # noqa: BLE001 — advisory metrics only
        return {"error": f"{type(e).__name__}: {str(e)[:160]}"}
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _hlo_overlap_metrics() -> "dict | None":
    """Static overlap/bytes/peak-HBM metrics of the LAST measured program,
    recorded into the emitted result line (and thus ``BENCH_*.json``) via
    the hlolint analyzer. ``BENCH_HLO=0`` disables; failures degrade to an
    error note — the analysis must never cost a measured headline."""
    if os.environ.get("BENCH_HLO", "1") == "0" or not _LAST_RUN:
        return None
    try:
        import jax

        from mpi4dl_tpu.analysis import analyze_compiled

        tr = _LAST_RUN["trainer"]
        compiled = tr._jit_step.lower(
            _LAST_RUN["state"], _LAST_RUN["xs"], _LAST_RUN["ys"]
        ).compile()
        rep = analyze_compiled(
            compiled,
            remat=tr.remat_report(),
            platform=jax.devices()[0].platform,
            config={"program": "train_step"},
        )
        if _REGISTRY is not None:
            from mpi4dl_tpu.analysis.metrics import publish_report

            publish_report(rep, _REGISTRY)
            # Footprint ledger: the already-compiled train step's peak
            # under program_peak_hbm_bytes (zero extra compile).
            from mpi4dl_tpu.telemetry.memory import FootprintLedger

            FootprintLedger(registry=_REGISTRY).record_compiled(
                "train_step", compiled
            )
        # The static report is the "should overlap" side the measured
        # trace attribution cross-checks against (_trace_attribution).
        _LAST_RUN["lint_report"] = rep
        # Static cost model (docs/ANALYSIS.md "Reading the cost model"):
        # price the same collective inventory under the live CPU prior and
        # the ICI prior, so BENCH_*.json carries the predicted comms time
        # and overlap ceiling next to the measured numbers and
        # `analyze bench-history` can trend predicted-vs-measured drift.
        from mpi4dl_tpu.analysis.costmodel import (
            predict_from_report,
            publish_prediction,
        )

        costmodel = {}
        for ic in ("cpu", "ici"):
            pred = predict_from_report(rep, interconnect=ic)
            costmodel[ic] = {
                "comms_s": pred["comms_s"],
                "exposed_s": pred["exposed_s"],
                "predicted_overlap_ratio": pred["overlap_ratio"],
                "overlap_claim": pred["overlap_claim"],
            }
            if _REGISTRY is not None:
                publish_prediction(pred, _REGISTRY, program="train_step")
            if ic == "cpu":
                # The prior matching the runtime we actually measure on;
                # _trace_attribution cross-checks drift against this one.
                _LAST_RUN["costmodel_pred"] = pred
        return {
            "costmodel": costmodel,
            "inventory": {k: v for k, v in rep.inventory.items() if v},
            "total_collective_bytes": rep.overlap["total_bytes"],
            "bytes_by_op": rep.overlap["bytes_by_op"],
            "async_pairs": rep.overlap["async_pairs"],
            "zero_overlap": len(rep.overlap["zero_overlap"]),
            "min_compute_between": rep.overlap["min_compute_between"],
            "peak_hbm_bytes": (
                rep.memory.get("peak_bytes") if rep.memory else None
            ),
            "findings": [
                f for f in rep.findings if f["severity"] != "info"
            ],
        }
    except Exception as e:  # noqa: BLE001 — advisory metrics only
        return {"error": f"{type(e).__name__}: {str(e)[:160]}"}


def _trace_attribution() -> "dict | None":
    """MEASURED device-time attribution of the headline train step: a
    2-step XProf capture (Trainer.capture_trace_attribution), bucketed
    compute/collective/transfer/host-gap + the measured-overlap verdict,
    cross-checked against the static hlolint report when one landed.
    BENCH_*.json thereby records the measured overlap trajectory next to
    the static prediction. ``BENCH_ATTRIBUTION=0`` disables; failures
    degrade to an error note."""
    if (
        os.environ.get("BENCH_ATTRIBUTION", "1") == "0"
        or not _LAST_RUN
    ):
        return None
    import shutil

    logdir = tempfile.mkdtemp(prefix="mpi4dl-bench-train-trace-")
    try:
        tr = _LAST_RUN["trainer"]
        state, summary = tr.capture_trace_attribution(
            _LAST_RUN["state"], _LAST_RUN["xs"], _LAST_RUN["ys"],
            steps=2, logdir=logdir, registry=_REGISTRY,
            program="train_step",
        )
        _LAST_RUN["state"] = state
        from mpi4dl_tpu.ops.layers import conv_overlap_impl

        out = {
            "n_steps": summary["n_steps"],
            "per_step_mean": summary["per_step_mean"],
            "overlap": summary["collective"],
            # Which spatial-conv impl produced this attribution: the
            # monolithic/decomposed A/B (sp2x2_overlap extra) must be
            # attributable from the result line alone.
            "conv_impl": conv_overlap_impl(),
        }
        lint_rep = _LAST_RUN.get("lint_report")
        if lint_rep is not None:
            from mpi4dl_tpu.analysis.trace import crosscheck_overlap

            out["crosscheck"] = [
                f.as_dict() for f in crosscheck_overlap(lint_rep, summary)
            ]
        pred = _LAST_RUN.get("costmodel_pred")
        if pred is not None:
            from mpi4dl_tpu.analysis.costmodel import crosscheck_cost_model

            measured = summary["collective"].get("overlap_ratio")
            out["costmodel"] = {
                "interconnect": pred["interconnect"],
                "predicted_overlap_ratio": pred["overlap_ratio"],
                "overlap_claim": pred["overlap_claim"],
                # Drift is only meaningful when the model makes an overlap
                # claim (async collectives present); the CPU mesh compiles
                # sync-only programs, so bench lines record null there and
                # the series starts populating on the first ICI run.
                "overlap_drift": (
                    abs(float(measured) - float(pred["overlap_ratio"]))
                    if pred["overlap_claim"] and measured is not None
                    else None
                ),
                "crosscheck": [
                    f.as_dict()
                    for f in crosscheck_cost_model(
                        pred, measured_overlap=measured
                    )
                ],
            }
        return out
    except Exception as e:  # noqa: BLE001 — advisory metrics only
        return {"error": f"{type(e).__name__}: {str(e)[:160]}"}
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def main():
    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    _budget()  # a malformed BENCH_TIME_BUDGET must fail before, not after,
    # the headline measurement pays its multi-minute compile

    from mpi4dl_tpu.utils import enable_compilation_cache

    enable_compilation_cache()  # warm-cache compiles make the suite fit any
    # driver budget (first-ever run still pays them; the budget skips extras)

    from mpi4dl_tpu import telemetry

    global _REGISTRY, _TELEMETRY_LOG
    _REGISTRY = telemetry.MetricsRegistry()
    _TELEMETRY_LOG = telemetry.JsonlWriter()  # MPI4DL_TPU_TELEMETRY_DIR-gated

    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.flops import mfu, train_flops_per_image
    from mpi4dl_tpu.models.amoebanet import amoebanetd
    from mpi4dl_tpu.models.resnet import get_resnet_v2
    from mpi4dl_tpu.utils import get_depth

    platform = jax.devices()[0].platform
    on_cpu = platform == "cpu"
    image_size = int(os.environ.get("BENCH_IMAGE_SIZE", "1024"))
    batch = int(os.environ.get("BENCH_BATCH", "2"))
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    which = os.environ.get("BENCH_MODEL", "all")
    if which not in ("resnet", "amoebanet", "all"):
        raise ValueError(f"BENCH_MODEL must be resnet|amoebanet|all, got {which!r}")
    warmup = 2
    if on_cpu and "BENCH_IMAGE_SIZE" not in os.environ:
        image_size, steps = 128, 3  # keep the CPU smoke path tractable

    dtype = jnp.float32 if on_cpu else jnp.bfloat16
    # One remat policy per image size, by the fixed rule the
    # training entry points share (train.default_remat — its docstring
    # holds the measurements); BENCH_REMAT names another for A/B.
    from mpi4dl_tpu.train import default_remat

    remat_pref = os.environ.get("BENCH_REMAT")

    def remat_for(size):
        return remat_pref or default_remat(size)

    extras: dict = {}
    # Packed activation layout (ops/packed.py): measured win on TPU;
    # BENCH_LAYOUT=nhwc reverts to the stock layout for A/B.
    layout = os.environ.get("BENCH_LAYOUT", "packed" if not on_cpu else "nhwc")

    def measure_resnet(size, b, baseline):
        """One ResNet-110 point: measure, plus MFU on the LOGICAL model —
        the packed layout executes more device FLOPs by design and must
        not flatter the utilization number."""
        depth = get_depth(2, 12)  # 110 — the reference benchmark's ResNet
        cells = get_resnet_v2(
            depth=depth, num_classes=10, pool_kernel=size // 4,
            layout=layout, dtype=dtype,
        )
        ips, remat, steps_summary = _train_throughput(
            cells, size, b, steps, warmup, dtype, remat_for(size)
        )
        logical = get_resnet_v2(
            depth=depth, num_classes=10, pool_kernel=size // 4, dtype=dtype
        )
        util = mfu(
            ips,
            train_flops_per_image(logical, size, dtype),
            n_devices=jax.device_count(),
        )
        return {
            "value": round(ips, 3),
            "remat": remat,
            "mfu": round(util, 4) if util is not None else None,
            "step_time_s": _step_percentiles(steps_summary),
            "vs_baseline": round(ips / baseline, 3),
        }

    layers, filters = (18, 416) if not on_cpu else (6, 64)

    def measure_amoeba(size, b):
        """One AmoebaNet-D point (the reference's headline model,
        benchmark-default 18 layers / 416 filters). >=2048px with bs>1
        runs as bs-1 scanned chunks (gradient accumulation, GEMS --times
        chunk semantics): the unchunked program reproducibly failed to
        compile at EVERY remat policy while bs=1 compiles and runs
        (docs/PERF.md round 3). BENCH_NO_ACCUM=1 reverts."""
        cells = amoebanetd(
            num_classes=10, num_layers=layers, num_filters=filters,
            dtype=dtype,
        )
        accum = (
            b if size >= 2048 and b > 1
            and not os.environ.get("BENCH_NO_ACCUM") else 1
        )
        remat = remat_for(size)
        budget_default = (
            size >= 2048
            and not remat_pref
            and "MPI4DL_TPU_SAVE_BUDGET_MB" not in os.environ
        )
        if budget_default:
            # Budgeted scan_save at >=2048: the full save set OOMs but a
            # 6000 MB grant compiles and measured +3% over plain "scan"
            # twice across rounds (r4: 1.249 vs 1.215, r5: 1.447 vs
            # 1.400 — docs/PERF.md round 5).
            os.environ["MPI4DL_TPU_SAVE_BUDGET_MB"] = "6000"
            remat = "scan_save"
        try:
            ips, remat, steps_summary = _train_throughput(
                cells, size, b, steps, warmup, dtype,
                remat, grad_accum=accum,
            )
        finally:
            if budget_default:
                # pop, not del: anything inside _train_throughput clearing
                # the variable must not turn cleanup into a KeyError
                # (ADVICE r5; matches the scanq cleanup below).
                os.environ.pop("MPI4DL_TPU_SAVE_BUDGET_MB", None)
        util = mfu(
            ips, train_flops_per_image(cells, size, dtype),
            n_devices=jax.device_count(),
        )
        entry = {
            "value": round(ips, 3),
            "remat": remat,
            "mfu": round(util, 4) if util is not None else None,
            "step_time_s": _step_percentiles(steps_summary),
        }
        if accum > 1:
            entry["grad_accum"] = accum
            # ADVICE r3: vs_baseline compares against the reference's
            # full-batch number while the measured run used bs-1 chunks
            # with per-chunk BatchNorm — say so in the entry itself.
            entry["note"] = (
                f"bs-{b // accum} chunks x{accum} (GEMS --times semantics, "
                "per-chunk BN) vs the reference's full-batch number"
            )
        base = AMOEBA_BASELINE.get((size, b))
        if base:
            entry["vs_baseline"] = round(ips / base, 3)
        return entry

    headline_error = None

    # --- Headline ----------------------------------------------------------
    # AmoebaNet-D @1024 bs2 — the reference's headline model (BASELINE.json
    # configs are AmoebaNet-centric; ref best ~3.0 img/s). BENCH_MODEL=
    # resnet keeps the previous ResNet-110 headline instead.
    try:
        if which in ("amoebanet", "all"):
            h_size, h_b = (image_size, batch) if not on_cpu else (64, 2)
            entry = dict(measure_amoeba(h_size, h_b))
            entry.setdefault("vs_baseline", None)
            _RESULT.update(
                metric=f"amoebanetd_{h_size}px_bs{h_b}_train_{platform}",
                unit="images/sec",
                **entry,
            )
        else:
            entry = measure_resnet(image_size, batch, RESNET_BASELINE)
            _RESULT.update(
                metric=f"resnet110_{image_size}px_bs{batch}_train_{platform}",
                unit="images/sec",
                **entry,
            )
        _emit()  # the driver has its number from this moment on
        hlo = _hlo_overlap_metrics()
        if hlo is not None:
            _RESULT["hlo"] = hlo
            _emit()
        attribution = _trace_attribution()
        if attribution is not None:
            _RESULT["attribution"] = attribution
            _emit()
    except Exception as e:  # noqa: BLE001 — extras may still succeed
        headline_error = f"{type(e).__name__}: {str(e)[:200]}"
        # Record in the result dict, not just a comment line: if an
        # extra later gets promoted, the JSON must still show that the
        # headline itself regressed.
        _RESULT["headline_error"] = headline_error
        print(f"# headline failed: {headline_error}", flush=True)

    def run_extra(tag, fn, est_seconds=300.0):
        """Run one extra under the budget; record + re-emit either way.
        If no headline landed yet, a successful extra is promoted to the
        headline on the spot — every emitted line has a real value."""
        if _remaining() < est_seconds:
            extras[tag] = {
                "skipped": f"insufficient budget: {int(_remaining())}s of "
                f"{int(_budget())}s left, estimated need {int(est_seconds)}s"
            }
        else:
            try:
                extras[tag] = fn()
            except Exception as e:  # noqa: BLE001 — extras never kill the line
                extras[tag] = {"error": f"{type(e).__name__}: {str(e)[:200]}"}
        if _RESULT.get("metric") is None and extras[tag].get("value") is not None:
            _RESULT.update(
                metric=f"{tag}_train_{platform}",
                unit="images/sec",
                **extras[tag],
            )
            _RESULT.setdefault("vs_baseline", None)  # documented line shape
        _RESULT["extras"] = extras
        if _RESULT.get("metric"):
            _emit()

    # --- Extras, cheapest-win first, each one re-emitting ------------------
    if which in ("resnet", "all") and not on_cpu:
        # est_seconds below are WARM-cache figures (the persistent
        # compilation cache makes reruns 3-5x cheaper than first-ever
        # compiles). Underestimating a cold run is the safe direction:
        # the budget only gates STARTING an extra, every completed
        # milestone is already emitted, and a driver-side kill therefore
        # loses nothing — whereas overestimating silently skips extras a
        # warm run had plenty of time for.
        if which == "all":
            # The other model family's @1024 point (ref ResNet best ~3.1).
            run_extra(
                f"resnet110_{image_size}px_bs{batch}",
                lambda: measure_resnet(image_size, batch, RESNET_BASELINE),
                est_seconds=300.0,
            )
        # High-res point (BASELINE.md: ref ResNet@2048 SP best ~1.0 img/s
        # bs=1; bs=2 OOMs every published scheme).
        run_extra(
            "resnet110_2048px_bs1",
            lambda: measure_resnet(2048, 1, RESNET_2048_BASELINE),
            est_seconds=200.0,
        )
    elif which == "all" and on_cpu:
        run_extra(
            f"resnet110_{image_size}px_bs{batch}",
            lambda: measure_resnet(image_size, batch, RESNET_BASELINE),
            est_seconds=120.0,
        )

    if which in ("amoebanet", "all") and not on_cpu:
        for size, b in [(2048, 2), (2048, 1)]:
            if (size, b) == (h_size, h_b):
                continue  # already the headline (e.g. BENCH_IMAGE_SIZE=2048)
            run_extra(
                f"amoebanetd_{size}px_bs{b}",
                functools.partial(measure_amoeba, size, b),
                est_seconds=300.0,
            )

    # Online-serving workload (any platform: the engine is single-chip by
    # design). Runs before the peak-pixel walk — the walk is expected to
    # eventually fail/eat budget and must not starve this measurement.
    if os.environ.get("BENCH_SERVING", "1") != "0":
        run_extra("serving_amoebanet3_32px", _measure_serving,
                  est_seconds=180.0)

    # Fleet recovery drill (router + 2 CPU replica subprocesses + kill
    # -9): rps-through-the-fault, requeue count, recovery latency.
    if os.environ.get("BENCH_FLEET", "1") != "0":
        run_extra("fleet_2replica", _measure_fleet, est_seconds=240.0)

    # Cold-start decomposition drill (telemetry/coldstart.py): a cold
    # respawn vs a warm-pool promotion, each recovery attributed across
    # spawn/import/construct/compile/warm/ready — bench-history trends
    # every phase_s series INVERTED so no single phase regrows silently.
    if os.environ.get("BENCH_COLDSTART", "1") != "0":
        run_extra("coldstart", _measure_coldstart, est_seconds=180.0)

    # Incident-engine drill: the kill drill scored by the incident
    # manager — MTTD/MTTR + first-cause blame accuracy. bench-history
    # trends incident.mttd_s / incident.mttr_s INVERTED (slower
    # detection or recovery is the regression; absent-not-zero).
    if os.environ.get("BENCH_INCIDENT", "1") != "0":
        run_extra("incident", _measure_incident, est_seconds=200.0)

    # Multi-tenant QoS (tenancy subsystem): noisy-neighbor victim p99
    # ratio + Jain's fairness index under a 10:1 flood, and the
    # tenancy-on overhead vs off — bench-history trends the ratio
    # INVERTED and fairness normal-sign.
    if os.environ.get("BENCH_MULTITENANT", "1") != "0":
        run_extra("multitenant", _measure_multitenant, est_seconds=150.0)

    # Numerics sentinel A/B + corrupt drill: canary-on vs -off rps and
    # the corruption→fence detection latency — bench-history trends
    # both INVERTED (a grown canary tax or slower detection regresses).
    if os.environ.get("BENCH_NUMERICS", "1") != "0":
        run_extra("numerics", _measure_numerics, est_seconds=120.0)

    # SP 2x2 halo/compute overlap A/B (CPU-mesh subprocess): both conv
    # impls' measured trace_overlap_ratio + step time in one round, so
    # bench-history can trend the overlap trajectory per arm.
    if os.environ.get("BENCH_SP_OVERLAP", "1") != "0":
        run_extra("sp2x2_overlap", _measure_sp_overlap, est_seconds=240.0)

    # Sharded-serving overlap A/B (CPU-mesh subprocess): the same two
    # conv impls on the SERVING hot path — a 2x2-sharded engine under
    # closed-loop load per arm, measured trace_overlap_ratio + p99
    # latency per arm trended by bench-history (latency inverted).
    if os.environ.get("BENCH_SERVING_SHARDED", "1") != "0":
        run_extra("serving_sharded", _measure_serving_sharded,
                  est_seconds=300.0)

    # Pipeline schedule A/B (CPU-mesh subprocess): gpipe vs interleaved
    # 1f1b, both arms' measured bubble fraction + img/s per round so
    # bench-history trends the bubble trajectory per schedule.
    if os.environ.get("BENCH_PIPELINE", "1") != "0":
        run_extra("pipeline", _measure_pipeline, est_seconds=180.0)

    # Gigapixel tiled inference (serve/tiled.py): peak feasible px walk
    # through the one-chip tile stream + latency at a fixed large size —
    # bench-history trends peak_px (normal) and p99 latency (inverted).
    if os.environ.get("BENCH_TILED", "1") != "0":
        run_extra("tiled_gigapixel", _measure_tiled_gigapixel,
                  est_seconds=240.0)

    if which in ("resnet", "all") and not on_cpu:
        def peak_px():
            # BASELINE.json capability metric: largest square resolution
            # whose full train step (fwd+bwd+update) fits ONE chip, bs=1 —
            # the single-chip floor of the "SP trains resolutions DP can't"
            # story (scripts/peak_pixels.py is the standalone walker).
            # Each size's success is recorded + emitted IMMEDIATELY: the
            # next (larger) attempt is expected to eventually fail, and a
            # wedged compile or budget kill must not erase a measured peak.
            entry = {
                "peak_trainable_px_per_chip": None,
                "img_per_sec_at_peak": None,
                "unit": "square image side, bs=1, one chip",
            }

            def record(size, ips, note=None, oom=None):
                if size is not None:
                    entry["peak_trainable_px_per_chip"] = size
                    entry["img_per_sec_at_peak"] = ips
                if note:
                    entry["stopped_by"] = note
                if oom is not None:
                    # Structured RESOURCE_EXHAUSTED parse (telemetry/
                    # memory.py) next to the raw stopped_by string: the
                    # wall's HBM table — used/limit/exceeded bytes and
                    # the largest buffers — lands in BENCH_*.json
                    # instead of dying in a truncated message.
                    entry["oom"] = oom
                extras["resnet_peak_pixels"] = entry
                _RESULT["extras"] = extras
                if _RESULT.get("metric"):
                    _emit()

            # Known-fatal sentinel: a failed walk attempt is a ~10-minute
            # compile the persistent cache can NOT memoize (failures are
            # never cached) — record it ourselves so every later bench run
            # skips straight past it. Entries carry the git revision and a
            # status: "confirmed" (the attempt genuinely raised) skips only
            # while the code is unchanged — any new commit invalidates the
            # verdict, so a round-N fix cannot be hidden by a round-(N-1)
            # cache entry (VERDICT r3 weak #6). "provisional" (attempt
            # started, never concluded — a driver kill mid-compile) is
            # retried once whenever the budget still allows a full attempt,
            # instead of requiring a manual BENCH_RETRY_FATAL=1 (ADVICE r3
            # medium). BENCH_RETRY_FATAL=1 still force-retries everything.
            sentinel = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                ".cache", "bench_known_fatal.json",
            )
            try:
                with open(sentinel) as f:
                    fatal = json.load(f)
            except Exception:  # noqa: BLE001 — absent/corrupt = empty
                fatal = {}

            prior = extras.get("resnet110_2048px_bs1", {})
            if prior.get("value") is not None:
                record(2048, prior["value"])
            for size in (3072, 4096, 8192):
                # 3072px: whole-model logarithmic recursion — under plain
                # "scan" the stored carries alone exceed HBM and the
                # compile dies at buffer assignment; scanlog is also 4x
                # faster than scan2 at 3072 (0.165 vs 0.040 img/s,
                # docs/PERF.md round 4). ≥4096px: the anchored-quadratic
                # "scanq" tier (O(1) live boundaries per run) — scanlog's
                # ~23.7 GB live set is a confirmed OOM there. The rule is
                # train.default_remat's; BENCH_REMAT overrides.
                walk_remat = remat_for(size)
                # Key covers everything that shapes the compiled program —
                # a different layout/dtype/policy A/B must not be skipped
                # on another config's verdict.
                from mpi4dl_tpu.train import scan_unroll

                # scanq program identity includes its store budget (set
                # below for the attempt; default 3000).
                qtag = (
                    "_q" + os.environ.get("MPI4DL_TPU_SCANQ_STORE_MB", "3000")
                    if walk_remat == "scanq" else ""
                )
                key = (
                    f"resnet110_{size}px_bs1_{walk_remat}"
                    f"_{layout}_{jnp.dtype(dtype).name}_u{scan_unroll()}{qtag}"
                )
                skip = sentinel_skip_reason(
                    fatal.get(key), _git_rev(), _remaining(),
                    bool(os.environ.get("BENCH_RETRY_FATAL")),
                )
                if skip:
                    record(None, None, f"{size}: {skip}")
                    break
                if _remaining() < 150:
                    record(None, None, f"{size}: budget exhausted before attempt")
                    break
                cells = get_resnet_v2(
                    depth=get_depth(2, 12), num_classes=10,
                    pool_kernel=size // 4, layout=layout, dtype=dtype,
                )

                def write_sentinel():
                    try:
                        os.makedirs(os.path.dirname(sentinel), exist_ok=True)
                        with open(sentinel, "w") as f:
                            json.dump(fatal, f)
                    except Exception:  # noqa: BLE001 — sentinel is advisory
                        pass

                # Pre-mark the attempt as PROVISIONAL: a failed walk compile
                # takes ~10 uncacheable minutes, and a driver kill
                # mid-compile would otherwise erase the evidence. Success
                # REMOVES the marker; a genuine failure upgrades it to
                # "confirmed". A kill of a would-have-succeeded attempt
                # leaves only the provisional marker, which the next
                # sufficiently-budgeted run retries automatically.
                old = fatal.get(key)
                prior_tries = (
                    int(old.get("tries", 1))
                    if isinstance(old, dict)
                    and old.get("status") == "provisional"
                    and old.get("rev") == _git_rev()
                    else 0
                )
                fatal[key] = {
                    "status": "provisional",
                    "rev": _git_rev(),
                    "tries": prior_tries + 1,
                    "msg": "attempt started but never concluded — likely "
                    "killed mid-compile by the driver's budget",
                }
                write_sentinel()
                # scanq attempts carry the measured store-budget default:
                # 3000 MB grants the late small-carry runs the plain
                # stored scan (+67% at 4096: 0.0594 vs 0.0355 img/s,
                # docs/PERF.md round 5; 6000 MB OOMs). Env override wins.
                scanq_default = (
                    walk_remat == "scanq"
                    and "MPI4DL_TPU_SCANQ_STORE_MB" not in os.environ
                )
                if scanq_default:
                    os.environ["MPI4DL_TPU_SCANQ_STORE_MB"] = "3000"
                try:
                    ips, _, _ = _train_throughput(
                        cells, size, 1, 3, 1, dtype, walk_remat
                    )
                except Exception as e:  # noqa: BLE001 — walk stops here
                    msg = f"{type(e).__name__}: {str(e)[:120]}"
                    oom = None
                    from mpi4dl_tpu.telemetry import memory as memobs

                    if memobs.is_oom_error(e):
                        # OOM forensics: emit the schema-valid oom.report
                        # (counter + env-gated JSONL) and embed the parse
                        # in the result line, raw message kept alongside.
                        ev = memobs.emit_oom_report(
                            e, program=f"resnet110_{size}px_bs1_walk",
                            registry=_REGISTRY, events=_TELEMETRY_LOG,
                        )
                        oom = {
                            "parsed": ev["attrs"]["parsed"],
                            "largest_buffer": ev["attrs"]["largest_buffer"],
                        }
                    record(None, None, f"{size}: {msg}", oom=oom)
                    fatal[key] = {
                        "status": "confirmed", "rev": _git_rev(),
                        "msg": msg,
                    }
                    write_sentinel()
                    break
                finally:
                    if scanq_default:
                        os.environ.pop("MPI4DL_TPU_SCANQ_STORE_MB", None)
                fatal.pop(key, None)
                write_sentinel()
                record(size, round(ips, 3))
            return entry

        run_extra("resnet_peak_pixels", peak_px, est_seconds=150.0)

    if _RESULT.get("value") is None:
        # ADVICE r2: an all-failure run must say so explicitly, not hand
        # downstream consumers a null value under a success-shaped line.
        _RESULT.update(
            {
                "metric": _RESULT.get("metric") or f"bench_failed_{platform}",
                "value": None,
                "unit": "images/sec",
                "vs_baseline": None,
                "error": headline_error
                or "no configuration produced a throughput",
                "extras": extras,
            }
        )
        _emit()
        sys.exit(1)


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except Exception as _e:  # noqa: BLE001
        # ANY escape path must still leave one parseable line on stdout —
        # setup failures (device discovery, imports, env validation)
        # included; rc=1 with zero JSON is the round-1/2 failure shape
        # this file exists to eliminate.  If a real measurement already
        # landed, re-emit IT (annotated) as the final line so a
        # keep-last-line driver still records the value.
        if _RESULT.get("value") is not None:
            _RESULT["note"] = (
                f"late failure after measurement: "
                f"{type(_e).__name__}: {str(_e)[:200]}"
            )
            _emit()
            sys.exit(0)
        print(
            json.dumps(
                {
                    "metric": "bench_failed_setup",
                    "value": None,
                    "unit": "images/sec",
                    "vs_baseline": None,
                    "error": f"{type(_e).__name__}: {str(_e)[:300]}",
                }
            ),
            flush=True,
        )
        sys.exit(1)
