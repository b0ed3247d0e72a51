"""End-to-end CLI smoke tests for all 8 training entry scripts.

The trainer classes are golden-tested (test_pipeline/test_train); what those
tests never touch is the scripts' argument plumbing — ``benchmarks/common.py``
routing (build_config/build_resnet/build_amoebanet/make_trainer) driven by
real argparse vectors. The reference's de-facto integration surface is
exactly these scripts (``/root/reference/benchmarks/*/benchmark_*.py``,
SURVEY.md §2.3); here each one runs 1-2 real steps in-process on the 8
virtual CPU devices (conftest), covering the VERDICT-r3 flag matrix:
``--halo-D2``, ``--local-DP 4``, GEMS+SP, ``--enable-master-comm-opt``,
``--eval-batches``, and ``--times 2``.
"""

import json
import os
import runpy
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B = os.path.join(REPO, "benchmarks")

# Tiny-but-real configs: ResNet scripts always build ResNet-110 (the
# reference hardcodes resnet_n=12 the same way), so they run @32px with 1-2
# steps; AmoebaNet scripts get shrunk via their own CLI (--num-layers /
# --num-filters — same knobs the reference exposes).
_COMMON = ["--image-size", "32", "--precision", "fp32", "--verbose"]
_AMOEBA_SMALL = ["--num-layers", "3", "--num-filters", "32"]

CASES = {
    "layer_parallelism/benchmark_resnet_lp.py": [
        "--batch-size", "4", "--parts", "2", "--split-size", "2",
        "--max-steps", "2", "--eval-batches", "1", *_COMMON,
    ],
    "layer_parallelism/benchmark_amoebanet_lp.py": [
        "--batch-size", "4", "--parts", "2", "--split-size", "2",
        "--max-steps", "2", *_AMOEBA_SMALL, "--image-size", "64",
        "--precision", "fp32", "--verbose",
    ],
    # --halo-D2: the fused-halo D2 spatial model through the full script.
    "spatial_parallelism/benchmark_resnet_sp.py": [
        "--batch-size", "2", "--parts", "1", "--split-size", "2",
        "--spatial-size", "1", "--num-spatial-parts", "4",
        "--slice-method", "square", "--halo-D2", "--fused-layers", "2",
        "--max-steps", "2", *_COMMON,
    ],
    # --local-DP 4: LBANN-style DP inside the LP stages after SP (8 devices).
    "spatial_parallelism/benchmark_amoebanet_sp.py": [
        "--batch-size", "8", "--parts", "1", "--split-size", "2",
        "--spatial-size", "1", "--num-spatial-parts", "4",
        "--slice-method", "square", "--local-DP", "4",
        "--max-steps", "2", *_AMOEBA_SMALL, "--image-size", "64",
        "--precision", "fp32", "--verbose",
    ],
    # --times 2: the GEMS effective-batch knob beyond its default.
    "gems_master_model/benchmark_resnet_gems_master.py": [
        "--batch-size", "2", "--parts", "2", "--split-size", "2",
        "--times", "2", "--max-steps", "2", *_COMMON,
    ],
    "gems_master_model/benchmark_amoebanet_gems_master.py": [
        "--batch-size", "2", "--parts", "2", "--split-size", "2",
        "--enable-master-comm-opt", "--max-steps", "2",
        *_AMOEBA_SMALL, "--image-size", "64", "--precision", "fp32",
        "--verbose",
    ],
    # GEMS+SP: spatial front + bidirectional pipeline (ref two-MPIComm path).
    "gems_master_with_spatial_parallelism/benchmark_resnet_gems_master_with_sp.py": [
        "--batch-size", "2", "--parts", "2", "--split-size", "3",
        "--spatial-size", "1", "--num-spatial-parts", "4",
        "--slice-method", "square", "--max-steps", "2", *_COMMON,
    ],
    "gems_master_with_spatial_parallelism/benchmark_amoebanet_gems_master_with_sp.py": [
        "--batch-size", "2", "--parts", "2", "--split-size", "3",
        "--spatial-size", "1", "--num-spatial-parts", "4",
        "--slice-method", "square", "--enable-master-comm-opt",
        "--max-steps", "2", *_AMOEBA_SMALL, "--image-size", "64",
        "--precision", "fp32", "--verbose",
    ],
}


# Every case compiles a full model on the CPU mesh — minutes each. The fast
# tier's engine coverage lives in the golden tests; these are the
# integration layer. (Marked per-test, not module-wide: the pure-JSON CLI
# smokes below belong to the fast tier.)
@pytest.mark.slow
@pytest.mark.parametrize("script", sorted(CASES), ids=lambda s: s.split("/")[-1])
def test_cli_script_smoke(script, monkeypatch, capsys):
    """Run the script's real __main__ path with a real argv; assert it
    trains (per-step loss lines via --verbose) and reports throughput."""
    # The scripts honour JAX_PLATFORMS: point them at the CPU simulation,
    # exactly as their own usage message instructs.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # ResNet-20 instead of ResNet-110: the scripts' plumbing (what this
    # test covers) is depth-independent, and the 54-cell CPU compile is
    # not a cost 8 parametrized smoke runs should pay.
    monkeypatch.setenv("MPI4DL_TPU_RESNET_N", "2")
    monkeypatch.setenv(
        "XLA_FLAGS",
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8",
    )
    monkeypatch.setattr(
        sys, "argv", [os.path.basename(script)] + CASES[script]
    )
    runpy.run_path(os.path.join(B, script), run_name="__main__")
    out = capsys.readouterr().out
    assert "loss" in out, out  # --verbose per-step line → a step really ran
    assert "img/s" in out, out  # the end-of-run throughput report
    if "--enable-master-comm-opt" in CASES[script]:
        # CLI parity: the flag is accepted and explained, not ignored.
        assert "comm-opt" in out, out
    if "--eval-batches" in CASES[script]:
        assert "eval (" in out, out


def test_analyze_trace_export_cli(tmp_path, capsys):
    """ISSUE CI satellite: `python -m mpi4dl_tpu.analyze trace-export`
    end-to-end through the analysis CLI's real dispatch — two processes'
    JSONL span segments in, one joined Chrome trace out. Pure JSON (the
    subcommand dispatches before any jax setup), so it runs in the fast
    tier."""
    from mpi4dl_tpu import telemetry
    from mpi4dl_tpu.analysis.cli import main

    log = tmp_path / "telemetry-fleet.jsonl"
    with open(log, "w") as f:
        for pid, name, marks in (
            (11, "client.request",
             [("issue", 1.0), ("client_wait", 2.0)]),
            (22, "serve.request",
             [("submit", 5.0), ("queue_wait", 5.4),
              ("device_compute", 5.9)]),
        ):
            ev = telemetry.span_event(
                name, "trace-join-1", telemetry.spans_from_marks(marks),
                attrs={"pid": pid}, ts=100.0,
            )
            f.write(json.dumps(ev) + "\n")
    out = tmp_path / "chrome.json"
    rc = main(["trace-export", str(log), "--trace-id", "trace-join-1",
               "-o", str(out)])
    assert rc == 0
    assert "2 process(es)" in capsys.readouterr().err
    doc = json.load(open(out))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {11, 22}
    assert all(e["args"]["trace_id"] == "trace-join-1" for e in xs)
    # --list mode names the trace; a bogus id exits nonzero.
    assert main(["trace-export", str(log), "--list"]) == 0
    assert "trace-join-1" in capsys.readouterr().out
    assert main(["trace-export", str(log), "--trace-id", "missing"]) == 1


def test_analyze_tail_cli(tmp_path, capsys):
    """ISSUE 10 CI satellite: `python -m mpi4dl_tpu.analyze tail` through
    the analysis CLI's real dispatch — pure JSON, pre-jax, fast tier.
    Canned logs: two span populations + a tail.sample + an exemplar-
    carrying metrics event; the deep joins are covered in test_tail.py."""
    from mpi4dl_tpu import telemetry
    from mpi4dl_tpu.analysis.cli import main

    log = tmp_path / "telemetry-tail.jsonl"
    reg = telemetry.MetricsRegistry()
    telemetry.declare(reg, "serve_request_latency_seconds").observe(
        0.5, exemplar="t-slow"
    )
    with open(log, "w") as f:
        for tid, e2e in (("t-slow", 0.5), ("t-fast", 0.01)):
            ev = telemetry.span_event(
                "serve.request", tid,
                telemetry.spans_from_marks([
                    ("submit", 1.0), ("queue_wait", 1.0 + e2e / 2),
                    ("device_compute", 1.0 + e2e),
                ]),
                attrs={"pid": 7, "role": "engine", "outcome": "served",
                       "e2e_latency_s": e2e},
                ts=100.0,
            )
            f.write(json.dumps(ev) + "\n")
        f.write(json.dumps({
            "ts": 100.1, "kind": "event", "name": "tail.sample",
            "attrs": {"trace_id": "t-slow", "e2e_latency_s": 0.5,
                      "threshold_s": 0.04, "pid": 7},
        }) + "\n")
        f.write(json.dumps(telemetry.metrics_event(reg, ts=101.0)) + "\n")

    assert main(["tail", str(log), "--top", "2"]) == 0
    out = capsys.readouterr().out
    assert "t-slow" in out and "t-fast" in out
    assert main(["tail", str(log), "--trace-id", "t-slow"]) == 0
    out = capsys.readouterr().out
    assert "dominant phase" in out and "tail.sample" in out
    assert "exemplar: serve_request_latency_seconds" in out
    assert main(["tail", str(log), "--list-exemplars"]) == 0
    assert "t-slow" in capsys.readouterr().out
    assert main(["tail", str(log), "--trace-id", "missing"]) == 1


def test_analyze_incident_cli_md_timeline_golden(tmp_path, capsys):
    """ISSUE 20 CI satellite: `python -m mpi4dl_tpu.analyze incident`
    through the real dispatch — pure JSON, pre-jax. Canned MULTI-PID
    logs whose file order disagrees with wall-clock order, plus a
    cause/symptom pair sharing one coarse timestamp: the rendered
    ``--md`` timeline must come out in causal order regardless."""
    from mpi4dl_tpu.analysis.cli import main

    # pid-7 log (supervisor side): the chaos op, the restart, and the
    # incident lifecycle.
    (tmp_path / "telemetry-7.jsonl").write_text(
        "".join(json.dumps(e) + "\n" for e in [
            {"ts": 100.0, "kind": "event", "name": "chaos.injected",
             "attrs": {"op": "kill:r1@+1s", "action": "kill", "pid": 8}},
            {"ts": 100.4, "kind": "event", "name": "elastic.restart",
             "attrs": {"replica": "r1", "reason": "exit"}},
            {"ts": 100.3, "kind": "event", "name": "incident.open",
             "attrs": {"id": "inc-7", "opened_ts": 100.3,
                       "alert": "replica_unreachable", "severity": "page",
                       "mtta_s": 0.3, "lookback_s": 10.0,
                       "members": [{"name": "replica_unreachable",
                                    "severity": "page",
                                    "first_firing_ts": 100.0}]}},
            {"ts": 101.5, "kind": "event", "name": "incident.close",
             "attrs": {"id": "inc-7", "closed_ts": 101.5, "mttr_s": 1.2,
                       "members": [{"name": "replica_unreachable",
                                    "severity": "page",
                                    "resolved_ts": 101.5}]}},
        ])
    )
    # pid-8 log (worker side), listed AFTER pid-7 but carrying EARLIER
    # wall times — and a page transition tying the chaos op's ts
    # exactly (coarse clocks do that): the cause must still sort first.
    (tmp_path / "telemetry-8.jsonl").write_text(
        "".join(json.dumps(e) + "\n" for e in [
            {"ts": 100.0, "kind": "event", "name": "alert.transition",
             "attrs": {"alert": "replica_unreachable", "severity": "page",
                       "from": "resolved", "to": "firing"}},
            {"ts": 99.5, "kind": "event", "name": "oom.report",
             "attrs": {"program": "serve_predict"}},
        ])
    )

    assert main(["incident", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "inc-7" in out and "injected chaos op kill:r1@+1s" in out

    assert main(["incident", str(tmp_path), "--md"]) == 0
    md = capsys.readouterr().out
    assert "# Incident inc-7 — closed" in md
    assert "| MTTR | 1.200s |" in md
    rows = [
        line.split("`")[1] for line in md.splitlines()
        if line.startswith("| ") and "`" in line
        and "| t−open |" not in line
    ]
    # Golden causal order: wall time across pids, cause before symptom
    # at the shared timestamp — NOT file order, NOT emission order.
    assert rows == [
        "replica_unreachable",  # opened-by field row
        "replica_unreachable",  # members field row
        "oom.report", "chaos.injected", "alert.transition",
        "elastic.restart",
    ]

    assert main(["incident", str(tmp_path), "--json"]) == 0
    (pm,) = json.loads(capsys.readouterr().out)
    assert [e["ts"] for e in pm["timeline"]] == [99.5, 100.0, 100.0, 100.4]
    assert main(["incident", str(tmp_path), "--incident-id", "nope"]) == 1


def test_fleet_cli_plan_smoke(capsys):
    """ISSUE CI satellite: `python -m mpi4dl_tpu.fleet --plan` — the
    pure-dispatch path: chaos specs parsed + validated, the fleet plan
    printed as JSON, no process spawned, no model compiled. Bad specs
    and out-of-fleet targets are usage errors, not silent no-ops."""
    from mpi4dl_tpu.fleet.__main__ import main

    rc = main(["--replicas", "2", "--chaos", "kill:1@2",
               "--chaos", "delay-scrape:0=3", "--plan"])
    assert rc == 0
    plan = json.loads(capsys.readouterr().out)
    assert plan["replicas"] == 2
    assert plan["chaos"] == ["kill:r1@+2s", "delay-scrape:r0=3s@+1s"]
    assert "mpi4dl_tpu.fleet.worker" in " ".join(plan["worker_cmd"])
    assert plan["federation"] is True
    # Unknown action and a target outside the fleet: loud exit 2.
    assert main(["--replicas", "2", "--chaos", "explode:1", "--plan"]) == 2
    assert main(["--replicas", "2", "--chaos", "kill:5", "--plan"]) == 2

    # ISSUE 12: the HA front door joins the plan — router count, warm
    # pool, router_cmd, and the kill:router chaos domain, with
    # out-of-set router targets as loud usage errors.
    rc = main(["--replicas", "2", "--routers", "2", "--warm-pool", "1",
               "--chaos", "kill:router:1@2", "--plan"])
    assert rc == 0
    plan = json.loads(capsys.readouterr().out)
    assert plan["routers"] == 2 and plan["warm_pool"] == 1
    assert plan["chaos"] == ["kill:router1@+2s"]
    assert "mpi4dl_tpu.fleet.frontdoor" in " ".join(plan["router_cmd"])
    assert main(["--replicas", "2", "--routers", "2",
                 "--chaos", "kill:router:2", "--plan"]) == 2
    # A warm-pool slot is a legitimate replica kill target.
    assert main(["--replicas", "2", "--warm-pool", "1",
                 "--chaos", "kill:2", "--plan"]) == 0
    capsys.readouterr()


def test_analyze_memory_plan_cli(tmp_path, capsys):
    """ISSUE CI satellite: `python -m mpi4dl_tpu.analyze memory-plan`
    artifact mode end-to-end through the CLI's real dispatch — committed
    peaks (baseline format + a footprint-ledger dump) against a limit,
    fits/doesn't verdicts, machine-readable plan, CI exit codes. Pure
    JSON (dispatched before any backend setup, like bench-history), so
    it runs in the fast tier."""
    from mpi4dl_tpu.analysis.cli import main

    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({
        "resnet_small": {"peak_bytes": 2 * 2**30},
        "resnet_huge": {"peak_bytes": 20 * 2**30},
    }))
    plan_path = tmp_path / "plan.json"
    rc = main(["memory-plan", "--baseline", str(base),
               "--limit-gb", "15.48", "--json", str(plan_path)])
    assert rc == 1  # the huge config does not fit → CI-visible
    out = capsys.readouterr().out
    assert "DOES NOT FIT" in out and "fits" in out
    plan = json.load(open(plan_path))
    assert plan["mode"] == "artifact"
    verdicts = {e["key"]: e["fits"] for e in plan["entries"]}
    assert verdicts == {"resnet_small": True, "resnet_huge": False}
    small = next(e for e in plan["entries"] if e["key"] == "resnet_small")
    assert small["headroom_ratio"] == pytest.approx(
        1 - 2 / 15.48, abs=1e-3
    )

    # Only the fitting key asked about → exit 0.
    assert main(["memory-plan", "--baseline", str(base), "--key",
                 "small", "--limit-gb", "15.48"]) == 0
    # No limit: peaks reported, verdict unknown, still usable (exit 0).
    assert main(["memory-plan", "--baseline", str(base)]) == 0
    # A ledger dump (engine stats()['memory'] shape) is also an input.
    ledger = tmp_path / "ledger.json"
    ledger.write_text(json.dumps({"entries": [
        {"program": "serve_predict", "bucket": 8, "peak_bytes": 2**30},
    ]}))
    rc = main(["memory-plan", "--ledger", str(ledger),
               "--limit-bytes", str(2**31), "--json", str(plan_path)])
    assert rc == 0
    plan = json.load(open(plan_path))
    assert plan["entries"][0]["key"] == "serve_predict[8]"
    assert plan["entries"][0]["fits"] is True
    # Empty input is a usage error, not a silent all-clear.
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert main(["memory-plan", "--baseline", str(empty)]) == 2


def test_analyze_coldstart_cli(tmp_path, capsys):
    """ISSUE 18 satellite: ``python -m mpi4dl_tpu.analyze coldstart``
    through the CLI's real dispatch — ledger dumps ranked into the
    top-executables manifest, the human-readable summary, and the
    ``--budget-s`` CI exit code. Pure JSON, fast tier."""
    from mpi4dl_tpu.analysis.cli import main

    ledger = tmp_path / "ledger.json"
    ledger.write_text(json.dumps({"entries": [
        {"program": "serve_predict", "bucket": 4,
         "fingerprint": "xf1111111111111111",
         "trace_s": 0.2, "compile_s": 1.5, "warm_s": 0.02},
        {"program": "serve_predict", "bucket": 1,
         "fingerprint": "xf2222222222222222",
         "trace_s": 0.1, "compile_s": 0.4, "warm_s": 0.01},
        {"program": "train_step",
         "fingerprint": "xf3333333333333333",
         "trace_s": 0.5, "compile_s": 2.5, "warm_s": 0.1},
    ]}))
    rc = main(["coldstart", str(ledger), "--top", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    # Ranked by compile seconds, --top truncates the listing.
    assert "1. train_step xf3333333333333333" in out
    assert "2. serve_predict[4]" in out
    assert "serve_predict[1]" not in out
    assert "compile 4.400s" in out

    # Same ledger recorded twice (two replicas): fingerprint grouping
    # merges each executable and counts occurrences.
    rc = main(["coldstart", str(ledger), str(ledger), "--top", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "x2" in out and "compile 5.000s" in out

    # The budget gate fails CI loudly.
    rc = main(["coldstart", str(ledger), "--budget-s", "2.0"])
    assert rc == 1
    err = capsys.readouterr().err
    assert "OVER BUDGET" in err


def test_analyze_memory_plan_bisect_tile_cli(tmp_path, capsys):
    """ISSUE satellite: ``analyze memory-plan --bisect tile`` — the
    gigapixel pre-run question "what tile size fits this chip" answered
    in pure compile mode (section-window + stitched-head executables
    lowered abstractly, nothing executed), binary-searched over the
    tile ladder, exit 1 when no tile fits."""
    from mpi4dl_tpu.analysis.cli import main

    plan_path = tmp_path / "tileplan.json"
    rc = main([
        "memory-plan", "--program", "serve", "--size", "64",
        "--bisect", "tile", "--tile-candidates", "16",
        "--tile-bucket", "1", "--limit-gb", "4",
        "--json", str(plan_path),
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "max feasible tile: 16" in out
    plan = json.load(open(plan_path))
    assert plan["bisect"]["axis"] == "tile"
    assert plan["bisect"]["max_feasible"] == 16
    # Every compiled candidate reports BOTH executables' peaks — the
    # head is the image-bound residual the tile size cannot shrink.
    cand = plan["bisect"]["candidates"][-1]
    assert cand["tile_peak_bytes"] > 0 and cand["head_peak_bytes"] > 0
    # No tile fits an absurd limit → CI-visible exit 1.
    rc = main([
        "memory-plan", "--program", "serve", "--size", "64",
        "--bisect", "tile", "--tile-candidates", "16",
        "--tile-bucket", "1", "--limit-bytes", "1000",
    ])
    assert rc == 1
    capsys.readouterr()


def test_analyze_sp_overlap_cli_decomposed_crosscheck(tmp_path, capsys):
    """ISSUE CI satellite: `python -m mpi4dl_tpu.analyze sp-overlap` on
    the DECOMPOSED arm — a live SP 2×2 capture of the decomposed-conv
    program, attributed, linted against partition math, and run through
    the trace-overlap-crosscheck, end-to-end via the analysis CLI's real
    dispatch (in-process: the 8-virtual-CPU mesh already exists)."""
    from mpi4dl_tpu.analysis.cli import main

    out_path = tmp_path / "sp_overlap.json"
    rc = main([
        "sp-overlap", "--arm", "decomposed", "--size", "32",
        "--steps", "2", "--warmup", "1", "--json", str(out_path),
    ])
    assert rc == 0
    err = capsys.readouterr().err
    assert "decomposed:" in err
    out = json.load(open(out_path))
    arm = out["arms"]["decomposed"]
    assert arm["conv_impl"] == "decomposed"
    assert arm["halo_shifts"] == 20
    assert arm["halo_shifts"] <= arm["permutes"] <= 2 * arm["halo_shifts"]
    assert arm["hlolint_errors"] == []
    # CPU emits sync collectives (no static overlap claim), so the
    # crosscheck must report NO disagreement on the decomposed capture.
    assert arm["crosscheck"] == []
    assert arm["n_steps"] >= 2
    assert 0.0 <= arm["trace_overlap_ratio"] <= 1.0


def test_analyze_pipeline_cli_one_arm(tmp_path, capsys):
    """ISSUE 14 CI satellite: `python -m mpi4dl_tpu.analyze pipeline` on
    one schedule arm — a live LP-pipeline capture attributed through the
    stage-switch lens, the measured bubble cross-checked against the
    schedule model, and the compiled program linted at the exact
    stage-permute budget — end-to-end via the analysis CLI's real
    dispatch (in-process: the 8-virtual-CPU mesh already exists)."""
    from mpi4dl_tpu.analysis.cli import main

    out_path = tmp_path / "pipeline_ab.json"
    rc = main([
        "pipeline", "--schedule", "gpipe", "--steps", "2", "--warmup", "1",
        "--json", str(out_path),
    ])
    assert rc == 0
    err = capsys.readouterr().err
    assert "gpipe:" in err
    out = json.load(open(out_path))
    arm = out["arms"]["gpipe"]
    assert arm["bubble_fraction"] == pytest.approx(
        arm["analytic_bubble_fraction"], abs=0.02
    )
    # Pure-LP program: the permute inventory sits exactly at the
    # stage-boundary budget and the window rule holds.
    assert arm["permutes"] == arm["permute_budget"] == 2
    assert arm["hlolint_errors"] == []
    assert arm["crosscheck"] == []
    assert arm["img_per_s"] > 0
    assert len(arm["stage_device_seconds"]) == 2


def test_serve_cli_mesh_sharded_smoke(tmp_path, capsys):
    """ISSUE CI satellite: `python -m mpi4dl_tpu.serve --mesh HxW` — the
    sharded synthetic engine end to end via the serve CLI: warms, serves
    a small closed loop, and the lint gate passes with the mesh-derived
    (halo-window) expectations instead of zero-collectives."""
    from mpi4dl_tpu.serve.__main__ import main

    out_path = tmp_path / "serve_mesh.json"
    rc = main([
        "--mesh", "2x2", "--image-size", "16", "--spatial-cells", "2",
        "--max-batch", "2", "--requests", "8", "--concurrency", "4",
        "--serial", "0", "--lint", "--json", str(out_path),
    ])
    assert rc == 0
    rep = json.load(open(out_path))
    assert rep["mesh"] == [2, 2]
    assert rep["loadgen"]["served"] == 8
    assert rep["loadgen"]["deadline_misses"] == 0
    assert rep["lint"]["ok"]
    assert rep["loadgen"]["engine"]["mesh"] == [2, 2]


def test_analyze_serving_sharded_cli_one_arm(tmp_path, capsys):
    """ISSUE CI satellite: `python -m mpi4dl_tpu.analyze serving-sharded`
    on one arm — a sharded engine under closed-loop load inside a live
    capture, attributed, mesh-lint gated, crosschecked — via the
    analysis CLI's real dispatch (in-process: the 8-virtual-CPU mesh
    already exists)."""
    from mpi4dl_tpu.analysis.cli import main

    out_path = tmp_path / "serving_sharded.json"
    rc = main([
        "serving-sharded", "--arm", "decomposed", "--size", "16",
        "--spatial-cells", "2", "--bucket", "2", "--requests", "12",
        "--concurrency", "4", "--json", str(out_path),
    ])
    assert rc == 0
    err = capsys.readouterr().err
    assert "decomposed:" in err
    out = json.load(open(out_path))
    arm = out["arms"]["decomposed"]
    assert arm["conv_impl"] == "decomposed"
    assert arm["hlolint_errors"] == []
    assert arm["crosscheck"] == []
    assert arm["deadline_misses"] == 0
    # Forward-only serving program: the permute inventory sits exactly
    # at the counted halo shifts.
    assert arm["permutes"] == arm["halo_shifts"] > 0
    assert arm["latency_ms"]["p99"] > 0
    assert 0.0 <= arm["trace_overlap_ratio"] <= 1.0
