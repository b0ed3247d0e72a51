"""bench.py contract tests: the driver parses its stdout, so the output
protocol (one complete JSON line per milestone, headline first, explicit
error shape, nonzero exit on no-measurement) is product surface. Runs the
real script as a subprocess on CPU with tiny shapes."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


@pytest.fixture(scope="session")
def cache_dir(tmp_path_factory):
    """One compilation cache for all bench subprocesses: the three
    measurement tests compile overlapping programs (the amoebanet 64px
    headline twice), and the cache is keyed by program, so sharing it
    saves minutes with no isolation cost."""
    return str(tmp_path_factory.mktemp("jaxcache"))


def _run(cache_dir, extra_env, timeout=900):
    # Strip inherited BENCH_* knobs: a developer's exported BENCH_IMAGE_SIZE
    # would disable bench.py's CPU shrink path and train at full resolution
    # on CPU (a guaranteed timeout), or silently change what's under test.
    base = {k: v for k, v in os.environ.items() if not k.startswith("BENCH_")}
    env = dict(
        base,
        PYTHONPATH=REPO + os.pathsep + base.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
        MPI4DL_TPU_CONV_IMPL="xla",
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        **extra_env,
    )
    return subprocess.run(
        [sys.executable, BENCH],
        env=env, capture_output=True, text=True, timeout=timeout,
    )


def _json_lines(out):
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    return [json.loads(l) for l in lines]


@pytest.mark.slow
def test_amoebanet_headline_line_shape(cache_dir):
    out = _run(cache_dir, {"BENCH_MODEL": "amoebanet"})
    assert out.returncode == 0, out.stderr[-2000:]
    records = _json_lines(out)
    assert records, "no JSON line emitted"
    # Every line is a complete record; the driver may keep first OR last.
    for r in records:
        assert r["unit"] == "images/sec"
        assert r["metric"].startswith("amoebanetd_")
        assert isinstance(r["value"], (int, float)) and r["value"] > 0
        assert "vs_baseline" in r
    # Result lines carry a registry snapshot in the JSONL metrics-event
    # schema (docs/OBSERVABILITY.md) — validated with the same validator
    # the event log enforces, and the train-side series must be populated.
    from mpi4dl_tpu import telemetry

    tele = telemetry.validate_event(records[-1]["telemetry"])
    assert tele["metrics"]["train_steps_total"]["series"][0]["value"] > 0


@pytest.mark.slow
def test_resnet_headline(cache_dir):
    out = _run(cache_dir, {"BENCH_MODEL": "resnet"})
    assert out.returncode == 0, out.stderr[-2000:]
    records = _json_lines(out)
    assert records[0]["metric"].startswith("resnet110_")
    assert records[0]["value"] > 0
    assert records[0]["vs_baseline"] is not None


@pytest.mark.slow
def test_budget_exhaustion_skips_extras_but_keeps_headline(cache_dir):
    # BENCH_MODEL=all on CPU: a 1-second budget cannot erase the headline
    # (the budget gates extras only), and EVERY extra — the resnet point
    # plus the serving/fleet/overlap/pipeline suite — must be skipped
    # with an explicit marker, never silently absent or half-run.
    # (Was `(extra,) = ...` from when the CPU path had one extra; every
    # extra added since landed its own skip entry here.)
    out = _run(cache_dir, {"BENCH_MODEL": "all", "BENCH_TIME_BUDGET": "1"})
    assert out.returncode == 0, out.stderr[-2000:]
    final = _json_lines(out)[-1]
    assert final["metric"].startswith("amoebanetd_")
    assert final["value"] > 0
    assert final["extras"], "no extras recorded at all"
    for tag, extra in final["extras"].items():
        assert "insufficient budget" in extra.get("skipped", ""), (tag, extra)
    assert "pipeline" in final["extras"]  # the PR-14 extra is wired in


def test_bad_budget_fails_before_compile(cache_dir):
    out = _run(cache_dir, {"BENCH_TIME_BUDGET": "not-a-number"}, timeout=120)
    assert out.returncode != 0
    # The failure must still leave one parseable line on stdout.
    records = _json_lines(out)
    assert records and records[-1].get("error")


def _load_bench():
    """Import bench.py in-process (it is a script, not a package module)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_sentinel_skip_reason():
    """Known-fatal sentinel policy (VERDICT r3 weak #6 + ADVICE r3 medium):
    confirmed failures skip only at the same code revision; provisional
    (never-concluded) markers auto-retry when the budget allows; legacy
    string entries and force-retry always rerun."""
    bench = _load_bench()
    skip = bench.sentinel_skip_reason

    confirmed = {"status": "confirmed", "rev": "aaaa", "msg": "HTTP 500"}
    provisional = {"status": "provisional", "rev": "aaaa", "msg": "killed"}

    # Confirmed at the SAME revision skips; at a different revision reruns.
    assert skip(confirmed, "aaaa", 1e9, False) is not None
    assert "HTTP 500" in skip(confirmed, "aaaa", 1e9, False)
    assert skip(confirmed, "bbbb", 1e9, False) is None
    # Unknown current revision fails open (rerun), even if stored matches.
    assert skip({**confirmed, "rev": "unknown"}, "unknown", 1e9, False) is None
    # Provisional: rerun with a fat budget, skip with a thin one.
    assert skip(provisional, "aaaa", 1200.0, False) is None
    assert skip(provisional, "aaaa", 120.0, False) is not None
    # A second never-concluded attempt at the same revision is fatal —
    # retry "once", not on every sufficiently-budgeted run.
    twice = {**provisional, "tries": 2}
    assert skip(twice, "aaaa", 1e9, False) is not None
    assert skip(twice, "bbbb", 1e9, False) is None  # new rev resets
    assert skip(twice, "aaaa", 1e9, True) is None  # force overrides
    # Legacy pre-r4 string entries always rerun.
    assert skip("JaxRuntimeError: ...", "aaaa", 120.0, False) is None
    # BENCH_RETRY_FATAL overrides everything.
    assert skip(confirmed, "aaaa", 1e9, True) is None


def test_bad_model_rejected(cache_dir):
    out = _run(cache_dir, {"BENCH_MODEL": "vgg"}, timeout=120)
    assert out.returncode != 0
    records = _json_lines(out)
    assert records and "BENCH_MODEL" in records[-1]["error"]
