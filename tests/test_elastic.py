"""Fault tolerance: supervised restart-from-checkpoint (mpi4dl_tpu/elastic.py).

The reference has no failure handling — a dead rank hangs the MPI world
(SURVEY §5.3). These tests cover the supervisor's two detectors (nonzero
exit, stale heartbeat) with trivial no-JAX workers, then the real
benchmark path end-to-end: a training run crash-injected mid-epoch must be
restarted by ``--max-restarts`` and resume from the checkpoint it left.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from mpi4dl_tpu import elastic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker(tmp_path, body: str) -> str:
    path = tmp_path / "worker.py"
    path.write_text(textwrap.dedent(body))
    return str(path)


def test_supervise_restarts_on_crash_and_appends_resume(tmp_path):
    marker = tmp_path / "state.txt"
    w = _worker(
        tmp_path,
        f"""
        import sys
        # Crash on the fresh run; succeed once restarted with --resume.
        if "--resume" not in sys.argv:
            sys.exit(3)
        open({str(marker)!r}, "w").write(" ".join(sys.argv[1:]))
        """,
    )
    msgs = []
    rc = elastic.supervise(
        [w], max_restarts=2, poll_interval=0.05, _print=msgs.append
    )
    assert rc == 0
    assert marker.read_text() == "--resume"
    assert any("restarting (1/2)" in m for m in msgs)
    assert any("completed after 1 restart" in m for m in msgs)


def test_supervise_gives_up_after_max_restarts(tmp_path):
    w = _worker(tmp_path, "raise SystemExit(7)")
    msgs = []
    rc = elastic.supervise(
        [w], max_restarts=2, resume_arg=None, poll_interval=0.05,
        _print=msgs.append,
    )
    assert rc == 7
    assert any("giving up after 2 restart(s)" in m for m in msgs)


@pytest.mark.slow
def test_supervise_kills_wedged_child_on_stale_heartbeat(tmp_path, monkeypatch):
    hb = tmp_path / "heartbeat"
    w = _worker(
        tmp_path,
        """
        import os, sys, time
        if "--resume" not in sys.argv:
            # Heartbeat once, then wedge (a deadlocked collective never
            # exits on its own — only staleness can catch it).
            os.utime(os.environ["MPI4DL_TPU_HEARTBEAT"], None)
            time.sleep(3600)
        """,
    )
    msgs = []
    rc = elastic.supervise(
        [w],
        max_restarts=1,
        # Interpreter startup alone is ~2s in this image;
        # the timeout must cover it or the healthy restarted child is
        # killed as "wedged" before it can exit.
        hang_timeout=8.0,
        heartbeat_path=str(hb),
        poll_interval=0.1,
        _print=msgs.append,
    )
    assert rc == 0
    assert any("killing wedged child" in m for m in msgs)
    assert any("wedged — restarting" in m for m in msgs)


def test_hang_timeout_requires_heartbeat():
    with pytest.raises(ValueError):
        elastic.supervise(["x.py"], hang_timeout=5.0)


def test_heartbeat_reporter_gated_on_health(tmp_path):
    """ISSUE satellite, unit level: beats happen while healthy, stop the
    moment the health state flips (or the watchdog trips), resume on
    recovery — the silence the supervisor's staleness detector needs."""
    from mpi4dl_tpu import telemetry

    hb = tmp_path / "heartbeat"
    health = telemetry.HealthState()
    wd = telemetry.Watchdog(min_timeout_s=60.0, start=False)
    r = elastic.HeartbeatReporter(str(hb), health=health, watchdog=wd)
    assert r.beat_once() and hb.exists()
    os.utime(hb, (0, 0))
    health.set_unhealthy("batcher crashed")
    assert not r.beat_once()
    assert os.path.getmtime(hb) == 0  # untouched while unhealthy
    health.set_healthy()
    assert r.beat_once()
    assert os.path.getmtime(hb) > 0
    # A tripped watchdog silences beats even with healthy unset state.
    wd.begin()
    wd.seed(0.001)
    assert wd.check(now=1e9) is not None  # force the trip
    os.utime(hb, (0, 0))
    assert not r.beat_once()
    assert os.path.getmtime(hb) == 0


def test_supervise_restarts_replica_wedged_behind_live_threads(
    tmp_path, monkeypatch
):
    """ISSUE satellite, fault drill: a serving-shaped replica whose
    batcher wedges while its OTHER threads stay alive. An unconditional
    heartbeat would stay fresh forever; the health-gated
    HeartbeatReporter goes silent when the watchdog trips, so
    supervise() kills the wedged process and the restarted one
    completes."""
    # supervise() inherits our env; the worker imports mpi4dl_tpu from
    # the repo (appended, so the caller's own PYTHONPATH entries stay).
    monkeypatch.setenv(
        "PYTHONPATH", REPO + os.pathsep + os.environ.get("PYTHONPATH", "")
    )
    hb = tmp_path / "heartbeat"
    w = _worker(
        tmp_path,
        """
        import os, sys, time
        from mpi4dl_tpu import elastic, telemetry
        if "--resume" in sys.argv:
            sys.exit(0)  # the restarted replica is healthy
        health = telemetry.HealthState()
        wd = telemetry.Watchdog(
            factor=1.0, min_timeout_s=0.3, poll_s=0.05, health=health,
        )
        hr = elastic.HeartbeatReporter(
            os.environ[elastic.HEARTBEAT_ENV], health=health,
            watchdog=wd, interval_s=0.05,
        )
        hr.start()
        wd.begin()        # work admitted...
        time.sleep(3600)  # ...and the loop wedges; threads stay alive
        """,
    )
    msgs = []
    rc = elastic.supervise(
        [w],
        max_restarts=1,
        # Covers interpreter + package import (~2s in this image) with
        # margin; the watchdog trips at 0.3s, so the beats are silent
        # long before this expires.
        hang_timeout=6.0,
        heartbeat_path=str(hb),
        poll_interval=0.1,
        _print=msgs.append,
    )
    assert rc == 0
    assert any("killing wedged child" in m for m in msgs)
    assert any("wedged — restarting" in m for m in msgs)


def test_maybe_supervise_noop_without_flag_or_in_child(monkeypatch):
    class A:
        max_restarts = 0

    elastic.maybe_supervise(A())  # returns (no sys.exit)
    monkeypatch.setenv(elastic.CHILD_ENV, "1")
    A.max_restarts = 3
    elastic.maybe_supervise(A())  # child: also a no-op


@pytest.mark.slow
def test_benchmark_crash_resume_end_to_end(tmp_path):
    """Real path: benchmark_resnet_lp crash-injected at step 2 restarts
    under --max-restarts and resumes from the step-2 checkpoint."""
    ckpt = tmp_path / "ckpt"
    env = dict(
        os.environ,
        # Appended, so the caller's own PYTHONPATH entries stay.
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        MPI4DL_TPU_CRASH_AT_STEP="2",
        MPI4DL_TPU_CONV_IMPL="xla",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jaxcache"),
    )
    out = subprocess.run(
        [
            sys.executable,
            os.path.join(
                REPO, "benchmarks", "layer_parallelism", "benchmark_resnet_lp.py"
            ),
            "--batch-size", "2", "--image-size", "8", "--num-epochs", "1",
            "--max-steps", "4", "--precision", "fp32",
            "--checkpoint-dir", str(ckpt), "--checkpoint-every", "1",
            "--max-restarts", "2",
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=1800,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "restarting (1/2)" in out.stdout
    assert "resumed from step 2" in out.stdout
    # Fresh run: 2 steps then crash (checkpoint at step 2); resumed run
    # honors the restored step as done work and trains ONLY the remaining
    # 2 of the 4 requested steps -> newest checkpoint is step 4, not 6.
    steps = sorted(d for d in os.listdir(ckpt) if d.startswith("step_"))
    meta = json.load(open(os.path.join(ckpt, steps[-1], "meta.json")))
    assert meta["step"] == 4
