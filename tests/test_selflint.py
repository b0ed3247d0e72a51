"""ISSUE 16 satellite: ``scripts/selflint.py`` — the stdlib-ast hygiene
lint over the repo's own source. Pins each rule on synthetic snippets
(golden findings), the allowlist mechanism, the scan scope, the CLI exit
codes, and — the point — that the real repo scans clean. Pure stdlib:
no jax, no device work."""

import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "selflint.py")

spec = importlib.util.spec_from_file_location("selflint", SCRIPT)
selflint = importlib.util.module_from_spec(spec)
spec.loader.exec_module(selflint)


def _lint_src(tmp_path, src, rel="mpi4dl_tpu/snippet.py"):
    p = tmp_path / "snippet.py"
    p.write_text(src)
    return selflint.lint_file(str(p), rel=rel)


# -- rule goldens -------------------------------------------------------------

def test_wallclock_compare_flagged(tmp_path):
    src = (
        "import time\n"
        "def f(deadline):\n"
        "    while time.time() < deadline:\n"
        "        pass\n"
    )
    fs = _lint_src(tmp_path, src)
    assert [(f["rule"], f["line"]) for f in fs] == [("wallclock-compare", 3)]
    assert "time.monotonic()" in fs[0]["message"]


def test_wallclock_timestamp_uses_are_fine(tmp_path):
    """Timestamps (stored, subtracted, printed) are legitimate wall-clock
    uses — only a time.time() nested inside a Compare fires. monotonic
    and perf_counter comparisons are the fix, so they never fire."""
    src = (
        "import time\n"
        "t0 = time.time()\n"                       # stored timestamp
        "dt = time.time() - t0\n"                  # display arithmetic
        "def g(deadline):\n"
        "    return time.monotonic() < deadline\n"  # the correct clock
        "ok = time.perf_counter() < 5\n"
    )
    assert _lint_src(tmp_path, src) == []


def test_uncataloged_metric_flagged_and_declare_is_fine(tmp_path):
    src = (
        "from mpi4dl_tpu import telemetry\n"
        "def f(reg):\n"
        "    telemetry.declare(reg, 'serve_queue_depth').set(3)\n"  # fine
        "    reg.gauge('rogue_gauge', 'h').set(1)\n"
        "    reg.counter('rogue_total', 'h').inc()\n"
        "    reg.histogram('rogue_ms', 'h').observe(2.0)\n"
    )
    fs = _lint_src(tmp_path, src)
    assert [(f["rule"], f["line"]) for f in fs] == [
        ("uncataloged-metric", 4),
        ("uncataloged-metric", 5),
        ("uncataloged-metric", 6),
    ]
    assert all("telemetry.declare" in f["message"] for f in fs)


def test_unnamed_thread_flagged_name_or_daemon_passes(tmp_path):
    src = (
        "import threading\n"
        "t1 = threading.Thread(target=f)\n"                    # flagged
        "t2 = threading.Thread(target=f, name='worker')\n"     # fine
        "t3 = threading.Thread(target=f, daemon=True)\n"       # fine
        "from threading import Thread\n"
        "t4 = Thread(target=f)\n"                              # flagged
    )
    fs = _lint_src(tmp_path, src)
    assert [(f["rule"], f["line"]) for f in fs] == [
        ("unnamed-thread", 2), ("unnamed-thread", 6),
    ]


def test_allowlist_suppresses_by_relpath(tmp_path):
    """The telemetry internals that implement declare() call the raw
    registry constructors on purpose — the allowlist keys on the
    repo-relative path, nothing else."""
    src = "def f(reg):\n    reg.gauge('x', 'h')\n"
    assert _lint_src(
        tmp_path, src, rel="mpi4dl_tpu/telemetry/catalog.py"
    ) == []
    assert _lint_src(
        tmp_path, src, rel="mpi4dl_tpu/telemetry/federation.py"
    ) == []
    # Any other path still fires — the allowlist is not a rule switch.
    assert len(_lint_src(tmp_path, src, rel="mpi4dl_tpu/other.py")) == 1


# -- scope + repo cleanliness -------------------------------------------------

def test_scan_scope_covers_package_scripts_and_bench():
    rels = {rel for _, rel in selflint.iter_sources(REPO)}
    assert "bench.py" in rels
    assert "scripts/selflint.py" in rels
    assert any(r.startswith("mpi4dl_tpu/") for r in rels)
    assert any(r.startswith("mpi4dl_tpu/analysis/") for r in rels)
    # Tests are excluded by construction: they monkeypatch clocks and
    # registries on purpose.
    assert not any(r.startswith("tests/") for r in rels)


def test_repo_lints_clean():
    """The gate itself: the repo's own source carries zero hygiene
    findings. A new time.time() deadline loop, rogue metric series, or
    anonymous thread fails tier-1 right here."""
    findings = selflint.lint_repo(REPO)
    assert findings == [], "\n".join(
        f"{f['path']}:{f['line']}: {f['rule']}: {f['message']}"
        for f in findings
    )


# The environment switches the hot path reads: each is a second path someone
# must keep compiling and measure, so a new one is a reviewed line here.
HOT_PATH_SWITCHES = {
    # ops/: which conv form, which weight-gradient form, the pool kernel
    "MPI4DL_TPU_CONV_IMPL", "MPI4DL_TPU_WGRAD_TAPS", "MPI4DL_TPU_WGRAD_TAPS_MIN_MB",
    "MPI4DL_TPU_CONV_OVERLAP", "MPI4DL_TPU_POOL_PALLAS", "MPI4DL_TPU_COUNTING_FLOPS",
    # train.py: the remat ladder's budgets
    "MPI4DL_TPU_GROUP_SIZE", "MPI4DL_TPU_NOCKPT_BUDGET_MB", "MPI4DL_TPU_SAVE_BUDGET_MB",
    "MPI4DL_TPU_SAVE_ORDER", "MPI4DL_TPU_SCAN2_OFFLOAD", "MPI4DL_TPU_SCAN2_UNROLL",
    "MPI4DL_TPU_SCANQ_STORE_MB", "MPI4DL_TPU_SCAN_UNROLL",
}


def test_the_hot_paths_environment_switches_are_the_listed_ones():
    """Every ``MPI4DL_TPU_*`` name that code under ``mpi4dl_tpu/ops/``,
    ``mpi4dl_tpu/parallel/`` and ``mpi4dl_tpu/train.py`` holds as a string
    of its own (what an ``os.environ`` read is given; prose is not)."""
    import ast
    import re

    paths = [os.path.join(REPO, "mpi4dl_tpu", "train.py")]
    for sub in ("ops", "parallel"):
        folder = os.path.join(REPO, "mpi4dl_tpu", sub)
        paths += [os.path.join(folder, f) for f in sorted(os.listdir(folder))
                  if f.endswith(".py")]
    read = set()
    for path in paths:
        with open(path) as f:
            tree = ast.parse(f.read())
        read |= {
            node.value for node in ast.walk(tree)
            if isinstance(node, ast.Constant) and isinstance(node.value, str)
            and re.fullmatch(r"MPI4DL_TPU_[A-Z0-9_]+", node.value)}
    assert read == HOT_PATH_SWITCHES


def test_cli_exit_codes_and_json(tmp_path):
    """Exit 0 + summary on the clean repo; exit 1 + findings on a dirty
    tree; --json emits a machine-readable array. Runs the script as a
    subprocess — the pre-commit/CI invocation shape — which also proves
    it never imports jax (bare interpreter, no JAX_PLATFORMS set)."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    r = subprocess.run(
        [sys.executable, SCRIPT], capture_output=True, text=True, env=env,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stdout

    dirty = tmp_path / "repo"
    (dirty / "mpi4dl_tpu").mkdir(parents=True)
    (dirty / "mpi4dl_tpu" / "bad.py").write_text(
        "import threading\nthreading.Thread(target=print).start()\n"
    )
    r = subprocess.run(
        [sys.executable, SCRIPT, "--root", str(dirty), "--json"],
        capture_output=True, text=True, env=env,
    )
    assert r.returncode == 1
    fs = json.loads(r.stdout)
    assert [(f["rule"], f["path"]) for f in fs] == [
        ("unnamed-thread", "mpi4dl_tpu/bad.py"),
    ]
