"""ISSUE 18: cold-start observability — executable fingerprints, the
compile-time ledger, and recovery phase attribution.

The tentpole claims pinned here:

- ``executable_fingerprint`` is a CONTENT key: stable across separate
  processes for the same config (the property that makes it usable as a
  fleet-wide compile-cache key), distinct under any config change that
  produces a different executable (px, bucket, dtype, mesh shape,
  shardings), and insensitive to the non-semantic decoration (source
  paths in ``metadata={...}``/``loc(...)``) that varies per checkout.
- ``FootprintLedger`` times the trace/compile split at record time,
  carries the fingerprint next to the predicted peak, merges the
  first-execute ``warm_s`` via ``annotate``, and accumulates every phase
  into the cataloged ``compile_seconds{program, phase}`` gauge —
  except ``rollup`` aggregates, which must not double-count.
- ``recovery_phase_decomposition`` always emits the full fixed phase
  vocabulary, sums exactly to the supervisor's recovery wall (spawn is
  the clamped residual), and drops unknown keys.
- ``enable_compilation_cache`` stops failing silent:
  ``compile_cache_enabled`` carries the state and the status its reason.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.telemetry import MetricsRegistry
from mpi4dl_tpu.telemetry.coldstart import (
    RECOVERY_PHASES,
    canonicalize_hlo,
    executable_fingerprint,
    publish_cache_status,
    recovery_phase_decomposition,
)
from mpi4dl_tpu.telemetry.memory import FootprintLedger

# ---------------------------------------------------------------------------
# canonicalization + fingerprint units


def test_canonicalize_strips_nonsemantic_decoration():
    a = canonicalize_hlo(
        'HloModule m, metadata={op_name="jit_f" source_file="/home/a/f.py"}\n'
        '  ROOT %r = f32[2] add(%a, %b) loc("/home/a/f.py":10)\n'
        '#loc1 = loc("/home/a/f.py":10:2)\n'
    )
    b = canonicalize_hlo(
        'HloModule m\n  ROOT %r = f32[2] add(%a, %b)\n'
    )
    assert a == b
    # Real opcode text survives — canonicalization is not a no-op hash.
    assert "add" in a and "metadata" not in a and "#loc" not in a


def test_fingerprint_shape_and_determinism():
    fp = executable_fingerprint("HloModule m", backend="cpu")
    assert fp.startswith("xf") and len(fp) == 18
    assert fp == executable_fingerprint("HloModule m", backend="cpu")


def test_fingerprint_distinct_per_config_axis():
    base = dict(
        backend="tpu", mesh_shape=(2, 2), in_shardings=("P(None)",),
        out_shardings=("P('data')",), donated=(0,),
        jax_version="0.9.0", jaxlib_version="0.9.0",
    )
    ref = executable_fingerprint("HloModule m", **base)
    for axis, value in [
        ("backend", "cpu"),
        ("mesh_shape", (1, 4)),          # same forward, different grid
        ("in_shardings", ("P('sp')",)),
        ("out_shardings", ("P(None)",)),
        ("donated", ()),
        ("jax_version", "0.9.1"),        # a jax upgrade invalidates keys
    ]:
        perturbed = executable_fingerprint(
            "HloModule m", **{**base, axis: value}
        )
        assert perturbed != ref, f"fingerprint blind to {axis}"
    assert executable_fingerprint("HloModule other", **base) != ref


# ---------------------------------------------------------------------------
# process stability (satellite b): the content-key property

_FP_SCRIPT = r"""
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp
from mpi4dl_tpu.evaluate import aot_compile_predict, collect_batch_stats
from mpi4dl_tpu.models.resnet import get_resnet_v2
from mpi4dl_tpu.parallel.partition import init_cells
from mpi4dl_tpu.utils import get_depth


def fingerprints(size, buckets, dtype):
    cells = get_resnet_v2(depth=get_depth(2, 1), num_classes=10,
                          pool_kernel=size // 4)
    params = init_cells(cells, jax.random.PRNGKey(0),
                        jnp.zeros((1, size, size, 3)))
    stats = collect_batch_stats(
        cells, params, [jnp.zeros((2, size, size, 3), jnp.float32)]
    )
    timings = {}
    aot_compile_predict(cells, params, stats, (size, size, 3),
                        buckets=buckets, dtype=dtype, timings=timings)
    return {str(b): t["fingerprint"] for b, t in timings.items()}


out = {"base": fingerprints(16, (1, 2), jnp.float32)}
if "--perturb" in sys.argv:
    out["px24"] = fingerprints(24, (1,), jnp.float32)
    out["bf16"] = fingerprints(16, (1,), jnp.bfloat16)
print(json.dumps(out))
"""


def _fp_run(tmp_path, *args):
    script = tmp_path / "fp.py"
    script.write_text(_FP_SCRIPT)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, str(script), *args],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_fingerprint_stable_across_processes(tmp_path):
    """Two separate interpreters, same config → identical fingerprints
    (a respawning worker can look up the fleet's artifact store before
    paying the compile); px / bucket / dtype perturbations → distinct."""
    run1 = _fp_run(tmp_path, "--perturb")
    run2 = _fp_run(tmp_path)
    assert run1["base"] == run2["base"]  # the content-key property
    base = run1["base"]
    assert base["1"] != base["2"]            # bucket changes the executable
    assert run1["px24"]["1"] != base["1"]    # px changes the executable
    assert run1["bf16"]["1"] != base["1"]    # dtype changes the executable
    for fp in base.values():
        assert fp.startswith("xf") and len(fp) == 18


# ---------------------------------------------------------------------------
# ledger: timed record, annotate, gauge accumulation


def test_record_lowered_times_and_fingerprints():
    reg = MetricsRegistry()
    ledger = FootprintLedger(registry=reg)
    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.zeros((4,), jnp.float32)
    entry = ledger.record_lowered("toy", fn, x)
    assert entry["program"] == "toy"
    assert entry["trace_s"] >= 0 and entry["compile_s"] > 0
    assert entry["fingerprint"].startswith("xf")
    g = reg.get("compile_seconds")
    assert g.value(program="toy", phase="compile") == pytest.approx(
        entry["compile_s"]
    )
    assert g.value(program="toy", phase="trace") == pytest.approx(
        entry["trace_s"]
    )
    # warm_s arrives late (first-execute, engine zeros run) via annotate
    # and accumulates into the same series.
    merged = ledger.annotate("toy", warm_s=0.25)
    assert merged["warm_s"] == 0.25 and merged["fingerprint"] == \
        entry["fingerprint"]
    assert g.value(program="toy", phase="warm") == 0.25
    # Unknown key: explicit no-op, nothing published.
    assert ledger.annotate("nope", warm_s=1.0) is None
    assert g.value(program="nope", phase="warm") == 0.0


def test_rollup_entries_do_not_double_count():
    """The tiled engine's per-image-bucket aggregate sums seconds the
    serve_tiled_* entries already carry — marked rollup, it must stay
    out of the gauge."""
    reg = MetricsRegistry()
    ledger = FootprintLedger(registry=reg)
    fn = jax.jit(lambda x: x + 1.0)
    ledger.record_lowered("serve_tiled_tile", fn, jnp.zeros((2,)), bucket=2)
    fine = reg.get("compile_seconds").value(
        program="serve_tiled_tile", phase="compile"
    )
    assert fine > 0
    compiled = fn.lower(jnp.zeros((2,))).compile()
    ledger.record_compiled(
        "serve_tiled", compiled, bucket=1,
        trace_s=9.0, compile_s=9.0, rollup=True,
    )
    assert reg.get("compile_seconds").value(
        program="serve_tiled", phase="compile"
    ) == 0.0
    # The entry itself still carries the aggregate for warmup_stats().
    assert ledger.get("serve_tiled", bucket=1)["compile_s"] == 9.0


# ---------------------------------------------------------------------------
# recovery phase decomposition


def test_recovery_phases_sum_to_recovery_wall():
    worker = {"import": 2.0, "construct": 1.0, "compile": 3.5,
              "warm": 0.3, "ready": 0.2, "bogus": 99.0}
    phases = recovery_phase_decomposition(10.0, worker)
    assert tuple(phases) == RECOVERY_PHASES
    assert "bogus" not in phases
    assert phases["spawn"] == pytest.approx(3.0)
    assert sum(phases.values()) == pytest.approx(10.0)


def test_recovery_phases_promotion_and_clamp():
    # Promotion: the whole recovery is routable-again time — compile and
    # warm honestly zero, spawn zero.
    p = recovery_phase_decomposition(0.05, {"ready": 0.05})
    assert p["compile"] == 0.0 and p["warm"] == 0.0
    assert p["spawn"] == 0.0 and p["ready"] == 0.05
    # Stub workers report nothing: the whole wall lands in spawn.
    p = recovery_phase_decomposition(4.0, None)
    assert p["spawn"] == 4.0 and sum(p.values()) == pytest.approx(4.0)
    # Clock skew / over-reporting never yields a negative residual.
    p = recovery_phase_decomposition(1.0, {"compile": 5.0})
    assert p["spawn"] == 0.0


# ---------------------------------------------------------------------------
# satellite a: the cache gate stops failing silent


def test_publish_cache_status_gate_is_loud():
    reg = MetricsRegistry()
    status = publish_cache_status(reg)
    gauge = reg.get("compile_cache_enabled").value()
    assert status["enabled"] is True and gauge == 1.0
    assert os.path.isdir(status["dir"])
