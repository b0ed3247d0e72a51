"""Sharded-serving halo/compute-overlap A/B harness.

``python -m mpi4dl_tpu.analyze serving-sharded`` runs a spatially-sharded
:class:`~mpi4dl_tpu.serve.ServingEngine` (serve/sharded.py) TWICE — once
with the monolithic spatial conv and once with the PR-9 decomposed impl
(``overlap_decompose``: interior conv with no halo dependency + boundary
strips) — and measures, per arm, ON THE SERVING HOT PATH:

- the **measured** ``trace_overlap_ratio`` of a live XProf capture over a
  closed-loop load run (the engine's own ``mpi4dl_serve_batch`` step
  annotations): the fraction of collective-permute time hidden behind
  concurrent compute — the number the decomposition exists to raise
  (T3 arXiv:2401.16677 / FLUX arXiv:2406.06858), now with per-request
  latency attached instead of train-step wall time;
- per-request latency (p50/p99) and throughput of the same load run;
- the **static** hlolint verdict with the MESH-DERIVED expectations
  (tile grid + counted halo shifts — the engine's own ``lint_report``);
- the ``trace-overlap-crosscheck`` findings joining static and measured;
- the PR-9 **bit-identity crosscheck**: both arms' logits for one probe
  example must be byte-equal (the decomposition changes the schedule,
  never the numbers).

Trials interleave across arms (mono, dec, mono, dec, ...) so slow host
drift hits both alike, and each arm's ratio pools overlapped/total
collective time over its captures. Run from bench.py as the
``serving_sharded`` extra subprocess (the 4-device CPU mesh must exist
regardless of the bench headline's backend); the CPU mesh proves
scheduling freedom, not wall-clock — the flag is the TPU lever.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile


def _build_arm_engine(impl, size, depth, spatial_cells, mesh, bucket):
    from mpi4dl_tpu.serve.sharded import synthetic_sharded_engine

    return synthetic_sharded_engine(
        mesh, image_size=size, depth=depth, spatial_cells=spatial_cells,
        conv_overlap=impl, buckets=(bucket,), max_queue=512,
        default_deadline_s=60.0, watchdog_factor=None,
        memory_monitor=False, tail_capacity=0,
    )


def run_serving_sharded_ab(
    size: int = 32,
    depth: int = 8,
    spatial_cells: int = 3,
    mesh=(2, 2),
    bucket: int = 4,
    requests: int = 48,
    concurrency: int = 8,
    trials: int = 1,
    arms=("monolithic", "decomposed"),
    registry=None,
) -> dict:
    """Both serving arms + the A/B verdict; see the module docstring.
    Requires enough devices for the tile mesh; raises the underlying
    config error otherwise."""
    import numpy as np

    from mpi4dl_tpu import profiling
    from mpi4dl_tpu.analysis.trace import (
        analyze_trace_dir,
        crosscheck_overlap,
        publish_attribution,
    )
    from mpi4dl_tpu.serve.loadgen import run_closed_loop

    th, tw = (int(d) for d in mesh)
    out = {
        "config": {
            "size": size, "depth": depth, "spatial_cells": spatial_cells,
            "mesh": f"{th}x{tw}", "bucket": bucket, "requests": requests,
            "concurrency": concurrency, "trials": trials,
        },
        "arms": {},
    }
    engines = {
        impl: _build_arm_engine(impl, size, depth, spatial_cells,
                                (th, tw), bucket)
        for impl in arms
    }
    try:
        # PR-9 bit-identity crosscheck on the serving forward: the two
        # arms compile DIFFERENT schedules of the SAME function.
        probe = np.asarray(
            np.random.default_rng(7).standard_normal((size, size, 3)),
            np.float32,
        )
        probe_logits = {
            impl: eng.predict_one(probe) for impl, eng in engines.items()
        }
        vals = list(probe_logits.values())
        bit_identical = all(
            np.array_equal(vals[0], v) for v in vals[1:]
        )

        pooled = {
            impl: {
                "total_s": 0.0, "overlapped_s": 0.0, "per_trial": [],
                "lat_p50": [], "lat_p99": [], "rps": [],
                "deadline_misses": 0, "n_steps": 0, "crosscheck": None,
                "report": engines[impl].lint_report(bucket=bucket),
            }
            for impl in arms
        }
        for impl in arms:
            engines[impl].start()
        for _ in range(max(1, int(trials))):
            for impl in arms:
                eng, acc = engines[impl], pooled[impl]
                logdir = tempfile.mkdtemp(
                    prefix=f"mpi4dl-serving-sharded-{impl}-"
                )
                try:
                    with profiling.trace(logdir):
                        rep = run_closed_loop(
                            eng, requests, concurrency=concurrency,
                            deadline_s=60.0,
                        )
                    summary = analyze_trace_dir(
                        logdir, step_name="mpi4dl_serve_batch"
                    )
                finally:
                    shutil.rmtree(logdir, ignore_errors=True)
                if registry is not None:
                    publish_attribution(
                        summary, registry,
                        program=f"serving_sharded_{impl}",
                    )
                coll = summary["collective"]
                acc["total_s"] += coll["total_s"]
                acc["overlapped_s"] += coll["overlapped_s"]
                acc["per_trial"].append(coll["overlap_ratio"])
                acc["n_steps"] += summary["n_steps"]
                acc["lat_p50"].append(rep["latency_s"]["p50"])
                acc["lat_p99"].append(rep["latency_s"]["p99"])
                acc["rps"].append(rep["throughput_rps"])
                acc["deadline_misses"] += rep["deadline_misses"]
                if acc["crosscheck"] is None:
                    acc["crosscheck"] = [
                        f.as_dict()
                        for f in crosscheck_overlap(acc["report"], summary)
                    ]
    finally:
        for eng in engines.values():
            try:
                eng.stop()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass

    def _mean(xs):
        return sum(xs) / len(xs) if xs else None

    for impl in arms:
        acc, eng = pooled[impl], engines[impl]
        report = acc["report"]
        total = acc["total_s"]
        out["arms"][impl] = {
            "conv_impl": impl,
            "trace_overlap_ratio": (
                acc["overlapped_s"] / total if total > 0 else None
            ),
            "overlap_ratio_per_trial": acc["per_trial"],
            "latency_ms": {
                "p50": round(_mean(acc["lat_p50"]) * 1e3, 3),
                "p99": round(_mean(acc["lat_p99"]) * 1e3, 3),
            },
            "throughput_rps": round(_mean(acc["rps"]), 2),
            "deadline_misses": acc["deadline_misses"],
            "n_steps": acc["n_steps"],
            "halo_shifts": eng._predictor.halo_shifts(),
            "permutes": report.inventory.get("collective-permute", 0),
            "hlolint_errors": [
                f for f in report.findings if f["severity"] == "error"
            ],
            "crosscheck": acc["crosscheck"] or [],
        }
    out["bit_identical_arms"] = bool(bit_identical)
    mono = out["arms"].get("monolithic")
    dec = out["arms"].get("decomposed")
    if mono and dec:
        out["halo_shifts_equal"] = (
            mono["halo_shifts"] == dec["halo_shifts"]
        )
        rm, rd = mono["trace_overlap_ratio"], dec["trace_overlap_ratio"]
        out["overlap_improved"] = (
            rm is not None and rd is not None and rd > rm
        )
    return out


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.analyze serving-sharded",
        description="Sharded-serving halo/compute overlap A/B: monolithic "
                    "vs decomposed spatial conv on the serving hot path, "
                    "measured + mesh-lint gated",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--size", type=int, default=32)
    p.add_argument("--depth", type=int, default=8)
    p.add_argument("--spatial-cells", type=int, default=3)
    p.add_argument("--mesh", default="2x2",
                   help="serving tile mesh HxW (square, 1xW, or Hx1)")
    p.add_argument("--bucket", type=int, default=4,
                   help="the single batch bucket both arms warm")
    p.add_argument("--requests", type=int, default=48,
                   help="closed-loop requests per capture")
    p.add_argument("--concurrency", type=int, default=8)
    p.add_argument("--trials", type=int, default=1,
                   help="captures per arm, interleaved across arms")
    p.add_argument("--arm", action="append", dest="arms", default=None,
                   choices=("monolithic", "decomposed"),
                   help="restrict to one arm (repeatable); default both")
    p.add_argument("--json", dest="json_out", default=None,
                   help="write the A/B record here ('-' = stdout)")
    p.add_argument("--require-improvement", action="store_true",
                   help="exit 1 unless the decomposed arm's measured "
                        "overlap ratio strictly beats the monolithic one")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    from mpi4dl_tpu.serve.sharded import parse_mesh
    from mpi4dl_tpu.utils import enable_compilation_cache

    mesh = parse_mesh(args.mesh)
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # The tile mesh needs virtual devices before backend init — the
        # same 8-device simulation the test suite runs on.
        import jax

        jax.config.update("jax_num_cpu_devices", max(8, mesh[0] * mesh[1]))
    enable_compilation_cache()
    # Each arm pins its own impl at compile; an inherited process-wide
    # override would collapse the A/B into one arm measured twice.
    os.environ.pop("MPI4DL_TPU_CONV_OVERLAP", None)

    out = run_serving_sharded_ab(
        size=args.size, depth=args.depth,
        spatial_cells=args.spatial_cells, mesh=mesh, bucket=args.bucket,
        requests=args.requests, concurrency=args.concurrency,
        trials=args.trials,
        arms=tuple(args.arms) if args.arms else ("monolithic", "decomposed"),
    )
    for impl, arm in out["arms"].items():
        ratio = arm["trace_overlap_ratio"]
        print(
            f"# {impl}: overlap_ratio="
            f"{ratio if ratio is None else round(ratio, 4)} "
            f"p99={arm['latency_ms']['p99']}ms "
            f"rps={arm['throughput_rps']} permutes={arm['permutes']} "
            f"halo_shifts={arm['halo_shifts']} "
            f"lint_errors={len(arm['hlolint_errors'])} "
            f"crosscheck={len(arm['crosscheck'])}",
            file=sys.stderr, flush=True,
        )
    payload = json.dumps(out)
    if args.json_out == "-" or args.json_out is None:
        print(payload, flush=True)
    else:
        with open(args.json_out, "w") as f:
            f.write(payload + "\n")
    rc = 0
    if any(a["hlolint_errors"] for a in out["arms"].values()):
        rc = 1
    if not out.get("bit_identical_arms", True):
        rc = 1
    if args.require_improvement and not out.get("overlap_improved"):
        rc = 1
    return rc


if __name__ == "__main__":  # pragma: no cover — exercised via analyze.py
    sys.exit(main())
