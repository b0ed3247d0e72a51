"""``python -m mpi4dl_tpu.analyze`` — compile a train step, lint its HLO.

Builds the same Trainer the bench/tests use, compiles
``trainer._jit_step.lower(...).compile()`` (no step is ever executed — on a
CPU mesh this lints the full distributed program without touching a TPU),
derives partition-math expectations (tile grid + counted halo shifts), runs
the rule engine, and writes one JSON report. Exit status is the lint gate:
nonzero iff findings at/above ``--fail-on`` severity exist.

Examples::

    JAX_PLATFORMS=cpu python -m mpi4dl_tpu.analyze --model resnet \
        --size 512 --json /tmp/r.json
    python -m mpi4dl_tpu.analyze --model amoebanet --size 64 --dp 2
    python -m mpi4dl_tpu.analyze --model resnet --size 512 --write-baseline

Subcommands: ``python -m mpi4dl_tpu.analyze bench-history
BENCH_r*.json`` compares the committed bench rounds and fails on a
throughput regression (:mod:`mpi4dl_tpu.analysis.bench_history`);
``python -m mpi4dl_tpu.analyze trace-export LOG... [--trace-id ID]``
joins span segments from N processes' JSONL telemetry logs by trace id
and writes one Chrome trace — a request's full client → queue → batch →
device lifetime across process boundaries
(:func:`mpi4dl_tpu.telemetry.federation.trace_export_main`);
``python -m mpi4dl_tpu.analyze tail LOGS... [--trace-id ID] [--top N]``
joins histogram exemplars, span segments, and ``tail.sample`` events to
answer "why was this request slow" per trace id — phase breakdown vs the
window p50, dominant phase named, worst-requests table
(:mod:`mpi4dl_tpu.analysis.tail`);
``python -m mpi4dl_tpu.analyze incident LOGS... [--incident-id ID]
[--json|--md]`` reconstructs incident timelines and postmortems —
lifecycle, causally ordered evidence, named first cause, blast radius —
from JSONL logs alone, matching the live ``/incidentz`` event for event
(:mod:`mpi4dl_tpu.analysis.incident`);
``python -m mpi4dl_tpu.analyze memory-plan`` predicts peak HBM vs the
device limit for a requested config — compile-only, nothing executes —
and bisects the max feasible px/bucket
(:mod:`mpi4dl_tpu.analysis.memory_plan`);
``python -m mpi4dl_tpu.analyze sp-overlap`` measures the SP 2×2 train
step's halo/compute overlap A/B — monolithic vs decomposed spatial conv
— with live trace attribution, partition-math lint, and the
``trace-overlap-crosscheck`` on each arm
(:mod:`mpi4dl_tpu.analysis.overlap_bench`);
``python -m mpi4dl_tpu.analyze serving-sharded`` runs the same A/B on the
SERVING hot path — a spatially-sharded ServingEngine under closed-loop
load per arm, with per-request latency, the mesh-derived lint gate, and
the bit-identity crosscheck between arms
(:mod:`mpi4dl_tpu.analysis.serving_overlap`);
``python -m mpi4dl_tpu.analyze pipeline`` measures the LP pipeline's
schedule A/B — gpipe vs interleaved 1f1b — with live per-stage trace
attribution, the measured bubble fraction cross-checked against the
schedule model, and the exact stage-permute lint budget
(:mod:`mpi4dl_tpu.analysis.pipeline_bench`);
``python -m mpi4dl_tpu.analyze costmodel`` prices a compiled program's
collectives under a parameterized interconnect table — predicted comms
seconds, achievable overlap ceiling, schedule-model bubble — publishes
the ``hlolint_predicted_*`` gauges, and crosschecks against a live trace
capture (``cost-model-crosscheck``); its ``--artifact`` mode prices
committed lint-report JSONs with no jax at all
(:mod:`mpi4dl_tpu.analysis.costmodel`);
``python -m mpi4dl_tpu.analyze coldstart LEDGER.json LOGS.jsonl ...``
ranks executables by compile seconds across footprint-ledger dumps
(grouped by content fingerprint), joins ``elastic.restart`` events and
the fleet recovery phase decomposition, and gates on ``--budget-s`` —
pure JSON, its ``--artifact`` mode needs no jax at all
(:mod:`mpi4dl_tpu.analysis.coldstart`);
``python -m mpi4dl_tpu.analyze numerics`` audits the three serving
forwards — single-chip, spatially sharded, halo-tiled — against each
other on the SAME deterministic canary batch and one weight set, gated
per pair at the documented f32 tolerances; its ``--artifact`` mode
re-gates committed audit reports and summarizes ``canary.failure``
events with no jax at all (:mod:`mpi4dl_tpu.analysis.numerics`).
"""

from __future__ import annotations

import argparse
import sys

from mpi4dl_tpu.analysis.rules import SEVERITY_ORDER


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.analyze",
        description="Static HLO lint over a compiled mpi4dl_tpu train step",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--model", choices=("resnet", "amoebanet"), default="resnet")
    p.add_argument("--size", type=int, default=512, help="square image size")
    p.add_argument("--batch", type=int, default=4, help="global batch size")
    p.add_argument("--depth", type=int, default=8, help="ResNet depth (v1)")
    p.add_argument(
        "--layers", type=int, default=6, help="AmoebaNet-D layer count"
    )
    p.add_argument(
        "--filters", type=int, default=64, help="AmoebaNet-D filter count"
    )
    p.add_argument(
        "--spatial-parts", type=int, default=4,
        help="spatial tiles for the resnet SP front (0 = pure DP)",
    )
    p.add_argument(
        "--spatial-cells", type=int, default=3,
        help="leading cells that run spatially partitioned (resnet)",
    )
    p.add_argument("--slice", default="square", dest="slice_method",
                   choices=("square", "vertical", "horizontal"))
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel replicas (0 = 1 for spatial, 2 for DP)")
    p.add_argument(
        "--remat", default="none",
        choices=("none", "cell", "sqrt", "scan", "scan2", "scanlog",
                 "scanq", "scan_save", "cell_save", "group_save"),
    )
    p.add_argument("--json", dest="json_out", default=None,
                   help="write the full report JSON here")
    p.add_argument("--baseline", default=None,
                   help="peak-memory baseline file "
                        "(default docs/artifacts/hlolint_baseline.json)")
    p.add_argument("--write-baseline", action="store_true",
                   help="record this run's peak memory as the new baseline")
    p.add_argument("--fail-on", default="error",
                   choices=("error", "warn", "never"),
                   help="minimum finding severity that fails the process")
    p.add_argument("--tolerance", type=float, default=0.05,
                   help="relative peak-memory regression tolerance")
    return p


def _build_trainer(args):
    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.train import Trainer

    spatial = args.model == "resnet" and args.spatial_parts > 0
    dp = args.dp or (1 if spatial else 2)
    remat = False if args.remat == "none" else args.remat
    if spatial:
        cfg = ParallelConfig(
            batch_size=args.batch, split_size=1, spatial_size=1,
            num_spatial_parts=(args.spatial_parts,),
            slice_method=args.slice_method,
            image_size=args.size, data_parallel=dp,
        )
    else:
        cfg = ParallelConfig(
            batch_size=args.batch, split_size=1, spatial_size=0,
            image_size=args.size, data_parallel=dp,
        )

    if args.model == "resnet":
        from mpi4dl_tpu.models.resnet import get_resnet_v1

        plain = get_resnet_v1(depth=args.depth)
        n_sp = min(args.spatial_cells, len(plain) - 1) if spatial else 0
        cells = (
            get_resnet_v1(depth=args.depth, spatial_cells=n_sp)
            if n_sp else plain
        )
        trainer = Trainer(
            cells, num_spatial_cells=n_sp, config=cfg, remat=remat,
            plain_cells=plain if n_sp else None,
        )
    else:
        from mpi4dl_tpu.models.amoebanet import amoebanetd

        cells = amoebanetd(
            num_classes=10, num_layers=args.layers, num_filters=args.filters
        )
        n_sp = 0
        trainer = Trainer(cells, num_spatial_cells=0, config=cfg, remat=remat)
    return trainer, cfg, n_sp


def _config_key(args, platform: str) -> str:
    shape = (
        f"sp{args.spatial_parts}x{args.spatial_cells}_{args.slice_method}"
        if args.model == "resnet" and args.spatial_parts > 0
        else f"dp{args.dp or 2}"
    )
    arch = (
        f"d{args.depth}" if args.model == "resnet"
        else f"l{args.layers}f{args.filters}"
    )
    return (
        f"{args.model}_{arch}_{args.size}px_bs{args.batch}_{shape}"
        f"_{args.remat}_{platform}"
    )


def main(argv=None) -> int:
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if argv and argv[0] == "bench-history":
        # Pure-JSON subcommand: no jax, no devices, no compile — safe to
        # dispatch before any backend setup.
        from mpi4dl_tpu.analysis.bench_history import main as bench_history

        return bench_history(argv[1:])
    if argv and argv[0] == "trace-export":
        # Also pure JSON: joins JSONL span logs into a Chrome trace.
        from mpi4dl_tpu.telemetry.federation import trace_export_main

        return trace_export_main(argv[1:])
    if argv and argv[0] == "tail":
        # Tail forensics: join histogram exemplars, cross-process span
        # segments, and tail.sample events to explain slow requests per
        # trace id. Pure JSON — runs on logs from a dead machine.
        from mpi4dl_tpu.analysis.tail import main as tail_main

        return tail_main(argv[1:])
    if argv and argv[0] == "incident":
        # Incident reconstruction: rebuild incident.open/update/close
        # lifecycles, correlated timelines, first causes, and blast
        # radii from JSONL logs — the offline twin of /incidentz. Pure
        # JSON — runs on logs from a dead machine.
        from mpi4dl_tpu.analysis.incident import main as incident_main

        return incident_main(argv[1:])
    if argv and argv[0] == "sp-overlap":
        # SP 2x2 halo/compute overlap A/B (monolithic vs decomposed
        # spatial conv): sets up its own CPU mesh + jax like the lint
        # path, measures a live capture per arm, lints both programs.
        from mpi4dl_tpu.analysis.overlap_bench import main as sp_overlap

        return sp_overlap(argv[1:])
    if argv and argv[0] == "pipeline":
        # Pipeline schedule A/B (gpipe vs interleaved 1f1b): sets up its
        # own CPU mesh like sp-overlap, measures a live capture per arm
        # (measured bubble fraction + img/s), lints both programs at the
        # exact stage-permute budget.
        from mpi4dl_tpu.analysis.pipeline_bench import main as pipeline_ab

        return pipeline_ab(argv[1:])
    if argv and argv[0] == "serving-sharded":
        # Sharded-serving overlap A/B (monolithic vs decomposed conv on
        # the serving hot path): builds its own CPU tile mesh like
        # sp-overlap, measures a load-run capture per arm, lints both
        # programs against the mesh-derived halo window.
        from mpi4dl_tpu.analysis.serving_overlap import main as serving_ab

        return serving_ab(argv[1:])
    if argv and argv[0] == "costmodel":
        # Static communication cost model. Its --artifact mode (price
        # committed lint-report JSONs under an interconnect table) is
        # pure JSON and dispatches before any backend setup, like
        # bench-history; the live mode compiles on its own mesh and
        # crosschecks the predictions against a short trace capture.
        from mpi4dl_tpu.analysis.costmodel import main as costmodel_main

        return costmodel_main(argv[1:])
    if argv and argv[0] == "coldstart":
        # Cold-start manifest: rank executables by compile seconds
        # across footprint-ledger dumps, join elastic.restart events and
        # fleet recovery phase decompositions. Pure JSON — runs on
        # artifacts from a dead machine, dispatches before any backend
        # setup like bench-history.
        from mpi4dl_tpu.analysis.coldstart import main as coldstart_main

        return coldstart_main(argv[1:])
    if argv and argv[0] == "numerics":
        # Cross-predictor canary equivalence audit (single-chip vs
        # sharded vs tiled at the documented f32 tolerances). Its
        # --artifact mode (re-gate committed audit reports, summarize
        # canary.failure JSONL events) is pure JSON and dispatches
        # before any backend setup, like bench-history; the live mode
        # sets up its own CPU mesh like sp-overlap.
        from mpi4dl_tpu.analysis.numerics import main as numerics_main

        return numerics_main(argv[1:])
    if argv and argv[0] == "memory-plan":
        # Feasibility planner. Its artifact mode (committed peaks vs a
        # limit) is pure JSON and must dispatch before any backend
        # setup, like bench-history; its compile mode sets up jax
        # itself only when asked to lower a config.
        from mpi4dl_tpu.analysis.memory_plan import main as memory_plan

        return memory_plan(argv[1:])
    args = build_parser().parse_args(argv)

    from mpi4dl_tpu.utils import enable_compilation_cache

    import os

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # The CPU mesh needs virtual devices before backend init (the same
        # 8-device simulation the test suite runs on).
        import jax

        jax.config.update("jax_num_cpu_devices", 8)
    enable_compilation_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4dl_tpu.analysis.expectations import compose
    from mpi4dl_tpu.analysis.memory import load_baseline, write_baseline
    from mpi4dl_tpu.analysis.report import analyze_compiled

    platform = jax.devices()[0].platform
    trainer, cfg, n_sp = _build_trainer(args)

    x_shape = (args.batch, args.size, args.size, 3)
    state = trainer.init(jax.random.PRNGKey(0), x_shape)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(x_shape), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(args.batch,)), jnp.int32)
    xs, ys = trainer.shard_batch(x, y)
    compiled = trainer._jit_step.lower(state, xs, ys).compile()

    # Algebra-derived gate: the trainer contributes its layer deltas
    # (spatial halo window or pure-DP) and compose() folds them into the
    # Expectations the rules consume — no hand-built special cases.
    expected = compose(trainer.collective_deltas(state.params, x_shape))

    key = _config_key(args, platform)
    baseline = load_baseline(key, args.baseline)
    report = analyze_compiled(
        compiled,
        expected=expected,
        remat=trainer.remat_report(),
        platform=platform,
        config={
            "key": key,
            "model": args.model,
            "image_size": args.size,
            "batch_size": args.batch,
            "spatial_cells": n_sp,
            "tile_shape": list(cfg.tile_shape),
            "data_parallel": cfg.data_parallel,
            "remat": args.remat,
            "halo_shifts": expected.halo_shifts,
        },
        baseline_bytes=baseline,
        tolerance=args.tolerance,
    )

    if args.write_baseline and report.memory:
        path = write_baseline(key, report.memory["peak_bytes"], args.baseline)
        print(f"# baseline[{key}] <- {report.memory['peak_bytes']} B ({path})")

    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(report.to_json())
            f.write("\n")
    print(report.summary_line())
    for f in report.findings:
        loc = f" [{f['location']}]" if f.get("location") else ""
        print(f"  {f['severity'].upper()} {f['rule']}{loc}: {f['message']}")

    if args.fail_on == "never" or report.max_severity is None:
        return 0
    if SEVERITY_ORDER[report.max_severity] >= SEVERITY_ORDER[args.fail_on]:
        return 1
    return 0


if __name__ == "__main__":  # pragma: no cover - exercised via analyze.py
    sys.exit(main())
