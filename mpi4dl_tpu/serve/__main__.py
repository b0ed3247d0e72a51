"""``python -m mpi4dl_tpu.serve`` — start a serving engine and load-test it.

Restores a self-describing checkpoint (``--ckpt``) or builds a synthetic
calibrated ResNet (default — no artifacts needed), AOT-warms every bucket,
runs the requested load model, and prints ONE JSON report line to stdout
(bench.py's keep-the-last-line protocol). ``--lint`` additionally gates
the serving executable's HLO through hlolint (zero collectives on the
single-chip path) and fails the process on error-severity findings.

Examples::

    JAX_PLATFORMS=cpu python -m mpi4dl_tpu.serve --requests 64
    python -m mpi4dl_tpu.serve --ckpt /ckpts/run1 --mode open \
        --rate 200 --duration 10 --deadline-ms 50 --lint
    JAX_PLATFORMS=cpu python -m mpi4dl_tpu.serve --requests 512 \
        --slo-availability 99.9 --slo-latency-ms 50 --metrics-port 0
    JAX_PLATFORMS=cpu python -m mpi4dl_tpu.serve --mesh 2x2 \
        --requests 64 --lint   # spatially-sharded forward, halo-window gate
"""

from __future__ import annotations

import argparse
import json
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.serve",
        description="mpi4dl_tpu online serving engine + load generator",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--ckpt", default=None,
                   help="self-describing checkpoint dir/path "
                        "(default: synthetic calibrated ResNet)")
    p.add_argument("--depth", type=int, default=11,
                   help="synthetic ResNet-v2 depth (9n+2)")
    p.add_argument("--image-size", type=int, default=32,
                   help="synthetic model input size")
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--calib-batches", type=int, default=2,
                   help="synthetic BN calibration batches")
    p.add_argument("--mesh", default=None, metavar="HxW",
                   help="spatially shard the serving forward over a "
                        "tile_h x tile_w device mesh (e.g. 2x2, 1x2): "
                        "each request's H/W partitions across chips with "
                        "halo exchanges, the hlolint gate flips to the "
                        "partition-math halo-permute window, and the "
                        "synthetic model becomes a spatial ResNet-v1 "
                        "front (default: single-chip engine)")
    p.add_argument("--conv-overlap", default=None,
                   choices=("monolithic", "decomposed"),
                   help="spatial conv/pool impl for the sharded forward "
                        "(overlap_decompose: interior hides the halo "
                        "permute; bit-identical outputs); default "
                        "inherits MPI4DL_TPU_CONV_OVERLAP")
    p.add_argument("--spatial-cells", type=int, default=None,
                   help="leading cells of the sharded model that run "
                        "spatially partitioned (--mesh only; default: "
                        "the checkpoint's stored spatial_cells builder "
                        "arg, or 3 for the synthetic model)")
    p.add_argument("--tiled", default=None, metavar="HxW",
                   help="gigapixel tiled inference (serve/tiled.py): "
                        "serve images of this size on ONE chip by "
                        "streaming halo-correct overlap-read tiles "
                        "through a fixed tile executable and stitching "
                        "exactly — the /predict_tiled surface, with its "
                        "own 'tiled' SLO class and per-request "
                        "tile/stitch report (mutually exclusive with "
                        "--mesh; with --ckpt, HxW must match the "
                        "checkpoint's image size)")
    p.add_argument("--tile", type=int, default=None,
                   help="tiled core extent in input px (a multiple of "
                        "the model's cumulative stride; default: a "
                        "quarter of the image). `analyze memory-plan "
                        "--bisect tile` computes the largest that fits "
                        "a chip")
    p.add_argument("--tile-batch", type=int, default=1,
                   help="largest power-of-two TILE bucket the tiled "
                        "forward batches windows into per dispatch "
                        "(1 = the exact, bit-identical default; larger "
                        "buckets trade last-bit determinism for "
                        "throughput at the documented f32 tolerance)")
    p.add_argument("--max-batch", type=int, default=8,
                   help="largest micro-batch bucket (power of two)")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="batch formation window")
    p.add_argument("--max-queue", type=int, default=64,
                   help="admission-control queue bound (per SLO class)")
    p.add_argument("--deadline-ms", type=float, default=10000.0,
                   help="per-request deadline")
    p.add_argument("--scheduler", choices=("edf", "fifo"), default="edf",
                   help="batch former: edf = continuous scheduler "
                        "(deadline-ordered class queues, in-flight "
                        "re-admission, burn-rate feedback); fifo = the "
                        "windowed max-wait/max-size former (the A/B "
                        "baseline)")
    p.add_argument("--slo-classes", default=None, metavar="SPEC",
                   help="named SLO classes partitioning the queue, "
                        "NAME=THRESHOLD[:TARGET_PCT][@DEADLINE] comma-"
                        "separated (e.g. 'tight=50ms:99.9@200ms,"
                        "bulk=2s'); each threshold becomes a per-class "
                        "latency objective whose burn rate feeds the "
                        "scheduler")
    p.add_argument("--class-mix", default=None, metavar="MIX",
                   help="loadgen traffic mix over the declared classes, "
                        "NAME:WEIGHT[:DEADLINE] comma-separated (e.g. "
                        "'tight:1:10s,bulk:3:60s'); the report then "
                        "carries per-class latency under by_class")
    p.add_argument("--tenants", default=None, metavar="SPEC",
                   help="multi-tenant admission: NAME=RPS:BURST[:WEIGHT]"
                        "[@CLASSES] comma-separated (e.g. "
                        "'tight=200:50:4,bulk=50:200:1@bulk', "
                        "'bulk=none' = unlimited); each tenant gets a "
                        "token-bucket quota (over-quota floods shed with "
                        "retry_after_s BEFORE taking queue slots) and a "
                        "deficit-weighted-fair share of EDF batch fill; "
                        "an implicit unlimited 'default' tenant is "
                        "appended for unlabeled traffic")
    p.add_argument("--tenant-mix", default=None, metavar="MIX",
                   help="loadgen traffic mix over tenants, NAME:WEIGHT "
                        "comma-separated (e.g. 'bulk:10,tight:1'); the "
                        "report then carries per-tenant outcomes and "
                        "latency under by_tenant")
    p.add_argument("--mode", choices=("closed", "open"), default="closed")
    p.add_argument("--requests", type=int, default=64,
                   help="closed loop: total requests")
    p.add_argument("--concurrency", type=int, default=16,
                   help="closed loop: client count")
    p.add_argument("--rate", type=float, default=100.0,
                   help="open loop: offered requests/sec")
    p.add_argument("--duration", type=float, default=5.0,
                   help="open loop: seconds")
    p.add_argument("--queue-full-retries", type=int, default=0,
                   help="opt-in client retries per request on queue-full "
                        "admission bounces, backing off per the engine's "
                        "retry_after_s cadence hint (0 = shed instantly)")
    p.add_argument("--retry-backoff-ms", type=float, default=None,
                   help="explicit retry backoff base; default honors the "
                        "engine's QueueFullError.retry_after_s hint")
    p.add_argument("--serial", type=int, default=16,
                   help="batch-size-1 serial baseline requests (0 skips)")
    p.add_argument("--lint", action="store_true",
                   help="hlolint the serving executable; fail on errors")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve a Prometheus /metrics endpoint on this "
                        "port for the run (0 = ephemeral; the bound port "
                        "is in the report and on stderr)")
    p.add_argument("--telemetry-dir", default=None,
                   help="write JSONL span/metrics events here "
                        "(default: $MPI4DL_TPU_TELEMETRY_DIR, unset = off)")
    p.add_argument("--watchdog-factor", type=float, default=20.0,
                   help="trip the stalled-loop watchdog at this multiple "
                        "of the rolling p99 request latency (0 disables)")
    p.add_argument("--watchdog-min-timeout", type=float, default=2.0,
                   help="floor of the watchdog timeout, seconds")
    p.add_argument("--flight-capacity", type=int, default=512,
                   help="flight-recorder ring size in events (0 disables)")
    p.add_argument("--flight-dir", default=None,
                   help="where watchdog/crash/SIGTERM flight dumps land "
                        "(default: the telemetry dir, then the temp dir)")
    p.add_argument("--tail-factor", type=float, default=4.0,
                   help="slow-request capture: trip at this multiple of "
                        "the rolling p99 e2e latency (floored at the "
                        "latency SLO threshold when one is set)")
    p.add_argument("--tail-min-interval", type=float, default=1.0,
                   help="rate limit between captured tail.sample "
                        "events, seconds")
    p.add_argument("--tail-capacity", type=int, default=64,
                   help="tail-sample ring size on /debugz (0 disables "
                        "capture)")
    p.add_argument("--slo-availability", type=float, default=None,
                   metavar="PCT",
                   help="availability SLO target in percent (e.g. 99.9): "
                        "good outcomes / all outcomes of "
                        "serve_requests_total; enables the SLO evaluator, "
                        "burn-rate alerts, /alertz, and the advisory "
                        "autoscale gauge")
    p.add_argument("--slo-latency-ms", type=float, default=None,
                   metavar="MS",
                   help="latency SLO threshold: --slo-latency-target "
                        "percent of served requests must finish within "
                        "this many milliseconds (e2e)")
    p.add_argument("--slo-latency-target", type=float, default=99.0,
                   metavar="PCT",
                   help="latency SLO target in percent")
    p.add_argument("--slo-interval", type=float, default=1.0,
                   help="SLO evaluator tick, seconds")
    p.add_argument("--trace-dir", default=None,
                   help="capture an XProf trace of the load run here and "
                        "attribute device time per serve batch "
                        "(report key 'attribution', /debugz, trace_* "
                        "gauges)")
    p.add_argument("--attribution-every", type=int, default=0,
                   help="sampled continuous attribution: every N "
                        "dispatches, capture+attribute one batch and "
                        "publish live trace_* gauges under "
                        "program=serve_sampled (0 disables; mutually "
                        "exclusive with --trace-dir, whose profile owns "
                        "the profiler)")
    p.add_argument("--attribution-min-interval", type=float, default=30.0,
                   help="floor between attribution samples, seconds — "
                        "caps the amortized sampling overhead at "
                        "~capture cost / interval regardless of rps")
    p.add_argument("--memory-guard", action="store_true",
                   help="refuse to warm any bucket whose footprint-"
                        "ledger predicted peak exceeds the device limit "
                        "(or whose compile OOMs) instead of crashing — "
                        "serving degrades to the buckets that fit")
    p.add_argument("--memory-limit-bytes", type=int, default=None,
                   help="device-capacity override for the memory guard "
                        "(default: the device's memory_stats() limit)")
    p.add_argument("--no-memory-monitor", action="store_true",
                   help="disable the live device_hbm_* gauge sampler")
    p.add_argument("--json", dest="json_out", default=None,
                   help="also write the report JSON here")
    return p


def _sharded_synthetic_engine(args, mesh_shape):
    """``--mesh HxW``: the sharded zero-artifact path — a spatial
    ResNet-v1 front over the tile mesh (serve/sharded.py), batcher and
    telemetry stack identical to the single-chip engine's."""
    from mpi4dl_tpu.serve.sharded import synthetic_sharded_engine

    return synthetic_sharded_engine(
        mesh_shape, image_size=args.image_size,
        depth=args.depth if args.depth != 11 else 8,  # v1 depths are 6n+2
        num_classes=args.classes,
        spatial_cells=(
            args.spatial_cells if args.spatial_cells is not None else 3
        ),
        calib_batches=args.calib_batches, conv_overlap=args.conv_overlap,
        max_batch=args.max_batch, max_wait_s=args.max_wait_ms / 1e3,
        max_queue=args.max_queue,
        default_deadline_s=args.deadline_ms / 1e3,
        metrics_port=args.metrics_port, telemetry_dir=args.telemetry_dir,
        **_liveness_kw(args),
    )


def _parse_tiled_size(spec: str) -> int:
    """``--tiled HxW`` → the (square) image extent; the synthetic tiled
    model's global-pool head needs H == W."""
    try:
        h, w = (int(p) for p in str(spec).lower().split("x"))
    except ValueError:
        raise SystemExit(
            f"--tiled must look like HxW (e.g. 8192x8192), got {spec!r}"
        ) from None
    if h != w:
        raise SystemExit(
            f"--tiled serves square images (the model head pools the "
            f"full feature map), got {h}x{w}"
        )
    return h


def _tiled_engine(args):
    """``--tiled HxW``: the gigapixel tile-streaming engine — synthetic
    by default, or the checkpoint's model served tiled (the size must
    match the checkpoint's, since the head is size-bound)."""
    from mpi4dl_tpu.serve.tiled import (
        synthetic_tiled_engine,
        tiled_engine_from_checkpoint,
    )

    size = _parse_tiled_size(args.tiled)
    kw = dict(
        tile=args.tile, tile_batch=args.tile_batch,
        max_queue=args.max_queue,
        default_deadline_s=args.deadline_ms / 1e3,
        metrics_port=args.metrics_port, telemetry_dir=args.telemetry_dir,
        **_liveness_kw(args),
    )
    if args.ckpt:
        eng = tiled_engine_from_checkpoint(args.ckpt, **kw)
        if eng.example_shape[0] != size:
            raise SystemExit(
                f"--tiled {size}x{size} does not match the checkpoint's "
                f"image size {eng.example_shape[0]} — the head is bound "
                "to the size the model was built for"
            )
        return eng
    return synthetic_tiled_engine(
        size, depth=args.depth if args.depth != 11 else 8,  # v1: 6n+2
        num_classes=args.classes, calib_batches=args.calib_batches,
        **kw,
    )


def _synthetic_engine(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4dl_tpu.evaluate import collect_batch_stats
    from mpi4dl_tpu.models.resnet import get_resnet_v2
    from mpi4dl_tpu.parallel.partition import init_cells
    from mpi4dl_tpu.serve import ServingEngine

    size = args.image_size
    cells = get_resnet_v2(
        depth=args.depth, num_classes=args.classes, pool_kernel=size // 4
    )
    rng = np.random.default_rng(0)
    x0 = jnp.zeros((1, size, size, 3), jnp.float32)
    params = init_cells(cells, jax.random.PRNGKey(0), x0)
    cal = [
        jnp.asarray(rng.standard_normal((4, size, size, 3)), jnp.float32)
        for _ in range(args.calib_batches)
    ]
    stats = collect_batch_stats(cells, params, cal)
    return ServingEngine(
        cells, params, stats, example_shape=(size, size, 3),
        max_batch=args.max_batch, max_wait_s=args.max_wait_ms / 1e3,
        max_queue=args.max_queue,
        default_deadline_s=args.deadline_ms / 1e3,
        metrics_port=args.metrics_port, telemetry_dir=args.telemetry_dir,
        **_liveness_kw(args),
    )


def _liveness_kw(args) -> dict:
    return {
        "slo_classes": args.slo_classes,
        "tenants": args.tenants,
        "scheduler": args.scheduler,
        "watchdog_factor": args.watchdog_factor or None,
        "watchdog_min_timeout_s": args.watchdog_min_timeout,
        "flight_capacity": args.flight_capacity,
        "flight_dir": args.flight_dir,
        "slo": _slo_config(args),
        "attribution_every": args.attribution_every,
        "attribution_min_interval_s": args.attribution_min_interval,
        "memory_guard": args.memory_guard,
        "memory_limit_bytes": args.memory_limit_bytes,
        "memory_monitor": not args.no_memory_monitor,
        "tail_factor": args.tail_factor,
        "tail_min_interval_s": args.tail_min_interval,
        "tail_capacity": args.tail_capacity,
    }


def _slo_config(args):
    """``--slo-availability 99.9 --slo-latency-ms 50`` → SLOConfig (CLI
    speaks percent, the library speaks ratios); None when neither
    objective is requested."""
    if args.slo_availability is None and args.slo_latency_ms is None:
        return None
    from mpi4dl_tpu.telemetry import SLOConfig

    return SLOConfig(
        availability=(
            args.slo_availability / 100.0
            if args.slo_availability is not None else None
        ),
        latency_threshold_s=(
            args.slo_latency_ms / 1e3
            if args.slo_latency_ms is not None else None
        ),
        latency_target=args.slo_latency_target / 100.0,
        interval_s=args.slo_interval,
    )


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    import os

    if args.tiled and args.mesh:
        raise SystemExit(
            "--tiled and --mesh are mutually exclusive: tiled streaming "
            "serves huge images on ONE chip; --mesh shards across chips"
        )

    mesh_shape = None
    if args.mesh:
        from mpi4dl_tpu.serve.sharded import parse_mesh

        mesh_shape = parse_mesh(args.mesh)
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # The tile mesh needs virtual devices before backend init
            # (the same simulation the test suite / analyze CLI use).
            import jax

            jax.config.update(
                "jax_num_cpu_devices", max(8, mesh_shape[0] * mesh_shape[1])
            )

    from mpi4dl_tpu.serve import ServingEngine
    from mpi4dl_tpu.serve.loadgen import (
        run_closed_loop,
        run_open_loop,
        serial_throughput,
    )

    if args.tiled:
        engine = _tiled_engine(args)
    elif args.ckpt and mesh_shape is not None:
        # Checkpoint → sharded serve: the spatial twin's builder args ride
        # in the checkpoint metadata (model_metadata(spatial_cells=...)),
        # so the path + mesh is all the config needed.
        from mpi4dl_tpu.serve.sharded import sharded_engine_from_checkpoint

        engine = sharded_engine_from_checkpoint(
            args.ckpt, mesh_shape, spatial_cells=args.spatial_cells,
            conv_overlap=args.conv_overlap,
            max_batch=args.max_batch, max_wait_s=args.max_wait_ms / 1e3,
            max_queue=args.max_queue,
            default_deadline_s=args.deadline_ms / 1e3,
            metrics_port=args.metrics_port,
            telemetry_dir=args.telemetry_dir,
            **_liveness_kw(args),
        )
    elif args.ckpt:
        engine = ServingEngine.from_checkpoint(
            args.ckpt, max_batch=args.max_batch,
            max_wait_s=args.max_wait_ms / 1e3, max_queue=args.max_queue,
            default_deadline_s=args.deadline_ms / 1e3,
            metrics_port=args.metrics_port, telemetry_dir=args.telemetry_dir,
            **_liveness_kw(args),
        )
    elif mesh_shape is not None:
        engine = _sharded_synthetic_engine(args, mesh_shape)
    else:
        engine = _synthetic_engine(args)

    # Postmortem on SIGTERM: dump the flight ring before the default
    # disposition terminates the process.
    engine.flight.install_signal_handlers()

    # Supervised replica (elastic.supervise / the fleet babysitter):
    # health-gated heartbeat — a wedged batcher trips the watchdog, the
    # beats stop, the supervisor kills and restarts this process.
    from mpi4dl_tpu import elastic

    heartbeat = None
    hb_path = elastic.heartbeat_path_from_env()
    if hb_path:
        heartbeat = elastic.HeartbeatReporter(
            hb_path, health=engine.health, watchdog=engine.watchdog,
        )
        heartbeat.start()

    if args.ckpt:
        model_name = "checkpoint:" + args.ckpt
    elif args.tiled:
        model_name = (
            f"synthetic_resnet_tiled{engine.example_shape[0]}px"
        )
    else:
        model_name = f"synthetic_resnet{args.depth}_{args.image_size}px"
    report = {
        "model": model_name,
        "buckets": list(engine.buckets),
        "mesh": list(engine.mesh_shape),
    }
    if engine.metrics_port is not None:
        report["metrics_port"] = engine.metrics_port
        # stderr, not stdout: the stdout protocol is "keep the last JSON
        # line", and the scrape URL must be visible while the run is live.
        endpoints = "/healthz, /debugz" + (
            ", /alertz" if engine.slo is not None else ""
        )
        print(
            f"# metrics: http://127.0.0.1:{engine.metrics_port}/metrics "
            f"(also {endpoints})",
            file=sys.stderr, flush=True,
        )
    if args.serial:
        report["serial"] = serial_throughput(engine, args.serial)

    from contextlib import nullcontext

    from mpi4dl_tpu.profiling import trace as profiler_trace

    engine.start()
    try:
        with profiler_trace(args.trace_dir) if args.trace_dir \
                else nullcontext():
            retry_kw = {
                "queue_full_retries": args.queue_full_retries,
                "retry_backoff_s": (
                    args.retry_backoff_ms / 1e3
                    if args.retry_backoff_ms is not None else None
                ),
            }
            if args.class_mix:
                from mpi4dl_tpu.serve.loadgen import ClassMix

                retry_kw["class_mix"] = ClassMix.parse(args.class_mix)
            if args.tenant_mix:
                from mpi4dl_tpu.serve.loadgen import TenantMix

                retry_kw["tenant_mix"] = TenantMix.parse(args.tenant_mix)
            if args.mode == "closed":
                report["loadgen"] = run_closed_loop(
                    engine, args.requests, concurrency=args.concurrency,
                    deadline_s=args.deadline_ms / 1e3,
                    events=engine.events, **retry_kw,
                )
            else:
                report["loadgen"] = run_open_loop(
                    engine, rate_rps=args.rate, duration_s=args.duration,
                    deadline_s=args.deadline_ms / 1e3,
                    events=engine.events, **retry_kw,
                )
    finally:
        engine.stop()
        if heartbeat is not None:
            heartbeat.close()

    if args.trace_dir:
        try:
            from mpi4dl_tpu.analysis.trace import (
                analyze_trace_dir,
                publish_attribution,
            )

            summary = analyze_trace_dir(
                args.trace_dir, step_name="mpi4dl_serve_batch"
            )
            publish_attribution(
                summary, engine.registry, program="serve_batch"
            )
            engine.set_attribution(summary)
            report["attribution"] = {
                k: summary[k]
                for k in ("n_steps", "per_step_mean", "range", "collective")
            }
        except Exception as e:  # noqa: BLE001 — attribution is advisory;
            # the load report must survive a broken trace
            report["attribution"] = {
                "error": f"{type(e).__name__}: {str(e)[:160]}"
            }

    if args.attribution_every and engine.last_attribution is not None:
        # The most recent sampled capture (the live gauges' source).
        report["attribution_sampled"] = engine.last_attribution

    if args.tiled:
        # Per-request tile counts + stitch/stream latency percentiles —
        # the loadgen numbers a gigapixel surface is judged by alongside
        # p50/p90/p99.
        report["tiled"] = engine.stats().get("tiled")

    if engine.slo is not None:
        report["slo"] = engine.slo.verdict()

    if args.serial and report["serial"]["throughput_rps"] > 0:
        report["speedup_vs_serial"] = (
            report["loadgen"]["throughput_rps"]
            / report["serial"]["throughput_rps"]
        )

    lint_failed = False
    if args.lint:
        rep = engine.lint_report()
        report["lint"] = {
            "ok": rep.ok,
            "summary": rep.summary_line(),
            "findings": rep.findings,
        }
        lint_failed = not rep.ok

    line = json.dumps(report)
    print(line, flush=True)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(line + "\n")
    return 2 if lint_failed else 0


if __name__ == "__main__":
    sys.exit(main())
