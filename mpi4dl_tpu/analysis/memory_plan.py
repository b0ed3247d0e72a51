"""``python -m mpi4dl_tpu.analyze memory-plan`` — the HBM feasibility planner.

Answers "will this config fit?" *before* anything executes — the question
the bench walk could only answer by dying at 8192² with an unparsed
RESOURCE_EXHAUSTED, and the question every scale-out item on the ROADMAP
(gigapixel tiled inference, multi-chip serving, the replica fleet) needs
a number for. Two modes:

**Artifact mode** (pure JSON — dispatched in ``analysis/cli.py`` before
any jax/backend setup, like ``bench-history``): read committed predicted
peaks — the hlolint baseline (``docs/artifacts/hlolint_baseline.json``)
and/or a :class:`~mpi4dl_tpu.telemetry.memory.FootprintLedger` dump —
and render a fits/doesn't verdict per key against ``--limit-gb`` /
``--limit-bytes``::

    python -m mpi4dl_tpu.analyze memory-plan --limit-gb 15.48
    python -m mpi4dl_tpu.analyze memory-plan --ledger ledger.json \
        --limit-bytes 16106127360 --json plan.json

**Compile mode** (``--program serve|train``): AOT-lower the requested
config WITHOUT executing it and predict its peak from the compiled
buffer assignment (:func:`mpi4dl_tpu.analysis.memory.memory_summary`) —
the number the allocator will actually request, exact by construction
(the admission guard in :class:`mpi4dl_tpu.serve.ServingEngine` reads
the same summary off the same executables). The serve path is lowered
fully abstractly (``jax.eval_shape`` params + batch-stats structure, a
``ShapeDtypeStruct`` input) — zero device arrays are ever materialized.
``--bisect px|bucket`` binary-searches the candidate ladder for the
largest feasible value::

    JAX_PLATFORMS=cpu python -m mpi4dl_tpu.analyze memory-plan \
        --program serve --size 1024 --bucket 8 --limit-gb 15.48
    JAX_PLATFORMS=cpu python -m mpi4dl_tpu.analyze memory-plan \
        --program serve --bucket 1 --bisect px --limit-gb 15.48
    JAX_PLATFORMS=cpu python -m mpi4dl_tpu.analyze memory-plan \
        --program train --model resnet --size 2048 --batch 1 \
        --remat scan --limit-gb 15.48

Exit status: 0 when everything asked about fits (or the bisect found a
feasible value), 1 when something does not fit, 2 on unusable input.
"""

from __future__ import annotations

import argparse
import json
import sys

from mpi4dl_tpu.analysis.memory import (
    DEFAULT_BASELINE_PATH,
    feasibility,
    load_baseline_all,
)

DEFAULT_PX_LADDER = "256,512,1024,1536,2048,3072,4096,6144,8192"
DEFAULT_TILE_LADDER = "64,128,256,512,1024,2048,4096"


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.analyze memory-plan",
        description="Predict peak HBM vs device limit; bisect feasibility",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    # -- limit (both modes) --------------------------------------------------
    p.add_argument("--limit-bytes", type=int, default=None,
                   help="device memory limit in bytes")
    p.add_argument("--limit-gb", type=float, default=None,
                   help="device memory limit in GiB (e.g. 15.48)")
    p.add_argument("--fit-margin", type=float, default=0.0,
                   help="required post-fit headroom fraction of the limit")
    p.add_argument("--json", dest="json_out", default=None,
                   help="write the machine-readable plan here")
    # -- artifact mode (pure JSON, no jax) -----------------------------------
    p.add_argument("--baseline", default=None,
                   help="hlolint baseline JSON of committed peaks "
                        f"(default {DEFAULT_BASELINE_PATH})")
    p.add_argument("--ledger", default=None,
                   help="a FootprintLedger dump "
                        "(telemetry.FootprintLedger.dump / "
                        "engine stats()['memory']['programs'])")
    p.add_argument("--key", action="append", default=None,
                   help="restrict artifact mode to these keys "
                        "(repeatable; substring match)")
    # -- compile mode --------------------------------------------------------
    p.add_argument("--program", choices=("serve", "train"), default=None,
                   help="AOT-lower this program instead of reading "
                        "artifacts (needs jax; nothing is executed)")
    p.add_argument("--model", choices=("resnet", "amoebanet"),
                   default="resnet")
    p.add_argument("--size", type=int, default=512,
                   help="square image size (px)")
    p.add_argument("--bucket", type=int, default=1,
                   help="serve: batch bucket to lower")
    p.add_argument("--batch", type=int, default=1,
                   help="train: global batch size")
    p.add_argument("--depth", type=int, default=11,
                   help="resnet depth (9n+2 for serve's v2, v1 for train)")
    p.add_argument("--layers", type=int, default=6,
                   help="amoebanet layer count")
    p.add_argument("--filters", type=int, default=64,
                   help="amoebanet filter count")
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--dtype", default="float32",
                   choices=("float32", "bfloat16"))
    p.add_argument("--dp", type=int, default=0,
                   help="train: data-parallel replicas (0 = cli default)")
    p.add_argument("--spatial-parts", type=int, default=0,
                   help="train: spatial tiles (resnet; 0 = pure DP)")
    p.add_argument("--remat", default="none",
                   choices=("none", "cell", "sqrt", "scan", "scan2",
                            "scanlog", "scanq", "scan_save", "cell_save",
                            "group_save"))
    p.add_argument("--bisect", choices=("px", "bucket", "tile"),
                   default=None,
                   help="binary-search the largest feasible value on the "
                        "candidate ladder (needs a limit). 'tile' "
                        "answers the gigapixel question: the largest "
                        "tile core whose tile-streaming executables "
                        "(section window + stitched-feature head, "
                        "serve/tiled.py) both fit the chip at --size")
    p.add_argument("--px-candidates", default=DEFAULT_PX_LADDER,
                   help="comma-separated px ladder for --bisect px")
    p.add_argument("--max-bucket", type=int, default=64,
                   help="largest power-of-two bucket for --bisect bucket")
    p.add_argument("--tile", type=int, default=None,
                   help="serve: predict the TILED forward's peaks at "
                        "this tile core instead of the monolithic "
                        "forward (a stride-aligned px count)")
    p.add_argument("--tile-candidates", default=DEFAULT_TILE_LADDER,
                   help="comma-separated stride-aligned tile-core "
                        "ladder for --bisect tile")
    p.add_argument("--tile-bucket", type=int, default=8,
                   help="TILE bucket the tiled section executable is "
                        "lowered at (the runtime's largest tile batch)")
    return p


def _resolve_limit(args, device_limit=None) -> "int | None":
    if args.limit_bytes is not None:
        return int(args.limit_bytes)
    if args.limit_gb is not None:
        return int(args.limit_gb * 2**30)
    return device_limit


# -- artifact mode (NO jax import anywhere on this path) ----------------------


def _artifact_entries(args) -> "list[dict]":
    entries = []
    if args.ledger:
        with open(args.ledger) as f:
            data = json.load(f)
        rows = data.get("entries", data) if isinstance(data, dict) else data
        for e in rows:
            key = e.get("program", "?")
            if e.get("bucket") is not None:
                key = f"{key}[{e['bucket']}]"
            entries.append({"key": key, "peak_bytes": e.get("peak_bytes")})
    if args.baseline or not args.ledger:
        for key, peak in sorted(load_baseline_all(args.baseline).items()):
            entries.append({"key": key, "peak_bytes": peak})
    if args.key:
        entries = [
            e for e in entries
            if any(k in e["key"] for k in args.key)
        ]
    return entries


def _artifact_mode(args) -> int:
    entries = _artifact_entries(args)
    limit = _resolve_limit(args)
    rows = []
    for e in entries:
        verdict = feasibility(e["peak_bytes"], limit, args.fit_margin)
        rows.append({"key": e["key"], **verdict})
    plan = {
        "mode": "artifact",
        "limit_bytes": limit,
        "fit_margin": args.fit_margin,
        "entries": rows,
        "ok": all(r["fits"] is not False for r in rows) if rows else None,
    }
    _render(plan, args)
    if not rows:
        print("no committed peaks found", file=sys.stderr)
        return 2
    return 0 if plan["ok"] else 1


def _render(plan: dict, args) -> None:
    rows = plan.get("entries") or []
    width = max([len(r["key"]) for r in rows] + [4])
    limit = plan.get("limit_bytes")
    print(
        f"memory-plan ({plan['mode']}): limit "
        + (f"{limit / 2**30:.2f} GiB" if limit else "unknown")
        + (f", margin {plan['fit_margin']:.0%}"
           if plan.get("fit_margin") else "")
    )
    for r in rows:
        peak = r.get("peak_bytes")
        peak_s = f"{peak / 2**30:7.3f}G" if peak is not None else "      ?"
        if r.get("fits") is None:
            verdict = "?"
        else:
            verdict = "fits" if r["fits"] else "DOES NOT FIT"
        head = (
            f" ({r['headroom_ratio']:+.1%} headroom)"
            if r.get("headroom_ratio") is not None else ""
        )
        print(f"  {r['key']:<{width}}  {peak_s}  {verdict}{head}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(plan, f, indent=2)
            f.write("\n")


# -- compile mode -------------------------------------------------------------


def _setup_backend() -> None:
    from mpi4dl_tpu.utils import enable_compilation_cache
    import os

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        import jax

        jax.config.update("jax_num_cpu_devices", 8)
    enable_compilation_cache()


def _serve_cells(args, px: int):
    if args.model == "resnet":
        from mpi4dl_tpu.models.resnet import get_resnet_v2

        return get_resnet_v2(
            depth=args.depth, num_classes=args.classes,
            pool_kernel=max(1, px // 4),
        )
    from mpi4dl_tpu.models.amoebanet import amoebanetd

    return amoebanetd(
        num_classes=args.classes, num_layers=args.layers,
        num_filters=args.filters,
    )


def _abstract_serve_state(cells, px: int, dtype):
    """Fully abstract ``(params, batch_stats)`` structures of a cell list
    at ``px`` — ``jax.eval_shape`` end to end, zero device arrays. The
    shared substrate of the monolithic and tiled compile-only peaks."""
    import jax

    from mpi4dl_tpu.evaluate import stats_unfreeze, _finalize
    from mpi4dl_tpu.ops.layers import bn_stats_mode
    from mpi4dl_tpu.parallel.partition import init_cells

    cells = tuple(cells)
    x1 = jax.ShapeDtypeStruct((1, px, px, 3), dtype)
    params_s = jax.eval_shape(
        lambda k, x: init_cells(list(cells), k, x),
        jax.random.PRNGKey(0), x1,
    )

    def collect_one(p, x):
        with bn_stats_mode("collect"):
            out, h = [], x
            for cell, pp in zip(cells, p):
                h, upd = cell.apply(dict(pp), h, mutable=["batch_stats"])
                out.append(upd.get("batch_stats", {}))
        return [_finalize(s) for s in stats_unfreeze(out)]

    stats_s = jax.eval_shape(collect_one, params_s, x1)
    return params_s, stats_s


def predict_serve_peak(cells, px: int, bucket: int, dtype=None) -> "dict | None":
    """Compile-only peak of the frozen-stats serve forward for one
    bucket — lowered FULLY abstractly (eval_shape params + batch-stats
    structure, ShapeDtypeStruct input), so nothing executes and no
    device array is materialized. The result is bit-identical to
    ``memory_summary`` of the executable the engine's AOT warm-up
    builds for the same config (tier-1-asserted)."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.analysis.memory import memory_summary
    from mpi4dl_tpu.evaluate import _apply_running

    dtype = jnp.dtype(dtype if dtype is not None else jnp.float32)
    cells = tuple(cells)
    params_s, stats_s = _abstract_serve_state(cells, px, dtype)

    def fwd(p, s, x):
        return _apply_running(cells, p, s, x)

    xb = jax.ShapeDtypeStruct((int(bucket), px, px, 3), dtype)
    compiled = jax.jit(fwd).lower(params_s, stats_s, xb).compile()
    return memory_summary(compiled)


def predict_tiled_peak(
    cells, px: int, tile: int, tile_bucket: int = 8, dtype=None
) -> "dict | None":
    """Compile-only peaks of the TILED forward (serve/tiled.py) at one
    tile core: the section executable at its ``tile_bucket × window ×
    window`` shape plus the head at the stitched-feature shape — both
    lowered abstractly, nothing executed. ``peak_bytes`` is the max of
    the two (both must fit the chip at run time); the per-executable
    breakdown and the derived geometry ride alongside. This is how
    "what tile size fits this chip" is answered BEFORE a gigapixel
    request exists."""
    import jax.numpy as jnp

    from mpi4dl_tpu.analysis.memory import memory_summary
    from mpi4dl_tpu.evaluate import aot_compile_tiled_predict
    from mpi4dl_tpu.serve.tiled import tile_geometry

    dtype = jnp.dtype(dtype if dtype is not None else jnp.float32)
    cells = tuple(cells)
    params_s, stats_s = _abstract_serve_state(cells, px, dtype)
    g = tile_geometry(
        cells, params_s, stats_s, (px, px, 3), tile, dtype=dtype
    )
    exe = aot_compile_tiled_predict(
        cells, params_s, stats_s, g.split,
        (*g.window_hw, 3), (*g.feat_hw, g.feat_channels),
        [int(tile_bucket)], dtype=dtype, feature_dtype=g.feat_dtype,
    )
    tile_sum = memory_summary(exe["tile"][int(tile_bucket)])
    head_sum = memory_summary(exe["head"])
    if tile_sum is None or head_sum is None:
        return None
    return {
        "peak_bytes": max(tile_sum["peak_bytes"], head_sum["peak_bytes"]),
        "tile_peak_bytes": tile_sum["peak_bytes"],
        "head_peak_bytes": head_sum["peak_bytes"],
        "geometry": g.describe(),
    }


def predict_train_peak(args, px: int, batch: int) -> "dict | None":
    """Compile-only peak of the full train step (fwd+bwd+update) for
    the requested config, via the same Trainer build the hlolint CLI
    uses. Parameter init executes (tiny, size-independent); the step
    itself is lowered and compiled but NEVER run."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.analysis.cli import _build_trainer
    from mpi4dl_tpu.analysis.memory import memory_summary

    ns = argparse.Namespace(
        model=args.model, size=px, batch=batch, depth=args.depth,
        layers=args.layers, filters=args.filters,
        spatial_parts=args.spatial_parts, spatial_cells=3,
        slice_method="square", dp=args.dp, remat=args.remat,
    )
    trainer, _, _ = _build_trainer(ns)
    dtype = jnp.dtype(args.dtype)
    x_shape = (batch, px, px, 3)
    state = trainer.init(jax.random.PRNGKey(0), x_shape, dtype=dtype)
    xs, ys = trainer.shard_batch(
        jnp.zeros(x_shape, dtype), jnp.zeros((batch,), jnp.int32)
    )
    compiled = trainer._jit_step.lower(state, xs, ys).compile()
    return memory_summary(compiled)


def _predict(args, px: int, bucket: int, tile: "int | None" = None) -> "dict | None":
    if args.program == "serve":
        if tile is not None:
            return predict_tiled_peak(
                _serve_cells(args, px), px, tile,
                tile_bucket=args.tile_bucket, dtype=args.dtype,
            )
        return predict_serve_peak(
            _serve_cells(args, px), px, bucket, dtype=args.dtype
        )
    return predict_train_peak(args, px, args.batch)


def _bisect(args, limit: int) -> dict:
    """Largest feasible value on the candidate ladder (binary search —
    peak is monotone in px, bucket, and tile core). Every compiled
    candidate is reported; refusals on RESOURCE_EXHAUSTED (the CPU
    backend can itself OOM lowering a huge program) count as
    infeasible. The ``tile`` axis predicts BOTH tiled executables
    (section window + head) and requires both to fit — when even the
    smallest tile's head is too big, nothing fits and the exit is 1."""
    from mpi4dl_tpu.telemetry.memory import is_oom_error

    if args.bisect == "px":
        ladder = sorted(
            int(v) for v in str(args.px_candidates).split(",") if v.strip()
        )
    elif args.bisect == "tile":
        if args.program != "serve":
            raise SystemExit("--bisect tile needs --program serve")
        ladder = sorted(
            int(v) for v in str(args.tile_candidates).split(",")
            if v.strip()
        )
    else:
        ladder, b = [], 1
        while b <= args.max_bucket:
            ladder.append(b)
            b *= 2
    candidates = []
    lo, hi = 0, len(ladder) - 1
    best = None
    first_bad = None
    while lo <= hi:
        mid = (lo + hi) // 2
        val = ladder[mid]
        px = val if args.bisect == "px" else args.size
        bucket = val if args.bisect == "bucket" else args.bucket
        tile = val if args.bisect == "tile" else None
        try:
            summary = _predict(args, px, bucket, tile=tile)
            peak = summary["peak_bytes"] if summary else None
        except Exception as e:  # noqa: BLE001 — a compile that OOMs IS
            if not is_oom_error(e):  # the infeasibility verdict
                raise
            summary, peak = None, None
        verdict = feasibility(peak, limit, args.fit_margin)
        fits = bool(verdict["fits"]) if peak is not None else False
        entry = {args.bisect: val, **verdict, "fits": fits}
        if summary and "tile_peak_bytes" in summary:
            entry["tile_peak_bytes"] = summary["tile_peak_bytes"]
            entry["head_peak_bytes"] = summary["head_peak_bytes"]
        candidates.append(entry)
        if fits:
            best = val
            lo = mid + 1
        else:
            first_bad = val
            hi = mid - 1
    candidates.sort(key=lambda c: c[args.bisect])
    return {
        "axis": args.bisect,
        "max_feasible": best,
        "first_infeasible": first_bad,
        "candidates": candidates,
    }


def _compile_mode(args) -> int:
    _setup_backend()
    from mpi4dl_tpu.telemetry.memory import device_memory_limit

    limit = _resolve_limit(args, device_memory_limit())
    config = {
        "program": args.program, "model": args.model, "size": args.size,
        "dtype": args.dtype,
    }
    if args.program == "serve":
        config["bucket"] = args.bucket
        if args.tile is not None or args.bisect == "tile":
            config["tile_bucket"] = args.tile_bucket
        if args.tile is not None:
            config["tile"] = args.tile
    else:
        config.update(batch=args.batch, remat=args.remat, dp=args.dp,
                      spatial_parts=args.spatial_parts)

    if args.bisect:
        if not limit:
            print("--bisect needs --limit-bytes/--limit-gb (or a device "
                  "that reports one)", file=sys.stderr)
            return 2
        bisect = _bisect(args, limit)
        plan = {
            "mode": "compile", "config": config, "limit_bytes": limit,
            "fit_margin": args.fit_margin, "bisect": bisect,
            "entries": [
                {"key": f"{args.bisect}={c[args.bisect]}", **{
                    k: c[k] for k in (
                        "peak_bytes", "limit_bytes", "fits",
                        "headroom_bytes", "headroom_ratio",
                    )
                }}
                for c in bisect["candidates"]
            ],
            "ok": bisect["max_feasible"] is not None,
        }
        _render(plan, args)
        print(
            f"max feasible {args.bisect}: {bisect['max_feasible']}"
            + (f" (first infeasible: {bisect['first_infeasible']})"
               if bisect["first_infeasible"] is not None else "")
        )
        return 0 if plan["ok"] else 1

    tile = args.tile if args.program == "serve" else None
    summary = _predict(args, args.size, args.bucket, tile=tile)
    peak = summary["peak_bytes"] if summary else None
    verdict = feasibility(peak, limit, args.fit_margin)
    key = (
        f"{args.program}_{args.model}_{args.size}px"
        + (f"_tile{tile}" if tile is not None else "")
        + (f"_b{args.bucket}" if args.program == "serve" and tile is None
           else "" if args.program == "serve"
           else f"_bs{args.batch}_{args.remat}")
    )
    plan = {
        "mode": "compile", "config": config, "limit_bytes": limit,
        "fit_margin": args.fit_margin, "predicted": summary,
        "entries": [{"key": key, **verdict}],
        "ok": verdict["fits"] is not False,
    }
    _render(plan, args)
    return 0 if plan["ok"] else 1


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.program is None:
        # Artifact mode: pure JSON over committed peaks — no jax import
        # anywhere on this path (dispatched pre-backend, like
        # bench-history).
        return _artifact_mode(args)
    return _compile_mode(args)


if __name__ == "__main__":  # pragma: no cover — exercised via analyze.py
    sys.exit(main())
