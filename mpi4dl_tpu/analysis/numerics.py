"""``python -m mpi4dl_tpu.analyze numerics`` — cross-predictor canary audit.

The numerics sentinel (telemetry/canary.py) verifies each live engine
against its OWN warm-up reference; this subcommand answers the question
the sentinel cannot: do the repo's three serving forwards — single-chip,
spatially sharded, halo-tiled — still agree with EACH OTHER on the same
canary input under the same weights, at the documented f32 boundaries?

Live mode builds one calibrated spatial ResNet (one set of weights),
derives the SAME deterministic canary batch the engines probe with
(:func:`mpi4dl_tpu.telemetry.canary_example`), runs it through a
:class:`SingleChipPredictor`, a :class:`ShardedPredictor` on a CPU tile
mesh, and a :class:`TiledPredictor`, and gates every pair on max-abs
divergence vs the documented tolerance (max-ulp recorded alongside as
the scale-free view). Per-pair bounds COMPOSE from each predictor's
documented distance to the plain forward — the same numbers the tier-1
equivalence suites pin (tests/test_serve_sharded.py 1e-5,
tests/test_serve_tiled.py 5e-6):

=====================  ==========================================
pair                   atol
=====================  ==========================================
single_chip | sharded  1e-5   (f32 reduction-order boundary)
single_chip | tiled    5e-6   (stitched cross-shape boundary)
sharded | tiled        1.5e-5 (triangle bound: 1e-5 + 5e-6)
=====================  ==========================================

``--artifact REPORT.json`` re-gates committed audit reports (and
summarizes ``canary.failure`` events out of JSONL telemetry logs) with
no jax at all — pure JSON, dispatched in ``analysis/cli.py`` before any
backend setup (pinned by tests/test_artifact_dispatch.py). Exit 1 iff
any pair breaches its bound, either mode.
"""

from __future__ import annotations

import argparse
import json
import sys

# Documented distance-to-plain-forward bound per predictor kind; a
# pair's gate is the triangle bound (sum). single_chip IS the plain
# forward on the serving path, so it contributes zero.
PREDICTOR_ATOL = {
    "single_chip": 0.0,
    "sharded": 1e-5,   # tests/test_serve_sharded.py reduction-order bound
    "tiled": 5e-6,     # tests/test_serve_tiled.py stitched-shape bound
}


def pair_atol(a: str, b: str) -> float:
    """Composed max-abs bound for one predictor pair (triangle over the
    documented per-predictor distances to the plain forward)."""
    try:
        return PREDICTOR_ATOL[a] + PREDICTOR_ATOL[b]
    except KeyError as e:
        raise ValueError(f"unknown predictor kind {e.args[0]!r}; expected "
                         f"one of {sorted(PREDICTOR_ATOL)}") from None


def audit_pairs(outputs: dict) -> "list[dict]":
    """All-pairs divergence table over ``{name: np.ndarray}`` canary
    outputs: max-abs (the gate) + max-ulp (the scale-free view) per
    pair, each against its composed bound. Live-mode only (numpy)."""
    import numpy as np

    from mpi4dl_tpu.telemetry.canary import ulp_diff

    names = sorted(outputs)
    pairs = []
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            atol = pair_atol(a, b)
            xa = np.asarray(outputs[a], np.float32)
            xb = np.asarray(outputs[b], np.float32)
            max_abs = float(np.max(np.abs(xa - xb))) if xa.size else 0.0
            pairs.append({
                "a": a,
                "b": b,
                "max_abs": max_abs,
                "max_ulp": int(np.max(ulp_diff(xa, xb))) if xa.size else 0,
                "atol": atol,
                "ok": bool(max_abs <= atol),
            })
    return pairs


def regate_pairs(pairs) -> "list[dict]":
    """Artifact-mode gate: re-apply each recorded pair's bound to its
    recorded max_abs — the committed report cannot vouch for itself.
    A pair with no usable numbers fails loudly instead of passing."""
    out = []
    for p in pairs or ():
        if not isinstance(p, dict):
            continue
        rec = dict(p)
        max_abs = rec.get("max_abs")
        atol = rec.get("atol")
        if not isinstance(atol, (int, float)):
            a, b = rec.get("a"), rec.get("b")
            try:
                atol = pair_atol(str(a), str(b))
            except ValueError:
                atol = None
            rec["atol"] = atol
        rec["ok"] = bool(
            isinstance(max_abs, (int, float))
            and isinstance(atol, (int, float))
            and max_abs <= atol
        )
        out.append(rec)
    return out


def load_artifacts(paths) -> dict:
    """Classify committed inputs: audit reports (``{"pairs": [...]}``)
    vs JSONL telemetry logs (collect their ``canary.failure`` events)."""
    pairs: "list[dict]" = []
    failures: "list[dict]" = []
    counts = {"reports": 0, "logs": 0}
    for path in paths:
        with open(path) as f:
            text = f.read()
        try:
            doc = json.loads(text)
        except ValueError:
            doc = None
        if isinstance(doc, dict) and isinstance(doc.get("pairs"), list):
            counts["reports"] += 1
            pairs.extend(p for p in doc["pairs"] if isinstance(p, dict))
            continue
        counts["logs"] += 1
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            if isinstance(ev, dict) and ev.get("name") == "canary.failure":
                failures.append(ev)
    return {"inputs": counts, "pairs": pairs, "failures": failures}


def run_live_audit(size, depth, spatial_cells, mesh, tile, seed) -> dict:
    """Build one calibrated spatial ResNet and push the deterministic
    canary batch through all three predictor kinds on this process's
    CPU mesh. Caller owns backend setup (set_cpu_devices before jax)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4dl_tpu.evaluate import collect_batch_stats
    from mpi4dl_tpu.models.resnet import get_resnet_v1
    from mpi4dl_tpu.parallel.partition import init_cells
    from mpi4dl_tpu.serve import SingleChipPredictor
    from mpi4dl_tpu.serve.sharded import ShardedPredictor, serving_mesh_config
    from mpi4dl_tpu.serve.tiled import TiledPredictor
    from mpi4dl_tpu.telemetry.canary import (
        canary_example,
        exact_digest,
        params_checksum,
        quantized_digest,
    )
    from mpi4dl_tpu.train import Trainer

    plain = get_resnet_v1(
        depth=depth, num_classes=10, pool_kernel=size // 4
    )
    n_sp = min(spatial_cells, len(plain) - 1)
    cells = get_resnet_v1(
        depth=depth, num_classes=10, pool_kernel=size // 4,
        spatial_cells=n_sp,
    )
    rng = np.random.default_rng(seed)
    params = init_cells(
        plain, jax.random.PRNGKey(seed), jnp.zeros((1, size, size, 3))
    )
    cal = [jnp.asarray(rng.standard_normal((4, size, size, 3)), jnp.float32)]
    stats = collect_batch_stats(plain, params, cal)

    shape = (size, size, 3)
    x = canary_example(shape, np.float32, seed=seed)

    cfg = serving_mesh_config(mesh, size)
    trainer = Trainer(
        cells, num_spatial_cells=n_sp, config=cfg, plain_cells=plain
    )
    predictors = {
        "single_chip": SingleChipPredictor(
            plain, params, stats, shape, jnp.float32
        ),
        "sharded": ShardedPredictor(trainer, params, stats, shape),
        "tiled": TiledPredictor(plain, params, stats, shape, tile or size),
    }

    outputs, per = {}, {}
    for name, pred in predictors.items():
        handle = pred.compile_bucket(1)
        row = np.asarray(pred.run(handle, x[None]))[0]
        outputs[name] = row
        per[name] = {
            "digest": exact_digest(row),
            "qdigest": quantized_digest(row),
            "device": str(pred.limit_device()),
            "program": pred.program,
            "params_checksum": params_checksum(pred.param_tree()),
        }

    pairs = audit_pairs(outputs)
    # One shared weight set is the audit's premise: every predictor's
    # live param-tree checksum must agree before divergence means
    # anything (tiled re-splits the tree; the checksum walks it in the
    # rejoined cell order, so agreement is required, not incidental).
    checksums = {per[n]["params_checksum"] for n in per}
    return {
        "canary": {
            "seed": seed,
            "shape": list(shape),
            "dtype": "float32",
            "digest": exact_digest(x),
        },
        "config": {
            "depth": depth, "spatial_cells": n_sp,
            "mesh": list(mesh), "tile": tile or size,
        },
        "predictors": per,
        "checksums_agree": len(checksums) == 1,
        "pairs": pairs,
        "ok": len(checksums) == 1 and all(p["ok"] for p in pairs),
    }


def _render(pairs, failures=None) -> "list[str]":
    lines = []
    for p in pairs:
        verdict = "ok" if p.get("ok") else "BREACH"
        atol = p.get("atol")
        lines.append(
            f"  {p.get('a')} | {p.get('b')}: max_abs "
            f"{p.get('max_abs'):.3g} vs atol "
            f"{format(atol, 'g') if atol is not None else '?'}"
            f" (max_ulp {p.get('max_ulp', '?')}) {verdict}"
        )
    by_check: "dict[str, int]" = {}
    for ev in failures or ():
        check = str((ev.get("attrs") or {}).get("check", "unknown"))
        by_check[check] = by_check.get(check, 0) + 1
    if by_check:
        kinds = ", ".join(f"{k}={v}" for k, v in sorted(by_check.items()))
        lines.append(f"# canary.failure events: "
                     f"{sum(by_check.values())} ({kinds})")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.analyze numerics",
        description=(
            "Cross-predictor canary equivalence audit: single-chip vs "
            "sharded vs tiled on one weight set, gated at the "
            "documented f32 tolerances."
        ),
    )
    ap.add_argument(
        "--artifact", action="append", default=None, metavar="PATH",
        help="pure-JSON mode: re-gate committed audit report(s) and "
             "summarize canary.failure events from JSONL logs "
             "(repeatable; no jax, no devices)",
    )
    ap.add_argument("--size", type=int, default=16, help="square image px")
    ap.add_argument("--depth", type=int, default=8, help="ResNet-v1 depth")
    ap.add_argument("--spatial-cells", type=int, default=2,
                    help="leading cells sharded spatially")
    ap.add_argument("--mesh", default="2x2",
                    help="sharded tile mesh HxW (CPU-simulated)")
    ap.add_argument("--tile", type=int, default=0,
                    help="tiled-predictor core tile px (0 = image size: "
                         "the degenerate single-window grid)")
    ap.add_argument("--seed", type=int, default=0,
                    help="canary derivation seed (matches the engines')")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the full audit report JSON here")
    args = ap.parse_args(argv)

    if args.artifact:
        joined = load_artifacts(args.artifact)
        pairs = regate_pairs(joined["pairs"])
        ok = bool(pairs) and all(p["ok"] for p in pairs)
        n_bad = sum(1 for p in pairs if not p["ok"])
        print(
            f"# numerics[artifact]: {len(pairs)} pair(s) from "
            f"{joined['inputs']['reports']} report(s), {n_bad} breach(es), "
            f"{len(joined['failures'])} canary.failure event(s)"
        )
        for line in _render(pairs, joined["failures"]):
            print(line)
        if args.json_out:
            doc = dict(joined, pairs=pairs, ok=ok)
            with open(args.json_out, "w") as f:
                json.dump(doc, f, indent=2)
                f.write("\n")
        if not pairs:
            print("# no audit pairs found in the artifacts",
                  file=sys.stderr)
            return 1
        return 0 if ok else 1

    from mpi4dl_tpu.serve.sharded import parse_mesh
    from mpi4dl_tpu.utils import enable_compilation_cache

    mesh = parse_mesh(args.mesh)
    import os

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        import jax

        jax.config.update("jax_num_cpu_devices", max(8, mesh[0] * mesh[1]))
    enable_compilation_cache()

    report = run_live_audit(
        args.size, args.depth, args.spatial_cells, mesh,
        args.tile, args.seed,
    )
    print(
        f"# numerics: canary {report['canary']['digest']} through "
        f"{len(report['predictors'])} predictors, "
        f"{'agree' if report['ok'] else 'DIVERGED'}"
    )
    for line in _render(report["pairs"]):
        print(line)
    if not report["checksums_agree"]:
        print("# param checksums disagree across predictors",
              file=sys.stderr)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
    return 0 if report["ok"] else 1


if __name__ == "__main__":  # pragma: no cover — exercised via analyze
    sys.exit(main())
