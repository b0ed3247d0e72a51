"""SP 2×2 halo/compute-overlap A/B harness.

``python -m mpi4dl_tpu.analyze sp-overlap`` runs the spatially-partitioned
(2×2 square tiles) ResNet train step TWICE — once with the monolithic
spatial conv (one VALID conv over the halo-extended tile) and once with
the decomposed impl (``MPI4DL_TPU_CONV_OVERLAP=decomposed``: interior
conv with no halo dependency + boundary-strip convs,
:func:`mpi4dl_tpu.ops.layers.overlap_decompose`) — and measures, per arm:

- the **measured** ``trace_overlap_ratio`` of a live XProf capture
  (:meth:`Trainer.capture_trace_attribution`): the fraction of
  collective-permute time hidden behind concurrent compute, the number
  the decomposition exists to raise (T3 arXiv:2401.16677 / FLUX
  arXiv:2406.06858);
- the mean annotated step wall time (``step_time_s``);
- the **static** hlolint verdict with partition-math expectations
  (tile grid + counted halo shifts — the halo-window rule must hold for
  the decomposed program too, since the permute inventory is unchanged:
  ``halo_exchange`` runs exactly once per windowed op either way);
- the ``trace-overlap-crosscheck`` findings joining the two.

Run from bench.py as a subprocess (the ``sp2x2_overlap`` extra) so the
4-device CPU mesh exists regardless of what backend the bench headline
initialized, and callable in-process (:func:`run_overlap_ab`) from tests
that already sit on the 8-virtual-CPU mesh.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys


@contextlib.contextmanager
def _conv_overlap_env(impl: str):
    """Set MPI4DL_TPU_CONV_OVERLAP for the duration of one arm's tracing
    (the selector is read at trace time, per spatial windowed op)."""
    prev = os.environ.get("MPI4DL_TPU_CONV_OVERLAP")
    os.environ["MPI4DL_TPU_CONV_OVERLAP"] = impl
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("MPI4DL_TPU_CONV_OVERLAP", None)
        else:
            os.environ["MPI4DL_TPU_CONV_OVERLAP"] = prev


def _build_arm(impl, size, batch, depth, spatial_cells, warmup):
    """One arm's context: the SP 2×2 trainer built (and warmed) under
    ``impl``, plus the static lint of its compiled step against the
    partition-math expectations."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4dl_tpu.analysis import analyze_compiled
    from mpi4dl_tpu.analysis.expectations import compose, spatial_delta
    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.models.resnet import get_resnet_v1
    from mpi4dl_tpu.train import Trainer

    with _conv_overlap_env(impl):
        cfg = ParallelConfig(
            batch_size=batch, split_size=1, spatial_size=1,
            num_spatial_parts=(4,), slice_method="square",
            image_size=size, data_parallel=1,
        )
        plain = get_resnet_v1(depth=depth)
        n_sp = min(spatial_cells, len(plain) - 1)
        cells = get_resnet_v1(depth=depth, spatial_cells=n_sp)
        trainer = Trainer(
            cells, num_spatial_cells=n_sp, config=cfg, plain_cells=plain
        )
        x_shape = (batch, size, size, 3)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(x_shape), jnp.float32)
        y = jnp.asarray(rng.integers(0, 10, size=(batch,)), jnp.int32)
        xs, ys = trainer.shard_batch(x, y)
        state = trainer.init(jax.random.PRNGKey(0), x_shape)

        halo_shifts = trainer.halo_shift_count(state.params, x_shape)
        compiled = trainer._jit_step.lower(state, xs, ys).compile()
        report = analyze_compiled(
            compiled,
            expected=compose(spatial_delta(cfg.tile_shape, halo_shifts)),
            platform=jax.devices()[0].platform,
            config={"program": f"sp2x2_train_{impl}", "conv_overlap": impl},
        )
        for _ in range(warmup):
            state, metrics = trainer.train_step(state, xs, ys)
        float(metrics["loss"])  # force execution before any capture
    return {
        "impl": impl, "trainer": trainer, "state": state,
        "xs": xs, "ys": ys, "halo_shifts": halo_shifts, "report": report,
    }


def run_overlap_ab(
    size: int = 32,
    batch: int = 4,
    depth: int = 8,
    spatial_cells: int = 3,
    steps: int = 3,
    warmup: int = 1,
    trials: int = 1,
    arms=("monolithic", "decomposed"),
    registry=None,
) -> dict:
    """Both arms + the A/B verdict. ``trials`` captures per arm run
    INTERLEAVED (mono, dec, mono, dec, ...) so slow host drift hits both
    arms alike, and the arm ratio pools overlapped/total collective time
    across its captures rather than averaging per-capture ratios.
    Requires ≥4 devices (the 2×2 tile mesh); raises the underlying
    config error otherwise."""
    from mpi4dl_tpu.analysis.trace import crosscheck_overlap

    out = {
        "config": {
            "size": size, "batch": batch, "depth": depth,
            "spatial_cells": spatial_cells, "steps": steps,
            "trials": trials, "mesh": "2x2 square tiles",
        },
        "arms": {},
    }
    ctxs = {
        impl: _build_arm(impl, size, batch, depth, spatial_cells, warmup)
        for impl in arms
    }
    pooled = {
        impl: {"total_s": 0.0, "overlapped_s": 0.0, "per_trial": [],
               "walls": [], "coll": [], "n_steps": 0, "crosscheck": None}
        for impl in arms
    }
    for _ in range(max(1, int(trials))):
        for impl in arms:
            import shutil
            import tempfile

            ctx, acc = ctxs[impl], pooled[impl]
            logdir = tempfile.mkdtemp(prefix=f"mpi4dl-sp-overlap-{impl}-")
            try:
                with _conv_overlap_env(impl):
                    ctx["state"], summary = (
                        ctx["trainer"].capture_trace_attribution(
                            ctx["state"], ctx["xs"], ctx["ys"], steps=steps,
                            logdir=logdir, registry=registry,
                            program=f"sp2x2_{impl}",
                        )
                    )
            finally:
                shutil.rmtree(logdir, ignore_errors=True)
            coll = summary["collective"]
            acc["total_s"] += coll["total_s"]
            acc["overlapped_s"] += coll["overlapped_s"]
            acc["per_trial"].append(coll["overlap_ratio"])
            acc["n_steps"] += summary["n_steps"]
            mean = summary["per_step_mean"] or {}
            if mean.get("wall_s") is not None:
                acc["walls"].append(mean["wall_s"])
            if mean.get("collective_s") is not None:
                acc["coll"].append(mean["collective_s"])
            if acc["crosscheck"] is None:
                acc["crosscheck"] = [
                    f.as_dict()
                    for f in crosscheck_overlap(ctx["report"], summary)
                ]
    for impl in arms:
        ctx, acc = ctxs[impl], pooled[impl]
        report = ctx["report"]
        total = acc["total_s"]
        ratio = acc["overlapped_s"] / total if total > 0 else None
        out["arms"][impl] = {
            "conv_impl": impl,
            "trace_overlap_ratio": ratio,
            "overlap_ratio_per_trial": acc["per_trial"],
            "collective_s": (
                sum(acc["coll"]) / len(acc["coll"]) if acc["coll"] else None
            ),
            "step_time_s": (
                round(sum(acc["walls"]) / len(acc["walls"]), 6)
                if acc["walls"] else None
            ),
            "n_steps": acc["n_steps"],
            "halo_shifts": ctx["halo_shifts"],
            "permutes": report.inventory.get("collective-permute", 0),
            "hlolint_errors": [
                f for f in report.findings if f["severity"] == "error"
            ],
            "crosscheck": acc["crosscheck"] or [],
        }
    mono = out["arms"].get("monolithic")
    dec = out["arms"].get("decomposed")
    if mono and dec:
        out["halo_shifts_equal"] = mono["halo_shifts"] == dec["halo_shifts"]
        rm, rd = mono["trace_overlap_ratio"], dec["trace_overlap_ratio"]
        out["overlap_improved"] = (
            rm is not None and rd is not None and rd > rm
        )
        sm, sd = mono["step_time_s"], dec["step_time_s"]
        out["step_time_speedup"] = (
            round(sm / sd, 4) if sm and sd else None
        )
    return out


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.analyze sp-overlap",
        description="SP 2x2 halo/compute overlap A/B: monolithic vs "
                    "decomposed spatial conv, measured + linted",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--size", type=int, default=32)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--depth", type=int, default=8)
    p.add_argument("--spatial-cells", type=int, default=3)
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--trials", type=int, default=1,
                   help="captures per arm, interleaved across arms; the "
                        "arm ratio pools collective time over all of them")
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

    from mpi4dl_tpu.utils import enable_compilation_cache

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # The 2x2 tile mesh needs virtual devices before backend init —
        # the same 8-device simulation the test suite runs on.
        import jax

        jax.config.update("jax_num_cpu_devices", 8)
    enable_compilation_cache()

    out = run_overlap_ab(
        size=args.size, batch=args.batch, depth=args.depth,
        spatial_cells=args.spatial_cells, steps=args.steps,
        warmup=args.warmup, trials=args.trials,
        arms=tuple(args.arms) if args.arms else ("monolithic", "decomposed"),
    )
    for impl, arm in out["arms"].items():
        ratio = arm["trace_overlap_ratio"]
        print(
            f"# {impl}: overlap_ratio="
            f"{ratio if ratio is None else round(ratio, 4)} "
            f"step={arm['step_time_s']}s permutes={arm['permutes']} "
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
    if args.require_improvement and not out.get("overlap_improved"):
        rc = 1
    return rc


if __name__ == "__main__":  # pragma: no cover — exercised via analyze.py
    sys.exit(main())
