"""Pipeline schedule A/B harness: measured bubble + img/s per schedule.

``python -m mpi4dl_tpu.analyze pipeline`` runs the LP pipeline train step
once per schedule arm — ``gpipe`` (fill-drain) and ``1f1b`` (interleaved
virtual stages) — and measures, per arm:

- the **measured** ``pipeline_bubble_fraction`` of a live XProf capture
  (:meth:`PipelineTrainer.capture_trace_attribution`): idle stage-switch
  slots over all slots, joined from the compiled program's branch
  closures to the real trace — the fill/drain fraction the ROADMAP's
  analytic ``(S-1)/(S-1+M)`` predicted but nothing measured;
- per-stage device seconds and the capture's images/sec;
- the **static** hlolint verdict with the permute window pinned at the
  EXACT stage-boundary budget (``Expectations.extra_permutes =
  PipelineTrainer.stage_permute_count()``);
- the ``pipeline-bubble-crosscheck`` joining analytic and measured.

The A/B verdict asserts what the 1F1B schedule exists for: its measured
bubble strictly below the GPipe arm's at equal (stages, micro-batches).
Run from bench.py as a subprocess (the ``pipeline`` extra) so the pipe
mesh exists regardless of the bench headline's backend, and callable
in-process (:func:`run_pipeline_ab`) from tests on the 8-virtual-CPU
mesh.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile


def _build_arm(schedule, size, batch, depth, stages, parts, virtual_stages,
               warmup):
    """One arm's context: the LP PipelineTrainer built (and warmed) under
    ``schedule``, plus the static lint of its compiled step with the
    permute window pinned at the exact stage-boundary budget."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4dl_tpu.analysis import analyze_compiled
    from mpi4dl_tpu.analysis.expectations import compose
    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.models.resnet import get_resnet_v1
    from mpi4dl_tpu.parallel.pipeline import PipelineTrainer

    cfg = ParallelConfig(
        batch_size=batch, parts=parts, split_size=stages, spatial_size=0,
        image_size=size,
    )
    cells = get_resnet_v1(depth=depth)
    trainer = PipelineTrainer(
        cells, cfg, schedule=schedule, virtual_stages=virtual_stages
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((batch, size, size, 3)), jnp.float32
    )
    y = jnp.asarray(rng.integers(0, 10, size=(batch,)), jnp.int32)
    xs, ys = trainer.shard_batch(x, y)
    state = trainer.init(jax.random.PRNGKey(0))

    compiled = trainer._jit_step.lower(state, xs, ys).compile()
    hlo_text = compiled.as_text()
    report = analyze_compiled(
        compiled,
        # Pure-LP program: the trainer's composed deltas carry zero halo
        # shifts, so the permute window collapses to exactly the
        # stage-boundary budget — the compiled inventory must sit AT
        # stage_permute_count() or the lint errors.
        expected=compose(trainer.collective_deltas(
            state, (batch, size, size, 3)
        )),
        platform=jax.devices()[0].platform,
        config={
            "program": f"pipeline_{schedule}", "schedule": schedule,
            "stages": stages, "parts": parts,
            "virtual_stages": trainer.v,
        },
    )
    loss = None
    for _ in range(max(1, warmup)):
        state, metrics = trainer.train_step(state, xs, ys)
        loss = float(metrics["loss"])  # force execution before any capture
    return {
        "schedule": schedule, "trainer": trainer, "state": state,
        "xs": xs, "ys": ys, "report": report, "warm_loss": loss,
        "hlo_text": hlo_text,
    }


def run_pipeline_ab(
    size: int = 32,
    batch: int = 8,
    depth: int = 8,
    stages: int = 2,
    parts: int = 4,
    virtual_stages: int = 2,
    steps: int = 3,
    warmup: int = 1,
    trials: int = 1,
    arms=("gpipe", "1f1b"),
    registry=None,
) -> dict:
    """Both schedule arms + the A/B verdict. ``trials`` captures per arm
    run INTERLEAVED (gpipe, 1f1b, gpipe, ...) so host drift hits both
    arms alike; the arm bubble pools idle/total slots across its captures
    and img/s is the mean of per-capture throughputs. The warm-up loss of
    each arm is recorded — both arms share one init, so the same value on
    both is the cheap in-band echo of the tier-1 loss-equality golden."""
    from mpi4dl_tpu.analysis.trace import crosscheck_bubble

    out = {
        "config": {
            "size": size, "batch": batch, "depth": depth,
            "stages": stages, "parts": parts,
            "virtual_stages": virtual_stages, "steps": steps,
            "trials": trials,
        },
        "arms": {},
    }
    ctxs = {
        arm: _build_arm(
            arm, size, batch, depth, stages, parts, virtual_stages, warmup
        )
        for arm in arms
    }
    pooled = {
        arm: {"idle": 0, "active": 0, "img": [], "stage_s": None,
              "analytic": None, "crosscheck": None}
        for arm in arms
    }
    for _ in range(max(1, int(trials))):
        for arm in arms:
            ctx, acc = ctxs[arm], pooled[arm]
            logdir = tempfile.mkdtemp(prefix=f"mpi4dl-pipeline-{arm}-")
            try:
                ctx["state"], summary = (
                    ctx["trainer"].capture_trace_attribution(
                        ctx["state"], ctx["xs"], ctx["ys"], steps=steps,
                        logdir=logdir, registry=registry,
                        program=f"pipeline_{arm}",
                        hlo_text=ctx["hlo_text"],
                    )
                )
            finally:
                shutil.rmtree(logdir, ignore_errors=True)
            pipe = summary["pipeline"]
            acc["idle"] += pipe["idle_slots"]
            acc["active"] += sum(pipe["active_slots_by_stage"])
            acc["img"].append(pipe["img_per_s"])
            acc["stage_s"] = pipe["stage_device_seconds"]
            acc["analytic"] = pipe["analytic_bubble_fraction"]
            if acc["crosscheck"] is None:
                acc["crosscheck"] = [
                    f.as_dict()
                    for f in crosscheck_bubble(acc["analytic"], pipe)
                ]
    for arm in arms:
        ctx, acc = ctxs[arm], pooled[arm]
        report = ctx["report"]
        total = acc["idle"] + acc["active"]
        out["arms"][arm] = {
            "schedule": arm,
            "bubble_fraction": acc["idle"] / total if total else None,
            "analytic_bubble_fraction": acc["analytic"],
            "img_per_s": (
                round(sum(acc["img"]) / len(acc["img"]), 3)
                if acc["img"] else None
            ),
            "stage_device_seconds": [
                round(s, 4) for s in (acc["stage_s"] or [])
            ],
            "warm_loss": ctx["warm_loss"],
            "permutes": report.inventory.get("collective-permute", 0),
            "permute_budget": ctx["trainer"].stage_permute_count(),
            "hlolint_errors": [
                f for f in report.findings if f["severity"] == "error"
            ],
            "crosscheck": acc["crosscheck"] or [],
        }
    gp = out["arms"].get("gpipe")
    fb = out["arms"].get("1f1b")
    if gp and fb:
        bg, bf = gp["bubble_fraction"], fb["bubble_fraction"]
        out["bubble_improved"] = (
            bg is not None and bf is not None and bf < bg
        )
        out["loss_equal"] = (
            gp["warm_loss"] is not None
            and fb["warm_loss"] is not None
            and abs(gp["warm_loss"] - fb["warm_loss"])
            <= 1e-5 * max(1.0, abs(gp["warm_loss"]))
        )
        ig, if_ = gp["img_per_s"], fb["img_per_s"]
        out["img_per_s_ratio"] = (
            round(if_ / ig, 4) if ig and if_ else None
        )
    return out


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.analyze pipeline",
        description="Pipeline schedule A/B: gpipe vs interleaved 1f1b, "
                    "measured bubble fraction + img/s, permute-budget "
                    "linted",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--size", type=int, default=32)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--depth", type=int, default=8)
    p.add_argument("--stages", type=int, default=2)
    p.add_argument("--parts", type=int, default=4)
    p.add_argument("--virtual-stages", type=int, default=2)
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--trials", type=int, default=1,
                   help="captures per arm, interleaved across arms; the "
                        "arm bubble pools idle/total slots over all of "
                        "them")
    p.add_argument("--schedule", action="append", dest="arms", default=None,
                   choices=("gpipe", "1f1b"),
                   help="restrict to one schedule arm (repeatable); "
                        "default both")
    p.add_argument("--json", dest="json_out", default=None,
                   help="write the A/B record here ('-' = stdout)")
    p.add_argument("--require-improvement", action="store_true",
                   help="exit 1 unless the 1f1b arm's measured bubble is "
                        "strictly below the gpipe arm's")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    from mpi4dl_tpu.utils import enable_compilation_cache

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # The pipe mesh needs virtual devices before backend init — the
        # same 8-device simulation the test suite runs on.
        import jax

        jax.config.update("jax_num_cpu_devices", max(8, args.stages))
    enable_compilation_cache()

    out = run_pipeline_ab(
        size=args.size, batch=args.batch, depth=args.depth,
        stages=args.stages, parts=args.parts,
        virtual_stages=args.virtual_stages, steps=args.steps,
        warmup=args.warmup, trials=args.trials,
        arms=tuple(args.arms) if args.arms else ("gpipe", "1f1b"),
    )
    for arm, rec in out["arms"].items():
        bub = rec["bubble_fraction"]
        print(
            f"# {arm}: bubble="
            f"{bub if bub is None else round(bub, 4)} "
            f"analytic={round(rec['analytic_bubble_fraction'], 4)} "
            f"img/s={rec['img_per_s']} permutes={rec['permutes']}"
            f"/{rec['permute_budget']} "
            f"lint_errors={len(rec['hlolint_errors'])} "
            f"crosscheck={len(rec['crosscheck'])}",
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
    if any(a["crosscheck"] for a in out["arms"].values()):
        rc = 1
    if args.require_improvement and not out.get("bubble_improved"):
        rc = 1
    return rc


if __name__ == "__main__":  # pragma: no cover — exercised via analyze.py
    sys.exit(main())
