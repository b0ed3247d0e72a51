"""Static communication cost model: bytes-moved inventory → predicted seconds.

The T3 observation (arXiv:2401.16677) behind hlolint's overlap rule also
prices the window: once the analyzer knows each collective's payload bytes
(:mod:`mpi4dl_tpu.analysis.hlo` shape math) and whether compute is scheduled
inside its start→done window (:mod:`mpi4dl_tpu.analysis.inventory`), a
per-link interconnect table turns the inventory into *predicted comms
seconds* and a *predicted achievable overlap ratio* — a committed number
the ICI measurement campaign can falsify, instead of CPU-measured vibes.
FLUX-style fused boundaries (arXiv:2406.06858) are the modeled best case:
every async window fully hidden, so the achievable ratio is a CEILING, not
an estimate of what the scheduler will actually do.

Three predictions per program, published as cataloged
``hlolint_predicted_*`` gauges and embedded in bench result lines:

- ``comms_s``: Σ per-collective time under ring/neighbor cost formulas
  (permute: ``lat + bytes/bw``; all-gather / reduce-scatter:
  ``(n-1)·lat + (n-1)/n · bytes/bw``; all-reduce doubles both terms —
  reduce-scatter + all-gather phases of a ring).
- ``overlap_ratio``: the achievable ceiling — the fraction of predicted
  collective seconds whose start→done window has compute scheduled inside
  it. Sync collectives (no ``-start``/``-done`` pair — every CPU-mesh
  collective) can hide nothing, so a CPU program predicts 0.0 and the
  model makes NO overlap claim there (mirrors the trace lens's "CPU emits
  sync collectives" no-claim rule).
- ``bubble_fraction``: passthrough of the schedule model
  (``PipelineTrainer.analytic_bubble_fraction``) when the program is a
  pipeline; None otherwise.

``crosscheck_cost_model`` compares the predictions against the LIVE
gauges (``trace_overlap_ratio``, ``pipeline_bubble_fraction``) and emits
``cost-model-crosscheck`` findings on disagreement beyond tolerance —
measured overlap ABOVE the achievable ceiling is an error (the model's
interconnect table or dependency math is wrong); measured below is info
(exposed latency the scheduler left on the table — T3's target case).

Honest calibration caveat (docs/ANALYSIS.md "Reading the cost model"):
the ``cpu`` table prices the 8-virtual-device shared-memory mesh, where
"links" are memcpy through a shared heap — its absolute seconds are only
order-of-magnitude. The ``ici`` table carries the campaign's priors
(per-link bandwidth/latency of a TPU v4-ish torus) and is exactly the
artifact real hardware falsifies (``docs/artifacts/costmodel_ici_r01.json``).
"""

from __future__ import annotations

import dataclasses
import json

from mpi4dl_tpu.analysis.rules import Finding

__all__ = [
    "INTERCONNECTS",
    "Interconnect",
    "collective_seconds",
    "crosscheck_cost_model",
    "predict_from_report",
    "predict_program",
    "publish_prediction",
]

#: |measured - predicted| slack before the crosscheck files a finding.
#: Generous on purpose: the model prices steady-state bandwidth, the
#: 2-step live capture measures warmup-adjacent steps.
DEFAULT_TOLERANCE = 0.15


@dataclasses.dataclass(frozen=True)
class Interconnect:
    """One link class of the parameterized interconnect table."""

    name: str
    # Per-link unidirectional bandwidth, bytes/second.
    bandwidth_bytes_per_s: float
    # Per-hop launch/teardown latency, seconds.
    latency_s: float
    doc: str = ""


INTERCONNECTS: "dict[str, Interconnect]" = {
    # TPU v4-ish ICI prior: ~100 GB/s per link per direction, ~1 us hop
    # latency. Campaign priors, not measurements — the committed
    # prediction artifact exists to be falsified on real hardware.
    "ici": Interconnect("ici", 100e9, 1e-6,
                        "TPU torus inter-chip links (campaign prior)"),
    # The 8-virtual-device CPU mesh: a "link" is a memcpy through the
    # shared heap. ~10 GB/s effective, ~5 us sync overhead per hop.
    # Order-of-magnitude only — see the calibration caveat above.
    "cpu": Interconnect("cpu", 10e9, 5e-6,
                        "shared-memory virtual-device mesh (approximate)"),
}


def collective_seconds(
    opcode: str, bytes_moved: int, ic: Interconnect, n_devices: int
) -> float:
    """Ring/neighbor cost of one collective on ``n_devices`` participants.

    ``bytes_moved`` is the payload the inventory derived from the output
    shape — the data a participant materializes, matching the standard
    ring formulations below.
    """
    n = max(int(n_devices), 2)
    bw, lat = ic.bandwidth_bytes_per_s, ic.latency_s
    if opcode == "collective-permute":
        # One neighbor hop, full payload.
        return lat + bytes_moved / bw
    if opcode in ("all-gather", "reduce-scatter", "all-to-all",
                  "ragged-all-to-all", "collective-broadcast"):
        # Ring: n-1 steps, each moving 1/n of the payload.
        return (n - 1) * lat + ((n - 1) / n) * bytes_moved / bw
    if opcode == "all-reduce":
        # Ring reduce-scatter + all-gather: both terms doubled.
        return 2 * (n - 1) * lat + (2 * (n - 1) / n) * bytes_moved / bw
    # Unknown collective class: price it as one full-payload hop rather
    # than silently dropping it from the total.
    return lat + bytes_moved / bw


def predict_program(
    collectives: "list[dict]",
    interconnect: "str | Interconnect" = "cpu",
    n_devices: int = 8,
    analytic_bubble: "float | None" = None,
) -> dict:
    """Price a program's collective records (``Report.collectives`` /
    ``collective_records`` as dicts: ``opcode``, ``bytes_moved``,
    ``is_async``, ``compute_between``).

    Returns the prediction dict bench lines embed and
    :func:`publish_prediction` publishes. ``overlap_claim`` is False when
    the program has no async collectives — the model then predicts 0.0
    achievable overlap but does NOT claim it (sync collectives say
    nothing about what an async lowering could hide).
    """
    ic = (interconnect if isinstance(interconnect, Interconnect)
          else INTERCONNECTS[interconnect])
    comms_s = 0.0
    hideable_s = 0.0
    n_async = 0
    per_op: "dict[str, dict]" = {}
    for r in collectives:
        op = r["opcode"]
        t = collective_seconds(op, int(r["bytes_moved"]), ic, n_devices)
        comms_s += t
        is_async = bool(r.get("is_async"))
        n_async += is_async
        # Achievable = the window exists (async) AND the schedule already
        # places compute inside it. A FLUX-style fused boundary could
        # hide more; this prices the program as compiled.
        if is_async and (r.get("compute_between") or 0) > 0:
            hideable_s += t
        slot = per_op.setdefault(
            op, {"count": 0, "bytes": 0, "seconds": 0.0}
        )
        slot["count"] += 1
        slot["bytes"] += int(r["bytes_moved"])
        slot["seconds"] += t
    for slot in per_op.values():
        slot["seconds"] = round(slot["seconds"], 9)
    overlap_claim = n_async > 0
    return {
        "interconnect": ic.name,
        "n_devices": int(n_devices),
        "n_collectives": len(collectives),
        "n_async": n_async,
        "comms_s": round(comms_s, 9),
        "hideable_s": round(hideable_s, 9),
        "exposed_s": round(comms_s - hideable_s, 9),
        "overlap_ratio": round(hideable_s / comms_s, 6) if comms_s else 0.0,
        "overlap_claim": overlap_claim,
        "bubble_fraction": (
            None if analytic_bubble is None else float(analytic_bubble)
        ),
        "per_op": per_op,
    }


def predict_from_report(
    report,
    interconnect: "str | Interconnect" = "cpu",
    n_devices: "int | None" = None,
    analytic_bubble: "float | None" = None,
) -> dict:
    """Price a :class:`~mpi4dl_tpu.analysis.report.Report` (or its
    ``as_dict()`` / loaded JSON form). ``n_devices`` defaults to the
    report config's ``n_devices`` when present, else 8 (the CPU mesh)."""
    d = report if isinstance(report, dict) else report.as_dict()
    cfg = d.get("config") or {}
    if n_devices is None:
        n_devices = int(cfg.get("n_devices") or 8)
    pred = predict_program(
        d.get("collectives") or [],
        interconnect=interconnect,
        n_devices=n_devices,
        analytic_bubble=analytic_bubble,
    )
    pred["program"] = str(
        cfg.get("program") or cfg.get("key") or d.get("module_name")
        or "unknown"
    )
    return pred


def publish_prediction(pred: dict, registry, program: "str | None" = None):
    """Publish one prediction as the cataloged ``hlolint_predicted_*``
    gauges, labeled by program and interconnect."""
    from mpi4dl_tpu import telemetry

    prog = str(program or pred.get("program") or "unknown")
    labels = {"program": prog, "interconnect": pred["interconnect"]}
    telemetry.declare(registry, "hlolint_predicted_comms_seconds").set(
        pred["comms_s"], **labels
    )
    telemetry.declare(registry, "hlolint_predicted_overlap_ratio").set(
        pred["overlap_ratio"], **labels
    )
    if pred.get("bubble_fraction") is not None:
        telemetry.declare(
            registry, "hlolint_predicted_bubble_fraction"
        ).set(pred["bubble_fraction"], **labels)
    return registry


def crosscheck_cost_model(
    pred: dict,
    measured_overlap: "float | None" = None,
    measured_bubble: "float | None" = None,
    tolerance: float = DEFAULT_TOLERANCE,
) -> "list[Finding]":
    """``cost-model-crosscheck``: predictions vs the live trace gauges.

    - No async collectives → no overlap claim → clean (the CPU-mesh
      no-claim rule, mirroring ``trace-overlap-crosscheck``).
    - measured overlap > achievable ceiling + tolerance → **error**: the
      runtime hid more communication than the dependency model says is
      hideable, so the model (interconnect table or start→done math) is
      wrong — fix the model, it is about to mis-advise the campaign.
    - measured overlap < ceiling - tolerance → info: achievable overlap
      the scheduler left exposed (the T3 target case).
    - |measured bubble - analytic bubble| > tolerance → **error**: the
      schedule model disagrees with the measured fill-drain — stage
      imbalance or a schedule bug, the same signal as
      ``pipeline-bubble-crosscheck`` but against the *predicted* gauge.
    """
    rule = "cost-model-crosscheck"
    out: "list[Finding]" = []
    if measured_overlap is not None and pred.get("overlap_claim"):
        ceiling = float(pred["overlap_ratio"])
        if measured_overlap > ceiling + tolerance:
            out.append(Finding(
                rule, "error",
                f"measured trace_overlap_ratio {measured_overlap:.2f} "
                f"exceeds the model's achievable ceiling {ceiling:.2f} "
                f"(+{tolerance:.2f} tolerance): the cost model's "
                "interconnect table or start->done dependency math is "
                "wrong for this program.",
            ))
        elif measured_overlap < ceiling - tolerance:
            out.append(Finding(
                rule, "info",
                f"measured trace_overlap_ratio {measured_overlap:.2f} is "
                f"below the achievable ceiling {ceiling:.2f}: the compiled "
                "schedule leaves hideable communication exposed "
                "(T3/FLUX opportunity, not a model error).",
            ))
    bubble = pred.get("bubble_fraction")
    if bubble is not None and measured_bubble is not None:
        if abs(measured_bubble - bubble) > tolerance:
            out.append(Finding(
                rule, "error",
                f"measured pipeline_bubble_fraction {measured_bubble:.3f} "
                f"disagrees with the schedule-model prediction "
                f"{bubble:.3f} by more than {tolerance:.2f}: stage "
                "imbalance or a schedule bug (same signal as "
                "pipeline-bubble-crosscheck, against the predicted gauge).",
            ))
    return out


# -- pure-JSON artifact mode (dispatched before any jax import) --------------

def artifact_main(argv: "list[str] | None" = None) -> int:
    """``analyze costmodel --artifact REPORT.json ...`` — price committed
    lint-report JSONs without jax, devices, or compilation (runs on logs
    from a dead machine, like bench-history)."""
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.analyze costmodel --artifact",
        description="Static comms cost predictions from committed lint "
                    "report JSONs (pure JSON - no jax).",
    )
    p.add_argument("reports", nargs="+", help="lint report JSON files")
    p.add_argument("--interconnect", choices=sorted(INTERCONNECTS),
                   default="ici")
    p.add_argument("--n-devices", type=int, default=None)
    p.add_argument("--json", dest="json_out", default=None,
                   help="write the predictions JSON here")
    args = p.parse_args(argv)

    preds = []
    for path in args.reports:
        with open(path) as f:
            d = json.load(f)
        pred = predict_from_report(
            d, interconnect=args.interconnect, n_devices=args.n_devices
        )
        pred["source"] = path
        preds.append(pred)
        print(
            f"# costmodel[{pred['program']}] {pred['interconnect']}: "
            f"comms {pred['comms_s'] * 1e3:.3f} ms, achievable overlap "
            f"{pred['overlap_ratio']:.2f}"
            + ("" if pred["overlap_claim"] else " (no claim: sync-only)")
        )
    payload = {"interconnect": args.interconnect, "predictions": preds}
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
    else:
        print(json.dumps(payload, indent=2, sort_keys=True))
    return 0


# -- live mode (compiles on this machine's mesh, crosschecks the trace) ------

def main(argv: "list[str] | None" = None) -> int:
    """``analyze costmodel`` — compile a program, price its collectives,
    capture a short live trace, and crosscheck predicted vs measured.

    ``--artifact`` routes to :func:`artifact_main` (pure JSON, no jax) —
    the flag is checked BEFORE any backend import so committed reports
    can be priced on a machine without devices.
    """
    argv = list(argv or [])
    if "--artifact" in argv:
        argv.remove("--artifact")
        return artifact_main(argv)

    import argparse

    p = argparse.ArgumentParser(
        prog="python -m mpi4dl_tpu.analyze costmodel",
        description="Static comms cost model: predicted seconds/overlap/"
                    "bubble for a compiled program, crosschecked against "
                    "a live trace capture.",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--interconnect", choices=sorted(INTERCONNECTS),
                   default="cpu")
    p.add_argument("--size", type=int, default=32)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--depth", type=int, default=8)
    p.add_argument("--spatial-parts", type=int, default=4)
    p.add_argument("--spatial-cells", type=int, default=3)
    p.add_argument("--schedule", choices=("none", "gpipe", "1f1b"),
                   default="none",
                   help="none = SP/DP train step; else a pipeline program "
                        "with the analytic bubble prediction")
    p.add_argument("--stages", type=int, default=2)
    p.add_argument("--parts", type=int, default=4)
    p.add_argument("--virtual-stages", type=int, default=2)
    p.add_argument("--steps", type=int, default=2,
                   help="live capture steps for the crosscheck (0 = "
                        "predictions only, no trace)")
    p.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE)
    p.add_argument("--json", dest="json_out", default=None)
    p.add_argument("--fail-on", default="error",
                   choices=("error", "warn", "never"))
    args = p.parse_args(argv)

    import os

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        import jax

        jax.config.update("jax_num_cpu_devices", 8)

    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi4dl_tpu import telemetry
    from mpi4dl_tpu.analysis.expectations import compose
    from mpi4dl_tpu.analysis.report import analyze_compiled
    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.models.resnet import get_resnet_v1

    rng = np.random.default_rng(0)
    x_shape = (args.batch, args.size, args.size, 3)
    x = jnp.asarray(rng.standard_normal(x_shape), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=(args.batch,)), jnp.int32)

    analytic_bubble = None
    if args.schedule != "none":
        from mpi4dl_tpu.parallel.pipeline import PipelineTrainer

        cfg = ParallelConfig(
            batch_size=args.batch, parts=args.parts,
            split_size=args.stages, spatial_size=0, image_size=args.size,
        )
        trainer = PipelineTrainer(
            get_resnet_v1(depth=args.depth), cfg, schedule=args.schedule,
            virtual_stages=args.virtual_stages,
        )
        state = trainer.init(jax.random.PRNGKey(0))
        program = f"pipeline_{args.schedule}"
        analytic_bubble = trainer.analytic_bubble_fraction()
    else:
        from mpi4dl_tpu.train import Trainer

        cfg = ParallelConfig(
            batch_size=args.batch, split_size=1, spatial_size=1,
            num_spatial_parts=(args.spatial_parts,),
            slice_method="square", image_size=args.size, data_parallel=1,
        )
        plain = get_resnet_v1(depth=args.depth)
        n_sp = min(args.spatial_cells, len(plain) - 1)
        cells = get_resnet_v1(depth=args.depth, spatial_cells=n_sp)
        trainer = Trainer(
            cells, num_spatial_cells=n_sp, config=cfg, plain_cells=plain
        )
        state = trainer.init(jax.random.PRNGKey(0), x_shape)
        program = "sp2x2_train"
    xs, ys = trainer.shard_batch(x, y)
    compiled = trainer._jit_step.lower(state, xs, ys).compile()
    deltas_args = (
        (state, x_shape) if args.schedule != "none"
        else (state.params, x_shape)
    )
    report = analyze_compiled(
        compiled,
        expected=compose(trainer.collective_deltas(*deltas_args)),
        platform=jax.devices()[0].platform,
        config={"program": program, "n_devices": cfg.num_devices},
    )
    pred = predict_from_report(
        report, interconnect=args.interconnect,
        n_devices=cfg.num_devices, analytic_bubble=analytic_bubble,
    )

    reg = telemetry.default_registry()
    publish_prediction(pred, reg, program=program)

    measured_overlap = measured_bubble = None
    if args.steps > 0:
        logdir = tempfile.mkdtemp(prefix="mpi4dl-costmodel-")
        try:
            state, summary = trainer.capture_trace_attribution(
                state, xs, ys, steps=args.steps, logdir=logdir,
                registry=reg, program=program,
            )
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        measured_overlap = summary["collective"]["overlap_ratio"]
        measured_bubble = (summary.get("pipeline") or {}).get(
            "bubble_fraction"
        )
    findings = crosscheck_cost_model(
        pred, measured_overlap=measured_overlap,
        measured_bubble=measured_bubble, tolerance=args.tolerance,
    )

    payload = {
        "program": program,
        "prediction": pred,
        "measured": {
            "trace_overlap_ratio": measured_overlap,
            "pipeline_bubble_fraction": measured_bubble,
        },
        "tolerance": args.tolerance,
        "crosscheck": [f.as_dict() for f in findings],
        "lint_errors": [
            f for f in report.findings if f["severity"] == "error"
        ],
    }
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
    claim = "" if pred["overlap_claim"] else " (no overlap claim: sync-only)"
    print(
        f"# costmodel[{program}] {pred['interconnect']}: comms "
        f"{pred['comms_s'] * 1e3:.3f} ms, achievable overlap "
        f"{pred['overlap_ratio']:.2f}{claim}"
        + (f", predicted bubble {pred['bubble_fraction']:.3f}"
           if pred["bubble_fraction"] is not None else "")
    )
    if measured_overlap is not None:
        print(f"# measured trace_overlap_ratio {measured_overlap:.2f}")
    if measured_bubble is not None:
        print(f"# measured pipeline_bubble_fraction {measured_bubble:.3f}")
    for f in findings:
        print(f"  {f.severity.upper()} {f.rule}: {f.message}")
    if not findings:
        print("# cost-model-crosscheck clean")

    sev = {"info": 0, "warn": 1, "error": 2}
    worst = max((sev[f.severity] for f in findings), default=-1)
    lint_worst = 2 if payload["lint_errors"] else -1
    worst = max(worst, lint_worst)
    if args.fail_on == "never" or worst < 0:
        return 0
    return 1 if worst >= sev[args.fail_on] else 0
