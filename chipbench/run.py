#!/usr/bin/env python3
"""One run of one benchmark cell on the machine it is started on.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's program the way its entry point does, makes weights and
inputs from the seed, warms up through the window's own loop, measures
whole steps for ``--seconds``, then frees the program's state and checks
the first steps against the plain float32 reference. Every earlier line of
output is one JSON object (what each holds: ``chipbench/README.md``); the
last line is the result. Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result: there is no CPU path.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# The step's executable is 254 MB. Where the machine caps the persistent
# compile cache below that (the chip machine comes with 192 MiB) it is never
# stored and every run compiles for four minutes; the benchmark's runs lift
# the cap, before jax reads it.
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def percentile(values, pct):
    """Linear interpolation on the sorted sample (numpy's default)."""
    vals = sorted(values)
    rank = (len(vals) - 1) * pct / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (rank - lo)


class Counters:
    """Counts of the runtime's own events (``jax.monitoring``)."""

    def __init__(self):
        import jax

        self.cache_hits = self.cache_misses = self.lowerings = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings += 1


def find_chips(wanted: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chipbench needs a TPU; JAX found platform {devices[0].platform!r}"
        )
    if len(devices) < wanted:
        raise SystemExit(f"the cell asks for {wanted} chips; JAX found {len(devices)}")
    return devices


def run(opts, devices, wrap_step=None, cell=None, peaks=None):
    """Everything after the look for a chip; returns the result object.
    Tests pass a tiny ``cell``, its ``peaks`` and, through ``wrap_step``,
    a timed path broken underneath."""
    import jax

    from chipbench.harness import check, spec, xtrace
    from chipbench.harness.session import Session, say

    cell = cell or spec.Cell(opts.workload)
    peaks = peaks or spec.peaks(devices[0].device_kind)
    counters = Counters()
    say(phase="start", workload=cell.name, seed=opts.seed, seconds=opts.seconds,
        trace=opts.trace, jax=jax.__version__,
        device_kind=devices[0].device_kind, device_count=len(devices),
        chips=cell.chips)

    session = Session(cell)
    trainer = session.trainer
    first = session.first_steps(
        opts.seed, int(cell.traffic["warmup_steps"]), wrap_step
    )
    loop = first.loop
    # Tracing the step leaves over a million tracked objects behind, and one
    # pass of Python's oldest generation over them stalls the host for
    # 0.4-0.5 s. When the next pass is due depends on how many objects
    # set-up happened to allocate, so it fell at step 98 of one tree's
    # window and outside another's: 0.5% of images/s on one chip, 1.3% on
    # four (PERF.md section 6, PR 30). The window starts from a collected
    # heap, and the pass counts as set-up.
    gc.collect()
    setup_s = time.perf_counter() - T_START
    say(phase="setup", setup_s=setup_s,
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        cache_hits=counters.cache_hits, cache_misses=counters.cache_misses,
        warmup_step_s=list(loop.step_s), warmup_losses=list(loop.losses))

    # -- the measured window: whole steps, nothing else ---------------------
    lowerings = counters.lowerings
    cache_hits = counters.cache_hits
    before = len(loop.step_s)
    failed_before = loop.failed
    started, window_s = loop.run(seconds=opts.seconds)
    step_s = loop.step_s[before:]
    spans = {k: v[before:] for k, v in loop.spans.items()}
    window_compiles = counters.lowerings - lowerings
    failed = loop.failed - failed_before
    images_per_s = session.batch * len(step_s) / window_s
    say(phase="window", steps=len(step_s), window_s=window_s,
        step_ms_min=1e3 * min(step_s), step_ms_p50=1e3 * percentile(step_s, 50),
        step_ms_p90=1e3 * percentile(step_s, 90), step_ms_max=1e3 * max(step_s),
        window_compiles=window_compiles, last_loss=loop.losses[-1])

    # -- the traced window: a few steady steps, a run of its own ------------
    reduced = None
    trace_steps = int(cell.traffic["trace_steps"])
    if opts.trace:
        logdir = os.path.join(ROOT, ".cache", "chipbench", "trace", cell.name)
        with xtrace.capture(logdir):
            loop.run(steps=trace_steps + 2)
        planes = xtrace.load(logdir)
        reduced = xtrace.reduce(
            planes, cell.config["entry_point"]["step_program"], trace_steps
        )
        if reduced is None:
            raise SystemExit("the trace holds no device line of the step program")
        say(phase="trace", steps=reduced.steps, window_s=reduced.window_s,
            busy_s=reduced.busy_s, planes=[p.name for p in planes])

    # -- memory, then free the program's state ------------------------------
    chips = list(trainer.mesh.devices.flat)
    allocator_peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in chips
    )
    xs, ys = trainer.shard_batch(*(jax.numpy.asarray(a) for a in first.batches[0]))
    footprint = trainer.record_memory_footprint(loop.state, xs, ys)
    del xs, ys
    say(phase="memory", allocator_peak_bytes=allocator_peak,
        allocator_stats=chips[0].memory_stats(),
        compiled_step=footprint and {
            k: footprint.get(k) for k in
            ("argument_bytes", "output_bytes", "alias_bytes", "temp_bytes",
             "generated_code_bytes", "peak_bytes")})
    step_bytes = (footprint or {}).get("peak_bytes") or 0
    loop.state = None

    # -- correctness: the reference follows the first steps -----------------
    t_ref = time.perf_counter()
    numbers = session.compare(first)
    correct, compared = check.verdict(numbers, cell.limits["limits"])
    if failed or loop.failed:
        correct = False
    say(phase="check", reference_s=time.perf_counter() - t_ref, correct=correct)

    context = {
        "cell": cell, "session": session, "trainer": trainer, "peaks": peaks,
        "reduced": reduced, "spans": spans, "step_s": step_s,
        "window_rate": images_per_s, "compiles_in_window": window_compiles,
        "setup_cache_hits": cache_hits, "footprint": footprint,
        "setup_seconds": setup_s, "percentile": percentile,
    }
    # Traced, the line holds the cell's per-layer metrics; otherwise its
    # end-to-end ones. A reader that finds nothing to read returns None
    # and its metric is left out.
    group, wanted = (
        ("layer_metrics", cell.per_layer) if opts.trace
        else ("end_to_end", cell.end_to_end)
    )
    metrics = {}
    for metric in wanted:
        value = spec.metric_reader(group, metric["name"])(context)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        # The allocator's peak leaves the step's temporaries out (2.97 GB
        # read where the compiled step holds 14.2 GiB, PERF.md section 7);
        # the compiled step's own footprint is what the chip must hold.
        "memory_peak_bytes": max(allocator_peak, step_bytes),
    }
    result = {
        "correct": bool(correct), "attempted": started, "failed": failed,
        "metrics": metrics, "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": reduced.device_ops, "idle_gaps": reduced.idle_gaps,
        }
    # last on the line: what was compared, each number beside its limit
    result["compared"] = compared
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)

    from chipbench.harness import spec

    devices = find_chips(spec.Cell(opts.workload).chips)
    result = run(opts, devices)
    for name, row in result["compared"].items():
        print(f"compared {name} {row['value']} limit {row['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
