"""Profiling / step timing.

The reference's observability is paired CUDA events around each batch plus
prints (``benchmark_amoebanet_sp.py:322-367``; SURVEY.md §5.1). The TPU
equivalents:

- :class:`StepTimer` — host wall-clock per step with ``block_until_ready``
  (async dispatch means a bare ``time.time()`` measures nothing), tracking
  the same statistics every reference benchmark prints (per-step seconds,
  images/sec, mean/median) plus p50/p90/p99 tail percentiles — a serving
  path lives and dies by tail latency, not means;
- :func:`percentiles` — the shared percentile helper (linear interpolation
  on the sorted sample, numpy's default method) used by :class:`StepTimer`
  and the serving load generator;
- :func:`trace` — ``jax.profiler`` trace context writing a TensorBoard/XProf
  trace directory (device timelines, HLO cost, ICI collectives); enabled by
  path or the ``MPI4DL_TPU_TRACE_DIR`` env var, no-op otherwise;
- :func:`annotate_step` — ``jax.profiler.StepTraceAnnotation`` wrapper the
  train/serve dispatch paths use, so XProf step boundaries carry the same
  step/batch ids as the telemetry span log
  (:mod:`mpi4dl_tpu.telemetry.spans`) and the two can be joined;
- :func:`capture` — programmatic trace capture: wraps :func:`trace` around
  N annotated, fully-blocked invocations of a step function and returns a
  :class:`Capture` whose :meth:`Capture.attribution` parses the emitted
  Chrome trace into a compute/collective/transfer/host-gap device-time
  report (:mod:`mpi4dl_tpu.analysis.trace`) — the runtime counterpart of
  hlolint's static overlap rule.

:class:`StepTimer` optionally publishes into a telemetry registry
(:mod:`mpi4dl_tpu.telemetry`): per-step ``train_step_seconds`` histogram
observations, a ``train_steps_total`` counter, and a
``train_images_per_sec`` gauge — the training side of the unified metric
catalog (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import statistics
import tempfile
import time
from typing import Any


def percentiles(values, pcts=(50, 90, 99)) -> dict:
    """``{"p50": v, ...}`` by linear interpolation on the sorted sample
    (numpy's default "linear" method, hand-rolled so callers measuring
    latency need no array round-trip). Empty input → empty dict."""
    vals = sorted(values)
    if not vals:
        return {}
    out = {}
    for p in pcts:
        rank = (len(vals) - 1) * p / 100.0
        lo = int(rank)
        hi = min(lo + 1, len(vals) - 1)
        out[f"p{p:g}"] = vals[lo] + (vals[hi] - vals[lo]) * (rank - lo)
    return out


class StepTimer:
    """Times steps and accumulates throughput stats.

    ``step()`` takes no argument — the context target (``as rec``) IS the
    setter for the result to block on::

        timer = StepTimer(batch_size=B, warmup=1)
        for ... :
            with timer.step() as rec:
                state, metrics = trainer.train_step(...)
                rec(metrics)           # anything with .block_until_ready leaves
        print(timer.summary())

    ``registry``: an optional :class:`mpi4dl_tpu.telemetry.MetricsRegistry`;
    each post-warmup step then also lands in the cataloged ``train_*``
    metrics (histogram + counter + throughput gauge).

    ``watchdog``: an optional :class:`mpi4dl_tpu.telemetry.Watchdog`; the
    timer then reports step begin/completion to it, so a hung step (no
    completion within K× the rolling p99) trips the same liveness
    machinery the serving engine uses.
    """

    def __init__(
        self, batch_size: int, warmup: int = 1, registry=None, watchdog=None
    ):
        self.batch_size = batch_size
        self.warmup = warmup
        self.times: list[float] = []
        self._seen = 0
        self._metrics = None
        self._watchdog = watchdog
        if registry is not None:
            from mpi4dl_tpu import telemetry

            self._metrics = (
                telemetry.declare(registry, "train_step_seconds"),
                telemetry.declare(registry, "train_steps_total"),
                telemetry.declare(registry, "train_images_per_sec"),
            )

    @contextlib.contextmanager
    def step(self):
        import jax

        out: list[Any] = []
        if self._watchdog is not None:
            self._watchdog.begin()
        dt = None
        try:
            t0 = time.perf_counter()
            yield out.append
            if out:
                jax.block_until_ready(out[-1])
            dt = time.perf_counter() - t0
        finally:
            if self._watchdog is not None:
                self._watchdog.done(dt)
        self._seen += 1
        if self._seen > self.warmup:
            self.times.append(dt)
            if self._metrics is not None:
                hist, total, ips = self._metrics
                hist.observe(dt)
                total.inc()
                ips.set(self.batch_size / dt if dt > 0 else 0.0)

    @property
    def images_per_sec(self) -> list[float]:
        # dt == 0 (a clock too coarse for a trivial step) reports 0.0
        # throughput — same convention as the telemetry gauge above —
        # instead of raising ZeroDivisionError in summary().
        return [self.batch_size / t if t > 0 else 0.0 for t in self.times]

    def summary(self) -> dict:
        if not self.times:
            return {"steps": 0}
        ips = self.images_per_sec
        out = {
            "steps": len(self.times),
            "step_time_mean_s": statistics.mean(self.times),
            "step_time_median_s": statistics.median(self.times),
            "images_per_sec_mean": statistics.mean(ips),
            "images_per_sec_median": statistics.median(ips),
        }
        for k, v in percentiles(self.times).items():
            out[f"step_time_{k}_s"] = v
        return out


@contextlib.contextmanager
def trace(logdir: str | None = None):
    """``jax.profiler.trace`` context. ``logdir`` (or ``MPI4DL_TPU_TRACE_DIR``)
    unset → no-op."""
    logdir = logdir or os.environ.get("MPI4DL_TPU_TRACE_DIR")
    if not logdir:
        yield None
        return
    import jax

    # Python-call tracing off: at its default level every Python function
    # call becomes a host slice, a multi-step capture overflows the trace
    # converter's 1M-event cap, and the device-thread slices the
    # attribution reads are what gets dropped. TraceAnnotation /
    # StepTraceAnnotation spans are host-tracer events and stay.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(logdir, profiler_options=options):
        yield logdir


@contextlib.contextmanager
def annotate_step(name: str, step: "int | None" = None):
    """``jax.profiler.StepTraceAnnotation`` around one dispatch, so XProf
    traces (:func:`trace`) slice the device timeline at the same step ids
    the telemetry span log records. Host-side step counters (not device
    arrays) only — reading a traced scalar here would force a sync.
    Degrades to a no-op if the profiler annotation API is unavailable."""
    import jax

    try:
        ann = (
            jax.profiler.StepTraceAnnotation(name, step_num=step)
            if step is not None
            else jax.profiler.StepTraceAnnotation(name)
        )
    except Exception:  # noqa: BLE001 — observability must not break dispatch
        yield
        return
    with ann:
        yield


#: Annotation name :func:`capture` wraps around each step. Distinct from
#: the dispatch-path names ("mpi4dl_train_step"/"mpi4dl_serve_batch") so
#: a capture window strictly CONTAINS each step's device work (the block
#: happens inside the annotation), even when the step function annotates
#: its own async dispatch internally.
CAPTURE_STEP_NAME = "mpi4dl_capture"


@dataclasses.dataclass
class Capture:
    """One finished :func:`capture`: where the trace landed, plus the
    host-measured wall time of each annotated step (the independent
    ground truth the attribution's per-step sums are checked against)."""

    trace_dir: str
    step_name: str
    n_steps: int
    step_times_s: list

    def attribution(self, registry=None, program: str = "capture") -> dict:
        """Parse the emitted Chrome trace into the per-step
        compute/collective/transfer/host-gap report
        (:func:`mpi4dl_tpu.analysis.trace.analyze_trace_dir`); with a
        ``registry``, also publish the cataloged ``trace_*`` gauges
        under ``program``."""
        from mpi4dl_tpu.analysis.trace import (
            analyze_trace_dir,
            publish_attribution,
        )

        summary = analyze_trace_dir(self.trace_dir, step_name=self.step_name)
        summary["host_step_times_s"] = list(self.step_times_s)
        if registry is not None:
            publish_attribution(summary, registry, program=program)
        return summary


def capture(
    step_fn,
    steps: int = 3,
    logdir: "str | None" = None,
    name: str = CAPTURE_STEP_NAME,
) -> Capture:
    """Trace ``steps`` invocations of ``step_fn(i)`` under
    ``jax.profiler.trace``, each wrapped in a step annotation with the
    result blocked to completion INSIDE the annotation — so every step's
    device work falls within its window and the attribution buckets sum
    to the step wall time. ``logdir=None`` captures into a fresh temp
    directory (reported on :attr:`Capture.trace_dir`)."""
    import jax

    if logdir is None:
        logdir = tempfile.mkdtemp(prefix="mpi4dl-capture-")
    times: list[float] = []
    with trace(logdir):
        for i in range(int(steps)):
            t0 = time.perf_counter()
            with annotate_step(name, i):
                out = step_fn(i)
                if out is not None:
                    jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
    return Capture(
        trace_dir=logdir, step_name=name, n_steps=int(steps),
        step_times_s=times,
    )
