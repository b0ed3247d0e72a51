"""The timed step loop, the one every phase of a run drives.

It is ``benchmarks/common.run_training``'s loop: take the next host batch
from the input pipeline, ``trainer.shard_batch``, ``trainer.train_step``,
read the loss on the host. A step's time is the host clock from the end of
the previous loss read to the end of this one, so the input pipeline and
the batch's way onto the chips are inside it. Warm-up, the measured window
and the traced window all call :meth:`StepLoop.run`; set-up hands the
window the very object it warmed up.
"""

from __future__ import annotations

import math
import time


class StepLoop:
    def __init__(self, trainer, state, batches, on_step=None):
        self.trainer, self.state = trainer, state
        self.batches = iter(batches)
        self.on_step = on_step  # called with (index, state, host batch)
        self.steps = 0
        self.failed = 0
        self.losses: list[float] = []
        self.step_s: list[float] = []
        self.spans: dict[str, list[float]] = {
            "data_next": [], "shard_batch": [], "dispatch": [], "loss_read": [],
        }

    def _span(self, name):
        return _Span(self.spans[name], name)

    def _one(self):
        import jax.numpy as jnp

        with self._span("data_next"):
            x, y = next(self.batches)
        with self._span("shard_batch"):
            xs, ys = self.trainer.shard_batch(jnp.asarray(x), jnp.asarray(y))
        with self._span("dispatch"):
            self.state, metrics = self.trainer.train_step(self.state, xs, ys)
        with self._span("loss_read"):
            loss = float(metrics["loss"])  # the host read ends the step
        if self.on_step is not None:
            self.on_step(self.steps, self.state, (x, y))
        return loss

    def run(self, steps=None, seconds=None):
        """Whole steps: ``steps`` of them, or until the first one that ends
        after ``seconds``. Returns ``(started, window_seconds)``; the step
        times are appended to ``step_s``."""
        started = 0
        t0 = last = time.perf_counter()
        while (steps is None or started < steps) and (
            seconds is None or last - t0 < seconds
        ):
            started += 1
            loss = self._one()
            now = time.perf_counter()
            self.step_s.append(now - last)
            last = now
            self.steps += 1
            self.losses.append(loss)
            if not math.isfinite(loss):
                self.failed += 1
        return started, last - t0


class _Span:
    """A host span on the profiler's clock (``TraceAnnotation``) whose
    length is also kept, in seconds, for the per-layer readers."""

    def __init__(self, sink, name):
        import jax

        self.sink = sink
        self.annotation = jax.profiler.TraceAnnotation("chipbench_" + name)

    def __enter__(self):
        self.annotation.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.sink.append(time.perf_counter() - self.t0)
        return self.annotation.__exit__(*exc)
