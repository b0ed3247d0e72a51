"""Profiler capture and the reduction from a trace to numbers.

The capture is ``jax.profiler.trace`` with Python-call tracing off (at its
default level a multi-step capture overflows the converter's event cap and
the device lines are what gets dropped). The ``.xplane.pb`` it leaves is
read with ``jax.profiler.ProfileData`` into plain :class:`Plane` /
:class:`Line` / :class:`Event` records, and every number below is computed
from those records, so the arithmetic is checked on a hand-built trace
(``tests/test_xtrace.py``) and is the same in every PR.

What a TPU trace of this runtime looks like (read by hand from the first
chip traces of PR 25, ``tools/trace_inventory.py``): one plane per chip named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed HLO
instruction, named by the instruction's whole text (``%fusion.7 = bf16[...]
fusion(...)``; a Pallas kernel's instruction carries the kernel's name,
``%mpi4dl_pool_bwd.79``; an asynchronous collective is a short ``-start`` and
a ``-done`` event); line ``XLA Modules`` holds one event per program run, the
step's named ``jit__train_step(...)``; ``Steps`` and ``Async XLA Ops`` are
not read. Host threads are lines of plane ``/host:CPU``; the harness's
``TraceAnnotation`` spans are events there, on the same clock. One step of
AmoebaNet-D is some 21,000 op events.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVES = {
    base + suffix
    for base in ("collective-permute", "all-reduce", "all-gather", "all-to-all",
                 "reduce-scatter", "collective-broadcast")
    for suffix in ("", "-start", "-done")
}
# An op event is named by its HLO instruction's text,
# "%fusion.7 = bf16[2,512,512,208]{...} fusion(...)": the instruction's name,
# its result, its opcode and operands (a short trace names it "fusion.7").
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SUFFIX = re.compile(r"([.\-_](\d+|remat\d*|remat_compressed|clone))+$")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float
    stats: dict

    @property
    def end_ns(self):
        return self.start_ns + self.duration_ns

    @property
    def op(self):
        """The instruction's own name, without ``%`` and the text after it."""
        return self.name.partition(" = ")[0].lstrip("%")

    @property
    def opcode(self):
        """The HLO opcode where the name carries the instruction's text,
        else the name without its number (``all-reduce.4`` -> ``all-reduce``)."""
        _, found, text = self.name.partition(" = ")
        match = _OPCODE.search(" " + text) if found else None
        return match.group(1) if match else _SUFFIX.sub("", self.op)

    @property
    def family(self):
        """The instruction's name without its numbering: ``fusion``,
        ``select_and_scatter``, ``mpi4dl_pool_bwd`` ... what XLA called
        this kind of op."""
        return _SUFFIX.sub("", self.op)


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list

    def line(self, name):
        for line in self.lines:
            if line.name == name:
                return line
        return None


@contextlib.contextmanager
def capture(logdir: str):
    """Trace the enclosed steps into ``logdir`` (emptied first)."""
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    # Level 1 keeps TraceAnnotation spans and drops the runtime's own host
    # events (1.5 million in a ten-step capture at the default level 2).
    options.host_tracer_level = 1
    with jax.profiler.trace(logdir, profiler_options=options):
        yield


def load(logdir: str, host_prefix="chipbench_",
         want_stats=("hlo_category", "tf_op", "long_name", "name")):
    """The capture under ``logdir`` as a list of :class:`Plane`; of the
    host's events only the harness's own spans are kept."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, found {paths}")
    planes = []
    for plane in ProfileData.from_file(paths[0]).planes:
        keep_stats = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            if keep_stats and line.name not in (OPS_LINE, MODULES_LINE):
                continue  # a chip's other lines (async ops, steps) are not read
            for ev in line.events:
                if not keep_stats and not ev.name.startswith(host_prefix):
                    continue
                stats = {}
                if keep_stats and line.name == OPS_LINE:
                    stats = {k: v for k, v in ev.stats if k in want_stats}
                events.append(Event(ev.name, ev.start_ns, ev.duration_ns, stats))
            lines.append(Line(line.name, events))
        planes.append(Plane(plane.name, lines))
    return planes


# -- reduction ---------------------------------------------------------------


def device_planes(planes):
    found = [(int(DEVICE_PLANE.match(p.name).group(1)), p)
             for p in planes if DEVICE_PLANE.match(p.name)]
    return [p for _, p in sorted(found, key=lambda t: t[0])]


def union_seconds(intervals) -> float:
    """Length of the union of ``(start_ns, end_ns)`` intervals, seconds."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def clip(events, t0, t1):
    """``(start, end)`` of each event's part inside ``[t0, t1]``."""
    out = []
    for ev in events:
        s, e = max(ev.start_ns, t0), min(ev.end_ns, t1)
        if e > s:
            out.append((s, e))
    return out


def step_window(plane, module_name: str, steps: int):
    """The device's own view of ``steps`` whole steps: from the start of a
    run of the step program to the start of the run ``steps`` later, on
    the device's clock. Takes the last such stretch the trace holds.
    Returns ``(t0_ns, t1_ns)`` or None."""
    line = plane.line(MODULES_LINE)
    if line is None:
        return None
    runs = sorted(
        (ev for ev in line.events if module_name in ev.name),
        key=lambda ev: ev.start_ns,
    )
    if len(runs) < steps + 1:
        return None
    return runs[-steps - 1].start_ns, runs[-1].start_ns


def is_collective(ev) -> bool:
    return ev.opcode in COLLECTIVES


def collective_seconds(events, t0, t1) -> float:
    """Time inside collectives. An asynchronous one shows as a ``-start``
    and a ``-done`` event; each is counted for its own duration (the time
    the device spends issuing and waiting), never the stretch between."""
    return sum((e - s) for s, e in clip([ev for ev in events if is_collective(ev)], t0, t1)) / 1e9


def kernel_events(events, kernel: str):
    """Events of the custom call that runs the named Pallas kernel: XLA
    names the instruction after the kernel (``%mpi4dl_pool_bwd.79``); a
    trace that names ops otherwise carries it in a string stat."""
    return [
        ev for ev in events
        if kernel in ev.op
        or any(isinstance(v, str) and kernel in v for v in ev.stats.values())
    ]


def kernel_seconds_per_step(reduced, kernel: str):
    """Seconds per step the first chip spends in the named kernel; None
    where the trace holds no event of it."""
    if reduced is None:
        return None
    chip = reduced.chips[0]
    events = kernel_events(chip["ops"], kernel)
    if not events:
        return None
    return sum(e - s for s, e in clip(events, *chip["window"])) / 1e9 / reduced.steps


def is_custom_call(ev) -> bool:
    return ev.opcode == "custom-call"


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers get from one traced window."""

    steps: int
    window_s: float            # mean over chips of the window's length
    busy_s: float              # mean over chips of the union of op intervals
    chips: list                # per chip: dict(window=(t0, t1), ops=[Event])
    device_ops: list           # [[op family (count), seconds]], chip 0, at most 10
    idle_gaps: list            # [[host span, seconds]], chip 0, at most 10


def reduce(planes, module_name: str, steps: int, host_prefix="chipbench_"):
    chips = []
    for plane in device_planes(planes):
        window = step_window(plane, module_name, steps)
        ops = plane.line(OPS_LINE)
        if window is None or ops is None:
            continue
        inside = [ev for ev in ops.events
                  if ev.end_ns > window[0] and ev.start_ns < window[1]]
        chips.append({"window": window, "ops": inside})
    if not chips:
        return None
    window_s = sum(c["window"][1] - c["window"][0] for c in chips) / 1e9 / len(chips)
    busy_s = sum(
        union_seconds(clip(c["ops"], *c["window"])) for c in chips
    ) / len(chips)
    first = chips[0]
    # One step runs some 20,000 ops, none of them long: the families of ops
    # (what XLA named them, numbering off) say where the time goes.
    seconds: dict = {}
    count: dict = {}
    t0, t1 = first["window"]
    for ev in first["ops"]:
        inside = max(min(ev.end_ns, t1) - max(ev.start_ns, t0), 0)
        seconds[ev.family] = seconds.get(ev.family, 0.0) + inside / 1e9
        count[ev.family] = count.get(ev.family, 0) + 1
    device_ops = sorted(seconds.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(
        steps=steps, window_s=window_s, busy_s=busy_s, chips=chips,
        device_ops=[[f"{k} (x{count[k]})", v] for k, v in device_ops],
        idle_gaps=idle_gaps(planes, first, host_prefix),
    )


def idle_gaps(planes, chip, host_prefix):
    """The chip's idle stretches inside its window, summed by the harness
    span (``data_next``, ``shard_batch``, ``dispatch``, ``loss_read``) that
    covers most of each; ``other`` where none does."""
    t0, t1 = chip["window"]
    busy = sorted(clip(chip["ops"], t0, t1))
    gaps, cursor = [], t0
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < t1:
        gaps.append((cursor, t1))
    spans = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans += [ev for ev in line.events if ev.name.startswith(host_prefix)]
    by_span: dict = {}
    for g0, g1 in gaps:
        best, cover = "other", 0.0
        for ev in spans:
            c = min(ev.end_ns, g1) - max(ev.start_ns, g0)
            if c > cover:
                best, cover = ev.name[len(host_prefix):], c
        by_span[best] = by_span.get(best, 0.0) + (g1 - g0) / 1e9
    return [[k, v] for k, v in sorted(by_span.items(), key=lambda kv: -kv[1])[:10]]
