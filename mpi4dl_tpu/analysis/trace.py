"""Runtime device-time attribution from XProf Chrome traces.

hlolint (:mod:`mpi4dl_tpu.analysis`) statically predicts communication
structure and overlap from scheduled HLO; this module measures what
actually happened at runtime. :func:`mpi4dl_tpu.profiling.capture` wraps
``jax.profiler.trace`` around N annotated steps; the profiler emits a
Chrome-trace JSON (``plugins/profile/<run>/*.trace.json.gz``) that this
parser reads with stdlib ``gzip`` + ``json`` only — no TF/protobuf/xprof
dependency — and turns into:

- a typed event inventory (:class:`TraceEvent`) split into host and
  device timelines by thread identity (CPU: the ``XLAPjRtCpuClient``
  executor threads carry per-HLO-op slices; TPU/GPU: ``/device:*``
  process timelines, preferring the ``XLA Ops`` line to avoid counting
  the module/step summary lines twice);
- per-step attribution (:func:`attribute_steps`): device slices are
  joined to the ``StepTraceAnnotation`` windows the train/serve dispatch
  paths already emit (:func:`mpi4dl_tpu.profiling.annotate_step`, the
  same host-side step ids the telemetry span log records), and each
  step's wall time is bucketed into **compute / collective / transfer /
  host_gap**. The buckets are exclusive by construction (priority
  collective > transfer > compute on the merged interval union, host_gap
  = wall − device-busy), so they sum exactly to the step wall time;
- a **measured-overlap** report: for every collective slice, the
  fraction of its duration during which compute was concurrently running
  on another device timeline — the runtime counterpart of the static
  start→done ``compute_between`` rule, per T3 (arXiv:2401.16677) / FLUX
  (arXiv:2406.06858) the quantity that decides spatial-parallel
  performance;
- :func:`crosscheck_overlap`: static verdict vs measured verdict on the
  same executable; disagreement ("schedule says the window is covered,
  the trace shows exposed latency") is a new lint finding
  (rule ``trace-overlap-crosscheck``).

Degradation contract (tier-1 tested): a missing/empty trace directory
raises :class:`TraceError` at the reader — never a KeyError three layers
down — and a trace with no step annotations still yields a whole-range
attribution (``n_steps == 0``) instead of failing.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

from mpi4dl_tpu.analysis.rules import Finding

#: Substrings (hyphenated HLO opcode stems) that mark a device slice as
#: collective traffic. Fusion kernel names use underscores, so an
#: ``all_reduce_fusion`` compute kernel does not false-positive here.
COLLECTIVE_MARKERS = (
    "collective-permute",
    "all-reduce",
    "all-gather",
    "all-to-all",
    "reduce-scatter",
    "collective-broadcast",
    "ragged-all-to-all",
)

#: The installed jax names an HLO instruction after the primitive that
#: made it (``ppermute.3``, ``psum.14``), and where no backend pass renames
#: it (the CPU backend) that is the slice's name in the trace — not the
#: opcode. Matched against the WHOLE name less its trailing ``.N``, so a
#: ``psum_fusion`` compute kernel does not false-positive.
COLLECTIVE_PRIMITIVE_STEMS = frozenset({
    "ppermute",
    "psum",
    "pmax",
    "pmin",
    "all_gather",
    "all_to_all",
    "reduce_scatter",
    "pbroadcast",
})
#: The primitive behind ``collective-permute``.
_PERMUTE_STEMS = ("collective-permute", "ppermute")

#: Case-insensitive substrings marking host<->device / device<->device
#: data movement (the "h2d" bucket; includes d2h and d2d).
TRANSFER_MARKERS = (
    "transfertodevice",
    "transferfromdevice",
    "transferraw",
    "d2d dispatch",
    "h2d",
    "d2h",
    "infeed",
    "outfeed",
    "copy-start",
    "copy-done",
    "bufferfromhost",
    "buffertohost",
)

#: Thread-name substrings that mark a CPU-backend device timeline: the
#: per-device PjRtCpuClient executor threads AND the shared XLAEigen
#: intra-op pool — XLA's thunk executor schedules op thunks onto either,
#: and which one a given op lands on varies run to run.
_CPU_DEVICE_THREAD_MARKERS = (
    "XLAPjRtCpuClient",
    "XLAEigen",
)

#: Runtime bookkeeping that shows up on device executor threads but is
#: not op execution (waits, region markers, executable wrappers). Counting
#: the ``ExecuteHelper`` wrapper would double every op under it.
_INFRA_PREFIXES = (
    "ThreadpoolListener",
    "ThunkExecutor",
    "SlinkyThreadPool",
    "PjRtCpu",
    "CommonPjRt",
    "Handle inputs",
    "Rendezvous",
    "InvokeRendezvous",
    "Wait",  # "Wait: pending_threads=3/8", "Wait for rendezvous callback"
    "ParseArguments",
    "PjitFunction",
    "ExecuteThunks",
    "end: ",  # the thunk executor's end-of-op markers
    "$",  # python-source host slices
)

_TRAILING_ID = re.compile(r"\.\d+$")

CATEGORIES = ("compute", "collective", "transfer", "host_gap")

#: Measured overlap ratio at/above which a trace's collective time counts
#: as "overlapped" (hidden behind compute) rather than "exposed".
OVERLAPPED_MIN = 0.5


class TraceError(RuntimeError):
    """The trace directory is missing, empty, or unreadable."""


@dataclasses.dataclass
class TraceEvent:
    """One complete ("X") slice from the Chrome trace, times in seconds."""

    name: str
    pid: int
    tid: int
    start_s: float
    end_s: float
    category: str  # "compute" | "collective" | "transfer"

    @property
    def duration_s(self) -> float:
        return self.end_s - self.start_s


def categorize(name: str) -> "str | None":
    """Device-slice category for an event name, or None for runtime
    bookkeeping that must not count as device busy time."""
    if any(m in name for m in COLLECTIVE_MARKERS):
        return "collective"
    if _TRAILING_ID.sub("", name) in COLLECTIVE_PRIMITIVE_STEMS:
        return "collective"
    low = name.lower()
    if any(m in low for m in TRANSFER_MARKERS):
        return "transfer"
    if any(name.startswith(p) for p in _INFRA_PREFIXES):
        return None
    return "compute"


def read_trace_events(trace_dir: str) -> "list[dict]":
    """Raw ``traceEvents`` of the NEWEST profiler run under ``trace_dir``
    (``plugins/profile/<run>/*.trace.json[.gz]``), all hosts merged.
    Raises :class:`TraceError` when there is nothing to read."""
    if not os.path.isdir(trace_dir):
        raise TraceError(f"trace directory {trace_dir!r} does not exist")
    runs = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*")))
    if not runs:
        raise TraceError(
            f"no profiler runs under {trace_dir!r} (expected "
            "plugins/profile/<run>/ — did the capture actually trace?)"
        )
    run = runs[-1]
    files = sorted(
        glob.glob(os.path.join(run, "*.trace.json.gz"))
        + glob.glob(os.path.join(run, "*.trace.json"))
    )
    if not files:
        raise TraceError(f"profiler run {run!r} has no *.trace.json[.gz]")
    events: list[dict] = []
    for path in files:
        opener = gzip.open if path.endswith(".gz") else open
        try:
            with opener(path, "rb") as f:
                data = json.loads(f.read())
        except (OSError, ValueError) as e:
            raise TraceError(f"unreadable trace file {path!r}: {e}") from e
        events.extend(data.get("traceEvents") or [])
    return events


def _name_tables(events) -> "tuple[dict, dict]":
    """(process names by pid, thread names by (pid, tid)) from "M" events."""
    procs: dict = {}
    threads: dict = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e.get("pid")] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = (
                e.get("args", {}).get("name", "")
            )
    return procs, threads


def device_slices(events) -> "list[TraceEvent]":
    """Device-timeline op slices, categorized; host threads and runtime
    bookkeeping excluded.

    CPU: XLA runs op thunks on the per-device ``XLAPjRtCpuClient``
    executor threads and the shared ``XLAEigen`` intra-op pool — both are
    device timelines here. TPU/GPU: each device is a ``/device:*``
    process whose ``XLA Ops`` thread carries the op timeline — when that
    named line exists only it is used, since the ``XLA
    Modules``/``Steps`` lines cover the same wall time again.
    """
    procs, threads = _name_tables(events)
    dev_pids = {
        pid for pid, name in procs.items()
        if str(name).startswith("/device:")
    }
    # Per accelerator pid: restrict to the "XLA Ops" line when present.
    ops_threads: dict = {}
    for (pid, tid), tname in threads.items():
        if pid in dev_pids and "XLA Ops" in str(tname):
            ops_threads.setdefault(pid, set()).add(tid)

    out: list[TraceEvent] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        pid, tid = e.get("pid"), e.get("tid")
        tname = str(threads.get((pid, tid), ""))
        if pid in dev_pids:
            allowed = ops_threads.get(pid)
            if allowed is not None and tid not in allowed:
                continue
            if any(k in tname for k in ("Steps", "Modules", "Framework",
                                        "Scope", "Source")):
                continue
        elif not any(m in tname for m in _CPU_DEVICE_THREAD_MARKERS):
            continue  # host thread
        cat = categorize(str(e.get("name", "")))
        if cat is None:
            continue
        ts = float(e.get("ts", 0.0))
        dur = float(e.get("dur", 0.0))
        if dur <= 0:
            continue
        out.append(TraceEvent(
            name=str(e.get("name")), pid=pid, tid=tid,
            start_s=ts / 1e6, end_s=(ts + dur) / 1e6, category=cat,
        ))
    out.sort(key=lambda ev: ev.start_s)
    return out


def step_windows(events, step_name: str) -> "list[tuple[float, float, str]]":
    """``(start_s, end_s, step_num)`` for every X event named exactly
    ``step_name`` — the ``StepTraceAnnotation`` windows."""
    out = []
    for e in events:
        if e.get("ph") != "X" or e.get("name") != step_name:
            continue
        ts = float(e.get("ts", 0.0))
        dur = float(e.get("dur", 0.0))
        num = str((e.get("args") or {}).get("step_num", len(out)))
        out.append((ts / 1e6, (ts + dur) / 1e6, num))
    out.sort()
    return out


# -- interval algebra (merged, half-open [s, e) second intervals) -------------


def _merged(intervals) -> "list[tuple[float, float]]":
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _total(merged) -> float:
    return sum(e - s for s, e in merged)


def _clip(intervals, lo: float, hi: float):
    return [
        (max(s, lo), min(e, hi))
        for s, e in intervals
        if min(e, hi) > max(s, lo)
    ]


def _intersect(a_merged, b_merged) -> "list[tuple[float, float]]":
    out, i, j = [], 0, 0
    while i < len(a_merged) and j < len(b_merged):
        s = max(a_merged[i][0], b_merged[j][0])
        e = min(a_merged[i][1], b_merged[j][1])
        if e > s:
            out.append((s, e))
        if a_merged[i][1] <= b_merged[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a_merged, b_merged) -> "list[tuple[float, float]]":
    out = []
    j = 0
    for s, e in a_merged:
        cur = s
        while j < len(b_merged) and b_merged[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_merged) and b_merged[k][0] < e:
            bs, be = b_merged[k]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# -- attribution --------------------------------------------------------------


def _bucket(slices, lo: float, hi: float) -> dict:
    """Exclusive category times over [lo, hi): collective > transfer >
    compute on the merged union, host_gap = wall − device-busy. The four
    buckets sum to ``hi - lo`` exactly."""
    by_cat = {c: [] for c in ("collective", "transfer", "compute")}
    for ev in slices:
        by_cat[ev.category].append((ev.start_s, ev.end_s))
    coll = _merged(_clip(by_cat["collective"], lo, hi))
    tran = _merged(_clip(by_cat["transfer"], lo, hi))
    comp = _merged(_clip(by_cat["compute"], lo, hi))
    collective_s = _total(coll)
    transfer_s = _total(_subtract(tran, coll))
    comm = _merged(list(coll) + list(tran))
    compute_s = _total(_subtract(comp, comm))
    busy_s = collective_s + transfer_s + compute_s
    wall_s = hi - lo
    return {
        "wall_s": wall_s,
        "compute_s": compute_s,
        "collective_s": collective_s,
        "transfer_s": transfer_s,
        "host_gap_s": max(0.0, wall_s - busy_s),
        "device_busy_s": busy_s,
    }


def attribute_steps(slices, windows) -> "list[dict]":
    """Per-step attribution: device slices joined (clipped) to each
    annotation window."""
    steps = []
    for lo, hi, num in windows:
        rec = {"step": num, "start_s": lo, "end_s": hi}
        rec.update(_bucket(slices, lo, hi))
        steps.append(rec)
    return steps


def measured_overlap(slices) -> dict:
    """Per-collective-slice overlap with concurrent compute on OTHER
    device timelines: the runtime analogue of the static
    ``compute_between`` count. Returns totals, the overall ratio, a
    per-op-stem breakdown, and a verdict ("no-collectives" /
    "overlapped" / "exposed", threshold 0.5)."""
    comp_by_thread: dict = {}
    for ev in slices:
        if ev.category == "compute":
            comp_by_thread.setdefault((ev.pid, ev.tid), []).append(
                (ev.start_s, ev.end_s)
            )
    comp_by_thread = {k: _merged(v) for k, v in comp_by_thread.items()}
    total = overlapped = 0.0
    by_op: dict = {}
    for ev in slices:
        if ev.category != "collective":
            continue
        other = _merged([
            iv
            for key, merged in comp_by_thread.items()
            if key != (ev.pid, ev.tid)
            for iv in merged
        ])
        got = _total(_intersect([(ev.start_s, ev.end_s)], other))
        total += ev.duration_s
        overlapped += got
        stem = _TRAILING_ID.sub("", ev.name)
        rec = by_op.setdefault(stem, {"n": 0, "total_s": 0.0,
                                      "overlapped_s": 0.0})
        rec["n"] += 1
        rec["total_s"] += ev.duration_s
        rec["overlapped_s"] += got
    ratio = overlapped / total if total > 0 else None
    if total == 0:
        verdict = "no-collectives"
    else:
        # Epsilon absorbs the us->s float conversion so an exactly-half
        # overlapped trace doesn't flap between verdicts.
        verdict = (
            "overlapped" if ratio >= OVERLAPPED_MIN - 1e-9 else "exposed"
        )
    return {
        "total_s": total,
        "overlapped_s": overlapped,
        "overlap_ratio": ratio,
        "by_op": by_op,
        "verdict": verdict,
    }


def analyze_events(events, step_name: str) -> dict:
    """Full attribution summary over raw ``traceEvents``. Works with zero
    step annotations (``n_steps == 0``; the whole-range bucket still
    answers "where did device time go")."""
    slices = device_slices(events)
    windows = step_windows(events, step_name)
    steps = attribute_steps(slices, windows)
    keys = ("wall_s", "compute_s", "collective_s", "transfer_s",
            "host_gap_s", "device_busy_s")
    totals = {k: sum(s[k] for s in steps) for k in keys}
    mean = (
        {k: totals[k] / len(steps) for k in keys} if steps else None
    )
    if slices:
        lo = min(ev.start_s for ev in slices)
        hi = max(ev.end_s for ev in slices)
        rng = _bucket(slices, lo, hi)
        rng["span_s"] = rng.pop("wall_s")
    else:
        rng = {"span_s": 0.0, "compute_s": 0.0, "collective_s": 0.0,
               "transfer_s": 0.0, "host_gap_s": 0.0, "device_busy_s": 0.0}
    return {
        "step_name": step_name,
        "n_steps": len(steps),
        "n_device_slices": len(slices),
        "steps": steps,
        "totals": totals,
        "per_step_mean": mean,
        "range": rng,
        "collective": measured_overlap(slices),
    }


def analyze_trace_dir(trace_dir: str, step_name: str = "mpi4dl_capture") -> dict:
    """Read + attribute one capture directory. The default ``step_name``
    matches :func:`mpi4dl_tpu.profiling.capture`; pass
    ``"mpi4dl_train_step"`` / ``"mpi4dl_serve_batch"`` to attribute the
    annotations the train/serve dispatch paths emit on their own."""
    summary = analyze_events(read_trace_events(trace_dir), step_name)
    summary["trace_dir"] = trace_dir
    return summary


# -- pipeline lens -------------------------------------------------------------
#
# Per-stage attribution + measured bubble fraction for the scan-over-ticks
# pipeline engine (mpi4dl_tpu/parallel/pipeline.py). The engine compiles
# each tick's stage dispatch to ONE `conditional` with S+1 branch
# computations — branches 0..S-1 are the per-pipe-device stage bodies,
# branch S is the idle branch a device takes on fill/drain ticks. Joining
# the compiled module's branch->instruction closure to the trace's op
# slices gives, per stage: its device seconds (time-weighted) and its
# executed slot count; the idle branch's count IS the bubble, measured on
# the real timeline. This is deliberately slot-counted rather than
# wall-clock-idle: on the CPU test mesh every virtual device multiplexes
# onto one shared XLAEigen pool, so per-device wall idle is unobservable
# (measured: summed busy exceeds n_devices x wall) while branch executions
# are exact. On a real TPU the same join works off the per-device
# timelines.

_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CALLED_RE = re.compile(
    r"(?:to_apply|calls|branch_computations|body|condition)="
    r"(?:%?([\w.\-]+)|\{([^}]*)\})"
)


def _called_computations(instr) -> "list[str]":
    out: list[str] = []
    for m in _CALLED_RE.finditer(instr.attrs):
        if m.group(1):
            out.append(m.group(1))
        else:
            out.extend(p.strip().lstrip("%") for p in m.group(2).split(","))
    return out


def _closure_names(module, comp_name: str) -> "set[str]":
    """All instruction names reachable from ``comp_name`` through
    to_apply/calls/branch/body/condition references (transitive)."""
    seen: set[str] = set()
    names: set[str] = set()
    todo = [comp_name]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        comp = module.computations.get(c)
        if comp is None:
            continue
        for instr in comp.instructions:
            names.add(instr.name)
            todo.extend(_called_computations(instr))
    return names


def stage_switches(hlo_text_or_module, n_stages: int) -> "list[dict]":
    """The pipeline stage switches of a compiled module: ``conditional``
    instructions with exactly ``n_stages + 1`` branch computations. For
    each, the per-branch instruction-name closure with names shared
    between branches of the same conditional dropped — a slice on a
    shared name cannot be attributed to one stage. Branch order is stage
    order (the engine builds the switch as ``[stage_0..stage_{S-1},
    idle]``; the AD transpose and remat replays keep it)."""
    from mpi4dl_tpu.analysis.hlo import parse_hlo_text

    module = (
        hlo_text_or_module
        if hasattr(hlo_text_or_module, "computations")
        else parse_hlo_text(hlo_text_or_module)
    )
    out = []
    for comp in module.computations.values():
        for instr in comp.instructions:
            if instr.opcode != "conditional":
                continue
            m = _BRANCHES_RE.search(instr.attrs)
            if not m:
                continue
            branches = [b.strip().lstrip("%") for b in m.group(1).split(",")]
            if len(branches) != n_stages + 1:
                continue
            closures = [_closure_names(module, b) for b in branches]
            unique = []
            for i, cl in enumerate(closures):
                others: set = set()
                for j, other in enumerate(closures):
                    if j != i:
                        others |= other
                unique.append(cl - others)
            out.append({
                "name": instr.name,
                "branches": branches,
                "unique_names": unique,  # [stage_0..stage_{S-1}, idle]
            })
    return out


def pipeline_attribution(
    events,
    hlo_text_or_module,
    n_stages: int,
    step_name: str = "mpi4dl_capture",
    analytic_bubble: "float | None" = None,
    schedule: "str | None" = None,
) -> dict:
    """Join a pipeline capture to its compiled program's stage switches:
    per-stage device seconds + executed slot counts, the idle branch's
    slot count, and the fleet ``bubble_fraction`` =
    ``idle_slots / (idle_slots + active_slots)`` — for the gated GPipe
    schedule this measures ``(S-1)/(S-1+M)`` on a live run, the number the
    ROADMAP said nothing measured. Raises :class:`TraceError` when the
    module has no ``n_stages + 1``-branch conditional (not a pipeline
    program, or the wrong stage count)."""
    switches = stage_switches(hlo_text_or_module, n_stages)
    if not switches:
        raise TraceError(
            f"compiled module has no conditional with {n_stages + 1} "
            "branches — not a PipelineTrainer program, or n_stages does "
            "not match its pipe depth"
        )
    slices = device_slices(events)
    windows = step_windows(events, step_name)
    if windows:
        lo = min(w[0] for w in windows)
        hi = max(w[1] for w in windows)
    elif slices:
        lo = min(ev.start_s for ev in slices)
        hi = max(ev.end_s for ev in slices)
    else:
        lo = hi = 0.0
    counts: dict = {}
    durs: dict = {}
    permute_s = 0.0
    for ev in slices:
        mid = (ev.start_s + ev.end_s) / 2
        if not (lo <= mid < hi):
            continue
        counts[ev.name] = counts.get(ev.name, 0) + 1
        durs[ev.name] = durs.get(ev.name, 0.0) + ev.duration_s
        if ev.category == "collective" and any(
            m in ev.name for m in _PERMUTE_STEMS
        ):
            permute_s += ev.duration_s

    def branch_count(unique_names) -> int:
        # Every instruction unique to the branch executes exactly once per
        # taken branch; the max absorbs instructions the runtime did not
        # emit slices for (elided/zero-duration thunks undercount).
        return max((counts.get(n, 0) for n in unique_names), default=0)

    def branch_seconds(unique_names) -> float:
        return sum(durs.get(n, 0.0) for n in unique_names)

    per_switch = []
    active_by_stage = [0] * n_stages
    seconds_by_stage = [0.0] * n_stages
    idle_slots = 0
    for sw in switches:
        active = [branch_count(u) for u in sw["unique_names"][:n_stages]]
        idle = branch_count(sw["unique_names"][n_stages])
        for s in range(n_stages):
            active_by_stage[s] += active[s]
            seconds_by_stage[s] += branch_seconds(sw["unique_names"][s])
        idle_slots += idle
        per_switch.append({
            "conditional": sw["name"],
            "active_slots": active,
            "idle_slots": idle,
        })
    active_slots = sum(active_by_stage)
    total_slots = active_slots + idle_slots
    bubble = idle_slots / total_slots if total_slots else None
    # Per-device idle share: each switch runs total/S/n_switches ticks per
    # device (replication-invariant), so device s idled 1 - active_s*S/total
    # of its slots.
    idle_share = [
        (1.0 - active_by_stage[s] * n_stages / total_slots)
        if total_slots else None
        for s in range(n_stages)
    ]
    out = {
        "n_stages": n_stages,
        "schedule": schedule,
        "n_steps": len(windows),
        "n_switches": len(switches),
        "per_switch": per_switch,
        "active_slots_by_stage": active_by_stage,
        "idle_slots": idle_slots,
        "total_slots": total_slots,
        "bubble_fraction": bubble,
        "idle_share_by_stage": idle_share,
        "stage_device_seconds": seconds_by_stage,
        "permute_seconds": permute_s,
    }
    if analytic_bubble is not None:
        out["analytic_bubble_fraction"] = float(analytic_bubble)
    return out


def analyze_pipeline_trace_dir(
    trace_dir: str,
    hlo_text: str,
    n_stages: int,
    step_name: str = "mpi4dl_capture",
    analytic_bubble: "float | None" = None,
    schedule: "str | None" = None,
) -> dict:
    """Read one capture directory and attribute it through the pipeline
    lens (:func:`pipeline_attribution`)."""
    return pipeline_attribution(
        read_trace_events(trace_dir), hlo_text, n_stages,
        step_name=step_name, analytic_bubble=analytic_bubble,
        schedule=schedule,
    )


#: |measured - analytic| beyond ``max(abs, rel * analytic)`` disagrees.
BUBBLE_TOL_ABS = 0.02
BUBBLE_TOL_REL = 0.15


def crosscheck_bubble(
    analytic: float,
    summary: dict,
    tol_abs: float = BUBBLE_TOL_ABS,
    tol_rel: float = BUBBLE_TOL_REL,
) -> "list[Finding]":
    """The schedule model says the bubble is ``(S-1)/(S-1+M)``; the trace
    says what fraction of slots the devices actually idled. Disagreement
    on the same executable is a lint finding (rule
    ``pipeline-bubble-crosscheck``) — the PR-4 static-vs-measured pattern,
    now for pipeline bubbles. ``summary`` is a
    :func:`pipeline_attribution` result."""
    measured = summary.get("bubble_fraction")
    rule = "pipeline-bubble-crosscheck"
    if measured is None:
        return [Finding(rule, "warn",
                        "the capture recorded no stage-switch slots at all "
                        "— wrong program, empty trace, or the idle branch "
                        "was folded away (the bubble is unmeasurable).")]
    if abs(measured - analytic) <= max(tol_abs, tol_rel * analytic):
        return []
    direction = "above" if measured > analytic else "below"
    return [Finding(rule, "warn",
                    f"measured pipeline bubble {measured:.4f} is {direction} "
                    f"the schedule-model {analytic:.4f} beyond tolerance: "
                    "the compiled schedule does not execute the idle "
                    "structure the model predicts (gating regressed, wrong "
                    "parts/stages, or the capture mixed programs).")]


def publish_pipeline_attribution(summary: dict, registry, program: str):
    """Publish one pipeline-lens summary under the cataloged
    ``pipeline_*`` gauges (docs/OBSERVABILITY.md), labeled by ``program``
    so schedule arms coexist in one registry."""
    from mpi4dl_tpu import telemetry

    if summary.get("bubble_fraction") is not None:
        telemetry.declare(registry, "pipeline_bubble_fraction").set(
            summary["bubble_fraction"], program=program
        )
    for s, secs in enumerate(summary.get("stage_device_seconds") or []):
        telemetry.declare(registry, "pipeline_stage_device_seconds").set(
            secs, program=program, stage=str(s)
        )
    if summary.get("img_per_s") is not None:
        telemetry.declare(registry, "pipeline_img_per_s").set(
            summary["img_per_s"], program=program
        )
    return registry


# -- telemetry + static cross-check -------------------------------------------


def publish_attribution(summary: dict, registry, program: str = "capture"):
    """Publish one attribution summary under the cataloged ``trace_*``
    gauges (docs/OBSERVABILITY.md), labeled by ``program`` so train and
    serve captures coexist in one registry. Per-step means when the
    capture had annotated steps, whole-range totals otherwise."""
    from mpi4dl_tpu import telemetry

    src = summary["per_step_mean"] or summary["range"]
    attr = telemetry.declare(registry, "trace_attribution_seconds")
    for cat in CATEGORIES:
        attr.set(src.get(f"{cat}_s", 0.0), program=program, category=cat)
    if summary["per_step_mean"] is not None:
        telemetry.declare(registry, "trace_step_wall_seconds").set(
            summary["per_step_mean"]["wall_s"], program=program
        )
    ratio = summary["collective"]["overlap_ratio"]
    if ratio is not None:
        telemetry.declare(registry, "trace_overlap_ratio").set(
            ratio, program=program
        )
    return registry


def static_overlap_verdict(overlap: dict) -> str:
    """Collapse a static ``Report.overlap`` summary into one verdict:
    "no-collectives", "sync" (collectives but no async start/done pairs —
    the schedule makes no overlap claim), "exposed" (async pairs with
    zero compute between), or "overlapped"."""
    if overlap.get("n_collectives", 0) == 0:
        return "no-collectives"
    if overlap.get("async_pairs", 0) == 0:
        return "sync"
    return "exposed" if overlap.get("zero_overlap") else "overlapped"


def crosscheck_overlap(report, summary: dict) -> "list[Finding]":
    """Static says "should overlap"; the trace says "did". Disagreement
    between the two verdicts on the same executable is a lint finding
    (rule ``trace-overlap-crosscheck``) — the closed loop between
    hlolint's schedule prediction and runtime reality. ``report`` is a
    :class:`mpi4dl_tpu.analysis.report.Report` or any dict carrying its
    ``overlap`` summary."""
    overlap = report["overlap"] if isinstance(report, dict) else report.overlap
    static = static_overlap_verdict(overlap)
    meas = summary["collective"]
    measured = meas["verdict"]
    rule = "trace-overlap-crosscheck"
    if static == "no-collectives" and measured != "no-collectives":
        return [Finding(rule, "warn",
                        f"static analysis saw zero collectives but the trace "
                        f"recorded {meas['total_s'] * 1e3:.3f} ms of "
                        "collective slices: the captured program is not the "
                        "analyzed one, or communication crept in at runtime.")]
    if static != "no-collectives" and measured == "no-collectives":
        return [Finding(rule, "warn",
                        f"static analysis counts "
                        f"{overlap.get('n_collectives')} collectives but the "
                        "trace recorded none: capture too short, wrong "
                        "program, or the runtime elided them.")]
    if static == "overlapped" and measured == "exposed":
        return [Finding(rule, "warn",
                        "static schedule places compute inside every "
                        "collective start->done window, but the measured "
                        f"overlap ratio is {meas['overlap_ratio']:.2f}: the "
                        "communication window is exposed latency at runtime "
                        "(T3/FLUX lost-overlap, invisible to the static "
                        "rule).")]
    if static == "exposed" and measured == "overlapped":
        return [Finding(rule, "info",
                        "static analysis flags zero-overlap collectives but "
                        "the runtime overlapped "
                        f"{meas['overlap_ratio']:.0%} of collective time "
                        "anyway (asynchronous progress outside the schedule).")]
    return []
