"""Memory observability: live HBM gauges, footprint ledger, OOM forensics.

The paper's entire reason for 5D parallelism is that very-high-resolution
images don't fit in device memory, yet until this module the stack was
blind on exactly that axis: the bench walk died at 8192² with an unparsed
``RESOURCE_EXHAUSTED`` string and nothing scraped a single byte of HBM.
Three pieces (docs/OBSERVABILITY.md "Memory"):

- :class:`MemoryMonitor` — samples ``jax.Device.memory_stats()`` per
  device at the SLO-evaluator cadence into the cataloged
  ``device_hbm_used_bytes`` / ``device_hbm_limit_bytes`` /
  ``device_hbm_headroom_ratio`` gauges. Backends that report no stats
  (the CPU simulation) degrade to *absent-not-wrong*: the gauge names
  stay declared, no series is ever published, nothing trips, and the
  sampling thread retires itself after the first absent sample.
- :class:`FootprintLedger` — records
  :func:`mpi4dl_tpu.analysis.memory.memory_summary` peaks for every
  executable the process compiles (each warmed serving bucket, the train
  step, eval programs) under ``serve_bucket_peak_hbm_bytes{bucket=}`` /
  ``program_peak_hbm_bytes{program=}``, and keeps the full breakdown for
  ``engine.stats()`` / ``/debugz`` / the feasibility planner's artifact
  mode.
- **OOM forensics** — :func:`parse_resource_exhausted` turns XLA's
  RESOURCE_EXHAUSTED breakdown (the message carries the full HBM table —
  docs/PERF.md round 4 learned this the hard way after three rounds of
  truncating it) into a structured record naming the memory space,
  used/limit/exceeded bytes, and the largest program allocations with
  their padding expansion; :func:`emit_oom_report` wraps it as a
  schema-valid ``oom.report`` JSONL event into the event log, the
  flight ring (+ optional dump), and the ``oom_reports_total`` counter.
"""

from __future__ import annotations

import json
import re
import threading
import time

# -- size parsing -------------------------------------------------------------

# XLA renders sizes in binary units ("18.95G" == 18.95 GiB) — the same
# convention its allocation dumps and docs/PERF.md round 4 use.
_UNIT = {"": 1, "B": 1, "K": 2**10, "M": 2**20, "G": 2**30, "T": 2**40,
         "P": 2**50}
_SIZE_RE = re.compile(r"^([\d.]+)\s*([KMGTP]?)(?:i?B)?$")


def parse_size(text: str) -> "int | None":
    """``"18.95G"`` / ``"288.00M"`` / ``"276.0K"`` / ``"123456"`` →
    bytes (binary units, XLA's convention); None when unparseable."""
    m = _SIZE_RE.match(str(text).strip())
    if not m:
        return None
    try:
        return int(float(m.group(1)) * _UNIT[m.group(2)])
    except (ValueError, OverflowError):
        return None


# -- OOM detection + parsing --------------------------------------------------

OOM_SIGNATURES = (
    "RESOURCE_EXHAUSTED",
    "ResourceExhausted",
    "Ran out of memory",
    "Out of memory",
)


def exception_chain_text(exc) -> str:
    """str(exc) plus every chained ``__cause__``/``__context__`` message
    — the HBM table can sit in a wrapped cause while the outer message
    says only that the compile died (bench.py's lesson, ADVICE r4)."""
    if isinstance(exc, str):
        return exc
    parts, seen, todo = [], set(), [exc]
    while todo:
        e = todo.pop()
        if e is None or id(e) in seen:
            continue
        seen.add(id(e))
        parts.append(str(e))
        todo.extend((e.__cause__, e.__context__))
    return "\n".join(parts)


def is_oom_error(exc_or_msg) -> bool:
    """True when the exception (whole chain) or message carries an XLA
    memory-exhaustion signature."""
    text = exception_chain_text(exc_or_msg)
    return any(sig in text for sig in OOM_SIGNATURES)


_SPACE_RE = re.compile(r"Ran out of memory in memory space (\w+)")
_USED_RE = re.compile(
    r"Used\s+([\d.]+[KMGTP]?i?B?)\s+of\s+([\d.]+[KMGTP]?i?B?)"
)
_EXCEEDED_RE = re.compile(r"Exceeded \w+ capacity by\s+([\d.]+[KMGTP]?i?B?)")
_PROGRAM_RE = re.compile(r"Program \w+ requirement\s+([\d.]+[KMGTP]?i?B?)")
_TOTAL_RE = re.compile(r"Total \w+ usage\s*>=\s*([\d.]+[KMGTP]?i?B?)")
_ALLOC_RE = re.compile(
    r"^\s*(\d+)\.\s+Size:\s+(\S+)\s*\n(.*?)(?:^\s*=====|\Z)",
    re.M | re.S,
)
_ALLOC_FIELDS = {
    "operator": re.compile(r"Operator:\s*(.+)"),
    "shape": re.compile(r"Shape:\s*(\S+)"),
    "unpadded": re.compile(r"Unpadded size:\s*(\S+)"),
    "padding": re.compile(
        r"Extra memory due to padding:\s*(\S+)\s*\(([\d.]+)x expansion\)"
    ),
    "xla_label": re.compile(r"XLA label:\s*(.+)"),
    "allocation_type": re.compile(r"Allocation type:\s*(.+)"),
}
_ALLOCATOR_RE = re.compile(
    r"(?:Out of memory allocating|Failed to allocate(?: request for)?)\s+"
    r"([\d.]+(?:[KMGTP]i?B?)?)\s*(?:bytes)?"
)


def _parse_allocations(text: str) -> list:
    out = []
    for m in _ALLOC_RE.finditer(text):
        entry = {
            "rank": int(m.group(1)),
            "size_bytes": parse_size(m.group(2)),
        }
        block = m.group(3)
        f = _ALLOC_FIELDS
        mm = f["shape"].search(block)
        if mm:
            # Drop the layout/tiling suffix: f32[1,3072,3072,16]{2,1,3,0:...}
            entry["shape"] = mm.group(1).split("{")[0]
        mm = f["unpadded"].search(block)
        if mm:
            entry["unpadded_bytes"] = parse_size(mm.group(1))
        mm = f["padding"].search(block)
        if mm:
            entry["padding_bytes"] = parse_size(mm.group(1))
            entry["padding_expansion"] = float(mm.group(2))
        mm = f["operator"].search(block)
        if mm:
            entry["operator"] = mm.group(1).strip()[:200]
        mm = f["xla_label"].search(block)
        if mm:
            entry["xla_label"] = mm.group(1).strip()[:200]
        mm = f["allocation_type"].search(block)
        if mm:
            entry["allocation_type"] = mm.group(1).strip()
        out.append(entry)
    out.sort(key=lambda e: e["rank"])
    return out


def parse_resource_exhausted(msg: str) -> "dict | None":
    """Structured parse of an XLA RESOURCE_EXHAUSTED message.

    Returns None when the text carries no OOM signature at all; else a
    dict with ``kind`` one of:

    - ``"hbm_oom"`` — the full compile-time HBM table ("Ran out of
      memory in memory space hbm", docs/PERF.md round 4): used/limit/
      exceeded/program bytes plus ``largest_allocations`` (size, shape,
      unpadded size, padding expansion, XLA label).
    - ``"allocator_oom"`` — a runtime allocator failure ("Out of memory
      allocating N bytes") with ``requested_bytes``.
    - ``"unclassified"`` — the signature without a parseable breakdown
      (e.g. the bare "TPU backend error (ResourceExhausted)" string the
      bench walk used to record raw).
    """
    if not is_oom_error(msg):
        return None
    text = str(msg)
    out: dict = {"kind": "unclassified", "memory_space": None}
    m = _SPACE_RE.search(text)
    if m:
        out["memory_space"] = m.group(1)
    m = _USED_RE.search(text)
    if m:
        out["used_bytes"] = parse_size(m.group(1))
        out["limit_bytes"] = parse_size(m.group(2))
    m = _EXCEEDED_RE.search(text)
    if m:
        out["exceeded_bytes"] = parse_size(m.group(1))
    m = _PROGRAM_RE.search(text)
    if m:
        out["program_bytes"] = parse_size(m.group(1))
    m = _TOTAL_RE.search(text)
    if m:
        out["total_bytes"] = parse_size(m.group(1))
    allocs = _parse_allocations(text)
    if allocs:
        out["largest_allocations"] = allocs
    if out.get("memory_space") or (
        out.get("used_bytes") is not None and allocs
    ):
        out["kind"] = "hbm_oom"
    else:
        m = _ALLOCATOR_RE.search(text)
        if m:
            req = parse_size(m.group(1))
            if req is not None:
                out["kind"] = "allocator_oom"
                out["requested_bytes"] = req
    return out


def largest_buffer(parsed: "dict | None") -> "str | None":
    """One-line name of the biggest program allocation in a parsed OOM —
    what a postmortem reader wants first ("the 4.50G padded copy of
    f32[1,3072,3072,16]")."""
    if not parsed:
        return None
    allocs = parsed.get("largest_allocations")
    if not allocs:
        return None
    a = allocs[0]
    bits = []
    if a.get("size_bytes") is not None:
        bits.append(f"{a['size_bytes'] / 2**30:.2f}G")
    if a.get("shape"):
        bits.append(a["shape"])
    if a.get("padding_expansion"):
        bits.append(f"{a['padding_expansion']:g}x padding")
    if a.get("xla_label"):
        bits.append(a["xla_label"].split(" = ")[0])
    return " ".join(bits) or None


def oom_report(
    exc_or_msg, program: str, bucket: "int | None" = None,
    attrs: "dict | None" = None,
) -> dict:
    """Build one schema-valid ``oom.report`` JSONL event: the structured
    parse alongside the raw message (truncated), naming the program,
    bucket, and largest buffer."""
    raw = exception_chain_text(exc_or_msg)
    parsed = parse_resource_exhausted(raw)
    ev_attrs = {
        "program": program,
        "parsed": parsed,
        "largest_buffer": largest_buffer(parsed),
        "raw": raw[:4000],
    }
    if bucket is not None:
        ev_attrs["bucket"] = int(bucket)
    if attrs:
        ev_attrs.update(attrs)
    from mpi4dl_tpu.telemetry.jsonl import validate_event

    return validate_event({
        "ts": time.time(), "kind": "event", "name": "oom.report",
        "attrs": ev_attrs,
    })


def emit_oom_report(
    exc_or_msg,
    program: str,
    bucket: "int | None" = None,
    registry=None,
    events=None,
    flight=None,
    dump: bool = False,
    attrs: "dict | None" = None,
) -> dict:
    """Build and fan out one ``oom.report``: JSONL event log (when
    enabled), flight ring (+ a ``reason="oom"`` dump when asked),
    ``oom_reports_total{program=}``. Returns the event. Never raises —
    forensics must not mask the OOM it is reporting."""
    ev = oom_report(exc_or_msg, program, bucket=bucket, attrs=attrs)
    try:
        if registry is not None:
            from mpi4dl_tpu import telemetry

            telemetry.declare(registry, "oom_reports_total").inc(
                program=program
            )
        if flight is not None and getattr(flight, "enabled", False):
            flight.record(ev)
            if dump:
                flight.dump(reason="oom")
        if events is not None and getattr(events, "enabled", False):
            events.write(ev)
    except Exception:  # noqa: BLE001 — postmortem is best-effort
        pass
    return ev


# -- live device memory -------------------------------------------------------


def device_memory_stats(device) -> "dict | None":
    """Normalized ``{"used_bytes", "limit_bytes", "peak_bytes"}`` from
    ``jax.Device.memory_stats()``; None when the backend reports nothing
    (the CPU simulation returns None — absence, not zeros)."""
    try:
        stats = device.memory_stats()
    except Exception:  # noqa: BLE001 — backend-dependent, absence is fine
        return None
    if not stats:
        return None
    used = stats.get("bytes_in_use")
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if used is None and limit is None:
        return None
    out: dict = {}
    if used is not None:
        out["used_bytes"] = int(used)
    if limit is not None:
        out["limit_bytes"] = int(limit)
    peak = stats.get("peak_bytes_in_use")
    if peak is not None:
        out["peak_bytes"] = int(peak)
    return out


def device_memory_limit(device=None) -> "int | None":
    """The device's HBM capacity in bytes, or None when the backend
    cannot report it (CPU) — the feasibility planner's default limit."""
    if device is None:
        import jax

        device = jax.devices()[0]
    stats = device_memory_stats(device)
    return None if stats is None else stats.get("limit_bytes")


class MemoryMonitor:
    """Samples per-device HBM occupancy into cataloged gauges.

    registry: gauges are DECLARED at construction (the catalog pin sees
        the names on every backend) but only SET when a device actually
        reports stats — absent-not-wrong on the CPU simulation.
    devices: explicit device list (tests pass stubs); None resolves
        ``jax.devices()`` lazily at the first sample.
    interval_s: sampling cadence of the daemon thread — the engine wires
        the SLO evaluator's cadence here so the headroom gauges move in
        step with the alert evaluation reading them.
    """

    def __init__(
        self, registry, devices=None, interval_s: float = 1.0,
    ):
        from mpi4dl_tpu import telemetry

        self._m_used = telemetry.declare(registry, "device_hbm_used_bytes")
        self._m_limit = telemetry.declare(registry, "device_hbm_limit_bytes")
        self._m_headroom = telemetry.declare(
            registry, "device_hbm_headroom_ratio"
        )
        self._devices = list(devices) if devices is not None else None
        self.interval_s = float(interval_s)
        self.supported: "bool | None" = None  # unknown until first sample
        self.last: "dict | None" = None
        self._stop_evt = threading.Event()
        self._thread: "threading.Thread | None" = None

    def sample_once(self) -> "dict | None":
        """One sample over every device; returns the per-device stats
        dict, or None when no device reports (then no gauge is set and
        nothing downstream can trip on a fabricated zero)."""
        if self._devices is None:
            import jax

            self._devices = list(jax.devices())
        out = {}
        for d in self._devices:
            stats = device_memory_stats(d)
            if stats is None:
                continue
            label = f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"
            used, limit = stats.get("used_bytes"), stats.get("limit_bytes")
            if used is not None:
                self._m_used.set(used, device=label)
            if limit:
                self._m_limit.set(limit, device=label)
                if used is not None:
                    stats["headroom_ratio"] = (limit - used) / limit
                    self._m_headroom.set(
                        stats["headroom_ratio"], device=label
                    )
            out[label] = stats
        self.supported = bool(out)
        self.last = out or None
        return out or None

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._run, name="mpi4dl-memory-monitor", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        while not self._stop_evt.is_set():
            try:
                if self.sample_once() is None:
                    # Backend reports nothing (CPU): retire the thread —
                    # absence costs zero steady-state work, and a process
                    # never grows HBM support mid-life.
                    return
            except Exception:  # noqa: BLE001 — sampling must never kill
                return  # the host process's sidecar thread
            if self._stop_evt.wait(self.interval_s):
                return

    def close(self) -> None:
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def state(self) -> dict:
        """The ``/debugz`` payload."""
        return {"supported": self.supported, "devices": self.last}


# -- footprint ledger ---------------------------------------------------------


class FootprintLedger:
    """Per-program predicted-peak ledger over compiled executables.

    Every entry is :func:`mpi4dl_tpu.analysis.memory.memory_summary` of
    one ``jax.stages.Compiled`` — the buffer-assignment totals the
    allocator will actually request, available on every backend (CPU
    included), recorded at compile time so the answer to "what will this
    program hold" exists *before* the first execution. Bucket entries
    publish ``serve_bucket_peak_hbm_bytes{bucket=}``; everything else
    publishes ``program_peak_hbm_bytes{program=}``.

    Cold-start additions (:mod:`mpi4dl_tpu.telemetry.coldstart`): every
    entry carries the executable's content ``fingerprint`` (the artifact-
    store key — computed here, at the only place every AOT compile in
    the codebase already passes through), and entries recorded with
    ``trace_s`` / ``compile_s`` / ``warm_s`` phase durations accumulate
    into the cataloged ``compile_seconds{program, phase}`` gauge.
    ``dump()`` is the input of ``python -m mpi4dl_tpu.analyze coldstart``.
    """

    def __init__(self, registry=None):
        self._entries: "dict[str, dict]" = {}
        self._lock = threading.Lock()
        self._m_bucket = self._m_program = self._m_compile = None
        if registry is not None:
            from mpi4dl_tpu import telemetry

            # Declared up front so the catalog pin sees the names even
            # before the first record lands.
            self._m_bucket = telemetry.declare(
                registry, "serve_bucket_peak_hbm_bytes"
            )
            self._m_program = telemetry.declare(
                registry, "program_peak_hbm_bytes"
            )
            self._m_compile = telemetry.declare(registry, "compile_seconds")

    def record_compiled(
        self, program: str, compiled, bucket: "int | None" = None, **extra
    ) -> dict:
        """Record one compiled executable's footprint; returns the entry
        (``peak_bytes`` None when the backend cannot report it — the
        entry still exists, the gauges stay absent)."""
        from mpi4dl_tpu.analysis.memory import memory_summary

        entry: dict = {"program": program, "ts": time.time(), **extra}
        if bucket is not None:
            entry["bucket"] = int(bucket)
        summary = memory_summary(compiled)
        if summary:
            entry.update(summary)
        else:
            entry["peak_bytes"] = None
        if entry.get("fingerprint") is None:
            # Callers that timed the lowering pass the (preferable)
            # pre-optimization fingerprint in extra; fall back to the
            # optimized text so every entry still has an identity.
            from mpi4dl_tpu.telemetry.coldstart import fingerprint_of

            entry["fingerprint"] = fingerprint_of(
                compiled, mesh_shape=extra.get("mesh_shape")
            )
        key = program if bucket is None else f"{program}[{int(bucket)}]"
        with self._lock:
            self._entries[key] = entry
        peak = entry.get("peak_bytes")
        if peak is not None:
            if bucket is not None and self._m_bucket is not None:
                self._m_bucket.set(peak, bucket=int(bucket))
            elif bucket is None and self._m_program is not None:
                self._m_program.set(peak, program=program)
        self._publish_phases(program, entry)
        return entry

    def record_lowered(
        self, program: str, fn, *args, bucket: "int | None" = None, **extra
    ) -> dict:
        """Lower + compile a jitted callable on the given (abstract or
        concrete) arguments WITHOUT executing it, then record — a
        warm-cache no-op for programs the process already compiled
        (XLA memoizes by program identity). The trace/compile split is
        timed here and the fingerprint taken from the LOWERED text (the
        key a respawning worker could compute before paying the
        compile)."""
        from mpi4dl_tpu.telemetry.coldstart import fingerprint_of

        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        extra.setdefault("trace_s", round(t1 - t0, 6))
        extra.setdefault("compile_s", round(t2 - t1, 6))
        extra.setdefault(
            "fingerprint",
            fingerprint_of(lowered, mesh_shape=extra.get("mesh_shape")),
        )
        return self.record_compiled(program, compiled, bucket=bucket, **extra)

    def annotate(
        self, program: str, bucket: "int | None" = None, **extra
    ) -> "dict | None":
        """Merge late-arriving facts (the first-execute ``warm_s``, which
        only exists after the engine's zeros run) into an existing entry;
        phase durations publish into ``compile_seconds`` like recorded
        ones. No-op on an unknown key."""
        key = program if bucket is None else f"{program}[{int(bucket)}]"
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            entry.update(extra)
            entry = dict(entry)
        self._publish_phases(program, extra)
        return entry

    def _publish_phases(self, program: str, fields: dict) -> None:
        """Accumulate any ``{trace,compile,warm}_s`` durations present in
        ``fields`` into ``compile_seconds{program, phase}`` — cumulative
        per program across buckets, the shape ``analyze coldstart`` and a
        compile-cache A/B read. Entries marked ``rollup`` (the tiled
        engine's per-image-bucket aggregate of its serve_tiled_* entries)
        are skipped — their seconds are already published once by the
        fine-grained entries they sum."""
        if self._m_compile is None or fields.get("rollup"):
            return
        for phase in ("trace", "compile", "warm"):
            v = fields.get(f"{phase}_s")
            if isinstance(v, (int, float)):
                self._m_compile.inc(float(v), program=program, phase=phase)

    def entries(self) -> "list[dict]":
        with self._lock:
            return [dict(v) for _, v in sorted(self._entries.items())]

    def get(self, program: str, bucket: "int | None" = None) -> "dict | None":
        key = program if bucket is None else f"{program}[{int(bucket)}]"
        with self._lock:
            e = self._entries.get(key)
        return dict(e) if e else None

    def summary(self) -> dict:
        """JSON-serializable view (``engine.stats()['memory']['programs']``,
        ``/debugz``, and the planner's ``--ledger`` artifact input)."""
        return {"entries": self.entries()}

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.summary(), f, indent=2)
            f.write("\n")
        return path
