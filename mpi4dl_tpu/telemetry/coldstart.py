"""Cold-start observability: executable fingerprints + recovery phases.

Every elasticity number in this repo is compile-bound — replica recovery
is ~7s cold vs 0.05s warm-pool and every scale-out pays full AOT warm-up
per bucket per replica — but until this module that cost was one scalar
(``fleet_recovery_seconds``) and a per-bucket wall time. Three pieces
turn it into an instrument:

- **Executable fingerprints** (:func:`executable_fingerprint` /
  :func:`fingerprint_of`): a deterministic content key over the
  canonicalized lowered HLO text + jax/jaxlib versions + backend + mesh
  shape — the identity the ROADMAP's fleet-shared artifact store will be
  keyed by. Computed at every :class:`~mpi4dl_tpu.telemetry.memory.
  FootprintLedger` record site and stored in ledger entries/``dump()``.
- **Phase vocabulary** (:data:`RECOVERY_PHASES`,
  :func:`recovery_phase_decomposition`): the fixed spawn → import →
  construct → compile → warm → ready decomposition the worker stamps
  into its ready handshake and the supervisor publishes as
  ``fleet_recovery_phase_seconds{phase=}`` — durations, not timestamps,
  so the arithmetic is clock-skew-safe across processes.
- **Cache honesty** (:func:`publish_cache_status`): the
  ``compile_cache_enabled`` gauge, 0 when
  :func:`mpi4dl_tpu.utils.enable_compilation_cache` failed or never ran —
  fleet runs stop silently paying compiles they believe are cached.

``python -m mpi4dl_tpu.analyze coldstart``
(:mod:`mpi4dl_tpu.analysis.coldstart`) joins the ledger dumps,
``elastic.restart`` events, and recovery phases into the ranked
"top executables by compile seconds" manifest the compile-cache service
will warm. jax is imported lazily here — the module itself stays
importable from pure-JSON analysis paths.
"""

from __future__ import annotations

import hashlib
import re

#: The fixed recovery-phase vocabulary. Worker-side durations cover
#: import → ready; ``spawn`` is the supervisor-side residual (process
#: fork + argv parse + anything before the worker's first stamp), so the
#: published phases always sum to ``fleet_recovery_seconds``. A warm-pool
#: promotion is pure ``ready`` (routing flip + health handshake): its
#: compile/warm phases are honestly zero — that IS the warm pool's claim.
RECOVERY_PHASES = ("spawn", "import", "construct", "compile", "warm", "ready")

# Volatile decoration stripped before hashing: per-op `metadata={...}`
# carries source_file absolute paths (checkout-dependent) and MLIR
# `loc(...)` / `#loc` lines carry the same — neither changes what the
# executable computes.
_METADATA_RE = re.compile(r",?\s*metadata=\{[^{}]*\}")
_LOC_RE = re.compile(r"\s*loc\([^()]*\)")
_LOC_LINE_RE = re.compile(r"^#loc\d*\s*=.*$", re.MULTILINE)
_WS_RE = re.compile(r"\s+")


def canonicalize_hlo(text: str) -> str:
    """Canonical form of lowered/compiled HLO or StableHLO text: volatile
    decoration (per-op ``metadata={...}``, MLIR ``loc(...)`` references
    and ``#loc`` lines) dropped, whitespace collapsed — two renderings of
    the same program hash equal, two different programs don't."""
    text = _METADATA_RE.sub("", text)
    text = _LOC_LINE_RE.sub("", text)
    text = _LOC_RE.sub("", text)
    return _WS_RE.sub(" ", text).strip()


def executable_fingerprint(
    hlo_text: str,
    *,
    backend: str = "",
    mesh_shape=None,
    in_shardings=None,
    out_shardings=None,
    donated=None,
    jax_version: "str | None" = None,
    jaxlib_version: "str | None" = None,
) -> str:
    """Deterministic content key of one executable: sha256 over the
    canonicalized program text plus everything that changes what XLA
    would emit for it — jax/jaxlib versions, backend, mesh shape, in/out
    shardings, donation. Same config in two processes → same key;
    perturb px/bucket/mesh/dtype → distinct key. This is the identity
    the fleet-shared artifact store (ROADMAP zero-cold-start item) keys
    serialized executables by."""
    if jax_version is None or jaxlib_version is None:
        jv, lv = _versions()
        jax_version = jax_version if jax_version is not None else jv
        jaxlib_version = jaxlib_version if jaxlib_version is not None else lv
    h = hashlib.sha256()
    for part in (
        canonicalize_hlo(hlo_text),
        jax_version,
        jaxlib_version,
        backend or "",
        repr(tuple(mesh_shape) if mesh_shape is not None else None),
        repr(in_shardings),
        repr(out_shardings),
        repr(donated),
    ):
        h.update(part.encode())
        h.update(b"\x00")
    return "xf" + h.hexdigest()[:16]


def _versions() -> "tuple[str, str]":
    try:
        import jax

        jv = jax.__version__
    except Exception:  # noqa: BLE001 — fingerprinting is best-effort
        jv = ""
    try:
        import jaxlib

        lv = getattr(jaxlib, "__version__", "")
    except Exception:  # noqa: BLE001
        lv = ""
    return jv, lv


def fingerprint_of(obj, *, mesh_shape=None, **config) -> "str | None":
    """Best-effort fingerprint of a ``jax.stages.Lowered`` or
    ``Compiled``: hashes ``obj.as_text()`` (prefer fingerprinting the
    LOWERED object — its pre-optimization text is the key a respawning
    worker can compute *before* paying the compile). Returns None when
    the object cannot render text; recording must never fail warm-up."""
    try:
        text = obj.as_text()
    except Exception:  # noqa: BLE001 — e.g. an executable without text
        return None
    try:
        import jax

        backend = jax.default_backend()
    except Exception:  # noqa: BLE001
        backend = ""
    return executable_fingerprint(
        text, backend=backend, mesh_shape=mesh_shape, **config
    )


def recovery_phase_decomposition(
    recovery_s: float, worker_phases: "dict | None"
) -> "dict[str, float]":
    """Fold a worker's self-reported phase DURATIONS into the fixed
    :data:`RECOVERY_PHASES` vocabulary: unknown keys are dropped, every
    phase is present (zeros for unused ones — so the published series
    stays honest across cold/promotion alternation instead of leaving a
    stale compile number standing), and ``spawn`` absorbs the residual
    ``recovery_s - sum(worker phases)`` clamped at 0. The result always
    sums to ``recovery_s`` (to within the clamp)."""
    phases = {p: 0.0 for p in RECOVERY_PHASES}
    total = 0.0
    for p, v in (worker_phases or {}).items():
        if p in phases and p != "spawn" and isinstance(v, (int, float)):
            phases[p] = float(v)
            total += float(v)
    phases["spawn"] = max(0.0, float(recovery_s) - total)
    return phases


def publish_cache_status(registry, attempt: bool = True) -> dict:
    """Publish the cataloged ``compile_cache_enabled`` gauge (1 = the
    persistent compilation cache is on, 0 = off) and return the status
    dict with the reason. With ``attempt=True`` (default) this first calls
    :func:`mpi4dl_tpu.utils.enable_compilation_cache`, which records its
    own decision — so a
    serving engine's scrape is honest about cache state without every
    entry point having to remember the call."""
    from mpi4dl_tpu import telemetry
    from mpi4dl_tpu.utils import (
        compilation_cache_status,
        enable_compilation_cache,
    )

    if attempt:
        try:
            enable_compilation_cache()
        except Exception:  # noqa: BLE001 — status reflects the failure
            pass
    status = compilation_cache_status()
    telemetry.declare(registry, "compile_cache_enabled").set(
        1.0 if status.get("enabled") else 0.0
    )
    return status
