"""Gigapixel tiled inference: a halo-correct tile-streaming forward.

The paper's workload is very-high-resolution images, yet the single-chip
forward peaks at what one device's HBM holds (training measured 4096² per
chip; 8192² dies RESOURCE_EXHAUSTED), and the multi-chip sharded path
(serve/sharded.py) needs a mesh. *Inference* under frozen batch statistics
has none of the gradient coupling that killed H-strip training
(docs/PERF.md round 5): every conv/pool/BN/ReLU in the pre-head stack is
spatially LOCAL, so the forward decomposes into overlap-read tiles whose
results stitch exactly. This module serves arbitrarily large images on ONE
chip at bounded memory:

- **Tile margin from partition math.** The overlap each tile must read
  beyond its core is the cumulative receptive-field growth of the
  conv/pool stack up to the head split — the same per-op ``padding ×
  cumulative-stride`` sum the spatial trainer's halo exchange carries
  (``Trainer.halo_shift_count`` counts the permutes; here there is no
  wire, so the "exchange" is an overlapped host-array read). It is
  derived by abstractly tracing the section under
  :func:`mpi4dl_tpu.ops.layers.record_windowed_ops` (``jax.eval_shape``,
  no device work), never hardcoded per model.
- **Exact stitching.** Tile windows are clamped inside the image: an
  interior window edge carries ≥ margin rows of REAL neighbor pixels (the
  conv's own zero padding contaminates at most the margin, which is
  cropped), and a window edge at the image boundary coincides with it, so
  the conv's zero padding there IS the monolithic padding. Every kept
  output element therefore sees exactly the bytes the monolithic forward
  saw — the stitched result is bit-identical wherever the backend rounds
  a convolution independently of the window's pixel count, and agrees at
  the f32 reduction-order boundary (~2e-6) where it does not: the
  installed XLA:CPU blocks a wide 3x3 conv's accumulation by its pixel
  count (tier-1-asserted, ``tests/_tiled_equiv_check.py``; not measured
  on a TPU).
- **One AOT-warmed tile executable.** Interior, edge, corner, and ragged
  tiles all run the SAME fixed ``window × window`` program (clamping
  keeps the shape constant), batched into power-of-two TILE buckets and
  streamed with double-buffered H2D staging: batch *k+1* stages and
  dispatches before batch *k*'s result is harvested, so transfers overlap
  device compute and the live set is bounded at two tile batches — peak
  HBM is the tile executable's, not the image's. The stitched feature map
  (1/stride² of the image) then runs the head once.

Serving surface: :func:`tiled_engine` puts a :class:`TiledPredictor`
behind the PR-13 predictor seam — batcher, EDF scheduler, deadlines,
spans, SLO evaluator, tail watcher all unchanged — with single-image
buckets and its own SLO class (default ``tiled``), so a 60-second
gigapixel request burns its own error budget, never the tight class's.
``python -m mpi4dl_tpu.serve --tiled HxW`` and the fleet worker's
``POST /predict_tiled`` (router/front-door passthrough included) expose
it; ``python -m mpi4dl_tpu.analyze memory-plan --bisect tile`` answers
"what tile size fits this chip" before anything runs, and the
``device_hbm_*`` gauges verify the bounded-memory claim live.

Scope: models whose pre-head section is a plain NHWC conv/pool stack
(every zoo ResNet). The packed activation layout folds image columns into
channels — its extents cannot be re-read as overlapping windows — and is
refused loudly at geometry time.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Sequence

import numpy as np

from mpi4dl_tpu.serve.batching import bucket_for, power_of_two_buckets
from mpi4dl_tpu.serve.engine import ServingEngine

#: Default SLO class of a tiled engine: its own latency objective so the
#: scheduler's burn-rate feedback and the SLO evaluator account gigapixel
#: requests separately from any interactive class.
DEFAULT_TILED_CLASS = "tiled"
DEFAULT_TILED_THRESHOLD_S = 120.0

#: The tiled_* metric names the predictor publishes (all cataloged —
#: declared in one call by :func:`declare_metrics`, the
#: ``fleet.declare_metrics`` pattern, so the catalog==runtime pin stays
#: honest without spawning a tiled engine in the full-stack fixture;
#: live series are exercised by ``tests/test_serve_tiled.py``).
TILED_METRICS = (
    "tiled_tiles_total",
    "tiled_tile_batches_total",
    "tiled_tiles_per_request",
    "tiled_stitch_seconds",
    "tiled_tile_stream_seconds",
)


def declare_metrics(registry) -> None:
    """Declare every tiled_* metric on ``registry`` (names only — the
    predictor's :meth:`TiledPredictor.bind_telemetry` publishes the live
    series on its engine's registry)."""
    from mpi4dl_tpu import telemetry

    for name in TILED_METRICS:
        telemetry.declare(registry, name)


@dataclasses.dataclass(frozen=True)
class TileGeometry:
    """The derived plan of one tiled forward: per-axis core/window tiling
    plus the section's stride/margin facts. ``tiles_h``/``tiles_w`` hold
    ``(core_start, core_len, window_start)`` per tile — every window has
    extent ``window_hw`` (clamped inside the image), cores partition it
    exactly."""

    image_hw: tuple
    tile_hw: tuple          # requested core extent (multiple of stride)
    margin_hw: tuple        # overlap read beyond the core, input px
    stride_hw: tuple        # cumulative section downsampling
    window_hw: tuple        # core + 2*margin, clamped to the image
    feat_hw: tuple          # stitched feature-map extent (pre-head)
    feat_channels: int
    feat_dtype: Any
    split: int              # cells[:split] = section, cells[split:] = head
    ops: tuple              # recorded windowed-op geometry (forensics)
    tiles_h: tuple
    tiles_w: tuple

    @property
    def n_tiles(self) -> int:
        return len(self.tiles_h) * len(self.tiles_w)

    @property
    def grid(self) -> tuple:
        return (len(self.tiles_h), len(self.tiles_w))

    def describe(self) -> dict:
        return {
            "image": list(self.image_hw),
            "tile": list(self.tile_hw),
            "margin": list(self.margin_hw),
            "stride": list(self.stride_hw),
            "window": list(self.window_hw),
            "grid": list(self.grid),
            "tiles_per_request": self.n_tiles,
            "feature_hw": list(self.feat_hw),
            "feature_channels": self.feat_channels,
        }


def _pair(v) -> tuple:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def section_margin(ops, image_hw) -> tuple:
    """Cumulative receptive-field growth of a recorded windowed-op stack,
    in input pixels per dim: ``Σ max(pad, kernel-1-pad) × downsampling``
    over the ops, where downsampling is the op's input extent relative to
    the image (the ``Trainer.halo_shift_count`` partition math without the
    wire). A tile core flanked by this many rows/cols of real neighbor
    data is untouched by the window-edge zero padding after the whole
    stack (the induction the stitch-exactness suite pins)."""
    margin = [0, 0]
    for op in ops:
        if op["kind"] == "packed":
            raise ValueError(
                "tiled inference does not support the packed activation "
                "layout: packed columns fold image W into channels, so "
                "overlap-read windows cannot be sliced from the input — "
                "build the model with layout='nhwc'"
            )
        for d in (0, 1):
            n, h = int(image_hw[d]), int(op["input_hw"][d])
            if h <= 0 or n % h:
                raise ValueError(
                    f"non-uniform downsampling: op input extent {h} does "
                    f"not divide the image extent {n} — tiled inference "
                    "needs stride-aligned section shapes"
                )
            k, p = op["kernel"][d], op["padding"][d]
            margin[d] += max(p, k - 1 - p) * (n // h)
    return tuple(margin)


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def _axis_plan(n: int, tile: int, margin: int) -> tuple:
    """Per-dim tiling: cores ``[i*tile, ...)`` (last one ragged), windows
    of constant extent ``tile + 2*margin`` clamped inside ``[0, n]`` so a
    window edge is either the image edge (conv padding == monolithic
    padding) or ≥ margin rows of real data from its core. Returns
    ``(entries, window)`` with entries ``(core0, core_len, win0)``."""
    win = tile + 2 * margin
    if win >= n:
        return ((0, n, 0),), n
    entries = []
    c0 = 0
    while c0 < n:
        clen = min(tile, n - c0)
        a = min(max(c0 - margin, 0), n - win)
        entries.append((c0, clen, a))
        c0 += clen
    return tuple(entries), win


def tile_geometry(
    cells: Sequence[Any],
    params: Sequence[Any],
    batch_stats,
    example_shape: Sequence[int],
    tile,
    split: "int | None" = None,
    dtype=None,
) -> TileGeometry:
    """Derive the tiled-forward plan for a model: abstractly trace the
    pre-head section (``jax.eval_shape`` — zero device work, works on
    ``ShapeDtypeStruct`` params too, which is what ``analyze memory-plan
    --bisect tile`` feeds it), collect every windowed op's geometry, and
    turn it into margin/stride/tile plans. Raises ``ValueError`` on
    layouts it cannot stitch exactly (packed layout, non-NHWC section
    output, stride-misaligned extents or tile sizes)."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.evaluate import _apply_running
    from mpi4dl_tpu.ops.layers import record_windowed_ops

    cells = tuple(cells)
    split = len(cells) - 1 if split is None else int(split)
    if not 0 < split < len(cells):
        raise ValueError(
            f"split must leave a non-empty section and head, got {split} "
            f"of {len(cells)} cells"
        )
    for i, cell in enumerate(cells):
        pack = getattr(cell, "pack", None)
        packed = (
            any(int(f) != 1 for f in pack)
            if isinstance(pack, (tuple, list))
            else (pack is not None and int(pack) != 1)
        )
        if packed:
            raise ValueError(
                "tiled inference does not support the packed activation "
                f"layout (cell {i} is packed): packed columns fold image "
                "W into channels, so overlap-read windows cannot be "
                "sliced from the input — build the model with "
                "layout='nhwc'"
            )
    h, w, c = (int(d) for d in example_shape)
    dtype = jnp.dtype(dtype if dtype is not None else jnp.float32)

    def sec_fwd(p, s, x):
        return _apply_running(cells[:split], p, s, x)

    xs = jax.ShapeDtypeStruct((1, h, w, c), dtype)
    with record_windowed_ops() as ops:
        feat = jax.eval_shape(
            sec_fwd, list(params[:split]), list(batch_stats[:split]), xs
        )
    if not hasattr(feat, "shape") or len(feat.shape) != 4:
        raise ValueError(
            "tiled inference needs an NHWC section output to stitch; the "
            f"section before cell {split} produced {feat!r} — move the "
            "split to the conv/pool stack's end"
        )
    fh, fw, fc = int(feat.shape[1]), int(feat.shape[2]), int(feat.shape[3])
    if fh <= 0 or fw <= 0 or h % fh or w % fw:
        raise ValueError(
            f"section output {fh}x{fw} does not divide the image {h}x{w} "
            "— tiled inference needs image extents divisible by the "
            "section's cumulative stride"
        )
    sh, sw = h // fh, w // fw
    mh, mw = section_margin(ops, (h, w))
    mh, mw = _round_up(mh, sh), _round_up(mw, sw)
    if tile is None:
        # Default core: a quarter of each extent (16 tiles/request),
        # stride-aligned — callers that care pick their own (or ask
        # `analyze memory-plan --bisect tile` for the largest that fits).
        tile = (max(sh, _round_up(h // 4, sh)), max(sw, _round_up(w // 4, sw)))
    th, tw = _pair(tile)
    if th < sh or tw < sw or th % sh or tw % sw:
        raise ValueError(
            f"tile {th}x{tw} must be a positive multiple of the section "
            f"stride {sh}x{sw}"
        )
    tiles_h, win_h = _axis_plan(h, th, mh)
    tiles_w, win_w = _axis_plan(w, tw, mw)
    return TileGeometry(
        image_hw=(h, w), tile_hw=(th, tw), margin_hw=(mh, mw),
        stride_hw=(sh, sw), window_hw=(win_h, win_w), feat_hw=(fh, fw),
        feat_channels=fc, feat_dtype=np.dtype(feat.dtype),
        split=split, ops=tuple(dict(o) for o in ops),
        tiles_h=tiles_h, tiles_w=tiles_w,
    )


class _TiledExecutable:
    """The compile_bucket handle of one tiled forward: the per-tile-bucket
    section executables plus the head. Duck-types the single executable
    the engine's footprint ledger and hlolint gate expect — both delegate
    to the LARGEST tile-bucket section program, because that is the hot
    loop whose peak bounds a request's memory (the head is recorded as
    its own ledger entry by the predictor)."""

    def __init__(self, tile: dict, head):
        self.tile = dict(tile)
        self.head = head
        self._lint = self.tile[max(self.tile)]

    def as_text(self) -> str:
        return self._lint.as_text()

    def memory_analysis(self):
        return self._lint.memory_analysis()


class TiledPredictor:
    """Compile/stage/run backend that serves one FIXED large example shape
    by streaming overlap-read tiles through a single AOT-warmed section
    executable and stitching exactly (module docstring has the math).

    cells / params / batch_stats: the calibrated plain-twin triple (the
        same artifacts the single-chip engine consumes).
    example_shape: the served ``(H, W, C)`` — the LARGE size; requests
        are validated against it by the engine as usual.
    tile: core tile extent in input px (int or ``(th, tw)``), a multiple
        of the section's cumulative stride. Bigger tiles amortize
        dispatch overhead, smaller ones bound memory —
        ``analyze memory-plan --bisect tile`` computes the largest that
        fits a chip.
    split: section/head cell boundary (default: everything but the last
        cell — the head the model builders emit).
    tile_batch: largest tile bucket; tile buckets are the powers of two
        up to it (``/predict_tiled``'s own buckets, orthogonal to the
        engine's per-IMAGE buckets, which default to 1). Default 1 —
        the EXACT path: every window runs the one batch-1 section
        executable, whose outputs equal the monolithic forward's to
        the bit or to f32 reduction order, as the backend's conv rounds
        (module docstring; tier-1-asserted). Raising it batches windows per
        dispatch (a throughput lever for small tiles), at the repo's
        documented cross-executable boundary: rows computed by a
        batch-b program agree with the batch-1/monolithic program at
        f32 reduction-order tolerance, not bitwise (the same ~1e-7
        boundary as cross-BUCKET rows in the plain engine).
    """

    program = "serve_tiled"
    mesh_shape = (1, 1)
    #: Engine warm-up flag: while True, runs execute normally but are
    #: excluded from the per-request stats/metrics (zeros warm traffic
    #: must not skew the stitch/stream percentiles the reports carry).
    warming = False

    def __init__(
        self,
        cells: Sequence[Any],
        params: Sequence[Any],
        batch_stats,
        example_shape: Sequence[int],
        tile,
        split: "int | None" = None,
        tile_batch: int = 1,
        dtype=None,
    ):
        import jax
        import jax.numpy as jnp

        self.cells = tuple(cells)
        self.example_shape = tuple(int(d) for d in example_shape)
        self.dtype = jnp.dtype(dtype if dtype is not None else jnp.float32)
        self.geometry = tile_geometry(
            self.cells, params, batch_stats, self.example_shape, tile,
            split=split, dtype=self.dtype,
        )
        # The grid is FIXED per engine, so only the tile buckets a
        # request actually dispatches exist: full chunks of the largest
        # bucket plus one padded remainder bucket — at most two compiled
        # shapes, never the whole power-of-two ladder.
        pow2 = power_of_two_buckets(max(1, int(tile_batch)))
        full, rem = divmod(self.geometry.n_tiles, max(pow2))
        used = set()
        if full:
            used.add(max(pow2))
        if rem:
            used.add(bucket_for(rem, pow2))
        self._tile_buckets = tuple(sorted(used))
        self.device = jax.devices()[0]
        split = self.geometry.split
        # Params/stats live on the device once, pre-split so the section
        # and head executables take exactly their own halves.
        self._p_sec = jax.device_put(list(params[:split]), self.device)
        self._s_sec = jax.device_put(list(batch_stats[:split]), self.device)
        self._p_head = jax.device_put(list(params[split:]), self.device)
        self._s_head = jax.device_put(list(batch_stats[split:]), self.device)
        self._np_dtype = np.dtype(self.dtype.name)
        # Per-image-bucket cold-start aggregates (summed over the tile
        # section executables + head compiled for that bucket) — the
        # engine merges them into its own ledger entry for the handle.
        self.compile_timings: "dict[int, dict]" = {}
        # Telemetry bindings (engine seam: bind_telemetry).
        self._ledger = None
        self._m_tiles = self._m_batches = None
        self._m_stitch = self._m_stream = None
        self._lock = threading.Lock()
        self._requests = 0
        self._tiles_total = 0
        self._stitch_s: "list[float]" = []
        self._stream_s: "list[float]" = []
        self.last_run: "dict | None" = None

    # -- engine seam ----------------------------------------------------------

    @property
    def num_devices(self) -> int:
        return 1

    def halo_shifts(self) -> int:
        """One chip exchanges nothing over the wire — the tile overlap is
        an overlapped HOST read, invisible to the permute window."""
        return 0

    def bind_telemetry(self, registry=None, ledger=None, events=None) -> None:
        """Engine-injected observability (called before warm-up): the
        footprint ledger the tile/head executables are recorded into and
        the registry the ``tiled_*`` series publish through. ``events``
        is accepted for symmetry (per-request facts ride the engine's own
        ``serve.request`` span events via ``last_run``)."""
        del events
        self._ledger = ledger
        if registry is not None:
            from mpi4dl_tpu import telemetry

            self._m_tiles = telemetry.declare(registry, "tiled_tiles_total")
            self._m_batches = telemetry.declare(
                registry, "tiled_tile_batches_total"
            )
            self._m_stitch = telemetry.declare(
                registry, "tiled_stitch_seconds"
            )
            self._m_stream = telemetry.declare(
                registry, "tiled_tile_stream_seconds"
            )
            telemetry.declare(registry, "tiled_tiles_per_request").set(
                self.geometry.n_tiles
            )

    def compile_bucket(self, bucket: int):
        """AOT-compile the used tile-bucket section executables + the
        head for one image bucket and record every executable's
        compile-time footprint: the handle itself lands in the engine's
        ledger as ``serve_tiled[bucket]`` (the TILE executable's peak —
        the number ``memory_guard`` and ``analyze memory-plan`` gate
        on), the head as its own ``serve_tiled_head`` entry (its
        footprint scales with image/stride², the residual term of the
        bounded-memory claim). First-exec setup is paid by the engine's
        own warm-up pass, which streams the SAME buckets this grid
        dispatches — no extra zeros runs here, so a gigapixel engine's
        warm-up costs one pass, not two."""
        from mpi4dl_tpu.evaluate import aot_compile_tiled_predict

        g = self.geometry
        timings: dict = {}
        exe = aot_compile_tiled_predict(
            self.cells,
            list(self._p_sec) + list(self._p_head),
            list(self._s_sec) + list(self._s_head),
            g.split,
            (*g.window_hw, self.example_shape[2]),
            (*g.feat_hw, g.feat_channels),
            self._tile_buckets,
            dtype=self.dtype,
            feature_dtype=g.feat_dtype,
            timings=timings,
        )
        handle = _TiledExecutable(exe["tile"], exe["head"])
        if self._ledger is not None:
            for tb, compiled in sorted(handle.tile.items()):
                self._ledger.record_compiled(
                    "serve_tiled_tile", compiled, bucket=tb,
                    window=list(g.window_hw), **timings.get(tb, {}),
                )
            self._ledger.record_compiled(
                "serve_tiled_head", handle.head,
                feature_hw=list(g.feat_hw), **timings.get("head", {}),
            )
        # The engine's own ledger entry for this image bucket gets the
        # SUMMED trace/compile seconds of every executable compiled here
        # (the cost a cold respawn pays for this bucket; the per-
        # executable split lives in the serve_tiled_* entries above).
        # rollup=True keeps the sums out of the compile_seconds gauge and
        # the analyzer's totals — the serve_tiled_* entries already
        # carry every second once.
        self.compile_timings[int(bucket)] = {
            "trace_s": round(
                sum(t.get("trace_s", 0.0) for t in timings.values()), 6
            ),
            "compile_s": round(
                sum(t.get("compile_s", 0.0) for t in timings.values()), 6
            ),
            "rollup": True,
        }
        del bucket  # every image bucket shares the tile/head executables
        return handle

    def stage(self, batch):
        """No-op by design: the full image must NEVER land on the device —
        :meth:`run` slices overlap-read windows from the host array and
        stages only those (double-buffered)."""
        return np.asarray(batch, self._np_dtype)

    def run(self, compiled, staged):
        staged = np.asarray(staged, self._np_dtype)
        outs = [self._run_one(compiled, staged[i])
                for i in range(staged.shape[0])]
        return np.stack(outs)

    def expectations(self):
        """Algebra-derived: the tiled zero-collective delta composes to
        the single-chip gate — any collective in a tile executable is a
        resharding regression."""
        from mpi4dl_tpu.analysis.expectations import compose

        return compose(self.collective_deltas())

    def collective_deltas(self):
        """One tiled zero-collective section delta
        (:mod:`mpi4dl_tpu.analysis.expectations`)."""
        from mpi4dl_tpu.analysis.expectations import tiled_delta

        return (tiled_delta(),)

    def platform(self) -> str:
        return self.device.platform

    def limit_device(self):
        return self.device

    def param_tree(self):
        """``(params, batch_stats)`` live trees, for the numerics
        sentinel's integrity checksum (telemetry/canary.py). Rejoins
        the section/head split in cell order, so the checksum matches a
        single-chip replica of the same checkpoint."""
        return (
            list(self._p_sec) + list(self._p_head),
            list(self._s_sec) + list(self._s_head),
        )

    def reload_params(self, params) -> None:
        """Replace the live parameter lists, re-split at the geometry
        boundary. The tile/head executables take params as call
        arguments (not closure captures), so the swap takes effect on
        the next dispatch."""
        import jax

        split = self.geometry.split
        self._p_sec = jax.device_put(list(params[:split]), self.device)
        self._p_head = jax.device_put(list(params[split:]), self.device)

    # -- the tile-streaming hot loop ------------------------------------------

    def _run_one(self, handle: _TiledExecutable, img: np.ndarray):
        import jax

        g = self.geometry
        wh, ww = g.window_hw
        sh, sw = g.stride_hw
        c = img.shape[-1]
        max_b = max(self._tile_buckets)
        jobs = [(th, tw) for th in g.tiles_h for tw in g.tiles_w]
        feat = np.empty((*g.feat_hw, g.feat_channels), g.feat_dtype)
        t0 = time.perf_counter()
        stitch_s = 0.0
        batch_counts: "dict[int, int]" = {}
        pending = None  # the double-buffer: one (group, device_out) in flight
        for i in range(0, len(jobs), max_b):
            group = jobs[i: i + max_b]
            bucket = bucket_for(len(group), self._tile_buckets)
            batch = (
                np.zeros((bucket, wh, ww, c), self._np_dtype)
                if len(group) < bucket
                else np.empty((bucket, wh, ww, c), self._np_dtype)
            )
            for j, ((_, _, ha), (_, _, wa)) in enumerate(group):
                batch[j] = img[ha: ha + wh, wa: wa + ww, :]
            staged = jax.device_put(batch, self.device)    # async H2D
            out = handle.tile[bucket](self._p_sec, self._s_sec, staged)
            batch_counts[bucket] = batch_counts.get(bucket, 0) + 1
            if pending is not None:
                # Harvest batch k while batch k+1 transfers/computes —
                # the live set never exceeds two staged tile batches.
                stitch_s += self._harvest(feat, *pending)
            pending = (group, out)
        if pending is not None:
            stitch_s += self._harvest(feat, *pending)
        t1 = time.perf_counter()
        hstaged = jax.device_put(
            np.ascontiguousarray(feat[None]), self.device
        )
        logits = np.asarray(
            handle.head(self._p_head, self._s_head, hstaged)
        )[0]
        t2 = time.perf_counter()
        stream_s = (t1 - t0) - stitch_s
        stitch_s += t2 - t1  # stitch = assembly copies + the head forward
        facts = {
            "tiles": len(jobs),
            "tile_batches": sum(batch_counts.values()),
            "stitch_s": stitch_s,
            "tile_stream_s": stream_s,
        }
        if self.warming:
            return logits
        with self._lock:
            self._requests += 1
            self._tiles_total += len(jobs)
            self._stitch_s.append(stitch_s)
            self._stream_s.append(stream_s)
            if len(self._stitch_s) > 2048:
                del self._stitch_s[:1024]
                del self._stream_s[:1024]
            self.last_run = facts
        if self._m_tiles is not None:
            self._m_tiles.inc(len(jobs))
            for b, n in batch_counts.items():
                self._m_batches.inc(n, bucket=b)
            self._m_stitch.observe(stitch_s)
            self._m_stream.observe(stream_s)
        return logits

    def _harvest(self, feat: np.ndarray, group, out) -> float:
        """Block on one tile batch and stitch its cores into the feature
        map; returns the host-side assembly time (the D2H wait is stream
        time, not stitch time)."""
        g = self.geometry
        sh, sw = g.stride_hw
        arr = np.asarray(out)  # blocks until the device batch finishes
        t = time.perf_counter()
        for j, ((hc0, hlen, ha), (wc0, wlen, wa)) in enumerate(group):
            fh0, fw0 = hc0 // sh, wc0 // sw
            oh0, ow0 = (hc0 - ha) // sh, (wc0 - wa) // sw
            nh, nw = hlen // sh, wlen // sw
            feat[fh0: fh0 + nh, fw0: fw0 + nw] = (
                arr[j, oh0: oh0 + nh, ow0: ow0 + nw]
            )
        return time.perf_counter() - t

    # -- observability --------------------------------------------------------

    def run_stats(self) -> dict:
        """Cumulative tiled-run facts (``engine.stats()["tiled"]``, the
        loadgen/CLI report's ``tiled`` block): geometry, request/tile
        totals, and per-request stitch/stream latency percentiles."""
        from mpi4dl_tpu.profiling import percentiles

        with self._lock:
            out = {
                **self.geometry.describe(),
                "requests": self._requests,
                "tiles_total": self._tiles_total,
                "stitch_s": percentiles(list(self._stitch_s)),
                "tile_stream_s": percentiles(list(self._stream_s)),
            }
        return out


def tiled_engine(
    cells: Sequence[Any],
    params: Sequence[Any],
    batch_stats,
    example_shape: Sequence[int],
    tile,
    split: "int | None" = None,
    tile_batch: int = 1,
    dtype=None,
    slo_class: "str | None" = DEFAULT_TILED_CLASS,
    slo_threshold_s: "float | None" = DEFAULT_TILED_THRESHOLD_S,
    **engine_kw,
) -> ServingEngine:
    """A :class:`ServingEngine` over a :class:`TiledPredictor`: the
    ``/predict_tiled`` surface. Image buckets default to ``(1,)`` (one
    gigapixel image per dispatch — batching them would multiply the
    first request's latency and the live set for no occupancy win; the
    TILE buckets inside the predictor are where batching pays), the
    default deadline stretches to minutes, and the engine declares its
    own SLO class (default ``tiled`` with a latency objective) so the
    PR-11 scheduler accounts this traffic's burn separately from any
    tight interactive class."""
    predictor = TiledPredictor(
        cells, params, batch_stats, example_shape, tile,
        split=split, tile_batch=tile_batch, dtype=dtype,
    )
    engine_kw.setdefault("buckets", (1,))
    engine_kw.setdefault("default_deadline_s", 600.0)
    if slo_class and engine_kw.get("slo_classes") is None:
        from mpi4dl_tpu.serve.scheduler import SLOClass

        engine_kw["slo_classes"] = (
            SLOClass(slo_class, latency_threshold_s=slo_threshold_s),
        )
    return ServingEngine.from_predictor(predictor, **engine_kw)


def tiled_engine_from_checkpoint(
    path_or_dir: str, tile, **engine_kw
) -> ServingEngine:
    """Tiled engine from a self-describing checkpoint path alone — the
    gigapixel twin of ``ServingEngine.from_checkpoint``: same rebuild, but
    the forward streams tiles instead of requiring the whole image (plus
    its activations) to fit the chip."""
    from mpi4dl_tpu.checkpoint import rebuild_from_checkpoint

    cells, state, stats, meta = rebuild_from_checkpoint(path_or_dir)
    if stats is None:
        raise ValueError(
            "checkpoint has no batch_stats.msgpack — calibrate with "
            "evaluate.collect_batch_stats and save_checkpoint(..., "
            "batch_stats=...) before serving"
        )
    spec = meta["model"]
    shape = (spec["image_size"], spec["image_size"], spec.get("channels", 3))
    engine_kw.setdefault("dtype", spec.get("dtype", "float32"))
    return tiled_engine(
        cells, state.params, stats, example_shape=shape, tile=tile,
        **engine_kw,
    )


def synthetic_tiled_engine(
    image_size: int,
    tile,
    depth: int = 8,
    num_classes: int = 10,
    calib_size: "int | None" = None,
    calib_batches: int = 1,
    seed: int = 0,
    **engine_kw,
) -> ServingEngine:
    """Zero-artifact tiled engine: a ResNet-v1 (depth 6n+2) with a
    global-average-pool head served at ``image_size``. Because the pooled
    head input is size-independent (the pool covers the whole feature
    map), parameters are initialized and BN-calibrated at a SMALL twin of
    the model (``calib_size``, default 64 px) — identical parameter tree,
    no need to run a full-image forward just to mint synthetic weights —
    then served at the large size through the tile stream."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.evaluate import collect_batch_stats
    from mpi4dl_tpu.models.resnet import get_resnet_v1
    from mpi4dl_tpu.parallel.partition import init_cells

    size = int(image_size)
    small = int(calib_size) if calib_size else min(64, size)
    # pool_kernel = size // 4 pools the WHOLE post-stack feature map in
    # both twins, so the head's Dense sees the same flattened width and
    # the two builds share one parameter structure.
    cells = get_resnet_v1(
        depth=depth, num_classes=num_classes, pool_kernel=size // 4
    )
    twin = get_resnet_v1(
        depth=depth, num_classes=num_classes, pool_kernel=small // 4
    )
    rng = np.random.default_rng(seed)
    params = init_cells(
        twin, jax.random.PRNGKey(seed), jnp.zeros((1, small, small, 3))
    )
    cal = [
        jnp.asarray(rng.standard_normal((4, small, small, 3)), jnp.float32)
        for _ in range(max(1, int(calib_batches)))
    ]
    stats = collect_batch_stats(twin, params, cal)
    return tiled_engine(
        cells, params, stats, example_shape=(size, size, 3), tile=tile,
        **engine_kw,
    )
