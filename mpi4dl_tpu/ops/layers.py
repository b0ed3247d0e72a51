"""Core layer library (plain + spatially-partitioned variants).

One set of modules covers what the reference implements three times over
(``src/torchgems/spatial.py`` ``conv_spatial``/``halo_exchange_layer``/``Pool``
plus the plain torch layers): every module takes a ``spatial`` flag, and when
set, runs on a local image tile inside ``shard_map`` using
:func:`mpi4dl_tpu.parallel.halo.halo_exchange` for boundary data.

Layout is NHWC throughout (TPU-native; the reference is NCHW).

Semantics parity notes:

- ``Conv2d(spatial=True)`` == ref ``conv_spatial`` (``spatial.py:25-1029``):
  zero-pad via neighbor halos then VALID conv; stride-2 requires
  power-of-two tiles, matching ref's asserts (``train_spatial.py:25-58``).
- ``TrainBatchNorm`` normalizes with current-batch statistics (training
  mode). With ``reduce_axes=()`` statistics are tile-local — exactly the
  reference's per-tile BN behavior under SP. With mesh axis names, stats are
  ``pmean``-ed across tiles (cross-tile BN) which restores bit-parity with a
  single-device golden model; this is what the spatial model builders use by
  default. Eval-time stats come from a *calibration pass* rather than EMA
  buffers mutated inside the train step (which stays pure/donated): see
  :func:`bn_stats_mode` and :mod:`mpi4dl_tpu.evaluate`. (The reference has
  no eval path at all — its BN buffers are written but never read.)
- ``Pool(spatial=True)`` == ref ``Pool`` (``spatial.py:1416-1509``): halo
  exchange of ``padding`` rows/cols, then VALID pooling.
- ``HaloExchange`` == ref ``halo_exchange_layer`` (``spatial.py:1032-1413``),
  the building block of the D2 fused-halo design.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.config import AXIS_TILE_H, AXIS_TILE_W
from mpi4dl_tpu.ops.fastconv import FastConv, conv_scope
from mpi4dl_tpu.parallel.halo import halo_exchange, zero_boundary_halo

TILE_AXES = (AXIS_TILE_H, AXIS_TILE_W)


def _pair(v) -> tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def conv_overlap_impl() -> str:
    """Spatial windowed-op decomposition selector: ``"monolithic"``
    (default — one VALID op over the whole halo-extended tile) or
    ``"decomposed"`` (interior op with NO data dependency on the halo
    ppermutes + thin boundary-strip ops consuming the exchanged halo,
    stitched into the identical output — see :func:`overlap_decompose`).
    ``MPI4DL_TPU_CONV_OVERLAP`` sets the process default; the ``overlap=``
    field on :class:`Conv2d` / :class:`Pool` overrides per layer."""
    import os

    impl = os.environ.get("MPI4DL_TPU_CONV_OVERLAP", "monolithic")
    impl = {
        "0": "monolithic", "off": "monolithic",
        "1": "decomposed", "on": "decomposed",
    }.get(impl, impl)
    if impl not in ("monolithic", "decomposed"):
        raise ValueError(
            "MPI4DL_TPU_CONV_OVERLAP must be monolithic|decomposed "
            f"(or 0/1/off/on), got {impl!r}"
        )
    return impl


# Trace-time recorders of PLAIN (non-spatial) windowed-op geometry — the
# count_halo_shifts pattern applied to receptive-field math instead of
# permute counting: tracing a model section under record_windowed_ops()
# (e.g. with jax.eval_shape — no device work) yields every conv/pool's
# kernel/stride/padding and input extent in call order, which is exactly
# the partition-math input the tiled-inference margin derives from
# (serve/tiled.py: margin = cumulative receptive-field growth, the
# single-device analogue of the spatial halo the exchange ops carry).
_WINDOWED_OP_RECORDERS: "list[list]" = []


@contextlib.contextmanager
def record_windowed_ops():
    """Record plain windowed-op geometry issued while tracing the
    enclosed region. Yields a list of dicts (kind/kernel/strides/
    padding/input_hw, in call order); packed-layout ops record
    ``kind="packed"`` so consumers that cannot reason about the packed
    column layout can refuse loudly instead of mis-stitching."""
    box: list = []
    _WINDOWED_OP_RECORDERS.append(box)
    try:
        yield box
    finally:
        _WINDOWED_OP_RECORDERS.remove(box)


def _record_windowed_op(kind, x, kh, kw, sh, sw, ph, pw, **extra) -> None:
    if not _WINDOWED_OP_RECORDERS:
        return
    rec = {
        "kind": kind,
        "kernel": (int(kh), int(kw)),
        "strides": (int(sh), int(sw)),
        "padding": (int(ph), int(pw)),
        "input_hw": (int(x.shape[1]), int(x.shape[2])),
        **extra,
    }
    for box in _WINDOWED_OP_RECORDERS:
        box.append(rec)


def _strip_bounds(n: int, k: int, s: int, p: int) -> tuple[int, int, int]:
    """Per-dim split of a spatial op's output rows into halo-dependent
    boundary strips and a halo-free interior.

    A VALID windowed op over the halo-extended tile produces ``n // s``
    output rows (post-trim); output row ``i`` consumes input rows
    ``[i*s - p, i*s - p + k - 1]`` of the LOCAL tile. Rows whose window
    stays inside ``[0, n)`` need no neighbor data. Returns
    ``(t_lo, t_hi, n_out)``: the count of output rows needing the
    low-side / high-side halo, and the trimmed output extent."""
    n_out = n // s
    t_lo = min(n_out, -(-p // s))  # first interior row: ceil(p/s)
    hi_int = (n - k + p) // s      # last row with i*s + k-1 - p <= n-1
    t_hi = min(n_out, max(0, n_out - 1 - hi_int))
    return t_lo, t_hi, n_out


def overlap_decompose(x, xe, op, kh, kw, sh, sw, ph, pw):
    """Compute ``op(xe)[:, :H//sh, :W//sw]`` as an interior application on
    the un-exchanged tile plus thin boundary-strip applications on the
    halo-extended tile — exact output stitching, different dataflow.

    ``op`` is any position-independent VALID windowed op with strides
    ``(sh, sw)`` and window ``(kh, kw)`` (a conv, a pool). The interior
    call reads ``x`` alone, so it has NO data dependency on the
    ``lax.ppermute`` chain that produced ``xe`` and XLA's scheduler is
    free to run it concurrently with the exchange (the T3/FLUX
    interior/boundary overlap decomposition, arXiv:2401.16677 /
    2406.06858). The boundary strips — at most ``ceil(p/s)`` output
    rows/cols per side — consume the halo once it arrives. Every output
    window sees exactly the bytes the monolithic op saw (boundary fill
    included, since the strips slice ``xe`` itself), so the stitched
    result is window-for-window identical.

    Returns the stitched ``[B, H//sh, W//sw, C']`` array, or ``None``
    when the tile is too small to have a non-empty interior in both dims
    (caller falls back to the monolithic path)."""
    b, h, w, c = x.shape
    tt, tb, ho = _strip_bounds(h, kh, sh, ph)
    tl, tr, wo = _strip_bounds(w, kw, sw, pw)
    if tt + tb >= ho or tl + tr >= wo or (tt + tb + tl + tr) == 0:
        return None
    n_ih, n_iw = ho - tt - tb, wo - tl - tr
    r0, c0 = tt * sh - ph, tl * sw - pw
    y_int = op(x[
        :,
        r0 : r0 + (n_ih - 1) * sh + kh,
        c0 : c0 + (n_iw - 1) * sw + kw,
        :,
    ])
    # Middle band: [left strip | interior | right strip] over the interior
    # rows; the side strips read xe rows aligned with the interior ones.
    mid = y_int
    if tl:
        y_l = op(xe[
            :, tt * sh : (ho - tb - 1) * sh + kh, : (tl - 1) * sw + kw, :
        ])
        mid = jnp.concatenate([y_l[:, :n_ih, :tl, :], mid], axis=2)
    if tr:
        y_r = op(xe[
            :, tt * sh : (ho - tb - 1) * sh + kh, (wo - tr) * sw :, :
        ])
        mid = jnp.concatenate([mid, y_r[:, :n_ih, :tr, :]], axis=2)
    parts = []
    if tt:
        y_top = op(xe[:, : (tt - 1) * sh + kh, :, :])
        parts.append(y_top[:, :tt, :wo, :])
    parts.append(mid)
    if tb:
        y_bot = op(xe[:, (ho - tb) * sh :, :, :])
        parts.append(y_bot[:, :tb, :wo, :])
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _check_window_coverage(kh, kw, sh, sw, ph, pw):
    """A spatially-partitioned windowed op is only exact when the halo
    (== padding) covers the window overlap beyond the stride: windows that
    straddle a tile boundary need ``k - s`` rows/cols of neighbor data and the
    exchange provides ``2*p``. The reference enforces the pool flavor of this
    with asserts (``spatial.py:1445-1464``); without the check the stitched
    output silently drops cross-boundary windows."""
    if kh - sh > 2 * ph or kw - sw > 2 * pw:
        raise ValueError(
            f"spatial window op needs padding >= (kernel - stride)/2 per dim "
            f"to cover tile-boundary windows; got kernel=({kh},{kw}) "
            f"strides=({sh},{sw}) padding=({ph},{pw})"
        )


# --- BN statistics mode -----------------------------------------------------
# Trace-time switch read by TrainBatchNorm/PackedTrainBatchNorm. "batch"
# (the default) declares NO extra variables, so the train step's params-only
# plumbing is untouched. "collect" accumulates exact pooled statistics into
# a mutable "batch_stats" collection (a calibration pass — cf. BN
# re-estimation practice); "running" normalizes with frozen {mean, var} from
# that collection (inference). A plain global rather than a module field so
# no model builder, cell class, or trainer needs a new knob; each
# mode-specific callable is traced exactly once under its own mode
# (mpi4dl_tpu/evaluate.py), so jit caching never crosses modes.
_BN_MODE = ["batch"]


def current_bn_mode() -> str:
    return _BN_MODE[0]


@contextlib.contextmanager
def bn_stats_mode(mode: str):
    """Trace the enclosed model application in the given BN mode
    ("batch" | "collect" | "running"). See module docstring."""
    if mode not in ("batch", "collect", "running"):
        raise ValueError(f"bn mode must be batch|collect|running, got {mode!r}")
    prev = _BN_MODE[0]
    _BN_MODE[0] = mode
    try:
        yield
    finally:
        _BN_MODE[0] = prev


def bn_moments(x):
    """Per-channel mean and mean of squares over every axis but the last,
    accumulated in float32."""
    red = tuple(range(x.ndim - 1))
    n = math.prod(x.shape[a] for a in red)
    mean = jnp.sum(x, red, dtype=jnp.float32) / n
    mean_sq = jnp.sum(jnp.square(x.astype(jnp.float32)), red) / n
    return mean, mean_sq


class TrainBatchNorm(nn.Module):
    """Batch normalization using current-batch statistics.

    reduce_axes: mesh axis names to average statistics over (cross-tile BN
    under spatial partitioning). Empty → local statistics (torch
    ``BatchNorm2d`` training-mode parity per device/tile).

    Under ``bn_stats_mode("collect")`` the (cross-tile-reduced) per-batch
    moments are additionally summed into a ``batch_stats`` collection;
    under ``bn_stats_mode("running")`` frozen ``{mean, var}`` stats from
    that collection replace the batch statistics (eval / inference).
    """

    eps: float = 1e-5
    reduce_axes: tuple[str, ...] = ()
    interior: tuple[int, int] = (0, 0)  # (halo_h, halo_w) rows/cols to EXCLUDE
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones_init(), (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (c,), jnp.float32)
        with jax.named_scope("mpi4dl_batchnorm"):
            if current_bn_mode() == "running":
                mean = self.variable(
                    "batch_stats", "mean", jnp.zeros, (c,), jnp.float32
                ).value
                var = self.variable(
                    "batch_stats", "var", jnp.ones, (c,), jnp.float32
                ).value
                w = (lax.rsqrt(var + self.eps) * scale).astype(x.dtype)
                b = (bias - mean * lax.rsqrt(var + self.eps) * scale).astype(x.dtype)
                return x * w + b
            # D2 fused-halo tiles carry `interior` rows/cols of neighbor data;
            # excluding them from the statistics makes cross-tile (pmean) stats
            # bit-identical to the plain model's — a correctness refinement over
            # the reference, which lets halo pixels skew per-tile BN.
            ih, iw = self.interior
            stat_src = x
            if ih:
                stat_src = stat_src[:, ih:-ih, :, :]
            if iw:
                stat_src = stat_src[:, :, iw:-iw, :]
            # Statistics in f32 with the upcast fused into the reductions and
            # the squaring AFTER the upcast (E[x^2]-E[x]^2 cancels
            # catastrophically if x^2 is rounded to bf16 first). The
            # normalize below stays in the input dtype, which profiling showed
            # otherwise costs ~12% of a bf16 train step in converts alone.
            mean, mean_sq = bn_moments(stat_src)
            if self.reduce_axes:
                mean = lax.pmean(mean, self.reduce_axes)
                mean_sq = lax.pmean(mean_sq, self.reduce_axes)
            if current_bn_mode() == "collect":
                _accumulate_bn_stats(self, mean, mean_sq)
            var = mean_sq - jnp.square(mean)
            w = (lax.rsqrt(var + self.eps) * scale).astype(x.dtype)
            b = (bias - mean * lax.rsqrt(var + self.eps) * scale).astype(x.dtype)
            return x * w + b


def _accumulate_bn_stats(mod: nn.Module, mean, mean_sq) -> None:
    """Sum this batch's (cross-tile-reduced) moments into the module's
    ``batch_stats`` collection. Equal-size calibration batches make the
    averaged moments EXACT pooled statistics (mean of per-batch E[x] and
    E[x²] over equal counts = pooled E[x] / E[x²]) — no EMA decay error."""
    c = mean.shape
    cnt = mod.variable("batch_stats", "count", jnp.zeros, (), jnp.float32)
    ms = mod.variable("batch_stats", "mean_sum", jnp.zeros, c, jnp.float32)
    mq = mod.variable("batch_stats", "mean_sq_sum", jnp.zeros, c, jnp.float32)
    cnt.value = cnt.value + 1.0
    ms.value = ms.value + mean
    mq.value = mq.value + mean_sq


class Conv2d(nn.Module):
    """2-D convolution, optionally spatially partitioned.

    Plain mode: symmetric zero padding ``padding`` (default (k-1)//2, torch
    style), stride ``strides``.

    Spatial mode (ref ``conv_spatial.forward`` ``spatial.py:1019-1029``):
    halo-exchange ``padding`` rows/cols from neighbor tiles, VALID conv on the
    extended tile, trim to ``H_local/stride`` outputs (exact equivalence with
    the global padded conv when tile sizes divide by the stride — the
    power-of-two constraint the reference asserts).

    ``exchange=False`` (with ``spatial=True``) gives the D2 "shrink" conv: no
    exchange, VALID conv on an input that already carries a wide halo — the
    output halo shrinks by (k-1)/2 (ref ``resnet_spatial_d2.py``).

    ``overlap``: ``"monolithic"`` | ``"decomposed"`` | None (None reads
    ``MPI4DL_TPU_CONV_OVERLAP``). The decomposed impl splits the exchange
    form into an interior conv with no halo dependency plus boundary-strip
    convs (:func:`overlap_decompose`) so XLA can hide the
    collective-permutes behind the interior MXU work; outputs are
    window-for-window identical and the permute inventory is unchanged
    (``halo_exchange`` is still called exactly once). NHWC only — the
    packed layout keeps the monolithic exchange.
    """

    features: int
    kernel_size: Any = 3
    strides: Any = 1
    padding: Any = None  # int/pair; None → (k-1)//2
    use_bias: bool = True
    spatial: bool = False
    exchange: bool = True
    pack: tuple[int, int] = (1, 1)  # (pack_in, pack_out); (1,1) = NHWC
    overlap: "str | None" = None  # None → MPI4DL_TPU_CONV_OVERLAP
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.strides)
        if self.padding is None:
            ph, pw = (kh - 1) // 2, (kw - 1) // 2
        else:
            ph, pw = _pair(self.padding)

        with jax.named_scope(conv_scope(kh, kw)):
            if self.pack != (1, 1):
                # Persistently-packed activation layout (ops/packed.py): the
                # input is [B, H, W/pack_in, pack_in*C]; emit packed too.
                # Spatial mode halo-exchanges whole packed columns (see
                # conv2d_packed) — the D1 per-op exchange form only; the D2
                # shrink form (exchange=False) has no packed variant.
                if self.spatial and not self.exchange:
                    raise NotImplementedError(
                        "packed layout has no D2 (pre-fetched halo) conv form"
                    )
                if self.spatial:
                    _check_window_coverage(kh, kw, sh, sw, ph, pw)
                # Packed columns fold W into C: the recorded extents cannot be
                # interpreted as image rows/cols, so geometry consumers refuse.
                _record_windowed_op("packed", x, kh, kw, sh, sw, ph, pw)
                from mpi4dl_tpu.ops.packed import PackedConv

                return PackedConv(
                    features=self.features,
                    kernel_size=(kh, kw),
                    pack_in=self.pack[0],
                    pack_out=self.pack[1],
                    strides=(sh, sw),
                    padding=((ph, ph), (pw, pw)),
                    use_bias=self.use_bias,
                    spatial=self.spatial,
                    dtype=self.dtype,
                    name="conv",
                )(x)

            conv = FastConv(
                features=self.features,
                kernel_size=(kh, kw),
                strides=(sh, sw),
                padding="VALID" if self.spatial else ((ph, ph), (pw, pw)),
                use_bias=self.use_bias,
                dtype=self.dtype,
                name="conv",
            )

            if not self.spatial:
                _record_windowed_op("conv", x, kh, kw, sh, sw, ph, pw)
                return conv(x)

            if self.exchange:
                _check_window_coverage(kh, kw, sh, sw, ph, pw)
                h_loc, w_loc = x.shape[1], x.shape[2]
                xe = halo_exchange(x, ph, pw, AXIS_TILE_H, AXIS_TILE_W)
                impl = self.overlap if self.overlap is not None else (
                    conv_overlap_impl()
                )
                if impl not in ("monolithic", "decomposed"):
                    raise ValueError(
                        f"overlap must be monolithic|decomposed, got {impl!r}"
                    )
                if impl == "decomposed" and (ph or pw):
                    # Interior conv reads the UN-exchanged tile: no data
                    # dependency on the halo ppermutes, so the scheduler can
                    # overlap them; boundary strips consume xe. Flax binds all
                    # calls to the one "conv" submodule, so the param tree is
                    # identical to the monolithic form.
                    y = overlap_decompose(x, xe, conv, kh, kw, sh, sw, ph, pw)
                    if y is not None:
                        return y
                # Trim to this tile's share of the global output grid. The first
                # VALID output aligns with the global grid because tile sizes are
                # multiples of the stride (power-of-two asserts, config.validate).
                return conv(xe)[:, : h_loc // sh, : w_loc // sw, :]

            # D2 shrink conv: input already carries a wide halo; VALID conv eats
            # (k-1) of it per dim. Strided shrink convs are handled by the D2
            # builder's halo-size formulas.
            return conv(x)


def max_pool_s1_valid(x, kh: int, kw: int):
    """Stride-1 VALID max pool as a tree of shifted ``jnp.maximum``s.

    Numerically identical forward to ``lax.reduce_window(max)``, but the
    backward lowers to selects + pads instead of ``select_and_scatter`` —
    measured 17% of the AmoebaNet train step on TPU (docs/PERF.md round 3);
    the genotype runs a 3×3 s1 max pool in every cell.

    Gradient tie-breaking is impl-consistent **per backend**, not globally:
    on CPU (and wherever the Pallas gate declines) every model path (plain,
    spatial, D2) uses the tree backward (maximum-chain subgradients), so
    same-backend golden comparisons are impl-consistent, like the
    reference's CUDA pooling is with itself. On TPU, shapes the one-pass
    Pallas backward admits dispatch to :mod:`mpi4dl_tpu.ops.pool_pallas`
    instead (identical forward values; first-max-wins backward — the
    ``select_and_scatter`` tie rule). Cross-backend gradient comparisons on
    tie-heavy data (e.g. bf16) must therefore run with
    ``MPI4DL_TPU_POOL_PALLAS=off``; the tree stays the CPU/test path and
    the fallback.
    """
    from mpi4dl_tpu.ops import pool_pallas

    if pool_pallas.dispatchable(x, kh, kw, 0, 0):
        return pool_pallas.max_pool(x, kh, kw, 0, 0)
    h, w = x.shape[1], x.shape[2]
    # Separable: max over rows, then cols (associativity makes the forward
    # identical to the 2-D window) — kh+kw maximum ops instead of kh*kw, and
    # the backward's select/accumulate chain shrinks proportionally.
    y = None
    for u in range(kh):
        s = lax.slice_in_dim(x, u, u + h - kh + 1, axis=1)
        y = s if y is None else jnp.maximum(y, s)
    x, y = y, None
    for v in range(kw):
        s = lax.slice_in_dim(x, v, v + w - kw + 1, axis=2)
        y = s if y is None else jnp.maximum(y, s)
    return y


class Pool(nn.Module):
    """Max/avg pooling, optionally with halo exchange (ref ``Pool``,
    ``spatial.py:1416-1509``).

    The reference asserts halo_len == padding and square kernels
    (``spatial.py:1445-1464``); we support rectangular but keep the same
    halo == padding rule.
    """

    kind: str  # "max" | "avg"
    kernel_size: Any = 2
    strides: Any = None  # None → kernel_size (torch default)
    padding: Any = 0
    spatial: bool = False
    count_include_pad: bool = True  # torch AvgPool2d default; AmoebaNet uses False
    overlap: "str | None" = None  # None → MPI4DL_TPU_CONV_OVERLAP

    @nn.compact
    def __call__(self, x):
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.strides if self.strides is not None else (kh, kw))
        ph, pw = _pair(self.padding)
        h_loc, w_loc = x.shape[1], x.shape[2]

        with jax.named_scope("mpi4dl_pool"):
            if self.spatial:
                # Applies to the padding==0 case too (e.g. kernel 3 stride 2
                # padding 0 would silently drop cross-boundary windows).
                _check_window_coverage(kh, kw, sh, sw, ph, pw)
            if self.spatial and (ph or pw):
                fill = float("-inf") if self.kind == "max" else 0.0
                if self.kind == "avg" and not self.count_include_pad:
                    # Monolithic only: the mask-ratio form below couples the
                    # numerator and divisor pools to one exchanged layout; the
                    # overlap decomposition covers the fill-value forms.
                    # Exact distributed count_include_pad=False: average = ratio
                    # of two sum-pools. The divisor pool runs on a validity mask
                    # built LOCALLY from tile position (ones, zeroed on the
                    # outside-image ring of global-boundary tiles) — no second
                    # exchange needed; boundary windows then divide by the true
                    # (unpadded) element count at any tile position.
                    xe = halo_exchange(x, ph, pw, AXIS_TILE_H, AXIS_TILE_W)
                    ones = zero_boundary_halo(
                        jnp.ones_like(xe), ph, pw, AXIS_TILE_H, AXIS_TILE_W
                    )
                    num = lax.reduce_window(
                        xe, 0.0, lax.add, (1, kh, kw, 1), (1, sh, sw, 1), "valid"
                    )
                    den = lax.reduce_window(
                        ones, 0.0, lax.add, (1, kh, kw, 1), (1, sh, sw, 1), "valid"
                    )
                    y = num / den
                    return y[:, : h_loc // sh, : w_loc // sw, :]
                exchanged = True
                pad = ((0, 0), (0, 0))
            else:
                exchanged = False
                pad = ((ph, ph), (pw, pw))

            def apply_pool(t, pad):
                if self.kind == "max":
                    if (sh, sw) == (1, 1):
                        # Stride-1: shifted-maximum decomposition (cheap
                        # backward; see max_pool_s1_valid). -inf edge pad ==
                        # torch MaxPool2d. Strided pools deliberately stay on
                        # reduce_window: slicing the s1 maxima by the stride is
                        # forward-identical but measured a 22% END-TO-END
                        # REGRESSION on AmoebaNet@1024 (6.37 -> 4.94 img/s) —
                        # the full-resolution maximum tree + its full-res
                        # backward select chain costs far more than the
                        # select_and_scatter it removes (docs/PERF.md round 3).
                        if pad != ((0, 0), (0, 0)):
                            t = lax.pad(
                                t,
                                jnp.asarray(float("-inf"), t.dtype),
                                ((0, 0, 0), (*pad[0], 0), (*pad[1], 0), (0, 0, 0)),
                            )
                        return max_pool_s1_valid(t, kh, kw)
                    return nn.max_pool(t, (kh, kw), strides=(sh, sw), padding=pad)
                if self.kind == "avg":
                    return nn.avg_pool(
                        t,
                        (kh, kw),
                        strides=(sh, sw),
                        padding=pad,
                        count_include_pad=self.count_include_pad,
                    )
                raise ValueError(f"unknown pool kind {self.kind!r}")

            if not exchanged:
                _record_windowed_op(
                    "pool", x, kh, kw, sh, sw, ph, pw,
                    pool_kind=self.kind,
                    count_include_pad=self.count_include_pad,
                )
                return apply_pool(x, pad)

            xe = halo_exchange(x, ph, pw, AXIS_TILE_H, AXIS_TILE_W, fill_value=fill)
            impl = self.overlap if self.overlap is not None else (
                conv_overlap_impl()
            )
            if impl not in ("monolithic", "decomposed"):
                raise ValueError(
                    f"overlap must be monolithic|decomposed, got {impl!r}"
                )
            if impl == "decomposed":
                # Same interior/boundary split as the spatial conv: the
                # interior pool needs no neighbor data (windows that touch the
                # halo — fill included — live in the boundary strips, which
                # slice xe and so see the exact monolithic bytes).
                y = overlap_decompose(
                    x, xe, lambda t: apply_pool(t, ((0, 0), (0, 0))),
                    kh, kw, sh, sw, ph, pw,
                )
                if y is not None:
                    return y
            y = apply_pool(xe, ((0, 0), (0, 0)))
            return y[:, : h_loc // sh, : w_loc // sw, :]


class HaloExchange(nn.Module):
    """Standalone halo-exchange layer (ref ``halo_exchange_layer``,
    ``spatial.py:1032-1413``): pad the tile with ``halo_len`` rows/cols of
    neighbor data and return it. Used by the D2 fused-halo design to amortize
    one wide exchange over several shrink convs."""

    halo_len: Any = 1

    @nn.compact
    def __call__(self, x):
        ph, pw = _pair(self.halo_len)
        return halo_exchange(x, ph, pw, AXIS_TILE_H, AXIS_TILE_W)


class Identity(nn.Module):
    """Pass-through module. Used as the `none` genotype op (stride 1) and as
    the plain twin of :class:`HaloExchange` (on the full image a halo
    exchange is a no-op), keeping param-list positions aligned."""

    @nn.compact
    def __call__(self, x):
        return x


class Dense(nn.Module):
    features: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        x = x.reshape((x.shape[0], -1))
        with jax.named_scope(conv_scope(1, 1)):
            return nn.Dense(self.features, dtype=self.dtype, name="fc")(x)


class Sequential(nn.Module):
    """Flat layer sequence — the unit the stage partitioner slices
    (ref builds flat ``nn.Sequential(OrderedDict)`` for the same reason,
    ``resnet.py:149-178``). Values between layers may be pytrees (AmoebaNet
    cells pass ``(concat, skip)`` tuples)."""

    layers: Sequence[Callable]

    @nn.compact
    def __call__(self, x):
        for layer in self.layers:
            x = layer(x)
        return x
