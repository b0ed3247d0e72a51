"""MXU-packed convolution: same math, lane-filling output channels.

Motivation (measured on the bench TPU, see ``docs/PERF.md``): the MXU's
effective rate is gated by the matmul's N dimension (output channels for a
conv). The reference models' CIFAR-style ResNet/AmoebaNet trunks carry 16-64
channels at very high resolution, so their convs run a [M, K] x [K, 16]
matmul — ~2.5 TF/s on hardware whose [M, K] x [K, 128] rate is ~25 TF/s.
The image is huge and the channel count tiny: exactly the wrong aspect
ratio for a 128x128 systolic array.

The fix is a layout identity, not an approximation. A stride-1 ``kh x kw``
conv producing ``O`` channels equals a stride-``(fh, fw)`` conv with a
``(kh+fh-1) x (kw+fw-1)`` *scattered* kernel producing ``fh*fw*O``
channels — output channel group (py, px) holds the original kernel shifted
by (py, px) and computes the original output subpixel (py, px) of each
``fh x fw`` output block — followed by a depth-to-space reshuffle. Zero
taps add exact zeros to the accumulator, so the result is the same sum of
the same products (mod f32 accumulation order). FLOPs inflate by
``(kh+fh-1)(kw+fw-1) / (kh kw)`` while the MXU N-dimension grows
``fh*fw``-fold — a large net win for small ``O`` (measured ~2x+ for 3x3 at
16-64 channels). 1x1 convs never profit: inflation is exactly ``fh*fw``,
cancelling the N gain — they stay on the stock path.

Custom VJPs cover BOTH stride-1 and strided convs. The stride-1 backward
packs the data gradient too — itself a small-N stride-1 conv of ``dy``
with the flipped/io-swapped kernel; the weight gradient uses the classic
transposed-wgrad conv (x as "CHWN", dy as the kernel) at ordinary sizes
and switches to per-tap ``dot_general``s (``wgrad_taps``) in the big-
size/small-batch regime where the conv form materializes pathologically-
padded operand copies (docs/PERF.md round 4). Strided convs keep XLA's
forward and dx but route their wgrad through the same taps gate.

Used by :class:`mpi4dl_tpu.ops.layers.Conv2d` via :class:`FastConv`;
selection is automatic (TPU + profitable shapes) and can be forced or
disabled with ``MPI4DL_TPU_CONV_IMPL`` = ``packed`` | ``xla`` | ``auto``.
"""

from __future__ import annotations

import functools
import os
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

_DIMNUMS = ("NHWC", "HWIO", "NHWC")

# Pack to at least this many output channels (the MXU lane count; measured
# rates keep improving up to ~128 lanes — see docs/PERF.md).
_TARGET_N = 128
# Accept at most this much FLOP inflation from kernel scattering.
_MAX_INFLATE = 4.0
# Candidate W-axis output-block factors. H is never packed (fh == 1):
# with W-only packing the depth-to-space is a pure reshape — the (py, px)
# interleave transpose that H-packing needs was measured at ~20 ms/step in
# the backward (profiled at 512px), far more than the FLOP delta between
# e.g. (2,4) and (1,8) packing.
_FACTORS_W = (2, 4, 8)


_SAVE_COMPACT = False


def save_compact_enabled() -> bool:
    """True while a trainer is tracing under the "scan_save" remat policy
    (the ``conv_out`` tag + compact reshape are emitted only then, so other
    policies pay no extra copies)."""
    return _SAVE_COMPACT


class save_conv_outputs:
    """Context manager enabling the ``conv_out`` tagging during tracing."""

    def __enter__(self):
        global _SAVE_COMPACT
        self._prev = _SAVE_COMPACT
        _SAVE_COMPACT = True

    def __exit__(self, *exc):
        global _SAVE_COMPACT
        _SAVE_COMPACT = self._prev


def conv_impl() -> str:
    """Global conv implementation selector: "auto" (default), "packed",
    or "xla" (``MPI4DL_TPU_CONV_IMPL``). Unknown values fail loudly."""
    impl = os.environ.get("MPI4DL_TPU_CONV_IMPL", "auto")
    if impl not in ("auto", "packed", "xla"):
        raise ValueError(
            f"MPI4DL_TPU_CONV_IMPL must be auto|packed|xla, got {impl!r}"
        )
    return impl


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def conv_scope(kh: int, kw: int) -> str:
    """The ``jax.named_scope`` the device trace finds a convolution's class
    by, from its window: a 1x1 (a product over pixels; a dense layer) or a
    k x k. ``layers.Conv2d`` opens it around forward, halo and bias; the
    ``custom_vjp`` backward rules' operators carry the forward's name stack
    under ``transpose(jvp())`` and so the scope (``tests/test_step_scopes.py``
    holds both gradients to it)."""
    return "mpi4dl_conv1x1" if (kh, kw) == (1, 1) else "mpi4dl_convkxk"


@functools.lru_cache(maxsize=None)
def pack_factors(kh: int, kw: int, c_out: int, w_out: int) -> tuple[int, int]:
    """Choose (1, fw) output-block factors for a stride-1 conv; (1, 1)
    means "don't pack". Only the W axis is ever packed (see ``_FACTORS_W``).

    Profitability model from the measured MXU rate curve: rate grows
    ~linearly in N up to ``_TARGET_N`` lanes, while scattering inflates
    FLOPs by ``(kw+fw-1)/kw``. Maximize ``min(N', TARGET)/inflation``;
    require a >1.3x modeled win.
    """
    if (kh == 1 and kw == 1) or c_out >= _TARGET_N:
        return (1, 1)

    def score(fw: int) -> float:
        inflation = (kw + fw - 1) / kw
        if inflation > _MAX_INFLATE:
            return 0.0
        gain = min(fw * c_out, _TARGET_N) / min(c_out, _TARGET_N)
        return gain / inflation

    best, best_s = (1, 1), 1.3
    for fw in _FACTORS_W:
        if w_out % fw:
            continue
        s = score(fw)
        if s > best_s:
            best, best_s = (1, fw), s
    return best


def _scatter_kernel(w, fh: int, fw: int):
    """[kh, kw, C, O] -> [kh+fh-1, kw+fw-1, C, fh*fw*O] scattered kernel.

    Built by padding + stacking (kernel-sized, fuses under jit)."""
    kh, kw, c, o = w.shape
    blocks = [
        jnp.pad(w, ((py, fh - 1 - py), (px, fw - 1 - px), (0, 0), (0, 0)))
        for py in range(fh)
        for px in range(fw)
    ]
    wp = jnp.stack(blocks, axis=3)  # [kh', kw', C, fh*fw, O]
    return wp.reshape(kh + fh - 1, kw + fw - 1, c, fh * fw * o)


def _depth_to_space(y, fh: int, fw: int):
    """[B, H, W, fh*fw*O] -> [B, H*fh, W*fw, O]."""
    b, h, w, c = y.shape
    o = c // (fh * fw)
    y = y.reshape(b, h, w, fh, fw, o)
    y = y.transpose(0, 1, 3, 2, 4, 5)
    return y.reshape(b, h * fh, w * fw, o)


def _conv_packed(x, w, padding, fh: int, fw: int):
    """Stride-1 conv with explicit padding pairs, packed formulation.

    The padding rides on the strided conv itself (no separate pad copy);
    window starts are identical to pad-then-VALID since the packed output
    extent divides exactly (checked by the dispatch policy)."""
    wp = _scatter_kernel(w, fh, fw)
    y = lax.conv_general_dilated(
        x, wp, (fh, fw), padding, dimension_numbers=_DIMNUMS
    )
    return _depth_to_space(y, fh, fw)


def _conv_plain(x, w, strides, padding):
    return lax.conv_general_dilated(
        x, w, strides, padding, dimension_numbers=_DIMNUMS
    )


def _packed_dispatch(x, w, padding):
    """Stride-1 conv: packed when the policy says so, else plain."""
    (ph0, ph1), (pw0, pw1) = padding
    if min(ph0, ph1, pw0, pw1) < 0:
        # Negative explicit padding (a full-correlation dx whose forward
        # padding exceeded kernel-1): jnp.pad can't express it; XLA can.
        return _conv_plain(x, w, (1, 1), padding)
    if w.shape[0] == 1 and w.shape[1] == 1 and max(ph0, ph1, pw0, pw1) == 0:
        # 1x1 conv: a plain matmul over pixels. Layout packing can't help
        # (FLOP inflation exactly cancels the lane gain) but skipping the
        # conv lowering measurably does. Contract on the 4-D tensor
        # directly — an explicit [B*H*W, C] reshape pins C as the minor
        # (lane) dim, and for C < 128 XLA then materializes the operand
        # padded up to 8x (measured: 2.25 GB for a 288 MB [3072^2, 16]
        # reshape, part of the >2048px OOM — docs/PERF.md round 4); on
        # 4-D operands the compiler keeps its own (H/W-minor) layouts.
        return lax.dot_general(
            x, w.reshape(w.shape[2], w.shape[3]), (((3,), (0,)), ((), ()))
        )
    w_out = x.shape[2] + pw0 + pw1 - w.shape[1] + 1
    fh, fw = pack_factors(w.shape[0], w.shape[1], w.shape[3], w_out)
    if (fh, fw) == (1, 1):
        return _conv_plain(x, w, (1, 1), padding)
    return _conv_packed(x, w, padding, fh, fw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv2d_s1(x, w, padding):
    return _packed_dispatch(x, w, padding)


def _conv2d_s1_fwd(x, w, padding):
    return _packed_dispatch(x, w, padding), (x, w)


def _conv2d_s1_bwd(padding, res, dy):
    x, w = res
    kh, kw, _, _ = w.shape
    (ph0, ph1), (pw0, pw1) = padding

    big = (
        not (kh == 1 and kw == 1)  # the 1x1 dx IS the layout-safe 4-D dot
        and _wgrad_taps_profitable(
            x.shape[0], x.shape[-1],
            float(np.prod(x.shape)) * x.dtype.itemsize,
        )
    )
    # dx: full correlation with the flipped, io-swapped kernel — a stride-1
    # small-N conv itself, so it goes through the packed dispatch too. In
    # the big-size regime the W-packed dx form materializes an 8x-padded
    # space-to-depth copy of dy (2.28 GB at 3072px — docs/PERF.md round
    # 4); leave the lowering to XLA there.
    wt = jnp.flip(w, axis=(0, 1)).swapaxes(2, 3)  # [kh, kw, O, C]
    dx_pad = ((kh - 1 - ph0, kh - 1 - ph1), (kw - 1 - pw0, kw - 1 - pw1))
    dx = _conv_plain(dy, wt, (1, 1), dx_pad) if big else _packed_dispatch(
        dy, wt, dx_pad
    )

    # dw[u, v, c, o] = sum_{b,h,w} xp[b, h+u, w+v, c] * dy[b, h, w, o].
    # 1x1: that's a plain x^T @ dy dot over pixels — no conv machinery.
    # Contract (B, H, W) on the 4-D operands directly (no [M, C] reshape —
    # see the layout note in _packed_dispatch's 1x1 branch).
    if kh == 1 and kw == 1 and max(ph0, ph1, pw0, pw1) == 0:
        c, o = x.shape[-1], dy.shape[-1]
        dw = lax.dot_general(
            x,
            dy,
            (((0, 1, 2), (0, 1, 2)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(1, 1, c, o)
        return dx.astype(x.dtype), dw.astype(w.dtype)

    xt = x
    if ph0 or ph1 or pw0 or pw1:
        xt = lax.pad(
            x,
            jnp.zeros((), x.dtype),
            ((0, 0, 0), (ph0, ph1, 0), (pw0, pw1, 0), (0, 0, 0)),
        )

    # k x k: the canonical "CHWN" backward-filter conv, per-tap dots where
    # that form would materialize pathologically-padded operand copies
    # (see wgrad_folded).
    dw = wgrad_folded(xt, dy, kh, kw)
    return dx.astype(x.dtype), dw.astype(w.dtype)


# Padded-copy threshold (MB) above which the per-tap wgrad engages — ONE
# value shared by the fastconv and packed gates. Default 3072 MB: padded
# copies up to a few GB are cheaper than the taps' kh*kw operand re-reads
# (the @1024 stem conv taking taps at a 537 MB copy measured a 13%
# END-TO-END loss, docs/PERF.md round 4); only the >=3072px regime (where
# the copies OOM) wants the aggressive setting, which Trainer.train_step
# arms via the context manager below. MPI4DL_TPU_WGRAD_TAPS_MIN_MB
# overrides BOTH gates unconditionally.
_TAPS_MIN_MB = [3072.0]


def taps_min_mb() -> float:
    env = os.environ.get("MPI4DL_TPU_WGRAD_TAPS_MIN_MB")
    return float(env) if env else _TAPS_MIN_MB[0]


class wgrad_taps_threshold:
    """Context manager scoping the taps gate threshold (MB) for the
    enclosed trace — how :class:`mpi4dl_tpu.train.Trainer` arms the
    aggressive big-image setting without mutating process state."""

    def __init__(self, mb: float):
        self._mb = float(mb)

    def __enter__(self):
        self._prev = _TAPS_MIN_MB[0]
        _TAPS_MIN_MB[0] = self._mb

    def __exit__(self, *exc):
        _TAPS_MIN_MB[0] = self._prev


def _wgrad_taps_profitable(b: int, c: int, x_bytes: float) -> bool:
    """True when the canonical backward-filter conv would materialize
    pathologically-padded operand copies and the per-tap dot form should
    be used instead.

    The backward-filter conv maps x's BATCH axis to the conv feature
    (lane) dim and x's CHANNEL axis to the conv batch (sublane) dim, so at
    batch 1 / small C the TPU materializes x in a layout padded to
    ~256/(B*C) times its logical bytes — measured 4.5 GB (16x) for a
    288 MB [1,3072,3072,16] tensor, the allocation that made every
    >2048px ResNet train step exceed HBM at compile (docs/PERF.md round
    4; row-folding the batch was tried first and just moved the padding
    into 5x-padded chunk copies). Gate: expansion >= 4 AND the padded
    copy would exceed :func:`taps_min_mb`.
    ``MPI4DL_TPU_WGRAD_TAPS`` = auto (default) | off.
    """
    if os.environ.get("MPI4DL_TPU_WGRAD_TAPS", "auto") == "off":
        return False
    expansion = 256.0 / (b * c)
    return expansion >= 4.0 and x_bytes * expansion >= taps_min_mb() * 1e6


def wgrad_taps(xt, dy, kh: int, kw: int, sh: int = 1, sw: int = 1):
    """dw[u,v,c,o] = sum_{b,i,j} xt[b,i*sh+u,j*sw+v,c] * dy[b,i,j,o] as
    kh*kw per-tap ``dot_general``s contracting (B, H, W) on plain 4-D
    (strided) SLICES of the operands — no reshape, no transposed copy, so
    XLA keeps its own (H/W-minor, unpadded) layouts for x and dy and the
    only temporaries are one product at a time. This is what makes
    >2048px train steps fit HBM; cost is kh*kw reads of x and dy.
    ``xt`` is the already-padded input."""
    b, hp, wp, c = xt.shape
    _, ho, wo, o = dy.shape
    taps = []
    for u in range(kh):
        for v in range(kw):
            xs = lax.slice(
                xt,
                (0, u, v, 0),
                (b, u + (ho - 1) * sh + 1, v + (wo - 1) * sw + 1, c),
                (1, sh, sw, 1),
            )
            taps.append(
                lax.dot_general(
                    xs,
                    dy,
                    (((0, 1, 2), (0, 1, 2)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
    return jnp.stack(taps).reshape(kh, kw, c, o)


def wgrad_folded(xt, dy, kh: int, kw: int):
    """Stride-1 wgrad: per-tap dots when the canonical backward-filter
    conv would materialize pathologically-padded copies
    (:func:`_wgrad_taps_profitable`), else the fast conv form. Identical
    math either way (mod f32 accumulation order — both contract in f32
    on the MXU)."""
    if _wgrad_taps_profitable(
        xt.shape[0], xt.shape[-1],
        float(np.prod(xt.shape)) * xt.dtype.itemsize,
    ):
        return wgrad_taps(xt, dy, kh, kw)
    dw = lax.conv_general_dilated(
        xt,
        dy,
        window_strides=(1, 1),
        padding="VALID",
        dimension_numbers=("CHWN", "IHWO", "NHWC"),
    )  # out: [C, kh, kw, O]
    return dw.transpose(1, 2, 0, 3)


def conv_bwd_with_taps(conv_fn, taps_gate, x, w, dy, strides, padding):
    """Shared backward for the strided/packed custom VJPs: dx always via
    XLA's own transpose of ``conv_fn`` (its base-dilated form keeps
    natural layouts — measured fine at every size); dw via per-tap
    strided dots when ``taps_gate(x)`` says the backward-filter form
    would materialize pathological copies (docs/PERF.md round 4), via
    the same pullback otherwise. ``conv_fn(x, w)`` must be the forward
    these gradients belong to."""
    kh, kw = w.shape[0], w.shape[1]
    _, pullback = jax.vjp(conv_fn, x, w)
    if taps_gate(x):
        dx, _ = pullback(dy)
        (ph0, ph1), (pw0, pw1) = padding
        xt = x
        if ph0 or ph1 or pw0 or pw1:
            xt = lax.pad(
                x,
                jnp.zeros((), x.dtype),
                ((0, 0, 0), (ph0, ph1, 0), (pw0, pw1, 0), (0, 0, 0)),
            )
        dw = wgrad_taps(xt, dy, kh, kw, strides[0], strides[1])
    else:
        dx, dw = pullback(dy)
    return dx.astype(x.dtype), dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv2d_strided(x, w, strides, padding):
    return _conv_plain(x, w, strides, padding)


def _conv2d_strided_fwd(x, w, strides, padding):
    return _conv_plain(x, w, strides, padding), (x, w)


def _conv2d_strided_bwd(strides, padding, res, dy):
    x, w = res
    return conv_bwd_with_taps(
        lambda xx, ww: _conv_plain(xx, ww, strides, padding),
        lambda xx: _wgrad_taps_profitable(
            xx.shape[0],
            xx.shape[-1],
            float(np.prod(xx.shape)) * xx.dtype.itemsize,
        ),
        x, w, dy, strides, padding,
    )


_conv2d_strided.defvjp(_conv2d_strided_fwd, _conv2d_strided_bwd)


_conv2d_s1.defvjp(_conv2d_s1_fwd, _conv2d_s1_bwd)


def conv2d(x, w, strides=(1, 1), padding=((0, 0), (0, 0))):
    """2-D conv (NHWC x HWIO -> NHWC), explicit padding pairs.

    Uses the MXU-packed formulation (with matching packed backward) for
    stride-1 convs when profitable on this platform; otherwise identical to
    ``lax.conv_general_dilated``.
    """
    strides = tuple(int(s) for s in strides)
    padding = tuple((int(p[0]), int(p[1])) for p in padding)
    impl = conv_impl()
    use_packed = impl == "packed" or (impl == "auto" and _on_tpu())
    if not use_packed:
        return _conv_plain(x, w, strides, padding)
    if strides != (1, 1):
        # Custom backward only when the big-size wgrad pathology gate is
        # armed for this shape (see _conv2d_strided_bwd) — the custom_vjp
        # wrapper itself costs fusion opportunities at small sizes.
        if _wgrad_taps_profitable(
            x.shape[0], x.shape[-1],
            float(np.prod(x.shape)) * x.dtype.itemsize,
        ):
            return _conv2d_strided(x, w, strides, padding)
        return _conv_plain(x, w, strides, padding)
    return _conv2d_s1(x, w, padding)


class FastConv(nn.Module):
    """Drop-in for ``nn.Conv`` (NHWC, explicit padding) routing through
    :func:`conv2d`. Parameter tree ("kernel", "bias"), shapes, and
    initialization match ``nn.Conv`` exactly, so models can swap freely."""

    features: int
    kernel_size: tuple[int, int]
    strides: tuple[int, int] = (1, 1)
    padding: Any = "SAME"  # pairs, "SAME", or "VALID" (nn.Conv default: SAME)
    use_bias: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        sh, sw = self.strides
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (kh, kw, x.shape[-1], self.features),
            jnp.float32,
        )
        bias = (
            self.param("bias", nn.initializers.zeros_init(), (self.features,), jnp.float32)
            if self.use_bias
            else None
        )
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias, dtype=self.dtype)
        padding = self.padding
        if padding == "VALID":
            padding = ((0, 0), (0, 0))
        elif padding == "SAME":
            # Explicit SAME pairs (XLA formula), so the packed path applies.
            def same(dim, k, s):
                total = max((-(-dim // s) - 1) * s + k - dim, 0)
                return (total // 2, total - total // 2)

            padding = (same(x.shape[1], kh, sh), same(x.shape[2], kw, sw))
        y = conv2d(x, kernel, (sh, sw), padding)
        if bias is not None:
            y = y + bias
        # Tag for the "scan_save" remat policy (convs then run once in
        # forward — backward recomputes only the cheap elementwise/BN
        # segments between conv outputs). When saving is active, tag a
        # compact [B, H, W*C] view: small-channel NHWC tensors store ~8x
        # larger in HBM (minor dim padded to the 128-lane tile), which is
        # exactly the footprint the policy is spending memory on.
        if not save_compact_enabled():
            return y
        if y.ndim == 4 and y.shape[-1] < 128:
            shape = y.shape
            yc = checkpoint_name(y.reshape(shape[0], shape[1], -1), "conv_out")
            return yc.reshape(shape)
        return checkpoint_name(y, "conv_out")
