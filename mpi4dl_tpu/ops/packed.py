"""Persistently-packed activation layout: the 128-lane pad-tax killer.

The problem (measured, docs/PERF.md round 2): TPU HBM stores a tensor's
minormost (channel) dim padded to the 128-lane tile, so the reference
models' small-channel/high-resolution trunks ([B, 1024, 1024, 16] and
friends) occupy up to 8x their logical bytes, and EVERY op touching them —
convs, BN, relu, residual adds — moves 8x the traffic. A 512px profile
showed the train step spending ~2/3 of its time in exactly those ops.

The fix is a layout change, not new math: activations live as

    [B, H, W/f, f*C]   with  f = 128 // C   (the "packed" layout)

which is bit-identical memory to NHWC *when C is minormost and dense* —
``pack``/``unpack`` are free reshapes of the logical data — but as the
tensor's actual shape it makes the minormost dim 128 wide, so HBM stores it
dense. BN, relu, and residual adds run on packed tensors unchanged (8x
less traffic); convolutions run directly on the packed form via a
*scattered kernel*: a stride-``s`` logical conv becomes a stride-``s'``
packed conv whose kernel gathers the right (tap, subpixel) pairs:

    y[b, h, f_out*jo + p, o] = sum_{u,v,c} x[b, h+u-ph, s*(f_out*jo+p)+v-pw, c]
                                          * K[u, v, c, o]

  packs to   yp[b, h, jo, p*O + o] = sum_{u, tt, q, c}
                 xp[b, h+u-ph, s'*jo + tt - pl', q*C + c] * Kp[u, tt, qC+c, pO+o]

  with  s' = s*f_out/f_in,  Kp[u, tt, q*C+c, p*O+o] = K[u, v, c, o]  where
  v = f_in*(tt - pl') + q - s*p + pw   (zero when v is out of kernel range).

Zero taps contribute exact zeros to the f32 accumulator and logical edge
padding coincides with whole packed-column padding (W % f == 0), so the
result is the same sum of the same products as the logical conv (mod f32
accumulation order). FLOPs inflate (kw'*f_in / kw useful fraction) but the
matmul's N dim becomes f_out*O = 128 — the MXU rate law (docs/PERF.md)
makes that a measured net win for every small-channel shape:

    fwd conv, one chip (ms):      packed    stock-NHWC
    3x3 16ch  @1024px              3.06       6.24
    3x3 32ch  @512px               2.74       5.06
    3x3 64ch  @256px               2.69       2.91

This is the pure-XLA successor to two earlier attempts: output-only
W-packing (ops/fastconv.py — input stays padded) and a Pallas compact-conv
kernel (round 2 — dead on arrival: Pallas block DMA on the bench runtime
tops out at ~45 GB/s vs XLA's ~350+ GB/s, see docs/PERF.md).

Parameter trees match the stock modules exactly (kernel [kh,kw,C,O], bias
[O], BN scale/bias [C]) so checkpoints and golden tests are interchangeable.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name


def pack_factor(c: int, w: int | None = None) -> int:
    """Subpixels per packed column for a C-channel tensor (1 = unpacked).
    ``w`` (logical width) caps the factor so W % f == 0."""
    f = max(128 // c, 1)
    if w is not None:
        while f > 1 and w % f:
            f //= 2
    return f


def pack(x, f: int):
    """[B, H, W, C] -> [B, H, W/f, f*C]; logical bytes unchanged."""
    if f == 1:
        return x
    b, h, w, c = x.shape
    return x.reshape(b, h, w // f, f * c)


def unpack(x, f: int):
    """[B, H, W/f, f*C] -> [B, H, W, C]."""
    if f == 1:
        return x
    b, h, wf, fc = x.shape
    return x.reshape(b, h, wf * f, fc // f)


def _plan(kw: int, s: int, pw: int, f_in: int, f_out: int):
    """Static W-axis plan: (stride', pad', vidx[kw', f_in, f_out], mask)."""
    assert (s * f_out) % f_in == 0, (s, f_in, f_out)
    s_p = s * f_out // f_in
    ts = [s * p + v - pw for p in range(f_out) for v in range(kw)]
    t_lo = min(ts) // f_in if min(ts) >= 0 else -((-min(ts) + f_in - 1) // f_in)
    t_hi = max(ts) // f_in
    kw_p = t_hi - t_lo + 1
    pl_p = -t_lo
    vidx = np.zeros((kw_p, f_in, f_out), np.int32)
    mask = np.zeros((kw_p, f_in, f_out), bool)
    for tt in range(kw_p):
        for q in range(f_in):
            for p in range(f_out):
                v = f_in * (tt - pl_p) + q - s * p + pw
                if 0 <= v < kw:
                    vidx[tt, q, p] = v
                    mask[tt, q, p] = True
    return s_p, pl_p, vidx, mask


def packed_kernel(w, f_in: int, f_out: int, s: int, pw: int):
    """[kh, kw, C, O] -> scattered [kh, kw', f_in*C, f_out*O] (+ plan)."""
    kh, kw, c, o = w.shape
    s_p, pl_p, vidx, mask = _plan(kw, s, pw, f_in, f_out)
    g = w[:, jnp.asarray(vidx.reshape(-1))]  # [kh, kw'*f_in*f_out, C, O]
    g = g.reshape(kh, vidx.shape[0], f_in, f_out, c, o)
    g = jnp.where(jnp.asarray(mask)[None, :, :, :, None, None], g, 0)
    kp = g.transpose(0, 1, 2, 4, 3, 5).reshape(
        kh, vidx.shape[0], f_in * c, f_out * o
    )
    return kp, s_p, pl_p


def _taps_profitable_packed(x) -> bool:
    """Use the per-tap wgrad for the packed core conv when the operand is
    large AND the contraction batch is tiny (B <= 2): XLA's backward-
    filter form space-to-depth-copies x AND dy (~4.5 GB of copies at
    3072px bs=1 — docs/PERF.md round 4) because the contraction batch
    underfills the feature dim; at larger batches the pathology is gone
    and taps would just pay kh*kw' re-reads. Taps on the packed layout
    are MXU-friendly (128-lane operands). Shares fastconv's off switch
    (MPI4DL_TPU_WGRAD_TAPS) and its single threshold (taps_min_mb: the
    3072 MB default, the Trainer's big-image context, or the env
    override — one value for both gates)."""
    import os

    from mpi4dl_tpu.ops.fastconv import taps_min_mb

    if os.environ.get("MPI4DL_TPU_WGRAD_TAPS", "auto") == "off":
        return False
    min_mb = taps_min_mb()
    b, c = x.shape[0], x.shape[-1]
    # Gate on the PADDED copy estimate, not raw bytes: the backward-filter
    # form pads the operand ~256/(B*C)-fold (an un-packed 3-channel stem
    # input at 4096px is 96 MB raw but an 8 GB padded copy — docs/PERF.md
    # round 4); fully-packed 128-lane operands still pay ~2x plus the
    # space-to-depth copies.
    expansion = 256.0 / (b * min(c, 128))
    return (
        b <= 2
        and float(np.prod(x.shape)) * x.dtype.itemsize * max(expansion, 2.0)
        >= min_mb * 1e6
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _packed_core(x, kp, strides, padding):
    """The packed conv's core ``conv_general_dilated`` with a backward
    that dodges the wgrad space-to-depth copies at large sizes."""
    return lax.conv_general_dilated(
        x, kp, strides, padding, dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def _packed_core_fwd(x, kp, strides, padding):
    return _packed_core(x, kp, strides, padding), (x, kp)


def _packed_core_bwd(strides, padding, res, dy):
    from mpi4dl_tpu.ops.fastconv import conv_bwd_with_taps

    x, kp = res
    return conv_bwd_with_taps(
        lambda xx, kk: lax.conv_general_dilated(
            xx, kk, strides, padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ),
        _taps_profitable_packed,
        x, kp, dy, strides, padding,
    )


_packed_core.defvjp(_packed_core_fwd, _packed_core_bwd)


def _core(x, kp, strides, padding):
    """Dispatch: the custom-VJP core only when the taps gate is armed for
    this shape — wrapping every conv in a custom_vjp was measured ~10%
    slower end-to-end at @1024 (the wrapper pins residuals and walls off
    fwd/bwd fusion XLA otherwise does); stock AD handles the small-size
    regime exactly as before."""
    if _taps_profitable_packed(x):
        return _packed_core(x, kp, strides, padding)
    return lax.conv_general_dilated(
        x, kp, strides, padding, dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def conv2d_packed(
    xp,
    w,
    f_in: int,
    f_out: int,
    strides,
    padding,
    spatial: bool = False,
):
    """Logical conv on packed operands. xp [B, H, W/f_in, f_in*C];
    w [kh, kw, C, O] (logical params); strides (sh, sw) with sh == sw;
    padding ((ph, ph), (pw, pw)) logical. Returns [B, H', W'/f_out, f_out*O].

    ``spatial=True`` (inside ``shard_map`` over the tile mesh axes) replaces
    the zero padding with a halo exchange — ref ``conv_spatial``
    (``spatial.py:25-1029``) on the packed layout. The exchange moves WHOLE
    packed columns: a packed column is bit-identical memory to ``f_in``
    logical columns, so the neighbor's edge column block carries exactly the
    logical halo (plus up to ``f_in - pw`` extra columns that the scattered
    kernel's zero taps ignore), and ``ppermute``'s zero fill at the mesh
    boundary reproduces ``ZeroPad2d`` semantics — the packed conv's masked
    taps never read past the logical pad width.
    """
    sh, sw = strides
    (ph0, ph1), (pw0, pw1) = padding
    assert pw0 == pw1, "packed conv needs symmetric W padding"
    kh, kw = w.shape[0], w.shape[1]
    kp, s_p, pl_p = packed_kernel(w, f_in, f_out, sw, pw0)
    win_p = xp.shape[2]

    if spatial:
        from mpi4dl_tpu.parallel.halo import halo_exchange

        assert ph0 == ph1, "packed spatial conv needs symmetric H padding"
        if (win_p * f_in) % (sw * f_out):
            raise ValueError(
                f"packed spatial conv: local width {win_p * f_in} must "
                f"divide by stride*f_out={sw * f_out}"
            )
        wout_p = win_p * f_in // (sw * f_out)  # this tile's output columns
        # Column halo wide enough for both the plan's left pad and the
        # rightmost window; off realigns the VALID output grid when the
        # exchange is wider than the plan's left pad.
        pr_p = s_p * (wout_p - 1) + kp.shape[1] - pl_p - win_p
        hw_p = max(pl_p, pr_p, 0)
        off, rem = divmod(hw_p - pl_p, s_p)
        if rem:
            raise ValueError(
                "packed spatial conv: halo width misaligned with the packed "
                f"stride (pl'={pl_p}, pr'={pr_p}, s'={s_p})"
            )
        h_loc = xp.shape[1]
        xe = halo_exchange(xp, ph0, hw_p)
        y = _core(xe, kp, (sh, s_p), ((0, 0), (0, 0)))
        return y[:, : h_loc // sh, off : off + wout_p, :]

    w_logical = win_p * f_in
    w_out = (w_logical + 2 * pw0 - kw) // sw + 1
    if w_out % f_out:
        raise ValueError(
            f"packed conv output width {w_out} must divide by f_out={f_out} "
            "(columns would be silently dropped); use a pack factor that "
            "divides the width"
        )
    wout_p = w_out // f_out
    # Right padding sized so the packed conv emits exactly wout_p columns
    # (the scattered kernel's tap range is asymmetric in general).
    pr_p = s_p * (wout_p - 1) + kp.shape[1] - pl_p - win_p
    return _core(xp, kp, (sh, s_p), ((ph0, ph1), (pl_p, pr_p)))


class PackedConv(nn.Module):
    """Conv on persistently-packed activations. Parameter tree ("kernel"
    [kh, kw, C, O], "bias" [O]) matches ``FastConv``/``nn.Conv`` exactly."""

    features: int
    kernel_size: tuple[int, int]
    pack_in: int
    pack_out: int
    strides: tuple[int, int] = (1, 1)
    padding: tuple[tuple[int, int], tuple[int, int]] = ((0, 0), (0, 0))
    use_bias: bool = True
    spatial: bool = False  # halo-exchange instead of zero pad (shard_map)
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        import os

        if os.environ.get("MPI4DL_TPU_COUNTING_FLOPS"):
            raise ValueError(
                "MFU FLOPs must be counted on the logical (stock-layout) "
                "model: PackedConv executes inflated scattered-kernel FLOPs "
                "by design (see mpi4dl_tpu/flops.py)"
            )
        kh, kw = self.kernel_size
        c_in = x.shape[-1] // self.pack_in
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (kh, kw, c_in, self.features),
            jnp.float32,
        )
        bias = (
            self.param(
                "bias", nn.initializers.zeros_init(), (self.features,), jnp.float32
            )
            if self.use_bias
            else None
        )
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias, dtype=self.dtype)
        y = conv2d_packed(
            x, kernel, self.pack_in, self.pack_out, self.strides, self.padding,
            spatial=self.spatial,
        )
        if bias is not None:
            y = y + jnp.tile(bias, self.pack_out)
        # scan_save remat tag (see fastconv.save_compact_enabled): packed
        # tensors are already dense-lane, no compact reshape needed.
        from mpi4dl_tpu.ops.fastconv import save_compact_enabled

        if save_compact_enabled():
            y = checkpoint_name(y, "conv_out")
        return y


class PackedTrainBatchNorm(nn.Module):
    """TrainBatchNorm on packed activations: statistics fold the subpixel
    axis into the batch axes, parameters stay logical [C] — numerics and
    parameter tree identical to ``TrainBatchNorm`` on the unpacked tensor
    (sums regrouped; f32 accumulation as there)."""

    pack: int
    eps: float = 1e-5
    reduce_axes: tuple[str, ...] = ()
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        from mpi4dl_tpu.ops.layers import _accumulate_bn_stats, current_bn_mode

        fc = x.shape[-1]
        c = fc // self.pack
        scale = self.param("scale", nn.initializers.ones_init(), (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (c,), jnp.float32)
        with jax.named_scope("mpi4dl_batchnorm"):
            if current_bn_mode() == "running":
                # Frozen calibration stats (mpi4dl_tpu/evaluate.py) — logical
                # [C], tiled over the subpixel axis like w/b below.
                mean = self.variable(
                    "batch_stats", "mean", jnp.zeros, (c,), jnp.float32
                ).value
                var = self.variable(
                    "batch_stats", "var", jnp.ones, (c,), jnp.float32
                ).value
                w = (lax.rsqrt(var + self.eps) * scale).astype(x.dtype)
                b = (bias - mean * lax.rsqrt(var + self.eps) * scale).astype(x.dtype)
                return x * jnp.tile(w, self.pack) + jnp.tile(b, self.pack)
            # Moments over the leading axes per PACKED channel
            # (layers.bn_moments), then averaged over the pack groups
            # (equal group sizes: mean of group means == pooled mean).
            from mpi4dl_tpu.ops.layers import bn_moments

            m_pc, msq_pc = bn_moments(x)
            mean = m_pc.reshape(self.pack, c).mean(0)
            mean_sq = msq_pc.reshape(self.pack, c).mean(0)
            if self.reduce_axes:
                mean = lax.pmean(mean, self.reduce_axes)
                mean_sq = lax.pmean(mean_sq, self.reduce_axes)
            if current_bn_mode() == "collect":
                _accumulate_bn_stats(self, mean, mean_sq)
            var = mean_sq - jnp.square(mean)
            w = (lax.rsqrt(var + self.eps) * scale).astype(x.dtype)
            b = (bias - mean * lax.rsqrt(var + self.eps) * scale).astype(x.dtype)
            return x * jnp.tile(w, self.pack) + jnp.tile(b, self.pack)
