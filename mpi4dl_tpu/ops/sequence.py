"""Sequence operators: what a token model's layers are made of.

``ops/layers.py`` holds the image classifiers' operators (NHWC convs, pools,
BatchNorm); this file holds RMSNorm, the rotary embedding, a causal
depthwise conv1d, causal grouped-query attention, the Gated DeltaNet and
Mamba-2 mixers (each a per-head state carried along the sequence, computed a
chunk of positions at a time) and an expert layer that holds a share of the
experts. The
grouped matrix products are
``jax.lax.ragged_dot`` over the token-expert pairs sorted by expert, at the
width of a prefix of the sorted rows that follows from the share held; the
rows past it run only in a step whose routing overflows it; what the
layer's forward chose, sorted, gathered and multiplied carries the name
"cell" remat keeps (``_kept``), so a training step runs the router, the
sorts and every grouped product's forward once. The
``S x S`` scores of a long sequence never exist at once (32 heads x 8192^2
floats are 8.6 GB): on a TPU, at the shapes ``ops/attention_pallas.py``
takes (bfloat16, head dim 64, 128 or 256, a length of whole blocks: all
three token models' at 8,192 positions), ``causal_attention`` is that
module's fused kernels, which keep a block's scores in VMEM and take their
plan from the shape; everywhere else (the CPU, a vmapped trace, a length
that is not whole blocks, another head dim) it is plain JAX, a block of
query rows at a time, each block recomputed in the backward pass. The gated
delta rule goes the same way: on a TPU, at the shapes
``ops/delta_rule_pallas.py`` takes, ``gated_delta_rule`` is that module's
kernels, which keep a chunk's squares and the carried state in VMEM;
everywhere else it is ``_chunked_rule``, plain JAX. Mamba-2's chunked scan
likewise: ``ssd_scan`` is ``ops/ssd_scan_pallas.py``'s kernels at the shapes
they take and ``_chunked_scan`` everywhere else. And the causal depthwise
convolution with its bias and SiLU, the form Gated DeltaNet and Mamba-2 put
over their projections: ``causal_conv_silu`` is ``ops/causal_conv_pallas.py``'s
kernels at the shapes they take (one pass over the array each way) and the
plain ``causal_depthwise_conv1d`` with ``nn.silu`` everywhere else; those two
mixers multiply a product of its own for what the convolution reads, so the
kernels take and give whole arrays. The rest is plain JAX everywhere.

Activations are ``[batch, positions, features]``. Each module computes in
its ``dtype`` (bfloat16 on the chip) with float32 parameters, float32
normalisation statistics and a float32 router.

The device trace finds the mechanisms by ``jax.named_scope``, at two levels.
**A mixer**: ``lfm2_moe`` (an expert layer; inside it ``shared_expert`` where
the layer has one; ``models/sdar.py`` opens ``sdar_moe`` around it),
``lfm2_attention`` (under block diffusion ``blockdiff_attention``, its kernels
under ``blockdiff_attention_kernels``), ``lfm2_shortconv``, ``gated_delta``
and ``mamba2``. The shared modules keep the names their first model gave
them: the benchmark's readers find them by name. **A part** of a mixer, so
that every line of a mixer's ``__call__`` falls under one:
``mpi4dl_part_proj`` (the dense projections into and out of a mixer with
their weights' casts, and the dense feed-forwards ``SwiGLU`` / ``SquaredReLU``,
a shared expert's among them), ``mpi4dl_part_conv``
(``causal_conv_silu``: on a TPU at the two cells' shapes the custom calls
``mpi4dl_causal_conv_fwd`` / ``_bwd`` with the taps' rows and the sums of their
gradient around them, else ``causal_depthwise_conv1d`` with its bias and
SiLU; in ``ShortConv`` the plain function with its two gates),
``mpi4dl_part_gates_norms`` (``beta``, ``g``, ``dt``'s softplus and the
decays, the L2 norms of q and k, ``RMSNorm(o) * silu(z)``,
``_gated_group_norm`` with ``D x``, attention's output gate), the recurrences
under the names they had (``ssd_scan``, ``gated_delta_rule``),
``mpi4dl_part_qk_prep`` (the q/k head norms, the rotary embedding, the
reshapes and casts into the kernels' operands), ``mpi4dl_part_attn_core``
(``causal_attention`` / ``block_diffusion_attention``, kernels and plain
path), ``mpi4dl_part_router`` (the router's product, scores, bias, ``top_k``,
weights), ``mpi4dl_part_dispatch`` (``group``, ``sizes``, the sorts, the
ranges' gathers and sums by token, the two-range conditionals) and, opened
inside it by ``_grouped_ffn``, ``mpi4dl_part_expert_products`` (the grouped
products with the activation between them; ``_whole_tiles`` and the expert
arrays' casts); the models' files open ``mpi4dl_part_block`` around a layer's
pre-norms, its residual adds and the embedding. A part is the innermost part
scope of a name stack. The backward rules this file writes
(``_token_sums_bwd``, ``_ranges_bwd``, ``_attention_bwd``, ``_inverse_bwd``)
open none: their operators carry the stack of the call they belong to
(``tests/test_token_scopes.py`` holds them to it). Scopes are metadata: no
traced or compiled instruction depends on one.
"""

from __future__ import annotations

import functools
import warnings
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from mpi4dl_tpu.config import KERNEL_RESIDUAL
from mpi4dl_tpu.ops import (
    attention_pallas, causal_conv_pallas, delta_rule_pallas, ssd_scan_pallas)

COUNTERS = "counters"  # the flax collection an expert layer sows its counts into
RULE_CHUNK = 64        # positions the gated delta rule takes as one triangular system


class RMSNorm(nn.Module):
    """``x / rms(x) * scale`` over the last axis, in float32; the result is
    float32 too (the router reads it so; a matrix product casts it).
    ``zero_centred``: the scale is ``1 + w`` with ``w`` from 0 (Qwen3-Next's
    convention), else ``w`` from 1."""

    eps: float
    zero_centred: bool = False

    @nn.compact
    def __call__(self, x):
        if self.zero_centred:
            scale = 1.0 + self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        else:
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale


def linear(features: int, dtype, name: str) -> nn.Dense:
    """A projection without bias (no layer of the LFM2 family has one)."""
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


class Embedding(nn.Module):
    """A table ``[vocab, hidden]`` (normal, standard deviation 1); token ids
    pick its rows."""

    vocab: int
    hidden: int

    @nn.compact
    def __call__(self, ids):
        table = self.param(
            "embedding", nn.initializers.normal(1.0), (self.vocab, self.hidden))
        return jnp.take(table, ids, axis=0)


def rope(x, theta: float, rotary: "int | None" = None, positions=None):
    """Rotary embedding of ``x [batch, rows, heads, dim]``, half-split
    pairing; angles and rotation in float32. ``positions [rows]``: every
    row's position; None: 0..S-1, the row's index (one document a sequence).
    ``rotary``: the leading dims that turn (a partial rotary factor; the
    rest pass as they are), None for all."""
    rotary = x.shape[-1] if rotary is None else rotary
    half = rotary // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if positions is None:
        positions = jnp.arange(x.shape[1], dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if rotary < x.shape[-1]:
        parts.append(x[..., rotary:])
    return jnp.concatenate(parts, axis=-1)


def causal_depthwise_conv1d(x, kernel):
    """``y[t] = sum_j kernel[j] * x[t - (L-1) + j]`` per feature, zeros
    before the start: position t sees t-L+1..t and nothing later.
    ``x [batch, positions, features]``, ``kernel [L, features]``."""
    taps, length = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j] * padded[:, j:j + length] for j in range(taps))


def causal_conv_silu(x, kernel, bias=None):
    """``silu(causal_depthwise_conv1d(x, kernel) + bias)``, the form Gated
    DeltaNet (no bias) and Mamba-2 put over their projections: ``x [batch,
    positions, features]``, ``kernel [L, features]`` and ``bias [features]``
    (None: none), both cast to ``x``'s dtype. Where
    ``causal_conv_pallas.dispatchable`` says so (TPU backend, not under
    ``vmap``, bfloat16, features of whole lanes, a length of whole blocks)
    that module's kernels: one pass over the array each way, the taps, the
    bias and the SiLU applied in float32 on values in VMEM and rounded once,
    the backward building the pre-activation again; else the plain function,
    its bias and ``nn.silu`` (the CPU, float32, the tiny cuts), which is also
    the kernels' oracle. ``ShortConv`` (two gates around three taps, no
    SiLU) does not come here: the compiler fuses its taps and gates into the
    projections' products."""
    kernel = kernel.astype(x.dtype)
    if causal_conv_pallas.dispatchable(x, kernel):
        return causal_conv_pallas.conv_silu(
            x, kernel, None if bias is None else bias.astype(x.dtype))
    y = causal_depthwise_conv1d(x, kernel)
    return nn.silu(y if bias is None else y + bias.astype(x.dtype))


class ShortConv(nn.Module):
    """LFM2's gated short convolution:
    ``B, C, u = split3(in_proj(x)); out_proj(C * conv(B * u))``."""

    hidden: int
    taps: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("lfm2_shortconv"):
            with jax.named_scope("mpi4dl_part_proj"):
                x = x.astype(self.dtype)
                gate_in, gate_out, u = jnp.split(
                    linear(3 * self.hidden, self.dtype, "in_proj")(x), 3, axis=-1)
            with jax.named_scope("mpi4dl_part_conv"):  # with the two gates around it
                kernel = _Kernel((self.taps, self.hidden), name="conv")()
                v = gate_out * causal_depthwise_conv1d(
                    gate_in * u, kernel.astype(self.dtype))
            with jax.named_scope("mpi4dl_part_proj"):
                return linear(self.hidden, self.dtype, "out_proj")(v)


class _Kernel(nn.Module):
    """A bare ``kernel`` parameter under a name of its own (LeCun normal
    over the leading axes, as ``nn.Dense`` draws its own)."""

    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(), self.shape)


# -- the gated delta rule ----------------------------------------------------


_exact = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)


@jax.custom_vjp
def _nilpotent_inverse(lower):
    """``(I - L)^-1`` of strictly lower triangular ``L [..., C, C]``, float32
    at precision "highest": ``L^C = 0``, so the inverse is the finite series
    ``I + L + ... + L^(C-1) = (I + L)(I + L^2)(I + L^4)...``, about
    ``log2 C`` pairs of batched products and no row-by-row substitution. The
    backward keeps the inverse alone: ``dL = T^T dT T^T``."""
    size = lower.shape[-1]
    inverse = jnp.eye(size, dtype=lower.dtype) + lower
    power, reach = lower, 2  # ``inverse`` holds the powers below ``reach``
    while reach < size:
        power = _exact(power, power)
        inverse = inverse + _exact(inverse, power)
        reach *= 2
    return inverse


def _inverse_bwd(inverse, ct):
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (_exact(_exact(transposed, ct), transposed),)


_nilpotent_inverse.defvjp(lambda lower: 2 * (_nilpotent_inverse(lower),), _inverse_bwd)


@jax.checkpoint
def _chunk_terms(q, k, v, g, beta):
    """What the gated delta rule needs of every chunk before any state is
    known, all chunks at once: ``q, k [b n c h d]``, ``v [b n c h r e]``,
    ``g, beta [b n c h r]`` (b batch, n chunk, c / m positions in the chunk,
    h key head, r value head of the key head, d key dim, e value dim) ->
    ``w, u`` (the WY pair: the corrected values are ``u - w S_0``), ``k``
    decayed to the chunk's end, the chunk's whole decay, ``q k^T`` under the
    decay and ``q`` decayed from the chunk's start. Recomputed in the
    backward pass: its many chunk-by-chunk squares are not kept."""
    dtype, chunk = v.dtype, g.shape[2]
    f32 = dict(preferred_element_type=jnp.float32)
    total = jnp.cumsum(g, axis=2)                              # G [b n c h r]
    at = jnp.arange(chunk)
    gap = total[:, :, :, None] - total[:, :, None]             # [b n c m h r]
    decay = jnp.exp(jnp.where(
        (at[:, None] >= at[None, :])[:, :, None, None], gap, -jnp.inf))
    decay = jnp.moveaxis(decay, (2, 3), (-2, -1))              # [b n h r c m]
    kk = jnp.einsum("bnchd,bnmhd->bnhcm", k, k, **f32)
    qk = jnp.einsum("bnchd,bnmhd->bnhcm", q, k, **f32)
    beta_c = jnp.moveaxis(beta, 2, -1)[..., None]              # [b n h r c 1]
    strictly = at[:, None] > at[None, :]
    system = jnp.where(strictly, beta_c * kk[:, :, :, None] * decay, 0.0)
    solve = _nilpotent_inverse(-system).astype(dtype)          # T [b n h r c m]
    within = (qk[:, :, :, None] * decay).astype(dtype)         # incl. the diagonal

    grown = jnp.exp(total)                                     # exp(G_c)
    to_end = jnp.exp(total[:, :, -1:] - total)                 # exp(G_C - G_c)
    v_beta = (v * beta[..., None]).astype(dtype)
    k_beta = (k[:, :, :, :, None] * (beta * grown)[..., None]).astype(dtype)
    u = jnp.einsum("bnhrcm,bnmhre->bnhrce", solve, v_beta, **f32)
    w = jnp.einsum("bnhrcm,bnmhrd->bnhrcd", solve, k_beta).astype(dtype)
    k_end = jnp.moveaxis(
        k[:, :, :, :, None] * to_end[..., None], 2, 4).astype(dtype)  # [b n h r c d]
    end = jnp.exp(total[:, :, -1])[..., None, None]            # [b n h r 1 1]
    q_grown = (q[:, :, :, :, None] * grown[..., None]).astype(dtype)  # [b n c h r d]
    return w, u, k_end, end, within, q_grown


@jax.checkpoint
def _chunked_rule(q, k, v, g, beta):
    batch, length, heads, key_dim = k.shape
    dtype, chunk = v.dtype, RULE_CHUNK
    pad = -length % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    chunks = (length + pad) // chunk
    w, u, k_end, end, within, q_grown = _chunk_terms(*(
        a.reshape(batch, chunks, chunk, *a.shape[2:]) for a in (q, k, v, g, beta)))
    f32 = dict(preferred_element_type=jnp.float32)

    def hand_on(state, chunk_):
        w_n, u_n, k_n, end_n = chunk_
        start = state.astype(dtype)
        fresh = (u_n - jnp.einsum("bhrcd,bhrde->bhrce", w_n, start, **f32)).astype(dtype)
        state = state * end_n + jnp.einsum("bhrcd,bhrce->bhrde", k_n, fresh, **f32)
        return state, (start, fresh)

    zero = jnp.zeros((batch, heads, v.shape[3], key_dim, v.shape[4]), jnp.float32)
    _, (starts, fresh) = lax.scan(
        hand_on, zero, tuple(jnp.moveaxis(a, 1, 0) for a in (w, u, k_end, end)))
    out = jnp.einsum("bnchrd,nbhrde->bnchre", q_grown, starts, **f32) \
        + jnp.einsum("bnhrcm,nbhrme->bnchre", within, fresh, **f32)
    out = out.reshape(batch, chunks * chunk, *out.shape[3:])
    return out[:, :length].astype(dtype)


def gated_delta_rule(q, k, v, g, beta):
    """The gated delta rule over a sequence, a chunk of ``RULE_CHUNK``
    positions at a time.

    Per value head, with a state ``S [Dk, Dv]`` that is zero before position
    0: ``S <- exp(g_t) S; r = v_t - S^T k_t; S <- S + beta_t k_t r^T;
    o_t = S^T q_t``. ``q, k [B, S, H, Dk]`` (already normalised and scaled),
    ``v [B, S, H, R, Dv]`` (``R`` value heads share a key head's ``q, k``),
    ``g, beta [B, S, H, R]`` float32 (``g <= 0`` the log of the decay)
    -> ``[B, S, H, R, Dv]`` in ``v``'s dtype.

    Within a chunk of ``C`` positions the ``C`` updates are one triangular
    system: with ``G`` the running sum of ``g`` inside the chunk and
    ``A[c, m] = beta_c (k_c . k_m) exp(G_c - G_m)`` for ``m < c``, the
    corrected values are ``(I + A)^-1 (beta v - (beta exp(G) k) S_0)`` (the
    WY form: ``T = (I + A)^-1`` once, then ``u = T beta v`` and
    ``w = T beta exp(G) k``), so the chunk needs the state ``S_0`` it starts
    from and no other. Where ``delta_rule_pallas.dispatchable`` says so (TPU
    backend, not under ``vmap``, bfloat16, key and value dims of whole
    lanes, a length of whole chunks) that module's kernels compute it, both
    passes, a chunk's squares and the carried state in VMEM; else
    ``_chunked_rule``, plain JAX and the same arithmetic: the terms of all
    chunks at once (``_chunk_terms``), between chunks the state handed on by
    a ``lax.scan`` whose step is two products, the outputs again two
    products over all the chunks at once.

    The plain path's backward pass is JAX's own through the scan, so it
    keeps one state a chunk (not one a position); the rule as a whole and
    the chunks' terms inside it are each recomputed there
    (``jax.checkpoint``), so that a layer's backward holds the rule's five
    inputs while its other parts are differentiated, and the chunks' squares
    only while they are. The kernels' backward is their own: a reverse sweep
    that keeps every chunk's start state and its system's inverse.

    Matrix products take operands in ``v``'s dtype and accumulate in
    float32; ``G``, the decays, ``A``, its inverse and the carried state are
    float32. Every exponent is of a difference ``<= 0``: a strong decay
    underflows to the 0 it is, nothing overflows. A length that is not whole
    chunks is padded at the end (``k, v, beta, g`` zero there change no
    state) and cut again."""
    with jax.named_scope("gated_delta_rule"):
        if delta_rule_pallas.dispatchable(q, k, v, g, beta, RULE_CHUNK):
            return delta_rule_pallas.rule(q, k, v, g, beta, RULE_CHUNK)
        return _chunked_rule(q, k, v, g, beta)


class GatedDeltaNet(nn.Module):
    """Qwen3-Next's linear-attention mixer: ``q, k, v, z = split(x W_qkvz)``,
    ``b, a = split(x W_ba)``; a causal depthwise convolution and a SiLU over
    ``concat(q, k, v)``; ``beta = sigmoid(b)`` and
    ``g = -exp(A_log) softplus(a + dt_bias)`` in float32; ``q, k``
    L2-normalised over a head's dims, ``q`` times ``key_dim ** -0.5``, each
    key head's pair used by ``value_heads / key_heads`` value heads; the
    gated delta rule (``gated_delta_rule``); then per head
    ``RMSNorm(o) * silu(z)`` (one scale of ``value_dim``, from 1) and
    ``out_proj``. The columns of ``W_qkvz`` are ``[q | k | v | z]`` and of
    ``W_ba`` ``[b | a]``, each in head order. ``q``, ``k``, ``v`` and ``z``
    are a product of ``x`` with their own columns each, and ``q``, ``k``,
    ``v`` go through the convolution (``causal_conv_silu``, their own columns
    of its taps) as whole arrays: its channels know nothing of each other,
    and on a TPU its kernels then read a product's result and write what the
    norms and the rule read, with no slice or concatenation copied around
    them in either pass."""

    hidden: int
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    taps: int
    eps: float
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("gated_delta"):
            batch, length, _ = x.shape
            heads, per_key = self.key_heads, self.value_heads // self.key_heads
            keys, values = heads * self.key_dim, self.value_heads * self.value_dim
            with jax.named_scope("mpi4dl_part_proj"):
                x = x.astype(self.dtype)
                # a product each for q, k, v and the gate: the channels of a
                # depthwise convolution know nothing of each other, so each goes
                # through the convolution as a whole array of its own
                w_qkvz = _Kernel((self.hidden, 2 * keys + 2 * values), name="in_proj_qkvz")()
                w_qkvz = w_qkvz.astype(self.dtype)
                edges = (0, keys, 2 * keys, 2 * keys + values)
                qkv = [jnp.matmul(x, w_qkvz[:, lo:hi]) for lo, hi in zip(edges, edges[1:])]
                z = jnp.matmul(x, w_qkvz[:, edges[-1]:])
                w_ba = _Kernel((self.hidden, 2 * self.value_heads), name="in_proj_ba")()
                b, a = jnp.split(jnp.matmul(
                    x, w_ba.astype(self.dtype), preferred_element_type=jnp.float32),
                    2, axis=-1)
            with jax.named_scope("mpi4dl_part_conv"):
                kernel = _Kernel((self.taps, 2 * keys + values), name="conv")()
                q, k, v = (causal_conv_silu(t, kernel[:, lo:hi])
                           for t, lo, hi in zip(qkv, edges, edges[1:]))

            with jax.named_scope("mpi4dl_part_gates_norms"):
                a_log = self.param(
                    "A_log", nn.initializers.normal(2.0), (self.value_heads,))
                dt_bias = self.param("dt_bias", nn.initializers.ones, (self.value_heads,))
                g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
                beta = jax.nn.sigmoid(b)

                def unit(t):  # a head's dims to length 1, in float32
                    t = t.reshape(batch, length, heads, self.key_dim).astype(jnp.float32)
                    return t * lax.rsqrt(
                        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

                q = (unit(q) * self.key_dim ** -0.5).astype(self.dtype)
                k = unit(k).astype(self.dtype)
                by_head = (batch, length, heads, per_key)
                v, g, beta = (v.reshape(*by_head, self.value_dim),
                              g.reshape(by_head), beta.reshape(by_head))
            out = gated_delta_rule(q, k, v, g, beta)
            with jax.named_scope("mpi4dl_part_gates_norms"):
                out = RMSNorm(self.eps, name="norm")(out) * nn.silu(
                    z.reshape(out.shape).astype(jnp.float32))
            with jax.named_scope("mpi4dl_part_proj"):
                return linear(self.hidden, self.dtype, "out_proj")(
                    out.astype(self.dtype).reshape(batch, length, values))


# -- Mamba-2 -----------------------------------------------------------------


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _chunked_scan(x, g, b, c, chunk: int):
    """``ssd_scan`` of one sequence: ``x [S, G, R, P]``, ``g [S, G, R]``,
    ``b, c [S, G, N]``."""
    length, dtype = x.shape[0], x.dtype
    pad = -length % chunk
    if pad:
        x, g, b, c = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                      for a in (x, g, b, c))
    chunks = (length + pad) // chunk
    x, g, b, c = (a.reshape(chunks, chunk, *a.shape[1:]) for a in (x, g, b, c))
    f32 = dict(preferred_element_type=jnp.float32)
    total = jnp.cumsum(g, axis=1)                              # a [n c g r]
    at = jnp.arange(chunk)
    gap = total[:, :, None] - total[:, None]                   # [n i j g r]
    decay = jnp.exp(jnp.where(
        (at[:, None] >= at[None, :])[:, :, None, None], gap, -jnp.inf))
    cb = jnp.einsum("nigs,njgs->nijg", c, b, **f32)
    within = (cb[..., None] * decay).astype(dtype)             # (C B^T) * L
    out = jnp.einsum("nijgr,njgrp->nigrp", within, x, **f32)

    to_end = jnp.exp(total[:, -1:] - total)                    # exp(a_end - a_j)
    own = jnp.einsum("ncgrp,ncgs->ngrps", (x * to_end[..., None]).astype(dtype), b, **f32)
    end = jnp.exp(total[:, -1])[..., None, None]               # [n g r 1 1]

    def hand_on(state, chunk_):
        own_n, end_n = chunk_
        return state * end_n + own_n, state

    _, starts = lax.scan(hand_on, jnp.zeros(own.shape[1:], jnp.float32), (own, end))
    out = out + jnp.exp(total)[..., None] * jnp.einsum(
        "ncgs,ngrps->ncgrp", c, starts.astype(dtype), **f32)
    return out.reshape(chunks * chunk, *out.shape[2:])[:length].astype(dtype)


def ssd_scan(x, g, b, c, chunk: int):
    """Mamba-2's recurrence over a sequence, a chunk of ``chunk`` positions
    at a time (the "state-space dual" form).

    Per head, with a state ``S [P, N]`` that is zero before position 0:
    ``S_t = exp(g_t) S_{t-1} + x_t B_t^T; y_t = S_t C_t``. ``x [B, S, G, R,
    P]`` (the layer's ``dt_t x_t``: ``R`` heads share a group's ``B, C``),
    ``g [B, S, G, R]`` float32 (``dt_t A <= 0``, the log of the decay),
    ``b, c [B, S, G, N]`` -> ``[B, S, G, R, P]`` in ``x``'s dtype. A scalar
    decay a head and position and no correction of the state by what it
    already holds: not the gated delta rule, and no triangular system.

    With ``a`` the running sum of ``g`` inside a chunk: a chunk's own
    positions give ``((C B^T) * L) x`` with ``L[i, j] = exp(a_i - a_j)`` for
    ``i >= j`` (every chunk at once, one ``[chunk, chunk]`` square a chunk
    and head); the chunk's own state is ``sum_j exp(a_end - a_j) x_j
    B_j^T``; between chunks ``S_c = exp(a_end) S_{c-1} + own`` is handed on
    by a ``lax.scan`` whose step is one multiply-add of the state; the
    state a chunk starts from adds ``exp(a_i) S_{c-1} C_i``.

    Where ``ssd_scan_pallas.dispatchable`` says so (TPU backend, not under
    ``vmap``, ``x, b, c`` bfloat16 and ``g`` float32, heads of whole
    bfloat16 tiles of 16 channels, a state and a chunk of whole lanes, a
    length of whole chunks: the Nemotron-H cell's 8 heads of 64 a group,
    state and chunks of 128) that module's kernels compute it, both passes,
    the positions along the lanes, a chunk's squares and the group's carried
    state in VMEM, the backward a reverse sweep of its own that keeps every
    chunk's start state. Everywhere else (the CPU,
    the tier-1 tests, the tiny cut, ``vmap``, float32) ``_chunked_scan``,
    plain JAX and the same arithmetic, which is also the kernels' oracle:
    the sequences of the batch one after the other (nothing here mixes
    them), each under ``jax.checkpoint``: a layer's backward pass holds the
    scan's four inputs while the layer's other parts are differentiated, and
    one sequence's squares and states (at 8,192 positions, 64 heads and
    chunks of 128 a float32 copy of the squares is 0.27 GB, of the states
    0.13) only while that sequence's scan is; JAX's own backward through
    the ``lax.scan`` keeps one state a chunk.

    Matrix products take operands in ``x``'s dtype and accumulate in
    float32; ``a``, every decay and the carried state are float32. Every
    exponent is of a difference ``<= 0``: a strong decay underflows to the 0
    it is, nothing overflows. On the plain path a length that is not whole
    chunks is padded at the end (``x, b, g`` zero there change no state) and
    cut again."""
    with jax.named_scope("ssd_scan"):
        if ssd_scan_pallas.dispatchable(x, g, b, c, chunk):
            return ssd_scan_pallas.scan(x, g, b, c, chunk)
        return lax.map(lambda row: _chunked_scan(*row, chunk), (x, g, b, c))


def _log_uniform_inverse_softplus(low: float, high: float, floor: float):
    """Mamba-2's ``dt_bias``: ``dt`` log-uniform over ``[low, high]``, not
    under ``floor``, through the inverse of the softplus."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(
            key, shape, dtype, jnp.log(low), jnp.log(high)))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _gated_group_norm(y, u, z, skip, scale, eps: float):
    """What follows Mamba-2's scan: ``y + D u`` (``y, u [B, S, G, R, P]``,
    ``skip [G R]``), times ``silu(z)`` (``z [B, S, G R P]``), an RMSNorm over
    each group's ``R P`` channels and the learned ``scale``; float32 inside,
    the inputs' dtype out. Recomputed in the backward pass: a layer keeps its
    three inputs in their dtype and not the float32 chain between them."""
    batch, length, groups, per, _ = y.shape
    y = y.astype(jnp.float32) + skip.reshape(groups, per, 1) * u.astype(jnp.float32)
    y = y.reshape(batch, length, groups, -1) * nn.silu(
        z.reshape(batch, length, groups, -1).astype(jnp.float32))
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return (y.reshape(batch, length, -1) * scale).astype(z.dtype)


class Mamba2(nn.Module):
    """Nemotron-H's Mamba-2 mixer: ``z, xBC, dt = split(x W_in)`` (widths
    ``heads x head_dim | heads x head_dim + 2 x groups x state | heads``);
    ``x, B, C = split(silu(conv(xBC) + b))`` with a causal depthwise
    convolution; ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` a
    head, float32; the recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, ``y_t = S_t C_t + D x_t`` (``ssd_scan``: on a TPU at the cell's
    shapes the kernels of ``ops/ssd_scan_pallas.py``, else ``_chunked_scan``,
    their oracle; head ``h`` reads group ``h // (heads / groups)``'s ``B,
    C``); then ``RMSNorm(y * silu(z)) * w``
    with the statistics over each group's channels (the gate before the
    norm) and ``out_proj``. No projection has a bias. ``dt``'s columns of
    ``W_in`` are multiplied apart from the rest so that they accumulate into
    float32 and the decays never pass through ``dtype``; ``z``, ``x``, ``B``
    and ``C`` are a product with their own columns each too, and ``x``,
    ``B``, ``C`` go through the convolution as whole arrays
    (``GatedDeltaNet``'s reason)."""

    hidden: int
    heads: int
    head_dim: int
    groups: int
    state: int
    taps: int
    chunk: int
    eps: float
    dt_range: tuple = (0.001, 0.1, 1e-4)   # time_step_min, _max, _floor
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("mamba2"):
            batch, length, _ = x.shape
            inner, per = self.heads * self.head_dim, self.heads // self.groups
            mixed = inner + 2 * self.groups * self.state
            with jax.named_scope("mpi4dl_part_proj"):
                x = x.astype(self.dtype)
                w_in = _Kernel(
                    (self.hidden, inner + mixed + self.heads), name="in_proj")()
                w_in = w_in.astype(self.dtype)
                z = jnp.matmul(x, w_in[:, :inner])
                edges = (0, inner, inner + self.groups * self.state, mixed)
                xbc = [jnp.matmul(x, w_in[:, inner + lo:inner + hi])
                       for lo, hi in zip(edges, edges[1:])]
                dt = jnp.matmul(
                    x, w_in[:, inner + mixed:], preferred_element_type=jnp.float32)
            with jax.named_scope("mpi4dl_part_conv"):
                kernel = _Kernel((self.taps, mixed), name="conv")()
                bias = self.param("conv_bias", nn.initializers.zeros, (mixed,))
                u, b, c = (causal_conv_silu(t, kernel[:, lo:hi], bias[lo:hi])
                           for t, lo, hi in zip(xbc, edges, edges[1:]))

            with jax.named_scope("mpi4dl_part_gates_norms"):
                by_head = (batch, length, self.groups, per)
                a_log = self.param(
                    "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                        key, shape, jnp.float32, 1.0, 16.0)), (self.heads,))
                dt_bias = self.param(
                    "dt_bias", _log_uniform_inverse_softplus(*self.dt_range), (self.heads,))
                skip = self.param("D", nn.initializers.ones, (self.heads,))
                scale = self.param("norm_scale", nn.initializers.ones, (inner,))
                dt = jax.nn.softplus(dt + dt_bias).reshape(by_head)
                g = dt * -jnp.exp(a_log).reshape(self.groups, per)
                u = u.reshape(*by_head, self.head_dim)
                by_group = (batch, length, self.groups, self.state)
                moved = (u * dt[..., None]).astype(self.dtype)
                b, c = b.reshape(by_group), c.reshape(by_group)
            y = ssd_scan(moved, g, b, c, self.chunk)
            with jax.named_scope("mpi4dl_part_gates_norms"):
                y = _gated_group_norm(y, u, z, skip, scale, self.eps)
            with jax.named_scope("mpi4dl_part_proj"):
                return linear(self.hidden, self.dtype, "out_proj")(y)


# -- attention ---------------------------------------------------------------


BlockMask = attention_pallas.BlockMask


def _key_ranges(start: int, end: int, mask):
    """The runs ``[(lo, hi)]`` of key rows that the query rows ``[start,
    end)`` can see any of: causal (``mask`` None) the prefix that ends with
    their last row; under a ``BlockMask`` the noisy rows of their own
    diffusion blocks and the clean rows of every block up to their last
    one's (a noisy row's own block among them is masked away again)."""
    if mask is None:
        return [(0, end)]
    length, unit = mask
    whole = lambda n: -(-n // unit) * unit  # noqa: E731  up to whole blocks
    runs, reach = [], 0  # ``reach``: the clean rows the last row of each copy sees
    if start < length:  # noisy rows: their own blocks of the noisy copy, earlier clean ones
        last = min(end, length)
        runs.append((start // unit * unit, whole(last)))
        reach = (last - 1) // unit * unit
    if end > length:  # clean rows: the clean blocks up to their own
        reach = max(reach, whole(end - length))
    if reach:
        runs.append((length, length + reach))
    return runs


def _rows_of(x, runs):
    """The runs' rows of ``x [B, rows, ...]``, side by side."""
    parts = [x[:, lo:hi] for lo, hi in runs]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _add_rows(total, part, runs):
    """``total`` with ``part``'s rows added at the runs they were taken from."""
    if len(runs) == 1:
        return total.at[:, runs[0][0]:runs[0][1]].add(part)
    at = 0
    for lo, hi in runs:
        total = total.at[:, lo:hi].add(part[:, at:at + hi - lo])
        at += hi - lo
    return total


def _visible(rows, keys, mask):
    """``[rows, keys]``: whether query row ``rows[i]`` sees key row
    ``keys[j]`` under the ``BlockMask``."""
    length, unit = mask
    rows, keys = rows[:, None], keys[None, :]
    row_block, key_block = rows % length // unit, keys % length // unit
    return jnp.where(
        rows < length,
        jnp.where(keys < length, key_block == row_block, key_block < row_block),
        (keys >= length) & (key_block <= row_block))


def _block_scores(q, k, first_row: int, runs=None, mask=None):
    """Masked, scaled scores of query rows ``first_row ...`` against the
    keys they can see any of (``_key_ranges``; causal: the prefix that ends
    with their last row), float32: ``q [B, rows, KV, G, D]``,
    ``k [B, keys, KV, D]`` -> ``[B, KV, G, rows, keys]``."""
    scores = jnp.einsum("bqkgd,bnkd->bkgqn", q, k,
                        preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    if mask is not None:
        rows = first_row + jnp.arange(q.shape[1])
        keys = jnp.concatenate([jnp.arange(lo, hi) for lo, hi in runs])
        return jnp.where(_visible(rows, keys, mask), scores, -jnp.inf)
    row = first_row + jnp.arange(q.shape[1])[:, None]
    return jnp.where(row >= jnp.arange(k.shape[1])[None, :], scores, -jnp.inf)


def _blocks(length: int, block: int):
    return [(start, min(start + block, length)) for start in range(0, length, block)]


def _attention_forward(q, k, v, block, mask=None):
    """``(out, log-sum-exp of every row's scores [B, KV, G, S])``."""
    out, lse = [], []
    for start, end in _blocks(q.shape[1], block):
        rows = q[:, start:end]
        if out:  # one block's scores at a time: start when the last is done
            rows, out[-1] = lax.optimization_barrier((rows, out[-1]))
        runs = _key_ranges(start, end, mask)
        scores = _block_scores(rows, _rows_of(k, runs), start, runs, mask)
        top = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - top)
        total = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bkgqn,bnkd->bqkgd", p.astype(v.dtype), _rows_of(v, runs),
                       preferred_element_type=jnp.float32)
        out.append((o / jnp.moveaxis(total, 3, 1)).astype(q.dtype))
        lse.append((top + jnp.log(total))[..., 0])
    return jnp.concatenate(out, axis=1), jnp.concatenate(lse, axis=-1)


def causal_attention(q, k, v, block: int):
    """Causal softmax attention with grouped queries:
    ``q [B, S, KV, G, D]`` (G query heads share a key-value head),
    ``k, v [B, S, KV, D]`` -> ``[B, S, KV, G, D]``. Where
    ``attention_pallas.dispatchable`` says so (TPU backend, not under
    ``vmap``, bfloat16, a head dim and a length of whole kernel blocks that
    ``attention_pallas.plan_for`` has a plan for) the fused kernels under
    that plan (block from the length, query heads a grid step from head dim,
    group and length); else ``blocked_causal_attention`` at ``block`` query
    rows. Both are the same arithmetic: bfloat16 operands, every product
    accumulated in float32, ``D^-0.5`` on the float32 scores (or, where it
    is a power of two, on the bfloat16 keys: the same numbers), float32
    softmax statistics, ``dp - delta`` in float32."""
    if attention_pallas.dispatchable(q, k):
        return attention_pallas.attention(
            q, k, v, attention_pallas.plan_for(q.shape, k.shape, q.dtype))
    return blocked_causal_attention(q, k, v, block)


def block_diffusion_attention(q, k, v, block: int, mask: BlockMask):
    """``causal_attention`` under block-diffusion training's mask: the rows
    of ``q, k, v`` are a noisy copy of the sequence beside the clean one
    (``BlockMask``: which row sees which; never a dense ``[2L, 2L]`` array).
    The fused kernels where they have a plan for the shape under the mask,
    both passes skipping every block of keys the mask empties; else
    ``blocked_masked_attention``, and on a TPU that is said aloud: the plain
    path keeps a block's float32 scores in HBM."""
    if attention_pallas.dispatchable(q, k, mask):
        with jax.named_scope("blockdiff_attention_kernels"):
            return attention_pallas.attention(
                q, k, v, attention_pallas.plan_for(q.shape, k.shape, q.dtype, mask),
                False, mask)
    if jax.default_backend() == "tpu":
        warnings.warn(
            f"block-diffusion attention of q {tuple(q.shape)} {q.dtype} under {mask} "
            "has no kernel plan: the plain blocked path runs")
    return blocked_masked_attention(q, k, v, block, mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def blocked_causal_attention(q, k, v, block: int):
    """``causal_attention`` in plain JAX, one block of query rows' scores
    alive at a time (the CPU's path, and the kernels' oracle). One softmax a
    row; a block meets only the keys up to its own last row, so the work is
    a little over half the square. The backward pass is its own: it keeps
    the output and each row's log-sum-exp, recomputes a block's
    probabilities from them, and takes the softmax's backward in float32
    (``dp`` accumulated in float32: as the transpose of a bfloat16 product
    it would be rounded to bfloat16 before the subtraction that cancels
    most of it)."""
    return _attention_forward(q, k, v, block)[0]


def _attention_fwd(q, k, v, block, mask=None):
    out, lse = _attention_forward(q, k, v, block, mask)
    return out, (q, k, v, out, lse)


def _attention_bwd(block, residuals, d_out, mask=None):
    q, k, v, out, lse = residuals
    # per row, sum(d_out * out): what the softmax's backward subtracts
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.moveaxis(delta, 1, 3)  # [B, S, KV, G] -> [B, KV, G, S]
    dq = []
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    for start, end in _blocks(q.shape[1], block):
        rows = slice(start, end)
        runs = _key_ranges(start, end, mask)
        # one block's scores at a time: start when the last block's sums are in
        do, dk, dv = lax.optimization_barrier((d_out[:, rows], dk, dv))
        p = jnp.exp(_block_scores(q[:, rows], _rows_of(k, runs), start, runs, mask)
                    - lse[..., rows, None])
        dp = jnp.einsum("bqkgd,bnkd->bkgqn", do, _rows_of(v, runs),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., rows, None]) * q.shape[-1] ** -0.5
        ds = ds.astype(q.dtype)
        dq.append(jnp.einsum("bkgqn,bnkd->bqkgd", ds, _rows_of(k, runs)))
        dk = _add_rows(dk, jnp.einsum(
            "bkgqn,bqkgd->bnkd", ds, q[:, rows], preferred_element_type=jnp.float32), runs)
        dv = _add_rows(dv, jnp.einsum(
            "bkgqn,bqkgd->bnkd", p.astype(do.dtype), do,
            preferred_element_type=jnp.float32), runs)
    return jnp.concatenate(dq, axis=1), dk.astype(k.dtype), dv.astype(v.dtype)


blocked_causal_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def blocked_masked_attention(q, k, v, block: int, mask: BlockMask):
    """``blocked_causal_attention`` under a ``BlockMask``: a block of query
    rows meets the keys it can see any of (its own diffusion blocks' noisy
    rows, the clean rows up to its last block's) and no others, so the work
    is that of two causal sequences and not of one of twice the length; the
    backward pass is the same one."""
    return _attention_forward(q, k, v, block, mask)[0]


blocked_masked_attention.defvjp(
    _attention_fwd,
    lambda block, mask, residuals, d_out: _attention_bwd(block, residuals, d_out, mask))


class Attention(nn.Module):
    """Causal grouped-query attention with an RMSNorm over each head's dims
    of q and of k (learned scale) before the rotary embedding. From the
    model's configuration: ``head_dim`` (0: ``hidden // heads``),
    ``rotary_dim`` (the leading dims of a head that the rotary embedding
    turns; None: all; 0: none, a model without a positional embedding),
    ``qk_norm`` (False: q and k as projected, no norm and no scale of
    theirs), ``output_gate`` (``q_proj`` is twice as wide, each head's
    second half a gate: the attention's output times its sigmoid, before
    ``out_proj``) and ``zero_centred_norms`` (``RMSNorm``).
    ``diffusion_block`` not 0: block-diffusion training, the rows a noisy
    copy of the sequence beside the clean one (``2 L`` rows, diffusion blocks
    of that many positions): row ``r`` is at position ``r mod L`` for the
    rotary embedding, and which row sees which is ``BlockMask(L,
    diffusion_block)`` (``block_diffusion_attention``), not causal; the
    layer is then found under the scope ``blockdiff_attention``."""

    hidden: int
    heads: int
    kv_heads: int
    eps: float
    rope_theta: float
    block: int = 512
    dtype: Any = jnp.bfloat16
    head_dim: int = 0
    rotary_dim: "int | None" = None
    output_gate: bool = False
    zero_centred_norms: bool = False
    qk_norm: bool = True
    diffusion_block: int = 0

    @nn.compact
    def __call__(self, x):
        scope = "blockdiff_attention" if self.diffusion_block else "lfm2_attention"
        with jax.named_scope(scope):
            batch, length, _ = x.shape
            d = self.head_dim or self.hidden // self.heads
            mask = positions = None
            if self.diffusion_block:
                mask = BlockMask(length // 2, self.diffusion_block)
                with jax.named_scope("mpi4dl_part_qk_prep"):
                    positions = jnp.arange(length) % mask.length
            with jax.named_scope("mpi4dl_part_proj"):
                x = x.astype(self.dtype)
                q = linear(self.heads * d * (1 + self.output_gate), self.dtype, "q_proj")(x)
                k = linear(self.kv_heads * d, self.dtype, "k_proj")(x)
                v = linear(self.kv_heads * d, self.dtype, "v_proj")(x)
            with jax.named_scope("mpi4dl_part_qk_prep"):
                if self.output_gate:
                    q, gate = jnp.split(
                        q.reshape(batch, length, self.heads, 2 * d), 2, axis=-1)
                else:
                    q = q.reshape(batch, length, self.heads, d)
                k = k.reshape(batch, length, self.kv_heads, d)
                v = v.reshape(batch, length, self.kv_heads, d)

                def placed(t, name):  # a head's norm, then its rotation
                    if self.qk_norm:
                        t = RMSNorm(self.eps, self.zero_centred_norms, name=name)(t)
                    if self.rotary_dim == 0:
                        return t
                    return rope(t, self.rope_theta, self.rotary_dim, positions)

                q, k = placed(q, "q_layernorm"), placed(k, "k_layernorm")
                q = q.astype(self.dtype).reshape(
                    batch, length, self.kv_heads, self.heads // self.kv_heads, d)
                k = k.astype(self.dtype)
            with jax.named_scope("mpi4dl_part_attn_core"):
                if mask is None:
                    out = causal_attention(q, k, v, self.block)
                else:
                    out = block_diffusion_attention(q, k, v, self.block, mask)
                out = out.reshape(batch, length, self.heads * d)
            if self.output_gate:
                with jax.named_scope("mpi4dl_part_gates_norms"):
                    out = out * jax.nn.sigmoid(gate.reshape(out.shape))
            with jax.named_scope("mpi4dl_part_proj"):
                return linear(self.hidden, self.dtype, "out_proj")(out)


# -- feed-forwards -----------------------------------------------------------


def _kept(x):
    """``x`` under the name "cell" remat keeps (``train._cell_ckpt``): the
    cell's replay computes nothing that only led to ``x``. It holds only
    where every reader of the value reads what this returns, so a value is
    named where it is made, and it is the value an activation is taken *of*,
    never an activation's own result: ``logistic``'s, ``exp``'s and
    ``softmax``'s derivatives read the unnamed result inside their own rules
    and would have the replay make it again, with all that led to it."""
    return checkpoint_name(x, KERNEL_RESIDUAL)


class SwiGLU(nn.Module):
    """``w2(silu(w1 x) * w3 x)``; ``w1 x`` and ``w3 x`` are ``_kept``."""

    hidden: int
    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("mpi4dl_part_proj"):
            x = x.astype(self.dtype)
            gate = nn.silu(_kept(linear(self.width, self.dtype, "w1")(x)))
            return linear(self.hidden, self.dtype, "w2")(
                gate * _kept(linear(self.width, self.dtype, "w3")(x)))


class SquaredReLU(nn.Module):
    """``w2(relu(w1 x)^2)``: Nemotron-H's feed-forward (``relu2``), no gate;
    ``w1 x`` is ``_kept``."""

    hidden: int
    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("mpi4dl_part_proj"):
            up = nn.relu(_kept(linear(self.width, self.dtype, "w1")(x.astype(self.dtype))))
            return linear(self.hidden, self.dtype, "w2")(jnp.square(up))


def _in_token_order(a, inverse, lo: int):
    """For every token-expert pair in token order its row of ``a``, which
    holds the rows of the sorted pairs ``lo ... lo + len(a)``; zeros for a
    pair sorted outside them (``inverse[j]`` is where pair ``j`` was sorted
    to). A gather."""
    at = inverse - lo
    if a.shape[0] == inverse.shape[0]:  # every pair's row is here
        return a[at]
    inside = (at >= 0) & (at < a.shape[0])
    return jnp.where(inside[:, None], a[jnp.clip(at, 0, a.shape[0] - 1)], 0)


@jax.custom_vjp
def _pair_weights(weights, pairs, inverse, lo):
    """``weights [P, 1]`` (a column, token order) at the pairs ``pairs`` of
    a range of the sorted rows from ``lo`` on. Both passes are gathers (a
    gather's own transpose is a scatter-add, which the chip serialises); the
    backward is as wide as all the pairs, of one column."""
    return weights[pairs]


_pair_weights.defvjp(
    lambda weights, pairs, inverse, lo: (weights[pairs], (inverse, lo)),
    lambda res, ct: (_in_token_order(ct, *res), None, None, None),
)


def _by_token(pairs, inverse, lo, k: int):
    """How a range's rows lie in token order, for ``_sum_by_token``:
    ``(token of every row in that order, the permutation that puts the rows
    in it, every token's first row there, whether it has one)``. ``pairs``
    are the range's token-expert pairs (sorted rows ``lo ...``; pair ``j`` is
    token ``j // k``'s), so their rising order is token order, and a token's
    first row is the count of the range's pairs of the tokens before it:
    a running sum over what ``inverse`` says of each pair, no search."""
    width = pairs.shape[0]
    sorted_pairs, order = lax.sort_key_val(pairs, jnp.arange(width, dtype=jnp.int32))
    at = inverse - lo
    count = jnp.sum(((at >= 0) & (at < width)).reshape(-1, k), axis=1, dtype=jnp.int32)
    return tuple(map(_kept, (
        sorted_pairs // k, order, jnp.cumsum(count) - count, count > 0)))


def _sum_by_token(rows, scale, by_token, k: int):
    """``[tokens, features]`` float32: for every token the sum of its rows
    among ``rows [n, features]``, each times its entry of ``scale [n, 1]``
    (float32; None: as they are). The rows are those of ``n`` token-expert
    pairs; ``by_token`` is ``_by_token``'s. At the width of the rows, not of
    all the pairs: the rows are put in token order, where a token's rows
    (``k`` at most) lie side by side; ``k`` shifted adds give each row the
    sum from itself to its token's last; every token then reads its first
    row's. No scatter: two gathers, of ``n`` and of ``tokens`` rows."""
    token, order, first, any_row = by_token
    n = rows.shape[0]
    ordered = jnp.pad(rows[order], ((0, k - 1), (0, 0)))
    weight = None if scale is None else jnp.pad(scale[order], ((0, k - 1), (0, 0)))
    after = jnp.pad(token, (0, k - 1), constant_values=-1)

    def shifted(d):
        term = ordered[d:d + n].astype(jnp.float32)
        if weight is not None:
            term = term * weight[d:d + n]
        return jnp.where((after[d:d + n] == token)[:, None], term, 0) if d else term

    run = functools.reduce(jnp.add, map(shifted, range(k)))
    return jnp.where(any_row[:, None], run[jnp.minimum(first, n - 1)], 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _token_rows(x, pairs, by_token, k: int):
    """Row ``pairs[i] // k`` of ``x`` for every i: the tokens' rows for a
    range of their token-expert pairs. Backward: each token's sum of its
    pairs' cotangents (``_sum_by_token``)."""
    return x[pairs // k]


_token_rows.defvjp(
    lambda x, pairs, by_token, k: (x[pairs // k], by_token),
    lambda k, by_token, ct: (
        _sum_by_token(ct, None, by_token, k).astype(ct.dtype), None, None),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _token_sums(rows, scale, pairs, by_token, k: int):
    """``_sum_by_token`` of a range's rows, each times its pair's weight;
    backward: every row its token's cotangent, a gather."""
    return _sum_by_token(rows, scale, by_token, k)


def _token_sums_bwd(k, residuals, ct):
    rows, scale, pairs = residuals
    ct = ct[pairs // k]
    return ((ct * scale).astype(rows.dtype),
            jnp.sum(ct * rows.astype(jnp.float32), axis=-1, keepdims=True), None, None)


_token_sums.defvjp(
    lambda rows, scale, pairs, by_token, k: (
        _sum_by_token(rows, scale, by_token, k), (rows, scale, pairs)),
    _token_sums_bwd)


def _prefix_rows(pairs: int, held: int, experts: int) -> int:
    """How many of the ``pairs`` sorted token-expert pair rows the expert
    layer always computes: twice the even share of a chip that holds
    ``held`` of ``experts``, all of them where that is half or more."""
    return min(pairs, 2 * pairs * held // experts)


def _grouped_ffn(rows, experts, groups):
    """Every row through its group's expert. The arrays an expert has say
    which feed-forward it is: three (``w1, w3, w2``) the gated SiLU,
    ``(silu(x w1) * x w3) w2``; two (``w1, w2``) the plain squared ReLU,
    ``relu(x w1)^2 w2``. ``x w1`` and ``x w3`` are ``_kept`` as the products
    give them, at the experts' width: the activation and the gate's product
    over them are cheap to make again and would double the bytes."""
    with jax.named_scope("mpi4dl_part_expert_products"):
        if len(experts) == 3:
            w1, w3, w2 = experts
            gate = nn.silu(_kept(lax.ragged_dot(rows, w1, groups)))
            return lax.ragged_dot(gate * _kept(lax.ragged_dot(rows, w3, groups)), w2, groups)
        w1, w2 = experts
        return lax.ragged_dot(
            jnp.square(nn.relu(_kept(lax.ragged_dot(rows, w1, groups)))), w2, groups)


def _range_ffn(bounds, x, weights, experts, order, inverse, sizes):
    """What a range of the sorted pair rows adds to the expert layer's
    result, ``[tokens, hidden]`` float32. ``bounds = (lo, width, own)``: the
    rows ``[lo, lo + width)`` are computed and those from ``own`` on are the
    range's (``own > lo`` where the last of several ranges of one width is
    moved back to end with the rows; ``lo`` and ``own`` may be traced). For
    them: their tokens' rows of ``x``, the grouped products (``_grouped_ffn``
    over ``experts``, the held experts' stacked arrays) over the part of
    each expert's group that lies in the range, each row times its pair's
    weight, and every token's sum of its rows. All of it at the width of
    the range: nothing here is as wide as all the pairs but one column of
    weights. ``sizes [held]`` are the groups, so the held pairs are the
    sorted rows ``[0, sum(sizes))``. ``_by_token``'s arrays, the gathered
    rows and the last product's output (which the weights' gradient reads)
    are ``_kept``; that holds for the prefix range, whose forward is the
    cell's own. In the ranges past it the names do nothing: they sit in the
    conditionals' branches, which the cell's checkpoint takes whole, and
    ``_ranges_bwd`` runs their forward once more as before."""
    lo, width, own = bounds
    k = weights.shape[1]
    ends = jnp.cumsum(sizes)
    groups = jnp.clip(ends, lo, lo + width) - jnp.clip(ends - sizes, lo, lo + width)
    # rows past the last group are not computed: keep what they hold out of
    # both passes
    at = lo + jnp.arange(width)
    held = ((at >= own) & (at < ends[-1]))[:, None]
    pairs = lax.dynamic_slice_in_dim(order, lo, width)
    by_token = _by_token(pairs, inverse, lo, k)
    rows = _kept(jnp.where(held, _token_rows(x, pairs, by_token, k), 0))
    y = _grouped_ffn(rows, experts, groups)
    scale = _pair_weights(weights.reshape(-1, 1), pairs, inverse, lo)
    return _token_sums(_kept(jnp.where(held, y, 0)), scale, pairs, by_token, k)


def _over_the_rest(prefix, pairs, held_pairs, step, carry):
    """``carry`` after ``step(bounds, carry)`` for every range of the sorted
    pair rows past ``prefix`` that holds held pairs (there are
    ``held_pairs > prefix`` of them, known on the device): ranges as wide as
    the prefix (the rest itself where that is narrower), the last moved back
    to end with the rows. One range: called once. More: a loop of as many
    trips as the held pairs reach, so that a layer that holds a sixteenth of
    its experts does not keep buffers for seven eighths of its rows."""
    width = min(prefix, pairs - prefix)
    most = -(-(pairs - prefix) // width)
    if most == 1:
        return step((prefix, width, prefix), carry)

    def one(i, carry):
        own = prefix + i * width
        return step((jnp.minimum(own, pairs - width), width, own), carry)

    return lax.fori_loop(0, (held_pairs - prefix + width - 1) // width, one, carry)


def _add_overflow(prefix, out, operands):
    """``out`` plus what the sorted pair rows from ``prefix`` on add to it,
    where the held pairs (``sum(sizes)`` of them) reach past ``prefix``;
    else ``out`` as it is. Decided on the device. Behind a barrier: without
    it the compiler moves what reads the result (a cast) into both
    branches, and the branch not taken is no longer free."""
    *_, order, _, sizes = operands
    held_pairs = jnp.sum(sizes)
    return lax.optimization_barrier(lax.cond(
        held_pairs > prefix,
        lambda out, *operands: _over_the_rest(
            prefix, order.shape[0], held_pairs,
            lambda bounds, out: out + _range_ffn(bounds, *operands), out),
        lambda out, *operands: out, out, *operands))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _two_ranges(prefix, x, weights, experts, order, inverse, sizes):
    """The expert layer's result from its sorted pair rows in ranges:
    ``[0, prefix)`` always, the rest (``_over_the_rest``: one range as wide
    as the prefix or narrower, or a loop of them) only in a step whose held
    pairs overflow the prefix; every held pair is computed exactly once
    either way. The backward is the prefix's own (``jax.vjp``, its residuals
    at the prefix's width), then a second conditional that adds the other
    ranges' gradients, whose forward it runs once more (the rare step
    pays that; it is the step that cost the whole width before).

    Why both conditionals hand a running sum through (the result, then the
    gradients) where the plain shape would be ``prefix + cond(rest,
    zeros)``: compiled for a v5e chip at LFM2-8B-A1B's widths, the branch
    not taken is then its bare parameter, no copy and nothing written. With
    zeros it writes 67 MB of result and 210 MB of gradients a layer and
    reads them back to add them: 5 ms a step of 320 on the chip, and 0.4
    GiB more of temporaries (PERF.md section 6, PR 36). The barriers after
    both conditionals keep it so: left free, the compiler moved the casts
    that read the gradients into the branches, where the branch not taken
    then wrote them out in float32 (14 ms a step). And why a backward of
    its own at all: ``lax.cond`` differentiated by JAX makes the branch
    taken write zeros for every residual of the other, 1.04 GB a layer."""
    operands = (x, weights, experts, order, inverse, sizes)
    return _add_overflow(prefix, _range_ffn((0, prefix, 0), *operands), operands)


def _ranges_fwd(prefix, *operands):
    floats, ints = operands[:3], operands[3:]
    out, pull = jax.vjp(lambda *f: _range_ffn((0, prefix, 0), *f, *ints), *floats)
    return _add_overflow(prefix, out, operands), (pull, operands)


def _ranges_bwd(prefix, residuals, ct):
    pull, operands = residuals
    floats, ints = operands[:3], operands[3:]
    order, _, sizes = ints
    held_pairs = jnp.sum(sizes)

    def add_overflow(grads, floats, ct):
        def add(bounds, grads):
            more = jax.vjp(lambda *f: _range_ffn(bounds, *f, *ints), *floats)[1](ct)
            return jax.tree.map(jnp.add, grads, more)

        return _over_the_rest(prefix, order.shape[0], held_pairs, add, grads)

    grads = lax.optimization_barrier(lax.cond(
        held_pairs > prefix, add_overflow,
        lambda grads, floats, ct: grads, pull(ct), floats, ct))
    return (*grads, None, None, None)


_two_ranges.defvjp(_ranges_fwd, _ranges_bwd)
# under ``jit`` a model's expert layers of one shape are traced,
# differentiated and lowered once, not once each: without it the token
# cell's first step took 3.8 s longer than with one range (PERF.md, PR 36)
_ranges_ffn = jax.jit(_two_ranges, static_argnums=0)


def _whole_tiles(experts, tile: int = 256):
    """The held experts' arrays with the experts' width padded by zeros to
    whole ``tile``s: columns of ``w1`` (and ``w3``), rows of ``w2``, which
    add nothing to the result (``relu(0)^2`` and ``silu(0) * 0`` are 0) and
    whose gradients the padding's own backward drops. The chip's grouped
    products run on whole tiles of 256 columns: at 8 experts x 768 rows and
    hidden 2688 a width of 1856 (Nemotron-H's, 7.25 tiles) reads 4.52 ms
    forward and 14.55 gradient, 1920 the same, 2048 reads 1.89 and 6.91, and
    1792 (LFM2's, 7 tiles) 2.45 and 8.55 (my chip run, PR 39). Widths of
    whole tiles pass as they are, and so do widths under one tile (the CPU
    tests' sizes, where the padding would be most of the work)."""
    width = experts[0].shape[-1]
    pad = -width % tile
    if not pad or width < tile:
        return experts
    *into, out = experts
    return (*(jnp.pad(w, ((0, 0), (0, 0), (0, pad))) for w in into),
            jnp.pad(out, ((0, 0), (0, pad), (0, 0))))


class _ExpertWeights(nn.Module):
    """The held experts' arrays, stacked: ``(w1, w3, w2)`` of a gated
    feed-forward, ``(w1, w2)`` of a plain one."""

    held: int
    hidden: int
    width: int
    gated: bool = True

    @nn.compact
    def __call__(self):
        # over the input axis alone: the leading axis counts experts
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=1, out_axis=2, batch_axis=0)
        into = (self.held, self.hidden, self.width)
        w1 = self.param("w1", init, into)
        w3 = (self.param("w3", init, into),) if self.gated else ()
        return (w1, *w3, self.param("w2", init, (self.held, self.width, self.hidden)))


class ExpertFFN(nn.Module):
    """A share of an expert layer: routes every token over ALL ``experts``
    (``scoring`` "sigmoid": sigmoid scores, or "softmax": a softmax over all
    the experts; the top ``per_token`` chosen on score + ``expert_bias``;
    weights the scores themselves, normalised to sum 1 and scaled) and
    computes the part of the result that the ``held`` experts from ``first``
    on give. What the other experts would add is left out: on the chips
    that share this layer it is their part of the sum. ``activation``:
    "swiglu", every expert the gated SiLU ``(silu(x w1) * x w3) w2``, or
    "relu2", the plain squared ReLU ``relu(x w1)^2 w2`` (two arrays an
    expert). ``shared_width`` not 0: a shared expert that every token takes,
    a feed-forward of that width and the experts' form, with ``shared_gate``
    times the sigmoid of a gate of its own (``x w_s``); every chip that
    shares the layer computes it alike, and it is added once here.

    No token-expert pair on a held expert is ever dropped. The ``P = tokens
    x per_token`` pairs are sorted by expert, the held ones first, so the
    ``n`` held pairs are the sorted rows ``[0, n)``, and ``jax.lax.
    ragged_dot`` multiplies each group by its expert (on a TPU a
    grouped-matmul kernel that skips the rows past the last group). The
    rows are computed in ranges by one function (``_range_ffn``, which
    gathers, multiplies, weights and sums back by token at the width of its
    range): the prefix ``[0, C)`` always, and the rows from ``C`` on only in
    a step whose own count says held pairs lie there (``n > C``, decided on
    the device: ``_ranges_ffn``), in ranges of ``C`` rows, as many as the
    held pairs reach (LFM2's quarter share: one; a sixteenth of 512 experts:
    up to seven, one loop). ``C = min(P, 2 P held / experts)``
    (``_prefix_rows``), twice the even share, so a chip whose experts draw
    up to twice their share of the routing works on ``C`` rows and not
    ``P``; a step in which every token picks ``per_token`` held experts is
    still computed in full. A layer that holds half or all of its experts
    has ``C == P``: one range, no conditional.

    The router (scores, choice, weights) is float32: a near-tie in the
    top-k that fell otherwise in bfloat16 would move a whole token's
    output; the rows and products are ``dtype``, the weighted sum over a
    token's pairs float32. Sows into the ``counters`` collection
    ``expert_pairs [held]``, the token-expert pairs each held expert
    computed, and ``prefix_alone``, 1 where ``n <= C``.

    What a training step executes of it under "cell" remat
    (``train._cell_ckpt``): the router's product three times (forward and
    two gradients), ``top_k`` and each of the three sorts once, the row
    gather once, every grouped product three times (forward and two
    gradients: nine calls a gated layer, six a plain one). Their results
    are ``_kept`` where they are made (the router's product before the
    scores are taken of it, the choice as ``top_k`` gives it, the chosen
    scores, ``sizes``, ``order``, ``inverse``; the rest in ``_range_ffn`` and
    ``_grouped_ffn``), so the replay of the cell makes again only what is
    elementwise over them: the scores, the weights' normalisation,
    ``group``, the activations, a gated shared expert's gate and last
    product. Under a bare ``jax.checkpoint`` each ran once more a step."""

    hidden: int
    width: int
    experts: int
    held: int
    first: int
    per_token: int
    norm_topk: bool = True
    scaling: float = 1.0
    expert_bias: bool = True
    dtype: Any = jnp.bfloat16
    scoring: str = "sigmoid"
    shared_width: int = 0
    activation: str = "swiglu"
    shared_gate: bool = True

    @nn.compact
    def __call__(self, x):
        """``x``: the normalised input in float32, ``[batch, positions, hidden]``."""
        with jax.named_scope("lfm2_moe"):
            with jax.named_scope("mpi4dl_part_router"):
                shape = x.shape
                x = x.reshape(-1, self.hidden)
                tokens, k = x.shape[0], self.per_token
                router = _Kernel((self.hidden, self.experts), name="gate")()
                score = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[self.scoring]
                scores = score(_kept(
                    jnp.matmul(x, router, precision=lax.Precision.HIGHEST)))
                choose_on = scores
                if self.expert_bias:
                    choose_on = scores + self.param(
                        "expert_bias", nn.initializers.zeros, (self.experts,))
                chosen = _kept(lax.top_k(choose_on, k)[1])
                weights = _kept(jnp.take_along_axis(scores, chosen, axis=-1))
                if self.norm_topk:
                    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
                weights = weights * self.scaling

            # token-expert pairs sorted by expert, the held experts' first
            with jax.named_scope("mpi4dl_part_dispatch"):
                local = chosen - self.first
                group = jnp.where((local >= 0) & (local < self.held), local, self.held)
                sizes = _kept(jnp.sum(
                    group[..., None] == jnp.arange(self.held), axis=(0, 1), dtype=jnp.int32))
                pairs = tokens * k
                prefix = _prefix_rows(pairs, self.held, self.experts)
                if not self.is_initializing():  # ``init`` returns parameters alone
                    self.sow(COUNTERS, "expert_pairs", sizes)
                    self.sow(COUNTERS, "prefix_alone",
                             (jnp.sum(sizes) <= prefix).astype(jnp.int32))
                order = _kept(lax.sort_key_val(
                    group.reshape(-1), jnp.arange(pairs, dtype=jnp.int32))[1])
                inverse = _kept(jnp.argsort(order))

            gated = {"swiglu": True, "relu2": False}[self.activation]
            with jax.named_scope("mpi4dl_part_expert_products"):
                experts = _whole_tiles(tuple(w.astype(self.dtype) for w in _ExpertWeights(
                    self.held, self.hidden, self.width, gated, name="experts")()))
            # the ranges' gathers, sums by token and conditionals; the grouped
            # products inside them open their own part (``_grouped_ffn``)
            with jax.named_scope("mpi4dl_part_dispatch"):
                operands = (x.astype(self.dtype), weights, experts, order, inverse, sizes)
                if prefix < pairs:
                    out = _ranges_ffn(prefix, *operands)
                else:
                    out = _range_ffn((0, prefix, 0), *operands)
                out = out.astype(self.dtype)
            if self.shared_width:
                # a dense feed-forward (it opens the part again around itself)
                with jax.named_scope("shared_expert"), jax.named_scope("mpi4dl_part_proj"):
                    gate = jax.nn.sigmoid(linear(1, self.dtype, "shared_expert_gate")(
                        operands[0])) if self.shared_gate else None
                    shared = (SwiGLU if gated else SquaredReLU)(
                        self.hidden, self.shared_width, self.dtype,
                        name="shared_expert")(operands[0])
                    out = out + (shared if gate is None else gate * shared)
            with jax.named_scope("mpi4dl_part_dispatch"):  # back in the input's shape
                return out.reshape(shape)


def step_counters(counted: dict) -> dict:
    """What a training step reports of the counts its cells sowed
    (``{name: [one array per sowing module]}``, summed over the data axis):
    from the expert layers' ``expert_pairs``, ``moe_pairs`` (token-expert
    pairs computed on held experts, all expert layers together) and
    ``moe_max_share`` (the busiest held expert's share of its own layer's
    pairs; even routing over ``held`` experts reads ``1 / held``); from
    their ``prefix_alone``, ``moe_narrow_layers`` (the expert layers that
    computed the prefix of their sorted rows alone; each data shard decides
    for its own tokens and counts for itself). Device scalars: the step
    never reads them on the host."""
    if "expert_pairs" not in counted:
        return {}
    pairs = jnp.stack(counted["expert_pairs"]).astype(jnp.float32)  # [layers, held]
    per_layer = jnp.sum(pairs, axis=1, keepdims=True)
    return {
        "moe_pairs": jnp.sum(pairs),
        "moe_max_share": jnp.max(pairs / jnp.maximum(per_layer, 1.0)),
        "moe_narrow_layers": jnp.sum(jnp.stack(counted["prefix_alone"]).astype(jnp.float32)),
    }
