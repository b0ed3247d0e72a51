"""Sequence operators: what a token model's layers are made of.

``ops/layers.py`` holds the image classifiers' operators (NHWC convs, pools,
BatchNorm); this file holds RMSNorm, the rotary embedding, a causal
depthwise conv1d, causal grouped-query attention and an expert layer that
holds a share of the experts. The grouped matrix products are
``jax.lax.ragged_dot`` over the token-expert pairs sorted by expert. The
``S x S`` scores of a long sequence never exist at once (32 heads x 8192^2
floats are 8.6 GB): on a TPU, at the shapes ``ops/attention_pallas.py``
takes, ``causal_attention`` is that module's fused kernels, which keep a
block's scores in VMEM; everywhere else (the CPU, a vmapped trace, a length
that is not whole blocks, another head dim) it is plain JAX, a block of
query rows at a time, each block recomputed in the backward pass. The rest
is plain JAX everywhere.

Activations are ``[batch, positions, features]``. Each module computes in
its ``dtype`` (bfloat16 on the chip) with float32 parameters, float32
normalisation statistics and a float32 router.

The device trace finds the three mechanisms by ``jax.named_scope``:
``lfm2_moe`` (router, top-k, sort, grouped products, combine),
``lfm2_attention`` and ``lfm2_shortconv``.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.ops import attention_pallas

COUNTERS = "counters"  # the flax collection an expert layer sows its counts into


class RMSNorm(nn.Module):
    """``x / rms(x) * scale`` over the last axis, in float32; the result is
    float32 too (the router reads it so; a matrix product casts it)."""

    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale


def linear(features: int, dtype, name: str) -> nn.Dense:
    """A projection without bias (no layer of the LFM2 family has one)."""
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


def rope(x, theta: float):
    """Rotary embedding of ``x [batch, positions, heads, dim]``, half-split
    pairing, positions 0..S-1 in every row (one document a sequence);
    angles and rotation in float32."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_depthwise_conv1d(x, kernel):
    """``y[t] = sum_j kernel[j] * x[t - (L-1) + j]`` per feature, zeros
    before the start: position t sees t-L+1..t and nothing later.
    ``x [batch, positions, features]``, ``kernel [L, features]``."""
    taps, length = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j] * padded[:, j:j + length] for j in range(taps))


class ShortConv(nn.Module):
    """LFM2's gated short convolution:
    ``B, C, u = split3(in_proj(x)); out_proj(C * conv(B * u))``."""

    hidden: int
    taps: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("lfm2_shortconv"):
            x = x.astype(self.dtype)
            gate_in, gate_out, u = jnp.split(
                linear(3 * self.hidden, self.dtype, "in_proj")(x), 3, axis=-1)
            kernel = _Kernel((self.taps, self.hidden), name="conv")()
            v = causal_depthwise_conv1d(gate_in * u, kernel.astype(self.dtype))
            return linear(self.hidden, self.dtype, "out_proj")(gate_out * v)


class _Kernel(nn.Module):
    """A bare ``kernel`` parameter under a name of its own (LeCun normal
    over the leading axes, as ``nn.Dense`` draws its own)."""

    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(), self.shape)


# -- attention ---------------------------------------------------------------


def _block_scores(q, k, first_row: int):
    """Masked, scaled scores of query rows ``first_row ...`` against the
    prefix that ends with their last row, float32: ``q [B, rows, KV, G, D]``,
    ``k [B, prefix, KV, D]`` -> ``[B, KV, G, rows, prefix]``."""
    scores = jnp.einsum("bqkgd,bnkd->bkgqn", q, k,
                        preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    row = first_row + jnp.arange(q.shape[1])[:, None]
    return jnp.where(row >= jnp.arange(k.shape[1])[None, :], scores, -jnp.inf)


def _blocks(length: int, block: int):
    return [(start, min(start + block, length)) for start in range(0, length, block)]


def _attention_forward(q, k, v, block):
    """``(out, log-sum-exp of every row's scores [B, KV, G, S])``."""
    out, lse = [], []
    for start, end in _blocks(q.shape[1], block):
        rows = q[:, start:end]
        if out:  # one block's scores at a time: start when the last is done
            rows, out[-1] = lax.optimization_barrier((rows, out[-1]))
        scores = _block_scores(rows, k[:, :end], start)
        top = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - top)
        total = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bkgqn,bnkd->bqkgd", p.astype(v.dtype), v[:, :end],
                       preferred_element_type=jnp.float32)
        out.append((o / jnp.moveaxis(total, 3, 1)).astype(q.dtype))
        lse.append((top + jnp.log(total))[..., 0])
    return jnp.concatenate(out, axis=1), jnp.concatenate(lse, axis=-1)


def causal_attention(q, k, v, block: int):
    """Causal softmax attention with grouped queries:
    ``q [B, S, KV, G, D]`` (G query heads share a key-value head),
    ``k, v [B, S, KV, D]`` -> ``[B, S, KV, G, D]``. Where
    ``attention_pallas.dispatchable`` says so (TPU backend, not under
    ``vmap``, bfloat16, head dim 64, a length of whole kernel blocks) the
    fused kernels, whose block is chosen from the length; else
    ``blocked_causal_attention`` at ``block`` query rows. Both are the same
    arithmetic: bfloat16 operands, every product accumulated in float32,
    float32 softmax statistics, ``dp - delta`` in float32."""
    if attention_pallas.dispatchable(q, k):
        return attention_pallas.attention(q, k, v, attention_pallas.block_for(q.shape[1]))
    return blocked_causal_attention(q, k, v, block)


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


def _attention_fwd(q, k, v, block):
    out, lse = _attention_forward(q, k, v, block)
    return out, (q, k, v, out, lse)


def _attention_bwd(block, residuals, d_out):
    q, k, v, out, lse = residuals
    # per row, sum(d_out * out): what the softmax's backward subtracts
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.moveaxis(delta, 1, 3)  # [B, S, KV, G] -> [B, KV, G, S]
    dq = []
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    for start, end in _blocks(q.shape[1], block):
        rows = slice(start, end)
        # one block's scores at a time: start when the last block's sums are in
        do, dk, dv = lax.optimization_barrier((d_out[:, rows], dk, dv))
        p = jnp.exp(_block_scores(q[:, rows], k[:, :end], start)
                    - lse[..., rows, None])
        dp = jnp.einsum("bqkgd,bnkd->bkgqn", do, v[:, :end],
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., rows, None]) * q.shape[-1] ** -0.5
        ds = ds.astype(q.dtype)
        dq.append(jnp.einsum("bkgqn,bnkd->bqkgd", ds, k[:, :end]))
        dk = dk.at[:, :end].add(jnp.einsum(
            "bkgqn,bqkgd->bnkd", ds, q[:, rows], preferred_element_type=jnp.float32))
        dv = dv.at[:, :end].add(jnp.einsum(
            "bkgqn,bqkgd->bnkd", p.astype(do.dtype), do,
            preferred_element_type=jnp.float32))
    return jnp.concatenate(dq, axis=1), dk.astype(k.dtype), dv.astype(v.dtype)


blocked_causal_attention.defvjp(_attention_fwd, _attention_bwd)


class Attention(nn.Module):
    """Causal grouped-query attention with an RMSNorm over each head's dims
    of q and of k (learned scale) before the rotary embedding."""

    hidden: int
    heads: int
    kv_heads: int
    eps: float
    rope_theta: float
    block: int = 512
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("lfm2_attention"):
            batch, length, _ = x.shape
            d = self.hidden // self.heads
            x = x.astype(self.dtype)
            q = linear(self.heads * d, self.dtype, "q_proj")(x)
            k = linear(self.kv_heads * d, self.dtype, "k_proj")(x)
            v = linear(self.kv_heads * d, self.dtype, "v_proj")(x)
            q = q.reshape(batch, length, self.heads, d)
            k = k.reshape(batch, length, self.kv_heads, d)
            v = v.reshape(batch, length, self.kv_heads, d)
            q = rope(RMSNorm(self.eps, name="q_layernorm")(q), self.rope_theta)
            k = rope(RMSNorm(self.eps, name="k_layernorm")(k), self.rope_theta)
            q = q.astype(self.dtype).reshape(
                batch, length, self.kv_heads, self.heads // self.kv_heads, d)
            out = causal_attention(q, k.astype(self.dtype), v, self.block)
            out = out.reshape(batch, length, self.heads * d)
            return linear(self.hidden, self.dtype, "out_proj")(out)


# -- feed-forwards -----------------------------------------------------------


class SwiGLU(nn.Module):
    """``w2(silu(w1 x) * w3 x)``."""

    hidden: int
    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        gate = nn.silu(linear(self.width, self.dtype, "w1")(x))
        return linear(self.hidden, self.dtype, "w2")(
            gate * linear(self.width, self.dtype, "w3")(x))


@jax.custom_vjp
def _permute(x, order, inverse):
    """``x[order]`` for a permutation ``order`` of the rows, ``inverse`` its
    inverse: both passes are gathers (a gather's own transpose is a
    scatter-add, which the chip serialises)."""
    return x[order]


_permute.defvjp(
    lambda x, order, inverse: (x[order], inverse),
    lambda inverse, ct: (ct[inverse], None, None),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pair_rows(x, order, inverse, k: int):
    """Row ``order[i] // k`` of ``x`` for every i: the tokens' rows in the
    order of their sorted token-expert pairs (pair ``j`` belongs to token
    ``j // k``). Backward: the pairs' cotangents back in token order, summed
    over a token's ``k`` pairs; gathers both ways."""
    return x[order // k]


_pair_rows.defvjp(
    lambda x, order, inverse, k: (x[order // k], (inverse, x.shape)),
    lambda k, res, ct: (
        ct[res[0]].reshape(res[1][0], k, res[1][1]).sum(axis=1, dtype=jnp.float32)
        .astype(ct.dtype), None, None),
)


class _ExpertWeights(nn.Module):
    held: int
    hidden: int
    width: int

    @nn.compact
    def __call__(self):
        # over the input axis alone: the leading axis counts experts
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=1, out_axis=2, batch_axis=0)
        return (self.param("w1", init, (self.held, self.hidden, self.width)),
                self.param("w3", init, (self.held, self.hidden, self.width)),
                self.param("w2", init, (self.held, self.width, self.hidden)))


class ExpertFFN(nn.Module):
    """A share of an expert layer: routes every token over ALL ``experts``
    (sigmoid scores; the top ``per_token`` chosen on score + ``expert_bias``;
    weights the scores themselves, normalised to sum 1 and scaled) and
    computes the part of the result that the ``held`` experts from ``first``
    on give. What the other experts would add is left out: on the chips
    that share this layer it is their part of the sum. No token-expert pair
    on a held expert is ever dropped: the pairs are sorted by expert, the
    held ones first, and ``jax.lax.ragged_dot`` multiplies each group by its
    expert (on a TPU a grouped-matmul kernel that skips the rows past the
    last group), so a step in which every token picks ``per_token`` held
    experts is computed in full, and one in which few do costs little.

    The router (scores, choice, weights) is float32: a near-tie in the
    top-k that fell otherwise in bfloat16 would move a whole token's
    output. Sows ``expert_pairs [held]`` into the ``counters`` collection:
    the token-expert pairs each held expert computed."""

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

    @nn.compact
    def __call__(self, x):
        """``x``: the normalised input in float32, ``[batch, positions, hidden]``."""
        with jax.named_scope("lfm2_moe"):
            shape = x.shape
            x = x.reshape(-1, self.hidden)
            tokens, k = x.shape[0], self.per_token
            router = _Kernel((self.hidden, self.experts), name="gate")()
            scores = jax.nn.sigmoid(
                jnp.matmul(x, router, precision=lax.Precision.HIGHEST))
            choose_on = scores
            if self.expert_bias:
                choose_on = scores + self.param(
                    "expert_bias", nn.initializers.zeros, (self.experts,))
            _, chosen = lax.top_k(choose_on, k)
            weights = jnp.take_along_axis(scores, chosen, axis=-1)
            if self.norm_topk:
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
            weights = weights * self.scaling

            # token-expert pairs sorted by expert, the held experts' first
            local = chosen - self.first
            group = jnp.where((local >= 0) & (local < self.held), local, self.held)
            sizes = jnp.sum(
                group[..., None] == jnp.arange(self.held), axis=(0, 1), dtype=jnp.int32)
            if not self.is_initializing():  # ``init`` returns parameters alone
                self.sow(COUNTERS, "expert_pairs", sizes)
            sorted_group, order = lax.sort_key_val(
                group.reshape(-1), jnp.arange(tokens * k, dtype=jnp.int32))
            inverse = jnp.argsort(order)
            held_sorted = (sorted_group < self.held)[:, None]

            w1, w3, w2 = (w.astype(self.dtype) for w in _ExpertWeights(
                self.held, self.hidden, self.width, name="experts")())
            # rows past the last group are not computed: keep what they hold
            # out of both passes
            rows = jnp.where(
                held_sorted, _pair_rows(x.astype(self.dtype), order, inverse, k), 0)
            gate = nn.silu(lax.ragged_dot(rows, w1, sizes))
            y = lax.ragged_dot(gate * lax.ragged_dot(rows, w3, sizes), w2, sizes)
            y = _permute(jnp.where(held_sorted, y, 0), inverse, order)
            y = y.reshape(tokens, k, self.hidden).astype(jnp.float32)
            out = jnp.sum(y * weights[..., None], axis=1)
            return out.astype(self.dtype).reshape(shape)


def step_counters(counted: dict) -> dict:
    """What a training step reports of the counts its cells sowed
    (``{name: [one array per sowing module]}``, summed over the data axis):
    from the expert layers' ``expert_pairs``, ``moe_pairs`` (token-expert
    pairs computed on held experts, all expert layers together) and
    ``moe_max_share`` (the busiest held expert's share of its own layer's
    pairs; even routing over ``held`` experts reads ``1 / held``). Device
    scalars: the step never reads them on the host."""
    if "expert_pairs" not in counted:
        return {}
    pairs = jnp.stack(counted["expert_pairs"]).astype(jnp.float32)  # [layers, held]
    per_layer = jnp.sum(pairs, axis=1, keepdims=True)
    return {
        "moe_pairs": jnp.sum(pairs),
        "moe_max_share": jnp.max(pairs / jnp.maximum(per_layer, 1.0)),
    }
