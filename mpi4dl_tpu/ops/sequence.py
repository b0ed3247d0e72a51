"""Sequence operators: what a token model's layers are made of.

``ops/layers.py`` holds the image classifiers' operators (NHWC convs, pools,
BatchNorm); this file holds RMSNorm, the rotary embedding, a causal
depthwise conv1d, causal grouped-query attention and an expert layer that
holds a share of the experts. The grouped matrix products are
``jax.lax.ragged_dot`` over the token-expert pairs sorted by expert, at the
width of a prefix of the sorted rows that follows from the share held; the
rows past it run only in a step whose routing overflows it. The
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _unsort(y, order, inverse, lo: int):
    """``_in_token_order`` of a range's results ``y``, ``order`` the range's
    part of the sort's permutation. Both passes are gathers (a gather's own
    transpose is a scatter-add, which the chip serialises)."""
    return _in_token_order(y, inverse, lo)


_unsort.defvjp(
    lambda y, order, inverse, lo: (_in_token_order(y, inverse, lo), order),
    lambda lo, order, ct: (ct[order], None, None),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _pair_rows(x, order, inverse, lo: int, k: int):
    """Row ``order[i] // k`` of ``x`` for every i: the tokens' rows in the
    order of a range of their sorted token-expert pairs (pair ``j`` belongs
    to token ``j // k``; ``order`` is the range's part of the permutation,
    from sorted row ``lo`` on). Backward: the pairs' cotangents back in
    token order, summed over a token's ``k`` pairs; gathers both ways."""
    return x[order // k]


_pair_rows.defvjp(
    lambda x, order, inverse, lo, k: (x[order // k], (inverse, x.shape)),
    lambda lo, k, res, ct: (
        _in_token_order(ct, res[0], lo).reshape(res[1][0], k, res[1][1])
        .sum(axis=1, dtype=jnp.float32).astype(ct.dtype), None, None),
)


def _prefix_rows(pairs: int, held: int, experts: int) -> int:
    """How many of the ``pairs`` sorted token-expert pair rows the expert
    layer always computes: twice the even share of a chip that holds
    ``held`` of ``experts``, all of them where that is half or more."""
    return min(pairs, 2 * pairs * held // experts)


def _range_ffn(bounds, x, weights, w1, w3, w2, order, inverse, sizes):
    """What the sorted pair rows ``[lo, hi) = bounds`` add to the expert
    layer's result, ``[tokens, hidden]`` float32: their tokens' rows of
    ``x``, the three grouped products over the part of each expert's group
    that lies in the range, and the weighted sum over each token's pairs
    (a pair outside the range adds zero). ``sizes [held]`` are the groups,
    so the held pairs are the sorted rows ``[0, sum(sizes))``."""
    lo, hi = bounds
    ends = jnp.cumsum(sizes)
    groups = jnp.clip(ends, lo, hi) - jnp.clip(ends - sizes, lo, hi)
    # rows past the last group are not computed: keep what they hold out of
    # both passes
    held = (lo + jnp.arange(hi - lo) < ends[-1])[:, None]
    rows = jnp.where(
        held, _pair_rows(x, order[lo:hi], inverse, lo, weights.shape[1]), 0)
    gate = nn.silu(lax.ragged_dot(rows, w1, groups))
    y = lax.ragged_dot(gate * lax.ragged_dot(rows, w3, groups), w2, groups)
    y = _unsort(jnp.where(held, y, 0), order[lo:hi], inverse, lo)
    y = y.reshape(*weights.shape, -1).astype(jnp.float32)
    return jnp.sum(y * weights[..., None], axis=1)


def _add_overflow(prefix, out, operands):
    """``out`` plus what the sorted pair rows from ``prefix`` on add to it,
    where the held pairs (``sum(sizes)`` of them) reach past ``prefix``;
    else ``out`` as it is. Decided on the device. Behind a barrier: without
    it the compiler moves what reads the result (a cast) into both
    branches, and the branch not taken is no longer free."""
    *_, order, _, sizes = operands
    rest = (prefix, order.shape[0])
    return lax.optimization_barrier(lax.cond(
        jnp.sum(sizes) > prefix,
        lambda out, *operands: out + _range_ffn(rest, *operands),
        lambda out, *operands: out, out, *operands))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _two_ranges(prefix, x, weights, w1, w3, w2, order, inverse, sizes):
    """The expert layer's result from its sorted pair rows in two ranges:
    ``[0, prefix)`` always, the rest only in a step whose held pairs
    overflow the prefix; every held pair is computed exactly once either
    way. The backward is the prefix's own (``jax.vjp``, its residuals at
    the prefix's width), then a second conditional that adds the other
    range's gradients, whose forward it runs once more (the rare step
    pays that; it is the step that cost the whole width before).

    Why both conditionals hand a running sum through (the result, then the
    five gradients) where the plain shape would be ``prefix + cond(rest,
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
    operands = (x, weights, w1, w3, w2, order, inverse, sizes)
    return _add_overflow(prefix, _range_ffn((0, prefix), *operands), operands)


def _ranges_fwd(prefix, *operands):
    floats, ints = operands[:5], operands[5:]
    out, pull = jax.vjp(lambda *f: _range_ffn((0, prefix), *f, *ints), *floats)
    return _add_overflow(prefix, out, operands), (pull, operands)


def _ranges_bwd(prefix, residuals, ct):
    pull, operands = residuals
    floats, ints = operands[:5], operands[5:]
    order, _, sizes = ints
    rest = (prefix, order.shape[0])

    def add_overflow(grads, floats, ct):
        more = jax.vjp(lambda *f: _range_ffn(rest, *f, *ints), *floats)[1](ct)
        return jax.tree.map(jnp.add, grads, more)

    grads = lax.optimization_barrier(lax.cond(
        jnp.sum(sizes) > prefix, add_overflow,
        lambda grads, floats, ct: grads, pull(ct), floats, ct))
    return (*grads, None, None, None)


_two_ranges.defvjp(_ranges_fwd, _ranges_bwd)
# under ``jit`` a model's expert layers of one shape are traced,
# differentiated and lowered once, not once each: without it the token
# cell's first step took 3.8 s longer than with one range (PERF.md, PR 36)
_ranges_ffn = jax.jit(_two_ranges, static_argnums=0)


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
    that share this layer it is their part of the sum.

    No token-expert pair on a held expert is ever dropped. The ``P = tokens
    x per_token`` pairs are sorted by expert, the held ones first, so the
    ``n`` held pairs are the sorted rows ``[0, n)``, and ``jax.lax.
    ragged_dot`` multiplies each group by its expert (on a TPU a
    grouped-matmul kernel that skips the rows past the last group). The
    rows are computed in two ranges by one function (``_range_ffn``): the
    prefix ``[0, C)`` always, and ``[C, P)`` only in a step whose own count
    says held pairs lie there (``n > C``, decided on the device:
    ``_ranges_ffn``). ``C = min(P, 2 P held / experts)`` (``_prefix_rows``),
    twice the even share, so a chip whose experts draw up to twice their
    share of the routing gathers, selects, multiplies and casts ``C`` rows
    and not ``P``; a step in which every token picks ``per_token`` held
    experts is still computed in full. A layer that holds half or all of
    its experts has ``C == P``: one range, no conditional.

    The router (scores, choice, weights) is float32: a near-tie in the
    top-k that fell otherwise in bfloat16 would move a whole token's
    output; the rows and products are ``dtype``, the weighted sum over a
    token's pairs float32. Sows into the ``counters`` collection
    ``expert_pairs [held]``, the token-expert pairs each held expert
    computed, and ``prefix_alone``, 1 where ``n <= C``."""

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
            pairs = tokens * k
            prefix = _prefix_rows(pairs, self.held, self.experts)
            if not self.is_initializing():  # ``init`` returns parameters alone
                self.sow(COUNTERS, "expert_pairs", sizes)
                self.sow(COUNTERS, "prefix_alone",
                         (jnp.sum(sizes) <= prefix).astype(jnp.int32))
            _, order = lax.sort_key_val(
                group.reshape(-1), jnp.arange(pairs, dtype=jnp.int32))
            inverse = jnp.argsort(order)

            w1, w3, w2 = (w.astype(self.dtype) for w in _ExpertWeights(
                self.held, self.hidden, self.width, name="experts")())
            operands = (x.astype(self.dtype), weights, w1, w3, w2, order, inverse, sizes)
            if prefix < pairs:
                out = _ranges_ffn(prefix, *operands)
            else:
                out = _range_ffn((0, prefix), *operands)
            return out.astype(self.dtype).reshape(shape)


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
