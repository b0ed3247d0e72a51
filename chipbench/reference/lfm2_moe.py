"""LFM2-MoE (LiquidAI LFM2-8B-A1B, ``model_type: lfm2_moe``), plain float32
reference of one chip's share of a deployment.

Source: https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json
(the keys below are that file's). A layer is

    h = x + Op(RMSNorm(x));  y = h + FFN(RMSNorm(h))

- ``Op``, by ``layer_types``: the gated short convolution
  ``B, C, u = split3(in_proj(x)); out_proj(C * conv(B * u))`` with a causal
  depthwise convolution of ``conv_L_cache`` taps (position t sees
  t-L+1..t, zeros before the start); or causal grouped-query attention with
  an RMSNorm over each head's dims of q and of k (learned scale) before the
  rotary embedding (half-split pairing, ``rope_theta``), scale
  ``head_dim ** -0.5``.
- ``FFN``: a dense SwiGLU ``w2(silu(w1 x) * w3 x)`` of ``intermediate_size``
  in the first ``num_dense_layers`` layers; after them the expert layer:
  ``s = sigmoid(x W_r)`` over ALL the published experts, the top
  ``num_experts_per_tok`` chosen on ``s + expert_bias``, their weights ``s``
  (without the bias) divided by their sum (``norm_topk_prob``) and times
  ``routed_scaling_factor``; each expert a SwiGLU of
  ``moe_intermediate_size``.
- Ends: an embedding table; a final RMSNorm and a linear head of its own
  (untied); no bias anywhere.

**The share.** ``num_experts`` is the number of experts this chip HOLDS;
``cut.num_experts.published`` is the router's width and
``cut.num_experts.first`` (0 where absent) the first held expert. The layer
routes over all experts and sums the chosen experts that are held; what the
absent ones would add is left out (model-configs guide, section 4), and
that partial result goes on. ``vocab_size`` is the slice of the vocabulary
held: ids, logits and loss are over the slice. Without a ``cut`` the model
is whole.

Plain means: every held expert is applied to every token and masked by the
routing weights; attention takes one softmax a row over the whole prefix,
a block of query rows at a time (each block recomputed in the backward
pass, so that 8,192 positions fit beside a float32 follower's state); all
of it ``jax.numpy`` in float32 at precision "highest". The router's scores
and choice are float32 in every ``mode``: the family routes in float32, and
a lower-precision recipe would too. Parameter names are those of the
program's tree (``mpi4dl_tpu/models/lfm2.py``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .plain import operand, product

QUERY_BLOCK = 512  # rows of queries whose scores are alive at once


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a cell is built from; hashable, so that layers of equal
    settings are one function object and share one compiled program."""

    hidden: int
    dense_width: int
    expert_width: int
    heads: int
    kv_heads: int
    conv_taps: int
    eps: float
    rope_theta: float
    experts: int          # the router's width: all the published experts
    held: int             # experts this chip holds ...
    first: int            # ... from this one on
    per_token: int
    norm_topk: bool
    scaling: float
    expert_bias: bool
    vocab: int

    @property
    def head_dim(self):
        return self.hidden // self.heads


def sizes(model: dict) -> Sizes:
    share = model.get("cut", {}).get("num_experts", {})
    held = int(model["num_experts"])
    return Sizes(
        hidden=int(model["hidden_size"]),
        dense_width=int(model["intermediate_size"]),
        expert_width=int(model["moe_intermediate_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        conv_taps=int(model["conv_L_cache"]),
        eps=float(model["norm_eps"]),
        rope_theta=float(model["rope_theta"]),
        experts=int(share.get("published", held)),
        held=held,
        first=int(share.get("first", 0)),
        per_token=int(model["num_experts_per_tok"]),
        norm_topk=bool(model["norm_topk_prob"]),
        scaling=float(model["routed_scaling_factor"]),
        expert_bias=bool(model["use_expert_bias"]),
        vocab=int(model["vocab_size"]),
    )


# -- layers ------------------------------------------------------------------


def _matmul(x, w, mode):
    y = jnp.matmul(operand(x, mode), operand(w, mode), precision=lax.Precision.HIGHEST)
    return product(y, mode)


def linear(scope, x, features):
    """``x W``: no layer of this family has a bias."""
    w = scope.param("kernel", (x.shape[-1], features), "fan_in")
    return _matmul(x, w, scope.mode)


def rms_norm(scope, x, eps):
    scale = scope.param("scale", (x.shape[-1],), "ones")
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def swiglu(x, w1, w3, w2, mode):
    return _matmul(jax.nn.silu(_matmul(x, w1, mode)) * _matmul(x, w3, mode), w2, mode)


def rope(x, theta):
    """Rotary embedding of ``x [batch, positions, heads, dim]``, half-split
    pairing (dim i turns with dim i + dim/2), positions 0..S-1 in every
    row: one document a sequence."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def short_conv(scope, x, s: Sizes):
    gate_in, gate_out, u = jnp.split(linear(scope.sub("in_proj"), x, 3 * s.hidden), 3, -1)
    w = scope.sub("conv").param("kernel", (s.conv_taps, s.hidden), "fan_in")
    length = x.shape[1]
    padded = jnp.pad(gate_in * u, ((0, 0), (s.conv_taps - 1, 0), (0, 0)))
    v = sum(w[j] * padded[:, j:j + length] for j in range(s.conv_taps))
    return linear(scope.sub("out_proj"), gate_out * v, s.hidden)


def _attend(q, k, v, first_row, mode):
    """Rows ``first_row ...`` of the queries against the prefix that ends
    with their last row: ``q [B, rows, KV, G, D]``, ``k, v [B, prefix, KV, D]``."""
    scores = jnp.einsum("bqkgd,bnkd->bkgqn", operand(q, mode), operand(k, mode),
                        precision=lax.Precision.HIGHEST)
    scores = product(scores, mode) * q.shape[-1] ** -0.5
    row = first_row + jnp.arange(q.shape[1])[:, None]
    scores = jnp.where(row >= jnp.arange(k.shape[1])[None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqn,bnkd->bqkgd", operand(p, mode), operand(v, mode),
                     precision=lax.Precision.HIGHEST)
    return product(out, mode)


def attention(scope, x, s: Sizes):
    batch, length, _ = x.shape
    d, groups = s.head_dim, s.heads // s.kv_heads
    q = linear(scope.sub("q_proj"), x, s.heads * d).reshape(batch, length, s.heads, d)
    k = linear(scope.sub("k_proj"), x, s.kv_heads * d).reshape(batch, length, s.kv_heads, d)
    v = linear(scope.sub("v_proj"), x, s.kv_heads * d).reshape(batch, length, s.kv_heads, d)
    q = rope(rms_norm(scope.sub("q_layernorm"), q, s.eps), s.rope_theta)
    k = rope(rms_norm(scope.sub("k_layernorm"), k, s.eps), s.rope_theta)
    q = q.reshape(batch, length, s.kv_heads, groups, d)
    blocks = []
    for start in range(0, length, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, length)
        block = jax.checkpoint(functools.partial(
            _attend, first_row=start, mode=scope.mode))
        blocks.append(block(q[:, start:end], k[:, :end], v[:, :end]))
    out = jnp.concatenate(blocks, axis=1).reshape(batch, length, s.heads * d)
    return linear(scope.sub("out_proj"), out, s.hidden)


def dense_ffn(scope, x, s: Sizes):
    w1, w3, w2 = (
        scope.sub(name).param("kernel", shape, "fan_in")
        for name, shape in (("w1", (s.hidden, s.dense_width)),
                            ("w3", (s.hidden, s.dense_width)),
                            ("w2", (s.dense_width, s.hidden))))
    return swiglu(x, w1, w3, w2, scope.mode)


def routing(scope, x, s: Sizes):
    """``(experts chosen [.., per_token], their weights)`` over all the
    published experts, float32 whatever the mode."""
    w_r = scope.sub("gate").param("kernel", (s.hidden, s.experts), "fan_in")
    scores = jax.nn.sigmoid(jnp.matmul(x, w_r, precision=lax.Precision.HIGHEST))
    choose_on = scores
    if s.expert_bias:
        choose_on = scores + scope.param("expert_bias", (s.experts,), "zeros")
    _, chosen = lax.top_k(choose_on, s.per_token)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if s.norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen, weights * s.scaling


def expert_ffn(scope, x, s: Sizes):
    """The held experts' part of the layer's output."""
    chosen, weights = routing(scope, x, s)
    experts = scope.sub("experts")
    # a stacked array's "fan_in" would multiply the expert axis in
    w1 = experts.param("w1", (s.held, s.hidden, s.expert_width), s.hidden ** -0.5)
    w3 = experts.param("w3", (s.held, s.hidden, s.expert_width), s.hidden ** -0.5)
    w2 = experts.param("w2", (s.held, s.expert_width, s.hidden), s.expert_width ** -0.5)
    one = jax.checkpoint(functools.partial(swiglu, mode=scope.mode))
    out = jnp.zeros_like(x)
    for e in range(s.held):
        weight = jnp.sum(jnp.where(chosen == s.first + e, weights, 0.0), axis=-1)
        out = out + weight[..., None] * one(x, w1[e], w3[e], w2[e])
    return out


# -- cells -------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _embedding(s: Sizes):
    def embedding(scope, ids):
        table = scope.sub("embed_tokens").param("embedding", (s.vocab, s.hidden), 1.0)
        return table[ids]

    return embedding


@functools.lru_cache(maxsize=None)
def _layer(s: Sizes, operator: str, experts: bool):
    op = {"conv": (short_conv, "conv"), "full_attention": (attention, "self_attn")}[operator]

    def layer(scope, x):
        h = x + op[0](scope.sub(op[1]), rms_norm(scope.sub("operator_norm"), x, s.eps), s)
        ffn = expert_ffn if experts else dense_ffn
        return h + ffn(scope.sub("feed_forward"), rms_norm(scope.sub("ffn_norm"), h, s.eps), s)

    return layer


@functools.lru_cache(maxsize=None)
def _head(s: Sizes):
    def head(scope, x):
        x = rms_norm(scope.sub("embedding_norm"), x, s.eps)
        return linear(scope.sub("lm_head"), x, s.vocab)

    return head


def _layers(model: dict):
    types = list(model["layer_types"])
    if len(types) != int(model["num_hidden_layers"]):
        raise ValueError("layer_types must name num_hidden_layers layers")
    dense = int(model["num_dense_layers"])
    return [(t, i >= dense) for i, t in enumerate(types)]


def cells(model: dict) -> list:
    """Embedding, one cell a layer, head, as ``cell(scope, x)`` functions."""
    s = sizes(model)
    return ([_embedding(s)] + [_layer(s, t, moe) for t, moe in _layers(model)]
            + [_head(s)])


def kinds(model: dict) -> list:
    """``stem``, then ``dense_`` or ``moe_`` + ``conv`` or ``attention`` for
    each layer, then ``head``; the check taps one cell of each kind."""
    short = {"conv": "conv", "full_attention": "attention"}
    return (["stem"] + [("moe_" if moe else "dense_") + short[t]
                        for t, moe in _layers(model)] + ["head"])


# -- the family's hooks ------------------------------------------------------


def input_spec(model: dict, traffic: dict):
    """Token ids: the sequence length is the traffic mix's."""
    return (int(traffic["sequence_length"]),), jnp.int32


def loss(logits, labels):
    """Mean softmax cross-entropy over every position of every sequence,
    against the label there (the traffic's next token)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def forward_flops_per_token(model: dict, sequence_length: int) -> float:
    """Matrix-multiplication FLOPs of one token's forward pass: projections,
    feed-forwards and the head at 2 x inputs x outputs; causal attention at
    half the square (a token meets ``sequence_length / 2`` keys on average,
    scores and weighted sum); an expert layer at its EXPECTED load,
    ``num_experts_per_tok x held / published`` token-expert pairs a token,
    whatever the program's router did; the router itself at its published
    width. Elementwise work (norms, gates, the depthwise convolution's
    three taps, softmax) is not counted."""
    s = sizes(model)
    kv = s.kv_heads * s.head_dim
    per_operator = {
        "conv": 2.0 * s.hidden * (3 * s.hidden + s.hidden),
        "full_attention": 2.0 * s.hidden * (2 * s.hidden + 2 * kv)
        + 2 * 2.0 * s.hidden * sequence_length / 2,
    }
    pairs = s.per_token * s.held / s.experts
    per_ffn = {
        False: 3 * 2.0 * s.hidden * s.dense_width,
        True: 2.0 * s.hidden * s.experts + pairs * 3 * 2.0 * s.hidden * s.expert_width,
    }
    total = sum(per_operator[t] + per_ffn[moe] for t, moe in _layers(model))
    return total + 2.0 * s.hidden * s.vocab


def train_flops_per_sample(model: dict, traffic: dict) -> float:
    """3 x forward (forward, input gradient, weight gradient) for one
    sequence; recomputation does not count."""
    length = int(traffic["sequence_length"])
    return 3.0 * forward_flops_per_token(model, length) * length
