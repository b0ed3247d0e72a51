"""SDAR-MoE (JetLM SDAR-30B-A3B-Chat, ``model_type: sdar_moe``), plain float32
reference of one chip's share of a deployment, on the path the model is
trained on: block diffusion.

Source: https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json
(the keys below are that file's). A layer is

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))

- ``Attn``: ``q = W_q x`` (``num_attention_heads`` heads of ``head_dim``),
  ``k``, ``v`` (``num_key_value_heads``); an RMSNorm with a learned scale
  over each head's dims of q and of k; the rotary embedding over all of a
  head's dims (half-split pairing, ``rope_theta``) at the row's position;
  scores times ``head_dim ** -0.5``, the mask below, one softmax a row;
  ``W_o``. No bias.
- ``MoE``: router logits ``x W_r`` over ALL the published experts, a softmax
  over all of them, the top ``num_experts_per_tok``, their weights divided by
  their sum (``norm_topk_prob``); each expert ``W2 (silu(W1 x) * W3 x)`` of
  ``moe_intermediate_size``. No shared expert, no bias on the choice.
- Ends: an embedding table; a final RMSNorm and a linear head of its own.

**The step** (block diffusion, BD3-LM's recipe, arXiv:2503.09573). For a
sequence ``x0`` of ``L`` tokens the model's input is ``2 L`` rows: rows
``[0, L)`` the noisy copy ``xt`` (``x0`` with the mask token at the masked
positions), rows ``[L, 2 L)`` ``x0`` itself. Row ``r`` is at position
``p(r) = r mod L``, in block ``b(r) = p(r) // B``, ``B`` the model's
``block_length``. Query ``r`` sees key ``s`` if and only if (``sees``)

1. both are noisy and ``b(s) == b(r)``; or
2. ``r`` is noisy, ``s`` clean and ``b(s) < b(r)``; or
3. both are clean and ``b(s) <= b(r)``;
4. a clean query never sees a noisy key.

Logits are taken from the noisy rows alone, ``[N, L, V]``; position ``i``'s
predict token ``i`` itself (no shift). Loss (``loss``): the mean over the
batch of ``(1 / L) sum_i w_i CE(logits_i, x0_i)``, ``w_i = 1 / t`` at the
masked positions and 0 elsewhere, ``t`` the sequence's noise level.

**The share**, as ``reference/lfm2_moe.py`` states it: ``num_experts`` the
experts this chip HOLDS, ``cut.num_experts.published`` the router's width,
``cut.num_experts.first`` the first held expert; the layer routes over all
experts and sums the chosen experts that are held. ``vocab_size`` is the
slice held.

Plain means: every held expert is applied to every row and masked by the
routing weights, one expert at a time; attention takes one softmax a row, a
block of query rows at a time against the noisy rows at its own positions
and the whole clean copy under the mask (each block recomputed in the
backward pass, so that 16,384 rows fit beside a float32 follower's state);
all of it ``jax.numpy`` in float32 at precision "highest". The router is
float32 in every ``mode``. Parameter names are those of the program's tree
(``mpi4dl_tpu/models/sdar.py``); nothing of the program is imported.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .plain import operand, product

QUERY_BLOCK = 256  # rows of queries whose scores are alive at once


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a cell is built from; hashable, so that layers of equal
    settings are one function object and share one compiled program."""

    hidden: int
    expert_width: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    rope_theta: float
    layers: int
    experts: int          # the router's width: all the published experts
    held: int             # experts this chip holds ...
    first: int            # ... from this one on
    per_token: int
    norm_topk: bool
    vocab: int
    block_length: int
    depth: int            # the published depth (the initialisers' 1 / sqrt(2 N))


def sizes(model: dict) -> Sizes:
    cut = model.get("cut", {})
    share = cut.get("num_experts", {})
    held = int(model["num_experts"])
    layers = int(model["num_hidden_layers"])
    return Sizes(
        hidden=int(model["hidden_size"]),
        expert_width=int(model["moe_intermediate_size"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        eps=float(model["rms_norm_eps"]),
        rope_theta=float(model["rope_theta"]),
        layers=layers,
        experts=int(share.get("published", held)),
        held=held,
        first=int(share.get("first", 0)),
        per_token=int(model["num_experts_per_tok"]),
        norm_topk=bool(model["norm_topk_prob"]),
        vocab=int(model["vocab_size"]),
        block_length=int(model["block_length"]),
        depth=int(cut.get("num_hidden_layers", {}).get("published", layers)),
    )


# -- layers ------------------------------------------------------------------


def _matmul(x, w, mode):
    y = jnp.matmul(operand(x, mode), operand(w, mode), precision=lax.Precision.HIGHEST)
    return product(y, mode)


def linear(scope, x, features, init="fan_in"):
    """``x W``: no layer of this family has a bias."""
    w = scope.param("kernel", (x.shape[-1], features), init)
    return _matmul(x, w, scope.mode)


def rms_norm(scope, x, eps):
    scale = scope.param("scale", (x.shape[-1],), "ones")
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def swiglu(x, w1, w3, w2, mode):
    return _matmul(jax.nn.silu(_matmul(x, w1, mode)) * _matmul(x, w3, mode), w2, mode)


def rope(x, theta, positions):
    """Rotary embedding of ``x [batch, rows, heads, dim]``, half-split
    pairing (dim i turns with dim i + dim/2), row ``r`` at ``positions[r]``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def sees(r, s, length: int, block: int):
    """Whether query row ``r`` sees key row ``s`` (arrays that broadcast):
    the four rules of the module's docstring."""
    r_noisy, s_noisy = r < length, s < length
    r_block, s_block = (r % length) // block, (s % length) // block
    return ((r_noisy & s_noisy & (s_block == r_block))
            | (r_noisy & ~s_noisy & (s_block < r_block))
            | (~r_noisy & ~s_noisy & (s_block <= r_block)))


def _attend(q, k, v, rows, keys, length, block, mode):
    """Query rows ``rows`` against key rows ``keys`` (index vectors):
    ``q [B, rows, KV, G, D]``, ``k, v [B, keys, KV, D]``."""
    scores = jnp.einsum("bqkgd,bnkd->bkgqn", operand(q, mode), operand(k, mode),
                        precision=lax.Precision.HIGHEST)
    scores = product(scores, mode) * q.shape[-1] ** -0.5
    scores = jnp.where(sees(rows[:, None], keys[None, :], length, block), scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqn,bnkd->bqkgd", operand(p, mode), operand(v, mode),
                     precision=lax.Precision.HIGHEST)
    return product(out, mode)


def attention(scope, x, s: Sizes):
    batch, total, _ = x.shape
    length = total // 2
    if total != 2 * length or length % s.block_length:
        raise ValueError(f"{total} rows are not two copies of whole blocks of {s.block_length}")
    d, groups = s.head_dim, s.heads // s.kv_heads
    positions = jnp.arange(total) % length
    q = linear(scope.sub("q_proj"), x, s.heads * d).reshape(batch, total, s.heads, d)
    k = linear(scope.sub("k_proj"), x, s.kv_heads * d).reshape(batch, total, s.kv_heads, d)
    v = linear(scope.sub("v_proj"), x, s.kv_heads * d).reshape(batch, total, s.kv_heads, d)
    q = rope(rms_norm(scope.sub("q_layernorm"), q, s.eps), s.rope_theta, positions)
    k = rope(rms_norm(scope.sub("k_layernorm"), k, s.eps), s.rope_theta, positions)
    q = q.reshape(batch, total, s.kv_heads, groups, d)
    # A block of query rows lies in one copy. Whatever the copy it is handed
    # the noisy rows at its own positions and the whole clean copy, and
    # ``sees`` lets through what the rules allow (for a clean block none of
    # the noisy rows): one shape for every block, so one body under
    # ``lax.map``, a block at a time, recomputed in the backward pass.
    size = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length

    def one(start):
        place = start % length
        take = lambda a: jnp.concatenate(  # noqa: E731
            [lax.dynamic_slice_in_dim(a, place, size, axis=1), a[:, length:]], axis=1)
        keys = jnp.concatenate([place + jnp.arange(size), length + jnp.arange(length)])
        return _attend(lax.dynamic_slice_in_dim(q, start, size, axis=1), take(k), take(v),
                       start + jnp.arange(size), keys, length, s.block_length, scope.mode)

    out = lax.map(jax.checkpoint(one), jnp.arange(0, total, size))  # [blocks, B, size, ...]
    out = jnp.moveaxis(out, 0, 1).reshape(batch, total, s.heads * d)
    return linear(scope.sub("out_proj"), out, s.hidden, _residual_init(s, s.heads * d))


def _residual_init(s: Sizes, fan_in: int) -> float:
    """A projection back into the residual stream: ``1 / sqrt(fan_in)`` over
    ``sqrt(2 x published depth)`` (two such projections a layer)."""
    return float(fan_in ** -0.5 / (2 * s.depth) ** 0.5)


def routing(scope, x, s: Sizes):
    """``(experts chosen [.., per_token], their weights)`` over all the
    published experts, float32 whatever the mode."""
    w_r = scope.sub("gate").param("kernel", (s.hidden, s.experts), "fan_in")
    scores = jax.nn.softmax(jnp.matmul(x, w_r, precision=lax.Precision.HIGHEST), axis=-1)
    weights, chosen = lax.top_k(scores, s.per_token)
    if s.norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen, weights


def expert_ffn(scope, x, s: Sizes):
    """The held experts' part of the layer's output."""
    chosen, weights = routing(scope, x, s)
    experts = scope.sub("experts")
    # a stacked array's "fan_in" would multiply the expert axis in
    w1 = experts.param("w1", (s.held, s.hidden, s.expert_width), s.hidden ** -0.5)
    w3 = experts.param("w3", (s.held, s.hidden, s.expert_width), s.hidden ** -0.5)
    w2 = experts.param("w2", (s.held, s.expert_width, s.hidden),
                       _residual_init(s, s.expert_width))

    @jax.checkpoint
    def weighted(x, weight, w1, w3, w2):  # kept whole: an expert's output is never stored
        return weight[..., None] * swiglu(x, w1, w3, w2, scope.mode)

    def add(out, expert):
        e, w1, w3, w2 = expert
        weight = jnp.sum(jnp.where(chosen == s.first + e, weights, 0.0), axis=-1)
        return out + weighted(x, weight, w1, w3, w2), None

    return lax.scan(add, jnp.zeros_like(x), (jnp.arange(s.held), w1, w3, w2))[0]


# -- cells -------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _embedding(s: Sizes):
    def embedding(scope, ids):
        table = scope.sub("embed_tokens").param("embedding", (s.vocab, s.hidden), 1.0)
        return table[ids]

    return embedding


@functools.lru_cache(maxsize=None)
def _attention_cell(s: Sizes):
    def attention_cell(scope, x):  # a layer's first half
        return x + attention(
            scope.sub("self_attn"), rms_norm(scope.sub("input_layernorm"), x, s.eps), s)

    return attention_cell


@functools.lru_cache(maxsize=None)
def _experts_cell(s: Sizes):
    def experts_cell(scope, h):  # a layer's second half
        return h + expert_ffn(
            scope.sub("mlp"), rms_norm(scope.sub("post_attention_layernorm"), h, s.eps), s)

    return experts_cell


@functools.lru_cache(maxsize=None)
def _head(s: Sizes):
    def head(scope, x):
        x = x[:, :x.shape[1] // 2]  # the noisy copy's rows
        x = rms_norm(scope.sub("norm"), x, s.eps)
        return linear(scope.sub("lm_head"), x, s.vocab)

    return head


def cells(model: dict) -> list:
    """Embedding, two cells a layer (``h = x + Attn(RMSNorm(x))``, then ``y =
    h + MoE(RMSNorm(h))``: the program's cells), head, as ``cell(scope, x)``
    functions."""
    s = sizes(model)
    return [_embedding(s)] + [_attention_cell(s), _experts_cell(s)] * s.layers + [_head(s)]


def kinds(model: dict) -> list:
    """``stem``, ``attn_blockdiff`` and ``moe_blockdiff`` for each layer,
    ``head``; the check taps one cell of each kind: an attention cell, whose
    parameters' cotangents no routing touches, and an expert cell."""
    layers = int(model["num_hidden_layers"])
    return ["stem"] + ["attn_blockdiff", "moe_blockdiff"] * layers + ["head"]


# -- the family's hooks ------------------------------------------------------


def input_spec(model: dict, traffic: dict):
    """Token ids, the noisy copy then the clean one: twice the traffic
    mix's sequence length."""
    return (2 * int(traffic["sequence_length"]),), jnp.int32


def loss(logits, labels):
    """The mean over the batch of ``(1 / L) sum_i w_i CE(logits_i, x0_i)``.
    ``labels`` int32 ``[N, L, 2]``: a position's token ``x0_i`` and the BITS
    of its float32 weight ``w_i`` (the traffic's stream: ``1 / t`` where the
    position was masked, 0 where it was not; the harness hands labels on as
    one int32 array, so a float32 weight travels as its bits and arrives
    exactly). Bare tokens ``[N, L]`` weigh 1 each."""
    if labels.ndim == logits.ndim:
        tokens = labels[..., 0]
        weights = lax.bitcast_convert_type(labels[..., 1], jnp.float32)
    else:
        tokens, weights = labels, 1.0
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ce = -jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
    return jnp.mean(weights * ce)


def visible_pairs(length: int, block: int) -> float:
    """Query-key pairs the mask lets through, one sequence and head: a
    position in block ``b`` sees ``B`` noisy and ``B b`` clean keys from its
    noisy row and ``B (b + 1)`` clean keys from its clean row, ``2 B (b +
    1)`` together; over the ``L / B`` blocks of ``B`` positions that is
    ``L (L + B)`` (two causal sequences would be ``L (L + 1)``)."""
    return float(length) * (length + block)


def forward_flops_per_sequence(model: dict, length: int) -> float:
    """Matrix-multiplication FLOPs of one sequence's forward pass: the
    projections, the router (at its published width) and the expert layer at
    its EXPECTED load (``num_experts_per_tok x held / published`` pairs a
    row, whatever the program's router did) over all ``2 L`` rows; attention
    at the mask's visible pairs (scores and weighted sum); the head over the
    ``L`` noisy rows. Elementwise work (norms, gates, softmax, the rotary
    embedding) is not counted."""
    s = sizes(model)
    wide, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    per_row = 2.0 * s.hidden * (2 * wide + 2 * kv) + 2.0 * s.hidden * s.experts \
        + (s.per_token * s.held / s.experts) * 3 * 2.0 * s.hidden * s.expert_width
    attention_ = 2 * 2.0 * s.head_dim * s.heads * visible_pairs(length, s.block_length)
    return s.layers * (2 * length * per_row + attention_) + length * 2.0 * s.hidden * s.vocab


def train_flops_per_sample(model: dict, traffic: dict) -> float:
    """3 x forward (forward, input gradient, weight gradient) for one
    sequence (both its copies); recomputation does not count."""
    return 3.0 * forward_flops_per_sequence(model, int(traffic["sequence_length"]))


def least_attention_flops_per_step(model: dict, traffic: dict) -> float:
    """The least matrix-multiplication FLOPs the attention cores of one step
    must do: per layer and query head six products (scores and weighted sum
    forward; ``dp``, ``dq``, ``dk``, ``dv`` backward) of ``2 x head_dim``
    FLOPs over the pairs the mask lets through. The remat's second forward
    and the backward's recomputed scores are executed and not counted."""
    s = sizes(model)
    pairs = visible_pairs(int(traffic["sequence_length"]), s.block_length)
    return int(traffic["batch_size"]) * s.layers * s.heads * 6 * 2.0 * s.head_dim * pairs


def least_attention_bytes_per_step(model: dict, traffic: dict) -> float:
    """The least bytes the attention cores of one step must move: per layer,
    bfloat16, each of q, k, v read and the output written once forward; q,
    k, v, the output and its cotangent read and dq, dk, dv written once
    backward, over both copies' rows."""
    s = sizes(model)
    rows = 2 * int(traffic["sequence_length"]) * int(traffic["batch_size"])
    wide, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    forward = 2 * wide + 2 * kv
    backward = 4 * wide + 4 * kv
    return 2.0 * s.layers * rows * (forward + backward)
