"""Qwen3-Next (Qwen3-Next-80B-A3B, ``model_type: qwen3_next``), plain float32
reference of one chip's share of a deployment.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json
(the keys below are that file's; the equations are those of the published
``modeling_qwen3_next.py``). With ``N(x) = x * rsqrt(mean(x^2) + eps) *
(1 + w)`` (``w`` from 0) a layer is

    h = x + Mixer(N(x));   y = h + MoE(N(h))

and layer ``i`` (counted from the published layer 0) mixes by gated softmax
attention when ``(i + 1) % full_attention_interval == 0``, else by the Gated
DeltaNet.

- **Gated DeltaNet.** ``q, k, v, z = split(x W_qkvz)``, ``b, a = split(x
  W_ba)``; ``q, k, v = split(silu(conv(concat(q, k, v))))`` with a causal
  depthwise convolution of ``linear_conv_kernel_dim`` taps (no bias);
  ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; ``q, k``
  L2-normalised over a head's dims (``x * rsqrt(sum x^2 + 1e-6)``), ``q``
  times ``linear_key_head_dim ** -0.5``, each key head's ``q, k`` used by
  ``linear_num_value_heads / linear_num_key_heads`` consecutive value heads.
  Per value head a state ``S [key dim, value dim]``, zero before position 0,
  and at every position, in this order,

      S <- exp(g_t) S;  r = v_t - S^T k_t;  S <- S + beta_t k_t r^T;  o_t = S^T q_t

  Then ``o <- w_n * o * rsqrt(mean(o^2) + eps) * silu(z)`` per head (``w_n``
  one plain scale of the value dim, from 1) and ``out = o W_o``. **Here the
  rule is its definition: the recurrence, position by position** (a
  ``lax.scan`` over the positions of a block inside a ``lax.scan`` over the
  blocks, each block under ``jax.checkpoint``, so that the backward pass
  keeps one state a block and recomputes inside it). The program computes it
  a chunk at a time by another formulation (a triangular system in its WY
  form); neither knows the other.
- **Gated attention.** ``q, gate = split per head(x W_q)``, ``k, v``; ``q <-
  rope(N_q(q))``, ``k <- rope(N_k(k))`` with ``N`` over a head's dims and the
  rotation on the first ``head_dim * partial_rotary_factor`` of them
  (half-split pairing within them); causal softmax attention at scale
  ``head_dim ** -0.5`` with grouped queries; ``out = (attn * sigmoid(gate)) W_o``.
- **Expert layer** (every layer): ``p = softmax(x W_r)`` over ALL the
  published experts; the top ``num_experts_per_tok``; their ``p`` divided by
  their sum (``norm_topk_prob``); each expert a SwiGLU of
  ``moe_intermediate_size``; plus ``sigmoid(x w_s) * SwiGLU_shared(x)``.
- **Ends.** An embedding table, a final ``N``, a linear head of its own.

**Departures from the published model** (each also under ``assumed`` in the
configuration's file): no multi-token-prediction head (the catalog's config
holds no key of it); the columns of ``W_qkvz`` are ``[q | k | v | z]`` and of
``W_ba`` ``[b | a]``, each in head order, where the published weights
interleave them by key head (a permutation of columns, nothing for weights
drawn from a seed); ``W_q``'s columns are per head ``[q | gate]`` as
published; the initialisers are a fresh model's (below); the ends are untied
(``tie_word_embeddings`` false, as published); the optimiser is the
program's SGD with momentum.

**The share** is ``lfm2_moe.py``'s: ``num_experts`` is the number of experts
this chip HOLDS, ``cut.num_experts.published`` the router's width,
``cut.num_experts.first`` (0 where absent) the first held expert. The layer
routes over all experts and sums the chosen ones that are held; the shared
expert, which every chip that shares the layer computes alike, is added
once; what the absent experts would add is left out. ``vocab_size`` is the
slice of the vocabulary held. Without a ``cut`` the model is whole.

Plain means: every held expert is applied to every token and masked by the
routing weights (one expert after the other, a ``lax.scan``); attention takes
one softmax a row over all the keys up to its own, a block of query rows at
a time (a ``lax.map`` over the blocks, every block against all the keys under
the causal mask); all of it ``jax.numpy`` in float32 at precision "highest".
A layer takes the sequences of the batch one after the other, mixer and
expert layer each recomputed in the backward pass (``_per_sequence``):
nothing in a layer mixes sequences, and a float32 follower's state leaves
room for one sequence's residuals at 8,192 positions, not two. The router,
``beta``, ``g`` and the decay are float32 in every ``mode``; a ``mode`` below
float32 rounds the operands of every matrix product, the three of the
recurrence among them. Parameter names are those of the program's tree
(``mpi4dl_tpu/models/qwen3_next.py``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .plain import operand, product

QUERY_BLOCK = 512   # rows of queries whose scores are alive at once
RULE_BLOCK = 64     # positions of the recurrence under one checkpoint
A_LOG_STD = 2.0     # a fresh model's A_log: normal, this standard deviation
HIGHEST = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a cell is built from; hashable, so that layers of equal
    settings are one function object and share one compiled program."""

    hidden: int
    interval: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary: int
    rope_theta: float
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_taps: int
    eps: float
    expert_width: int
    shared_width: int
    experts: int          # the router's width: all the published experts
    held: int             # experts this chip holds ...
    first: int            # ... from this one on
    per_token: int
    norm_topk: bool
    vocab: int


def sizes(model: dict) -> Sizes:
    share = model.get("cut", {}).get("num_experts", {})
    held = int(model["num_experts"])
    head_dim = int(model["head_dim"])
    return Sizes(
        hidden=int(model["hidden_size"]),
        interval=int(model["full_attention_interval"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=head_dim,
        rotary=int(head_dim * float(model["partial_rotary_factor"])),
        rope_theta=float(model["rope_theta"]),
        key_heads=int(model["linear_num_key_heads"]),
        value_heads=int(model["linear_num_value_heads"]),
        key_dim=int(model["linear_key_head_dim"]),
        value_dim=int(model["linear_value_head_dim"]),
        conv_taps=int(model["linear_conv_kernel_dim"]),
        eps=float(model["rms_norm_eps"]),
        expert_width=int(model["moe_intermediate_size"]),
        shared_width=int(model["shared_expert_intermediate_size"]),
        experts=int(share.get("published", held)),
        held=held,
        first=int(share.get("first", 0)),
        per_token=int(model["num_experts_per_tok"]),
        norm_topk=bool(model["norm_topk_prob"]),
        vocab=int(model["vocab_size"]),
    )


# -- layers ------------------------------------------------------------------


def _einsum(spec, a, b, mode):
    y = jnp.einsum(spec, operand(a, mode), operand(b, mode), precision=HIGHEST)
    return product(y, mode)


def _matmul(x, w, mode):
    return _einsum("...i,io->...o", x, w, mode)


def linear(scope, x, features):
    """``x W``: no layer of this family has a bias."""
    w = scope.param("kernel", (x.shape[-1], features), "fan_in")
    return _matmul(x, w, scope.mode)


def norm(scope, x, eps):
    """``N``: the scale is ``1 + w``, ``w`` from 0."""
    w = scope.param("scale", (x.shape[-1],), "zeros")
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def swiglu(x, w1, w3, w2, mode):
    return _matmul(jax.nn.silu(_matmul(x, w1, mode)) * _matmul(x, w3, mode), w2, mode)


def rope(x, theta, rotary):
    """Rotary embedding of the first ``rotary`` dims of ``x [batch,
    positions, heads, dim]``, half-split pairing within them (dim i turns
    with dim i + rotary/2), the other dims as they are; positions 0..S-1 in
    every row: one document a sequence."""
    half = rotary // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def delta_rule(q, k, v, g, beta, mode="f32", block=RULE_BLOCK):
    """The gated delta rule as its definition. ``q, k [B, S, H, Dk]``,
    ``v [B, S, H, Dv]``, ``g, beta [B, S, H]`` (one entry a value head:
    ``q, k`` already repeated) -> ``o [B, S, H, Dv]``. The state
    ``[B, H, Dk, Dv]`` is zero before position 0."""
    batch, length, heads, key_dim = k.shape
    pad = -length % block
    by_position = [jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)]
    if pad:  # k, beta and g zero: the state stays as it is, the rows are cut
        by_position = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in by_position]
    blocks = [a.reshape(-1, block, *a.shape[1:]) for a in by_position]

    def position(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None, None]
        r = v_t - _einsum("bhde,bhd->bhe", state, k_t, mode)
        state = state + _einsum("bhd,bhe->bhde", beta_t[..., None] * k_t, r, mode)
        return state, _einsum("bhde,bhd->bhe", state, q_t, mode)

    @jax.checkpoint
    def one_block(state, rows):
        return lax.scan(position, state, rows)

    zero = jnp.zeros((batch, heads, key_dim, v.shape[-1]), jnp.float32)
    _, out = lax.scan(one_block, zero, tuple(blocks))
    out = out.reshape(-1, *out.shape[2:])[:length]
    return jnp.moveaxis(out, 0, 1)


def gated_delta_net(scope, x, s: Sizes):
    batch, length, _ = x.shape
    keys, values = s.key_heads * s.key_dim, s.value_heads * s.value_dim
    qkvz = linear(scope.sub("in_proj_qkvz"), x, 2 * keys + 2 * values)
    qkv, z = qkvz[..., :2 * keys + values], qkvz[..., 2 * keys + values:]
    ba = linear(scope.sub("in_proj_ba"), x, 2 * s.value_heads)
    b, a = ba[..., :s.value_heads], ba[..., s.value_heads:]
    w = scope.sub("conv").param("kernel", (s.conv_taps, 2 * keys + values), "fan_in")
    padded = jnp.pad(qkv, ((0, 0), (s.conv_taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(w[j] * padded[:, j:j + length] for j in range(s.conv_taps)))
    q = qkv[..., :keys].reshape(batch, length, s.key_heads, s.key_dim)
    k = qkv[..., keys:2 * keys].reshape(batch, length, s.key_heads, s.key_dim)
    v = qkv[..., 2 * keys:].reshape(batch, length, s.value_heads, s.value_dim)

    a_log = scope.param("A_log", (s.value_heads,), A_LOG_STD)
    dt_bias = scope.param("dt_bias", (s.value_heads,), "ones")
    g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
    beta = jax.nn.sigmoid(b)

    def unit(t):
        return t * lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

    per_key = s.value_heads // s.key_heads
    q = jnp.repeat(unit(q) * s.key_dim ** -0.5, per_key, axis=2)
    k = jnp.repeat(unit(k), per_key, axis=2)
    o = delta_rule(q, k, v, g, beta, scope.mode)
    w_n = scope.sub("norm").param("scale", (s.value_dim,), "ones")
    o = w_n * o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + s.eps)
    o = o * jax.nn.silu(z.reshape(o.shape))
    return linear(scope.sub("out_proj"), o.reshape(batch, length, values), s.hidden)


def _attend(q, k, v, first_row, mode):
    """Rows ``first_row ...`` of the queries against all the keys, those
    after a row masked: ``q [B, rows, KV, G, D]``, ``k, v [B, S, KV, D]``."""
    scores = _einsum("bqkgd,bnkd->bkgqn", q, k, mode) * q.shape[-1] ** -0.5
    row = first_row + jnp.arange(q.shape[1])[:, None]
    scores = jnp.where(row >= jnp.arange(k.shape[1])[None, :], scores, -jnp.inf)
    return _einsum("bkgqn,bnkd->bqkgd", jax.nn.softmax(scores, axis=-1), v, mode)


def gated_attention(scope, x, s: Sizes):
    batch, length, _ = x.shape
    d, groups = s.head_dim, s.heads // s.kv_heads
    q_gate = linear(scope.sub("q_proj"), x, s.heads * 2 * d).reshape(batch, length, s.heads, 2 * d)
    q, gate = q_gate[..., :d], q_gate[..., d:]
    k = linear(scope.sub("k_proj"), x, s.kv_heads * d).reshape(batch, length, s.kv_heads, d)
    v = linear(scope.sub("v_proj"), x, s.kv_heads * d).reshape(batch, length, s.kv_heads, d)
    q = rope(norm(scope.sub("q_layernorm"), q, s.eps), s.rope_theta, s.rotary)
    k = rope(norm(scope.sub("k_layernorm"), k, s.eps), s.rope_theta, s.rotary)
    q = q.reshape(batch, length, s.kv_heads, groups, d)
    # whole blocks of query rows (rows added to fill the last are cut again;
    # each sees real keys, so none is all masked), one block at a time
    rows = min(QUERY_BLOCK, length)
    blocks = -(-length // rows)
    q = jnp.pad(q, ((0, 0), (0, blocks * rows - length)) + ((0, 0),) * 3)
    q = jnp.moveaxis(q.reshape(batch, blocks, rows, s.kv_heads, groups, d), 1, 0)

    @jax.checkpoint
    def block(at):
        index, q_rows = at
        return _attend(q_rows, k, v, index * rows, scope.mode)

    out = lax.map(block, (jnp.arange(blocks), q))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, blocks * rows, s.heads * d)[:, :length]
    out = out * jax.nn.sigmoid(gate.reshape(out.shape))
    return linear(scope.sub("out_proj"), out, s.hidden)


def routing(scope, x, s: Sizes):
    """``(experts chosen [.., per_token], their weights)`` over all the
    published experts, float32 whatever the mode."""
    w_r = scope.sub("gate").param("kernel", (s.hidden, s.experts), "fan_in")
    p = jax.nn.softmax(jnp.matmul(x, w_r, precision=HIGHEST), axis=-1)
    weights, chosen = lax.top_k(p, s.per_token)
    if s.norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen, weights


def routed_experts(scope, x, s: Sizes):
    """The held experts' part of the layer's output."""
    chosen, weights = routing(scope, x, s)
    experts = scope.sub("experts")
    # a stacked array's "fan_in" would multiply the expert axis in
    w1 = experts.param("w1", (s.held, s.hidden, s.expert_width), s.hidden ** -0.5)
    w3 = experts.param("w3", (s.held, s.hidden, s.expert_width), s.hidden ** -0.5)
    w2 = experts.param("w2", (s.held, s.expert_width, s.hidden), s.expert_width ** -0.5)

    @jax.checkpoint
    def add(out, expert):
        e, a, b, c = expert
        weight = jnp.sum(jnp.where(chosen == s.first + e, weights, 0.0), axis=-1)
        return out + weight[..., None] * swiglu(x, a, b, c, scope.mode), None

    return lax.scan(add, jnp.zeros_like(x), (jnp.arange(s.held), w1, w3, w2))[0]


def shared_expert(scope, x, s: Sizes):
    """``sigmoid(x w_s) * SwiGLU_shared(x)``: what every token takes."""
    shared = scope.sub("shared_expert")
    w1, w3, w2 = (
        shared.sub(name).param("kernel", shape, "fan_in")
        for name, shape in (("w1", (s.hidden, s.shared_width)),
                            ("w3", (s.hidden, s.shared_width)),
                            ("w2", (s.shared_width, s.hidden))))
    gate = jax.nn.sigmoid(linear(scope.sub("shared_expert_gate"), x, 1))
    return gate * swiglu(x, w1, w3, w2, scope.mode)


def expert_layer(scope, x, s: Sizes):
    return routed_experts(scope, x, s) + shared_expert(scope, x, s)


# -- cells -------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _embedding(s: Sizes):
    def embedding(scope, ids):
        table = scope.sub("embed_tokens").param("embedding", (s.vocab, s.hidden), 1.0)
        return table[ids]

    return embedding


def _per_sequence(fn, x):
    """``fn`` of every sequence of the batch in turn (``fn`` takes and gives
    a batch of one), each recomputed in the backward pass."""
    return lax.map(jax.checkpoint(lambda row: fn(row[None])[0]), x)


@functools.lru_cache(maxsize=None)
def _layer(s: Sizes, mixer: str):
    mix, name = {"linear": (gated_delta_net, "linear_attn"),
                 "attention": (gated_attention, "self_attn")}[mixer]

    def layer(scope, x):
        def mixed(x):
            return x + mix(scope.sub(name), norm(scope.sub("input_layernorm"), x, s.eps), s)

        def fed(h):
            return h + expert_layer(
                scope.sub("mlp"), norm(scope.sub("post_attention_layernorm"), h, s.eps), s)

        return _per_sequence(fed, _per_sequence(mixed, x))

    return layer


@functools.lru_cache(maxsize=None)
def _head(s: Sizes):
    def head(scope, x):
        return linear(scope.sub("lm_head"), norm(scope.sub("norm"), x, s.eps), s.vocab)

    return head


def _mixers(model: dict):
    if model.get("mlp_only_layers") or int(model.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("every layer is an expert layer")
    interval = int(model["full_attention_interval"])
    return ["attention" if (i + 1) % interval == 0 else "linear"
            for i in range(int(model["num_hidden_layers"]))]


def cells(model: dict) -> list:
    """Embedding, one cell a layer, head, as ``cell(scope, x)`` functions."""
    s = sizes(model)
    return [_embedding(s)] + [_layer(s, m) for m in _mixers(model)] + [_head(s)]


def kinds(model: dict) -> list:
    """``stem``, ``moe_linear`` or ``moe_attention`` for each layer,
    ``head``; the check taps one cell of each kind."""
    return ["stem"] + ["moe_" + m for m in _mixers(model)] + ["head"]


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
    """Matrix-multiplication FLOPs of one token's forward pass, the least
    the mathematics needs: projections, feed-forwards and the head at 2 x
    inputs x outputs; causal attention at half the square (a token meets
    ``sequence_length / 2`` keys on average, scores and weighted sum); the
    delta rule at the three products of its recurrence (``S^T k``,
    ``k r^T``, ``S^T q``: 3 x 2 x key dim x value dim a value head,
    whatever chunked form a program computes it by); the expert layer at
    its EXPECTED load, ``num_experts_per_tok x held / published``
    token-expert pairs a token, whatever the program's router did, plus the
    shared expert and its gate; the router at its published width.
    Elementwise work (norms, gates, the depthwise convolution's taps,
    softmax, the decay) is not counted."""
    s = sizes(model)
    keys, values = s.key_heads * s.key_dim, s.value_heads * s.value_dim
    per_mixer = {
        "linear": 2.0 * s.hidden * (2 * keys + 2 * values + 2 * s.value_heads)
        + 2.0 * values * s.hidden
        + 3 * 2.0 * s.value_heads * s.key_dim * s.value_dim,
        "attention": 2.0 * s.hidden * (2 * s.heads + 2 * s.kv_heads) * s.head_dim
        + 2.0 * s.heads * s.head_dim * s.hidden
        + 2 * 2.0 * s.heads * s.head_dim * sequence_length / 2,
    }
    pairs = s.per_token * s.held / s.experts
    moe = (2.0 * s.hidden * s.experts
           + pairs * 3 * 2.0 * s.hidden * s.expert_width
           + 3 * 2.0 * s.hidden * s.shared_width + 2.0 * s.hidden)
    total = sum(per_mixer[m] + moe for m in _mixers(model))
    return total + 2.0 * s.hidden * s.vocab


def train_flops_per_sample(model: dict, traffic: dict) -> float:
    """3 x forward (forward, input gradient, weight gradient) for one
    sequence; recomputation does not count."""
    length = int(traffic["sequence_length"])
    return 3.0 * forward_flops_per_token(model, length) * length
