"""Nemotron-H (``model_type: nemotron_h``): the tower that
Nemotron-Labs-TwoTower-30B-A3B's ``config.json`` describes, plain float32
reference of one chip's share of a deployment.

Source: https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16/blob/main/config.json
(the keys below are that file's; the equations are those of the published
``modeling_nemotron_h.py``). Every layer is ONE mixer,

    y = x + Mixer(N(x)),   N(x) = x * rsqrt(mean(x^2) + layer_norm_epsilon) * w   (w from 1)

and ``hybrid_override_pattern`` names each layer's mixer by a letter.

- **``M``, the Mamba-2 mixer.** With ``H = mamba_num_heads``, ``P =
  mamba_head_dim``, ``G = n_groups``, ``N = ssm_state_size`` and ``d_inner =
  H P`` (not ``expand x hidden_size``): ``[z | xBC | dt] = x W_in`` with
  widths ``d_inner | d_inner + 2 G N | H``, no bias; ``xBC = silu(conv(xBC) +
  b)``, a causal depthwise convolution of ``conv_kernel`` taps; ``[x | B |
  C] = split(xBC)`` as ``[H, P] | [G, N] | [G, N]``, head ``h`` reading group
  ``h // (H / G)``; ``dt = softplus(dt + dt_bias)`` (``time_step_limit`` is
  ``(0, inf)``: no clamp), ``A = -exp(A_log)`` a head. Per head a state ``S
  [P, N]``, zero before position 0, and at every position

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t

  Then ``y <- GroupRMSNorm(y * silu(z)) * w_n`` (the gate BEFORE the norm;
  statistics over each of the ``G`` groups of ``d_inner / G`` channels) and
  ``out = y W_o``. **Here the recurrence is its definition, position by
  position** (a ``lax.scan`` over the positions of a block inside a
  ``lax.scan`` over the blocks, each block under ``jax.checkpoint``, so
  that the backward pass keeps one state a block and recomputes inside it).
  The program computes it a chunk of ``chunk_size`` positions at a time by
  another formulation (the state-space dual: a chunk's own positions as one
  masked product, a state handed from chunk to chunk); neither knows the
  other, and ``chunk_size`` is read by nothing here.
- **``E``, the expert layer.** ``s = sigmoid(x W_r)`` over ALL the published
  routed experts; the top ``num_experts_per_tok`` of ``s + expert_bias``
  (``n_group`` and ``topk_group`` 1: no group limit); weights ``s[chosen] /
  sum(s[chosen]) * routed_scaling_factor``; every expert ``W2 relu(W1
  x)^2`` (``mlp_hidden_act: relu2``, two arrays an expert, no gate); plus one
  shared expert of that form and width
  ``moe_shared_expert_intermediate_size``, ungated, added once.
- **``*``, attention.** ``q, k, v`` without bias, ``num_attention_heads /
  num_key_value_heads`` grouped queries of ``head_dim``; NO rotary embedding
  and no q/k norm (the family has no positional embedding: ``rope_theta``
  and ``partial_rotary_factor`` are in the config and read by nothing);
  causal softmax at ``head_dim ** -0.5``; ``o_proj``.
- **Ends.** An embedding table, a final ``N``, a linear head of its own.

**Not built** (also under ``assumed`` in the configuration's file): the
TwoTower release's second, denoiser tower, its adaLN, the cross-tower
conditioning, the block length and the noise schedule of block-diffusion
decoding: the published ``config.json`` holds no key of any of them. This
is the tower the config describes, trained causally on the next token.
Other departures: the initialisers are a fresh model's within what
``plain.params_maker`` draws (normals, ones, zeros; the file says which);
the optimiser is the program's SGD with momentum.

**The share** is ``lfm2_moe.py``'s: ``n_routed_experts`` is the number of
experts this chip HOLDS, ``cut.n_routed_experts.published`` the router's
width, ``cut.n_routed_experts.first`` (0 where absent) the first held
expert. The layer routes over all experts and sums the chosen ones that are
held; the shared expert, which every chip that shares the layer computes
alike, is added once; what the absent experts would add is left out.
``vocab_size`` is the slice of the vocabulary held. Without a ``cut`` the
model is whole.

**Kinds** (``kinds``): ``stem``, then ``mamba``, ``moe_relu2`` or
``attention`` a layer, ``head``. An expert layer's kind starts with
``moe_``: the benchmark's reader of token-expert pairs a token
(``layer_metrics/moe_pairs_per_token.py``) counts the expert layers by that
prefix, here as in the two other token families.

Plain means: every held expert is applied to every token and masked by the
routing weights (one expert after the other, a ``lax.scan``); attention takes
one softmax a row over all the keys up to its own, a block of query rows at
a time (a ``lax.map`` over the blocks, every block against all the keys under
the causal mask); all of it ``jax.numpy`` in float32 at precision "highest".
A layer takes the sequences of the batch one after the other, recomputed in
the backward pass (``_per_sequence``): nothing in a layer mixes sequences,
and a float32 follower's state leaves room for one sequence's residuals at
8,192 positions, not two. The router, ``dt``, ``A`` and the decay are
float32 in every ``mode``; a ``mode`` below float32 rounds the operands of
every matrix product, the recurrence's outer product and read-out among
them. Parameter names are those of the program's tree
(``mpi4dl_tpu/models/nemotron_h.py``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .plain import operand, product

QUERY_BLOCK = 256   # rows of queries whose scores are alive at once
SCAN_BLOCK = 64     # positions of the recurrence under one checkpoint
A_LOG_STD = 2.0     # a fresh model's A_log: normal, this standard deviation
DT_BIAS_STD = 1.0   # ... and its dt_bias (a wide one conditions the cell badly:
                    # the configuration's file, ``assumed.initializers``)
HIGHEST = lax.Precision.HIGHEST
KINDS = {"M": "mamba", "E": "moe_relu2", "*": "attention"}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a cell is built from; hashable, so that layers of equal
    settings are one function object and share one compiled program."""

    hidden: int
    eps: float
    mamba_heads: int
    mamba_head_dim: int
    groups: int
    state: int
    conv_taps: int
    heads: int
    kv_heads: int
    head_dim: int
    expert_width: int
    shared_width: int
    experts: int          # the router's width: all the published experts
    held: int             # experts this chip holds ...
    first: int            # ... from this one on
    per_token: int
    norm_topk: bool
    scaling: float
    vocab: int
    residual_layers: int  # the published depth where rescale_prenorm_residual, else 1


def sizes(model: dict) -> Sizes:
    share = model.get("cut", {}).get("n_routed_experts", {})
    held = int(model["n_routed_experts"])
    return Sizes(
        hidden=int(model["hidden_size"]),
        eps=float(model["layer_norm_epsilon"]),
        mamba_heads=int(model["mamba_num_heads"]),
        mamba_head_dim=int(model["mamba_head_dim"]),
        groups=int(model["n_groups"]),
        state=int(model["ssm_state_size"]),
        conv_taps=int(model["conv_kernel"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]),
        expert_width=int(model["moe_intermediate_size"]),
        shared_width=int(model["moe_shared_expert_intermediate_size"]),
        experts=int(share.get("published", held)),
        held=held,
        first=int(share.get("first", 0)),
        per_token=int(model["num_experts_per_tok"]),
        norm_topk=bool(model["norm_topk_prob"]),
        scaling=float(model["routed_scaling_factor"]),
        vocab=int(model["vocab_size"]),
        residual_layers=int(model.get("cut", {}).get("num_hidden_layers", {}).get(
            "published", model["num_hidden_layers"]))
        if model.get("rescale_prenorm_residual") else 1,
    )


# -- layers ------------------------------------------------------------------


def _einsum(spec, a, b, mode):
    y = jnp.einsum(spec, operand(a, mode), operand(b, mode), precision=HIGHEST)
    return product(y, mode)


def _matmul(x, w, mode):
    return _einsum("...i,io->...o", x, w, mode)


def linear(scope, x, features, residual=None):
    """``x W``: no projection of this family has a bias. ``residual``: the
    layers of the published model, for a projection back into the residual
    stream (``_into_residual``)."""
    init = "fan_in" if residual is None else _into_residual(x.shape[-1], residual)
    w = scope.param("kernel", (x.shape[-1], features), init)
    return _matmul(x, w, scope.mode)


def _into_residual(fan_in: int, layers: int) -> float:
    """A fresh model's standard deviation for a mixer's projection back into
    the residual stream: ``1 / sqrt(fan_in)`` over ``sqrt(layers)``, the
    published ``rescale_prenorm_residual`` (the GPT-2 scheme: the weights of
    residual layers scaled by ``1 / sqrt(N)`` at initialisation)."""
    return float(fan_in ** -0.5 * layers ** -0.5)


def norm(scope, x, eps):
    """``N``: a plain scale, from 1."""
    w = scope.param("scale", (x.shape[-1],), "ones")
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def relu2(x, w1, w2, mode):
    """``relu(x W1)^2 W2``."""
    return _matmul(jnp.square(jax.nn.relu(_matmul(x, w1, mode))), w2, mode)


def recurrence(x, dt, a, b, c, mode="f32", block=SCAN_BLOCK):
    """Mamba-2's state-space recurrence as its definition. ``x [B, S, H,
    P]``, ``dt [B, S, H]``, ``a [H]`` (negative), ``b, c [B, S, H, N]`` (one
    entry a head: a group's ``B, C`` already repeated) -> ``S_t C_t`` of
    every position ``[B, S, H, P]``, without the ``D x_t`` skip. The state
    ``[B, H, P, N]`` is zero before position 0."""
    batch, length, heads, dim = x.shape
    pad = -length % block
    by_position = [jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)]
    if pad:  # dt zero: the state stays as it is; the rows are cut
        by_position = [jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                       for t in by_position]
    blocks = [t.reshape(-1, block, *t.shape[1:]) for t in by_position]

    def position(state, at):
        x_t, dt_t, b_t, c_t = at
        state = state * jnp.exp(dt_t * a)[..., None, None] + _einsum(
            "bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t, mode)
        return state, _einsum("bhpn,bhn->bhp", state, c_t, mode)

    @jax.checkpoint
    def one_block(state, rows):
        return lax.scan(position, state, rows)

    zero = jnp.zeros((batch, heads, dim, b.shape[-1]), jnp.float32)
    _, out = lax.scan(one_block, zero, tuple(blocks))
    out = out.reshape(-1, *out.shape[2:])[:length]
    return jnp.moveaxis(out, 0, 1)


def mamba2(scope, x, s: Sizes):
    batch, length, _ = x.shape
    heads, dim, per = s.mamba_heads, s.mamba_head_dim, s.mamba_heads // s.groups
    inner, mixed = heads * dim, heads * dim + 2 * s.groups * s.state
    projected = linear(scope.sub("in_proj"), x, inner + mixed + heads)
    z, xbc, dt = (projected[..., :inner], projected[..., inner:inner + mixed],
                  projected[..., inner + mixed:])
    w = scope.sub("conv").param("kernel", (s.conv_taps, mixed), "fan_in")
    bias = scope.param("conv_bias", (mixed,), "zeros")
    padded = jnp.pad(xbc, ((0, 0), (s.conv_taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(
        sum(w[j] * padded[:, j:j + length] for j in range(s.conv_taps)) + bias)
    u = xbc[..., :inner].reshape(batch, length, heads, dim)
    by_group = (batch, length, s.groups, s.state)
    b = jnp.repeat(xbc[..., inner:inner + s.groups * s.state].reshape(by_group), per, axis=2)
    c = jnp.repeat(xbc[..., inner + s.groups * s.state:].reshape(by_group), per, axis=2)

    a = -jnp.exp(scope.param("A_log", (heads,), A_LOG_STD))
    dt = jax.nn.softplus(dt + scope.param("dt_bias", (heads,), DT_BIAS_STD))
    skip = scope.param("D", (heads,), "ones")
    y = recurrence(u, dt, a, b, c, scope.mode) + skip[:, None] * u
    # the gate, then the norm over each group of ``inner / groups`` channels
    y = y.reshape(batch, length, s.groups, -1) * jax.nn.silu(
        z.reshape(batch, length, s.groups, -1))
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + s.eps)
    y = y.reshape(batch, length, inner) * scope.param("norm_scale", (inner,), "ones")
    return linear(scope.sub("out_proj"), y, s.hidden, s.residual_layers)


def _attend(q, k, v, first_row, mode):
    """Rows ``first_row ...`` of the queries against all the keys, those
    after a row masked: ``q [B, rows, KV, G, D]``, ``k, v [B, S, KV, D]``."""
    scores = _einsum("bqkgd,bnkd->bkgqn", q, k, mode) * q.shape[-1] ** -0.5
    row = first_row + jnp.arange(q.shape[1])[:, None]
    scores = jnp.where(row >= jnp.arange(k.shape[1])[None, :], scores, -jnp.inf)
    return _einsum("bkgqn,bnkd->bqkgd", jax.nn.softmax(scores, axis=-1), v, mode)


def attention(scope, x, s: Sizes):
    batch, length, _ = x.shape
    d, groups = s.head_dim, s.heads // s.kv_heads
    q = linear(scope.sub("q_proj"), x, s.heads * d).reshape(
        batch, length, s.kv_heads, groups, d)
    k = linear(scope.sub("k_proj"), x, s.kv_heads * d).reshape(batch, length, s.kv_heads, d)
    v = linear(scope.sub("v_proj"), x, s.kv_heads * d).reshape(batch, length, s.kv_heads, d)
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
    return linear(scope.sub("out_proj"), out, s.hidden, s.residual_layers)


def routing(scope, x, s: Sizes):
    """``(experts chosen [.., per_token], their weights)`` over all the
    published experts, float32 whatever the mode: sigmoid scores, the choice
    on score + bias, the weights the scores themselves."""
    w_r = scope.sub("gate").param("kernel", (s.hidden, s.experts), "fan_in")
    scores = jax.nn.sigmoid(jnp.matmul(x, w_r, precision=HIGHEST))
    _, chosen = lax.top_k(scores + scope.param("expert_bias", (s.experts,), "zeros"),
                          s.per_token)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if s.norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen, weights * s.scaling


def routed_experts(scope, x, s: Sizes):
    """The held experts' part of the layer's output."""
    chosen, weights = routing(scope, x, s)
    experts = scope.sub("experts")
    # a stacked array's "fan_in" would multiply the expert axis in
    w1 = experts.param("w1", (s.held, s.hidden, s.expert_width), s.hidden ** -0.5)
    w2 = experts.param("w2", (s.held, s.expert_width, s.hidden),
                       _into_residual(s.expert_width, s.residual_layers))

    @jax.checkpoint
    def add(out, expert):
        e, up, down = expert
        weight = jnp.sum(jnp.where(chosen == s.first + e, weights, 0.0), axis=-1)
        return out + weight[..., None] * relu2(x, up, down, scope.mode), None

    return lax.scan(add, jnp.zeros_like(x), (jnp.arange(s.held), w1, w2))[0]


def shared_expert(scope, x, s: Sizes):
    """``relu(x W1)^2 W2``: what every token takes, ungated."""
    shared = scope.sub("shared_expert")
    w1 = shared.sub("w1").param("kernel", (s.hidden, s.shared_width), "fan_in")
    w2 = shared.sub("w2").param(
        "kernel", (s.shared_width, s.hidden), _into_residual(s.shared_width, s.residual_layers))
    return relu2(x, w1, w2, scope.mode)


def expert_layer(scope, x, s: Sizes):
    return routed_experts(scope, x, s) + shared_expert(scope, x, s)


MIXERS = {"M": mamba2, "E": expert_layer, "*": attention}

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
def _layer(s: Sizes, letter: str):
    def layer(scope, x):
        def mixed(x):
            return x + MIXERS[letter](scope.sub("mixer"), norm(scope.sub("norm"), x, s.eps), s)

        return _per_sequence(mixed, x)

    return layer


@functools.lru_cache(maxsize=None)
def _head(s: Sizes):
    def head(scope, x):
        return linear(scope.sub("lm_head"), norm(scope.sub("norm_f"), x, s.eps), s.vocab)

    return head


def _pattern(model: dict) -> str:
    pattern = str(model["hybrid_override_pattern"])
    if set(pattern) - set(MIXERS) or len(pattern) != int(model["num_hidden_layers"]):
        raise ValueError(f"a pattern of num_hidden_layers letters of {sorted(MIXERS)}")
    return pattern


def cells(model: dict) -> list:
    """Embedding, one cell a layer, head, as ``cell(scope, x)`` functions."""
    s = sizes(model)
    return [_embedding(s)] + [_layer(s, m) for m in _pattern(model)] + [_head(s)]


def kinds(model: dict) -> list:
    """``stem``, ``mamba`` / ``moe_relu2`` / ``attention`` for each layer,
    ``head``; the check taps one cell of each kind."""
    return ["stem"] + [KINDS[m] for m in _pattern(model)] + ["head"]


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
    inputs x outputs; the state-space recurrence at its three products a
    position and head (the decay of the state, ``dt x B^T`` added to it,
    ``S C`` read from it: 3 x 2 x head dim x state, whatever chunked form a
    program computes it by); causal attention at half the square (a token
    meets ``sequence_length / 2`` keys on average, scores and weighted
    sum); the expert layer at its EXPECTED load, ``num_experts_per_tok x
    held / published`` token-expert pairs a token, whatever the program's
    router did, plus the shared expert; the router at its published width.
    Elementwise work (norms, gates, the depthwise convolution's taps,
    softmax, ``D x``) is not counted."""
    s = sizes(model)
    inner = s.mamba_heads * s.mamba_head_dim
    per_mixer = {
        "M": 2.0 * s.hidden * (2 * inner + 2 * s.groups * s.state + s.mamba_heads)
        + 2.0 * inner * s.hidden
        + 3 * 2.0 * s.mamba_heads * s.mamba_head_dim * s.state,
        "*": 2.0 * s.hidden * (s.heads + 2 * s.kv_heads) * s.head_dim
        + 2.0 * s.heads * s.head_dim * s.hidden
        + 2 * 2.0 * s.heads * s.head_dim * sequence_length / 2,
        "E": 2.0 * s.hidden * s.experts
        + s.per_token * s.held / s.experts * 2 * 2.0 * s.hidden * s.expert_width
        + 2 * 2.0 * s.hidden * s.shared_width,
    }
    return sum(per_mixer[m] for m in _pattern(model)) + 2.0 * s.hidden * s.vocab


def train_flops_per_sample(model: dict, traffic: dict) -> float:
    """3 x forward (forward, input gradient, weight gradient) for one
    sequence; recomputation does not count."""
    length = int(traffic["sequence_length"])
    return 3.0 * forward_flops_per_token(model, length) * length
