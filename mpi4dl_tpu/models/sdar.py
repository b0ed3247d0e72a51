"""SDAR-MoE (JetLM SDAR-30B-A3B-Chat, ``model_type: sdar_moe``) as a flat
cell list (embedding, two cells a layer: attention, then the expert layer,
as ``models/nemotron_h.py`` keeps one mixer a cell; head), on the path it is
trained on: block diffusion.

The model is described by its published ``config.json``
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json): a
layer is ``h = x + Attn(RMSNorm(x)); y = h + MoE(RMSNorm(h))``, no bias
anywhere. ``Attn``: grouped-query attention at ``head_dim``, an RMSNorm over
each head's dims of q and of k, the rotary embedding over all of them.
``MoE``: a float32 softmax over all ``num_experts`` router logits, the top
``num_experts_per_tok``, their weights divided by their sum
(``norm_topk_prob``), every expert a SwiGLU of ``moe_intermediate_size``; no
shared expert, no bias on the choice. A final RMSNorm and an untied head.

**The step.** A model that generates by diffusion over blocks is trained by
it (the BD3-LM recipe, arXiv:2503.09573): the input is ``2 L`` rows a
sequence, a noisy copy (masked positions hold the mask token) beside the
clean one; row ``r`` is at position ``r mod L``; a noisy row sees the noisy
rows of its own block of ``block_length`` positions and the clean rows of
earlier blocks, a clean row the clean rows of its own block and earlier ones
(``ops/sequence.Attention`` with ``diffusion_block``). Logits are taken from
the noisy rows alone, position ``i``'s predict token ``i`` itself, and the
loss is the model's own (``block_diffusion_loss``): a weighted cross-entropy
on the positions that were masked. ``block_length`` is a key of this program
(the published config has none; the released models decode in blocks of 4).

**A chip's share of a deployment**, as ``models/lfm2.py`` reads it:
``num_experts`` the experts held, ``cut.num_experts.published`` the router's
width, ``cut.num_experts.first`` the first expert held, ``vocab_size`` the
slice of the vocabulary, ``num_hidden_layers`` the stage's layers.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import lax

from mpi4dl_tpu.ops.sequence import (
    COUNTERS,
    Attention,
    Embedding,
    ExpertFFN,
    RMSNorm,
    linear,
)


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    hidden_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rms_norm_eps: float
    rope_theta: float
    num_hidden_layers: int
    num_experts: int            # held on this chip
    router_experts: int         # the router's width: all of them
    first_expert: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    vocab_size: int
    block_length: int

    @classmethod
    def from_dict(cls, config: dict) -> "SDARConfig":
        """From a ``config.json``'s keys (and its ``cut`` group, if any)."""
        refused = {
            "attention_bias": False, "tie_word_embeddings": False,
            "use_sliding_window": False, "rope_scaling": None,
            "mlp_only_layers": [], "decoder_sparse_step": 1,
        }
        for key, only in refused.items():
            if config.get(key, only) != only:
                raise ValueError(f"{key} = {config[key]!r} is not supported (only {only!r})")
        share = config.get("cut", {}).get("num_experts", {})
        held = int(config["num_experts"])
        return cls(
            hidden_size=int(config["hidden_size"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            rms_norm_eps=float(config["rms_norm_eps"]),
            rope_theta=float(config["rope_theta"]),
            num_hidden_layers=int(config["num_hidden_layers"]),
            num_experts=held,
            router_experts=int(share.get("published", held)),
            first_expert=int(share.get("first", 0)),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            norm_topk_prob=bool(config["norm_topk_prob"]),
            vocab_size=int(config["vocab_size"]),
            block_length=int(config["block_length"]),
        )


class SDAREmbed(nn.Module):
    """Token ids ``[batch, 2 L]`` (noisy copy, clean copy) ->
    ``[batch, 2 L, hidden]``."""

    config: SDARConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, ids):
        c = self.config
        with jax.named_scope("mpi4dl_part_block"):
            return Embedding(
                c.vocab_size, c.hidden_size, name="embed_tokens")(ids).astype(self.dtype)


class SDARAttention(nn.Module):
    """A layer's first half, ``h = x + Attn(RMSNorm(x))`` over both copies'
    rows under the block mask: a cell of its own, so that the attention's
    parameters are a tree of their own (the benchmark's cell-by-cell check
    holds their cotangents apart from the expert layer's, whose routing is
    discrete)."""

    config: SDARConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.config
        with jax.named_scope("mpi4dl_part_block"):
            normed = RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
        mixed = Attention(
            c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.rms_norm_eps, c.rope_theta, dtype=self.dtype, head_dim=c.head_dim,
            diffusion_block=c.block_length, name="self_attn")(normed)
        with jax.named_scope("mpi4dl_part_block"):
            return x + mixed


class SDARExperts(nn.Module):
    """A layer's second half, ``y = h + MoE(RMSNorm(h))``; the expert layer
    is found under the scope ``sdar_moe``."""

    config: SDARConfig
    dtype: Any = jnp.bfloat16

    counters = COUNTERS  # the expert layer's counts (``Trainer`` reads them back)

    @nn.compact
    def __call__(self, h):
        c = self.config
        with jax.named_scope("mpi4dl_part_block"):
            normed = RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(h)
        with jax.named_scope("sdar_moe"):
            fed = ExpertFFN(
                c.hidden_size, c.moe_intermediate_size, c.router_experts,
                c.num_experts, c.first_expert, c.num_experts_per_tok,
                c.norm_topk_prob, expert_bias=False, dtype=self.dtype,
                scoring="softmax", name="mlp")(normed)
            with jax.named_scope("mpi4dl_part_block"):
                return h + fed


class SDARHead(nn.Module):
    """The noisy copy's rows, a final RMSNorm and the linear head: logits
    ``[batch, L, vocabulary held]``."""

    config: SDARConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = x[:, :x.shape[1] // 2]
        x = RMSNorm(c.rms_norm_eps, name="norm")(x).astype(self.dtype)
        return linear(c.vocab_size, self.dtype, "lm_head")(x)


def sdar(config: "dict | SDARConfig", dtype: Any = jnp.float32) -> list[nn.Module]:
    """The model of ``config`` as a flat cell list: embedding, two cells a
    layer (attention, then the expert layer), head."""
    if not isinstance(config, SDARConfig):
        config = SDARConfig.from_dict(config)
    layers = [cell(config, dtype) for _ in range(config.num_hidden_layers)
              for cell in (SDARAttention, SDARExperts)]
    return [SDAREmbed(config, dtype), *layers, SDARHead(config, dtype)]


def block_diffusion_loss(logits, y, mean):
    """The model's loss for ``Trainer`` (``train.position_cross_entropy``'s
    signature): the mean over the batch of ``(1 / L) sum_i w_i CE(logits_i,
    target_i)``, ``logits [N, L, V]`` the noisy rows'. ``y`` int32 ``[N, L,
    2]``: a position's target and the bits of its float32 weight
    (``data.BlockDiffusionTokens``: ``1 / t`` where the position was masked,
    0 where it was not); bare targets ``[N, L]`` weigh 1 each. Accuracy: the
    share of all ``L`` positions whose token the logits name. Counts
    ``loss_positions``, the positions whose weight is not 0."""
    with jax.named_scope("blockdiff_loss"):
        if y.ndim == logits.ndim:
            target = y[..., 0]
            weight = lax.bitcast_convert_type(y[..., 1], jnp.float32)
        else:
            target, weight = y, jnp.ones(y.shape, jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), target)
        loss = mean(jnp.sum(weight * ce), target.size)
        named = jnp.sum(jnp.argmax(logits, axis=-1) == target)
        accuracy = mean(named.astype(jnp.float32), target.size)
        return loss, accuracy, {"loss_positions": jnp.sum(weight != 0)}
