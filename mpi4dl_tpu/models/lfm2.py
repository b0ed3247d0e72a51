"""LFM2-MoE (LiquidAI LFM2-8B-A1B and its family) as a flat cell list.

The model is described by its published ``config.json``
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json): a layer
is ``h = x + Op(RMSNorm(x)); y = h + FFN(RMSNorm(h))`` with ``Op`` the gated
short convolution or causal grouped-query attention by ``layer_types`` and
``FFN`` a dense SwiGLU in the first ``num_dense_layers`` layers, the expert
layer after (``ops/sequence.py``). The cell list is an embedding cell, one
cell a layer and a head cell (final RMSNorm, a linear head of its own), the
unit ``Trainer`` and the stage partitioner slice, as ``amoebanetd(...)`` is.

**A chip's share of a deployment.** Where each layer of the model is divided
over several chips, the config a chip is given counts what it holds:
``num_experts`` the experts held, ``vocab_size`` its slice of the
vocabulary, ``layer_types`` its stage of the stack, and a ``cut`` group
states the published values beside them. Of that group the model reads
``cut.num_experts.published`` (the router's width: every token is routed
over all the experts) and ``cut.num_experts.first`` (the first expert held,
0 where absent). The expert layer then computes the held experts' part of
the sum and nothing stands in for the others.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from mpi4dl_tpu.ops.sequence import (
    COUNTERS,
    Attention,
    Embedding,
    ExpertFFN,
    RMSNorm,
    ShortConv,
    SwiGLU,
    linear,
)


@dataclasses.dataclass(frozen=True)
class LFM2Config:
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    conv_L_cache: int
    norm_eps: float
    rope_theta: float
    layer_types: tuple
    num_dense_layers: int
    num_experts: int            # held on this chip
    router_experts: int         # the router's width: all of them
    first_expert: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    use_expert_bias: bool
    vocab_size: int

    @classmethod
    def from_dict(cls, config: dict) -> "LFM2Config":
        """From a ``config.json``'s keys (and its ``cut`` group, if any)."""
        if config.get("conv_bias"):
            raise ValueError("conv_bias is not supported: the family has no bias")
        types = tuple(config["layer_types"])
        if len(types) != int(config["num_hidden_layers"]):
            raise ValueError("layer_types must name num_hidden_layers layers")
        share = config.get("cut", {}).get("num_experts", {})
        held = int(config["num_experts"])
        return cls(
            hidden_size=int(config["hidden_size"]),
            intermediate_size=int(config["intermediate_size"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            conv_L_cache=int(config["conv_L_cache"]),
            norm_eps=float(config["norm_eps"]),
            rope_theta=float(config["rope_theta"]),
            layer_types=types,
            num_dense_layers=int(config["num_dense_layers"]),
            num_experts=held,
            router_experts=int(share.get("published", held)),
            first_expert=int(share.get("first", 0)),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            norm_topk_prob=bool(config["norm_topk_prob"]),
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            use_expert_bias=bool(config["use_expert_bias"]),
            vocab_size=int(config["vocab_size"]),
        )


class LFM2Embed(nn.Module):
    """Token ids ``[batch, positions]`` -> ``[batch, positions, hidden]``."""

    config: LFM2Config
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, ids):
        c = self.config
        with jax.named_scope("mpi4dl_part_block"):
            return Embedding(
                c.vocab_size, c.hidden_size, name="embed_tokens")(ids).astype(self.dtype)


class LFM2Layer(nn.Module):
    """``h = x + Op(RMSNorm(x)); y = h + FFN(RMSNorm(h))``."""

    config: LFM2Config
    operator: str   # "conv" or "full_attention"
    experts: bool   # the expert layer, else the dense SwiGLU
    dtype: Any = jnp.bfloat16

    # the collection the expert layer counts its token-expert pairs into;
    # ``Trainer`` reads it back from the cells that name one
    counters = COUNTERS

    @nn.compact
    def __call__(self, x):
        c = self.config
        with jax.named_scope("mpi4dl_part_block"):
            normed = RMSNorm(c.norm_eps, name="operator_norm")(x)
        if self.operator == "conv":
            op = ShortConv(c.hidden_size, c.conv_L_cache, self.dtype, name="conv")
        elif self.operator == "full_attention":
            op = Attention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.norm_eps, c.rope_theta, dtype=self.dtype, name="self_attn")
        else:
            raise ValueError(f"unknown layer type {self.operator!r}")
        mixed = op(normed)
        with jax.named_scope("mpi4dl_part_block"):
            h = x + mixed
            normed = RMSNorm(c.norm_eps, name="ffn_norm")(h)
        if self.experts:
            ffn = ExpertFFN(
                c.hidden_size, c.moe_intermediate_size, c.router_experts,
                c.num_experts, c.first_expert, c.num_experts_per_tok,
                c.norm_topk_prob, c.routed_scaling_factor, c.use_expert_bias,
                self.dtype, name="feed_forward")
        else:
            ffn = SwiGLU(c.hidden_size, c.intermediate_size, self.dtype,
                         name="feed_forward")
        fed = ffn(normed)
        with jax.named_scope("mpi4dl_part_block"):
            return h + fed


class LFM2Head(nn.Module):
    """Final RMSNorm and the linear head: logits over the vocabulary held."""

    config: LFM2Config
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = RMSNorm(c.norm_eps, name="embedding_norm")(x).astype(self.dtype)
        return linear(c.vocab_size, self.dtype, "lm_head")(x)


def lfm2(config: "dict | LFM2Config", dtype: Any = jnp.float32) -> list[nn.Module]:
    """The model of ``config`` as a flat cell list: embedding, one cell a
    layer, head."""
    if not isinstance(config, LFM2Config):
        config = LFM2Config.from_dict(config)
    layers = [
        LFM2Layer(config, operator, i >= config.num_dense_layers, dtype)
        for i, operator in enumerate(config.layer_types)
    ]
    return [LFM2Embed(config, dtype), *layers, LFM2Head(config, dtype)]
