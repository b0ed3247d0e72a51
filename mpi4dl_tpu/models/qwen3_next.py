"""Qwen3-Next (Qwen3-Next-80B-A3B, ``model_type: qwen3_next``) as a flat cell
list.

The model is described by its published ``config.json``
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json).
With ``N`` an RMSNorm whose scale is ``1 + w`` (``w`` from 0), a layer is

    h = x + Mixer(N(x));   y = h + MoE(N(h))

``Mixer`` is gated softmax attention in layer ``i`` when
``(i + 1) % full_attention_interval == 0`` and the Gated DeltaNet otherwise;
``MoE`` is in every layer the expert layer (softmax scores over all the
experts, the top ``num_experts_per_tok`` renormalised) plus a shared expert
under a sigmoid gate of its own. All three are ``ops/sequence.py``'s. The
cell list is an embedding cell, one cell a layer and a head cell (final
``N``, a linear head of its own), as ``models/lfm2.py``'s is. The
multi-token-prediction head of the published model is not built.

**A chip's share of a deployment** is stated as ``LFM2Config`` reads it:
``num_experts`` the experts held, ``vocab_size`` the slice of the
vocabulary, ``num_hidden_layers`` the layers of this stage (counted from
published layer 0, so the layer pattern starts where the model's does), and
under ``cut.num_experts`` the ``published`` width of the router and the
``first`` expert held.
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
    GatedDeltaNet,
    RMSNorm,
    linear,
)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    hidden_size: int
    num_hidden_layers: int
    full_attention_interval: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    rms_norm_eps: float
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int            # held on this chip
    router_experts: int         # the router's width: all of them
    first_expert: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    vocab_size: int

    @classmethod
    def from_dict(cls, config: dict) -> "Qwen3NextConfig":
        """From a ``config.json``'s keys (and its ``cut`` group, if any)."""
        if config.get("mlp_only_layers") or int(config.get("decoder_sparse_step", 1)) != 1:
            raise ValueError("every layer is an expert layer: no mlp_only_layers, "
                             "decoder_sparse_step 1")
        if config.get("rope_scaling"):
            raise ValueError("rope_scaling is not supported")
        share = config.get("cut", {}).get("num_experts", {})
        held = int(config["num_experts"])
        return cls(
            hidden_size=int(config["hidden_size"]),
            num_hidden_layers=int(config["num_hidden_layers"]),
            full_attention_interval=int(config["full_attention_interval"]),
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            partial_rotary_factor=float(config["partial_rotary_factor"]),
            rope_theta=float(config["rope_theta"]),
            linear_num_key_heads=int(config["linear_num_key_heads"]),
            linear_num_value_heads=int(config["linear_num_value_heads"]),
            linear_key_head_dim=int(config["linear_key_head_dim"]),
            linear_value_head_dim=int(config["linear_value_head_dim"]),
            linear_conv_kernel_dim=int(config["linear_conv_kernel_dim"]),
            rms_norm_eps=float(config["rms_norm_eps"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            shared_expert_intermediate_size=int(config["shared_expert_intermediate_size"]),
            num_experts=held,
            router_experts=int(share.get("published", held)),
            first_expert=int(share.get("first", 0)),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            norm_topk_prob=bool(config["norm_topk_prob"]),
            vocab_size=int(config["vocab_size"]),
        )

    def mixer(self, layer: int) -> str:
        """``"full_attention"`` or ``"linear_attention"``."""
        whole = (layer + 1) % self.full_attention_interval == 0
        return "full_attention" if whole else "linear_attention"


class Qwen3NextEmbed(nn.Module):
    """Token ids ``[batch, positions]`` -> ``[batch, positions, hidden]``."""

    config: Qwen3NextConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, ids):
        c = self.config
        with jax.named_scope("mpi4dl_part_block"):
            return Embedding(
                c.vocab_size, c.hidden_size, name="embed_tokens")(ids).astype(self.dtype)


class Qwen3NextLayer(nn.Module):
    """``h = x + Mixer(N(x)); y = h + MoE(N(h))``."""

    config: Qwen3NextConfig
    mixer: str   # "linear_attention" or "full_attention"
    dtype: Any = jnp.bfloat16

    # the collection the expert layer counts its token-expert pairs into
    counters = COUNTERS

    @nn.compact
    def __call__(self, x):
        c = self.config
        with jax.named_scope("mpi4dl_part_block"):
            normed = RMSNorm(c.rms_norm_eps, True, name="input_layernorm")(x)
        if self.mixer == "linear_attention":
            mix = GatedDeltaNet(
                c.hidden_size, c.linear_num_key_heads, c.linear_num_value_heads,
                c.linear_key_head_dim, c.linear_value_head_dim,
                c.linear_conv_kernel_dim, c.rms_norm_eps, dtype=self.dtype,
                name="linear_attn")
        elif self.mixer == "full_attention":
            mix = Attention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.rms_norm_eps, c.rope_theta, dtype=self.dtype,
                head_dim=c.head_dim,
                rotary_dim=int(c.head_dim * c.partial_rotary_factor),
                output_gate=True, zero_centred_norms=True, name="self_attn")
        else:
            raise ValueError(f"unknown mixer {self.mixer!r}")
        mixed = mix(normed)
        with jax.named_scope("mpi4dl_part_block"):
            h = x + mixed
            normed = RMSNorm(c.rms_norm_eps, True, name="post_attention_layernorm")(h)
        moe = ExpertFFN(
            c.hidden_size, c.moe_intermediate_size, c.router_experts,
            c.num_experts, c.first_expert, c.num_experts_per_tok,
            c.norm_topk_prob, expert_bias=False, dtype=self.dtype,
            scoring="softmax", shared_width=c.shared_expert_intermediate_size,
            name="mlp")
        fed = moe(normed)
        with jax.named_scope("mpi4dl_part_block"):
            return h + fed


class Qwen3NextHead(nn.Module):
    """Final ``N`` and the linear head: logits over the vocabulary held."""

    config: Qwen3NextConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = RMSNorm(c.rms_norm_eps, True, name="norm")(x).astype(self.dtype)
        return linear(c.vocab_size, self.dtype, "lm_head")(x)


def qwen3_next(config: "dict | Qwen3NextConfig", dtype: Any = jnp.float32) -> list[nn.Module]:
    """The model of ``config`` as a flat cell list: embedding, one cell a
    layer, head."""
    if not isinstance(config, Qwen3NextConfig):
        config = Qwen3NextConfig.from_dict(config)
    layers = [Qwen3NextLayer(config, config.mixer(i), dtype)
              for i in range(config.num_hidden_layers)]
    return [Qwen3NextEmbed(config, dtype), *layers, Qwen3NextHead(config, dtype)]
