"""Nemotron-H (``model_type: nemotron_h``; here the tower that
Nemotron-Labs-TwoTower-30B-A3B's ``config.json`` describes) as a flat cell
list.

The model is described by its published ``config.json``
(https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16/blob/main/config.json).
Every layer is ONE mixer, ``y = x + Mixer(N(x))`` with ``N`` a plain RMSNorm
(scale from 1), and ``hybrid_override_pattern`` names the mixer of each
layer by a letter: ``M`` the Mamba-2 state-space mixer, ``E`` the expert
layer (sigmoid scores over all the routed experts, the top
``num_experts_per_tok`` on score + bias, their scores normalised and times
``routed_scaling_factor``; every expert ``W2 relu(W1 x)^2``; one shared
expert of that form, ungated), ``*`` causal grouped-query attention with no
positional embedding and no q/k norm. All three are ``ops/sequence.py``'s.
The cell list is an embedding cell, one cell a layer and a head cell (final
``N``, a linear head of its own), as ``models/lfm2.py``'s is.

Not built, because the published config holds no key of it: the second
(denoiser) tower of the TwoTower release, its adaLN, the cross-tower
conditioning and block-diffusion decoding. This is the tower the config
describes, trained causally on the next token.

**A chip's share of a deployment** is stated as ``LFM2Config`` reads it:
``n_routed_experts`` the experts held, ``vocab_size`` the slice of the
vocabulary, ``hybrid_override_pattern`` the layers of this stage, and under
``cut.n_routed_experts`` the ``published`` width of the router and the
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
    Mamba2,
    RMSNorm,
    linear,
)

MIXERS = "ME*"  # a layer's letter: Mamba-2, expert layer, attention
# query rows whose scores are alive at once on attention's plain path: at 32
# heads and two 8,192-token sequences 256 rows are 0.5 GiB of float32 scores,
# what the other token models' blocks of 512 hold at their batch and heads.
# (Where the fused kernels dispatch, as at the chip's cell since PR 40, they
# take their own block from the length; on the plain path the chip's
# scheduler keeps many blocks of the backward alive while memory allows, and
# the cure is in the shared backward: ROADMAP S18.)
ATTENTION_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    hidden_size: int
    pattern: str                # one letter of ``MIXERS`` a layer
    layer_norm_epsilon: float
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    time_step: tuple            # time_step_min, _max, _floor
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    n_routed_experts: int       # held on this chip
    router_experts: int         # the router's width: all of them
    first_expert: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    vocab_size: int

    @classmethod
    def from_dict(cls, config: dict) -> "NemotronHConfig":
        """From a ``config.json``'s keys (and its ``cut`` group, if any)."""
        pattern = str(config["hybrid_override_pattern"])
        if set(pattern) - set(MIXERS):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: a layer is one of {list(MIXERS)} "
                "(Mamba-2, expert layer, attention); a dense feed-forward is not built")
        if len(pattern) != int(config["num_hidden_layers"]):
            raise ValueError("hybrid_override_pattern must name num_hidden_layers layers")
        if int(config.get("n_group", 1)) != 1 or int(config.get("topk_group", 1)) != 1:
            raise ValueError("group-limited routing is not built: n_group and topk_group 1")
        for key in ("mlp_bias", "attention_bias", "use_bias", "mamba_proj_bias"):
            if config.get(key):
                raise ValueError(f"{key} is not supported: no projection has a bias")
        if config.get("mlp_hidden_act", "relu2") != "relu2":
            raise ValueError("the feed-forwards are squared ReLUs: mlp_hidden_act relu2")
        if not config.get("use_conv_bias", True) or int(config.get("n_shared_experts", 1)) != 1:
            raise ValueError("the convolution has its bias and the layer one shared expert")
        share = config.get("cut", {}).get("n_routed_experts", {})
        held = int(config["n_routed_experts"])
        return cls(
            hidden_size=int(config["hidden_size"]),
            pattern=pattern,
            layer_norm_epsilon=float(config["layer_norm_epsilon"]),
            mamba_num_heads=int(config["mamba_num_heads"]),
            mamba_head_dim=int(config["mamba_head_dim"]),
            n_groups=int(config["n_groups"]),
            ssm_state_size=int(config["ssm_state_size"]),
            conv_kernel=int(config["conv_kernel"]),
            chunk_size=int(config["chunk_size"]),
            time_step=(float(config["time_step_min"]), float(config["time_step_max"]),
                       float(config["time_step_floor"])),
            num_attention_heads=int(config["num_attention_heads"]),
            num_key_value_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            moe_intermediate_size=int(config["moe_intermediate_size"]),
            moe_shared_expert_intermediate_size=int(
                config["moe_shared_expert_intermediate_size"]),
            n_routed_experts=held,
            router_experts=int(share.get("published", held)),
            first_expert=int(share.get("first", 0)),
            num_experts_per_tok=int(config["num_experts_per_tok"]),
            norm_topk_prob=bool(config["norm_topk_prob"]),
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            vocab_size=int(config["vocab_size"]),
        )


class NemotronHEmbed(nn.Module):
    """Token ids ``[batch, positions]`` -> ``[batch, positions, hidden]``."""

    config: NemotronHConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, ids):
        c = self.config
        with jax.named_scope("mpi4dl_part_block"):
            return Embedding(
                c.vocab_size, c.hidden_size, name="embed_tokens")(ids).astype(self.dtype)


class NemotronHLayer(nn.Module):
    """``y = x + Mixer(N(x))``, the mixer by the pattern's letter."""

    config: NemotronHConfig
    kind: str   # "M", "E" or "*"
    dtype: Any = jnp.bfloat16

    @property
    def counters(self):
        """The collection an expert layer counts its token-expert pairs
        into; ``Trainer`` reads it back from the cells that name one."""
        return COUNTERS if self.kind == "E" else None

    @nn.compact
    def __call__(self, x):
        c = self.config
        with jax.named_scope("mpi4dl_part_block"):
            normed = RMSNorm(c.layer_norm_epsilon, name="norm")(x)
        if self.kind == "M":
            mixer = Mamba2(
                c.hidden_size, c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                c.ssm_state_size, c.conv_kernel, c.chunk_size, c.layer_norm_epsilon,
                c.time_step, self.dtype, name="mixer")
        elif self.kind == "E":
            mixer = ExpertFFN(
                c.hidden_size, c.moe_intermediate_size, c.router_experts,
                c.n_routed_experts, c.first_expert, c.num_experts_per_tok,
                c.norm_topk_prob, c.routed_scaling_factor, dtype=self.dtype,
                shared_width=c.moe_shared_expert_intermediate_size,
                activation="relu2", shared_gate=False, name="mixer")
        elif self.kind == "*":
            mixer = Attention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.layer_norm_epsilon, 0.0, dtype=self.dtype, head_dim=c.head_dim,
                rotary_dim=0, qk_norm=False, block=ATTENTION_BLOCK, name="mixer")
        else:
            raise ValueError(f"unknown layer kind {self.kind!r}")
        mixed = mixer(normed)
        with jax.named_scope("mpi4dl_part_block"):
            return x + mixed


class NemotronHHead(nn.Module):
    """Final ``N`` and the linear head: logits over the vocabulary held."""

    config: NemotronHConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.config
        x = RMSNorm(c.layer_norm_epsilon, name="norm_f")(x).astype(self.dtype)
        return linear(c.vocab_size, self.dtype, "lm_head")(x)


def nemotron_h(config: "dict | NemotronHConfig", dtype: Any = jnp.float32) -> list[nn.Module]:
    """The model of ``config`` as a flat cell list: embedding, one cell a
    layer, head."""
    if not isinstance(config, NemotronHConfig):
        config = NemotronHConfig.from_dict(config)
    layers = [NemotronHLayer(config, kind, dtype) for kind in config.pattern]
    return [NemotronHEmbed(config, dtype), *layers, NemotronHHead(config, dtype)]
