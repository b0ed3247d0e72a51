"""A toy token-sequence family's plain reference, for ``test_family.py``:
an embedding, two expert cells, a per-position head. It is what a new
family brings as a file of its own: ``cells`` and ``kinds`` beside the three
hooks the image classifiers leave out. Imports nothing of the toy program
(``toy_tokens_program.py``); parameter names are those of its flax tree.

An expert cell holds two dense experts and sends the even positions to the
first, the odd ones to the second. As a plain reference does, it multiplies
every expert by every token and keeps one product, so a FLOP count read off
its jaxpr is twice the model's: the family's own count is
``train_flops_per_sample``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import plain


@functools.lru_cache(maxsize=None)
def _embed(vocab, hidden):
    def embed(scope, ids):
        table = scope.sub("embed").param("embedding", (vocab, hidden), 1.0)
        return table[ids]

    return embed


@functools.lru_cache(maxsize=None)
def _experts(hidden):
    def experts(scope, x):
        odd = (jnp.arange(x.shape[1]) % 2 == 1)[None, :, None]
        even_y, odd_y = (
            plain.dense(scope.sub(name), x, hidden) for name in ("even", "odd"))
        return x + jax.nn.relu(jnp.where(odd, odd_y, even_y))

    return experts


@functools.lru_cache(maxsize=None)
def _head(vocab):
    def head(scope, x):
        return plain.dense(scope.sub("out"), x, vocab)

    return head


def cells(model: dict) -> list:
    vocab, hidden = int(model["vocab_size"]), int(model["hidden_size"])
    return [_embed(vocab, hidden), _experts(hidden), _experts(hidden), _head(vocab)]


def kinds(model: dict) -> list:
    return ["stem", "experts", "experts", "head"]


def input_spec(model: dict, traffic: dict):
    """Token ids: the sequence length is the traffic mix's."""
    return (int(traffic["sequence_length"]),), jnp.int32


def loss(logits, labels):
    """Mean softmax cross-entropy over every position of every sequence."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def train_flops_per_sample(model: dict, traffic: dict) -> float:
    """3 x forward: a token multiplies one expert of each expert cell
    and the head."""
    vocab, hidden = int(model["vocab_size"]), int(model["hidden_size"])
    per_token = 2.0 * (2 * hidden * hidden + hidden * vocab)
    return 3.0 * per_token * int(traffic["sequence_length"])
