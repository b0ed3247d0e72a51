"""A stand-in program for the toy token-sequence family of
``test_family.py``: flax cells in bfloat16, a trainer with the attributes the
harness takes from ``train.Trainer``, the builder and the input stream a
configuration's ``entry_point`` names. What a ``model_config`` PR would add
to the program itself; here it only shows that the harness needs no edit.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from mpi4dl_tpu.train import TrainState, apply_cells


class Embed(nn.Module):
    vocab: int
    hidden: int
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, ids):
        return nn.Embed(self.vocab, self.hidden, dtype=self.dtype, name="embed")(ids)


class Experts(nn.Module):
    hidden: int
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        odd = (jnp.arange(x.shape[1]) % 2 == 1)[None, :, None]
        even_y, odd_y = (
            nn.Dense(self.hidden, dtype=self.dtype, name=name)(x)
            for name in ("even", "odd"))
        return x + nn.relu(jnp.where(odd, odd_y, even_y))


class Head(nn.Module):
    vocab: int
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        return nn.Dense(self.vocab, dtype=self.dtype, name="out")(x)


def token_cells(model: dict, dtype=jnp.float32) -> list:
    vocab, hidden = model["vocab_size"], model["hidden_size"]
    return [Embed(vocab, hidden, dtype), Experts(hidden, dtype),
            Experts(hidden, dtype), Head(vocab, dtype)]


def single_device_step(cells, learning_rate=0.001, momentum=0.9):
    """``(tx, step)`` as ``train.single_device_step``, with the loss taken
    at every position."""
    tx = optax.sgd(learning_rate, momentum=momentum)

    def step(state, x, y):
        def loss_fn(params):
            logits = apply_cells(cells, params, x).astype(jnp.float32)
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), {"loss": loss}

    return tx, step


class ToyTrainer:
    remat = False
    n_spatial = 0

    def __init__(self, cells, learning_rate, momentum):
        self.cells = cells
        self.mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        self.tx, step = single_device_step(cells, learning_rate, momentum)
        self.train_step = jax.jit(step, donate_argnums=0)

    def shard_batch(self, x, y):
        return x, y

    def record_memory_footprint(self, state, x, y):
        return None


def build_trainer(config: dict, batch_size: int):
    """``entry_point.build_trainer``: ``(trainer, cfg)``; ``cfg`` is what
    ``input_stream`` is handed, here the configuration itself."""
    opt = config["optimizer"]
    trainer = ToyTrainer(token_cells(config, jnp.bfloat16),
                         opt["learning_rate"], opt["momentum"])
    return trainer, config


def input_stream(cfg: dict, traffic: dict, seed: int):
    """``entry_point.input_stream``: uniform token ids and next-token
    labels from the seed, every row new."""
    rng = np.random.default_rng(seed)
    shape = (int(traffic["batch_size"]), int(traffic["sequence_length"]))
    while True:
        yield (rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32),
               rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32))
