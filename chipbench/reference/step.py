"""The reference's training step, followed layer by layer.

Loss: the family's (``loss(logits, labels)``, handed in by the harness; mean
softmax cross-entropy where the reference module defines none). Optimiser:
SGD with momentum, ``trace = g + momentum * trace; p -= lr * trace`` (the
configuration file states ``lr`` and ``momentum``). The forward keeps each
cell's input and the backward takes one cell's VJP at a time, recomputing
that cell's forward, so that a float32 step of a model whose bf16 step
fills the chip still fits: at any moment one cell's residuals are alive.
Cell inputs beyond ``device_budget`` bytes wait on the host. An integer
input (token ids) has no cotangent: the first cell's VJP is then taken
over its parameters alone and the backward pass ends there.
"""

from __future__ import annotations

import concurrent.futures
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import plain
from .plain import Scope, jitted


def _cell_vjp(fn, mode):
    """``(v, x, ct) -> (dv, dx)``; ``(dv,)`` where the input has no
    cotangent."""
    def vjp(v, x, ct):
        _, pull = plain.vjp(lambda v_, x_: fn(Scope(v_["params"], mode), x_), v, x)
        return pull(ct)

    return jax.jit(vjp)


def _head_loss_grad(fn, mode, loss_fn):
    def loss(v, x, labels):
        return loss_fn(fn(Scope(v["params"], mode), x), labels)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _sgd(params, trace, grads, lr, momentum):
    trace = jax.tree.map(lambda g, t: g + momentum * t, grads, trace)
    params = jax.tree.map(lambda p, t: p - lr * t, params, trace)
    return params, trace


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def _shapes(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _signature(tree):
    return tuple((a.shape, str(a.dtype)) for a in jax.tree.leaves(tree))


def compile_all(jobs: dict, workers=None) -> dict:
    """``{key: (jitted function, argument shapes)}`` -> ``{key: compiled}``.
    Lowered one after the other, compiled side by side: the chip's compiler
    works one program on one core (a wide float32 cell takes it a minute
    or two), and a cold run has some thirty of them to make."""
    lowered = {k: fn.lower(*args) for k, (fn, args) in jobs.items()}
    # four at a time: one compile of a wide float32 cell takes several GB of
    # the host's memory, and twelve side by side ran a 40 GiB machine out
    workers = workers or max(1, min(4, (os.cpu_count() or 2) - 1))
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        pending = {k: pool.submit(low.compile) for k, low in lowered.items()}
        return {k: f.result() for k, f in pending.items()}


class Follower:
    """Follows training steps of ``cells`` from ``params`` in ``mode``'s
    arithmetic. Cells of equal settings and input shapes share one compiled
    forward and one compiled VJP; all are compiled before the first step."""

    def __init__(self, cells, params, lr, momentum, loss, mode="f32",
                 device_budget=2 << 30):
        self.cells, self.mode, self.loss = list(cells), mode, loss
        self.params = params
        self.trace = jax.tree.map(jnp.zeros_like, params)
        self.lr, self.momentum = float(lr), float(momentum)
        self.device_budget = device_budget
        self.programs = None
        self.keys = None
        self.prepare_s = 0.0  # seconds spent compiling (or loading) programs

    def prepare(self, x, labels):
        """Compile every distinct forward and VJP for inputs like ``x``."""
        jobs, self.keys = {}, []
        h = _shapes(x)
        for i, fn in enumerate(self.cells):
            v = _shapes(self.params[i])
            key = (fn, _signature(v), _signature(h))
            self.keys.append(key)
            forward = jitted(fn, self.mode)
            y = jax.eval_shape(forward, v, h)
            if i == len(self.cells) - 1:
                jobs.setdefault(("head", key), (
                    _head_loss_grad(fn, self.mode, self.loss),
                    (v, h, _shapes(labels))))
            elif ("forward", key) not in jobs:
                if i and not plain.takes_cotangent(h):
                    raise TypeError(
                        f"cell {i} takes an integer input; only the first may")
                jobs[("forward", key)] = (forward, (v, h))
                jobs[("vjp", key)] = (_cell_vjp(fn, self.mode), (v, h, y))
            h = y
        t0 = time.perf_counter()
        self.programs = compile_all(jobs)
        self.prepare_s = time.perf_counter() - t0

    def forward_cell(self, i, x):
        return self.programs[("forward", self.keys[i])](self.params[i], x)

    def vjp_cell(self, i, x, ct):
        return self.programs[("vjp", self.keys[i])](self.params[i], x, ct)

    def forward(self, x):
        """The input of every cell: ``inputs[i]`` feeds cell i."""
        inputs, held = [], 0
        for i in range(len(self.cells) - 1):
            size = _nbytes(x)
            if held + size > self.device_budget:
                inputs.append(jax.tree.map(np.asarray, x))
            else:
                inputs.append(x)
                held += size
            x = self.forward_cell(i, x)
        inputs.append(x)
        return inputs

    def step(self, x, labels, taps=(), on_tap=None):
        """One step on the batch; returns ``(loss, grads)``. For each cell
        index in ``taps``, ``on_tap(self, index, cell_input)`` is called
        before the parameters move (the cell-by-cell comparison feeds that
        input to the program's cell)."""
        x = plain.cast_floating(jnp.asarray(x), jnp.float32)
        labels = jnp.asarray(labels, jnp.int32)
        if self.programs is None:
            self.prepare(x, labels)
        inputs = self.forward(x)
        n = len(self.cells)
        grads = [None] * n
        if n - 1 in taps:
            on_tap(self, n - 1, inputs[-1])
        (loss, (grads[-1], ct)) = self.programs[("head", self.keys[-1])](
            self.params[-1], inputs[-1], labels
        )
        for i in range(n - 2, -1, -1):
            xi = jax.tree.map(jnp.asarray, inputs[i])
            inputs[i] = None
            # (dv, dx); (dv,) from a first cell fed integers
            grads[i], *dx = self.vjp_cell(i, xi, ct)
            if i in taps:
                on_tap(self, i, xi)
            ct = dx[0] if dx else None
        self.params, self.trace = _sgd(
            self.params, self.trace, grads, self.lr, self.momentum
        )
        return float(loss), grads
