"""Device time under the program's ``jax.named_scope``s.

The chip's trace names an op by its HLO instruction's text and carries no
name stack (read off a v5e trace in PR 31: an op event's stats are
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale Multiplier``);
the compiled step's text gives every instruction its ``op_name``, the jax
name stack, in which a scope survives ``jvp``, ``transpose`` and remat
(``jit(_train_step)/transpose(jvp())/checkpoint/.../lfm2_moe/...``). This
joins the two by instruction name. A fusion carries the name stack of one of
the ops fused into it, so one that spans two scopes counts under the scope
it happens to carry; an instruction the compiler made itself (a copy, a
slice, an async pair's ``-done``) carries none and counts under no scope.

The compiled text comes from ``trainer.compiled_step`` on shapes like the
window's: one more trace of the step and a cache hit, taken once per run and
kept on the context. A program without that accessor reads nothing.
"""

from __future__ import annotations

import re

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"', re.M)
_KEY = "_step_op_names"


def _step_arguments(context):
    """``(state, x, y)`` as shapes with the shardings the window's loop
    gives them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from chipbench.reference import plain
    from mpi4dl_tpu.train import TrainState

    trainer, session = context["trainer"], context["session"]
    params = jax.eval_shape(session.make_params, 0)
    x = jax.ShapeDtypeStruct(session.x_shape, session.x_dtype)

    def forward(ps, h):
        for cell, p in zip(session.ref_cells, ps):
            h = cell(plain.Scope(p["params"]), h)
        return h

    labels = jax.eval_shape(forward, params, x).shape[:-1]
    state = jax.eval_shape(
        lambda p: TrainState(p, trainer.tx.init(p), jnp.zeros((), jnp.int32)), params)

    def placed(tree, spec):
        sharding = NamedSharding(trainer.mesh, spec)
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)

    return (placed(state, PartitionSpec()), placed(x, trainer.x_spec),
            placed(jax.ShapeDtypeStruct(labels, jnp.int32), trainer.y_spec))


def step_op_names(context) -> dict:
    """``{HLO instruction name: op_name}`` of the compiled step; empty for a
    program that has no ``compiled_step``."""
    if _KEY not in context:
        compiled_step = getattr(context["trainer"], "compiled_step", None)
        text = compiled_step(*_step_arguments(context)).as_text() if compiled_step else ""
        context[_KEY] = dict(_INSTRUCTION.findall(text))
    return context[_KEY]


def ms_per_step(context, scopes):
    """Device milliseconds per step, first chip, in the ops whose name stack
    (or own name) holds one of ``scopes``; None where the run was not traced
    or no op carries one."""
    from . import xtrace

    reduced = context["reduced"]
    if reduced is None:
        return None
    op_names = step_op_names(context)
    chip = reduced.chips[0]
    events = [
        ev for ev in chip["ops"]
        if any(scope in ev.op or scope in op_names.get(ev.op, "") for scope in scopes)
    ]
    if not events:
        return None
    return 1e3 * sum(e - s for s, e in xtrace.clip(events, *chip["window"])) / 1e9 / reduced.steps
