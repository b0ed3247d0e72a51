"""The comparison that decides ``correct``.

Two groups of numbers, each printed beside its limit (the limits and the
readings they were set from are in ``chipbench/cells/<cell>.json``):

1. **Whole step, on the timed object.** The compiled step and state that
   the window drives take their first steps from the seed through the
   window's own loop; the float32 reference follows the same steps layer
   by layer. Compared: each step's loss, the norm of the first gradient as
   the optimiser gets it (the momentum trace after one step) and the norm
   of the parameters' change after the steps. A freshly initialised net
   with batch-2 BatchNorm amplifies rounding by a few thousand through its
   backward pass (``PERF.md`` section 6), so no limit on these separates
   bf16 from fp8; they are held against the faults they are there to
   catch: a part of the batch or a tile left out (loss), a step that
   returns its state unchanged (the norms).
2. **Cell by cell, teacher-forced.** A seeded sample of the program's own
   cells (its modules, its bf16 arithmetic, its kernels; for a spatial
   cell inside ``shard_map`` over the trainer's mesh) is fed the
   reference's input of that cell and a seeded cotangent; output, input
   cotangent and parameter cotangents are compared with the reference's by
   relative L2 error. Nothing has been amplified yet at a cell's input, so
   this is the number a lower precision fails. A cell fed integers (token
   ids) has no input cotangent: its VJPs return ``(y, dv)`` and its
   ``cell_dx_err`` is not taken.
"""

from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def leaf_norms(tree):
    return [jnp.linalg.norm(a.astype(jnp.float32).ravel()) for a in jax.tree.leaves(tree)]


@jax.jit
def leaf_norms_of_difference(a, b):
    return leaf_norms(jax.tree.map(lambda x, y: x - y, a, b))


def norm_gaps(program, reference, whole_floor=0.0):
    """Per leaf, |program's norm - reference's| over the reference's norm of
    that leaf or of the median leaf, whichever is larger (some gradients
    are all but zero). Returns ``(worst leaf, whole tree)``: the second is
    the same gap taken on the norms of all leaves together, over the
    reference's or ``whole_floor``, whichever is larger (a whole first
    gradient is all but zero when every image of the batch carries one
    label: see :func:`whole_norm`'s use in ``session.py``)."""
    p = np.asarray([float(v) for v in program], np.float64)
    r = np.asarray([float(v) for v in reference], np.float64)
    floor = statistics.median(r.tolist())
    worst = float(np.max(np.abs(p - r) / np.maximum(r, floor)))
    scale = max(whole_norm(r), float(whole_floor))
    whole = float(abs(whole_norm(p) - whole_norm(r)) / scale)
    return worst, whole


def whole_norm(leaf_norms_) -> float:
    """The norm of a whole tree from the norms of its leaves."""
    return float(np.linalg.norm(np.asarray(leaf_norms_, np.float64)))


@jax.jit
def _error_energy(a, b):
    num = sum(
        jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )
    den = sum(jnp.sum(jnp.square(y.astype(jnp.float32))) for y in jax.tree.leaves(b))
    return num, den


def relative_l2(a, b) -> float:
    """||a - b|| / ||b|| over all leaves of the two trees together."""
    num, den = _error_energy(a, b)
    return math.sqrt(float(num) / float(den))


def sample_taps(kinds, seed: int):
    """One cell index of each kind, drawn from the seed: always a stem, a
    head and one of every kind between (normal / reduction, stride-1 /
    stride-2)."""
    rng = np.random.default_rng(seed)
    taps = []
    for kind in dict.fromkeys(kinds):
        members = [i for i, k in enumerate(kinds) if k == kind]
        taps.append(int(members[rng.integers(len(members))]))
    return sorted(taps)


def seeded_cotangent(y, seed: int, index: int):
    """A standard-normal cotangent for the cell's output, from the seed."""
    leaves, treedef = jax.tree.flatten(y)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), index)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef,
        [jax.random.normal(k, a.shape, jnp.float32) for k, a in zip(keys, leaves)],
    )


def reference_cell_vjp(fn, mode, variables, x, ct):
    """``(y, dv, dx)`` of one reference cell in ``mode``'s arithmetic;
    ``(y, dv)`` where the input is integer."""
    from chipbench.reference.plain import Scope, vjp

    def run(v, x, ct):
        y, pull = vjp(lambda v_, x_: fn(Scope(v_["params"], mode), x_), v, x)
        return (y,) + tuple(pull(ct))

    return jax.jit(run)(variables, x, ct)


def program_cell_vjp(trainer, index, variables, x, ct):
    """``(y, dv, dx)`` of the program's own cell ``index`` at the input and
    cotangent given, floating inputs in the program's dtype (``(y, dv)``
    where the input is integer); a spatial cell runs on tiles under
    ``shard_map`` over the trainer's mesh, as it does in the step."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench.reference.plain import cast_floating, vjp

    cell = trainer.cells[index]
    dtype = jnp.dtype(getattr(cell, "dtype", None) or jnp.float32)
    spatial = index < trainer.n_spatial

    def apply(v, x):
        return cell.apply(v, x)

    if spatial:
        x_specs = jax.tree.map(lambda _: trainer.x_spec, x)
        y_specs = jax.tree.map(lambda _: trainer.x_spec, ct)
        apply = jax.shard_map(
            apply, mesh=trainer.mesh, in_specs=(P(), x_specs),
            out_specs=y_specs, check_vma=False,
        )
        put = lambda t, s: jax.device_put(  # noqa: E731
            t, jax.tree.map(lambda sp: NamedSharding(trainer.mesh, sp), s)
        )
        variables = jax.device_put(variables, NamedSharding(trainer.mesh, P()))
        x, ct = put(x, x_specs), put(ct, y_specs)

    def run(v, x, ct):
        y, pull = vjp(apply, v, cast_floating(x, dtype))
        ct = jax.tree.map(lambda c, o: c.astype(o.dtype), ct, y)
        return (y,) + tuple(pull(ct))

    return jax.jit(run)(variables, x, ct)


def verdict(numbers: dict, limits: dict):
    """``(correct, compared)``: every number is printed beside its limit and
    kept, ``{name: {"value", "limit"}}``, for the end of the result line;
    ``correct`` is true when every number that has a limit is finite and
    within it. Numbers without a limit are shown for the record."""
    import json

    correct, compared = True, {}
    for name, value in numbers.items():
        limit = limits.get(name)
        ok = limit is None or (math.isfinite(value) and value <= limit)
        correct = correct and ok
        # strict JSON has no NaN: a number that is not finite goes as text
        shown = value if math.isfinite(value) else repr(value)
        compared[name] = {"value": shown, "limit": limit}
        print(json.dumps({
            "check": name, "value": shown, "limit": limit,
            "ok": bool(ok) if limit is not None else None,
        }), flush=True)
    missing = sorted(set(limits) - set(numbers))
    if missing:
        print(json.dumps({"check": "missing", "names": missing}), flush=True)
        compared.update({name: {"value": None, "limit": limits[name]} for name in missing})
        correct = False
    return correct, compared
