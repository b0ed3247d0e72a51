#!/usr/bin/env python3
"""What the comparison that decides ``correct`` can see in a block-diffusion
cell, read on the chip: every compared number as ``tools/readings.py`` reads
it, and beside it what that tool cannot say.

    python chipbench/tools/blockdiff_probe.py --workload <cell> --seeds 11,12 \
        [--control fp8 --control-seeds 2] [--witness bf16] \
        [--faults causal,leak,block8,unnormalised,next_share --fault-seeds 1] [--out chiprun_out/x.json]

1. **Named leaves.** ``grad_norm_gap_worst_leaf`` and
   ``change_norm_gap_worst_leaf`` name no leaf. Here the three leaves with
   the largest gap are named, with the program's and the reference's norms,
   for the program, for the control and for a **witness**: the plain
   reference itself in bfloat16 arithmetic (``reference/plain.py``'s mode
   ``bf16``) following the same batches from the same weights. The witness
   shares no code with the program; a gap that both show at the same leaf is
   the arithmetic's on these weights, not a kernel's.
2. **The mask token's routing.** Every masked row enters as one token. For
   each expert cell, from the float32 reference's own input of that cell:
   how many distinct top-k sets the masked rows pick and the share of the
   commonest, the experts the two commonest sets differ in and whether this
   chip holds them, and how many rows pick another set once that input is
   rounded to bfloat16 (what a tapped program cell is fed), masked rows and
   the others apart.
3. **Planted faults.** The tapped attention cell and the tapped expert cell
   of the program are run again with a fault planted, against the same
   reference numbers. In the mask: ``causal`` (plain causal attention over
   the ``2 L`` rows as they lie: the clean copy sees the whole noisy one),
   ``leak`` (a clean row also sees the noisy rows of earlier blocks; plain
   blocked path), ``block8`` (the cell built with blocks of twice the
   length). In the expert layer: ``unnormalised`` (the chosen experts'
   weights not divided by their sum), ``next_share`` (the cell built for the
   next chip's experts over this chip's weights). Each fault's three errors
   are printed; the cell's file holds them against its limits.

Needs the chip the cell asks for, like ``run.py``; one process, one set-up.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # see run.py

KEYS = ("cell_y_err", "cell_dv_err", "cell_dx_err")


# -- planted faults ------------------------------------------------------------


MASK_FAULTS = ("causal", "leak")
CELL_FAULTS = {  # fault -> (the kind of cell it is planted in, the changed settings)
    "block8": ("attn_blockdiff", lambda c: {"block_length": 2 * c.block_length}),
    "unnormalised": ("moe_blockdiff", lambda c: {"norm_topk_prob": not c.norm_topk_prob}),
    "next_share": ("moe_blockdiff", lambda c: {"first_expert": c.first_expert + c.num_experts}),
}


def fault_kind(fault: str) -> str:
    return "attn_blockdiff" if fault in MASK_FAULTS else CELL_FAULTS[fault][0]


@contextlib.contextmanager
def planted(fault: str, trainer=None, index=None):
    """The program with ``fault`` planted: in its attention under the block
    mask, or in cell ``index`` of ``trainer`` built from changed settings."""
    import dataclasses

    from mpi4dl_tpu.ops import sequence

    sound = sequence.block_diffusion_attention
    kept = {name: getattr(sequence, name) for name in ("_visible", "_key_ranges")}

    def causal(q, k, v, block, mask):
        return sequence.causal_attention(q, k, v, block)

    def leaky_ranges(start, end, mask):
        runs = kept["_key_ranges"](start, end, mask)
        length, unit = mask
        reach = (end - 1 - length) // unit * unit if start >= length else 0
        return ([(0, reach)] if reach > 0 else []) + runs

    def leaky_visible(rows, keys, mask):
        length, unit = mask
        r, s = rows[:, None], keys[None, :]
        earlier = (r >= length) & (s < length) & (s // unit < (r - length) // unit)
        return kept["_visible"](rows, keys, mask) | earlier

    def leak(q, k, v, block, mask):
        return sequence.blocked_masked_attention(q, k, v, block, mask)

    cell = None
    try:
        if fault == "leak":
            sequence._key_ranges, sequence._visible = leaky_ranges, leaky_visible
        if fault in MASK_FAULTS:
            sequence.block_diffusion_attention = {"causal": causal, "leak": leak}[fault]
        else:
            cell = trainer.cells[index]
            changed = dataclasses.replace(cell.config, **CELL_FAULTS[fault][1](cell.config))
            trainer.cells[index] = cell.clone(config=changed)
        yield
    finally:
        sequence.block_diffusion_attention = sound
        for name, fn in kept.items():
            setattr(sequence, name, fn)
        if cell is not None:
            trainer.cells[index] = cell


# -- named leaves --------------------------------------------------------------


def leaf_names(params) -> list:
    import jax

    names = []
    for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
        parts = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        cell = int(parts[0])
        names.append(f"cell{cell:02d}/" + "/".join(p for p in parts[1:] if p != "params"))
    return names


def worst_leaves(names, got, ref, count=3):
    """The ``count`` leaves with the largest gap, as ``check.norm_gaps``
    takes a leaf's gap: ``|p - r|`` over the reference's norm of that leaf
    or of the median leaf, whichever is larger."""
    import statistics

    import numpy as np

    p, r = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    floor = statistics.median(r.tolist())
    gaps = np.abs(p - r) / np.maximum(r, floor)
    order = np.argsort(-gaps)[:count]
    return [{"leaf": names[i], "gap": float(gaps[i]), "program": float(p[i]),
             "reference": float(r[i]), "median_leaf": float(floor)} for i in order]


# -- the mask token's routing ----------------------------------------------------

_CHOOSERS: dict = {}


def routing_report(reference, model, variables, h, masked):
    """One expert cell's choice of experts from the reference's input ``h
    [1, 2L, hidden]`` of that cell; ``masked [2L]`` marks the noisy copy's
    masked rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference.plain import Scope

    s = reference.sizes(model)
    if s not in _CHOOSERS:  # one traced program for all of a model's expert cells
        def choose(v, h):
            scope = Scope(v["params"])
            n = reference.rms_norm(scope.sub("post_attention_layernorm"), h, s.eps)
            return reference.routing(scope.sub("mlp"), n, s)[0]

        _CHOOSERS[s] = jax.jit(choose)
    choose = _CHOOSERS[s]
    exact = np.sort(np.asarray(choose(variables, h))[0], axis=-1)
    rounded = np.sort(np.asarray(
        choose(variables, h.astype(jnp.bfloat16).astype(jnp.float32)))[0], axis=-1)
    moved = (exact != rounded).any(axis=-1)
    sets, counts = np.unique(exact[masked], axis=0, return_counts=True)
    order = np.argsort(-counts)
    held = lambda e: bool(s.first <= e < s.first + s.held)  # noqa: E731
    out = {
        "masked_rows": int(masked.sum()),
        "masked_distinct_sets": int(len(counts)),
        "masked_commonest_share": float(counts[order[0]] / counts.sum()),
        "commonest_held": int(sum(held(e) for e in sets[order[0]])),
        "rows_moved_by_bf16_input": {
            "masked": int(moved[masked].sum()), "others": int(moved[~masked].sum())},
    }
    if len(order) > 1:
        a, b = set(sets[order[0]].tolist()), set(sets[order[1]].tolist())
        out["second_share"] = float(counts[order[1]] / counts.sum())
        out["differ_in"] = [{"expert": int(e), "held": held(e)} for e in sorted(a ^ b)]
    # the held experts whose pairs the rounding moves, masked rows alone
    gained = np.zeros(s.experts, np.int64)
    for before, after in zip(exact[masked & moved], rounded[masked & moved]):
        for e in set(after.tolist()) - set(before.tolist()):
            gained[e] += 1
        for e in set(before.tolist()) - set(after.tolist()):
            gained[e] -= 1
    out["held_pairs_moved_masked"] = {
        str(e): int(gained[e]) for e in range(s.first, s.first + s.held) if gained[e]}
    return out


# -- one seed ------------------------------------------------------------------


def probe(session, seed, control, witness, faults, say):
    """One seed's row: ``Session.compare``'s walk (first steps through the
    window's loop, the float32 follower with the seed's taps, then the
    control) with every cell tapped, so that each expert cell's input is
    seen, and the follower's leaf norms kept."""
    import jax
    import numpy as np

    from chipbench.harness import check
    from chipbench.reference import plain

    first = session.first_steps(seed, session.check_steps)
    first.loop.state = None
    taps = check.sample_taps(session.kinds, seed)
    kinds = session.kinds
    x0, _ = first.batches[0]
    length = x0.shape[1] // 2
    mask_id = int(session.cell.model["vocab_size"]) - 1
    masked = np.concatenate([np.asarray(x0[0, :length]) == mask_id, np.zeros(length, bool)])
    errors = {k: {} for k in KEYS}
    control_errors = {k: {} for k in KEYS}
    by_cell, routing, planted_errors = {}, {}, {}

    def on_tap(follower, index, x):
        if kinds[index] == "moe_blockdiff":
            routing[index] = routing_report(
                session.reference, session.cell.model, follower.params[index], x, masked)
        if index not in taps:
            return
        fn, variables = session.ref_cells[index], follower.params[index]
        y_shape = jax.eval_shape(lambda v, x_: fn(plain.Scope(v["params"]), x_), variables, x)
        ct = check.seeded_cotangent(y_shape, seed, index)
        if index == len(kinds) - 1:
            ref = check.reference_cell_vjp(fn, "f32", variables, x, ct)
        else:
            ref = (follower.forward_cell(index, x),) + tuple(follower.vjp_cell(index, x, ct))
        got = check.program_cell_vjp(session.trainer, index, variables, x, ct)
        by_cell[index] = {"kind": kinds[index]}
        for key, a, b in zip(KEYS, got, ref):
            errors[key][index] = by_cell[index][key] = check.relative_l2(a, b)
        if control:
            got = check.reference_cell_vjp(fn, control, variables, x, ct)
            for key, a, b in zip(KEYS, got, ref):
                control_errors[key][index] = by_cell[index][key + ".control"] = (
                    check.relative_l2(a, b))
        for fault in faults:
            if fault_kind(fault) == kinds[index]:
                t0 = time.perf_counter()
                try:
                    with planted(fault, session.trainer, index):
                        got = check.program_cell_vjp(session.trainer, index, variables, x, ct)
                    planted_errors[fault] = {
                        key: check.relative_l2(a, b) for key, a, b in zip(KEYS, got, ref)}
                except Exception as err:  # a fault that does not fit: say so, go on
                    planted_errors[fault] = {"error": f"{type(err).__name__}: {err}"[:400]}
                planted_errors[fault]["seconds"] = time.perf_counter() - t0
                say(phase="fault", seed=seed, cell=index, fault=fault, **planted_errors[fault])

    followed = session._follow(first, "f32", range(len(kinds)), on_tap)
    numbers = session._numbers(first, followed, errors)
    names = leaf_names(jax.eval_shape(session.make_params, 0))
    row = {
        "seed": seed, "taps": {str(i): kinds[i] for i in taps}, "program": numbers,
        "cells": {str(i): v for i, v in by_cell.items()},
        "routing": {str(i): v for i, v in routing.items()},
        "faults": planted_errors,
        "leaves": {"program": {
            "grad": worst_leaves(names, first.grad_norms, followed.grad_norms),
            "change": worst_leaves(names, first.change_norms, followed.change_norms)}},
    }
    for name, mode, cell_errors in (("control", control, control_errors),
                                    ("witness", witness, {k: {} for k in KEYS})):
        if not mode:
            continue
        stand_in = session._follow(first, mode)
        row[name] = session._numbers(stand_in, followed, cell_errors)
        row["leaves"][name] = {
            "grad": worst_leaves(names, stand_in.grad_norms, followed.grad_norms),
            "change": worst_leaves(names, stand_in.change_norms, followed.change_norms)}
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated whole numbers")
    ap.add_argument("--control", default=None, choices=("bf16", "fp8"))
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="take the control on the first N seeds only")
    ap.add_argument("--witness", default=None, choices=("bf16", "fp8"),
                    help="the reference in this arithmetic follows every seed's steps")
    ap.add_argument("--faults", default="",
                    help="comma-separated: causal, leak, block8, unnormalised, next_share")
    ap.add_argument("--fault-seeds", type=int, default=1,
                    help="plant the faults on the first N seeds only")
    ap.add_argument("--out", default=None)
    opts = ap.parse_args(argv)

    from chipbench import run
    from chipbench.harness import spec
    from chipbench.harness.session import Session, say

    cell = spec.Cell(opts.workload)
    run.find_chips(cell.chips)
    session = Session(cell)
    faults = [f for f in opts.faults.split(",") if f]
    rows = []
    for n, seed in enumerate(int(s) for s in opts.seeds.split(",")):
        t0 = time.perf_counter()
        control = opts.control
        if opts.control_seeds is not None and n >= opts.control_seeds:
            control = None
        row = probe(session, seed, control, opts.witness,
                    faults if n < opts.fault_seeds else [], say)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        say(phase="probe", **row)
        if opts.out:  # after every seed: a later one may not end
            os.makedirs(os.path.dirname(os.path.join(ROOT, opts.out)), exist_ok=True)
            with open(os.path.join(ROOT, opts.out), "w") as f:
                json.dump({"workload": cell.name, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
