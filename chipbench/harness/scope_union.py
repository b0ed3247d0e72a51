"""Device time under a scope whose ops nest: the union of their intervals.

``scopes.ms_per_step`` adds up the durations of a scope's op events, which is
the scope's device time as long as no event lies inside another. A ``while``
does: the trace's op line holds one event for the loop, from its first trip to
its last, and one for every op of its body inside that (read off the first
traced run of the Nemotron-H cell, PR 39: every op family's time summed to the
chip's busy time plus the ``while`` family's 127 ms a step). A scope that holds
a loop, as Mamba-2's scan does (a ``lax.map`` over the sequences around a
``lax.scan`` over the chunks), would count the loop's time twice and the inner
loop's three times. The union of the intervals counts every nanosecond once;
for a scope without loops it is the sum.
"""

from __future__ import annotations

from . import scopes, xtrace


def ms_per_step(context, names):
    """Device milliseconds per step, first chip, in which an op whose name
    stack (or own name) holds one of ``names`` runs; None where the run was
    not traced or no op carries one."""
    reduced = context["reduced"]
    if reduced is None:
        return None
    op_names = scopes.step_op_names(context)
    chip = reduced.chips[0]
    events = [ev for ev in chip["ops"]
              if any(name in ev.op or name in op_names.get(ev.op, "") for name in names)]
    if not events:
        return None
    return 1e3 * xtrace.union_seconds(xtrace.clip(events, *chip["window"])) / reduced.steps
