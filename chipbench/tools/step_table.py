#!/usr/bin/env python3
"""Where a traced step's device time goes, by model cell and operator class:
the table ``harness/step_classes.py`` splits the trace into, in ms a step,
with row and column sums, the collectives' part of each class, and what is
left ``unscoped`` by op family.

    python chipbench/tools/step_table.py --workload <cell> --seed <n> \
        [--seconds 30] [--dump chiprun_out/step_<cell>]
    python chipbench/tools/step_table.py --from chiprun_out/step_<cell>

The first form is one traced run of the cell as ``run.py --trace 1`` makes it
(needs the chips the cell asks for): the result line is printed as ``run.py``
prints it, then the table. ``--dump`` keeps what the table was made from, the
compiled step's text and the first chip's op events of the traced window, so
that the second form prints it again anywhere, without a chip.
"""

from __future__ import annotations

import argparse
import collections
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # see run.py


def table_lines(text, events, window, steps):
    """The printed table from the compiled step's ``text`` and the first
    chip's op ``events`` (``xtrace.Event``) inside ``window``."""
    from chipbench.harness import step_classes, xtrace

    ms = step_classes.split_events(text, events, window, steps, families=True)
    busy = 1e3 * xtrace.union_seconds(xtrace.clip(events, *window)) / steps
    columns = [c for c in step_classes.CLASSES
               if any(k[0] == c for k in ms)]
    rows = sorted({k[1] for k in ms if k[1] is not None}) + [None]
    cell = collections.defaultdict(float)
    for (cls, at, _, _), v in ms.items():
        cell[at, cls] += v
    width = max(9, *(len(c) + 1 for c in columns))
    lines = ["ms a step, first chip; rows: model cells (mpi4dl_cell<NN>), "
             "'-' under no cell",
             "cell  " + "".join(f"{c:>{width}}" for c in columns + ["sum"])]
    for at in rows:
        values = [cell[at, c] for c in columns]
        lines.append(f"{at or '-':<6}" + "".join(
            f"{v:>{width}.3f}" for v in values + [sum(values)]))
    totals = [sum(cell[at, c] for at in rows) for c in columns]
    lines.append(f"{'sum':<6}" + "".join(
        f"{v:>{width}.3f}" for v in totals + [sum(totals)]))
    collectives = [sum(v for (cls, _, coll, _), v in ms.items() if coll and cls == c)
                   for c in columns]
    lines.append(f"{'coll.':<6}" + "".join(
        f"{v:>{width}.3f}" for v in collectives + [sum(collectives)]))
    lines.append(f"busy (union of the op intervals) {busy:.3f}; classes + unscoped "
                 f"{sum(totals):.3f}; collectives by their own durations "
                 f"{1e3 * xtrace.collective_seconds(events, *window) / steps:.3f}")
    families = sorted(((v, fam) for (cls, _, _, fam), v in ms.items()
                       if cls == step_classes.UNSCOPED), reverse=True)
    lines.append("unscoped by op family: " + (", ".join(
        f"{fam} {v:.3f}" for v, fam in families[:12]) or "nothing"))
    return lines


def _up_to_opcode(name):
    """An event's name cut after its opcode's parenthesis: all that
    ``xtrace.Event`` reads of it."""
    from chipbench.harness import xtrace

    _, found, text = name.partition(" = ")
    opcode = xtrace._OPCODE.search(" " + text) if found else None
    return name[:len(name) - len(text) + opcode.end() - 1] if opcode else name[:200]


def dump(path, context):
    """Keep the text and the first chip's events of the traced window."""
    from chipbench.harness import step_classes

    os.makedirs(path, exist_ok=True)
    reduced = context["reduced"]
    chip = reduced.chips[0]
    with gzip.open(os.path.join(path, "step_text.txt.gz"), "wt") as f:
        f.write(step_classes.step_text(context))
    with gzip.open(os.path.join(path, "events.json.gz"), "wt") as f:
        json.dump({"steps": reduced.steps, "window": chip["window"],
                   "events": [[_up_to_opcode(ev.name), ev.start_ns, ev.duration_ns]
                              for ev in chip["ops"]]}, f)


def load(path):
    from chipbench.harness import xtrace

    with gzip.open(os.path.join(path, "step_text.txt.gz"), "rt") as f:
        text = f.read()
    with gzip.open(os.path.join(path, "events.json.gz"), "rt") as f:
        kept = json.load(f)
    events = [xtrace.Event(n, s, d, {}) for n, s, d in kept["events"]]
    return text, events, tuple(kept["window"]), kept["steps"]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--dump", default=None, help="directory to keep text and events in")
    ap.add_argument("--from", dest="kept", default=None, help="a directory --dump wrote")
    opts = ap.parse_args(argv)
    if opts.kept:
        print("\n".join(table_lines(*load(opts.kept))))
        return
    if opts.workload is None or opts.seed is None:
        ap.error("--workload and --seed, or --from")

    from chipbench import run
    from chipbench.harness import spec, step_classes

    # the readers' context is made inside run.run: the first of them to ask
    # for the split hands it over
    kept, split = {}, step_classes._split

    def keep(context):
        kept["context"] = context
        return split(context)

    step_classes._split = keep
    opts.trace = 1
    result = run.run(opts, run.find_chips(spec.Cell(opts.workload).chips))
    print(json.dumps(result), flush=True)
    context = kept.get("context")
    if context is None or not step_classes.step_text(context):
        raise SystemExit("the cell reports no metric of harness/step_classes.py, "
                         "or the program has no compiled_step")
    if opts.dump:
        dump(os.path.join(ROOT, opts.dump), context)
    reduced = context["reduced"]
    chip = reduced.chips[0]
    print("\n".join(table_lines(
        step_classes.step_text(context), chip["ops"], chip["window"], reduced.steps)))


if __name__ == "__main__":
    main()
