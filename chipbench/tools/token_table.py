#!/usr/bin/env python3
"""Where a token cell's traced step goes, by model cell, mixer and part: the
tables ``harness/token_parts.py`` splits the trace into, in ms a step: model
cell x part, mixer x part, the compiler's layout turns of each part, and under
each part what XLA named its ops, with counts (and the layout turns' shapes,
so that "32 copies of f32[2,8192,4096]" is a line and not an inference).

    python chipbench/tools/token_table.py --workload <cell> --seed <n> \
        [--seconds 30] [--dump chiprun_out/parts_<cell>]
    python chipbench/tools/token_table.py --from chiprun_out/parts_<cell>

The first form is one traced run of the cell as ``run.py --trace 1`` makes it
(needs the chip): the result line is printed as ``run.py`` prints it, then the
tables. ``--dump`` keeps what they were made from (``tools/step_table.py``'s
two files: the compiled step's text and the first chip's op events of the
traced window), so that the second form, and ``step_table.py --from``, print
theirs again anywhere, without a chip. A program without the part scopes (a
tree before PR 45) still dumps; its table says so.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # see run.py

_SHAPE = re.compile(r" = \(?([a-z0-9]+\[[0-9,]*\])")


def _grid(title, rows, columns, value, label=str):
    """A table of ``value(row, column)`` with row and column sums; columns
    that hold nothing are left out."""
    columns = [c for c in columns if any(value(r, c) for r in rows)] + ["sum"]
    widths = [max(9, len(c) + 1) for c in columns]
    first = max(6, *(len(label(r)) + 1 for r in rows))

    def line(name, values):
        return f"{name:<{first}}" + "".join(
            f"{v:>{w}.3f}" for v, w in zip(values + [sum(values)], widths))

    lines = [title, f"{'':<{first}}" + "".join(f"{c:>{w}}" for c, w in zip(columns, widths))]
    lines += [line(label(r), [value(r, c) for c in columns[:-1]]) for r in rows]
    lines.append(line("sum", [sum(value(r, c) for r in rows) for c in columns[:-1]]))
    return lines


def table_lines(text, events, window, steps, top=8):
    """The printed tables from the compiled step's ``text`` and the first
    chip's op ``events`` (``xtrace.Event``) inside ``window``."""
    from chipbench.harness import token_parts, xtrace

    busy = 1e3 * xtrace.union_seconds(xtrace.clip(events, *window)) / steps
    if not token_parts.has_parts(text):
        return [f"busy (union of the op intervals) {busy:.3f} ms a step; the compiled "
                "step names no mpi4dl_part_* scope: nothing to split"]
    def named(ev):  # what XLA called the op, and the shape it writes
        shape = _SHAPE.search(ev.name)
        return ev.family, shape.group(1) if shape else ""

    table = token_parts.classify(text)
    ms = token_parts.split_events(table, events, window, steps, also=named)
    count = collections.Counter()  # events a step, by what the times are keyed by
    for ev in events:
        if ev.op in table and window[0] <= ev.start_ns < window[1]:
            count[table[ev.op], named(ev)] += 1 / steps
    by = collections.defaultdict(float)
    for (found, _), v in ms.items():
        by["cell", found.cell, found.part] += v
        by["mixer", found.mixer, found.part] += v
        if found.layout:
            by["layout", found.cell is not None, found.part] += v
    parts = [p for p in token_parts.PARTS if any(k[2] == p for k in by)]
    cells = sorted({k[1] for k in by if k[0] == "cell" and k[1] is not None}) + [None]
    mixers = [m for m in token_parts.MIXER_SCOPES if any(k[:2] == ("mixer", m) for k in by)]
    lines = _grid(
        "ms a step, first chip; rows: model cells (mpi4dl_cell<NN>), '-' under no cell",
        cells, parts, lambda r, c: by["cell", r, c], lambda r: r or "-")
    lines += [""] + _grid(
        "rows: mixers (the innermost of the modules' scopes), '-' under none",
        mixers + [None], parts, lambda r, c: by["mixer", r, c], lambda r: r or "-")
    lines += [""] + _grid(
        "the compiler's layout turns (copies always; transposes and bitcast-converts "
        "with no part of their own), by the part they inherit; inside the tables above",
        [True, False], parts, lambda r, c: by["layout", r, c],
        lambda r: "in a cell" if r else "outside")
    total = sum(v for (found, _), v in ms.items())
    lines += ["", f"busy (union of the op intervals) {busy:.3f}; parts + unscoped {total:.3f}",
              "", "by part: what XLA named its ops (ms a step, events a step); "
              "then its layout turns by the shape they write"]
    for part in parts:
        families, turns = collections.defaultdict(lambda: [0.0, 0.0]), {}
        for (found, (family, shape)), v in ms.items():
            if found.part != part:
                continue
            n = count[found, (family, shape)]
            families[family][0] += v
            families[family][1] += n
            if found.layout:
                held = turns.setdefault((family, shape), [0.0, 0.0])
                held[0] += v
                held[1] += n
        lines.append(f"{part}: " + ", ".join(
            f"{family} {v:.3f} (x{n:.0f})" for family, (v, n) in sorted(
                families.items(), key=lambda kv: -kv[1][0])[:top]))
        if turns:
            lines.append("    layout: " + ", ".join(
                f"{family} {shape} {v:.3f} (x{n:.0f})" for (family, shape), (v, n) in sorted(
                    turns.items(), key=lambda kv: -kv[1][0])[:top]))
    return lines


def dump(path, context):
    """Keep the traced step's text and the first chip's events of the window:
    ``step_table.py``'s files and its writer, once the text is the step the
    cell's own labels compile (where that module looks for it)."""
    from chipbench.harness import step_classes, token_parts
    from chipbench.tools import step_table

    context.setdefault(step_classes._TEXT, token_parts.step_text(context))
    step_table.dump(path, context)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--dump", default=None, help="directory to keep text and events in")
    ap.add_argument("--from", dest="kept", default=None, help="a directory --dump wrote")
    opts = ap.parse_args(argv)

    from chipbench.tools import step_table

    if opts.kept:
        print("\n".join(table_lines(*step_table.load(opts.kept))))
        return
    if opts.workload is None or opts.seed is None:
        ap.error("--workload and --seed, or --from")

    from chipbench import run
    from chipbench.harness import spec, token_parts

    # the readers' context is made inside run.run: the first of them to ask
    # for the split hands it over
    kept, split = {}, token_parts._split

    def keep(context):
        kept["context"] = context
        return split(context)

    token_parts._split = keep
    opts.trace = 1
    result = run.run(opts, run.find_chips(spec.Cell(opts.workload).chips))
    print(json.dumps(result), flush=True)
    context = kept.get("context")
    if context is None or not token_parts.step_text(context):
        raise SystemExit("the cell reports no metric of harness/token_parts.py, "
                         "or the program has no compiled_step")
    if opts.dump:
        dump(os.path.join(ROOT, opts.dump), context)
    reduced = context["reduced"]
    chip = reduced.chips[0]
    print("\n".join(table_lines(
        token_parts.step_text(context), chip["ops"], chip["window"], reduced.steps)))


if __name__ == "__main__":
    main()
