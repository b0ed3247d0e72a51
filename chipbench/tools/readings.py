#!/usr/bin/env python3
"""Readings a limit is set from: the program's compared numbers, and the
control's, over many seeds in one process (set-up is long; the benchmark's
own runs never run the control).

    python chipbench/tools/readings.py --workload <cell> --seeds 11,12,13 \
        [--control fp8] [--out chiprun_out/readings_<cell>.json]

Training's readings need no measured window: each seed makes weights and
state, takes the compared steps through the window's loop, frees the state
and lets the float32 reference follow. With ``--control`` the reference in
that arithmetic stands in the program's place as well. Needs the chips the
cell asks for, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"  # see run.py


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated whole numbers")
    ap.add_argument("--control", default=None, choices=("bf16", "fp8"))
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="take the control on the first N seeds only")
    ap.add_argument("--control-only", action="store_true",
                    help="leave the program out: the control against the "
                         "float32 reference (needs one chip, whatever the cell)")
    ap.add_argument("--out", default=None)
    opts = ap.parse_args(argv)

    from chipbench import run
    from chipbench.harness import spec
    from chipbench.harness.session import Session, say

    cell = spec.Cell(opts.workload)
    run.find_chips(1 if opts.control_only else cell.chips)
    session = Session(cell)
    seeds = [int(s) for s in opts.seeds.split(",")]
    rows = []
    for n, seed in enumerate(seeds):
        if opts.control_only:
            first = session.batches_only(seed)
        else:
            first = session.first_steps(seed, session.check_steps)
            first.loop.state = None
        control = opts.control
        if opts.control_seeds is not None and n >= opts.control_seeds:
            control = None
        got = session.compare(first, control=control)
        program, stand_in = got if control else (got, None)
        rows.append({"seed": seed, "program": program, "control": stand_in})
        say(phase="reading", **rows[-1])
    names = list(rows[0]["program"] or rows[0]["control"])
    summary = {
        name: {
            "program_max": max(
                (r["program"][name] for r in rows if r["program"]), default=None
            ),
            "control_min": min(
                (r["control"][name] for r in rows if r["control"]), default=None
            ),
        }
        for name in names
    }
    say(phase="summary", workload=cell.name, seeds=seeds, control=opts.control,
        numbers=summary)
    if opts.out:
        os.makedirs(os.path.dirname(os.path.join(ROOT, opts.out)), exist_ok=True)
        with open(os.path.join(ROOT, opts.out), "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)


if __name__ == "__main__":
    main()
