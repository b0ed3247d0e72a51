"""What one run is made of, found by name from ``BENCHMARK.json``.

A workload entry names a configuration and a traffic mix; each is a data
file of its own, and so are the cell's limits and every per-layer metric's
reader. Nothing here knows a model, a cell or a metric by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "chipbench")


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def benchmark():
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One workload: its entry, configuration, traffic mix, limits, and the
    metrics it reports."""

    def __init__(self, name: str, bench=None, bench_dir=BENCH_DIR):
        bench = bench or benchmark()
        entries = {w["name"]: w for w in bench["workloads"]}
        if name not in entries:
            raise SystemExit(
                f"no workload {name!r} in BENCHMARK.json (has: {sorted(entries)})"
            )
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = _read_json(
            os.path.join(ROOT, configs[self.entry["config"]]["file"])
        )
        # the sizes the reference and the program's builder read: a group
        # of their own, or the file itself where it holds a published
        # config's keys at its top level
        self.model = self.config.get("model", self.config)
        self.traffic = _read_json(
            os.path.join(bench_dir, "traffic", self.entry["traffic"] + ".json")
        )
        self.limits = _read_json(
            os.path.join(bench_dir, "cells", name + ".json")
        )
        self.end_to_end = [
            m for m in bench["end_to_end"] if name in m.get("workloads", [name])
        ]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if name in m.get("workloads", [name]) and m["moves"] in reported
        ]


def load_module(path: str, name: str):
    """A module from a file under ``chipbench/`` (metric and reference
    files are found by path; their names need not be identifiers)."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(group: str, name: str):
    """The reader of one metric: ``chipbench/<group>/<name>.py``'s ``read``
    (``group`` is ``end_to_end`` or ``layer_metrics``)."""
    path = os.path.join(BENCH_DIR, group, name + ".py")
    return load_module(path, f"chipbench_{group}_" + re.sub(r"\W", "_", name)).read


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip; an unknown kind is an error."""
    table = _read_json(os.path.join(BENCH_DIR, "peaks.json"))["device_kinds"]
    if device_kind not in table:
        raise SystemExit(
            f"no peaks on record for device_kind {device_kind!r}; "
            "add it to chipbench/peaks.json with its source"
        )
    return table[device_kind]
