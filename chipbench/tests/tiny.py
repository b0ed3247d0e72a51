"""A tiny benchmark for the CPU tests: the real harness, readers and
reference, on a configuration cut to sizes a test run can hold. The cut is
a file of the configuration's own, ``tests/tiny/<config>.json``:

- ``config``, ``traffic``: the names of the configuration and of a traffic
  mix it is tested under (``configs/<config>.json``, ``traffic/<traffic>.json``);
- ``model``: the model keys it changes;
- ``argv``: ``{flag: value}``, written over the entry point's ``argv``;
- ``env``: environment the program's builder reads (optional);
- ``traffic_cut``: the traffic's lengths at the tiny size (optional);
- ``reference_case``: what ``test_reference.py`` compares (optional).

A new configuration's tests come as such a file; nothing here names one.
"""

from __future__ import annotations

import argparse
import glob
import json
import os

from chipbench.harness import spec

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1 << 34}


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def configs(bench_dir=spec.BENCH_DIR) -> list:
    """The names of the configurations that have a tiny file."""
    paths = glob.glob(os.path.join(bench_dir, "tests", "tiny", "*.json"))
    return sorted(os.path.splitext(os.path.basename(p))[0] for p in paths)


def tiny_file(config_name: str, bench_dir=spec.BENCH_DIR) -> dict:
    return read_json(bench_dir, "tests", "tiny", config_name + ".json")


def tiny_cell(tmp_path, config_name: str, limits=None,
              bench_dir=spec.BENCH_DIR) -> spec.Cell:
    """A cell of the named configuration, cut as its tiny file says, under
    the traffic mix that file names; its files are written under
    ``tmp_path``. ``bench_dir`` is where configurations, traffic mixes and
    tiny files are looked for (a test may bring a directory of its own)."""
    tiny = tiny_file(config_name, bench_dir)
    config = read_json(bench_dir, "configs", config_name + ".json")
    config.get("model", config).update(tiny.get("model", {}))
    argv = config["entry_point"].get("argv", [])
    for flag, value in tiny.get("argv", {}).items():
        argv[argv.index(flag) + 1] = value
    os.environ.update(tiny.get("env", {}))
    traffic = read_json(bench_dir, "traffic", tiny["traffic"] + ".json")
    traffic.update(trace_steps=3, warmup_steps=3, **tiny.get("traffic_cut", {}))
    name = config_name + "_tiny"
    files = {
        "config.json": config,
        os.path.join("traffic", tiny["traffic"] + ".json"): traffic,
        os.path.join("cells", name + ".json"): {"cell": name, "limits": limits or {}},
    }
    for rel, body in files.items():
        path = os.path.join(tmp_path, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(body, f)
    real = spec.benchmark()
    bench = {
        "configs": [{"name": config_name, "file": os.path.join(tmp_path, "config.json")}],
        "workloads": [{"name": name, "config": config_name, "traffic": tiny["traffic"],
                       "chips": config["layout"]["chips"]}],
        "end_to_end": [dict(m, workloads=[name]) for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=[name]) for m in real["per_layer"]],
    }
    return spec.Cell(name, bench=bench, bench_dir=str(tmp_path))


def options(name, seed=1234567891, seconds=0.5, trace=0):
    return argparse.Namespace(workload=name, seed=seed, seconds=seconds, trace=trace)
