"""A tiny benchmark for the CPU tests: the real harness, readers and
reference, on the configurations under ``chipbench/configs/`` cut to sizes a
test run can hold (the cut changes the model's sizes only, never a file)."""

from __future__ import annotations

import argparse
import json
import os

from chipbench.harness import spec

TINY_MODELS = {
    "amoebanetd_1024": {"num_layers": 3, "num_filters": 32, "image_size": 64},
    "amoebanetd_1024_sp2x2": {"num_layers": 3, "num_filters": 32, "image_size": 256},
    "resnet110_1024": {"depth": 11, "image_size": 32},
}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1 << 34}
TRAFFIC = "synthetic_bs2"


def tiny_cell(tmp_path, config_name: str, limits=None) -> spec.Cell:
    """A cell of the named configuration, cut to a tiny model, under the
    benchmark's own traffic mix; its files are written under ``tmp_path``."""
    with open(os.path.join(spec.BENCH_DIR, "configs", config_name + ".json")) as f:
        config = json.load(f)
    tiny = TINY_MODELS[config_name]
    config["model"].update(tiny)
    argv = config["entry_point"]["argv"]
    for flag, key in (("--image-size", "image_size"), ("--num-layers", "num_layers"),
                      ("--num-filters", "num_filters")):
        if key in tiny and flag in argv:
            argv[argv.index(flag) + 1] = str(tiny[key])
    if "depth" in tiny:  # the program's resnet builder reads its depth here
        os.environ["MPI4DL_TPU_RESNET_N"] = str((tiny["depth"] - 2) // 9)
    with open(os.path.join(spec.BENCH_DIR, "traffic", TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    traffic.update(trace_steps=3, warmup_steps=3)
    name = config_name + "_tiny"
    files = {
        "config.json": config,
        os.path.join("traffic", TRAFFIC + ".json"): traffic,
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
        "workloads": [{"name": name, "config": config_name, "traffic": TRAFFIC,
                       "chips": config["layout"]["chips"]}],
        "end_to_end": [dict(m, workloads=[name]) for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=[name]) for m in real["per_layer"]],
    }
    return spec.Cell(name, bench=bench, bench_dir=str(tmp_path))


def options(name, seed=1234567891, seconds=0.5, trace=0):
    return argparse.Namespace(workload=name, seed=seed, seconds=seconds, trace=trace)
