"""Walk ``BENCHMARK.json``: every name leads to a file, every arrow to a
metric the cell reports, and the words hold only what the contract allows."""

import json
import os
import re

import pytest

from chipbench.harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LAYERS = {"entry points", "trainer", "parallelism", "ops", "Pallas kernels",
          "runtime glue", "device"}


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert bench["command"][1].startswith("chipbench/")
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_lines(bench):
    entries = (bench["configs"] + bench["workloads"] + bench["end_to_end"]
               + bench["per_layer"])
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for entry in entries:
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            if key in entry and (key != "source" or "file" in entry):
                text = entry[key]
                assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])


def _cut_is_stated(config):
    """A configuration cut to one chip's share says so in its file: under
    ``cut``, the deployment it stands for (how many chips share a layer,
    and how) and, for each key of ``reduced``, the published value beside
    the held one, which is the one the file runs."""
    if not config["reduced"]:
        assert "cut" not in config
        return
    cut = config["cut"]
    assert set(cut) == set(config["reduced"]) | {"deployment"}
    assert isinstance(cut["deployment"], str) and len(cut["deployment"]) >= 20
    for key in config["reduced"]:
        assert set(cut[key]) >= {"published", "held"}, key
        assert cut[key]["held"] != cut[key]["published"], key
        places = [p for p in (config, config.get("model", {})) if key in p]
        assert places, key
        assert all(p[key] == cut[key]["held"] for p in places), key


def _cells_lead_to_their_files(bench, bench_dir=spec.BENCH_DIR):
    configs = {c["name"]: c for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in bench["workloads"]:
        cell = spec.Cell(w["name"], bench=bench, bench_dir=bench_dir)
        assert cell.config["name"] == w["config"]
        assert cell.config["reduced"] == configs[w["config"]]["reduced"]
        _cut_is_stated(cell.config)
        assert cell.config["source"] == configs[w["config"]]["source"]
        assert cell.traffic["name"] == w["traffic"]
        assert cell.limits["cell"] == w["name"] and cell.limits["limits"]
        assert w["chips"] == cell.config["layout"]["chips"]
        assert os.path.exists(os.path.join(
            spec.ROOT, cell.config["reference"]["module"].replace(".", "/") + ".py"))


def test_every_cell_leads_to_its_files(bench):
    _cells_lead_to_their_files(bench)
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1


@pytest.mark.parametrize("stated", [True, False])
def test_a_cut_configuration_is_admitted_and_held_to_its_cut(bench, tmp_path, stated):
    """Files under ``tmp_path``: an accepted configuration cut in depth, as
    a configuration sized to one chip's share of a deployment is. With the
    published value beside the held one it passes the walk; without, or
    with a ``reduced`` that ``BENCHMARK.json`` does not repeat, it fails."""
    entry = dict(bench["configs"][0])
    workload = next(w for w in bench["workloads"] if w["config"] == entry["name"])
    real = spec.Cell(workload["name"])
    config = json.loads(json.dumps(real.config))
    config["reduced"] = ["num_layers"]
    config["model"]["num_layers"] = 6
    config["cut"] = {
        "deployment": "3 chips share the stack as pipeline stages of 6 layers: the first",
        "num_layers": {"published": 18, "held": 6},
    }
    if not stated:
        del config["cut"]["num_layers"]["published"]
    for rel, body in (("config.json", config),
                      (f"traffic/{workload['traffic']}.json", real.traffic),
                      (f"cells/{workload['name']}.json", real.limits)):
        path = tmp_path / rel
        path.parent.mkdir(exist_ok=True)
        path.write_text(json.dumps(body))
    entry.update(file=str(tmp_path / "config.json"), reduced=["num_layers"])
    cut = dict(bench, configs=[entry], workloads=[workload])
    if stated:
        _cells_lead_to_their_files(cut, str(tmp_path))
        entry["reduced"] = []
    with pytest.raises(AssertionError):
        _cells_lead_to_their_files(cut, str(tmp_path))


def test_a_kind_set_apart_has_its_limit_and_exists(bench):
    import importlib

    for w in bench["workloads"]:
        cell = spec.Cell(w["name"])
        reference = importlib.import_module(cell.config["reference"]["module"])
        kinds = set(reference.kinds(cell.model))
        for error, apart in cell.limits.get("apart", {}).items():
            assert error in cell.limits["limits"]
            for kind in apart:
                assert kind in kinds
                assert f"{error}.{kind}" in cell.limits["limits"]


def test_end_to_end_metrics(bench):
    names = [m["name"] for m in bench["end_to_end"]]
    assert "setup_s" in names
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert callable(spec.metric_reader("end_to_end", m["name"]))


def test_per_layer_metrics_have_a_reader_a_layer_and_an_arrow(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["layer"] in LAYERS
        assert callable(spec.metric_reader("layer_metrics", m["name"]))
        assert set(m.get("workloads", cells)) <= cells
        for name in m.get("workloads", cells):
            assert m["moves"] in {e["name"] for e in spec.Cell(name).end_to_end}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for name in cells:
        assert spec.Cell(name).per_layer


def test_run_py_names_no_cell_configuration_or_metric(bench):
    with open(os.path.join(spec.BENCH_DIR, "run.py")) as f:
        text = f.read()
    words = ([c["name"] for c in bench["configs"]] + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    for word in words:
        assert f'"{word}"' not in text, word
    json.dumps(bench)


FAMILY_WORDS = ("image_size", "image_channels", "num_classes", "SyntheticImages")


def test_the_harness_names_no_familys_input_key_outside_the_defaults():
    """A family's input keys come from its own files; ``harness/defaults.py``
    alone holds those of the family that brings none."""
    import glob

    paths = [os.path.join(spec.BENCH_DIR, "run.py")]
    for group in ("harness", "layer_metrics", "end_to_end", "tools"):
        paths += glob.glob(os.path.join(spec.BENCH_DIR, group, "*.py"))
    assert len(paths) > 20
    for path in paths:
        with open(path) as f:
            text = f.read()
        if path.endswith(os.path.join("harness", "defaults.py")):
            assert all(word in text for word in FAMILY_WORDS)
            continue
        for word in FAMILY_WORDS:
            assert word not in text, (os.path.relpath(path, spec.BENCH_DIR), word)

