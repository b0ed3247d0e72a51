"""``causal_conv_kernel_ms`` and ``causal_conv_kernel_roofline``
(``chipbench/layer_metrics``, PR 47) on a hand-built trace: two steps of 100
ms holding the causal convolution's kernels under the names XLA gives their
instructions; the least-byte count on the two cells' own files; and the two
``BENCHMARK.json`` entries. Written here and not under ``chipbench/tests`` (as
``tests/test_chipbench_ssd_scan_metrics.py``'s cases are): the PR that brought
the readers adds those two files to the benchmark and nothing else."""

import json
import os
import types

import pytest

from chipbench.harness import spec, xtrace
from chipbench.harness.xtrace import Event, Line, Plane

MS = 1_000_000  # ns
QWEN = "qwen3_next_80b_a3b_share16_seq8k_bs2"
NEMOTRON = "nemotron_twotower_30b_a3b_share16_seq8k_bs2"
NAMES = ("causal_conv_kernel_ms", "causal_conv_kernel_roofline")
CALL = ' custom-call(bf16[8]{0} %f), custom_call_target="tpu_custom_call"'
# five passes of two bytes over 2 x 8,192 positions: three layers of 8,192
# channels, four of 6,144
LEAST = {QWEN: 5 * 2 * 2 * 8192 * 8192 * 3, NEMOTRON: 5 * 2 * 2 * 8192 * 6144 * 4}


def _plane(kernels: bool):
    """A step: a fusion 0-50 ms, then (``kernels``) a layer's forward of 1
    ms, another's of 2 (under "cell" remat the replay runs none) and a
    backward of 4 ms, as the chip names them; a scan kernel beside them,
    which is not theirs."""
    ops, modules = [], []
    for k in range(3):
        t = k * 100 * MS
        modules.append(Event("jit__train_step(1)", t, 95 * MS, {}))
        ops.append(Event("%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop", t, 50 * MS, {}))
        ops.append(Event("%mpi4dl_ssd_scan_fwd.3 = (bf16[8]{0}, f32[8]{0})" + CALL,
                         t + 90 * MS, 4 * MS, {}))
        if kernels:
            ops += [
                Event("%mpi4dl_causal_conv_fwd.3 = bf16[8]{0}" + CALL, t + 50 * MS, 1 * MS, {}),
                Event("%jvp_mpi4dl_causal_conv_fwd_.1 = bf16[8]{0}" + CALL, t + 64 * MS, 2 * MS, {}),
                Event("%mpi4dl_causal_conv_bwd.1 = (bf16[8]{0}, f32[8]{0})" + CALL,
                      t + 78 * MS, 4 * MS, {}),
            ]
    return Plane("/device:TPU:0", [Line("XLA Modules", modules), Line("XLA Ops", ops)])


def _context(kernels: bool, cell_name=NEMOTRON):
    cell = spec.Cell(cell_name)
    return {"reduced": xtrace.reduce([_plane(kernels)], "train_step", 2),
            "cell": types.SimpleNamespace(model=cell.model, traffic=cell.traffic),
            "peaks": {"hbm_bytes_per_s": 819e9}}


def _roofline():
    return spec.load_module(os.path.join(
        spec.BENCH_DIR, "layer_metrics", "causal_conv_kernel_roofline.py"), "roofline")


@pytest.mark.parametrize("cell_name, layers, channels", [
    (QWEN, 3, 2 * 16 * 128 + 32 * 128), (NEMOTRON, 4, 64 * 64 + 2 * 8 * 128)])
def test_least_bytes_are_five_passes_of_the_configurations_channels(cell_name, layers, channels):
    cell = spec.Cell(cell_name)
    module = _roofline()
    assert module.conv_layers_and_channels(cell.model) == (layers, channels)
    by_hand = 5 * 2 * (2 * 8192) * channels * layers
    assert module.least_bytes_per_step(cell.model, cell.traffic) == by_hand == LEAST[cell_name]
    # 4.9 ms a step at the chip's 819 GB/s in either cell
    assert by_hand / 819e9 == pytest.approx(4.92e-3, rel=0.01)


@pytest.mark.parametrize("change, layers", [
    (dict(hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"), 23),
    (dict(hybrid_override_pattern="E*E"), 0), (dict(hybrid_override_pattern="M"), 1)])
def test_the_mamba_layers_are_the_patterns_ms(change, layers):
    cell = spec.Cell(NEMOTRON)
    least = _roofline().least_bytes_per_step(dict(cell.model, **change), cell.traffic)
    assert least == layers * LEAST[NEMOTRON] / 4


@pytest.mark.parametrize("depth, interval, layers", [(48, 4, 36), (4, 4, 3), (8, 2, 4)])
def test_the_delta_net_layers_are_those_that_are_not_attentions(depth, interval, layers):
    cell = spec.Cell(QWEN)
    model = dict(cell.model, num_hidden_layers=depth, full_attention_interval=interval)
    assert _roofline().least_bytes_per_step(model, cell.traffic) == layers * LEAST[QWEN] / 3


@pytest.mark.parametrize("cell_name", [QWEN, NEMOTRON])
def test_the_kernels_are_found_by_their_names_common_start(cell_name):
    context = _context(kernels=True, cell_name=cell_name)
    assert spec.metric_reader("layer_metrics", NAMES[0])(context) == pytest.approx(7.0)
    share = spec.metric_reader("layer_metrics", NAMES[1])(context)
    assert share == pytest.approx(100 * (LEAST[cell_name] / 819e9) / 7e-3, rel=1e-9)
    assert 0 < share < 100


@pytest.mark.parametrize("context", [{"reduced": None}, "no kernel"])
def test_without_a_trace_or_without_the_kernels_nothing_is_read(context):
    """An untraced run, and the parent of the PR that brought the kernels
    (its trace holds the scan's kernels' calls and none of the convolution's)."""
    context = _context(kernels=False) if context == "no kernel" else context
    for name in NAMES:
        assert spec.metric_reader("layer_metrics", name)(context) is None


def test_the_two_entries_list_the_two_cells_alone():
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR), "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    entries = {m["name"]: m for m in benchmark["per_layer"]}
    names = [m["name"] for m in benchmark["per_layer"]]
    at = names.index(NAMES[0])  # added side by side, in this order; later PRs append after them
    assert names[at:at + 2] == list(NAMES)
    for name, unit, better in zip(NAMES, ("ms", "%"), ("lower", "higher")):
        assert entries[name] == {
            "name": name, "unit": unit, "better": better, "source": "device_trace",
            "layer": "Pallas kernels", "moves": "images_per_s", "workloads": [QWEN, NEMOTRON]}
