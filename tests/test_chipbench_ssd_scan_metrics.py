"""``ssd_scan_kernel_ms`` and ``ssd_scan_kernel_roofline``
(``chipbench/layer_metrics``, PR 42) on a hand-built trace: two steps of 100
ms holding Mamba-2's scan's kernels under the names XLA gives their
instructions; the least-FLOP count on the Nemotron-H cell's own files and
against the reference's own count; and the two ``BENCHMARK.json`` entries.
Written here and not under ``chipbench/tests`` (as
``tests/test_chipbench_delta_rule_metrics.py``'s cases are): the PR that
brought the readers adds those two files to the benchmark and nothing else."""

import json
import os
import types

import pytest

from chipbench.harness import spec, xtrace
from chipbench.harness.xtrace import Event, Line, Plane
from chipbench.reference import nemotron_h as ref

MS = 1_000_000  # ns
CELL = "nemotron_twotower_30b_a3b_share16_seq8k_bs2"
NAMES = ("ssd_scan_kernel_ms", "ssd_scan_kernel_roofline")
CALL = ' custom-call(bf16[8]{0} %f), custom_call_target="tpu_custom_call"'
LEAST = 618475290624  # 0.6185 TFLOP a step


def _plane(kernels: bool):
    """A step: a fusion 0-50 ms, then (``kernels``) a layer's forward of 3
    ms, the remat's forward again (it keeps the backward's states: another
    result) of 4 and the backward of 8 ms, as the chip names them; an
    attention kernel beside them, which is not theirs."""
    ops, modules = [], []
    for k in range(3):
        t = k * 100 * MS
        modules.append(Event("jit__train_step(1)", t, 95 * MS, {}))
        ops.append(Event("%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop", t, 50 * MS, {}))
        ops.append(Event("%jvp_mpi4dl_attention_fwd_.1 = (bf16[8]{0}, f32[8]{0})" + CALL,
                         t + 90 * MS, 4 * MS, {}))
        if kernels:
            ops += [
                Event("%mpi4dl_ssd_scan_fwd.3 = bf16[8]{0}" + CALL, t + 50 * MS, 3 * MS, {}),
                Event("%mpi4dl_ssd_scan_fwd.3.remat = (bf16[8]{0}, f32[8]{0})" + CALL,
                      t + 64 * MS, 4 * MS, {}),
                Event("%mpi4dl_ssd_scan_bwd.1 = (bf16[8]{0}, bf16[8]{0}, bf16[8]{0}, f32[8]{0})"
                      + CALL, t + 78 * MS, 8 * MS, {}),
            ]
    return Plane("/device:TPU:0", [Line("XLA Modules", modules), Line("XLA Ops", ops)])


def _context(kernels: bool):
    cell = spec.Cell(CELL)
    return {"reduced": xtrace.reduce([_plane(kernels)], "train_step", 2),
            "cell": types.SimpleNamespace(model=cell.model, traffic=cell.traffic),
            "peaks": {"bf16_flops_per_s": 197e12}}


def _least_flops(model, traffic):
    module = spec.load_module(
        os.path.join(spec.BENCH_DIR, "layer_metrics", "ssd_scan_kernel_roofline.py"), "roofline")
    return module.least_flops_per_step(model, traffic)


def test_least_flops_are_the_recurrences_three_products_times_three():
    cell = spec.Cell(CELL)
    # 3 (the training step) x 3 products x 2 x 64 heads x 64 x 128
    # x 4 Mamba-2 layers x 8,192 positions x 2 sequences
    by_hand = 3 * (3 * 2 * 64 * 64 * 128) * 4 * 8192 * 2
    assert _least_flops(cell.model, cell.traffic) == by_hand == LEAST
    assert by_hand == pytest.approx(0.6185e12, rel=2e-4)


def test_least_flops_are_the_references_recurrence_term_times_three():
    """``forward_flops_per_token`` grows with the state's size by the
    recurrence's term and by ``W_in``'s columns for ``B`` and ``C`` (2 x
    hidden x 2 x groups a unit of state and Mamba-2 layer): what is left of
    its growth is the recurrence, a token and forward pass."""
    cell = spec.Cell(CELL)
    model, length = cell.model, int(cell.traffic["sequence_length"])
    grown = ref.forward_flops_per_token(model, length) - ref.forward_flops_per_token(
        dict(model, ssm_state_size=0), length)
    projections = 4 * 2.0 * model["hidden_size"] * 2 * model["n_groups"] * model["ssm_state_size"]
    positions = length * int(cell.traffic["batch_size"])
    assert 3 * (grown - projections) * positions == _least_flops(model, cell.traffic) == LEAST


@pytest.mark.parametrize("pattern, layers", [
    ("MEMEM*EME", 4), ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME", 23),
    ("M", 1), ("E*E", 0)])
def test_the_scans_layers_are_the_patterns_ms(pattern, layers):
    cell = spec.Cell(CELL)
    model = dict(cell.model, hybrid_override_pattern=pattern)
    assert _least_flops(model, cell.traffic) == layers * LEAST / 4


def test_the_kernels_are_found_by_their_names_common_start():
    context = _context(kernels=True)
    assert spec.metric_reader("layer_metrics", NAMES[0])(context) == pytest.approx(15.0)
    share = spec.metric_reader("layer_metrics", NAMES[1])(context)
    assert share == pytest.approx(100 * (LEAST / 197e12) / 15e-3, rel=1e-9)
    assert 0 < share < 100


@pytest.mark.parametrize("context", [{"reduced": None}, "no kernel"])
def test_without_a_trace_or_without_the_kernels_nothing_is_read(context):
    """An untraced run, and the parent of the PR that brought the kernels
    (its trace holds the attention kernels' calls and none of the scan's)."""
    context = _context(kernels=False) if context == "no kernel" else context
    for name in NAMES:
        assert spec.metric_reader("layer_metrics", name)(context) is None


def test_the_two_entries_list_the_nemotron_h_cell_alone():
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR), "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    entries = {m["name"]: m for m in benchmark["per_layer"]}
    names = [m["name"] for m in benchmark["per_layer"]]
    at = names.index(NAMES[0])  # added side by side, in this order; later PRs append after them
    assert names[at:at + 2] == list(NAMES)
    for name, unit, better in zip(NAMES, ("ms", "%"), ("lower", "higher")):
        assert entries[name] == {
            "name": name, "unit": unit, "better": better, "source": "device_trace",
            "layer": "Pallas kernels", "moves": "images_per_s", "workloads": [CELL]}
