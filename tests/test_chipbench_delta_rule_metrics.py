"""``delta_rule_kernel_ms`` and ``delta_rule_kernel_roofline``
(``chipbench/layer_metrics``, PR 38) on a hand-built trace: two steps of 100
ms holding the gated delta rule's kernels under the names XLA gives their
instructions; the least-FLOP count on the Qwen3-Next cell's own files; and
the two ``BENCHMARK.json`` entries. Written here and not under
``chipbench/tests`` (as ``tests/test_chipbench_attention_metrics.py``'s
cases are): the PR that brought the readers adds those two files to the
benchmark and nothing else."""

import json
import os
import types

import pytest

from chipbench.harness import spec, xtrace
from chipbench.harness.xtrace import Event, Line, Plane

MS = 1_000_000  # ns
CELL = "qwen3_next_80b_a3b_share16_seq8k_bs2"
NAMES = ("delta_rule_kernel_ms", "delta_rule_kernel_roofline")
CALL = ' custom-call(bf16[8]{0} %f), custom_call_target="tpu_custom_call"'


def _plane(kernels: bool):
    """A step: a fusion 0-50 ms, then (``kernels``) a layer's forward of 12
    ms, the remat's forward again (it keeps the backward's states: another
    result) and the backward of 8 ms, as the chip names them; an attention
    kernel beside them, which is not theirs."""
    ops, modules = [], []
    for k in range(3):
        t = k * 100 * MS
        modules.append(Event("jit__train_step(1)", t, 95 * MS, {}))
        ops.append(Event("%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop", t, 50 * MS, {}))
        ops.append(Event("%jvp_mpi4dl_attention_fwd_.1 = (bf16[8]{0}, f32[8]{0})" + CALL,
                         t + 90 * MS, 4 * MS, {}))
        if kernels:
            ops += [
                Event("%mpi4dl_delta_rule_fwd.3 = bf16[8]{0}" + CALL, t + 50 * MS, 12 * MS, {}),
                Event("%mpi4dl_delta_rule_fwd.3.remat = (bf16[8]{0}, bf16[8]{0}, f32[8]{0})" + CALL,
                      t + 64 * MS, 12 * MS, {}),
                Event("%mpi4dl_delta_rule_bwd.1 = (bf16[8]{0}, bf16[8]{0}, bf16[8]{0}, f32[8]{0})"
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
        os.path.join(spec.BENCH_DIR, "layer_metrics", "delta_rule_kernel_roofline.py"), "roofline")
    return module.least_flops_per_step(model, traffic)


def test_least_flops_are_the_recurrences_three_products_times_three():
    cell = spec.Cell(CELL)
    # 3 (the training step) x 3 products x 2 x 128 x 128 x 32 value heads
    # x 3 linear layers of the held 4 x 8,192 positions x 2 sequences
    by_hand = 3 * (3 * 2 * 128 * 128 * 32) * 3 * 8192 * 2
    assert _least_flops(cell.model, cell.traffic) == by_hand == 463856467968
    assert by_hand == pytest.approx(0.4638e12, rel=2e-4)


@pytest.mark.parametrize("layers, interval, linear", [(4, 4, 3), (48, 4, 36), (8, 2, 4), (3, 4, 3)])
def test_the_linear_layers_are_those_that_are_not_every_nth(layers, interval, linear):
    cell = spec.Cell(CELL)
    model = dict(cell.model, num_hidden_layers=layers, full_attention_interval=interval)
    assert _least_flops(model, cell.traffic) == linear * _least_flops(cell.model, cell.traffic) / 3


def test_the_kernels_are_found_by_their_names_common_start():
    context = _context(kernels=True)
    assert spec.metric_reader("layer_metrics", NAMES[0])(context) == pytest.approx(32.0)
    share = spec.metric_reader("layer_metrics", NAMES[1])(context)
    assert share == pytest.approx(100 * (463856467968 / 197e12) / 32e-3, rel=1e-9)
    assert 0 < share < 100


@pytest.mark.parametrize("context", [{"reduced": None}, "no kernel"])
def test_without_a_trace_or_without_the_kernels_nothing_is_read(context):
    """An untraced run, and the parent of the PR that brought the kernels
    (its trace holds the attention kernels' calls and none of the rule's)."""
    context = _context(kernels=False) if context == "no kernel" else context
    for name in NAMES:
        assert spec.metric_reader("layer_metrics", name)(context) is None


def test_the_two_entries_list_the_qwen3_next_cell_alone():
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
