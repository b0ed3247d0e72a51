"""``gated_attn_kernel_ms`` / ``gated_attn_kernel_roofline`` (the Qwen3-Next
cell) and ``nemotron_attn_kernel_ms`` / ``nemotron_attn_kernel_roofline``
(the Nemotron-H cell), PR 40, on a hand-built trace: two steps of 100 ms
holding the fused attention kernels under the names XLA gives their
instructions; the least-FLOP count on each cell's own files; and the four
``BENCHMARK.json`` entries."""

import json
import os
import types

import pytest

from chipbench.harness import spec, xtrace
from chipbench.harness.xtrace import Event, Line, Plane

MS = 1_000_000  # ns
CALL = ' custom-call(bf16[8]{0} %f), custom_call_target="tpu_custom_call"'
# (cell, its two metrics, least FLOPs a step by hand)
# 2 sequences x 1 attention layer x heads x 6 products x 2 x head_dim x 8192 x 8193 / 2
CELLS = [
    ("qwen3_next_80b_a3b_share16_seq8k_bs2",
     ("gated_attn_kernel_ms", "gated_attn_kernel_roofline"), 2 * 16 * 6 * 2 * 256 * 8192 * 8193 / 2),
    ("nemotron_twotower_30b_a3b_share16_seq8k_bs2",
     ("nemotron_attn_kernel_ms", "nemotron_attn_kernel_roofline"), 2 * 32 * 6 * 2 * 128 * 8192 * 8193 / 2),
]


def _plane(kernels: bool):
    """A step: a fusion 0-50 ms, then (``kernels``) the layer's forward of 9
    ms, the remat's forward again and the backward of 20 ms as the chip names
    them; a delta-rule kernel beside them, which is not theirs."""
    ops, modules = [], []
    for k in range(3):
        t = k * 100 * MS
        modules.append(Event("jit__train_step(1)", t, 95 * MS, {}))
        ops.append(Event("%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop", t, 50 * MS, {}))
        ops.append(Event("%mpi4dl_delta_rule_fwd.3 = bf16[8]{0}" + CALL, t + 90 * MS, 4 * MS, {}))
        if kernels:
            ops += [
                Event("%jvp_mpi4dl_attention_fwd_.1 = (bf16[8]{0}, f32[8]{0})" + CALL,
                      t + 50 * MS, 9 * MS, {}),
                Event("%jvp_mpi4dl_attention_fwd_.1.remat = (bf16[8]{0}, f32[8]{0})" + CALL,
                      t + 60 * MS, 9 * MS, {}),
                Event("%transpose_jvp_mpi4dl_attention_bwd__.1 = (f32[8]{0}, f32[8]{0}, f32[8]{0})"
                      + CALL, t + 70 * MS, 20 * MS, {}),
            ]
    return Plane("/device:TPU:0", [Line("XLA Modules", modules), Line("XLA Ops", ops)])


def _context(cell_name: str, kernels: bool):
    cell = spec.Cell(cell_name)
    return {"reduced": xtrace.reduce([_plane(kernels)], "train_step", 2),
            "cell": types.SimpleNamespace(model=cell.model, traffic=cell.traffic),
            "peaks": {"bf16_flops_per_s": 197e12}}


def _least_flops(metric, model, traffic):
    module = spec.load_module(
        os.path.join(spec.BENCH_DIR, "layer_metrics", metric + ".py"), "roofline")
    return module.least_flops_per_step(model, traffic)


@pytest.mark.parametrize("cell_name, names, by_hand", CELLS)
def test_least_flops_are_six_products_over_the_causal_half(cell_name, names, by_hand):
    cell = spec.Cell(cell_name)
    assert _least_flops(names[1], cell.model, cell.traffic) == by_hand
    assert by_hand == pytest.approx(3.2989e12, rel=1e-4)


def test_the_head_dim_is_the_configurations_own_key():
    """Neither cell's head dim is ``hidden_size / num_attention_heads`` (128
    and 84), which is what ``attn_kernel_roofline`` would take."""
    for cell_name, names, by_hand in CELLS:
        cell = spec.Cell(cell_name)
        assert cell.model["head_dim"] != cell.model["hidden_size"] // cell.model["num_attention_heads"]
        wider = dict(cell.model, head_dim=2 * cell.model["head_dim"])
        assert _least_flops(names[1], wider, cell.traffic) == 2 * by_hand


@pytest.mark.parametrize("layers, interval, attention", [(4, 4, 1), (48, 4, 12), (8, 2, 4), (3, 4, 0)])
def test_qwen3_nexts_attention_layers_are_every_nth(layers, interval, attention):
    cell_name, names, by_hand = CELLS[0]
    cell = spec.Cell(cell_name)
    model = dict(cell.model, num_hidden_layers=layers, full_attention_interval=interval)
    assert _least_flops(names[1], model, cell.traffic) == attention * by_hand


@pytest.mark.parametrize("pattern, attention", [
    ("MEMEM*EME", 1), ("MEMEMEME", 0), ("M*E*", 2),
    ("MEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEM*EMEME", 6)])
def test_nemotron_hs_attention_layers_are_the_patterns_stars(pattern, attention):
    cell_name, names, by_hand = CELLS[1]
    cell = spec.Cell(cell_name)
    model = dict(cell.model, hybrid_override_pattern=pattern)
    assert _least_flops(names[1], model, cell.traffic) == attention * by_hand


@pytest.mark.parametrize("cell_name, names, by_hand", CELLS)
def test_the_kernels_are_found_by_their_names_common_start(cell_name, names, by_hand):
    context = _context(cell_name, kernels=True)
    assert spec.metric_reader("layer_metrics", names[0])(context) == pytest.approx(38.0)
    share = spec.metric_reader("layer_metrics", names[1])(context)
    assert share == pytest.approx(100 * (by_hand / 197e12) / 38e-3, rel=1e-9)
    assert 25 < share < 100


@pytest.mark.parametrize("cell_name, names, by_hand", CELLS)
@pytest.mark.parametrize("context", [{"reduced": None}, "no kernel"])
def test_without_a_trace_or_without_the_kernels_nothing_is_read(context, cell_name, names, by_hand):
    """An untraced run, and the parent of the PR that planned the kernels for
    these shapes (its trace holds the delta rule's calls and none of
    attention's)."""
    context = _context(cell_name, kernels=False) if context == "no kernel" else context
    for name in names:
        assert spec.metric_reader("layer_metrics", name)(context) is None


@pytest.mark.parametrize("cell_name, names, by_hand", CELLS)
def test_the_entries_list_their_own_cell_alone(cell_name, names, by_hand):
    with open(os.path.join(os.path.dirname(spec.BENCH_DIR), "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    entries = {m["name"]: m for m in benchmark["per_layer"]}
    listed = [m["name"] for m in benchmark["per_layer"]]
    at = listed.index(names[0])  # added side by side, in this order; later PRs append after them
    assert listed[at:at + 2] == list(names)
    for name, unit, better in zip(names, ("ms", "%"), ("lower", "higher")):
        assert entries[name] == {
            "name": name, "unit": unit, "better": better, "source": "device_trace",
            "layer": "Pallas kernels", "moves": "images_per_s", "workloads": [cell_name]}
    assert {m["name"] for m in spec.Cell(cell_name).per_layer} >= set(names)
