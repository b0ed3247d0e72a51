"""``attn_kernel_ms`` and ``attn_kernel_roofline`` on a hand-built trace:
two steps of 100 ms holding the fused attention kernels under the names XLA
gives their instructions, and the least-FLOP count on the cell's own files."""

import types

import pytest

from chipbench.harness import spec, xtrace
from chipbench.harness.xtrace import Event, Line, Plane

MS = 1_000_000  # ns
CELL = "lfm2_8b_a1b_share4_seq8k_bs1"


def _plane(kernels: bool):
    """A step: a fusion 0-50 ms, then (``kernels``) two forwards of 4 ms and
    a backward of 8 ms as the chip names them."""
    ops, modules = [], []
    for k in range(3):
        t = k * 100 * MS
        modules.append(Event("jit__train_step(1)", t, 90 * MS, {}))
        ops.append(Event("%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop", t, 50 * MS, {}))
        if kernels:
            ops += [
                Event("%jvp_mpi4dl_attention_fwd_.1 = (bf16[8]{0}, f32[8]{0}) custom-call(bf16[8]{0} %f), "
                      'custom_call_target="tpu_custom_call"', t + 50 * MS, 4 * MS, {}),
                Event("%jvp_mpi4dl_attention_fwd_.1.remat = (bf16[8]{0}, f32[8]{0}) custom-call(bf16[8]{0} "
                      '%f), custom_call_target="tpu_custom_call"', t + 60 * MS, 4 * MS, {}),
                Event("%transpose_jvp_mpi4dl_attention_bwd__.1 = (f32[8]{0}) custom-call(bf16[8]{0} %f), "
                      'custom_call_target="tpu_custom_call"', t + 70 * MS, 8 * MS, {}),
            ]
    return Plane("/device:TPU:0", [Line("XLA Modules", modules), Line("XLA Ops", ops)])


def _context(kernels: bool):
    cell = spec.Cell(CELL)
    return {"reduced": xtrace.reduce([_plane(kernels)], "train_step", 2),
            "cell": types.SimpleNamespace(model=cell.model, traffic=cell.traffic),
            "peaks": {"bf16_flops_per_s": 197e12}}


def test_least_flops_are_six_products_over_the_causal_half():
    cell = spec.Cell(CELL)
    module = spec.load_module(
        spec.os.path.join(spec.BENCH_DIR, "layer_metrics", "attn_kernel_roofline.py"), "roofline")
    # 2 attention layers x 32 heads x 6 products x 2 x 64 x 8192 x 8193 / 2
    assert module.least_flops_per_step(cell.model, cell.traffic) == 2 * 32 * 6 * 2 * 64 * 8192 * 8193 / 2
    assert module.least_flops_per_step(cell.model, cell.traffic) == pytest.approx(1.6495e12, rel=1e-4)


def test_the_kernels_are_found_by_their_own_names():
    context = _context(kernels=True)
    assert spec.metric_reader("layer_metrics", "attn_kernel_ms")(context) == pytest.approx(16.0)
    share = spec.metric_reader("layer_metrics", "attn_kernel_roofline")(context)
    assert share == pytest.approx(100 * (1.6495e12 / 197e12) / 16e-3, rel=1e-4)
    assert 0 < share < 100


@pytest.mark.parametrize("context", [{"reduced": None}, "no kernel"])
def test_without_a_trace_or_without_the_kernels_nothing_is_read(context):
    """An untraced run, and the parent of the PR that brought the kernels."""
    context = _context(kernels=False) if context == "no kernel" else context
    for name in ("attn_kernel_ms", "attn_kernel_roofline"):
        assert spec.metric_reader("layer_metrics", name)(context) is None
