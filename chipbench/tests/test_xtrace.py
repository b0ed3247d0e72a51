"""The reduction from a trace to numbers, on a hand-built trace whose
answers are known: two chips, ten whole steps of 100 us."""

import pytest

from chipbench.harness import spec, xtrace
from chipbench.harness.xtrace import Event, Line, Plane

US = 1000  # ns
STEPS = 10


def _chip(index, skew_ns=0):
    """Per step of 100 us: a fusion 0-40, a pool kernel 40-50, an async
    permute start 50-52 and done 60-65 with a convolution 52-60 between, an
    all-reduce 65-70; idle 70-100. One more program run follows the ten."""
    ops, modules = [], []
    for k in range(STEPS + 1):
        t = skew_ns + k * 100 * US
        modules.append(Event("jit__train_step(123)", t, 70 * US, {}))
        ops += [
            # named as the chip's trace names ops: the instruction's text
            Event("%fusion.7 = bf16[2,8,8,4]{3,2,1,0:T(8,128)(2,1)} fusion("
                  "bf16[2,8,8,4]{3,2,1,0} %custom-call.3), kind=kLoop", t, 40 * US, {}),
            Event("%mpi4dl_pool_bwd.3 = bf16[2,8,8,4]{3,2,1,0:T(8,128)(2,1)S(1)} "
                  "custom-call(bf16[2,8,8,4]{3,2,1,0} %fusion.7), "
                  'custom_call_target="tpu_custom_call"', t + 40 * US, 10 * US, {}),
            Event("collective-permute-start.1", t + 50 * US, 2 * US, {}),
            Event("convolution.2", t + 52 * US, 8 * US, {}),
            Event("collective-permute-done.1", t + 60 * US, 5 * US, {}),
            Event("all-reduce.4", t + 65 * US, 5 * US, {}),
        ]
    return Plane(f"/device:TPU:{index}", [
        Line("XLA Modules", modules), Line("XLA Ops", ops), Line("Steps", []),
    ])


def _host():
    spans = []
    for k in range(STEPS + 1):
        t = k * 100 * US
        spans += [
            Event("chipbench_loss_read", t + 5 * US, 70 * US, {}),
            Event("chipbench_data_next", t + 76 * US, 20 * US, {}),
            Event("chipbench_dispatch", t + 96 * US, 8 * US, {}),
        ]
    return Plane("/host:CPU", [Line("python", spans)])


@pytest.fixture(scope="module")
def reduced():
    planes = [_host(), _chip(1, skew_ns=3 * US), _chip(0), Plane("/device:CUSTOM:0", [])]
    return xtrace.reduce(planes, "train_step", STEPS)


def test_union_counts_overlap_once():
    assert xtrace.union_seconds([(0, 10), (5, 20), (30, 40), (32, 35)]) == pytest.approx(30e-9)


def test_window_is_whole_steps_on_each_chips_clock(reduced):
    assert reduced.steps == STEPS
    assert reduced.window_s == pytest.approx(STEPS * 100e-6)
    assert [c["window"][0] for c in reduced.chips] == [0, 3 * US]


def test_busy_union_and_idle_share(reduced):
    assert reduced.busy_s == pytest.approx(STEPS * 70e-6)
    read = spec.metric_reader("layer_metrics", "device_idle_pct")
    # 70 us busy a step against the untraced window's 80 us steps
    assert read({"reduced": reduced, "step_s": [80e-6] * 5}) == pytest.approx(12.5)
    assert 1 - reduced.busy_s / reduced.window_s == pytest.approx(0.30)  # traced


def test_kernel_sum_and_roofline_arithmetic(reduced):
    seconds = xtrace.kernel_seconds_per_step(reduced, "mpi4dl_pool_bwd")
    assert seconds == pytest.approx(10e-6)
    assert xtrace.kernel_seconds_per_step(reduced, "mpi4dl_wgrad") is None
    ms = spec.metric_reader("layer_metrics", "pool_bwd_ms")({"reduced": reduced})
    assert ms == pytest.approx(0.010)


def test_collectives_count_start_and_done_once_each(reduced):
    chip = reduced.chips[0]
    per_step = xtrace.collective_seconds(chip["ops"], *chip["window"]) / STEPS
    assert per_step == pytest.approx(12e-6)  # 2 + 5 + 5, not the 50-65 stretch
    ms = spec.metric_reader("layer_metrics", "collective_ms")({"reduced": reduced})
    assert ms == pytest.approx(0.012)


def test_own_ops_leave_out_collectives_and_custom_calls(reduced):
    ms = spec.metric_reader("layer_metrics", "xla_ops_ms")({"reduced": reduced})
    assert ms == pytest.approx(0.048)  # fusion 40 + convolution 8


def test_breakdown_names_ops_and_attributes_gaps(reduced):
    ops = dict(reduced.device_ops)
    assert ops[f"fusion (x{STEPS})"] == pytest.approx(STEPS * 40e-6)
    assert ops[f"collective-permute-done (x{STEPS})"] == pytest.approx(STEPS * 5e-6)
    assert len(reduced.device_ops) <= 10
    gaps = dict(reduced.idle_gaps)
    # each step's 70-100 us gap lies mostly under data_next (76-96)
    assert gaps == {"data_next": pytest.approx(STEPS * 30e-6)}


def test_readers_find_nothing_without_a_trace():
    for name in ("device_idle_pct", "collective_ms", "xla_ops_ms", "pool_bwd_ms"):
        context = {"reduced": None, "step_s": [1.0]}
        assert spec.metric_reader("layer_metrics", name)(context) is None
