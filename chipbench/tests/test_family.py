"""A family comes as files, never as an edit of the harness: a toy
token-sequence model (integer input, a loss at every position, a builder,
an input stream and a FLOP count of its own) runs through ``run.run()``, the
comparison and the reference tests with every harness file as it is. The
toy's reference and stand-in program are ``toy_tokens_reference.py`` and
``toy_tokens_program.py``; its configuration, traffic mix, tiny file and
cell file are written here, under ``tmp_path``.
"""

import json
import os

import jax
import pytest

from chipbench import run
from chipbench.harness import counting, spec
from chipbench.harness.session import Session
from chipbench.tests import test_reference, tiny, toy_tokens_reference
from chipbench.tests.test_check import _HalfTheBatch, _StateUnchanged

FILES = {
    "configs/toy_tokens.json": {
        "name": "toy_tokens",
        "source": "chipbench/tests/test_family.py",
        # as a published config has them: sizes at the top level of the file
        "vocab_size": 96,
        "hidden_size": 64,
        "reduced": [],
        "precision": {"stated": "bfloat16"},
        "optimizer": {"name": "sgd", "learning_rate": 0.001, "momentum": 0.9},
        "entry_point": {
            "build_trainer": "chipbench.tests.toy_tokens_program:build_trainer",
            "input_stream": "chipbench.tests.toy_tokens_program:input_stream",
            "step_program": "step",
        },
        "reference": {"module": "chipbench.tests.toy_tokens_reference"},
        "layout": {"chips": 1},
    },
    "traffic/toy_seq64.json": {
        "name": "toy_seq64",
        "batch_size": 2,
        "sequence_length": 64,
        "prefetch": False,
        "warmup_steps": 3,
        "check_steps": 3,
        "trace_steps": 3,
    },
    "tests/tiny/toy_tokens.json": {
        "config": "toy_tokens",
        "traffic": "toy_seq64",
        "model": {"hidden_size": 32},
        "traffic_cut": {"sequence_length": 16},
        "reference_case": {
            "model": {"hidden_size": 32},
            "input_below": 96,
            "program_cells": "chipbench.tests.toy_tokens_program:token_cells",
            "program_step": "chipbench.tests.toy_tokens_program:single_device_step",
        },
    },
}
# Tiny-size readings on the CPU (this file's own, PR 30; seeds 97-108,
# 2147483659, 3000000019), the sound program's largest against the smallest
# of what each number has to refuse.
LIMITS = {
    "cell_y_err": 0.01,        # 0.0030; the fp8 control 0.0346
    "grad_norm_gap": 0.05,     # 0.0014; half the batch twice 0.27, state unchanged 0.89
    "change_norm_gap": 0.05,   # 0.0008; half the batch twice 0.21, state unchanged 1.0
    "loss_gap_step1": 0.002,   # 0.0004; held against garbage (half a batch reads from 0.004)
}


@pytest.fixture
def toy_dir(tmp_path):
    """A directory laid out as ``chipbench/`` that holds the toy's files
    and nothing of the harness."""
    for rel, body in FILES.items():
        path = tmp_path / "bench" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    return str(tmp_path / "bench")


def _cell(tmp_path, toy_dir, limits=None):
    return tiny.tiny_cell(tmp_path / "cell", "toy_tokens", limits, bench_dir=toy_dir)


def test_the_toy_passes_the_fp8_control_fails_and_ids_have_no_cotangent(
        tmp_path, toy_dir, capsys):
    cell = _cell(tmp_path, toy_dir)
    session = Session(cell)
    assert session.x_shape == (2, 16) and session.x_dtype == jax.numpy.int32
    first = session.first_steps(2147483659, session.check_steps)
    assert first.batches[0][0].dtype == first.batches[0][1].dtype == "int32"
    assert first.batches[0][1].shape == (2, 16)  # a label at every position
    first.loop.state = None
    program, control = session.compare(first, control="fp8")
    assert program["cell_y_err"] < LIMITS["cell_y_err"] < control["cell_y_err"]
    assert all(v == v for v in program.values())  # no NaN
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    errors = next(l for l in lines if l.get("phase") == "reference")["cell_errors"]
    # the embedding is tapped (it is the only stem) and compared in its
    # output and its parameters' cotangents; fed integers, it has no third
    assert "0" in errors["cell_y_err"] and "0" in errors["cell_dv_err"]
    assert "0" not in errors["cell_dx_err"] and len(errors["cell_dx_err"]) == 2
    assert program["cell_dx_err"] == max(errors["cell_dx_err"].values())


@pytest.mark.parametrize("broken", [None, _StateUnchanged, _HalfTheBatch])
def test_a_toy_run_with_the_timed_path_broken_is_not_correct(
        tmp_path, toy_dir, broken):
    cell = _cell(tmp_path, toy_dir, LIMITS)
    result = run.run(
        tiny.options(cell.name, seed=97), jax.devices(), wrap_step=broken,
        cell=cell, peaks=tiny.PEAKS,
    )
    assert result["correct"] is (broken is None)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"images_per_s", "step_ms_p90", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_mfu_pct_takes_the_familys_own_count(tmp_path, toy_dir):
    cell = _cell(tmp_path, toy_dir)
    session = Session(cell)
    own = toy_tokens_reference.train_flops_per_sample(cell.model, cell.traffic)
    assert own == 3 * 2 * (2 * 32 * 32 + 32 * 96) * 16
    assert session.flops_per_sample == own
    # read off the plain reference's jaxpr, each expert cell counts both of
    # its experts for every token: the count the hook is there to replace
    plain_count = counting.train_flops_per_sample(
        session.ref_cells, session.x_shape[1:], session.x_dtype)
    assert plain_count == 3 * 2 * (4 * 32 * 32 + 32 * 96) * 16
    mfu = spec.metric_reader("layer_metrics", "mfu_pct")(
        {"session": session, "peaks": tiny.PEAKS, "cell": cell, "window_rate": 50.0})
    assert mfu == pytest.approx(100.0 * own * 50.0 / tiny.PEAKS["bf16_flops_per_s"])


def test_the_toys_reference_tests_come_from_its_tiny_file(toy_dir):
    case = test_reference.load_case("toy_tokens", toy_dir)
    assert case.x.dtype == "int32" and case.y.shape == case.x.shape
    test_reference.test_parameter_tree_is_the_programs(case)
    test_reference.test_kinds_name_every_cell(case)
    test_reference.test_every_cell_and_its_vjp_agree_with_the_float32_twin(case)
    test_reference.test_losses_of_the_first_steps_agree(case)


def test_no_harness_file_is_among_the_toys(toy_dir):
    theirs = {os.path.relpath(os.path.join(d, f), toy_dir)
              for d, _, files in os.walk(toy_dir) for f in files}
    assert theirs == set(FILES)
    for rel in FILES:
        assert not os.path.exists(os.path.join(spec.BENCH_DIR, rel)), rel
