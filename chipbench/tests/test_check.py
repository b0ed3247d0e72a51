"""The comparison that decides ``correct``, at sizes a test run can hold:
the bf16 program passes the cell-by-cell check, the fp8 control fails it
(for every configuration that has a tiny file under ``tests/tiny/``: on four
virtual devices for the spatial one), and
a run whose timed path is broken underneath comes out not correct."""

import jax
import pytest

from chipbench import run
from chipbench.harness.session import Session
from chipbench.tests import tiny

# Tiny-size readings on the CPU (this file's own, PR 25): the bf16 program's
# cell_y_err reads 0.005-0.010, the fp8 control's 0.03-0.06.
CELL_Y_LIMIT = 0.02


@pytest.mark.parametrize("config", tiny.configs())
def test_program_passes_and_fp8_control_fails(tmp_path, config):
    cell = tiny.tiny_cell(tmp_path, config)
    assert len(jax.devices()) >= cell.chips
    session = Session(cell)
    first = session.first_steps(2147483659, session.check_steps)
    first.loop.state = None
    program, control = session.compare(first, control="fp8")
    assert program["cell_y_err"] < CELL_Y_LIMIT < control["cell_y_err"]
    assert control["cell_dx_err"] > program["cell_dx_err"]
    assert all(v == v for v in program.values())  # no NaN


def test_the_first_gradient_is_measured_against_the_largest_of_the_steps():
    """A first gradient that is all but zero (one label twice in a batch of
    two) is no scale for its own gap; an unchanged state still reads 1.0
    where the first gradient is an ordinary one."""
    from chipbench.harness import check

    ordinary, residual = [30.0, 40.0], [0.6, 0.8]
    assert check.norm_gaps([0.66, 0.88], residual)[1] == pytest.approx(0.1)
    assert check.norm_gaps([0.66, 0.88], residual, whole_floor=50.0)[1] == \
        pytest.approx(0.002)
    assert check.norm_gaps([0.0, 0.0], ordinary, whole_floor=50.0)[1] == 1.0
    assert check.norm_gaps([33.0, 44.0], ordinary, whole_floor=10.0)[1] == \
        pytest.approx(0.1)


def test_a_kind_set_apart_reads_under_its_own_name():
    """``apart`` in a cell's file takes a kind of cell out of an error's
    largest-over-taps and reports it as ``<error>.<kind>``."""
    import types

    steps = types.SimpleNamespace(
        losses=[1.0], grad_norms=[1.0, 2.0], change_norms=[1.0, 2.0],
        grad_scale=5.0 ** 0.5)
    fake = types.SimpleNamespace(
        kinds=["stem", "normal", "normal", "head"],
        cell=types.SimpleNamespace(
            limits={"apart": {"cell_dv_err": ["stem"]}}),
    )
    errors = {
        "cell_y_err": {0: 0.3, 1: 0.1, 3: 0.2},
        "cell_dv_err": {0: 0.9, 1: 0.1, 2: 0.4, 3: 0.2},
    }
    numbers = Session._numbers(fake, steps, steps, errors)
    assert numbers["cell_y_err"] == 0.3
    assert numbers["cell_dv_err"] == 0.4
    assert numbers["cell_dv_err.stem"] == 0.9
    assert numbers["loss_gap_step1"] == numbers["grad_norm_gap"] == 0.0


class _StateUnchanged:
    """The trainer with a step that returns its state as it got it."""

    def __init__(self, trainer):
        self._trainer = trainer

    def __getattr__(self, name):
        return getattr(self._trainer, name)

    def train_step(self, state, xs, ys):
        import jax.numpy as jnp

        copy = jax.tree.map(jnp.copy, state)  # the real step donates it
        _, metrics = self._trainer.train_step(state, xs, ys)
        return copy, metrics


class _HalfTheBatch:
    """The trainer with a step that sees the first image twice."""

    def __init__(self, trainer):
        self._trainer = trainer

    def __getattr__(self, name):
        return getattr(self._trainer, name)

    def shard_batch(self, x, y):
        import jax.numpy as jnp

        x = jnp.concatenate([x[:1], x[:1]])
        y = jnp.concatenate([y[:1], y[:1]])
        return self._trainer.shard_batch(x, y)


LIMITS = {"loss_gap_step1": 0.02, "change_norm_gap": 0.5, "cell_y_err": CELL_Y_LIMIT}


@pytest.mark.parametrize("broken", [None, _StateUnchanged, _HalfTheBatch])
def test_a_run_with_the_timed_path_broken_is_not_correct(tmp_path, broken):
    cell = tiny.tiny_cell(tmp_path, "resnet110_1024", limits=LIMITS)
    result = run.run(
        tiny.options(cell.name, seed=97), jax.devices(), wrap_step=broken,
        cell=cell, peaks=tiny.PEAKS,
    )
    assert result["correct"] is (broken is None)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"images_per_s", "step_ms_p90", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    # what was compared closes the line, each number beside its limit
    assert list(result)[-1] == "compared"
    assert {k: v["limit"] for k, v in result["compared"].items() if v["limit"]} == LIMITS
    over = [k for k, v in result["compared"].items()
            if v["limit"] and not v["value"] <= v["limit"]]
    assert bool(over) is (broken is not None)


def test_the_window_starts_from_a_collected_heap(tmp_path, monkeypatch, capsys):
    """One full collection ends set-up: after the warm-up steps, before
    ``setup_s`` is read and the window opens, so that no tree's window holds
    the pass over the step's trace that another tree's lacks."""
    import gc
    import json
    import types

    def collect():
        print(json.dumps({"phase": "collect", "objects": gc.collect()}))

    monkeypatch.setattr(run, "gc", types.SimpleNamespace(collect=collect))
    cell = tiny.tiny_cell(tmp_path, "resnet110_1024", limits=LIMITS)
    run.run(tiny.options(cell.name, seed=97), jax.devices(), cell=cell, peaks=tiny.PEAKS)
    phases = [json.loads(line).get("phase") for line in capsys.readouterr().out.splitlines()
              if line.startswith("{")]
    assert phases.count("collect") == 1
    assert phases.index("build") < phases.index("collect") < phases.index("setup") \
        < phases.index("window")


def test_a_run_with_the_exchange_between_chips_left_out_is_not_correct(
        tmp_path, monkeypatch):
    """The spatial cell on four virtual devices with every halo strip
    arriving as zeros, as if no neighbour had sent: the tapped spatial
    cells' outputs are wrong at every tile edge. (The sound program under
    the same limit: ``test_program_passes_and_fp8_control_fails``.)"""
    import jax.numpy as jnp

    from mpi4dl_tpu.parallel import halo

    monkeypatch.setattr(
        halo, "_shift", lambda x, axis_name, direction: jnp.zeros_like(x))
    cell = tiny.tiny_cell(tmp_path, "amoebanetd_1024_sp2x2", limits=LIMITS)
    result = run.run(
        tiny.options(cell.name, seed=97), jax.devices(), cell=cell, peaks=tiny.PEAKS,
    )
    assert result["correct"] is False and result["failed"] == 0
    assert result["compared"]["cell_y_err"]["value"] > 5 * CELL_Y_LIMIT
