"""Each plain reference agrees with the program's float32 twin at a tiny
size: the parameter tree, every cell's output and VJP, and the loss of the
first steps. (Whole-net gradients of a fresh net are not compared leaf by
leaf: batch-2 BatchNorm and ReLU masks amplify float32 rounding by some
thousands through the backward pass, PERF.md section 6; cell by cell
nothing is amplified.)

The cases are the ``reference_case`` of the files under ``tests/tiny/``:
``model`` (the keys changed from the configuration's), ``program_cells``
(``"<module>:<callable>"``: the program's float32 cells of that model) and,
for a family the program trains by another step than its image classifiers',
``program_step`` (``cells -> (tx, step)``, as ``train.single_device_step``).
``input_below`` bounds the values of an integer input (a vocabulary's size).
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import check, defaults, program
from chipbench.reference import plain
from chipbench.reference.step import Follower
from chipbench.tests import tiny


@dataclasses.dataclass
class Case:
    module: object      # the reference module
    model: dict
    traffic: dict
    ref_cells: list
    params: list
    x: np.ndarray
    y: np.ndarray
    program_cells: object   # model -> the program's float32 cells
    program_step: object    # cells -> (tx, step)


def load_case(config_name, bench_dir=tiny.spec.BENCH_DIR) -> Case:
    cell = tiny.tiny_file(config_name, bench_dir)
    case = cell["reference_case"]
    config = tiny.read_json(bench_dir, "configs", config_name + ".json")
    traffic = dict(tiny.read_json(bench_dir, "traffic", cell["traffic"] + ".json"),
                   **cell.get("traffic_cut", {}))
    model = dict(config.get("model", config), **case["model"])
    module = importlib.import_module(config["reference"]["module"])
    ref_cells = module.cells(model)
    sample, dtype = getattr(module, "input_spec", defaults.input_spec)(model, traffic)
    shape = (2,) + tuple(sample)
    params = plain.make_params(plain.record_specs(ref_cells, shape, dtype), 3000000019)
    rng = np.random.default_rng(7)
    if jnp.issubdtype(dtype, jnp.floating):
        x = rng.random(shape, dtype=np.float32)
    else:
        x = rng.integers(0, case["input_below"], shape, dtype=np.int32)
    logits = jax.eval_shape(
        lambda: _forward(ref_cells, params, jnp.asarray(x)))
    y = rng.integers(0, logits.shape[-1], logits.shape[:-1], dtype=np.int32)
    step = case.get("program_step", "mpi4dl_tpu.train:single_device_step")
    return Case(module, model, traffic, ref_cells, params, x, y,
                program.resolve(case["program_cells"]), program.resolve(step))


def _forward(ref_cells, params, h):
    for fn, v in zip(ref_cells, params):
        h = fn(plain.Scope(v["params"]), h)
    return h


CASES = [c for c in tiny.configs() if "reference_case" in tiny.tiny_file(c)]


@pytest.fixture(scope="module", params=CASES)
def case(request):
    return load_case(request.param)


def test_parameter_tree_is_the_programs(case):
    from mpi4dl_tpu.parallel.partition import init_cells

    theirs = jax.eval_shape(
        lambda: init_cells(case.program_cells(case.model), jax.random.PRNGKey(0),
                           jnp.zeros(case.x.shape, case.x.dtype))
    )
    assert jax.tree.structure(theirs) == jax.tree.structure(case.params)
    assert [a.shape for a in jax.tree.leaves(theirs)] == [
        a.shape for a in jax.tree.leaves(case.params)]
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(case.params))


def test_kinds_name_every_cell(case):
    kinds = case.module.kinds(case.model)
    assert len(kinds) == len(case.ref_cells)
    assert kinds[0] == "stem" and kinds[-1] == "head"


def test_every_cell_and_its_vjp_agree_with_the_float32_twin(case):
    cells = case.program_cells(case.model)
    h = jnp.asarray(case.x)
    for i, (fn, cell) in enumerate(zip(case.ref_cells, cells)):
        y_shape = jax.eval_shape(
            lambda v, x_: fn(plain.Scope(v["params"]), x_), case.params[i], h)
        ct = check.seeded_cotangent(y_shape, 11, i)
        ref = check.reference_cell_vjp(fn, "f32", case.params[i], h, ct)
        y, pull = plain.vjp(lambda v, x_: cell.apply(v, x_), case.params[i], h)
        got = (y,) + tuple(pull(ct))
        assert len(got) == len(ref) == (3 if plain.takes_cotangent(h) else 2)
        for what, a, b in zip(("y", "dv", "dx"), got, ref):
            # a ReLU mask flipped by a last-bit difference moves a VJP by
            # the square root of the share flipped; a wrong cell is O(0.1-1)
            assert check.relative_l2(a, b) < 5e-3, (i, what)
        h = ref[0]


def test_losses_of_the_first_steps_agree(case):
    from mpi4dl_tpu.train import TrainState

    x, y = case.x, case.y
    tx, step = case.program_step(case.program_cells(case.model))
    state = TrainState(params=case.params, opt_state=tx.init(case.params),
                       step=jnp.zeros((), jnp.int32))
    loss_fn = getattr(case.module, "loss", defaults.loss)
    follower = Follower(case.ref_cells, case.params, 0.001, 0.9, loss_fn,
                        device_budget=1 << 20)
    for k in range(2):
        state, metrics = step(state, jnp.asarray(x), jnp.asarray(y))
        loss, _ = follower.step(x, y)
        # step 2 starts from parameters moved by an amplified gradient
        assert float(metrics["loss"]) == pytest.approx(loss, rel=1e-4 if k == 0 else 0.1)
