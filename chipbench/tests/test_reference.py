"""Each plain reference agrees with the program's float32 twin at a tiny
size: the parameter tree, every cell's output and VJP, and the loss of the
first steps. (Whole-net gradients of a fresh net are not compared leaf by
leaf: batch-2 BatchNorm and ReLU masks amplify float32 rounding by some
thousands through the backward pass, PERF.md section 6; cell by cell
nothing is amplified.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import check
from chipbench.reference import amoebanetd, plain, resnet_v2
from chipbench.reference.step import Follower


def _program_cells(name, model):
    if name == "amoebanetd":
        from mpi4dl_tpu.models.amoebanet import amoebanetd as build

        return build(num_layers=model["num_layers"], num_filters=model["num_filters"],
                     num_classes=model["num_classes"])
    from mpi4dl_tpu.models.resnet import get_resnet_v2

    return get_resnet_v2(depth=model["depth"], num_classes=model["num_classes"],
                         pool_kernel=model["image_size"] // 4)


CASES = {
    "amoebanetd": (amoebanetd, dict(num_layers=3, num_filters=32, num_classes=10,
                                    image_size=128)),
    "resnet_v2": (resnet_v2, dict(depth=20, num_classes=10, image_size=32)),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    module, model = CASES[request.param]
    ref_cells = module.cells(model)
    shape = (2, model["image_size"], model["image_size"], 3)
    params = plain.make_params(plain.record_specs(ref_cells, shape), 3000000019)
    x = np.random.default_rng(7).random(shape, dtype=np.float32)
    return request.param, module, model, ref_cells, params, x


def test_parameter_tree_is_the_programs(case):
    from mpi4dl_tpu.parallel.partition import init_cells

    name, _, model, _, params, x = case
    theirs = jax.eval_shape(
        lambda: init_cells(_program_cells(name, model), jax.random.PRNGKey(0),
                           jnp.zeros(x.shape))
    )
    assert jax.tree.structure(theirs) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(theirs)] == [
        a.shape for a in jax.tree.leaves(params)]
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(params))


def test_kinds_name_every_cell(case):
    _, module, model, ref_cells, _, _ = case
    kinds = module.kinds(model)
    assert len(kinds) == len(ref_cells)
    assert kinds[0] == "stem" and kinds[-1] == "head"


def test_every_cell_and_its_vjp_agree_with_the_float32_twin(case):
    name, _, model, ref_cells, params, x = case
    program = _program_cells(name, model)
    h = jnp.asarray(x)
    for i, (fn, cell) in enumerate(zip(ref_cells, program)):
        y_shape = jax.eval_shape(
            lambda v, x_: fn(plain.Scope(v["params"]), x_), params[i], h)
        ct = check.seeded_cotangent(y_shape, 11, i)
        ref = check.reference_cell_vjp(fn, "f32", params[i], h, ct)
        y, pull = jax.vjp(lambda v, x_: cell.apply(v, x_), params[i], h)
        got = (y,) + tuple(pull(ct))
        for what, a, b in zip(("y", "dv", "dx"), got, ref):
            # a ReLU mask flipped by a last-bit difference moves a VJP by
            # the square root of the share flipped; a wrong cell is O(0.1-1)
            assert check.relative_l2(a, b) < 5e-3, (i, what)
        h = ref[0]


def test_losses_of_the_first_steps_agree(case):
    from mpi4dl_tpu.train import TrainState, single_device_step

    name, _, model, ref_cells, params, x = case
    y = np.array([3, 7], np.int32)
    tx, step = single_device_step(_program_cells(name, model))
    state = TrainState(params=params, opt_state=tx.init(params),
                       step=jnp.zeros((), jnp.int32))
    follower = Follower(ref_cells, params, 0.001, 0.9, device_budget=1 << 20)
    for k in range(2):
        state, metrics = step(state, jnp.asarray(x), jnp.asarray(y))
        loss, _ = follower.step(x, y)
        # step 2 starts from parameters moved by an amplified gradient
        assert float(metrics["loss"]) == pytest.approx(loss, rel=1e-4 if k == 0 else 0.1)
