"""The names the device trace finds the image step by (PR 41): every model
cell under ``mpi4dl_cell<NN>``, every operator class, the optimiser and the
loss under a ``jax.named_scope`` that survives ``jvp``, ``transpose`` and the
``custom_vjp`` rules, read off the *lowered* step (what the program controls;
a backend's compiled text drops and rewrites stacks its own way). Tiny
AmoebaNet-D and ResNet steps, one device and 2x2 tiles.
"""

import collections
import re

import jax
import jax.numpy as jnp
import pytest

from chipbench.harness import step_classes
from mpi4dl_tpu.config import ParallelConfig
from mpi4dl_tpu.train import Trainer, cell_scope

STEPS = {  # name: (model, spatial, image size)
    "amoebanet": ("amoebanet", False, 64),
    "amoebanet_sp2x2": ("amoebanet", True, 256),
    "resnet": ("resnet", False, 32),
    "resnet_sp2x2": ("resnet", True, 64),
}
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather")


def _trainer(model, spatial, size, remat=False, resnet_n="1"):
    """The model as the benchmark entry points build it, cut to a few cells."""
    from benchmarks.common import build_amoebanet, build_resnet
    from mpi4dl_tpu.parallel.pipeline import PipelineTrainer
    from mpi4dl_tpu.parser import get_parser

    args = get_parser().parse_args([
        "--batch-size", "2", "--image-size", str(size), "--split-size", "1",
        "--num-layers", "3", "--num-filters", "32"])
    cfg = ParallelConfig(
        batch_size=2, split_size=1, spatial_size=1 if spatial else 0,
        num_spatial_parts=(4,), slice_method="square", image_size=size)
    build = build_resnet if model == "resnet" else build_amoebanet
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("MPI4DL_TPU_RESNET_N", resnet_n)  # "1": ResNet-11, five cells
        n_sp = (PipelineTrainer.spatial_cell_count(len(build(args, cfg)[1]), cfg)
                if spatial else 0)
        cells, plain = build(args, cfg, spatial_cells=n_sp)[:2]
    return Trainer(cells, num_spatial_cells=n_sp, config=cfg, plain_cells=plain,
                   mesh=cfg.make_mesh(jax.devices()[:4 if spatial else 1]),
                   remat=remat)


def _arguments(trainer, size):
    state = jax.eval_shape(
        lambda: trainer.init(jax.random.PRNGKey(0), (2, size, size, 3)))
    return (state, jax.ShapeDtypeStruct((2, size, size, 3), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.int32))


def _operators(trainer, size):
    """``[(Instruction, name stack)]`` of the lowered step. The lowered
    module keeps the ``shard_map`` body and every inner ``jit`` as a
    computation of its own whose stacks are relative to it: a call site's
    stack goes in front of its callee's. Reducer regions (``to_apply`` of a
    ``reduce`` or an ``all-reduce``) run as no operator."""
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True
    module = trainer._jit_step.lower(*_arguments(trainer, size)).compiler_ir(
        dialect="hlo").as_hlo_module()
    text = module.to_string(options)
    computations = step_classes.parse(text)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)

    def walk(name, prefix):
        for ins in computations[name]:
            stack = "/".join(part for part in (prefix, ins.op_name) if part)
            if ins.opcode == "call":
                yield from walk(ins.calls, stack)
            else:
                yield ins, stack

    return list(walk(entry, ""))


@pytest.fixture(scope="module", params=sorted(STEPS))
def step(request):
    model, spatial, size = STEPS[request.param]
    trainer = _trainer(model, spatial, size)
    return request.param, trainer, _operators(trainer, size)


def test_every_windowed_operator_and_product_falls_in_its_class(step):
    name, trainer, operators = step
    seen = collections.Counter()
    for ins, stack in operators:
        cls, cell = step_classes.scope_of(stack)
        backward = "transpose(" in stack
        if ins.opcode == "convolution":
            assert cls in ("convkxk", "conv1x1") and cell, (ins.name, stack)
            if not backward:  # a gradient's window is not the kernel's
                taps = set(ins.window.split("x"))
                assert cls == ("conv1x1" if taps == {"1"} else "convkxk"), stack
            seen[cls, backward] += 1
        elif ins.opcode == "dot":
            assert cls == "conv1x1" and cell, (ins.name, stack)
            seen[cls, backward] += 1
        elif ins.opcode in ("reduce-window", "select-and-scatter"):
            assert cls == "pool" and cell, (ins.name, stack)
            seen["pool", backward] += 1
    # forward, and both gradients (a weight's and a datum's) behind it
    for cls in ("convkxk", "conv1x1") + (("pool",) if "amoebanet" in name else ()):
        assert seen[cls, False] > 0, cls
        assert seen[cls, True] >= (2 * seen[cls, False] - 2 if cls != "pool" else 1), cls


def test_collectives_are_told_apart_by_their_stack(step):
    name, trainer, operators = step
    seen = collections.Counter()
    for ins, stack in operators:
        if ins.opcode not in COLLECTIVES:
            continue
        cls, cell = step_classes.scope_of(stack)
        if ins.opcode != "all-reduce":
            assert cls == "halo" and cell, (ins.name, stack)
        elif cls is None:  # the gradients' sum: no cell, no class, backward
            assert cell is None and "transpose(jvp())" in stack, stack
            assert stack.endswith("/psum")
            cls = "grad_allreduce"
        else:
            assert (cls, bool(cell)) in (("batchnorm", True), ("loss", False)), stack
        seen[cls] += 1
    if name.endswith("sp2x2"):
        assert all(seen[c] for c in ("halo", "batchnorm", "loss", "grad_allreduce")), seen


def test_every_operator_of_a_model_cell_carries_its_index(step):
    name, trainer, operators = step
    modules = {type(cell).__name__ for cell in trainer.cells}
    cells = set()
    for ins, stack in operators:
        cell = step_classes.scope_of(stack)[1]
        if any(f"/{module}/" in f"/{stack}/" for module in modules):
            assert cell is not None, (ins.name, stack)
        if cell:
            cells.add(cell)
    assert cells == {f"{i:02d}" for i in range(len(trainer.cells))}


def test_the_optimiser_and_the_loss_carry_their_scopes(step):
    name, trainer, operators = step
    optimiser = [ins for ins, stack in operators if "mpi4dl_optimizer" in stack]
    assert all(stack.startswith("jit(_train_step)/mpi4dl_optimizer/")
               for ins, stack in operators if "mpi4dl_optimizer" in stack)
    updated = sum(ins.opcode == "add" for ins in optimiser)
    parameters = sum(ins.opcode == "parameter" and ins.op_name.startswith("state.params")
                     for ins, _ in operators)
    assert updated >= 2 * parameters > 0  # momentum's add and the weight's
    # outside the model's cells nothing multiplies or adds but they and the
    # step counter
    bare = [stack for ins, stack in operators
            if ins.opcode in ("multiply", "add") and stack.count("/") == 1
            and "mpi4dl_" not in stack]
    assert bare == ["jit(_train_step)/add"], bare
    softmax = [stack for ins, stack in operators
               if ins.opcode in ("exponential", "log")]
    assert softmax and all("mpi4dl_loss" in stack for stack in softmax), softmax


def test_no_scope_is_part_of_another_name(step):
    name, trainer, operators = step
    scopes = set(step_classes.CLASS_SCOPES) | {
        cell_scope(i) for i in range(len(trainer.cells))} | {cell_scope(3, 5)}
    for a in scopes:
        assert not [b for b in scopes if a != b and a in b], a
    others = set()
    for ins, stack in operators:
        others.update(part for part in re.split(r"[/()]", stack)
                      if part and part not in scopes)
        if ins.opcode == "parameter" and ins.op_name.startswith("state"):
            others.add(ins.op_name)  # a parameter's path in the state
    clash = [(a, b) for a in scopes for b in others if a in b]
    assert not clash, clash[:5]


@pytest.mark.parametrize("remat", ["cell", "scan", "scanlog", "sqrt"])
def test_every_remat_policy_names_its_cells(remat):
    """The policies reach the cells through ``_run_cell`` or the scan plan:
    every cell's index, or the run it is stacked into, is on an equation of
    the traced step (its name stack; the jaxpr's text holds none)."""
    from chipbench.harness import counting

    # three blocks a stage: the second and third are one scanned run
    trainer = _trainer("resnet", False, 32, remat=remat, resnet_n="3")
    jaxpr = jax.make_jaxpr(trainer._train_step)(*_arguments(trainer, 32)).jaxpr
    names = set()  # (first, last or "") of every cell scope
    for eqn in counting._walk(jaxpr):
        names.update(re.findall(
            r"mpi4dl_cells?(\d\d)(?:to(\d\d))?", str(eqn.source_info.name_stack)))
    covered = set()
    for first, last in names:
        covered.update(range(int(first), int(last or first) + 1))
    assert covered == set(range(len(trainer.cells))), names
    if remat == "scan":
        assert any(last for _, last in names), names


def test_compiled_step_is_made_once_for_the_same_arguments():
    """``record_memory_footprint`` and the trace's readers ask for the same
    compiled step: one lowering serves both, another shape makes another."""
    trainer = _trainer("resnet", False, 16)
    state = trainer.init(jax.random.PRNGKey(0), (2, 16, 16, 3))
    xs, ys = trainer.shard_batch(
        jnp.zeros((2, 16, 16, 3), jnp.float32), jnp.zeros((2,), jnp.int32))
    lowerings = []

    class Counting:
        def __init__(self, jitted):
            self.jitted = jitted

        def lower(self, *args):
            lowerings.append(args)
            return self.jitted.lower(*args)

    trainer._jit_step = Counting(trainer._jit_step)
    first = trainer.compiled_step(state, xs, ys)
    entry = trainer.record_memory_footprint(state, xs, ys)
    assert entry["peak_bytes"] > 0
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        (state, xs, ys))
    assert trainer.compiled_step(*shapes) is first
    assert len(lowerings) == 1
    half = trainer.shard_batch(
        jnp.zeros((1, 16, 16, 3), jnp.float32), jnp.zeros((1,), jnp.int32))
    assert trainer.compiled_step(state, *half) is not first
    assert len(lowerings) == 2
