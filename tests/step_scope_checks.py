"""What every token model's reader test also holds its step to since PR 41:
beside the model's own scopes the step carries ``mpi4dl_cell<NN>`` for every
cell, ``mpi4dl_optimizer`` and ``mpi4dl_loss``, and the benchmark's readers
of them read the hand-made trace (one event of 1 ms an instruction)."""

import pytest


def check_step_scopes(context, op_names, trainer):
    """``context`` holds the test's ``reduced`` (every instruction of
    ``op_names`` one event of 1 ms, two steps); it is left as it was."""
    from chipbench.harness import spec, step_classes

    cells = {step_classes.scope_of(stack)[1] for stack in op_names.values()}
    assert cells - {None} == {f"{i:02d}" for i in range(len(trainer.cells))}, cells
    for scope in ("mpi4dl_optimizer", "mpi4dl_loss"):
        assert sum(scope in stack for stack in op_names.values()) > 3, scope
    mine = dict(context)
    split = step_classes.split(mine)
    chip = mine["reduced"].chips[0]
    events = sum(1 for ev in chip["ops"] if ev.start_ns >= chip["window"][0])
    assert sum(split.values()) == pytest.approx(events / 2)  # classes + unscoped = busy
    read = {name: spec.metric_reader("layer_metrics", name)(mine)
            for name in ("optimizer_ms", "head_loss_ms", "unscoped_ms")}
    assert read["optimizer_ms"] > 1 and read["head_loss_ms"] > 1
    # the made-up trace gives an event to fused instructions and reducers'
    # parameters too, which run as no op and so fall under no scope; of the
    # instructions that stand in the step with a stack of their own, what no
    # scope reaches is the step counter and little else
    table = step_classes.classify(step_classes.step_text(mine))
    unscoped = sum(table.get(ev.op, (step_classes.UNSCOPED,))[0] == step_classes.UNSCOPED
                   for ev in chip["ops"] if ev.start_ns >= chip["window"][0])
    assert read["unscoped_ms"] == pytest.approx(unscoped / 2)
    standing = [table[name][0] for name, stack in op_names.items()
                if name in table and stack.startswith("jit(")]
    assert standing.count(step_classes.UNSCOPED) < 0.05 * len(standing)
    assert read["head_loss_ms"] >= step_classes.ms(mine, ("loss",))
    parent = {k: v for k, v in context.items() if not k.startswith("_step")}
    parent["trainer"] = object()  # a program without ``compiled_step``
    for name in read:
        assert spec.metric_reader("layer_metrics", name)(parent) is None
