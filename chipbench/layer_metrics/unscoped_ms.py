"""Device milliseconds per step in ops that no scope of the program reaches:
neither a model cell (``mpi4dl_cell<NN>``) nor an operator class, the
optimiser, the loss or the gradients' sum, after an instruction the compiler
made has taken its consumer's scope (``harness/step_classes.py``, rules 3 and
5). What the program's spans cannot see yet; first chip, from the device
trace. None from a program without the scopes."""

from chipbench.harness import step_classes


def read(context):
    if step_classes.split(context) is None:
        return None
    return step_classes.ms(context, (step_classes.UNSCOPED,)) or 0.0
