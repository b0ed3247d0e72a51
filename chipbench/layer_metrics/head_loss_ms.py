"""Device milliseconds per step in the model's last cell (the classifier of
an image model, the language-model head of a token model; forward, recomputed
forward and backward, under ``mpi4dl_cell<NN>`` with the highest index) plus
the loss (softmax cross-entropy, accuracy and their sums over the mesh, under
``mpi4dl_loss``); ``harness/step_classes.py`` says which op counts where.
First chip, from the device trace. None from a program without the scopes."""

from chipbench.harness import step_classes


def read(context):
    if step_classes.split(context) is None:
        return None
    head = step_classes.ms(context, cell=step_classes.head_cell(context))
    loss = step_classes.ms(context, ("loss",))
    return None if head is None and loss is None else (head or 0.0) + (loss or 0.0)
