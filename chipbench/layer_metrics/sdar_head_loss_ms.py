"""Device milliseconds per step in the SDAR cell's last cell (the noisy
copy's 8,192 rows, the final RMSNorm and the head over the vocabulary's
slice: forward, recomputed forward and backward, under ``mpi4dl_cell<NN>``
with the highest index) plus the model's own loss (the weighted cross-entropy
of ``models/sdar.block_diffusion_loss``, under ``mpi4dl_loss``):
``head_loss_ms``' reading (``harness/step_classes.py`` says which op counts
where) on the step the block-diffusion labels compile, which
``layer_metrics/blockdiff_scopes.py`` finds. First chip, from the device
trace. None from a program without the scopes."""

from chipbench.harness import step_classes
from chipbench.layer_metrics import blockdiff_scopes


def read(context):
    head = blockdiff_scopes.class_ms(context, cell=step_classes.head_cell(context))
    loss = blockdiff_scopes.class_ms(context, ("loss",))
    return None if head is None and loss is None else (head or 0.0) + (loss or 0.0)
