"""Device milliseconds per step of the SDAR cell in ops that no scope of the
program reaches (neither a model cell, the optimiser nor the loss, after an
instruction the compiler made has taken its consumer's scope):
``unscoped_ms``' reading on the step the block-diffusion labels compile
(``layer_metrics/blockdiff_scopes.py``). With the cells' times, the
optimiser's and the loss's it adds up to the trace's busy time; what the
program's spans cannot see. First chip, from the device trace. None from a
program without the scopes."""

from chipbench.harness import step_classes
from chipbench.layer_metrics import blockdiff_scopes


def read(context):
    if blockdiff_scopes.class_ms(context) is None:
        return None
    return blockdiff_scopes.class_ms(context, (step_classes.UNSCOPED,)) or 0.0
