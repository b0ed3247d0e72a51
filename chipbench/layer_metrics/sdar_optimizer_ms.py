"""Device milliseconds per step in the SDAR cell's optimiser pass (momentum
update and ``apply_updates`` over 834.9M parameters, under
``mpi4dl_optimizer``, with the copies and casts that feed it):
``optimizer_ms``' reading on the step the block-diffusion labels compile
(``layer_metrics/blockdiff_scopes.py``). Its floor is memory: 20 bytes a
parameter, 16.7 GB a step. First chip, from the device trace. None from a
program without the scope."""

from chipbench.layer_metrics import blockdiff_scopes


def read(context):
    return blockdiff_scopes.class_ms(context, ("optimizer",))
