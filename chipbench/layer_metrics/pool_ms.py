"""Device milliseconds per step in the pools (max and average, forward and
backward, under the program's ``jax.named_scope("mpi4dl_pool")``):
``reduce_window``, ``select_and_scatter``, the shifted-maximum tree, the pads
around them and the ``mpi4dl_pool_bwd`` kernel, which ``pool_bwd_ms`` reads
alone (``harness/step_classes.py``). First chip, from the device trace. None
from a program without the scope."""

from chipbench.harness import step_classes


def read(context):
    return step_classes.ms(context, ("pool",))
