"""Device milliseconds per step in the halo exchange and the tile merge
(the program's ``jax.named_scope("mpi4dl_halo")``): the permutes and the
join's all-gathers, and the slices, pads, fills and concatenations under the
scope (``harness/step_classes.py``). First chip, from the device trace."""

from chipbench.harness import step_classes


def read(context):
    return step_classes.ms(context, ("halo",))
