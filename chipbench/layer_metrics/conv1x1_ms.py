"""Device milliseconds per step in the 1x1 convolutions and dense layers
(products over pixels; the program's ``jax.named_scope("mpi4dl_conv1x1")``):
forward, data gradient and weight gradient, fused epilogues and the copies
that feed them (``harness/step_classes.py`` rules 1 and 3). First chip, from
the device trace. None from a program without the scope."""

from chipbench.harness import step_classes


def read(context):
    return step_classes.ms(context, ("conv1x1",))
