"""Device milliseconds per step in the k x k convolutions (3x3, 1x7, 7x1;
the program's ``jax.named_scope("mpi4dl_convkxk")``): forward, data gradient
and weight gradient, the epilogues the chip fuses into them (a fusion that
holds a convolution is the convolution's, ``harness/step_classes.py`` rule 1)
and the copies, casts and slices that feed them (rule 3). First chip, from
the device trace. None from a program without the scope."""

from chipbench.harness import step_classes


def read(context):
    return step_classes.ms(context, ("convkxk",))
