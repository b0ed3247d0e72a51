"""Device milliseconds per step in BatchNorm (moments, normalisation and
their backward, under the program's ``jax.named_scope("mpi4dl_batchnorm")``;
under spatial parallelism also the cross-tile means' all-reduces, which
``bn_allreduce_ms`` reads alone) where the chip did not fuse it into a
convolution (``harness/step_classes.py`` rule 1). First chip, from the
device trace. None from a program without the scope."""

from chipbench.harness import step_classes


def read(context):
    return step_classes.ms(context, ("batchnorm",))
