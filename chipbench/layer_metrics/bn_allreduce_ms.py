"""Device milliseconds per step in BatchNorm's cross-tile means: the
collectives under the program's ``jax.named_scope("mpi4dl_batchnorm")``
(``harness/step_classes.py`` rule 4), forward and backward. First chip, from
the device trace."""

from chipbench.harness import step_classes


def read(context):
    return step_classes.ms(context, ("batchnorm",), collectives_only=True)
