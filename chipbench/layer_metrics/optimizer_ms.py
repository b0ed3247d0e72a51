"""Device milliseconds per step in the optimiser pass (``optax``'s momentum
update and ``apply_updates`` over every parameter, under the program's
``jax.named_scope("mpi4dl_optimizer")``), with the copies and casts that feed
it (``harness/step_classes.py``); first chip, from the device trace. Its
floor is memory: 20 bytes a parameter (read weight, gradient and momentum,
write weight and momentum, float32). None from a program without the scope."""

from chipbench.harness import step_classes


def read(context):
    return step_classes.ms(context, ("optimizer",))
