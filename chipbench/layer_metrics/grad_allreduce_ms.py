"""Device milliseconds per step in the gradients' sum over the mesh: the
collectives whose name stack holds no model cell and no class
(``jit(_train_step)/transpose(jvp())/shard_map/psum``; ``harness/
step_classes.py`` rule 4), a ``-start`` and its ``-done`` each for its own
duration as ``collective_ms`` counts them. First chip, from the device trace."""

from chipbench.harness import step_classes


def read(context):
    return step_classes.ms(
        context, (step_classes.GRAD_ALLREDUCE,), collectives_only=True)
