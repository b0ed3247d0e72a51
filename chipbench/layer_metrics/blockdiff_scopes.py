"""Device time under the scopes of a program whose labels carry weights.

``harness/scopes.py`` and ``harness/step_classes.py`` join the trace's ops
with the compiled step's name stacks, and ask the trainer for that step on
labels shaped as the logits without their last axis. The block-diffusion
step's labels are ``[N, L, 2]`` (a token and its weight's bits a position:
``reference/sdar.py``, ``loss``), so such a question would compile another
step, whose instruction names are not the traced one's. This asks with the
labels the window's loop gave the step (the trainer then hands back the step
it already compiled) and leaves the text where those two modules look for
it; then it is their readers. A program without the scopes, without
``compiled_step`` or under plain labels reads as it does there: nothing, and
no error.

This reaches into names of the two harness modules that are theirs alone
(PERF.md section 7 asks a ``benchmark`` PR to let ``scopes._step_arguments``
take the family's label spec, after which this file goes). Until then a
rename there must not turn these metrics silent: the import fails instead.
"""

from chipbench.harness import scopes, step_classes

for _module, _names in ((scopes, ("_KEY", "_INSTRUCTION", "_step_arguments", "ms_per_step")),
                        (step_classes, ("_TEXT", "ms", "split", "head_cell", "UNSCOPED"))):
    for _name in _names:
        assert hasattr(_module, _name), (
            f"{_module.__name__}.{_name} is gone: layer_metrics/blockdiff_scopes.py "
            "reads the compiled step through it")


def _place_step_text(context) -> bool:
    """The traced step's text under both harness modules' keys; False from
    a program without ``compiled_step``."""
    if scopes._KEY in context and step_classes._TEXT in context:
        return True
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    trainer, session = context["trainer"], context["session"]
    compiled_step = getattr(trainer, "compiled_step", None)
    if compiled_step is None:
        return False
    state, x, _ = scopes._step_arguments(context)
    length = int(context["cell"].traffic["sequence_length"])
    y = jax.ShapeDtypeStruct(
        (session.batch, length, 2), jnp.int32,
        sharding=NamedSharding(trainer.mesh, trainer.y_spec))
    text = compiled_step(state, x, y).as_text()
    context[scopes._KEY] = dict(scopes._INSTRUCTION.findall(text))
    context[step_classes._TEXT] = text
    return True


def ms_per_step(context, names):
    """``harness/scopes.ms_per_step`` on the traced step."""
    if context["reduced"] is None or not _place_step_text(context):
        return None
    return scopes.ms_per_step(context, names)


def class_ms(context, classes=None, cell=None):
    """``harness/step_classes.ms`` on the traced step: the milliseconds a
    step of the given classes and cell, every nanosecond counted once."""
    if context["reduced"] is None or not _place_step_text(context):
        return None
    if step_classes.split(context) is None:
        return None
    return step_classes.ms(context, classes, cell)
