"""Expert layers of the newest step that computed the prefix of their sorted
token-expert pair rows alone: the step's own count ``moe_narrow_layers``
(``trainer.last_metrics``; a device scalar the step never reads on the
host). An expert layer computes the rows past the prefix only when its own
count of held pairs overflows it; with even routing every expert layer
stays narrow, and the count is the number of expert layers. None from a
program whose step does not count it."""


def read(context):
    metrics = getattr(context["trainer"], "last_metrics", None) or {}
    if "moe_narrow_layers" not in metrics:
        return None
    return float(metrics["moe_narrow_layers"])
