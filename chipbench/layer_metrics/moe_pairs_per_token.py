"""Token-expert pairs the held experts computed in the newest step, per
token and per expert layer: the step's own count ``moe_pairs``
(``trainer.last_metrics``; a device scalar the step never reads on the
host) / (tokens in the batch x expert layers). Expected:
``num_experts_per_tok x held / published`` (1.0 for 8 of 32 experts and 4 a
token); it moves with the router's choices, which the FLOP count behind
``mfu_pct`` does not read."""


def read(context):
    metrics = getattr(context["trainer"], "last_metrics", None) or {}
    if "moe_pairs" not in metrics:
        return None
    session = context["session"]
    tokens = 1
    for n in session.x_shape:
        tokens *= n
    expert_layers = sum(kind.startswith("moe_") for kind in session.kinds)
    return float(metrics["moe_pairs"]) / (tokens * expert_layers)
