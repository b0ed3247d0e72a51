"""Share (%) of the ``L`` positions of the newest step's sequences that
carried loss: the step's own count ``loss_positions`` (the model's loss
counts the positions whose weight is not 0; ``trainer.last_metrics``, a
device scalar the step never reads on the host) / (sequences in the batch x
the traffic's sequence length). The traffic's own check: the stream masks
each position with probability ``t``, ``t`` uniform over ``[t_min, 1]`` a
sequence, so a step reads about ``100 t`` and the steps 50 on average; 0 or
100 on every step says the labels' weights did not arrive. None from a
program whose step does not count it."""


def read(context):
    metrics = getattr(context["trainer"], "last_metrics", None) or {}
    if "loss_positions" not in metrics:
        return None
    cell = context["cell"]
    positions = int(cell.traffic["batch_size"]) * int(cell.traffic["sequence_length"])
    return 100.0 * float(metrics["loss_positions"]) / positions
