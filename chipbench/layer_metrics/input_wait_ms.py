"""Host milliseconds per step in the input pipeline and on the batch's way
to the chips: the harness's spans around ``next(batch)`` and
``trainer.shard_batch``, mean over the measured window."""


def read(context):
    spans = context["spans"]
    waits = [a + b for a, b in zip(spans["data_next"], spans["shard_batch"])]
    return 1e3 * sum(waits) / len(waits) if waits else None
