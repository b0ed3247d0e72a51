"""What the compiled step holds on one chip: arguments + outputs +
temporaries - aliased, from the compiled step's ``memory_analysis()``
(``Trainer.record_memory_footprint``)."""


def read(context):
    footprint = context["footprint"] or {}
    peak = footprint.get("peak_bytes")
    return None if peak is None else peak / 2**30
