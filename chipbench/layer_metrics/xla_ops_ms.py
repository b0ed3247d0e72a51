"""Device milliseconds per step in ops XLA generated itself: neither a
collective nor a custom call (the convolutions, BatchNorm, elementwise
passes, ``select_and_scatter``), first chip, from the device trace."""

from chipbench.harness import xtrace


def read(context):
    reduced = context["reduced"]
    if reduced is None:
        return None
    chip = reduced.chips[0]
    own = [ev for ev in chip["ops"]
           if not xtrace.is_collective(ev) and not xtrace.is_custom_call(ev)]
    return 1e3 * xtrace.union_seconds(xtrace.clip(own, *chip["window"])) / reduced.steps
