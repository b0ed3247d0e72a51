"""Device milliseconds per step inside collective ops (permute,
all-reduce, all-gather; a -start and its -done each counted for its own
duration), first chip of the cell, from the device trace."""

from chipbench.harness import xtrace


def read(context):
    reduced = context["reduced"]
    if reduced is None:
        return None
    chip = reduced.chips[0]
    if not any(xtrace.is_collective(ev) for ev in chip["ops"]):
        return None
    return 1e3 * xtrace.collective_seconds(chip["ops"], *chip["window"]) / reduced.steps
