"""Share of its roofline Mamba-2's scan's kernels reach: the least
matrix-multiplication FLOPs the recurrence of one step must do / the chip's
peak bf16 FLOP/s / the kernels' measured time. Least: the recurrence itself,
as ``chipbench/reference/nemotron_h.py`` counts it, whatever chunked form
computes it: per position and head three products (the decay of the state,
``dt x B^T`` added to it, ``S C`` read from it) of ``2 x mamba_head_dim x
ssm_state_size`` FLOPs, every Mamba-2 layer (the ``M``s of
``hybrid_override_pattern``), every position of the batch, times three for
the training step (the backward's two products for each of the forward's).
A chunk's squares, the remat's second forward and whatever the backward
builds again are executed and not counted, so the share cannot pass 100%.
The kernels are bound by the vector and matrix units, not by memory (some
2.6 KB of x, B, C, out a position and group against 0.8 MFLOP): the FLOPs
are the roofline."""

from chipbench.harness import xtrace

KERNEL = "mpi4dl_ssd_scan"


def least_flops_per_step(model: dict, traffic: dict) -> float:
    layers = str(model["hybrid_override_pattern"]).count("M")
    per_position = 3 * 2.0 * int(model["mamba_num_heads"]) * int(
        model["mamba_head_dim"]) * int(model["ssm_state_size"])
    positions = int(traffic["sequence_length"]) * int(traffic["batch_size"])
    return 3 * per_position * layers * positions


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    if seconds is None:
        return None
    cell = context["cell"]
    least = least_flops_per_step(cell.model, cell.traffic)
    return 100.0 * (least / context["peaks"]["bf16_flops_per_s"]) / seconds
