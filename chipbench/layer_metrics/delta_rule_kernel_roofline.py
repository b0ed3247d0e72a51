"""Share of its roofline the gated delta rule's kernels reach: the least
matrix-multiplication FLOPs the rule of one step must do / the chip's peak
bf16 FLOP/s / the kernels' measured time. Least: the recurrence itself, as
``chipbench/reference/qwen3_next.py`` counts it, whatever chunked form
computes it: per position and value head three products (``S^T k``, the
outer product ``k r^T`` and ``S^T q``) of ``2 x linear_key_head_dim x
linear_value_head_dim`` FLOPs, every linear-attention layer (those of
``num_hidden_layers`` that are not every ``full_attention_interval``-th),
every position of the batch, times three for the training step (the
backward's two products for each of the forward's). A chunk's squares, its
system's inverse, the remat's second forward and whatever the backward builds
again are executed and not counted, so the share cannot pass 100%. The
kernels are bound by the matrix unit, not by memory (some 1.6 KB of q, k, v,
out a position and key head against 0.4 MFLOP): the FLOPs are the roofline."""

from chipbench.harness import xtrace

KERNEL = "mpi4dl_delta_rule"


def least_flops_per_step(model: dict, traffic: dict) -> float:
    layers = int(model["num_hidden_layers"])
    linear = layers - layers // int(model["full_attention_interval"])
    per_position = 3 * 2.0 * int(model["linear_key_head_dim"]) * int(
        model["linear_value_head_dim"]) * int(model["linear_num_value_heads"])
    positions = int(traffic["sequence_length"]) * int(traffic["batch_size"])
    return 3 * per_position * linear * positions


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    if seconds is None:
        return None
    cell = context["cell"]
    least = least_flops_per_step(cell.model, cell.traffic)
    return 100.0 * (least / context["peaks"]["bf16_flops_per_s"]) / seconds
