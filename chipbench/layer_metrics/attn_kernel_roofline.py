"""Share of its roofline the fused attention kernels reach: the least
matrix-multiplication FLOPs the attention cores of one step must do / the
chip's peak bf16 FLOP/s / the kernels' measured time. Least: per attention
layer and head, six products (scores and weighted sum forward; ``dp``,
``dq``, ``dk``, ``dv`` backward) of 2 x head_dim FLOPs over the
``S (S + 1) / 2`` score entries on and below the diagonal, from the
configuration's own fields and the traffic's sequence length and batch. The
remat's second forward and the backward's recomputed scores are executed
and not counted, so the share cannot pass 100%. The kernels are bound by the
matrix unit, not by memory (8 bytes of q, k, v, out a row against 2 x 64 x
8192 FLOPs): the FLOPs are the roofline."""

from chipbench.harness import xtrace

KERNEL = "mpi4dl_attention"


def least_flops_per_step(model: dict, traffic: dict) -> float:
    layers = sum(t == "full_attention" for t in model["layer_types"])
    heads = int(model["num_attention_heads"])
    head_dim = int(model["hidden_size"]) // heads
    length = int(traffic["sequence_length"])
    entries = length * (length + 1) / 2
    return int(traffic["batch_size"]) * layers * heads * 6 * 2.0 * head_dim * entries


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    if seconds is None:
        return None
    cell = context["cell"]
    least = least_flops_per_step(cell.model, cell.traffic)
    return 100.0 * (least / context["peaks"]["bf16_flops_per_s"]) / seconds
