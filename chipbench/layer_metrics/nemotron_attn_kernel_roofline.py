"""Share of its roofline the fused attention kernels reach in the Nemotron-H
cell: the least matrix-multiplication FLOPs the attention cores of one step
must do / the chip's peak bf16 FLOP/s / the kernels' measured time. Least,
as ``attn_kernel_roofline`` counts it: per attention layer and query head,
six products (scores and weighted sum forward; ``dp``, ``dq``, ``dk``,
``dv`` backward) of 2 x head_dim FLOPs over the ``S (S + 1) / 2`` score
entries on and below the diagonal. The head dim is the configuration's own
``head_dim`` (128: not ``hidden_size / num_attention_heads``, which is 84
here) and the attention layers are the ``*`` of ``hybrid_override_pattern``
(one of the held nine). The remat's second forward and the backward's
recomputed scores are executed and not counted, so the share cannot pass
100%. The kernels are bound by the matrix unit, not by memory (8 bytes of
q, k, v, out a row and head dim against 2 x 128 x 8192 FLOPs): the FLOPs are
the roofline."""

from chipbench.harness import xtrace

KERNEL = "mpi4dl_attention"


def least_flops_per_step(model: dict, traffic: dict) -> float:
    layers = str(model["hybrid_override_pattern"]).count("*")
    heads = int(model["num_attention_heads"])
    length = int(traffic["sequence_length"])
    entries = length * (length + 1) / 2
    return (int(traffic["batch_size"]) * layers * heads * 6 * 2.0 * int(model["head_dim"])
            * entries)


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    if seconds is None:
        return None
    cell = context["cell"]
    least = least_flops_per_step(cell.model, cell.traffic)
    return 100.0 * (least / context["peaks"]["bf16_flops_per_s"]) / seconds
