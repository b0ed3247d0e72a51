"""Share of its roofline the attention kernels under the block-diffusion
mask reach: the least time the attention cores of one step need on this chip
/ the kernels' measured time. Least, as the configuration's reference counts
it (``reference/sdar.py``): per layer and query head six products (scores and
weighted sum forward; ``dp``, ``dq``, ``dk``, ``dv`` backward) of ``2 x
head_dim`` FLOPs over the ``L (L + B)`` query-key pairs the mask lets
through, at the chip's peak bf16 FLOP/s; or q, k, v, the output and their
cotangents once each way at its HBM bytes/s, whichever is longer (the FLOPs,
by two hundred times). The remat's second forward, the backward's recomputed
scores and the masked part of the blocks on the diagonal are executed and not
counted, so the share cannot pass 100%."""

from chipbench.harness import xtrace
from chipbench.reference import sdar

KERNEL = "mpi4dl_blockdiff_attention"


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    if seconds is None:
        return None
    cell, peaks = context["cell"], context["peaks"]
    least = max(
        sdar.least_attention_flops_per_step(cell.model, cell.traffic) / peaks["bf16_flops_per_s"],
        sdar.least_attention_bytes_per_step(cell.model, cell.traffic) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
