"""Device milliseconds per step in the fused attention kernels under the
block-diffusion mask, found by their own name
(``mpi4dl_blockdiff_attention_fwd`` and ``_bwd``; XLA names the instructions
after them): the forward, the remat's forward again and the backward of every
held layer, first chip, from the device trace. The part of
``blockdiff_attn_ms`` that is the kernels themselves. Nothing (the metric is
left out) where no such kernel ran: a program without them, or a shape that
took the plain path. This is the counter that says the mechanism engaged."""

from chipbench.harness import xtrace

KERNEL = "mpi4dl_blockdiff_attention"


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    return None if seconds is None else 1e3 * seconds
