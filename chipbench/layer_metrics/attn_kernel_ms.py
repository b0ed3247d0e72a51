"""Device milliseconds per step in the fused causal-attention kernels'
custom calls, found by their own name (``mpi4dl_attention_fwd`` and
``mpi4dl_attention_bwd``; XLA names the instructions after them): the
forward, the remat's forward again and the backward of every attention
layer, first chip, from the device trace. Nothing (the metric is left out)
where no such kernel ran: the parent of the PR that brought the kernels, or
a shape that took the plain path."""

from chipbench.harness import xtrace

KERNEL = "mpi4dl_attention"


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    return None if seconds is None else 1e3 * seconds
