"""Device milliseconds per step in the causal depthwise convolution's own
kernels' custom calls, found by their names' common start
(``mpi4dl_causal_conv_fwd`` and ``mpi4dl_causal_conv_bwd``; XLA names the
instructions after them): the forward and the backward of every Gated
DeltaNet or Mamba-2 layer's convolution with its bias and SiLU, first chip,
from the device trace. The part of ``tok_conv_ms`` that is the kernels
themselves; the rest of it is what XLA does around the calls (the splits of
the output, the taps' rows, the sums of the taps' gradient). Nothing (the
metric is left out) where no such kernel ran: the parent of the PR that
brought the kernels, or a shape that took the plain path. This is the counter
that says the mechanism engaged."""

from chipbench.harness import xtrace

KERNEL = "mpi4dl_causal_conv"


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    return None if seconds is None else 1e3 * seconds
