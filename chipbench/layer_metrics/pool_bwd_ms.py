"""Device milliseconds per step in the Pallas kernel ``mpi4dl_pool_bwd``
(the stride-1 3x3 max pool's backward), first chip, from the device
trace."""

from chipbench.harness import xtrace

KERNEL = "mpi4dl_pool_bwd"


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    return None if seconds is None else 1e3 * seconds
