"""Device milliseconds per step in Mamba-2's scan's own kernels' custom
calls, found by their names' common start (``mpi4dl_ssd_scan_fwd`` and
``mpi4dl_ssd_scan_bwd``; XLA names the instructions after them): the forward,
the remat's forward again and the backward of every Mamba-2 layer, first
chip, from the device trace. The part of ``ssd_scan_ms`` that is the kernels
themselves; the rest of it is what XLA does around the calls (the running
sums of ``g``, the rows' layout, casts). Nothing (the metric is left out)
where no such kernel ran: the parent of the PR that brought the kernels, or
a shape that took the plain path. This is the counter that says the
mechanism engaged."""

from chipbench.harness import xtrace

KERNEL = "mpi4dl_ssd_scan"


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    return None if seconds is None else 1e3 * seconds
