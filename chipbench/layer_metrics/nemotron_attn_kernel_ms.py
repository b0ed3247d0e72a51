"""Device milliseconds per step in the fused causal-attention kernels'
custom calls in the Nemotron-H cell (``mpi4dl_attention_fwd`` and
``mpi4dl_attention_bwd``, at head dim 128 and 16 query heads a key-value
head since PR 40): the forward, the remat's forward again and the backward
of the one attention layer, first chip, from the device trace. The part of
``nemotron_attn_ms`` that is the kernels themselves; the rest of it is the
projections and the layout changes around the calls. Nothing (the metric is
left out) where no such kernel ran: the parent of the PR that planned the
kernels for this shape, where the layer took the blocked plain path. This is
the counter that says the mechanism engaged. ``attn_kernel_ms``'s reader
under the name this cell reports."""

from chipbench.harness import spec

read = spec.metric_reader("layer_metrics", "attn_kernel_ms")
