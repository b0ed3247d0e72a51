"""Device milliseconds per step in the expert layers with their shared
expert (router, sigmoid, top-k, sort of the token-expert pairs, the two
grouped products of a squared-ReLU expert, combine, the ungated shared
expert): forward, recomputed forward and backward, first chip. The expert
layer is the module ``moe_ms`` reads, under the scope its first model gave it
(``lfm2_moe``), its grouped products counted by their own name as there:
this is that reader under the name the Nemotron-H cell reports."""

from chipbench.harness import spec

read = spec.metric_reader("layer_metrics", "moe_ms")
