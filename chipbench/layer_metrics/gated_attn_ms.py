"""Device milliseconds per step in the gated softmax attention layers
(projections with the output gate, q/k norms, the partial rotary embedding,
causal attention, the gate): forward, recomputed forward and backward, first
chip. The attention module is the one ``attn_ms`` reads, under the scope its
first model gave it (``lfm2_attention``): this is that reader under the name
the Qwen3-Next cell reports."""

from chipbench.harness import spec

read = spec.metric_reader("layer_metrics", "attn_ms")
