"""Device milliseconds per step in the attention layer (q, k, v and output
projections at 16 query heads a key-value head and head dim 128, no rotary
embedding and no q/k norm, blocked causal softmax attention): forward,
recomputed forward and backward, first chip. The attention module is the one
``attn_ms`` reads, under the scope its first model gave it
(``lfm2_attention``): this is that reader under the name the Nemotron-H cell
reports."""

from chipbench.harness import spec

read = spec.metric_reader("layer_metrics", "attn_ms")
