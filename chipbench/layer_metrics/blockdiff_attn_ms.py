"""Device milliseconds per step in the attention layers under the
block-diffusion mask (q, k, v and output projections at 8 query heads a
key-value head and head dim 128, q/k norms, the rotary embedding at ``row mod
L``, attention of a noisy copy beside the clean one): forward, recomputed
forward and backward of all held layers, first chip. The layer's own scope,
``blockdiff_attention`` (``ops/sequence.Attention`` with ``diffusion_block``),
joined with the trace through the compiled step's text
(``layer_metrics/blockdiff_scopes.py``). None from a program without it."""

from chipbench.layer_metrics import blockdiff_scopes

SCOPES = ("blockdiff_attention",)


def read(context):
    return blockdiff_scopes.ms_per_step(context, SCOPES)
