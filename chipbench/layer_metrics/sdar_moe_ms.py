"""Device milliseconds per step in the SDAR cell's expert layers (float32
router over 128 experts, softmax, top-8, sort of the token-expert pairs of
both copies' 16,384 rows, the three grouped products of a SwiGLU expert,
combine; no shared expert): forward, recomputed forward and backward of all
held layers, first chip. The layer's own scope, ``sdar_moe``
(``models/sdar.SDARExperts``), and the grouped products by their own name as
``moe_ms`` counts them (the chip's compiler renames ``jax.lax.ragged_dot``'s
custom calls to ``ragged-dot-*``). None from a program without the scope."""

from chipbench.layer_metrics import blockdiff_scopes

SCOPES = ("sdar_moe", "ragged-dot")


def read(context):
    return blockdiff_scopes.ms_per_step(context, SCOPES)
