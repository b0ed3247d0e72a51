"""Device milliseconds per step in the attention layers (projections, q/k
norms, rotary embedding, blocked causal softmax attention): forward,
recomputed forward and backward, first chip. The trace's ops are matched to
the program's ``jax.named_scope`` through the compiled step's text
(``harness/scopes.py``, which says what a fusion that spans two scopes
counts under)."""

from chipbench.harness import scopes

SCOPES = ("lfm2_attention",)


def read(context):
    return scopes.ms_per_step(context, SCOPES)
