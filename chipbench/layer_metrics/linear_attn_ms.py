"""Device milliseconds per step in the Gated DeltaNet mixers (projections,
the causal depthwise convolution, the gates, the chunked delta rule, the
gated output norm, out_proj): forward, recomputed forward and backward,
first chip. The trace's ops are matched to the program's
``jax.named_scope("gated_delta")`` through the compiled step's text
(``harness/scopes.py``, which says what a fusion that spans two scopes
counts under). None from a program that has no such scope."""

from chipbench.harness import scopes

SCOPES = ("gated_delta",)


def read(context):
    return scopes.ms_per_step(context, SCOPES)
