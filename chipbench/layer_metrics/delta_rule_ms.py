"""Device milliseconds per step in the chunked gated delta rule alone (the
decays, a chunk's triangular system and its inverse, the WY products, the
state handed from chunk to chunk, the outputs), without the mixer's
projections and convolution: the part of ``linear_attn_ms`` under
``jax.named_scope("gated_delta_rule")``; forward, recomputed forward and
backward, first chip (``harness/scopes.py``)."""

from chipbench.harness import scopes

SCOPES = ("gated_delta_rule",)


def read(context):
    return scopes.ms_per_step(context, SCOPES)
