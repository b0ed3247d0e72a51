"""Device milliseconds per step in the Mamba-2 mixers (``in_proj``, the
causal depthwise convolution and its SiLU, ``dt`` and the decays, the chunked
state-space scan, ``D x``, the gated group norm, ``out_proj``): forward,
recomputed forward and backward, first chip. The trace's ops are matched to
the program's ``jax.named_scope("mamba2")`` through the compiled step's text
(``harness/scopes.py``, which says what a fusion that spans two scopes
counts under); the scan inside it is two nested loops, whose events lie inside
one another, so the time is the union of the ops' intervals
(``harness/scope_union.py``). None from a program that has no such scope."""

from chipbench.harness import scope_union

SCOPES = ("mamba2",)


def read(context):
    return scope_union.ms_per_step(context, SCOPES)
