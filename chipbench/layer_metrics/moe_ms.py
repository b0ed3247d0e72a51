"""Device milliseconds per step in the expert layers (router, top-k, sort
of the token-expert pairs, grouped products, combine): forward, recomputed
forward and backward, first chip. The trace's ops are matched to the
program's ``jax.named_scope`` through the compiled step's text
(``harness/scopes.py``, which says what a fusion that spans two scopes
counts under). The grouped products are counted by their own name as well:
the chip's compiler turns ``jax.lax.ragged_dot`` into custom calls named
``ragged-dot-*`` whose ``op_name`` it rewrites to that name (read off the
compiled step, PR 31); nothing else in this model is a ragged dot."""

from chipbench.harness import scopes

SCOPES = ("lfm2_moe", "ragged-dot")


def read(context):
    return scopes.ms_per_step(context, SCOPES)
