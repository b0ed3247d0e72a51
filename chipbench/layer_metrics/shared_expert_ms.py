"""Device milliseconds per step in the shared experts alone (the SwiGLU every
token takes and its sigmoid gate): the part of ``sparse_moe_ms`` under
``jax.named_scope("shared_expert")``; forward, recomputed forward and
backward, first chip (``harness/scopes.py``). None from a program whose
expert layer has no such scope."""

from chipbench.harness import scopes

SCOPES = ("shared_expert",)


def read(context):
    return scopes.ms_per_step(context, SCOPES)
