"""Device milliseconds per step in Mamba-2's chunked scan alone (the running
sums and decays of a chunk, ``(C B^T) * L`` and its product with the chunk's
inputs, the chunks' own states, the state handed from chunk to chunk, what
the state a chunk starts from adds), without the mixer's projections,
convolution and norm: the part of ``mamba_mixer_ms`` under
``jax.named_scope("ssd_scan")``; forward, recomputed forward and backward,
first chip; the union of the ops' intervals, because the scan is a loop over
the sequences around a loop over the chunks and a loop's event spans its
body's (``harness/scope_union.py``)."""

from chipbench.harness import scope_union

SCOPES = ("ssd_scan",)


def read(context):
    return scope_union.ms_per_step(context, SCOPES)
