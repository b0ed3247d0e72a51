"""Device milliseconds per step in a mixer's gates and norms,
``mpi4dl_part_gates_norms``: Gated DeltaNet's ``beta``, ``g``, the L2 norms of
q and k and the per-head ``RMSNorm(o) * silu(z)``; Mamba-2's ``dt`` softplus,
decays, ``dt x`` and ``_gated_group_norm`` with ``D x``; attention's output
gate; float32 elementwise passes all, forward, recomputed forward and
backward (``harness/token_parts.py``). First chip, from the device trace.
None from a program without the part scopes."""

from chipbench.harness import token_parts


def read(context):
    return token_parts.ms(context, ("gates_norms",))
