"""Device milliseconds per step in the expert layers' routers,
``mpi4dl_part_router``: the float32 product at precision "highest", the
scores, the bias, ``top_k``, the chosen weights and their scaling; forward,
recomputed forward and backward (``harness/token_parts.py``). First chip,
from the device trace. None from a program without the part scopes."""

from chipbench.harness import token_parts


def read(context):
    return token_parts.ms(context, ("router",))
