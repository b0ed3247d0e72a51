"""Device milliseconds per step between attention's projections and its
core, ``mpi4dl_part_qk_prep``: the q/k head norms, the rotary embedding, the
output gate's split, the reshapes and casts into the kernels' operands;
forward, recomputed forward and backward (``harness/token_parts.py``). First
chip, from the device trace. None from a program without the part scopes."""

from chipbench.harness import token_parts


def read(context):
    return token_parts.ms(context, ("qk_prep",))
