"""Device milliseconds per step in the held experts' grouped products,
``mpi4dl_part_expert_products``: ``_grouped_ffn`` (the ``ragged-dot-*`` custom
calls by their own name, the activation between them), ``_whole_tiles``'
padding and the expert arrays' casts and transposes; forward, recomputed
forward and both gradients (``harness/token_parts.py``). First chip, from the
device trace. None from a program without the part scopes."""

from chipbench.harness import token_parts


def read(context):
    return token_parts.ms(context, ("expert_products",))
