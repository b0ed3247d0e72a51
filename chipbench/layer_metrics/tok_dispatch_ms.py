"""Device milliseconds per step in the expert layers' bookkeeping,
``mpi4dl_part_dispatch``: ``group``, ``sizes``, the sort and ``argsort``, the
rows' gathers (``_token_rows``), the weighted sums by token (``_token_sums``,
``_sum_by_token``, ``_in_token_order``, ``_pair_weights``), their backward
rules and the two-range conditionals' own adds and selects: everything of
``ExpertFFN`` that is neither the router nor a product
(``harness/token_parts.py``). First chip, from the device trace. None from a
program without the part scopes."""

from chipbench.harness import token_parts


def read(context):
    return token_parts.ms(context, ("dispatch",))
