"""Device milliseconds per step of a token cell in ops that end with no part
(``harness/token_parts.py``, rule 6): under no ``mpi4dl_part_*`` scope, no
recurrence, not the optimiser, the loss or the head cell, after an
instruction with no part of its own has taken its consumer's. With the parts
it adds up to the trace's busy time. First chip, from the device trace. None
from a program without the part scopes."""

from chipbench.harness import token_parts


def read(context):
    return token_parts.ms(context, (token_parts.UNSCOPED,))
