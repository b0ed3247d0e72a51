"""Device milliseconds per step in what a token model's layer does around
its mixers, ``mpi4dl_part_block`` (opened by ``models/{lfm2,qwen3_next,
nemotron_h,sdar}.py``): the pre-norms, the residual adds, the embedding and
its scatter-add; forward, recomputed forward and backward
(``harness/token_parts.py``). First chip, from the device trace. None from a
program without the part scopes."""

from chipbench.harness import token_parts


def read(context):
    return token_parts.ms(context, ("block",))
