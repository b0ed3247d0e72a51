"""Device milliseconds per step in the causal depthwise convolutions of a
token cell, ``mpi4dl_part_conv``: ``causal_depthwise_conv1d`` (pad and one
shifted multiply-add a tap) with its bias and SiLU in Mamba-2 and Gated
DeltaNet, with its two gates in LFM2's short convolution; forward, recomputed
forward and backward (``harness/token_parts.py``). It is the time of the ops
that are the convolution's **alone**: a fusion that holds a ``dot`` is the
dot's part (rule 3), so where the compiler fuses the taps and gates into the
mixer's projections that time is in ``tok_proj_ms`` and not here. On
``lfm2_8b_a1b_share4_seq8k_bs1`` that is nearly all of it: this reads 2.2 ms
where the whole ``lfm2_shortconv`` mixer (``shortconv_ms``) reads 49.7, the
rest being ``lfm2_shortconv`` x ``proj`` in ``tools/token_table.py``'s mixer x
part table (my chip run, PR 45). In Mamba-2 and Gated DeltaNet the convolution
runs in fusions of its own and this is its time. First chip, from the device
trace. None from a program without the part scopes."""

from chipbench.harness import token_parts


def read(context):
    return token_parts.ms(context, ("conv",))
