"""Device milliseconds per step in attention's core,
``mpi4dl_part_attn_core``: ``causal_attention`` /
``block_diffusion_attention``, the fused kernels' custom calls (by their own
names) with the layout turns the compiler puts around them, or the plain
blocked path (``harness/token_parts.py``). The accepted ``*attn_kernel_ms``
read the kernels alone. First chip, from the device trace. None from a
program without the part scopes."""

from chipbench.harness import token_parts


def read(context):
    return token_parts.ms(context, ("attn_core",))
