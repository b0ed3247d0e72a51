"""Device milliseconds per step in the dense projections of a token cell's
mixers, ``mpi4dl_part_proj`` (``in_proj_qkvz`` / ``in_proj_ba``, Mamba-2's
``in_proj`` with ``dt``'s columns, ``q_proj`` / ``k_proj`` / ``v_proj``, every
``out_proj``, the short convolution's two, a dense layer's SwiGLU and the
shared expert, each with its weight's cast): forward, recomputed forward and
both gradients, the compiler's copies that feed them included, and whatever
else the compiler fused into a projection's ``dot`` (rule 3 of
``harness/token_parts.py``, whose docstring carries the rules: LFM2's short
convolution's taps and gates ride there, see ``tok_conv_ms.py``). First chip,
from the device trace. None from a program without the part scopes."""

from chipbench.harness import token_parts


def read(context):
    return token_parts.ms(context, ("proj",))
