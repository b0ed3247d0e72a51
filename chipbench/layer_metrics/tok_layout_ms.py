"""Device milliseconds per step in the compiler's layout turns inside a token
cell's model cells, ``harness/token_parts.py``'s rule 5 as it stands: every
``copy`` and ``copy-start`` / ``-done`` **whatever its own name stack says**
(no line of the program lowers to one, and most of the compiler's copies keep
the stack of the op they were cut from: 164-376 of a step's copies carry a
part of their own), a ``transpose`` or ``bitcast-convert`` only where its own
stack holds no part (the program writes those too), and a fusion of nothing
but such ops. Whatever part they carry or inherit from the op they feed, this
is time that is inside the ``tok_*_ms`` of the parts, not beside them. First
chip, from the device trace. None from a program without the part scopes."""

from chipbench.harness import token_parts


def read(context):
    return token_parts.ms(context, layout_only=True, cells_only=True)
