"""Every op event of a token cell's traced window under one model cell, one
mixer and one part of it: the step's device time split by the second level of
the program's ``jax.named_scope``s.

Inside the scopes the token models' mixers have had since their first PR
(``mamba2``, ``gated_delta``, ``lfm2_attention``, ``blockdiff_attention``,
``lfm2_shortconv``, ``lfm2_moe``, ``shared_expert``: here a **mixer** is the
innermost of them on an op's name stack) ``mpi4dl_tpu/ops/sequence.py`` and
``mpi4dl_tpu/models/{lfm2,qwen3_next,nemotron_h,sdar}.py`` name the **part**
every line belongs to: ``mpi4dl_part_proj`` (the dense projections into and
out of a mixer, a dense or shared feed-forward), ``mpi4dl_part_conv`` (the
causal depthwise convolution with its bias, SiLU and gates),
``mpi4dl_part_gates_norms``, ``mpi4dl_part_qk_prep``, ``mpi4dl_part_attn_core``,
``mpi4dl_part_router``, ``mpi4dl_part_dispatch``,
``mpi4dl_part_expert_products``, ``mpi4dl_part_block`` (a layer's pre-norms and
residual adds, the embedding) and, under the names they had, the recurrences
``ssd_scan`` and ``gated_delta_rule`` (part ``recurrence``). Beside them stand
the step's own ``mpi4dl_optimizer`` and ``mpi4dl_loss`` and the head cell
(the last ``mpi4dl_cell<NN>``), part ``head``. Scopes are compared as whole
words of a name stack, so ``gated_delta`` is not ``gated_delta_rule``.

The join with the trace is ``step_classes.py``'s (the chip's trace names an
op by its HLO instruction, the compiled step's text gives the instruction its
name stack), under this file's table of names:

1. A part is the **innermost** part scope of a stack: the grouped products
   open ``mpi4dl_part_expert_products`` inside the ``mpi4dl_part_dispatch``
   that calls them.
2. **What an instruction is called outweighs its stack**: the chip's compiler
   renames ``jax.lax.ragged_dot``'s custom calls ``ragged-dot-*`` and cuts
   their stack at the enclosing ``jit`` (where it still says ``dispatch``);
   they are ``expert_products``. A Pallas kernel's custom call is its
   recurrence's (``mpi4dl_ssd_scan*``, ``mpi4dl_delta_rule*``) or
   ``attn_core``'s (``mpi4dl_attention*``, ``mpi4dl_blockdiff_attention*``).
3. **A fusion** is the cell, mixer and part of the ``dot`` or ``convolution``
   fused into it whatever its epilogue, else of most of its instructions.
4. **An instruction with no part of its own** (what the compiler made or
   rewrote: a ``copy``, ``transpose``, ``bitcast``, ``slice``, ``convert``, an
   asynchronous pair; the casts of the parameters before the first cell)
   **takes its consumer's cell, mixer and part, else its operand's
   producer's**, an asynchronous ``-done`` asking its ``-start`` first; one
   left over in the body of a ``while`` or the branch of a ``conditional``
   takes its caller's. It keeps a cell or a mixer its own stack names.
5. Beside its part an instruction is **layout** or not: a ``copy`` or a
   ``copy-start`` / ``-done`` always (no line of the program lowers to one;
   most of the compiler's copies keep the stack of the op they were cut
   from), a ``transpose`` or ``bitcast-convert`` whose own stack holds no
   part, and a fusion of nothing else. That column is the compiler's layout
   turns, whatever part they carry or inherit.
6. An op that ends with no part is ``unscoped``.

Times are the first chip's, per step, every nanosecond given to the innermost
event that covers it (``step_classes.innermost_seconds``): a ``while`` counts
once, and the parts, ``optimizer``, ``loss``, ``head`` and ``unscoped`` add up
to the trace's busy time. A program without the part scopes (any tree before
PR 45), a run that was not traced and a program without ``compiled_step``
read None everywhere.

The least work the two shares of the peak are taken against is counted in
their readers' own files (``layer_metrics/tok_proj_roofline.py``,
``tok_expert_products_roofline.py``).
"""

from __future__ import annotations

import collections
import re

from . import step_classes

PART_SCOPES = {
    "mpi4dl_part_proj": "proj",
    "mpi4dl_part_conv": "conv",
    "mpi4dl_part_gates_norms": "gates_norms",
    "ssd_scan": "recurrence",
    "gated_delta_rule": "recurrence",
    "mpi4dl_part_qk_prep": "qk_prep",
    "mpi4dl_part_attn_core": "attn_core",
    "mpi4dl_part_router": "router",
    "mpi4dl_part_dispatch": "dispatch",
    "mpi4dl_part_expert_products": "expert_products",
    "mpi4dl_part_block": "block",
    "mpi4dl_optimizer": "optimizer",
    "mpi4dl_loss": "loss",
}
# rule 2: by the instruction's own name, the longer name first
NAMED_PARTS = (
    ("ragged-dot", "expert_products"),
    ("mpi4dl_blockdiff_attention", "attn_core"),
    ("mpi4dl_attention", "attn_core"),
    ("mpi4dl_delta_rule", "recurrence"),
    ("mpi4dl_ssd_scan", "recurrence"),
)
MIXER_SCOPES = ("mamba2", "gated_delta", "lfm2_attention", "blockdiff_attention",
                "lfm2_shortconv", "lfm2_moe", "shared_expert")
HEAD, UNSCOPED = "head", "unscoped"
PARTS = tuple(dict.fromkeys(PART_SCOPES.values())) + (HEAD, UNSCOPED)
_STEP = ("optimizer", "loss")  # the step's own: they lie in no cell

_WORD = re.compile(r"[A-Za-z0-9_]+")
_CELL = re.compile(r"^mpi4dl_cells?(\d\d(?:to\d\d)?)$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_CALLS_ONE = re.compile(
    r"\b(?:body|condition|true_computation|false_computation|calls)=%?([\w.\-]+)")
_CALLS_MANY = re.compile(r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_HEAVY = ("dot", "convolution")
_NO_OPS = ("parameter", "constant")
_COPIES = ("copy", "copy-start", "copy-done")  # no line of the program lowers to one
_TURNS = ("transpose", "bitcast-convert")
_FREE = ("bitcast", "parameter", "constant", "tuple", "get-tuple-element")
_TEXT, _SPLIT = "_token_parts_text", "_token_parts_split"

Found = collections.namedtuple("Found", "cell mixer part layout")


def scope_of(op_name: str, own_name: str = ""):
    """``(cell, mixer, part)`` a name stack and an instruction's own name
    say, each None where they say nothing: the last cell's two digits, the
    innermost mixer scope, the part by rule 2 else rule 1."""
    cell = mixer = part = None
    for word in _WORD.findall(op_name):
        if word in PART_SCOPES:
            part = PART_SCOPES[word]
        elif word in MIXER_SCOPES:
            mixer = word
        else:
            found = _CELL.match(word)
            if found:
                cell = found.group(1)
    named = next((p for name, p in NAMED_PARTS if name in own_name), None)
    return cell, mixer, named or part


def _own(op_name, own_name, head):
    """:func:`scope_of`, and the head cell's ops without a part are ``head``'s."""
    cell, mixer, part = scope_of(op_name, own_name)
    if part is None and cell is not None and cell == head:
        part = HEAD
    return cell, mixer, part


def _fused(instructions, head):
    """Rule 3: ``(cell, mixer, part)`` of a fused computation."""
    for ins in instructions:
        if ins.opcode in _HEAVY:
            found = _own(ins.op_name, "", head)
            if found[2] is not None:
                return found
    votes = collections.Counter(
        _own(ins.op_name, "", head) for ins in instructions
        if ins.op_name and ins.opcode not in _NO_OPS)
    votes.pop((None, None, None), None)
    return votes.most_common(1)[0][0] if votes else (None, None, None)


def _is_layout(ins, computations, own_part) -> bool:
    """Rule 5."""
    opcodes = [ins.opcode] if ins.opcode != "fusion" else [
        i.opcode for i in computations.get(ins.calls, ()) if i.opcode not in _FREE]
    if not opcodes:
        return False
    if all(op in _COPIES for op in opcodes):
        return True
    return own_part is None and all(op in _COPIES + _TURNS for op in opcodes)


def _callers(text: str) -> dict:
    """``{computation: the instruction whose body, condition, branch or
    callee it is}``."""
    out = {}
    for line in text.splitlines():
        found = _INSTRUCTION.match(line)
        if found is None:
            continue
        called = _CALLS_ONE.findall(line)
        for many in _CALLS_MANY.findall(line):
            called += re.findall(r"[\w.\-]+", many)
        for computation in called:
            out.setdefault(computation, found.group(1))
    return out


def _merge(own, got):
    """What an instruction whose ``own`` cell, mixer and part lack something
    takes from a neighbour's ``got``: what it lacks; a neighbour in another
    cell hands on no mixer."""
    cell, mixer, part = own
    if mixer is None and cell in (None, got[0]):
        mixer = got[1]
    return cell or got[0], mixer, part or got[2]


def _whole(found) -> bool:
    """Nothing left to inherit: a part, and a cell unless the part is the
    step's own."""
    return found[2] is not None and (found[0] is not None or found[2] in _STEP)


def classify(text: str, head: "str | None" = None) -> dict:
    """``{instruction: Found(cell, mixer, part, layout)}`` for every
    instruction of the module that runs as an op of its own (those of fused
    computations are their fusion's); part is one of ``PARTS``. ``head``: the
    head cell's two digits; None: the last cell the text names."""
    computations = step_classes.parse(text)
    fused = {ins.calls for body in computations.values() for ins in body
             if ins.opcode == "fusion"}
    if head is None:
        cells = [int(c[-2:]) for w in set(_WORD.findall(text)) for c in _CELL.findall(w)]
        head = f"{max(cells):02d}" if cells else ""
    callers = _callers(text)
    out, waiting = {}, []
    for comp, body in computations.items():
        if comp in fused:
            continue
        own, found, users = {}, {}, collections.defaultdict(list)
        names = {ins.name for ins in body}
        for ins in body:
            for operand in ins.operands:
                if operand in names:
                    users[operand].append(ins.name)
            mine = (None, None, None)
            if ins.opcode == "fusion":
                mine = _fused(computations.get(ins.calls, ()), head)
            if mine == (None, None, None):
                mine = _own(ins.op_name, ins.name, head)
            own[ins.name] = mine
            if _whole(mine):
                found[ins.name] = mine
        # rule 4: from the consumer (the last printed resolves first) ...
        for ins in reversed(body):
            if ins.name in found or ins.opcode in _NO_OPS:
                continue
            asked = users[ins.name]
            if ins.opcode.endswith("-done"):
                asked = ins.operands[:1] + asked
            got = next((found[u] for u in asked if u in found), None)
            if got:
                found[ins.name] = _merge(own[ins.name], got)
        # ... else from the operand's producer
        for ins in body:
            if ins.name in found or ins.opcode in _NO_OPS:
                continue
            got = next((found[o] for o in ins.operands if o in found), None)
            if got:
                found[ins.name] = _merge(own[ins.name], got)
        for ins in body:
            layout = _is_layout(ins, computations, own[ins.name][2])
            if ins.name in found:
                out[ins.name] = Found(*found[ins.name], layout)
            else:
                waiting.append((comp, ins.name, own[ins.name], layout))
    # ... else from the loop or conditional whose body it is in (a caller is
    # printed after what it calls: the outermost resolves first)
    for comp, name, mine, layout in reversed(waiting):
        caller = out.get(callers.get(comp))
        if caller is not None and caller.part != UNSCOPED:
            mine = _merge(mine, caller[:3])
        out[name] = Found(mine[0], mine[1], mine[2] or UNSCOPED, layout)
    return out


def step_text(context) -> str:
    """The traced step's compiled text. ``step_classes.step_text`` asks the
    trainer with labels shaped as the logits less their last axis; a stream
    whose labels are shaped otherwise (block diffusion's carry a weight a
    position) compiled another step, so this asks with the labels the cell's
    own stream gives, and the trainer hands back the step it already made.
    Empty for a program without ``compiled_step``."""
    if step_classes._TEXT in context:  # placed for this cell's traced step
        return context[step_classes._TEXT]
    if _TEXT not in context:
        context[_TEXT] = _step_text(context)
    return context[_TEXT]


def _step_text(context) -> str:
    import jax
    from jax.sharding import NamedSharding

    from . import program, scopes

    trainer, session, cell = context["trainer"], context["session"], context["cell"]
    compiled_step = getattr(trainer, "compiled_step", None)
    if compiled_step is None:
        return ""
    state, x, y = scopes._step_arguments(context)
    stream = iter(program.input_stream(
        cell.config, session.cfg, dict(cell.traffic, prefetch=False), 0))
    labels = next(stream)[1]
    if tuple(labels.shape) != tuple(y.shape):
        y = jax.ShapeDtypeStruct(
            tuple(labels.shape), y.dtype,
            sharding=NamedSharding(trainer.mesh, trainer.y_spec))
    return compiled_step(state, x, y).as_text()


def has_parts(text: str) -> bool:
    return "mpi4dl_part_" in text


def split_events(table, events, window, steps, also=None):
    """``{Found: ms a step}`` of one chip's op ``events`` inside ``window``
    under ``table``, :func:`classify`'s of the compiled step's text; with
    ``also`` the key is ``(Found, also(event))``."""
    nowhere = Found(None, None, UNSCOPED, False)

    def key(ev):
        found = table.get(ev.op, nowhere)
        return (found, also(ev)) if also else found

    seconds = step_classes.innermost_seconds(events, *window, key=key)
    return {k: 1e3 * v / steps for k, v in seconds.items()}


def split(context):
    """``{Found: ms a step}`` of the traced window, first chip; None where
    the run was not traced or the program carries no part scope."""
    if _SPLIT not in context:
        context[_SPLIT] = _split(context)
    return context[_SPLIT]


def _split(context):
    reduced = context["reduced"]
    if reduced is None:
        return None
    text = step_text(context)
    if not has_parts(text):
        return None
    chip = reduced.chips[0]
    return split_events(classify(text), chip["ops"], chip["window"], reduced.steps)


def ms(context, parts=None, layout_only=False, cells_only=False):
    """Milliseconds a step in the given parts (all when None); with
    ``layout_only`` the compiler's layout turns among them alone, with
    ``cells_only`` only what lies in a model cell. None where :func:`split`
    reads nothing; 0.0 where the program has the scopes and no op falls
    there."""
    table = split(context)
    if table is None:
        return None
    return sum((v for found, v in table.items()
                if (parts is None or found.part in parts)
                and (found.layout or not layout_only)
                and (found.cell is not None or not cells_only)), 0.0)


def roofline_pct(context, part: str, least_flops):
    """Share of the matrix units' peak a part reaches: its least FLOPs a
    step / the chip's peak / the part's measured time; None where the part
    reads nothing."""
    spent = ms(context, (part,))
    if not spent:
        return None
    return 100.0 * least_flops / context["peaks"]["bf16_flops_per_s"] / (spent / 1e3)
