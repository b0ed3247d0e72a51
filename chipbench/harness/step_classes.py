"""Every op event of the traced window under one class and at most one model
cell: the step's device time split by the program's ``jax.named_scope``s.

The program names where its work happens (``mpi4dl_cell<NN>`` around every
model cell, ``mpi4dl_cells<NN>to<MM>`` around a scanned run of them,
``mpi4dl_convkxk`` / ``mpi4dl_conv1x1`` / ``mpi4dl_batchnorm`` /
``mpi4dl_pool`` / ``mpi4dl_halo`` around the operator classes,
``mpi4dl_optimizer`` and ``mpi4dl_loss`` in the step); the chip's trace names
an op by its HLO instruction and carries no name stack, so the two are
joined through the compiled step's text as ``scopes.py`` does, but
instruction by instruction and with the operands read:

1. **A fusion takes the class of the heaviest thing fused into it**, read
   from the instructions of its called computation: a computation that holds
   a ``convolution`` or a ``dot`` is that instruction's class and cell
   whatever its epilogue (the chip fuses a convolution with its BatchNorm and
   ReLU, and the fusion's own ``op_name`` is any one of theirs); else the
   class, and the cell, that most of its instructions carry.
2. Any other instruction takes the innermost (rightmost) scope of its own
   ``op_name``; a Pallas kernel's custom call also its own name
   (``mpi4dl_pool_bwd`` holds ``mpi4dl_pool``).
3. **An instruction whose stack holds none of these scopes** (what the
   compiler made or rewrote, a ``copy``, ``copy-start`` / ``-done``,
   ``bitcast``, ``transpose``, ``slice``, a rewritten convolution, the
   grouped products' ``ragged-dot`` custom calls; or what sits between the
   scopes) **takes the class and cell of the instruction that consumes it,
   else of its operand's producer**, read from the operands in the compiled
   text; an asynchronous ``-done`` asks its ``-start`` first. A
   ``convolution``, ``select-and-scatter`` or ``reduce-window`` among them (or
   a fusion that holds one) is booked by its opcode to its class, a
   convolution's window telling 1x1 from k x k, and inherits only the cell.
4. Collectives (``xtrace.is_collective``) never inherit: one under
   ``mpi4dl_batchnorm`` is BatchNorm's, one under ``mpi4dl_halo`` the halo
   exchange's, one under ``mpi4dl_loss`` the loss's, one with no cell and no
   class the gradients' sum, class ``grad_allreduce``.
5. An op with a cell and no class is that cell's ``other`` (ReLUs, adds,
   concatenations; all of a token model's cell); an op with neither is
   ``unscoped``.

Times are read off the first chip, per step, and every nanosecond counts
once: where events nest (a ``while`` spans its body's) the innermost one has
the time, so the classes and ``unscoped`` add up to the trace's busy time.
A program without the scopes (no ``compiled_step``, or a text that names no
cell and no optimiser) reads None everywhere.
"""

from __future__ import annotations

import collections
import re

from . import scopes, xtrace

CLASS_SCOPES = {
    "mpi4dl_convkxk": "convkxk",
    "mpi4dl_conv1x1": "conv1x1",
    "mpi4dl_batchnorm": "batchnorm",
    "mpi4dl_pool": "pool",
    "mpi4dl_halo": "halo",
    "mpi4dl_optimizer": "optimizer",
    "mpi4dl_loss": "loss",
}
OTHER, UNSCOPED, GRAD_ALLREDUCE = "other", "unscoped", "grad_allreduce"
CLASSES = tuple(CLASS_SCOPES.values()) + (GRAD_ALLREDUCE, OTHER, UNSCOPED)

_CELL = re.compile(r"mpi4dl_cells?(\d\d(?:to\d\d)?)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_WINDOW = re.compile(r"window=\{size=([0-9x]+)")
_TOKEN = re.compile(r"[\w.\-]+")
_HEAVY = ("convolution", "dot")
_NO_OPS = ("parameter", "constant")  # they run as no op and hand on no scope
_POOLS = ("select-and-scatter", "reduce-window")
_TEXT, _SPLIT, _FLOPS = "_step_text", "_step_classes", "_step_class_flops"

Instruction = collections.namedtuple(
    "Instruction", "name opcode operands calls op_name window")


def parse(text: str) -> dict:
    """``{computation: [Instruction]}`` of an HLO module's text, each
    computation's instructions in the order printed (operands first)."""
    computations, current = {}, None
    for line in text.splitlines():
        found = _INSTRUCTION.match(line)
        if found is None:
            head = _COMPUTATION.match(line)
            if head:
                current = computations.setdefault(head.group(1), [])
            continue
        if current is None:
            continue
        name, rest = found.groups()
        opcode = xtrace._OPCODE.search(" " + rest)
        if opcode is None:
            continue
        operands = _operand_text(rest, opcode.end() - 1)
        after = rest[opcode.end() - 1 + len(operands):]
        calls, op_name = _CALLS.search(after), _OP_NAME.search(after)
        window = _WINDOW.search(after) if opcode.group(1) == "convolution" else None
        current.append(Instruction(
            name, opcode.group(1), _TOKEN.findall(operands),
            calls and calls.group(1), op_name.group(1) if op_name else "",
            window and window.group(1)))
    return computations


def _operand_text(rest, start):
    """The operand list that opens at ``rest[start - 1]``'s parenthesis."""
    depth = 0
    for i in range(start - 1, len(rest)):
        depth += (rest[i] == "(") - (rest[i] == ")")
        if depth == 0:
            return rest[start - 1:i + 1]
    return rest[start - 1:]


def scope_of(op_name: str, own_name: str = ""):
    """``(class or None, cell or None)`` a name stack says: the innermost
    class scope, the cell's two digits (``03``; ``03to05`` for a run)."""
    best, cls = -1, None
    for scope, name in CLASS_SCOPES.items():
        at = op_name.rfind(scope)
        if at > best:
            best, cls = at, name
    if cls is None:
        cls = next((n for s, n in CLASS_SCOPES.items() if s in own_name), None)
    cells = _CELL.findall(op_name)
    return cls, (cells[-1] if cells else None)


def _fused(instructions):
    """Rule 1: the class and cell of a fused computation."""
    for ins in instructions:
        if ins.opcode in _HEAVY:
            found = scope_of(ins.op_name)
            if found != (None, None):
                return found
    votes = collections.Counter(
        scope_of(ins.op_name) for ins in instructions
        if ins.op_name and ins.opcode not in _NO_OPS)
    votes.pop((None, None), None)
    return votes.most_common(1)[0][0] if votes else (None, None)


def _opcode_class(ins, computations):
    """The class an opcode alone gives: a convolution's by its window, a
    ``select-and-scatter``'s and a ``reduce-window``'s the pools'; a fusion's
    that of the first such instruction it holds."""
    inner = computations.get(ins.calls, ()) if ins.opcode == "fusion" else (ins,)
    for each in inner:
        if each.opcode == "convolution":
            taps = set((each.window or "1").split("x"))
            return "conv1x1" if taps == {"1"} else "convkxk"
        if each.opcode in _POOLS:
            return "pool"
    return None


def classify(text: str) -> dict:
    """``{instruction: (class, cell, is a collective)}`` for every
    instruction of the module that runs as an op of its own (those of fused
    computations are their fusion's); class is one of ``CLASSES``, cell its
    two digits or None."""
    computations = parse(text)
    fused = {ins.calls for body in computations.values() for ins in body
             if ins.opcode == "fusion"}
    out = {}
    for comp, body in computations.items():
        if comp in fused:
            continue
        found, users = {}, collections.defaultdict(list)
        names = {ins.name for ins in body}
        for ins in body:
            for operand in ins.operands:
                if operand in names:
                    users[operand].append(ins.name)
            if ins.opcode == "fusion":
                own = _fused(computations.get(ins.calls, ()))
                if own == (None, None):
                    own = scope_of(ins.op_name)
            else:
                own = scope_of(ins.op_name, ins.name)
            if own != (None, None):
                found[ins.name] = own
        collective = {ins.name for ins in body if ins.opcode in xtrace.COLLECTIVES}
        scopeless = names - set(found)
        # rule 3: from the consumer (the last printed resolves first) ...
        for ins in reversed(body):
            if ins.name in found or ins.name in collective or ins.opcode in _NO_OPS:
                continue
            asked = users[ins.name]
            if ins.opcode.endswith("-done"):
                asked = ins.operands[:1] + asked
            got = next((found[u] for u in asked if u in found), None)
            if got:
                found[ins.name] = got
        # ... else from the operand's producer; of the collectives only a
        # -done inherits, from its -start
        for ins in body:
            if ins.name in found or ins.opcode in _NO_OPS:
                continue
            asked = ins.operands
            if ins.name in collective:
                asked = asked[:1] if ins.opcode.endswith("-done") else ()
            got = next((found[o] for o in asked if o in found), None)
            if got:
                found[ins.name] = got
        for ins in body:
            cls, cell = found.get(ins.name, (None, None))
            if ins.name in scopeless and ins.name not in collective:
                # what it is outweighs who uses it; the cell stays inherited
                cls = _opcode_class(ins, computations) or cls
            if cls is None and cell is None:
                cls = GRAD_ALLREDUCE if ins.name in collective else UNSCOPED
            elif cls is None:
                cls = OTHER
            out[ins.name] = (cls, cell, ins.name in collective)
    return out


def step_text(context) -> str:
    """The compiled step's text (``trainer.compiled_step`` on shapes like
    the window's, kept on the context); empty for a program without that
    accessor."""
    if _TEXT not in context:
        compiled_step = getattr(context["trainer"], "compiled_step", None)
        context[_TEXT] = (
            compiled_step(*scopes._step_arguments(context)).as_text()
            if compiled_step else "")
    return context[_TEXT]


def innermost_seconds(events, t0, t1, key):
    """``{key(event): seconds}`` inside ``[t0, t1]`` with every nanosecond
    given to the innermost event that covers it (the one that started
    last): the values add up to the union of the events' intervals."""
    spans = sorted(
        ((max(ev.start_ns, t0), min(ev.end_ns, t1), key(ev)) for ev in events
         if ev.end_ns > t0 and ev.start_ns < t1),
        key=lambda span: (span[0], -span[1]))
    totals, live, cursor = collections.defaultdict(float), [], t0

    def advance(to):
        """Give ``[cursor, to)`` to the innermost live event, closing those
        that end on the way; an idle stretch goes to nobody."""
        nonlocal cursor
        while live and cursor < to:
            end, k = live[-1]
            upto = min(end, to)
            if upto > cursor:
                totals[k] += upto - cursor
                cursor = upto
            if end <= to:
                live.pop()
        cursor = max(cursor, to)

    for start, end, k in spans:
        advance(start)
        live.append((end, k))
    advance(t1)
    return {k: v / 1e9 for k, v in totals.items()}


def split(context):
    """``{(class, cell, is a collective): ms a step}`` of the traced window,
    first chip; None where the run was not traced or the program carries
    none of the scopes."""
    if _SPLIT not in context:
        context[_SPLIT] = _split(context)
    return context[_SPLIT]


def _split(context):
    reduced = context["reduced"]
    if reduced is None:
        return None
    text = step_text(context)
    if "mpi4dl_cell" not in text and "mpi4dl_optimizer" not in text:
        return None
    chip = reduced.chips[0]
    return split_events(text, chip["ops"], chip["window"], reduced.steps)


def split_events(text, events, window, steps, families=False):
    """``{(class, cell, is a collective): ms a step}`` of one chip's op
    ``events`` inside ``window`` under the compiled step's ``text``; with
    ``families`` the key's fourth part is an unscoped op's family (what XLA
    named it), None for the others."""
    table = classify(text)

    def key(ev):
        found = table.get(ev.op) or (UNSCOPED, None, xtrace.is_collective(ev))
        if families:
            found += (ev.family if found[0] == UNSCOPED else None,)
        return found

    seconds = innermost_seconds(events, *window, key=key)
    return {k: 1e3 * v / steps for k, v in seconds.items()}


def ms(context, classes=None, cell=None, collectives_only=False):
    """Milliseconds a step in the given classes (all when None) and cell
    (every one when None); None where :func:`split` reads nothing or no op
    falls there."""
    table = split(context)
    if table is None:
        return None
    picked = [v for (cls, at, coll), v in table.items()
              if (classes is None or cls in classes)
              and (cell is None or at == cell)
              and (coll or not collectives_only)]
    return sum(picked) if picked else None


def cell_ms(context) -> dict:
    """``{cell: ms a step}`` over all classes, model cells only."""
    table = split(context) or {}
    out = collections.defaultdict(float)
    for (_, cell, _), v in table.items():
        if cell is not None:
            out[cell] += v
    return dict(out)


def head_cell(context) -> str:
    return f"{len(context['trainer'].cells) - 1:02d}"


def conv_class_flops(session) -> dict:
    """``{"convkxk": FLOPs, "conv1x1": FLOPs}`` of one training step's
    least work on the whole batch: 3 x the reference's forward FLOPs
    (forward, data gradient, weight gradient; ``counting.py``'s count, split
    by the window of each convolution; a product of matrices is a 1x1)."""
    from . import counting

    flops = {"convkxk": 0.0, "conv1x1": 0.0}
    jaxpr = counting._forward_jaxpr(session.ref_cells, session.x_shape, session.x_dtype)
    for eqn in counting._walk(jaxpr):
        prim = eqn.primitive.name
        if prim == "conv_general_dilated":
            spec = eqn.params["dimension_numbers"].rhs_spec
            taps = {eqn.invars[1].aval.shape[d] for d in spec[2:]}
            flops["conv1x1" if taps == {1} else "convkxk"] += counting._eqn_flops(eqn)
        elif prim == "dot_general":
            flops["conv1x1"] += counting._eqn_flops(eqn)
    return {k: 3.0 * v for k, v in flops.items()}


def roofline_pct(context, cls):
    """Share of the matrix units' peak a convolution class reaches: its
    least FLOPs a step (a chip's share under spatial parallelism) / the
    chip's peak / the class's measured time."""
    spent = ms(context, (cls,))
    if not spent:
        return None
    trainer = context["trainer"]
    tiles = trainer.mesh.devices.size if trainer.n_spatial else 1
    if _FLOPS not in context:  # one trace of the reference serves both classes
        context[_FLOPS] = conv_class_flops(context["session"])
    least = context[_FLOPS][cls] / tiles
    return 100.0 * least / context["peaks"]["bf16_flops_per_s"] / (spent / 1e3)
