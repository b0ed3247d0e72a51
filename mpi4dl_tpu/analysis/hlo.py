"""Text parser for compiled HLO modules.

``compiled.as_text()`` (post-optimization, post-scheduling HLO) is the one
artifact every backend can produce, and it is stable across versions
where the protobuf is not. The grammar actually needed for analysis is
small and stable: one instruction per line, ``%name = shape opcode(operands), attrs``,
computations delimited by ``{``/``}``, with the entry computation marked
``ENTRY``. Within a scheduled module (``is_scheduled=true`` in the header)
the listed instruction order IS the schedule, which is what makes
start→done distance a real overlap measurement rather than a guess.

Parsing is deliberately tolerant: unknown attributes are kept raw, unknown
dtypes get itemsize 0 (they count as 0 bytes instead of crashing the lint),
and malformed lines are skipped — a lint must degrade to "less information",
never to a parse crash on a new compiler version's output.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Iterable

# Bytes per element for HLO primitive types. Unlisted types (token, opaque,
# tuple placeholders) contribute 0 bytes.
DTYPE_BYTES = {
    "pred": 1,
    "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
    "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1, "f8e5m2": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
}

_SHAPE_RE = re.compile(r"^([a-zA-Z0-9]+)\[([0-9,]*)\](\{[^}]*\})?")
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
# ``%computation (params) -> shape {``  /  ``ENTRY %main.1 ... {``
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(")


@dataclasses.dataclass(frozen=True)
class HloShape:
    """A (possibly tuple) HLO shape. ``dims`` is empty for scalars."""

    dtype: str | None
    dims: tuple[int, ...] = ()
    elements: tuple["HloShape", ...] = ()

    @property
    def is_tuple(self) -> bool:
        return self.dtype is None

    def byte_size(self) -> int:
        if self.is_tuple:
            return sum(e.byte_size() for e in self.elements)
        n = 1
        for d in self.dims:
            n *= d
        return n * DTYPE_BYTES.get(self.dtype, 0)


@dataclasses.dataclass
class HloInstruction:
    name: str
    opcode: str
    shape: HloShape
    operands: tuple[str, ...]  # operand instruction names, %-stripped
    attrs: str  # raw trailing attribute text
    index: int  # position within its computation (schedule order)
    is_root: bool = False

    @property
    def channel_id(self) -> int | None:
        m = re.search(r"channel_id=(\d+)", self.attrs)
        return int(m.group(1)) if m else None


@dataclasses.dataclass
class HloComputation:
    name: str
    instructions: list[HloInstruction]
    is_entry: bool = False

    def __iter__(self):
        return iter(self.instructions)


@dataclasses.dataclass
class HloModule:
    name: str
    computations: dict[str, HloComputation]
    header: str = ""

    @property
    def is_scheduled(self) -> bool:
        return "is_scheduled=true" in self.header

    @property
    def entry(self) -> HloComputation | None:
        for c in self.computations.values():
            if c.is_entry:
                return c
        return None

    def all_instructions(self) -> Iterable[HloInstruction]:
        for comp in self.computations.values():
            yield from comp.instructions


def _match_paren(s: str, start: int) -> int:
    """Index just past the ``)`` closing the ``(`` at ``start``; respects
    nesting but not quotes (operand lists never contain quoted parens)."""
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(s)


def _split_top_commas(s: str) -> list[str]:
    parts, depth, cur = [], 0, []
    for ch in s:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    tail = "".join(cur).strip()
    if tail:
        parts.append(tail)
    return parts


def parse_shape(s: str) -> tuple[HloShape | None, str]:
    """Parse one shape at the head of ``s``; returns (shape, rest)."""
    s = s.lstrip()
    if s.startswith("("):
        end = _match_paren(s, 0)
        inner = s[1 : end - 1]
        elems = []
        for part in _split_top_commas(inner):
            shp, _ = parse_shape(part)
            if shp is not None:
                elems.append(shp)
        return HloShape(None, (), tuple(elems)), s[end:]
    m = _SHAPE_RE.match(s)
    if not m:
        return None, s
    dtype = m.group(1)
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return HloShape(dtype, dims), s[m.end():]


def _operand_names(operand_text: str) -> tuple[str, ...]:
    """Instruction names referenced in an operand list — each operand is
    ``[shape] %name`` (typed form) or just ``name``; constants/literals
    have no name and are skipped."""
    names = []
    for part in _split_top_commas(operand_text):
        m = re.search(r"%([\w.\-]+)\s*$", part)
        if m:
            names.append(m.group(1))
            continue
        # Untyped compact form: a bare identifier that isn't a literal.
        bare = part.strip()
        if re.fullmatch(r"[A-Za-z_][\w.\-]*", bare) and not _SHAPE_RE.match(bare):
            names.append(bare)
    return tuple(names)


def parse_instruction(line: str, index: int) -> HloInstruction | None:
    m = _INSTR_RE.match(line)
    if not m:
        return None
    is_root = bool(m.group(1))
    name = m.group(2)
    rhs = m.group(3)
    shape, rest = parse_shape(rhs)
    if shape is None:
        return None
    om = re.match(r"\s*([\w\-]+)\s*\(", rest)
    if not om:
        return None
    opcode = om.group(1)
    open_at = om.end() - 1
    close_at = _match_paren(rest, open_at)
    operand_text = rest[open_at + 1 : close_at - 1]
    attrs = rest[close_at:].lstrip(", ")
    # Operands of call-like ops (fusion/call/while) are still value names;
    # computation references live in attrs (to_apply=..., calls=...).
    return HloInstruction(
        name=name,
        opcode=opcode,
        shape=shape,
        operands=_operand_names(operand_text),
        attrs=attrs,
        index=index,
        is_root=is_root,
    )


def parse_hlo_text(text: str) -> HloModule:
    """Parse a full ``compiled.as_text()`` dump into an :class:`HloModule`."""
    lines = text.splitlines()
    header = ""
    name = ""
    for line in lines:
        if line.startswith("HloModule"):
            header = line
            parts = line.split(None, 2)
            name = parts[1].rstrip(",") if len(parts) > 1 else ""
            break

    computations: dict[str, HloComputation] = {}
    current: HloComputation | None = None
    for line in lines:
        stripped = line.strip()
        if not stripped or stripped.startswith("//") or stripped.startswith("HloModule"):
            continue
        if stripped.endswith("{") and "=" not in stripped.split("(")[0]:
            m = _COMP_RE.match(stripped)
            if m:
                current = HloComputation(
                    name=m.group(2), instructions=[], is_entry=bool(m.group(1))
                )
                computations[current.name] = current
                continue
        if stripped == "}" or stripped.startswith("}"):
            current = None
            continue
        if current is not None and "=" in stripped:
            instr = parse_instruction(stripped, len(current.instructions))
            if instr is not None:
                current.instructions.append(instr)
    return HloModule(name=name, computations=computations, header=header)
