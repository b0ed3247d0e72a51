"""Pallas TPU kernels: Mamba-2's chunked scan (the state-space dual form),
both passes.

Why: the Nemotron-H tower's four Mamba-2 layers at 2 x 8,192 positions (8
groups of 8 heads of 64 channels, a state of 128, chunks of 128) spent 118 ms
of a 661 ms step in ``ops/sequence._chunked_scan`` (ledger, PR 41): every
chunk's ``[128, 128]`` float32 square a head (the decays ``L``, then ``(C
B^T) * L``) went through HBM in float32 and again in bfloat16, the chunks'
own states likewise, the state's hand-on was a ``lax.scan`` of 64 trips a
sequence with the state in HBM, and the forward ran three times, because two
``jax.checkpoint``s bought the backward's memory with forwards. Here a
chunk's squares and the group's carried state live and die in VMEM: the
scan's HBM traffic is its inputs, its output and the start states the
backward keeps.

The arithmetic is the plain path's and the configuration's
(``sequence.ssd_scan``'s docstring has the formulas): products take bfloat16
operands and accumulate in float32; the running sums ``a``, every decay and
the carried state are float32; ``(C B^T) * L``, ``x * exp(a_end - a_j)`` and
the start state are rounded to bfloat16 exactly where ``_chunked_scan``
rounds them; every exponent is of a difference <= 0; chunks of the
configuration's 128. ``_chunk`` is one chunk of one group on 2-D values.

* the positions lie along the lanes. ``x`` and the result are the kernels'
  as ``[B, G, R P, S]`` (``_turned``): a head's chunk is ``[64, 128]``,
  channels down, positions across. That is the layout the chip's compiler
  holds the mixer's ``[B, S, G, R, P]`` arrays in (``{1,4,3,2,0}``, the
  positions minor-most: with 64 channels a head a channels-minor tile would
  be half padding), so the turn is a bitcast in the compiled step and no
  copy on either side.
* what a group's heads share and what they do not. ``B`` and ``C`` are a
  group's, so a chunk costs one ``B C^T`` ``[128, 128]`` (``(C B^T)^T``), one
  product of all the heads' start states with ``C^T`` (``[512, 128] x [128,
  128]``) and one own-state product ``(x * to_end) B`` (``[512, 128] x [128,
  128]``) for its eight heads; a head's own is its mask ``L^T[j, i] =
  exp(a_i - a_j)``, ``(B C^T) * L^T``, its cast and ``x [64, 128]`` times
  that square.
* the decays. ``a`` comes in as rows (``[8, 128]`` float32, a head a
  sublane: one register a chunk and group). With the positions along the
  lanes ``exp(a_i)`` and ``exp(a_end - a_j)`` are taken on those rows (three
  exps a chunk for all the heads) and spread down a head's channels for
  nothing; only the mask needs ``a_j`` as a column ``[128, 1]``, a masked
  lane sum of the head's row laid along the diagonal: exact, and what a lane
  sum leaves is the same number in every lane, so spreading the column over
  the square costs nothing either. No array with a minor dimension of 8
  crosses HBM.
* forward (``mpi4dl_ssd_scan_fwd``), grid (sequence, group, block of 8
  chunks; the last axis sequential): the block's chunks in order, a loop
  that is not unrolled (a chunk's lanes are a dynamic slice of whole
  lanes). The heads' states ``[8, 64, 128]`` float32 (256 KB) stay in VMEM
  scratch across a sequence's blocks and start from zero at the first.
  Called for a backward pass it also writes every chunk's start states
  (float32: 268 MB a layer, kept from the layer's forward to its backward).
* backward (``mpi4dl_ssd_scan_bwd``), the same grid from the last block to
  the first: ``dS`` is carried in VMEM as ``S`` was; a chunk's backward is
  ``jax.vjp`` of ``_chunk`` taken inside the kernel body on VMEM values (the
  chunk's squares are built again, its start states come from the forward),
  so there is one statement of the arithmetic. A product's cotangent is
  rounded to bfloat16 before the transposed products, which is what the
  chip's default precision does to the plain path's. ``b``'s and ``c``'s
  cotangents are summed over the group's heads by the products themselves,
  in float32, and rounded once.
* outside, in plain JAX: the running sums of ``g`` inside each chunk and their
  layout as rows ``[batch, group, chunk, 8, 128]`` (4 MB), and the way back.

The plain path's two ``jax.checkpoint``s play no part here: under the cell's
"cell" remat the scan runs forward (keeping the states), then backward.
``_scan_fwd`` gives all the forward call writes (the output, the start
states) the name ``config.KERNEL_RESIDUAL``, which the cell's checkpoint
keeps (``train._cell_ckpt``, PR 44), so the cell's replay in the backward
pass has no use for a second forward call: 402 MB a layer held from the
layer's forward to its backward.

Timed alone at the cell's shape (``x [2, 8192, 8, 8, 64]``, ``b, c [2, 8192, 8,
128]`` bfloat16, ``g [2, 8192, 8, 8]`` float32 as the benchmark's fresh model
makes them; TPU v5 lite, jax 0.9.0; jitted, host clock around
``block_until_ready``, least of five; ``scripts/time_ssd_scan.py plain
kernels turned``; ms forward / gradient (the forward that keeps the backward's
residuals, then the backward) / a layer's passes as the step runs them =
forward + gradient; my chip run, PR 42, call 149):

    plain JAX (``_chunked_scan`` under ``lax.map``; its gradient
      runs the scan forward, again, backward)             9.73 / 14.86 / 24.59
    the kernels, ``x`` and the result ``[B, S, G, R, P]``
      as a jit's arguments lie                            1.81 / 4.59 / 6.40
    the kernels, ``x`` and the result in their own
      ``[B, G, R P, S]`` (``scan_turned``)                1.89 / 4.41 / 6.30

The turn of a jit's arguments does not show. These are host-clock times of a
whole jitted call (the running sums, the rows, the cotangent's casts and the
dispatch are in them); on the device's clock, in the cell's step, the eight
forward and four backward calls take 12.41 ms together
(``ssd_scan_kernel_ms``, 25.3% of the 3.14 ms the recurrence's least work
takes at the chip's 197 TFLOP/s: ``ssd_scan_kernel_roofline``) and the scope
around them 16.51 where the plain path took 118.16 (``ssd_scan_ms``; my chip
run, PR 42, the traced pair of call 149). The loop over a grid step's chunks
is not unrolled (PR 38 paid 23 s of ``setup_s`` for a body written out eight
times) and the start states are kept in float32 (the plain path's backward
reads the float32 state where ``d a_end`` is formed). What bounds the kernels
is the vector unit, not the matrix unit: a chunk and group is some 3,500
vector instructions (my sandbox compile's LLO text: the eight masks at 16
registers and five operations each, the lane sums, casts, the matrix unit's
pushes and pops) for 13 products of ``128^3``. The layouts and plans tried
and dropped on the way are in PERF.md section 6, what is left on the table in
its section 7.

Dispatch (``dispatchable``): TPU backend, not under ``vmap``, ``x, b, c``
bfloat16 and ``g`` float32, heads of whole bfloat16 tiles of 16 channels (64
in the cell), a state and a chunk of whole lanes (128 and 128), a length of
whole chunks, a grid step that fits VMEM; everything else (the CPU, the
tier-1 tests, the tiny cut at head dim 8, state 16 and chunks of 32,
``vmap``, float32) takes ``sequence._chunked_scan`` under ``lax.map``, which
is also the kernels' oracle. No switch. ``tests/test_tpu_compile.py``
compiles the cell's layer for a described v5e chip and fails if the kernels
are not in the compiled text of both passes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi4dl_tpu.config import KERNEL_RESIDUAL

# ``dot``: a product of operands as they are, accumulated in float32, whose
# backward rounds the cotangent to the operands' dtype first (what the chip's
# default precision does to the plain path's); ``by_chunk``: ``[B, S, ...] ->
# [B, S / chunk, chunk, everything else]``, no copy.
from mpi4dl_tpu.ops.delta_rule_pallas import by_chunk, dot

# The pallas_calls' names: how the kernels are found in a compiled step's
# text and in a profiler trace (the benchmark's readers look for their
# common start, ``mpi4dl_ssd_scan``).
FWD_NAME = "mpi4dl_ssd_scan_fwd"
BWD_NAME = "mpi4dl_ssd_scan_bwd"
LANES = 128
SUBLANES = 8
_BF16_ROWS = 16             # rows of a bfloat16 tile: a head's channels are whole tiles
STEP_CHUNKS = (8, 4, 2, 1)  # chunks a grid step takes: the most that divides the sequence's
_VMEM_LIMIT = 64 * 1024 * 1024
_F32 = jnp.float32


# -- a chunk, in plain jnp on VMEM values --------------------------------------


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _columns(rows):
    """``rows [n, C]`` float32 -> ``n`` columns ``[C, 1]``: a masked lane sum
    of each row laid along the diagonal (exact; what the lane sum leaves is
    the same number in every lane, so spreading a column over a square costs
    nothing)."""
    size = rows.shape[1]
    diagonal = _iota((size, size), 0) == _iota((size, size), 1)
    return [jnp.sum(jnp.where(diagonal, rows[r:r + 1], 0.0), axis=1, keepdims=True)
            for r in range(rows.shape[0])]


def _chunk(xs, b, c, rows, states, *, dtype):
    """One chunk of ``C`` positions of one group with its ``R`` heads,
    positions along the lanes: ``sequence._chunked_scan``'s arithmetic on 2-D
    values. ``xs`` the heads' ``x`` turned, ``R x [P, C]``; ``b, c [C, N]``;
    all float32 (the cell's bfloat16 numbers); ``rows [R8, C]`` float32 (the
    running sums ``a`` of the chunk's ``g``, a head a row); ``states`` the
    heads' ``[P, N]`` float32 at the chunk's start -> ``(outputs R x [P, C],
    states at the chunk's end)``. Products take operands in ``dtype`` and
    accumulate in float32; every exponent is of a difference <= 0.

    The group's heads share ``(C B^T)^T = B C^T``, the product with the
    start states (``S C^T``, all heads' rows at once) and the own-state
    product (``(x * to_end) B``); a head's own is its mask ``L^T[j, i] =
    exp(a_i - a_j)``, ``(B C^T) * L^T`` and ``x`` times that square. With the
    positions along the lanes ``exp(a_i)`` and ``exp(a_end - a_j)`` are rows
    (one register holds eight heads') that spread down a head's channels for
    nothing; only ``a_j`` of the mask is needed as a column."""
    size, heads, width = b.shape[0], len(xs), xs[0].shape[0]
    upper = _iota((size, size), 0) <= _iota((size, size), 1)       # [j, i]: j <= i
    last = _iota((1, size), 1) == size - 1
    bd, cd = b.astype(dtype), c.astype(dtype)
    bc = dot(bd, cd, "nt")                                                      # (C B^T)^T
    whole = jnp.sum(jnp.where(last, rows, 0.0), axis=1, keepdims=True)           # a_end [R8, 1]
    grown, to_end, end = jnp.exp(rows), jnp.exp(whole - rows), jnp.exp(whole)
    columns = _columns(rows[:heads])
    starts = jnp.concatenate([state.astype(dtype) for state in states], axis=0)  # [R P, N]
    from_state = dot(starts, cd, "nt")                                          # S C^T [R P, C]
    own = dot(jnp.concatenate(
        [(x * to_end[r:r + 1]).astype(dtype) for r, x in enumerate(xs)], axis=0), bd)
    outs, ends = [], []
    for r, (x, state) in enumerate(zip(xs, states)):
        channels = slice(r * width, (r + 1) * width)
        decay = jnp.exp(jnp.where(upper, rows[r:r + 1] - columns[r], -jnp.inf))  # L^T
        within = (bc * decay).astype(dtype)                                      # ((C B^T) * L)^T
        outs.append(dot(x.astype(dtype), within) + grown[r:r + 1] * from_state[channels])
        ends.append(state * end[r:r + 1] + own[channels])
    return outs, ends


# -- the kernels ---------------------------------------------------------------


def _chunk_of(j, chunk):
    """The lanes of the grid step's ``j``-th chunk in a ``[R P, n C]`` block."""
    return pl.ds(pl.multiple_of(j * chunk, chunk), chunk)


def _fwd_kernel(x_ref, b_ref, c_ref, rows_ref, o_ref, *rest, keep):
    """One (sequence, group, block of chunks): the block's chunks in order
    (a loop, not unrolled). The heads' states ``[R, P, N]`` float32 stay in
    VMEM scratch across a sequence's blocks, zero before the first.

    x_ref, o_ref ``[R P, n C]`` (positions along the lanes); b_ref, c_ref
    ``[n, C, N]``; rows_ref ``[n, R8, C]`` float32; with ``keep`` also
    starts_ref ``[n, R, P, N]``: every chunk's start states, the backward's."""
    state_ref = rest[-1]
    heads, width, _ = state_ref.shape
    chunk = rows_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, _F32)

    def one(j, carry):
        lanes = _chunk_of(j, chunk)
        states = [state_ref[r] for r in range(heads)]
        outs, ends = _chunk(
            [x_ref[r * width:(r + 1) * width, lanes].astype(_F32) for r in range(heads)],
            b_ref[j].astype(_F32), c_ref[j].astype(_F32), rows_ref[j], states, dtype=x_ref.dtype)
        for r in range(heads):
            o_ref[r * width:(r + 1) * width, lanes] = outs[r].astype(o_ref.dtype)
            state_ref[r] = ends[r]
            if keep:
                rest[0][j, r] = states[r].astype(rest[0].dtype)
        return carry

    lax.fori_loop(0, rows_ref.shape[0], one, 0)


def _bwd_kernel(x_ref, b_ref, c_ref, rows_ref, do_ref, starts_ref,
                dx_ref, db_ref, dc_ref, drows_ref, dstate_ref):
    """The reverse sweep: one (sequence, group, block of chunks, the last
    block first), the block's chunks from its last to its first; ``dS``
    stays in VMEM scratch as the forward's ``S`` does. A chunk's backward is
    ``jax.vjp`` of ``_chunk`` on VMEM values: the chunk's squares are built
    again from its inputs, its start states come from the forward pass; the
    cotangents of ``b`` and ``c`` are the sum over the group's heads as the
    products make it."""
    heads, width, _ = dstate_ref.shape
    chunks, _, chunk = rows_ref.shape
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros(dstate_ref.shape, _F32)

    def one(t, carry):
        j = chunks - 1 - t
        lanes = _chunk_of(j, chunk)
        channels = [slice(r * width, (r + 1) * width) for r in range(heads)]
        _, pull = jax.vjp(
            functools.partial(_chunk, dtype=dtype),
            [x_ref[rows, lanes].astype(_F32) for rows in channels], b_ref[j].astype(_F32),
            c_ref[j].astype(_F32), rows_ref[j],
            [starts_ref[j, r].astype(_F32) for r in range(heads)])
        dxs, db, dc, drows, dstates = pull(
            ([do_ref[rows, lanes].astype(_F32) for rows in channels],
             [dstate_ref[r] for r in range(heads)]))
        for r, rows in enumerate(channels):
            dx_ref[rows, lanes] = dxs[r].astype(dx_ref.dtype)
            dstate_ref[r] = dstates[r]
        db_ref[j] = db.astype(db_ref.dtype)
        dc_ref[j] = dc.astype(dc_ref.dtype)
        drows_ref[j] = drows
        return carry

    lax.fori_loop(0, chunks, one, 0)


# -- layouts and calls -----------------------------------------------------------


def _turned(x):
    """``[B, S, G, R, P] -> [B, G, R P, S]``: the positions along the lanes.
    On the chip the compiler holds the mixer's ``[B, S, G, R, P]`` arrays
    with the positions minor-most already (the module's docstring), so this
    is that array as it lies and no copy."""
    b, s, g, r, p = x.shape
    return x.transpose(0, 2, 3, 4, 1).reshape(b, g, r * p, s)


def _unturned(xt, heads):
    """``_turned``'s inverse."""
    b, g, wide, s = xt.shape
    return xt.reshape(b, g, heads, wide // heads, s).transpose(0, 4, 1, 2, 3)


def _whole_tiles(heads):
    """``heads`` filled up to whole tiles of 8 sublanes."""
    return heads + -heads % SUBLANES


def _rows(total, chunk):
    """``a [B, S, G, R]`` float32 -> ``[B, G, chunks, R8, C]``: a chunk's
    running sums as lane-dense rows, a head a sublane, filled with zeros up
    to whole tiles of 8 (4 MB in the cell, where ``R`` is 8)."""
    b, s, g, r = total.shape
    rows = total.reshape(b, s // chunk, chunk, g, r).transpose(0, 3, 1, 4, 2)
    return jnp.pad(rows, ((0, 0),) * 3 + ((0, _whole_tiles(r) - r), (0, 0)))


def _from_rows(rows, shape):
    """``_rows``' inverse, ``shape = [B, S, G, R]``."""
    return rows[:, :, :, :shape[3]].transpose(0, 2, 4, 1, 3).reshape(shape)


def _call(kernel, name, xt_shape, heads, states_n, chunk, reverse, interpret):
    """``pallas_call`` over (sequence, group, block of chunks), the blocks of
    a sequence in order (``reverse``: the last first) on one core, with the
    heads' float32 states as the scratch that is carried across them; and
    the block of a grid step in each of the kernels' array layouts."""
    b, g, wide, s = xt_shape
    chunks = s // chunk
    step = step_chunks(chunks)
    blocks = chunks // step
    width, r8 = wide // heads, _whole_tiles(heads)

    def at(i):
        return blocks - 1 - i if reverse else i

    spec = dict(
        x=pl.BlockSpec((None, None, wide, step * chunk), lambda n, j, i: (n, j, 0, at(i))),
        bc=pl.BlockSpec((None, step, chunk, states_n), lambda n, j, i: (n, at(i), 0, j)),
        rows=pl.BlockSpec((None, None, step, r8, chunk), lambda n, j, i: (n, j, at(i), 0, 0)),
        states=pl.BlockSpec((None, None, step, heads, width, states_n),
                            lambda n, j, i: (n, j, at(i), 0, 0, 0)))
    call = functools.partial(
        pl.pallas_call, kernel, grid=(b, g, blocks),
        scratch_shapes=[pltpu.VMEM((heads, width, states_n), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)
    return call, spec


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def forward(xt, total, b, c, heads, chunk, keep=False, interpret=False):
    """``out [B, G, R P, S]`` from ``xt [B, G, R P, S]`` (``_turned``),
    ``total [B, S, G, R]`` float32 (``g`` summed inside each chunk) and ``b,
    c [B, S, G, N]``; with ``keep`` also every chunk's start states ``[B, G,
    chunks, R, P, N]`` float32."""
    batch, g, wide, s = xt.shape
    n, chunks = b.shape[-1], s // chunk
    call, spec = _call(functools.partial(_fwd_kernel, keep=keep), FWD_NAME,
                       xt.shape, heads, n, chunk, False, interpret)
    out_shape, out_specs = [jax.ShapeDtypeStruct(xt.shape, xt.dtype)], [spec["x"]]
    if keep:
        out_shape.append(
            jax.ShapeDtypeStruct((batch, g, chunks, heads, wide // heads, n), _F32))
        out_specs.append(spec["states"])
    return tuple(call(
        in_specs=[spec["x"], spec["bc"], spec["bc"], spec["rows"]],
        out_specs=out_specs, out_shape=out_shape,
    )(xt, by_chunk(b, chunk), by_chunk(c, chunk), _rows(total, chunk)))


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def backward(xt, total, b, c, starts, d_out, heads, chunk, interpret=False):
    """``(dxt, d total, db, dc)``, shaped and typed as the inputs."""
    batch, g, wide, s = xt.shape
    n, chunks = b.shape[-1], s // chunk
    call, spec = _call(_bwd_kernel, BWD_NAME, xt.shape, heads, n, chunk, True, interpret)
    shared = jax.ShapeDtypeStruct((batch, chunks, chunk, g * n), b.dtype)
    dxt, db, dc, drows = call(
        in_specs=[spec["x"], spec["bc"], spec["bc"], spec["rows"], spec["x"], spec["states"]],
        out_specs=[spec["x"], spec["bc"], spec["bc"], spec["rows"]],
        out_shape=[jax.ShapeDtypeStruct(xt.shape, xt.dtype), shared, shared,
                   jax.ShapeDtypeStruct((batch, g, chunks, _whole_tiles(heads), chunk), _F32)],
    )(xt, by_chunk(b, chunk), by_chunk(c, chunk), _rows(total, chunk), d_out, starts)
    return dxt, _from_rows(drows, total.shape), db.reshape(b.shape), dc.reshape(c.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _scan(xt, total, b, c, heads, chunk, interpret):
    return forward(xt, total, b, c, heads, chunk, False, interpret)[0]


def _scan_fwd(xt, total, b, c, heads, chunk, interpret):
    # all the forward call writes, under the name "cell" remat keeps
    # (``attention_pallas._attention_fwd``)
    out, starts = (checkpoint_name(x, KERNEL_RESIDUAL)
                   for x in forward(xt, total, b, c, heads, chunk, True, interpret))
    return out, (xt, total, b, c, starts)


def _scan_bwd(heads, chunk, interpret, residuals, d_out):
    return backward(*residuals, d_out, heads, chunk, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan_turned(xt, g, b, c, heads, chunk, interpret=False):
    """``scan`` on ``x`` and for a result in the kernels' own layout ``[B, G,
    R P, S]``. The running sum of ``g`` inside each chunk (4 MB in the cell)
    and its layout as rows are plain JAX's."""
    batch, s = g.shape[:2]
    total = jnp.cumsum(g.reshape(batch, s // chunk, chunk, *g.shape[2:]), axis=2).reshape(g.shape)
    return _scan(xt, total, b, c, heads, chunk, interpret)


def scan(x, g, b, c, chunk, interpret=False):
    """Mamba-2's recurrence (``sequence.ssd_scan``'s contract) through the
    kernels: ``S`` whole chunks of ``chunk`` positions. The turn of ``x`` and
    of the result is plain JAX's (no copy in the compiled step)."""
    heads = x.shape[3]
    return _unturned(scan_turned(_turned(x), g, b, c, heads, chunk, interpret), heads)


# -- the gate --------------------------------------------------------------------


def step_chunks(chunks: int) -> int:
    """How many chunks a grid step takes: the most of ``STEP_CHUNKS`` that
    divides the sequence's."""
    return next(n for n in STEP_CHUNKS if chunks % n == 0)


def supported(x_shape, n, dtype, chunk) -> bool:
    """The shapes the kernels are written (and compiled, for a described
    chip) for: bfloat16, heads of whole bfloat16 tiles of 16 channels, a
    state ``N`` and a chunk of whole lanes, a length of whole chunks, and a
    backward grid step (its blocks buffered twice, the two float32 states)
    inside half of the kernels' VMEM; the other half is for a chunk's squares
    and what the compiler spills."""
    if len(x_shape) != 5:
        return False
    length, r, p = x_shape[1], x_shape[3], x_shape[4]
    if (dtype != jnp.bfloat16 or p % _BF16_ROWS or n % LANES or chunk % LANES
            or length == 0 or length % chunk):
        return False
    step = step_chunks(length // chunk)
    a_chunk = (3 * chunk * r * p * 2                        # x, d_out, dx
               + 4 * chunk * n * 2                          # b, c and their cotangents
               + 2 * _whole_tiles(r) * chunk * 4            # the rows and theirs
               + r * p * n * 4)                             # the start states
    return 2 * step * a_chunk + 2 * r * p * n * 4 <= _VMEM_LIMIT // 2


def dispatchable(x, g, b, c, chunk) -> bool:
    """TPU backend, shapes the kernels take, and not under a batched
    (vmapped) trace (``attention_pallas.dispatchable``'s policy)."""
    from mpi4dl_tpu.parallel.halo import _is_batch_tracer

    if jax.default_backend() != "tpu" or any(map(_is_batch_tracer, (x, g, b, c))):
        return False
    return (x.dtype == b.dtype == c.dtype and g.dtype == jnp.float32
            and b.shape == c.shape and len(b.shape) == 4
            and supported(tuple(x.shape), b.shape[-1], x.dtype, chunk))
