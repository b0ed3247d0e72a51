"""Pallas TPU kernels: the chunked gated delta rule, both passes.

Why: Qwen3-Next's three Gated DeltaNet layers at 2 x 8,192 positions (16 key
heads of 128, two value heads of 128 each) spent 334 ms of an 820 ms step in
``ops/sequence._chunked_rule`` (ledger, PR 37): every chunk's ``[64, 64]``
float32 squares (the decays, the system, ten products of its inverse's
series) went through HBM as 134 MB arrays, the terms four times forward and
the state's hand-on, a ``lax.scan`` of 128 trips with the 4 MB state in HBM,
three times, because two ``jax.checkpoint``s bought the backward's memory
with forwards. Here a chunk's squares and the carried state live and die in
VMEM: the rule's HBM traffic is its inputs, its output and what the backward
keeps.

The arithmetic is the plain path's and the configuration's
(``sequence.gated_delta_rule``'s docstring has the formulas): products take
bfloat16 operands and accumulate in float32; ``G``, the decays, the system
``A``, its inverse ``T`` and the carried state are float32; every exponent
is of a difference <= 0; chunks of 64. ``_chunk`` is ``_chunk_terms`` and
``hand_on`` for one chunk of one key head on 2-D values, the same roundings
at the same places. One thing differs: ``T = (I + A)^-1`` is not the series
``(I + L)(I + L^2)...`` (ten float32 products a system) but the diagonal
blocks of 16 by substitution on the vector unit and two doublings by block
products over the rows that change (``unit_lower_inverse``), float32 at
precision "highest" throughout and as close to a float64 inverse (3.4e-7
over 256 systems of the cell's kind, both routes). With the series the
kernels' bfloat16 output on the chip was the plain path's to the last bit;
with the blocks it differs by 3e-4 (relative L2), the cotangents by
0.003-0.004 either way.

* forward (``mpi4dl_delta_rule_fwd``), grid (batch, key head, block of 8
  chunks; the last axis sequential): the block's chunks in order, the key
  head's value heads side by side (they share ``q``, ``k``, ``k k^T`` and
  ``q k^T``, and their chains are independent work for the scheduler). The
  states ``[R, 128, 128]`` float32 stay in VMEM scratch across a head's
  blocks. Called for a backward pass it also writes every chunk's start
  state (bfloat16, the operand the products took: 268 MB a layer) and every
  system's inverse (float32, 134 MB).
* backward (``mpi4dl_delta_rule_bwd``), the same grid from the last block to
  the first: ``dS`` is carried in VMEM as ``S`` was; a chunk's backward is
  ``jax.vjp`` of ``_chunk`` taken inside the kernel body on VMEM values (the
  chunk's squares are built again, its start state and ``T`` come from the
  forward; ``dA = -T^T dT T^T`` in float32, the plain path's rule), so there
  is one statement of the arithmetic. A product's cotangent is rounded to
  bfloat16 before the transposed products, which is what the chip's default
  precision does to the plain path's.
* outside, in plain JAX: the running sums of ``g`` inside each chunk and
  their transpose (2 MB each), and the layout of ``G`` and ``beta`` as
  lane-dense rows ``[batch, key head, chunk, 2R, 64]``; a chunk's columns
  ``[64, 1]`` are taken from the rows in the kernel (a masked lane sum), so
  no array with a minor dimension of 2 crosses HBM. ``q``, ``k``, ``v`` and
  the output are read and written in the layout the mixer has them in.

The plain path's two ``jax.checkpoint``s play no part here: under the cell's
"cell" remat the rule runs forward (keeping the states and inverses), then
backward. ``_rule_fwd`` gives all the forward call writes (the output, the
start states, the inverses) the name ``config.KERNEL_RESIDUAL``, which the
cell's checkpoint keeps (``train._cell_ckpt``, PR 44), so the cell's replay
in the backward pass has no use for a second forward call: 536 MB a layer
held from the layer's forward to its backward.

Timed alone at the cell's shape (``q, k [2, 8192, 16, 128]``, ``v [2, 8192,
16, 2, 128]`` bfloat16, ``g, beta [2, 8192, 16, 2]`` float32 as a fresh
model makes them; TPU v5 lite, jax 0.9.0; jitted, host clock around
``block_until_ready``, least of five; ``scripts/time_delta_rule.py``; ms
forward / gradient (the forward that keeps the backward's residuals, then
the backward) / a layer's passes as the step runs them = forward +
gradient; my chip runs, PR 38):

    plain JAX (``_chunked_rule``; its gradient runs the rule
      forward, again, its terms again, backward)        23.68 / 59.10 / 82.79
    one fused kernel a pass, 8 chunks a grid step, T by the
      series (ten float32 products), T and states kept  15.84 / 24.50 / 40.33
        the grid step's loop unrolled by 2              15.15 / 23.50 / 38.66
        unrolled whole (8)                              14.64 / 22.38 / 37.01
        16 chunks a grid step                           15.75 / 24.47 / 40.22
        4 chunks a grid step                            15.67 / 24.52 / 40.19
        the pair's systems as one block-diagonal
          128 x 128 matrix (five-product series of 128) 16.16 / 24.95 / 41.11
        T not kept, the backward computes it again      15.77 / 34.90 / 50.67
    T by blocks of 16 (substitution, then block products
      over all 64 rows)                                 11.86 / 20.75 / 32.61
        the pair's systems as one 128 x 128 matrix      14.88 / 23.62 / 38.50
    T by blocks of 16, products over the 32 rows that
      change: what this module is                       10.67 / 19.54 / 30.20
        unrolled by 2                                   10.29 / 18.77 / 29.06
        unrolled by 4                                   10.18 / 18.11 / 28.29
        unrolled whole (8)                               9.96 / 17.52 / 27.48
        4 chunks a grid step, unrolled whole            10.09 / 18.17 / 28.26
        16 chunks a grid step, unrolled by 2            10.29 / 18.56 / 28.85
        unrolled by 2, T not kept                       10.34 / 23.66 / 34.00
        blocks of 8                                     12.58 / 21.41 / 34.00
        blocks of 32                                    10.82 / 19.61 / 30.43
    diagnostics (wrong or lower arithmetic, never shipped):
        T = I - A, no inverse at all                     5.74 / 14.73 / 20.47
        the series at the matrix unit's default
          precision (T off by 1.5e-4, not float32)       9.94 / 17.65 / 27.59

So of the first plan's 15.8 ms forward 10.1 were the inverse; by blocks it
is 4.9, and 5.7 ms are the rule's other fourteen products a chunk and key
head and the vector unit's work around them; the backward kernel alone is
some 8 ms. **The grid step's loop is not unrolled though unrolling it whole
is 2.7 ms a layer faster**: the step's trace and lowering pay for every
copy of a chunk's body (fifteen substitution steps and a dozen products a
value head, again inside the backward's ``jax.vjp``). With the eight chunks
unrolled and each of the three layers tracing its own calls the cell's warm
first step took 39.1-39.6 s where the parent's takes 16.5-18.2 (``setup_s``
59.8-62.3 against 39.4-42.7, my chip runs, PR 38: past the benchmark's 10%
bound); with one body a loop, and ``forward`` / ``backward`` under
``jax.jit`` so that the layers share one trace and one lowering, the step's
trace and lowering take 3.5 s in the sandbox against the plain path's 3.2
(11.9 unrolled). The least work, the recurrence's three products a position and
value head, is 0.155 TFLOP a layer's three passes, 0.8 ms at the chip's 197
TFLOP/s: the kernels reach 3% of it, the plain path 1%; the products are
``64 x 64 x 128`` and ``64 x 128 x 128``, a fraction of the 128 x 128 unit
each, and a chunk's chain is long. What is left on the table is in PERF.md
section 7.

Dispatch (``dispatchable``): TPU backend, not under ``vmap``, bfloat16, key
and value dims of whole lanes (128 in the cell), a length of whole chunks,
a grid step that fits VMEM; everything else (the CPU, the tier-1 tests,
the tiny cut at key dim 16, ``vmap``) takes ``sequence._chunked_rule``,
which is also the kernels' oracle. No switch. ``tests/test_tpu_compile.py``
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

# The pallas_calls' names: how the kernels are found in a compiled step's
# text and in a profiler trace (the benchmark's readers look for their
# common start, ``mpi4dl_delta_rule``).
FWD_NAME = "mpi4dl_delta_rule_fwd"
BWD_NAME = "mpi4dl_delta_rule_bwd"
LANES = 128
STEP_CHUNKS = (8, 4, 2, 1)  # chunks a grid step takes, the most that divides
BLOCK = 16                  # rows of a system's diagonal blocks inverted by substitution
_VMEM_LIMIT = 64 * 1024 * 1024
_F32 = jnp.float32
_FORMS = {"nn": ((1,), (0,)), "nt": ((1,), (1,)), "tn": ((0,), (0,))}  # a @ b, a @ b.T, a.T @ b


# -- a chunk, in plain jnp on VMEM values --------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def dot(a, b, form="nn"):
    """``a @ b`` ("nn"), ``a @ b.T`` ("nt") or ``a.T @ b`` ("tn"): operands
    as they are (bfloat16 in the cell), accumulated in float32. The backward
    rounds the cotangent to the operands' dtype first, which is what the
    plain path's products do on the chip (a float32 operand of a product at
    default precision is taken in bfloat16). ``ssd_scan_pallas``'s chunk is
    made of the same products."""
    return lax.dot_general(a, b, (_FORMS[form], ((), ())), preferred_element_type=_F32)


def _dot_bwd(form, operands, ct):
    a, b = operands
    ct = ct.astype(a.dtype)
    if form == "nn":
        da, db = dot(ct, b, "nt"), dot(a, ct, "tn")
    elif form == "nt":
        da, db = dot(ct, b, "nn"), dot(ct, a, "tn")
    else:
        da, db = dot(b, ct, "nt"), dot(a, ct, "nn")
    return da.astype(a.dtype), db.astype(b.dtype)


dot.defvjp(lambda a, b, form: (dot(a, b, form), (a, b)), _dot_bwd)


def _exact(a, b, form="nn"):
    """A float32 product good to float32 (six passes of the matrix unit)."""
    return lax.dot_general(a, b, (_FORMS[form], ((), ())), precision=lax.Precision.HIGHEST,
                           preferred_element_type=_F32)


def _iota(size, axis):
    return lax.broadcasted_iota(jnp.int32, (size, size), axis)


def unit_lower_inverse(system, block=BLOCK):
    """``(I + A)^-1`` of strictly lower triangular ``A [C, C]`` float32, good
    to float32: the diagonal blocks of ``block`` rows by substitution on the
    vector unit (all of them at once, ``block - 1`` rank-one updates), then
    merged pair by pair, ``[[T1, 0], [-T2 A21 T1, T2]]``: two float32
    products a doubling, over the second blocks' rows alone (the others do
    not change)."""
    size = system.shape[-1]
    row, col = _iota(size, 0), _iota(size, 1)
    first = row - (row & (block - 1))  # the first column of the row's diagonal block
    x = jnp.where(row == col, 1.0, 0.0)
    for m in range(block - 1):
        below = jnp.sum(jnp.where(col == first + m, system, 0.0), axis=1, keepdims=True)
        pivot = jnp.broadcast_to(
            x.reshape(size // block, block, size)[:, m:m + 1], (size // block, block, size))
        x = x - below * pivot.reshape(size, size)
    width = block
    while width < size:  # ``x`` holds the inverses of the diagonal blocks of ``width``
        pair = ~(2 * width - 1)
        off = jnp.where(((row & pair) == (col & pair)) & ((row & width) != (col & width)),
                        system, 0.0)
        second = [slice(at, at + width) for at in range(width, size, 2 * width)]
        t2 = jnp.concatenate([x[rows] for rows in second], axis=0)
        y = _exact(jnp.concatenate([off[rows] for rows in second], axis=0), x)   # A21 T1
        zero = jnp.zeros((width, size), _F32)
        spread = jnp.concatenate(  # A21 T1 at the second blocks' rows, zeros at the first's
            [part for i in range(len(second)) for part in (zero, y[i * width:(i + 1) * width])],
            axis=0)
        t2 = t2 - _exact(t2, spread)                                              # - T2 A21 T1
        x = jnp.concatenate(
            [part for i, rows in enumerate(second)
             for part in (x[rows.start - width:rows.start], t2[i * width:(i + 1) * width])],
            axis=0)
        width *= 2
    return x


@jax.custom_vjp
def _solved(system, solve):
    """``solve``, which is ``(I + system)^-1``, as a function of ``system``:
    the backward keeps the inverse alone, ``dA = -T^T dT T^T`` in float32."""
    return solve


def _solved_bwd(solve, ct):
    return -_exact(solve, _exact(ct, solve, "nt"), "tn"), jnp.zeros_like(solve)


_solved.defvjp(lambda system, solve: (solve, solve), _solved_bwd)


def _chunk(q, k, values, rows, states, solves=None, *, dtype):
    """One chunk of ``C`` positions of one key head with its ``R`` value
    heads, the arithmetic of ``sequence._chunk_terms`` and
    ``_chunked_rule.hand_on`` on 2-D values: ``q, k [C, D]`` float32 (the
    cell's bfloat16 numbers), ``values`` ``R x [C, E]`` float32, ``rows
    [2R, C]`` float32 (the running sums ``G`` of the chunk's ``g`` for every
    value head, then its ``beta``), ``states`` ``R x [D, E]`` float32 at the
    chunk's start, ``solves`` ``R x [C, C]`` (the systems' inverses where a
    forward pass kept them) -> ``(outputs R x [C, E], states at the end),
    (starts in ``dtype``, inverses)``. Products take operands in ``dtype``
    and accumulate in float32; every exponent is of a difference <= 0."""
    size, heads = q.shape[0], len(values)
    at_row, at_col = _iota(size, 0), _iota(size, 1)
    diagonal, strictly = at_row == at_col, at_row > at_col
    last = lax.broadcasted_iota(jnp.int32, (1, size), 1) == size - 1
    qd, kd = q.astype(dtype), k.astype(dtype)
    kk, qk = dot(kd, kd, "nt"), dot(qd, kd, "nt")

    def column(row):  # [1, C] -> [C, 1]: exact, no transpose
        return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1, keepdims=True)

    # Every value head's system and its inverse first, then the heads' products:
    # the compiler schedules in program order, and with one loop over the heads
    # a forward call took 9.09 ms in the cell's step where this order takes 8.33
    # (my chip runs, PR 38).
    columns, decays, systems = [], [], []
    for r in range(heads):
        total_row, beta_row = rows[r:r + 1], rows[heads + r:heads + r + 1]
        total, beta = column(total_row), column(beta_row)
        decay = jnp.exp(jnp.where(at_row >= at_col, total - total_row, -jnp.inf))
        columns.append((total_row, total, beta))
        decays.append(decay)
        systems.append(jnp.where(strictly, beta * kk * decay, 0.0))
    if solves is None:
        solves = [unit_lower_inverse(lax.stop_gradient(a)) for a in systems]

    outs, ends, starts, kept = [], [], [], []
    for r in range(heads):
        (total_row, total, beta), decay = columns[r], decays[r]
        solve = _solved(systems[r], solves[r])
        within = (qk * decay).astype(dtype)                       # incl. the diagonal
        grown = jnp.exp(total)                                    # exp(G_c)
        whole = jnp.sum(jnp.where(last, total_row, 0.0), axis=1, keepdims=True)  # G_C [1, 1]
        to_end = jnp.exp(whole - total)                           # exp(G_C - G_c)
        v_beta = (values[r] * beta).astype(dtype)
        k_beta = (k * (beta * grown)).astype(dtype)
        solve_d = solve.astype(dtype)
        u = dot(solve_d, v_beta)
        w = dot(solve_d, k_beta).astype(dtype)
        k_end = (k * to_end).astype(dtype)
        q_grown = (q * grown).astype(dtype)
        start = states[r].astype(dtype)
        fresh = (u - dot(w, start)).astype(dtype)
        end = jnp.broadcast_to(jnp.exp(whole), (1, states[r].shape[1]))
        ends.append(states[r] * end + dot(k_end, fresh, "tn"))
        outs.append(dot(q_grown, start) + dot(within, fresh))
        starts.append(start)
        kept.append(solve)
    return (outs, ends), (starts, kept)


# -- the kernels ---------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, rows_ref, o_ref, *rest, heads, keep):
    """One (batch, key head, block of chunks): the block's chunks in order
    (a loop, not unrolled: the module's docstring says what that costs and
    saves), the key head's ``R`` value heads side by side. The states ``[R,
    D, E]`` float32 stay in VMEM scratch across a head's blocks, zero before
    the first.

    q_ref, k_ref ``[n, C, D]``; v_ref, o_ref ``[n, C, R E]``; rows_ref
    ``[n, 2R, C]`` float32; with ``keep`` also starts_ref ``[n, R, D, E]``
    (every chunk's start state, in the products' dtype) and solve_ref
    ``[n, R, C, C]`` float32 (every system's inverse): the backward's."""
    state_ref = rest[-1]
    width = v_ref.shape[-1] // heads
    lanes = [slice(r * width, (r + 1) * width) for r in range(heads)]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, _F32)

    def one(j, states):
        (outs, states), (starts, solves) = _chunk(
            q_ref[j].astype(_F32), k_ref[j].astype(_F32),
            [v_ref[j, :, lane].astype(_F32) for lane in lanes], rows_ref[j], states,
            dtype=v_ref.dtype)
        for r, lane in enumerate(lanes):
            o_ref[j, :, lane] = outs[r].astype(o_ref.dtype)
            if keep:
                rest[0][j, r] = starts[r]
                rest[1][j, r] = solves[r]
        return states

    states = lax.fori_loop(0, q_ref.shape[0], one, [state_ref[r] for r in range(heads)])
    for r in range(heads):
        state_ref[r] = states[r]


def _bwd_kernel(q_ref, k_ref, v_ref, rows_ref, do_ref, starts_ref, solve_ref,
                dq_ref, dk_ref, dv_ref, drows_ref, dstate_ref, *, heads):
    """The reverse sweep: one (batch, key head, block of chunks, the last
    block first), the block's chunks from its last to its first; ``dS``
    ``[R, D, E]`` float32 stays in VMEM scratch as the forward's ``S`` does.
    A chunk's backward is ``jax.vjp`` of ``_chunk`` on VMEM values: the
    chunk's squares are built again from its inputs, its start state and
    the system's inverse come from the forward pass."""
    width = v_ref.shape[-1] // heads
    lanes = [slice(r * width, (r + 1) * width) for r in range(heads)]
    dtype = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros(dstate_ref.shape, _F32)

    def one(t, dstates):
        j = q_ref.shape[0] - 1 - t
        solves = [solve_ref[j, r] for r in range(heads)]
        _, pull = jax.vjp(
            lambda *inputs: _chunk(*inputs, solves, dtype=dtype)[0],
            q_ref[j].astype(_F32), k_ref[j].astype(_F32),
            [v_ref[j, :, lane].astype(_F32) for lane in lanes], rows_ref[j],
            [starts_ref[j, r].astype(_F32) for r in range(heads)])
        dq, dk, dvalues, drows, dstates = pull(
            ([do_ref[j, :, lane].astype(_F32) for lane in lanes], dstates))
        dq_ref[j] = dq.astype(dq_ref.dtype)
        dk_ref[j] = dk.astype(dk_ref.dtype)
        for r, lane in enumerate(lanes):
            dv_ref[j, :, lane] = dvalues[r].astype(dv_ref.dtype)
        drows_ref[j] = drows
        return dstates

    dstates = lax.fori_loop(0, q_ref.shape[0], one, [dstate_ref[r] for r in range(heads)])
    for r in range(heads):
        dstate_ref[r] = dstates[r]


# -- layouts and calls -----------------------------------------------------------


def by_chunk(x, chunk):
    """``[B, S, ...] -> [B, S / chunk, chunk, everything else]``: no copy
    (``ssd_scan_pallas`` lays its ``b`` and ``c`` out the same way)."""
    return x.reshape(x.shape[0], x.shape[1] // chunk, chunk, -1)


def _rows(total, beta, chunk):
    """``G`` and ``beta`` ``[B, S, H, R]`` float32 -> ``[B, H, chunks, 2R, C]``:
    a chunk's per-position numbers as lane-dense rows, ``G`` of every value
    head and then ``beta`` (4 MB in the cell)."""
    both = jnp.concatenate([total, beta], axis=-1)              # [B, S, H, 2R]
    b, s, h, n = both.shape
    return both.reshape(b, s // chunk, chunk, h, n).transpose(0, 3, 1, 4, 2)


def _from_rows(rows, shape):
    """``_rows``' inverse: ``(G's, beta's)``, each ``shape = [B, S, H, R]``."""
    b, h, chunks, n, chunk = rows.shape
    both = rows.transpose(0, 2, 4, 1, 3).reshape(b, chunks * chunk, h, n)
    return both[..., :shape[-1]], both[..., shape[-1]:]


def _call(kernel, name, shapes, chunk, reverse, interpret):
    """``pallas_call`` over (batch, key head, block of chunks), the blocks
    of a head in order (``reverse``: the last first) on one core, with the
    ``[R, D, E]`` float32 scratch that is carried across them; and the block
    of a grid step in each of the kernels' array layouts."""
    (b, s, h, d), (r, e) = shapes[0], shapes[1][3:]
    chunks = s // chunk
    step = step_chunks(chunks)
    blocks = chunks // step

    def at(i):
        return blocks - 1 - i if reverse else i

    spec = dict(
        keys=pl.BlockSpec((None, step, chunk, d), lambda n, j, i: (n, at(i), 0, j)),
        values=pl.BlockSpec((None, step, chunk, r * e), lambda n, j, i: (n, at(i), 0, j)),
        rows=pl.BlockSpec((None, None, step, 2 * r, chunk), lambda n, j, i: (n, j, at(i), 0, 0)),
        states=pl.BlockSpec((None, None, step, r, d, e), lambda n, j, i: (n, j, at(i), 0, 0, 0)),
        solves=pl.BlockSpec((None, None, step, r, chunk, chunk),
                            lambda n, j, i: (n, j, at(i), 0, 0, 0)))
    call = functools.partial(
        pl.pallas_call, kernel, grid=(b, h, blocks),
        scratch_shapes=[pltpu.VMEM((r, d, e), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)
    return call, spec


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def forward(q, k, v, total, beta, chunk, keep=False, interpret=False):
    """``out [B, S, H, R, E]`` from ``q, k [B, S, H, D]``, ``v [B, S, H, R,
    E]`` and ``total`` (``g`` summed inside each chunk), ``beta [B, S, H, R]``
    float32; with ``keep`` also every chunk's start state ``[B, H, chunks, R,
    D, E]`` in ``v``'s dtype and every system's inverse ``[B, H, chunks, R, C,
    C]`` float32."""
    b, s, h, d = q.shape
    r, e = v.shape[3:]
    chunks = s // chunk
    call, spec = _call(functools.partial(_fwd_kernel, heads=r, keep=keep), FWD_NAME,
                       (q.shape, v.shape), chunk, False, interpret)
    out_shape = [jax.ShapeDtypeStruct((b, chunks, chunk, h * r * e), v.dtype)]
    out_specs = [spec["values"]]
    if keep:
        out_shape += [jax.ShapeDtypeStruct((b, h, chunks, r, d, e), v.dtype),
                      jax.ShapeDtypeStruct((b, h, chunks, r, chunk, chunk), _F32)]
        out_specs += [spec["states"], spec["solves"]]
    out, *kept = call(
        in_specs=[spec["keys"], spec["keys"], spec["values"], spec["rows"]],
        out_specs=out_specs, out_shape=out_shape,
    )(by_chunk(q, chunk), by_chunk(k, chunk), by_chunk(v, chunk), _rows(total, beta, chunk))
    return (out.reshape(v.shape), *kept)


@functools.partial(jax.jit, static_argnums=(8, 9))
def backward(q, k, v, total, beta, starts, solves, d_out, chunk, interpret=False):
    """``(dq, dk, dv, d total, d beta)``, shaped and typed as the inputs."""
    b, s, h, d = q.shape
    r, e = v.shape[3:]
    chunks = s // chunk
    call, spec = _call(functools.partial(_bwd_kernel, heads=r), BWD_NAME,
                       (q.shape, v.shape), chunk, True, interpret)
    keys = jax.ShapeDtypeStruct((b, chunks, chunk, h * d), q.dtype)
    dq, dk, dv, drows = call(
        in_specs=[spec["keys"], spec["keys"], spec["values"], spec["rows"], spec["values"],
                  spec["states"], spec["solves"]],
        out_specs=[spec["keys"], spec["keys"], spec["values"], spec["rows"]],
        out_shape=[keys, keys, jax.ShapeDtypeStruct((b, chunks, chunk, h * r * e), v.dtype),
                   jax.ShapeDtypeStruct((b, h, chunks, 2 * r, chunk), _F32)],
    )(by_chunk(q, chunk), by_chunk(k, chunk), by_chunk(v, chunk), _rows(total, beta, chunk),
      by_chunk(d_out, chunk), starts, solves)
    d_total, d_beta = _from_rows(drows, beta.shape)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), d_total, d_beta


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, total, beta, chunk, interpret):
    return forward(q, k, v, total, beta, chunk, False, interpret)[0]


def _rule_fwd(q, k, v, total, beta, chunk, interpret):
    # all the forward call writes, under the name "cell" remat keeps
    # (``attention_pallas._attention_fwd``)
    out, starts, solves = (checkpoint_name(x, KERNEL_RESIDUAL)
                           for x in forward(q, k, v, total, beta, chunk, True, interpret))
    return out, (q, k, v, total, beta, starts, solves)


def _rule_bwd(chunk, interpret, residuals, d_out):
    return backward(*residuals, d_out, chunk, interpret)


_rule.defvjp(_rule_fwd, _rule_bwd)


def rule(q, k, v, g, beta, chunk, interpret=False):
    """The gated delta rule (``sequence.gated_delta_rule``'s contract)
    through the kernels: ``S`` whole chunks of ``chunk`` positions. The
    running sum of ``g`` inside each chunk (2 MB in the cell) and its
    transpose are plain JAX's."""
    b, s = g.shape[:2]
    total = jnp.cumsum(g.reshape(b, s // chunk, chunk, *g.shape[2:]), axis=2).reshape(g.shape)
    return _rule(q, k, v, total, beta, chunk, interpret)


# -- the gate --------------------------------------------------------------------


def step_chunks(chunks: int) -> int:
    """How many chunks a grid step takes: the most of ``STEP_CHUNKS`` that
    divides the sequence's."""
    return next(n for n in STEP_CHUNKS if chunks % n == 0)


def supported(k_shape, v_shape, dtype, chunk) -> bool:
    """The shapes the kernels are written (and compiled, for a described
    chip) for: bfloat16, key and value dims of whole lanes, a length of
    whole chunks, and a backward grid step (its blocks buffered twice, the
    two float32 states) inside half of the kernels' VMEM; the other half is
    for a chunk's squares and what the compiler spills."""
    if len(k_shape) != 4 or len(v_shape) != 5:
        return False
    length, d = k_shape[1], k_shape[3]
    r, e = v_shape[3:]
    if dtype != jnp.bfloat16 or d % LANES or e % LANES or length == 0 or length % chunk:
        return False
    step = step_chunks(length // chunk)
    a_chunk = (2 * 2 * chunk * d * 2            # q, k and their cotangents
               + 3 * chunk * r * e * 2          # v, d_out, dv
               + 2 * 2 * r * LANES * 4          # the rows and theirs
               + r * d * e * 2 + r * chunk * LANES * 4)  # the start states, the inverses
    return 2 * step * a_chunk + 2 * r * d * e * 4 <= _VMEM_LIMIT // 2


def dispatchable(q, k, v, g, beta, chunk) -> bool:
    """TPU backend, shapes the kernels take, and not under a batched
    (vmapped) trace (``attention_pallas.dispatchable``'s policy)."""
    from mpi4dl_tpu.parallel.halo import _is_batch_tracer

    if jax.default_backend() != "tpu" or any(map(_is_batch_tracer, (q, k, v, g, beta))):
        return False
    return q.dtype == k.dtype == v.dtype and supported(
        tuple(k.shape), tuple(v.shape), v.dtype, chunk)
