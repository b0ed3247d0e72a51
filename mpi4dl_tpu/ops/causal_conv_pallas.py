"""Pallas TPU kernels: the causal depthwise convolution with its bias and
SiLU, both passes.

Why: Qwen3-Next's three Gated DeltaNet layers and the Nemotron-H tower's four
Mamba-2 layers put ``y = silu(sum_j w[j] x[t - (L-1) + j] + b)`` (``L`` = 4
taps a channel) over ``bf16[2, 8192, 8192]`` / ``[2, 8192, 6144]`` arrays and
spent 40.9 / 39.9 ms a step in it (ledger, PR 46: ``tok_conv_ms``), 13.6 /
10.0 ms a layer where reading and writing the array once takes 0.65 / 0.49:
``ops/sequence.causal_depthwise_conv1d`` is a pad and a Python ``sum`` of
shifted slices, each tap a sublane-misaligned slice of packed bfloat16 rows,
the bias, the SiLU, its derivative, the taps' gradient's reductions and the
padded sum passes of their own, and the forward ran twice under "cell" remat:
some twenty passes a layer. The operator has to move five: the forward reads
``x`` and writes ``y``; the backward reads ``x`` and ``dy`` and writes ``dx``
(the taps' and the bias' gradients are ``[L, C]`` and ``[C]``). Here it moves
those five and a tile of rows at a block's edge.

The arithmetic is the plain function's on the same operands (``x``, the taps
and the bias in bfloat16 as the configurations state), with less rounding:
every product, the taps' sum in the plain function's order, the bias, the SiLU
and its derivative are float32 on values in VMEM and a result is rounded once
where it is written (the plain path rounds after every product and add), the
taps' and the bias' gradients are summed over positions and sequences in
float32. ``sigmoid`` is ``1 / (1 + exp(-pre))`` with an exact reciprocal.
Nothing is in a lower precision than the plain path.

* the layout is the arrays' own: ``[B, S, C]``, channels along the lanes,
  positions down the sublanes, no copy on either side of a call. A grid step
  is a block of ``Plan.rows`` positions by ``Plan.lanes`` channels (2,048 x
  512 at the cells' shapes: 2 MB of bfloat16, the DMA's rows 1 KB); inside it
  a lane column of 128 channels at a time, ``ROWS`` = 128 positions a loop
  trip (not unrolled): sixteen float32 registers a value, enough independent
  work to fill the vector unit's pipeline between a trip's dependent steps
  (the timings below: a trip of 32 rows takes 1.3 times as long).
* the shifts are taken on float32 values: a trip converts its 128 rows once,
  puts the eight rows before them in front (a whole float32 tile) and takes
  the ``L - 1`` views at sublane offsets 5, 6, 7 as value slices, which the
  compiler makes of sublane rotates and selects
  (``ops/pool_pallas.py`` found nothing faster for the same need). No
  packed bfloat16 row is ever shifted.
* forward (``mpi4dl_causal_conv_fwd``), grid (sequence, block of channels,
  block of positions; the last axis sequential): the eight rows that precede
  a trip are handed from trip to trip in registers and from grid step to
  grid step in VMEM scratch, zeros before a sequence's first block: ``x`` is
  read once, nothing twice.
* backward (``mpi4dl_causal_conv_bwd``), the same grid from a sequence's last
  block to its first, a block's trips from the last to the first: the
  pre-activation is built again from ``x`` (never stored), ``g = dy
  silu'(pre)`` with ``silu'(p) = s (1 + p (1 - s))``, ``dx[t] = sum_j w[j]
  g[t + L - 1 - j]`` reads the rows of ``g`` *after* the trip, which the trip
  before left in registers and the grid step before in scratch (zeros after a
  sequence's end). The rows of ``x`` before a block's first trip come through
  a second ``BlockSpec`` of the same array, the bfloat16 tile of 16 rows that
  ends where the block starts (0.8% more read at 2,048 rows a block). The
  taps' and bias' gradients are summed tile by tile (register adds, no sum
  across sublanes) into an output block ``[L + 1, 8, lanes]`` float32 that
  stays in VMEM across a sequence's blocks; XLA adds the eight rows and the
  sequences (``[B, L + 1, 8, C]``, 1.3 MB).
* the taps and the bias reach the kernels as one ``[8, C]`` float32 array
  (``_packed``: the taps' rows, the bias' row, zeros; the bfloat16 values
  exactly), a register a lane column; a convolution without a bias adds a row
  of zeros. Hence ``2 <= L <= 7``.

``_conv_fwd`` gives the forward call's output the name
``config.KERNEL_RESIDUAL``, which the cell's checkpoint keeps
(``train._cell_ckpt``, PR 44), so the cell's replay in the backward pass has
no use for a second forward call: 268 / 201 MB a layer held from the layer's
forward to its backward.

The callers (``sequence.GatedDeltaNet``, ``sequence.Mamba2``) hand the
kernels whole arrays: a depthwise convolution's channels know nothing of each
other, so ``q``, ``k``, ``v`` (``x``, ``B``, ``C``) are a product of the
projection's columns, a forward and a backward call each. With one call over
``concat(q, k, v)`` as a slice of the wider projection the compiler put a
copy of the slice before each call, a ``reduce-precision`` pass over the kept
output, a copy of ``v`` out of it for the rule's call and a padded
concatenation of the cotangents around the backward call (my sandbox
compiles for a described chip, PR 47): eight more passes a layer than the
kernels make.

Timed alone at the cells' shapes (``x [2, 8192, 8192]`` bfloat16 without a
bias, Qwen3-Next's, and ``[2, 8192, 6144]`` with one, Nemotron-H's; four taps;
TPU v5 lite, jax 0.9.0; jitted, host clock around ``block_until_ready``, least
of five; ``scripts/time_causal_conv.py``; ms forward / backward (the pull-back
of a cotangent alone) / their sum, a layer's passes as the step runs them
since the output is kept; my chip runs, PR 47, calls 1, 2 and 4):

                                        Qwen3-Next's           Nemotron-H's
    plain JAX (its pull-back builds
      the forward again; the step ran
      the forward twice besides)        2.68 / 8.38 / 11.06    2.19 / 6.47 / 8.67
    the kernels as they stand: a trip
      of 128 rows x 128 lanes, both
      loops rolled (call 4)             1.67 / 2.75 / 4.42     1.41 / 2.27 / 3.68
    the same with the four lane
      columns and the backward's first
      trip written out (call 2)         1.64 / 2.66 / 4.30     1.37 / 2.27 / 3.65
    written out so, a trip of 16 x 128  3.52 / 4.28 / 7.80     2.82 / 3.46 / 6.27
    32 x 128                            2.20 / 3.30 / 5.49     1.88 / 2.81 / 4.68
    64 x 128                            1.92 / 2.96 / 4.88     1.54 / 2.40 / 3.94
    256 x 128                           1.71 / 2.86 / 4.57     1.39 / 2.31 / 3.70
    64 x 256                            1.62 / 2.85 / 4.48     1.33 / 2.29 / 3.62
    128 x 256                           1.65 / 2.83 / 4.48     1.41 / 2.44 / 3.85
    128 x 512                           2.02 / 3.41 / 5.43     1.63 / 2.94 / 4.57
    32 x 512                            1.69 / 2.89 / 4.57     1.49 / 2.40 / 3.89

A trip has to be long: its steps depend on each other (convert, shift, the
taps' chain, ``exp``, the reciprocal, the products) and the loop is not
pipelined, so sixteen registers a value keep the vector unit fed where four
leave it waiting (5.49 -> 4.30). A grid step's block hardly matters
(Qwen3-Next's shape, trips of 32 x 128, the sum: 2,048 x 512 5.49, 1,024 x
512 5.65, 512 x 512 5.60, 2,048 x 256 5.46, 1,024 x 256 5.53, 512 x 128
6.14): the DMA is not what bounds the kernels. The host's clock carries the
dispatch and the taps' rows; on the device's clock, in the Qwen3-Next cell's
step, the nine forward and nine backward calls take 3.24 + 5.39 = 8.63 ms
(``causal_conv_kernel_ms``, 57.0% of the 4.92 ms the five passes take at 819
GB/s: ``causal_conv_kernel_roofline``; the forward's two at 498 GB/s, the
backward's three at 448) and the part around them 11.93 where the plain path
took 40.86 (``tok_conv_ms``; my chip run, PR 47, the traced pair of call 3,
the lane columns still written out); in the Nemotron-H cell's, the kernels as
they stand, twelve and twelve calls take 3.22 + 5.48 = 8.70 ms (56.5%) and the
part 12.43 where 39.93 (the traced pair of call 5).

Tried and dropped: the lane columns and the first trip of the backward
written out in Python (eight copies of the trip's body: 3% faster alone, and
0.35 s of tracing a kernel and shape where the rolled loops take 0.03, in
every run's set-up: ``setup_s`` read 5-13% over the parent's in three warm
pairs with it); one call over ``concat(q, k, v)`` on one wide product of its
own (8.19 and 6.45 ms of the convolution's part in a DeltaNet and a Mamba-2
layer alone, ``scripts/time_mixer_parts.py``, where a call a piece reads 5.46
and 4.56 and the plain path 13.46 and 10.05: the copy of ``v`` out of the kept
output, the ``reduce-precision`` pass over all of it and the cotangents'
concatenation stay); the trips and blocks above. Not tried: the shifted views
as ``pltpu.roll`` or as unaligned loads from a float32 scratch
(``ops/pool_pallas.py`` measured both within 3% of value slices), a strided
load that would turn a shift into an address, an approximate reciprocal (a
lower precision).

Dispatch (``dispatchable``): TPU backend, not under ``vmap``, ``x`` and the
taps bfloat16, channels of whole lanes (128), a length of whole trips (128;
the block of a grid step is the most of ``BLOCK_ROWS`` x ``BLOCK_LANES`` that
divides the array), two to seven taps; everything else (the CPU, the tier-1
tests, the tiny cuts at 96 channels or 80 positions, ``vmap``, float32) takes
``sequence.causal_depthwise_conv1d`` with its bias and ``nn.silu``, which is
also the kernels' oracle. No switch. LFM2's ``ShortConv`` (three taps between
two gates, no SiLU) never asks: the compiler fuses its taps and gates into the
projections' products and a custom call would break that.
``tests/test_tpu_compile.py`` compiles both mixers for a described v5e chip
and fails if the kernels are not in the compiled text of both passes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi4dl_tpu.config import KERNEL_RESIDUAL

# The pallas_calls' names: how the kernels are found in a compiled step's
# text and in a profiler trace (the benchmark's readers look for their
# common start, ``mpi4dl_causal_conv``).
FWD_NAME = "mpi4dl_causal_conv_fwd"
BWD_NAME = "mpi4dl_causal_conv_bwd"
HALO = 8          # float32 rows handed from chunk to chunk: a whole tile, so ``taps - 1 <= 8``
_PACKED = 16      # rows of a bfloat16 tile: the block of rows before a backward grid step
ROWS = 128        # positions a loop trip takes: eight bfloat16 tiles, sixteen float32 registers a value
WIDTH = 128       # channels a loop trip takes: a lane column
BLOCK_ROWS = (2048, 1024, 512, 256, 128)  # positions a grid step takes: the most that divides the length
BLOCK_LANES = (512, 256, 128)  # channels a grid step takes: the most that divides the width
_VMEM_LIMIT = 64 * 1024 * 1024
_F32 = jnp.float32


class Plan(NamedTuple):
    """A grid step's block: ``rows`` positions by ``lanes`` channels."""

    rows: int
    lanes: int


# -- a chunk, in plain jnp on VMEM values ----------------------------------------


def _pre(before, cur, w, taps):
    """``(the taps' shifted views of a chunk, its pre-activation)``:
    ``before [HALO, 128]`` the rows that precede ``cur [rows, 128]``, float32;
    ``w`` the taps' rows and the bias' ``[1, 128]``. View ``j`` is the chunk
    ``taps - 1 - j`` positions earlier; the sum runs in the plain function's
    order, in float32."""
    rows = cur.shape[0]
    ext = jnp.concatenate([before, cur], axis=0)
    views = [ext[HALO - (taps - 1) + j:HALO - (taps - 1) + j + rows] for j in range(taps - 1)]
    views.append(cur)
    pre = w[0] * views[0]
    for j in range(1, taps):
        pre = pre + w[j] * views[j]
    return views, pre + w[taps]


def _sigmoid(pre):
    return 1.0 / (1.0 + jnp.exp(-pre))


def _tile_sum(a):
    """``[rows, 128] -> [8, 128]``: the rows' tiles added, register by
    register (no sum across sublanes: XLA adds the eight rows outside)."""
    return jnp.sum(a.reshape(a.shape[0] // HALO, HALO, a.shape[1]), axis=0)


# -- the kernels -----------------------------------------------------------------


def _column_of(c):
    """The lanes of a block's ``c``-th column of ``WIDTH`` channels."""
    return pl.ds(pl.multiple_of(c * WIDTH, WIDTH), WIDTH)


def _rows_of(t):
    return pl.ds(pl.multiple_of(t * ROWS, ROWS), ROWS)


def _fwd_kernel(x_ref, taps_ref, y_ref, carry_ref, *, taps):
    """One (sequence, block of channels, block of positions), the blocks of
    a sequence in order: a lane column of ``WIDTH`` channels at a time,
    ``ROWS`` positions a loop trip (neither loop unrolled), the last ``HALO``
    rows (float32) handed from trip to trip in registers and from grid step
    to grid step in ``carry_ref [HALO, lanes]``, zeros before a sequence's
    first block.

    x_ref, y_ref ``[rows, lanes]``; taps_ref ``[8, lanes]`` float32: the
    taps' rows, then the bias'."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = jnp.zeros(carry_ref.shape, _F32)

    def column(c, _):
        lanes = _column_of(c)
        w = [taps_ref[j:j + 1, lanes] for j in range(taps + 1)]

        def one(t, before):
            at = _rows_of(t)
            cur = x_ref[at, lanes].astype(_F32)
            _, pre = _pre(before, cur, w, taps)
            y_ref[at, lanes] = (pre * _sigmoid(pre)).astype(y_ref.dtype)
            return cur[ROWS - HALO:]

        carry_ref[:, lanes] = lax.fori_loop(0, x_ref.shape[0] // ROWS, one, carry_ref[:, lanes])
        return _

    lax.fori_loop(0, x_ref.shape[1] // WIDTH, column, 0)


def _bwd_kernel(x_ref, before_ref, dy_ref, taps_ref, dx_ref, sums_ref, carry_ref, *, taps):
    """The reverse sweep: one (sequence, block of channels, block of
    positions, the last block first), a lane column at a time, its chunks of
    ``ROWS`` positions from the last to the first. A chunk's pre-activation
    is built again from ``x`` (the rows before a chunk are the tile of 16 rows
    above it; before the block's first chunk they come through
    ``before_ref``, the tile that ends where the block starts; zeros before a
    sequence's start), ``g = dy silu'(pre)`` is formed in float32, ``dx[t] =
    sum_j w[j] g[t + taps - 1 - j]`` reads the ``HALO`` rows of ``g`` after
    the chunk, which the trip before left in registers and the grid step
    before in ``carry_ref`` (zeros after a sequence's end); the taps' and the
    bias' gradients are summed tile by tile into ``sums_ref [taps + 1, 8,
    lanes]`` float32, which stays in VMEM across a sequence's blocks."""
    step, steps = pl.program_id(2), pl.num_programs(2)
    chunks = x_ref.shape[0] // ROWS

    @pl.when(step == 0)
    def _():
        carry_ref[...] = jnp.zeros(carry_ref.shape, _F32)
        sums_ref[...] = jnp.zeros(sums_ref.shape, _F32)

    # the sequence's first block is the sweep's last: nothing precedes it
    started = jnp.where(step == steps - 1, 0.0, 1.0).astype(_F32)

    def column(c, _):
        lanes = _column_of(c)
        w = [taps_ref[j:j + 1, lanes] for j in range(taps + 1)]
        edge = before_ref[:, lanes].astype(_F32)[_PACKED - HALO:] * started

        def one(n, carry):
            after, sums = carry[0], carry[1:]
            t = chunks - 1 - n
            at = _rows_of(t)
            # the tile above the chunk; the block's first chunk reads its own
            # first tile there and takes the rows before the block instead
            tile = pl.ds(pl.multiple_of(jnp.maximum(t * ROWS - _PACKED, 0), _PACKED), _PACKED)
            inside = jnp.where(t == 0, 0.0, 1.0).astype(_F32)
            before = (x_ref[tile, lanes].astype(_F32)[_PACKED - HALO:] * inside
                      + edge * (1.0 - inside))
            views, pre = _pre(before, x_ref[at, lanes].astype(_F32), w, taps)
            s = _sigmoid(pre)
            g = dy_ref[at, lanes].astype(_F32) * (s * (1.0 + pre * (1.0 - s)))
            ext = jnp.concatenate([g, after], axis=0)
            dx = w[taps - 1] * g
            for j in range(taps - 1):
                dx = dx + w[j] * ext[taps - 1 - j:taps - 1 - j + ROWS]
            dx_ref[at, lanes] = dx.astype(dx_ref.dtype)
            sums = [total + _tile_sum(g * view) for total, view in zip(sums, views)]
            return (g[:HALO], *sums, carry[-1] + _tile_sum(g))

        zero = jnp.zeros((HALO, WIDTH), _F32)
        carry = lax.fori_loop(0, chunks, one, (carry_ref[:, lanes],) + (zero,) * (taps + 1))
        carry_ref[:, lanes] = carry[0]
        for j in range(taps + 1):
            sums_ref[j, :, lanes] += carry[1 + j]
        return _

    lax.fori_loop(0, x_ref.shape[1] // WIDTH, column, 0)


# -- the calls -------------------------------------------------------------------


def _packed(kernel, bias):
    """``[8, C]`` float32: the taps' rows, the bias' row, zeros."""
    rows = jnp.concatenate([kernel.astype(_F32), bias.astype(_F32)[None]], axis=0)
    return jnp.pad(rows, ((0, HALO - rows.shape[0]), (0, 0)))


def _call(kernel, name, shape, plan, interpret):
    """``pallas_call`` over (sequence, block of channels, block of positions),
    a sequence's blocks one after the other on one core, with the ``HALO``
    float32 rows handed across them as scratch."""
    batch, length, channels = shape
    return functools.partial(
        pl.pallas_call, kernel, grid=(batch, channels // plan.lanes, length // plan.rows),
        scratch_shapes=[pltpu.VMEM((HALO, plan.lanes), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def forward(x, packed, taps, plan, interpret=False):
    """``silu(conv(x) + bias) [B, S, C]`` from ``x [B, S, C]`` and ``packed
    [8, C]`` (``_packed``)."""
    block = pl.BlockSpec((None, plan.rows, plan.lanes), lambda n, c, i: (n, i, c))
    return _call(functools.partial(_fwd_kernel, taps=taps), FWD_NAME, x.shape, plan, interpret)(
        in_specs=[block, pl.BlockSpec((HALO, plan.lanes), lambda n, c, i: (0, c))],
        out_specs=block, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x, packed)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def backward(x, dy, packed, taps, plan, interpret=False):
    """``(dx [B, S, C], sums [B, taps + 1, 8, C] float32)``: the input's
    cotangent, and the taps' and the bias' summed over a sequence's positions
    but for the eight rows of a tile."""
    batch, length, channels = x.shape
    last = length // plan.rows - 1
    tiles = plan.rows // _PACKED  # the tiles of 16 rows a block holds

    def block(n, c, i):
        return (n, last - i, c)

    def before(n, c, i):  # the tile that ends where the block starts (the first: masked)
        return (n, jnp.maximum((last - i) * tiles - 1, 0), c)

    spec = pl.BlockSpec((None, plan.rows, plan.lanes), block)
    return _call(functools.partial(_bwd_kernel, taps=taps), BWD_NAME, x.shape, plan, interpret)(
        in_specs=[spec, pl.BlockSpec((None, _PACKED, plan.lanes), before), spec,
                  pl.BlockSpec((HALO, plan.lanes), lambda n, c, i: (0, c))],
        out_specs=[spec, pl.BlockSpec((None, taps + 1, HALO, plan.lanes),
                                      lambda n, c, i: (n, 0, 0, c))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((batch, taps + 1, HALO, channels), _F32)],
    )(x, x, dy, packed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(x, kernel, bias, plan, interpret):
    return forward(x, _packed(kernel, bias), kernel.shape[0], plan, interpret)


def _conv_fwd(x, kernel, bias, plan, interpret):
    # what the forward call writes, under the name "cell" remat keeps
    # (``attention_pallas._attention_fwd``)
    y = checkpoint_name(
        forward(x, _packed(kernel, bias), kernel.shape[0], plan, interpret), KERNEL_RESIDUAL)
    return y, (x, kernel, bias)


def _conv_bwd(plan, interpret, residuals, dy):
    x, kernel, bias = residuals
    taps = kernel.shape[0]
    dx, sums = backward(x, dy, _packed(kernel, bias), taps, plan, interpret)
    sums = jnp.sum(sums, axis=(0, 2))
    return dx, sums[:taps].astype(kernel.dtype), sums[taps].astype(bias.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def conv_silu(x, kernel, bias=None, plan=None, interpret=False):
    """``silu(sequence.causal_depthwise_conv1d(x, kernel) + bias)`` through
    the kernels: ``x [B, S, C]``, ``kernel [L, C]``, ``bias [C]`` or None."""
    if bias is None:
        bias = jnp.zeros(x.shape[-1:], kernel.dtype)
    return _conv(x, kernel, bias, plan or plan_for(x.shape), interpret)


# -- the gate --------------------------------------------------------------------


def plan_for(shape) -> "Plan | None":
    """The block of a grid step: the most rows of ``BLOCK_ROWS`` that divide
    the length by the most lanes of ``BLOCK_LANES`` that divide the width;
    None where none does."""
    _, length, channels = shape
    rows = next((n for n in BLOCK_ROWS if length and length % n == 0), None)
    lanes = next((n for n in BLOCK_LANES if channels and channels % n == 0), None)
    return None if rows is None or lanes is None else Plan(rows, lanes)


def supported(x_shape, kernel_shape, dtype) -> bool:
    """The shapes the kernels are written (and compiled, for a described
    chip) for: bfloat16 ``[B, S, C]``, channels of whole lanes, a length of
    whole blocks, two to seven taps (the taps' rows and the bias' fill one
    tile of eight; ``taps - 1`` rows fit the tile handed from chunk to
    chunk)."""
    return (len(x_shape) == 3 and len(kernel_shape) == 2 and dtype == jnp.bfloat16
            and 2 <= kernel_shape[0] < HALO and kernel_shape[1] == x_shape[2]
            and plan_for(x_shape) is not None)


def dispatchable(x, kernel) -> bool:
    """TPU backend, shapes the kernels take, and not under a batched
    (vmapped) trace (``attention_pallas.dispatchable``'s policy)."""
    from mpi4dl_tpu.parallel.halo import _is_batch_tracer

    if jax.default_backend() != "tpu" or _is_batch_tracer(x) or _is_batch_tracer(kernel):
        return False
    return x.dtype == kernel.dtype and supported(tuple(x.shape), tuple(kernel.shape), x.dtype)
