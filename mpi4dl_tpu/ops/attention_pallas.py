"""Pallas TPU kernels: fused causal grouped-query attention, both passes.

Why: LFM2-8B-A1B's two attention layers at 8,192 positions (32 query heads
of 64 over 8 key-value heads) took 144 ms of a 487 ms step as plain JAX
(``ops/sequence.blocked_causal_attention``): every block's float32 scores
went to HBM and back, 4.3 GB a layer-pass, three passes a layer (forward,
the "cell" remat's forward again, the backward's recomputation). Here a
block's scores, probabilities and ``ds`` live and die in VMEM. Since PR 40
the same kernels serve the one attention layer of the Qwen3-Next cell (16
query heads of 256 over 2 key-value heads, two sequences) and of the
Nemotron-H cell (32 of 128 over 2), 105 and 149 ms a step on the plain path:
what differs between the three is the plan (``plan_for``), taken from head
dim, heads a group and length.

The arithmetic is the plain path's and the configuration's: bfloat16
operands, every product accumulated in float32, float32 maximum / sum /
log-sum-exp, ``delta = sum(d_out * out)`` and ``dp - delta`` in float32,
``p`` and ``ds`` rounded to bfloat16 only as operands of the next product.
The scale ``D^-0.5`` is a power of two at D = 64 and 256, so it is folded
into the keys (and into ``dk`` at the end) exactly; at D = 128 it is not
(2^-3.5), and the float32 scores and ``ds`` take it, as on the plain path
(``_folds``).

Layout: keys run down the sublanes and queries along the lanes (scores
``[keys, queries]``), so a query's running maximum, sum, log-sum-exp and
``delta`` are lane-dense rows ``[1, block]``, the reductions over keys are
elementwise across vregs (no cross-lane reduce, no ``[block, 128]`` copies
of a row statistic), and the accumulators ``[D, block]`` and every block
that crosses HBM but a key block ``[block, D]`` fill their lanes. XLA
brings q, k, v and d_out into that blocked, transposed form and the results
back (0.6 ms of device time a layer's three passes, beside the kernels' 14.2).

* grouped queries: a grid step takes ``plan.heads`` of a group's query
  heads side by side against their one key-value head: no repeated K / V
  anywhere, and the chains are independent work for the scheduler. A group
  wider than a step is so many sub-groups, each a grid row beside its
  key-value head's (the row's key-value head is ``row // sub-groups``; a
  block whose index does not change is not fetched again).
* forward, grid (batch, sub-group, block of queries): the head's whole K
  and V stay in VMEM (fetched once a head); an in-kernel loop with a dynamic
  trip count runs the key blocks below the diagonal, then the diagonal
  block masked: blocks above the diagonal are never touched.
* backward, one kernel, grid (batch, sub-group, block of keys): the query
  blocks from the diagonal on; scores recomputed once, five products a
  block; ``dk`` / ``dv`` summed over the query blocks and the step's heads
  in float32 and written once; ``dq`` of the sub-group's whole sequence
  accumulates in its float32 output block, which stays in VMEM across the
  key blocks. That residency is what the plan is made for: queries,
  cotangents (bfloat16) and ``dq`` (float32) of ``heads x S x D``, buffered
  twice, within half of ``_VMEM_LIMIT``: four heads of 64, two of 128 or one
  of 256 at 8,192 positions. With one sub-group a key-value head the
  kernel's sums are ``dk`` / ``dv``; with more they are float32 partial sums
  a sub-group, which XLA adds up (268 MB written and read at Nemotron-H's
  shape, 0.6 ms).

Timed at LFM2's shape (q [1, 8192, 8, 4, 64], k, v [1, 8192, 8, 64],
bfloat16; TPU v5 lite, jax 0.9.0; jitted on the cell's layout, so each
figure holds its own layout changes; ms forward / backward / a layer's
three passes = 2 forwards + backward; my chip runs, PR 34):

    plain JAX, 512 query rows a block             19.19 / 30.02 / 68.40
    jax flash_attention (K, V repeated to 32 heads), best of four block
      plans (q 1024, k_major 1024, k 1024)          5.18 / 18.13 / 28.50
    jax splash_attention, MQA form vmapped over the key-value heads,
      best of five (q 1024, kv 1024, compute 512, fused backward)
                                                    4.77 / 11.01 / 20.54
      (the same, split dq / dkv kernels at 512:     5.77 / 17.06 / 28.61)
    these kernels, one query head a grid step, 512  4.30 /  8.39 / 16.98
    these kernels, the group side by side:   256    7.49 /  7.52 / 22.50
                                             512    3.84 /  7.35 / 15.02
                                            1024    3.68 /  8.36 / 15.71

The kernels alone at 512 (device events): forward 3.59 ms, backward 7.03.
The least work, six products over the S(S+1)/2 entries a head, is 4.19 ms a
layer at the chip's 197 TFLOP/s; a layer's three passes execute nine over
whole blocks (1.31 TFLOP) in 14.2 ms, 92 TFLOP/s: D = 64 fills half of the
128-deep matrix unit in the two products that contract over D and half of
its width in the other three, so 98 is what this formulation can reach.
Block 512 is kept for every length it divides; 256 and 128 serve shorter
sequences (and the interpreter's tests).

Timed at the two wide shapes (``scripts/time_attention.py``, the same
method; my chip runs, PR 40; heads a grid step x block; ms):

    Qwen3-Next, q [2, 8192, 2, 8, 256]        forward   backward   three passes
    plain JAX, 512 query rows a block          23.26      43.80      90.32
    the plan: 1 x 512                           9.50      21.62      40.61
    1 x 256                                    12.64      23.71
    2 x 512 / 2 x 256 (the backward's
      residents past VMEM: refused)             9.35 / 12.14
    4 x 512 / 4 x 256 (the same)                9.31 / 12.18
    the backward's whole-sequence blocks buffered once
      (``pipeline_mode=pl.Buffered(1)``), so that twice the heads fit:
      1 x 512 / 2 x 512 / 2 x 256                         21.81 / 20.80 / 21.29

    Nemotron-H, q [2, 8192, 2, 16, 128]
    plain JAX, 256 query rows a block          39.36      74.99     153.71
    the plan: 2 x 512                          11.28      20.64      43.20
    1 x 512 / 1 x 256                          11.74 / 22.25   21.93 / 24.60
    2 x 256                                    15.97      21.79
    4 x 512 / 4 x 256 (backward: refused)      11.13 / 12.84
    buffered once: 2 x 512 / 4 x 512 / 4 x 256            21.06 / 20.07 / 20.29

So the plan is one rule: block 512 where it divides the length, and as many
heads a step as fill 256 lanes of head dims (``_STEP_WIDTH``), fewer where
the group or VMEM says so. What lost: smaller blocks (a block's softmax
statistics and the accumulator's rescaling are paid twice as often); more
heads a forward step than a backward step (0.2 ms a pass: not worth a
second number in the plan); single buffering (0.6-1.0 ms a backward, one a
step, against a pipeline mode no other kernel here uses). A step's heads
are never more than four, so the unrolled chains are never more than LFM2's
and the step's trace and lowering did not grow (``setup_s``).

The kernels alone, as the two cells' steps run them (device events, my
chip runs, PR 40): forward 7.56 ms and backward 17.29 at Qwen3-Next's shape,
9.27 and 16.71 at Nemotron-H's; a layer's three passes 32.4 / 35.3 ms
(``gated_attn_kernel_ms``, ``nemotron_attn_kernel_ms``), the rest of the
standalone figures being the layout changes, which the step fuses in part.
The three passes execute nine products over whole blocks, 5.26 TFLOP, at 162
/ 149 TFLOP/s, where the least work (six products over the causal half,
3.30 TFLOP) is 16.75 ms at the chip's 197: 51.7% / 47.5% of the roofline.
What is left above it: the recomputed scores and the remat's second forward
(a third of the executed work), the blocks above the diagonal's half (6%),
and the softmax's elementwise work, which the matrix unit does not hide:
0.4 us a head and block of 512 x 512 scores in the forward at either head
dim, beside 1.36 us of products at D = 256 and 0.68 at 128, so the forward
runs at 78% / 64% of the peak over the work it executes and the backward at
86% / 89%. Around the kernels the steps spend 8 / 5 ms in layout changes
(``_queries_t`` and back, for q, out, d_out and dq): at head dims of whole
lanes the kernels could read q and d_out row-major (``dot_general`` takes
the transposed operand), which LFM2's 64 does not allow.

Under a ``BlockMask`` (PR 43: block-diffusion training, a noisy copy of the
sequence beside the clean one, ``2 L`` rows) the same step bodies
(``_fwd_step``, ``_bwd_step``) run in kernels of their own,
``mpi4dl_blockdiff_attention_fwd`` / ``_bwd``, whose walk is the mask's: a
block of queries takes its own block of keys first (every row sees itself
there, so the running maximum is finite from the start), then the clean
blocks before its place whole, and a noisy block the clean block at its place
under the strict mask; the backward the mirror image from a block of keys.
Nothing the mask empties is touched: the clean-to-noisy quadrant, every
off-diagonal noisy block, everything past the place: the work of two causal
sequences of ``L``, not of one of ``2 L``. The plan counts ``2 L`` rows of
residents, so 16,384 rows at head dim 128 run one query head a grid step.

What a step calls (PR 44): one forward and one backward kernel a layer. The
tables above count a layer's passes as the steps ran them until then, two
forwards and a backward, because the cell's bare ``jax.checkpoint`` replayed
the forward call only to get back ``out`` and the log-sum-exp it had already
written. ``_attention_fwd`` (the rule is shared by the causal kernels and the
ones under a ``BlockMask``) now gives both the name ``config.KERNEL_RESIDUAL``
and "cell" remat keeps that name (``train._cell_ckpt``), so the replay has no
use for the call: ``q``, ``k`` and ``v`` are still recomputed from the cell's
input, ``out`` (134 MB a layer in the three wide cells, 34 in LFM2's) and the
log-sum-exp (1-2 MB) are held from the layer's forward to its backward, and a
layer executes seven products over whole blocks where it executed nine, the
least being six (so no roofline share can pass 6/7 this way). In the compiled
steps (my sandbox compiles for a described v5e chip, PR 44): 8 forward and 8
backward calls under the mask in the SDAR cell where the parent has 16 and 8,
2 and 2 in LFM2's (4 and 2), 1 and 1 in the Qwen3-Next and Nemotron-H cells
(2 and 1), with no more bytes of layout copies around them than the parent
has in any of the four. On the chip (device events of the traced steps, my
chip runs, PR 44; parent -> change, ms a step and share of the roofline):
the SDAR cell's kernels 312.9 -> 228.8 (42.8 -> 58.6%), Qwen3-Next's 32.41 ->
24.75 (51.7 -> 67.7%), Nemotron-H's 35.26 -> 25.99 (47.5 -> 64.4%), LFM2's
28.35 -> 21.15 (29.5 -> 39.6%); a call takes what it took.

Dispatch (``dispatchable``): TPU backend, not under ``vmap``, and a plan
(``plan_for``: bfloat16, head dim 64, 128 or 256, a length of whole blocks,
a step's heads within VMEM); everything else takes the plain path, which is
also the tests' oracle. No switch. ``tests/test_tpu_compile.py`` compiles
the three cells' shapes, and shapes the plan admits that no cell runs, for
a described v5e chip and fails if the kernels are not in the compiled text.
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
# common start, ``mpi4dl_attention``).
FWD_NAME = "mpi4dl_attention_fwd"
BWD_NAME = "mpi4dl_attention_bwd"
# the same two under the block-diffusion mask (names that do not hold the
# causal kernels' common start: each reader finds its own)
BLOCKDIFF_FWD_NAME = "mpi4dl_blockdiff_attention_fwd"
BLOCKDIFF_BWD_NAME = "mpi4dl_blockdiff_attention_bwd"
HEAD_DIMS = (64, 128, 256)
BLOCKS = (512, 256, 128)
_VMEM_LIMIT = 64 * 1024 * 1024
_STEP_WIDTH = 256  # heads a grid step x head dim: four heads of 64, two of 128, one of 256
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


class Plan(NamedTuple):
    """What the kernels take from the shape (``plan_for``)."""

    block: int  # rows of a block of queries and of a block of keys
    heads: int  # query heads of a group a grid step takes side by side


class BlockMask(NamedTuple):
    """The mask of block-diffusion training over ``2 x length`` rows: rows
    ``[0, length)`` are the noisy copy of a sequence, rows ``[length, 2 x
    length)`` the clean one; row ``r`` is position ``r mod length``, in the
    diffusion block ``position // block``. A noisy query sees the noisy keys
    of its own block and the clean keys of earlier blocks; a clean query sees
    the clean keys of its own block and earlier ones, and never a noisy key.
    Every row sees itself. ``length`` is whole blocks."""

    length: int  # L: positions of the sequence, rows of each copy
    block: int   # B: positions of a diffusion block


def _causal(block):
    """``[keys, queries]``: the key is not later than the query (both count
    from the same start: a diagonal block)."""
    shape = (block, block)
    return lax.broadcasted_iota(jnp.int32, shape, 0) <= lax.broadcasted_iota(jnp.int32, shape, 1)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _fwd_start(qt_ref):
    """The online softmax before any key: per query head of the step
    ``(running maximum, running sum, accumulator [D, block])``."""
    group, d, block = qt_ref.shape
    f32 = jnp.float32
    return ((jnp.full((1, block), -jnp.inf, f32), jnp.zeros((1, block), f32),
             jnp.zeros((d, block), f32)),) * group


def _fwd_step(qt_ref, ks, vt, carry, seen, scale, fold):
    """One block of keys ``ks [block, D]``, ``vt [D, block]`` against the
    step's query heads: the online softmax's update. ``seen [keys, queries]``:
    the block's mask, None where every key is seen. A query that sees no key
    of the block keeps what it has, if it has seen one before (its maximum is
    then finite): the kernels take first a block in which every query sees
    itself."""
    out = []
    for g, (top, total, acc) in enumerate(carry):
        s = _dot(ks, qt_ref[g])  # [keys, queries]
        if not fold:
            s = s * scale
        if seen is not None:
            s = jnp.where(seen, s, -jnp.inf)
        new_top = jnp.maximum(top, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - new_top)
        shrink = jnp.exp(top - new_top)
        total = shrink * total + jnp.sum(p, axis=0, keepdims=True)
        acc = shrink * acc + _dot(vt, p.astype(vt.dtype))
        out.append((new_top, total, acc))
    return tuple(out)


def _fwd_end(carry, ot_ref, lse_ref):
    for g, (top, total, acc) in enumerate(carry):
        ot_ref[g] = (acc / total).astype(ot_ref.dtype)
        lse_ref[g] = top + jnp.log(total)


def _fwd_kernel(qt_ref, ks_ref, vt_ref, ot_ref, lse_ref, *, block, scale, fold):
    """One (batch, sub-group of a key-value head's query heads, block of
    queries): online softmax over the key blocks up to the diagonal, for the
    sub-group's H query heads side by side: their chains are independent, so
    one head's softmax arithmetic runs under another's products. Keys run
    down the sublanes and queries along the lanes, so a query's running
    maximum, sum and log-sum-exp are lane-dense rows ``[1, block]`` and the
    reductions over keys are elementwise across vregs.

    qt_ref ``[H, D, block]``; ks_ref ``[blocks, block, D]`` (keys x D^-0.5
    where ``fold``, else the keys, and the float32 scores take ``scale``)
    and vt_ref ``[blocks, D, block]``: the key-value head's whole
    sequence; ot_ref ``[H, D, block]``; lse_ref ``[H, 1, block]`` float32."""
    i = pl.program_id(2)

    def step(j, carry, diagonal):
        return _fwd_step(qt_ref, ks_ref[j], vt_ref[j], carry,
                         _causal(block) if diagonal else None, scale, fold)

    carry = lax.fori_loop(0, i, functools.partial(step, diagonal=False), _fwd_start(qt_ref))
    _fwd_end(step(i, carry, True), ot_ref, lse_ref)


def _bwd_step(refs, i, carry, seen, scale, fold):
    """The query block ``i`` of the step's heads against the kernel's block
    of keys: the scores again, five products a head; ``dk`` / ``dv`` into the
    carry, ``dq`` into its resident block. ``refs``: the keys ``[block, D]``,
    their transpose, the values, and the kernel's whole-sequence refs (``q``,
    ``d_out``, log-sum-exp, ``delta``, ``dq``). ``seen``: as ``_fwd_step``'s
    (a query that sees no key of the block gets ``p = 0`` from its finite
    log-sum-exp)."""
    ks, kst, v, qt_ref, dot_ref, lse_ref, delta_ref, dqt_ref = refs
    dk, dv = carry
    f32 = jnp.float32
    for g in range(qt_ref.shape[0]):
        qt, dot = qt_ref[g, i], dot_ref[g, i]
        s = _dot(ks, qt)  # [keys, queries]
        if not fold:
            s = s * scale
        if seen is not None:
            s = jnp.where(seen, s, -jnp.inf)
        p = jnp.exp(s - lse_ref[g, i])
        dp = _dot(v, dot)
        if fold:  # without the D^-0.5: dq takes it from the keys, dk at the end
            ds = (p * (dp - delta_ref[g, i])).astype(qt.dtype)
        else:
            ds = (p * (dp - delta_ref[g, i]) * scale).astype(qt.dtype)
        dv = dv + lax.dot_general(p.astype(dot.dtype), dot, _NT, preferred_element_type=f32)
        dk = dk + lax.dot_general(ds, qt, _NT, preferred_element_type=f32)
        dqt_ref[g, i] += _dot(kst, ds)
    return dk, dv


def _bwd_kernel(ks_ref, kst_ref, v_ref, qt_ref, dot_ref, lse_ref, delta_ref,
                dqt_ref, dk_ref, dv_ref, *, block, scale, fold):
    """One (batch, sub-group of a key-value head's query heads, block of
    keys): the query blocks from the diagonal on, the sub-group's H query
    heads side by side. ``dk`` / ``dv`` of this key block are summed over the
    query blocks and the sub-group in float32 and written once; ``dq`` of the
    whole sequence stays in VMEM across the key blocks of a sub-group (zeroed
    at the first, written back after the last).

    ks_ref ``[block, D]`` and kst_ref ``[D, block]`` (keys x D^-0.5 where
    ``fold``, else the keys: the float32 scores and ``ds`` take ``scale``),
    v_ref ``[block, D]``; qt_ref, dot_ref ``[H, blocks, D, block]`` and
    lse_ref, delta_ref ``[H, blocks, 1, block]``: the sub-group's whole
    sequence; dqt_ref ``[H, blocks, D, block]`` float32; dk_ref, dv_ref
    ``[block, D]``."""
    j = pl.program_id(2)
    blocks = qt_ref.shape[1]
    ks = ks_ref[...]
    refs = (ks, kst_ref[...], v_ref[...], qt_ref, dot_ref, lse_ref, delta_ref, dqt_ref)
    f32 = jnp.float32

    @pl.when(j == 0)
    def _():
        dqt_ref[...] = jnp.zeros(dqt_ref.shape, f32)

    def step(i, carry, diagonal):
        return _bwd_step(refs, i, carry, _causal(block) if diagonal else None, scale, fold)

    carry = step(j, (jnp.zeros(ks.shape, f32), jnp.zeros(ks.shape, f32)), True)
    dk, dv = lax.fori_loop(j + 1, blocks, functools.partial(step, diagonal=False), carry)
    dk_ref[...] = (dk * scale if fold else dk).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _diffusion_blocks(block, unit):
    """``[keys, queries]`` twice: the diffusion block (``unit`` positions)
    of every key and of every query of two blocks of rows that start at the
    same position."""
    shape = (block, block)
    return (lax.broadcasted_iota(jnp.int32, shape, 0) // unit,
            lax.broadcasted_iota(jnp.int32, shape, 1) // unit)


def _own(key_block, query_block, noisy):
    """A block of rows against itself: a noisy row (``noisy`` 1, a scalar)
    sees its diffusion block's rows, a clean one (0) its own and earlier
    blocks'. The earliest block seen is the row's own times ``noisy``:
    integers, since the chip's compiler selects no masks by a scalar."""
    return (key_block >= query_block * noisy) & (key_block <= query_block)


def _blockdiff_fwd_kernel(qt_ref, ks_ref, vt_ref, ot_ref, lse_ref, *,
                          block, half, unit, scale, fold):
    """``_fwd_kernel`` under a ``BlockMask``: the rows are a noisy copy
    (blocks of rows ``[0, half)``) beside the clean one (``[half, 2 half)``),
    diffusion blocks of ``unit`` positions. A block of queries takes first
    its own block of keys, where every query sees itself (a noisy one the
    noisy keys of its diffusion block, a clean one the clean keys of its own
    and earlier ones), then the clean blocks before its place whole, and, a
    noisy one, the clean block AT its place under the strict mask (earlier
    diffusion blocks alone): ``place + 1`` key blocks a clean block of
    queries, ``place + 2`` a noisy one. The clean-to-noisy quadrant, every
    other noisy block and everything past the place are never touched."""
    i = pl.program_id(2)
    noisy = (i < half).astype(jnp.int32)
    place = i - half * (1 - noisy)
    key_block, query_block = _diffusion_blocks(block, unit)

    def step(j, carry, seen):
        return _fwd_step(qt_ref, ks_ref[j], vt_ref[j], carry, seen, scale, fold)

    carry = step(i, _fwd_start(qt_ref), _own(key_block, query_block, noisy))
    carry = lax.fori_loop(0, place, lambda j, c: step(half + j, c, None), carry)
    carry = lax.fori_loop(  # one trip for a noisy block of queries, none for a clean one
        0, noisy, lambda _, c: step(half + place, c, key_block < query_block), carry)
    _fwd_end(carry, ot_ref, lse_ref)


def _blockdiff_bwd_kernel(ks_ref, kst_ref, v_ref, qt_ref, dot_ref, lse_ref, delta_ref,
                          dqt_ref, dk_ref, dv_ref, *, block, half, unit, scale, fold):
    """``_bwd_kernel`` under a ``BlockMask``. A noisy block of keys meets the
    one block of queries that sees it, its own. A clean block of keys meets
    its own block of queries, the noisy queries at its place under the strict
    mask, and both copies' later blocks whole. Refs as ``_bwd_kernel``'s, the
    whole sequence being the ``2 half`` blocks of both copies."""
    j = pl.program_id(2)
    noisy = (j < half).astype(jnp.int32)
    place = j - half * (1 - noisy)
    ks = ks_ref[...]
    refs = (ks, kst_ref[...], v_ref[...], qt_ref, dot_ref, lse_ref, delta_ref, dqt_ref)
    f32 = jnp.float32
    key_block, query_block = _diffusion_blocks(block, unit)

    @pl.when(j == 0)
    def _():
        dqt_ref[...] = jnp.zeros(dqt_ref.shape, f32)

    def step(i, carry, seen):
        return _bwd_step(refs, i, carry, seen, scale, fold)

    def both_copies(i, carry):
        return step(half + i, step(i, carry, None), None)

    carry = step(j, (jnp.zeros(ks.shape, f32), jnp.zeros(ks.shape, f32)),
                 _own(key_block, query_block, noisy))
    carry = lax.fori_loop(  # one trip for a clean block of keys, none for a noisy one
        0, 1 - noisy, lambda _, c: step(place, c, key_block < query_block), carry)
    # both copies' later blocks: none for a noisy block of keys
    dk, dv = lax.fori_loop(place + 1, half - (half - place - 1) * noisy, both_copies, carry)
    dk_ref[...] = (dk * scale if fold else dk).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _kernel(causal, diffusion, mask, block, **kwargs):
    """The kernel body and its ``pallas_call``'s name: the causal one, or
    under ``mask`` the block-diffusion one."""
    if mask is None:
        return functools.partial(causal[0], block=block, **kwargs), causal[1]
    return functools.partial(
        diffusion[0], block=block, half=mask.length // block, unit=mask.block,
        **kwargs), diffusion[1]


def _split(x, block):
    """``[B, S, ...] -> [B, S / block, block, ...]``."""
    return x.reshape(x.shape[0], x.shape[1] // block, block, *x.shape[2:])


def _queries_t(x, block):
    """``[B, S, KV, G, D] -> [B, KV, G, blocks, D, block]``."""
    return _split(x, block).transpose(0, 3, 4, 1, 5, 2)


def _queries(x_t, shape):
    """``_queries_t``'s inverse, to ``shape``."""
    return x_t.transpose(0, 3, 5, 1, 2, 4).reshape(shape)


def _keys(x, block):
    """``[B, S, KV, D] -> [B, KV, blocks, block, D]``."""
    return _split(x, block).transpose(0, 3, 1, 2, 4)


def _keys_t(x, block):
    """``[B, S, KV, D] -> [B, KV, blocks, D, block]``."""
    return _split(x, block).transpose(0, 3, 1, 4, 2)


def _rows_t(x, block):
    """A float32 number a query row ``[B, KV, G, S] -> [B, KV, G, blocks, 1, block]``."""
    return x.reshape(*x.shape[:3], x.shape[3] // block, 1, block)


def _folds(d: int) -> bool:
    """Whether ``D^-0.5`` is a power of two (D = 64, 256), so that the keys
    take it exactly in bfloat16; else (D = 128) the float32 scores and
    ``ds`` take it, as on the plain path."""
    return (d.bit_length() - 1) % 2 == 0


def _scaled(k):
    """The keys x D^-0.5 where that is exact in bfloat16, else the keys."""
    d = k.shape[-1]
    return k * jnp.asarray(d ** -0.5, k.dtype) if _folds(d) else k


def _sub_groups(x_t, heads):
    """``[B, KV, G, ...] -> [B, KV x G / heads, heads, ...]``: a key-value
    head's group as sub-groups of ``heads`` query heads, each a grid row of
    its own beside its key-value head's."""
    b, kv, g = x_t.shape[:3]
    return x_t.reshape(b, kv * g // heads, heads, *x_t.shape[3:])


def _key_value_head(subs):
    """A grid row's key-value head: ``subs`` sub-groups share one (at one a
    head the row itself, so that such a shape's index maps trace as they did
    before there were sub-groups)."""
    return (lambda h: h) if subs == 1 else (lambda h: h // subs)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)


def forward(q, k, v, plan, interpret=False, mask=None):
    """``(out [B, S, KV, G, D], log-sum-exp [B, KV, G, S] float32)``; under
    ``mask`` (a ``BlockMask``) ``S`` is both copies' rows."""
    b, s, kv, g, d = q.shape
    block, heads = plan
    blocks, subs = s // block, g // heads
    of = _key_value_head(subs)
    kernel, name = _kernel((_fwd_kernel, FWD_NAME), (_blockdiff_fwd_kernel, BLOCKDIFF_FWD_NAME),
                           mask, block, scale=d ** -0.5, fold=_folds(d))

    def group(*tail):  # a sub-group's query heads, one block of queries
        return pl.BlockSpec((None, None, heads, None) + tail, lambda n, h, i: (n, h, 0, i, 0, 0))

    def whole(*tail):  # their key-value head's whole sequence
        return pl.BlockSpec((None, None, blocks) + tail, lambda n, h, i: (n, of(h), 0, 0, 0))

    out_t, lse = pl.pallas_call(
        kernel,
        grid=(b, kv * subs, blocks),
        in_specs=[group(d, block), whole(block, d), whole(d, block)],
        out_specs=[group(d, block), group(1, block)],
        out_shape=[jax.ShapeDtypeStruct((b, kv * subs, heads, blocks, d, block), q.dtype),
                   jax.ShapeDtypeStruct((b, kv * subs, heads, blocks, 1, block), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        name=name,
    )(_sub_groups(_queries_t(q, block), heads), _keys(_scaled(k), block), _keys_t(v, block))
    out_t = out_t.reshape(b, kv, g, blocks, d, block)
    return _queries(out_t, q.shape), lse.reshape(b, kv, g, s)


def backward(q, k, v, out, lse, d_out, plan, interpret=False, mask=None):
    """``(dq, dk, dv)``, shaped and typed as ``q, k, v``."""
    b, s, kv, g, d = q.shape
    block, heads = plan
    blocks, subs = s // block, g // heads
    of = _key_value_head(subs)
    kernel, name = _kernel((_bwd_kernel, BWD_NAME), (_blockdiff_bwd_kernel, BLOCKDIFF_BWD_NAME),
                           mask, block, scale=d ** -0.5, fold=_folds(d))
    scaled = _scaled(k)
    # per row, sum(d_out * out): what the softmax's backward subtracts
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.moveaxis(delta, 1, 3)  # [B, S, KV, G] -> [B, KV, G, S]

    def keys(*tail):  # the key-value head's block of keys
        return pl.BlockSpec((None, None, None) + tail, lambda n, h, j: (n, of(h), j, 0, 0))

    def sums(*tail):  # that block's dk / dv, a sub-group's
        return pl.BlockSpec((None, None, None) + tail, lambda n, h, j: (n, h, j, 0, 0))

    def whole(*tail):  # a sub-group's query heads, their whole sequence
        return pl.BlockSpec((None, None, heads, blocks) + tail, lambda n, h, j: (n, h, 0, 0, 0, 0))

    def sub(x_t):
        return _sub_groups(x_t, heads)

    def summed(like):  # one sub-group a key-value head: its sums are dk / dv;
        return like.dtype if subs == 1 else jnp.float32  # more: partial sums, added up below

    dq_t, dk, dv = pl.pallas_call(
        kernel,
        grid=(b, kv * subs, blocks),
        in_specs=[keys(block, d), keys(d, block), keys(block, d),
                  whole(d, block), whole(d, block), whole(1, block), whole(1, block)],
        out_specs=[whole(d, block), sums(block, d), sums(block, d)],
        out_shape=[jax.ShapeDtypeStruct((b, kv * subs, heads, blocks, d, block), jnp.float32),
                   jax.ShapeDtypeStruct((b, kv * subs, blocks, block, d), summed(k)),
                   jax.ShapeDtypeStruct((b, kv * subs, blocks, block, d), summed(v))],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name=name,
    )(_keys(scaled, block), _keys_t(scaled, block), _keys(v, block),
      sub(_queries_t(q, block)), sub(_queries_t(d_out, block)), sub(_rows_t(lse, block)),
      sub(_rows_t(delta, block)))

    def keys_back(x, like):  # [B, KV x subs, blocks, block, D] -> [B, S, KV, D]
        if subs > 1:
            x = jnp.sum(x.reshape(b, kv, subs, blocks, block, d), axis=2).astype(like.dtype)
        return x.transpose(0, 2, 3, 1, 4).reshape(like.shape)

    dq_t = dq_t.reshape(b, kv, g, blocks, d, block)
    return _queries(dq_t.astype(q.dtype), q.shape), keys_back(dk, k), keys_back(dv, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def attention(q, k, v, plan, interpret=False, mask=None):
    """Softmax attention with grouped queries through the kernels, causal or
    under ``mask`` (a ``BlockMask``; ``S`` is then both copies' rows):
    ``q [B, S, KV, G, D]``, ``k, v [B, S, KV, D]`` -> ``[B, S, KV, G, D]``;
    ``S`` (a copy's rows under a mask) a multiple of ``plan.block``, ``G`` of
    ``plan.heads``, ``D`` one of ``HEAD_DIMS``."""
    return forward(q, k, v, plan, interpret, mask)[0]


def _attention_fwd(q, k, v, plan, interpret, mask):
    # all the forward call writes, under the name "cell" remat keeps: the
    # cell's replay then has no use for the call (``train._cell_ckpt``)
    out, lse = (checkpoint_name(x, KERNEL_RESIDUAL)
                for x in forward(q, k, v, plan, interpret, mask))
    return out, (q, k, v, out, lse)


def _attention_bwd(plan, interpret, mask, residuals, d_out):
    return backward(*residuals, d_out, plan, interpret, mask)


attention.defvjp(_attention_fwd, _attention_bwd)


def block_for(length: int):
    """The largest block the kernels take that divides the sequence; None
    where none does."""
    return next((blk for blk in BLOCKS if length % blk == 0), None)


def plan_for(q_shape, k_shape, dtype, mask=None):
    """The kernels' plan for ``q [B, S, KV, G, D]`` and ``k [B, S, KV, D]``,
    from head dim, heads a group and length alone; None for shapes the
    kernels are not written (and compiled, for a described chip) for. They
    take bfloat16, a head dim of ``HEAD_DIMS`` and a sequence that is whole
    blocks; under ``mask`` (a ``BlockMask``) ``S`` is both copies' rows, each
    copy is whole blocks and a block is whole diffusion blocks. A grid step
    takes as many of a group's query heads as divide the group, stay within
    ``_STEP_WIDTH`` lanes of head dims (the chains a step unrolls are never
    more than four) and whose whole-sequence queries, cotangents and float32
    ``dq``, each buffered twice, fit half of the kernels' VMEM in the
    backward; the other half is for a key-value head's whole K and V in the
    forward and for the scores of a step's blocks."""
    if len(q_shape) != 5 or len(k_shape) != 4 or dtype != jnp.bfloat16:
        return None
    length, group, d = q_shape[1], q_shape[3], q_shape[4]
    if mask is None:
        block = block_for(length)
    elif length != 2 * mask.length:
        return None
    else:
        block = next((blk for blk in BLOCKS
                      if mask.length % blk == 0 and blk % mask.block == 0), None)
    if d not in HEAD_DIMS or block is None:
        return None
    fits = [h for h in range(1, _STEP_WIDTH // d + 1)
            if group % h == 0 and 2 * h * length * d * (2 + 2 + 4) <= _VMEM_LIMIT // 2]
    return Plan(block, fits[-1]) if fits else None


def supported(q_shape, k_shape, dtype, mask=None) -> bool:
    """Whether the kernels have a plan for these shapes."""
    return plan_for(q_shape, k_shape, dtype, mask) is not None


def dispatchable(q, k, mask=None) -> bool:
    """TPU backend, shapes the kernels take, and not under a batched
    (vmapped) trace: a batched ``pallas_call`` compiles through an added
    grid dimension only sometimes, and the gate, which plans the un-batched
    shape, cannot vouch for it (``pool_pallas.dispatchable``'s policy)."""
    from mpi4dl_tpu.parallel.halo import _is_batch_tracer

    if jax.default_backend() != "tpu" or _is_batch_tracer(q) or _is_batch_tracer(k):
        return False
    return supported(tuple(q.shape), tuple(k.shape), q.dtype, mask)
