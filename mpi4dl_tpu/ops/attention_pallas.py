"""Pallas TPU kernels: fused causal grouped-query attention, both passes.

Why: LFM2-8B-A1B's two attention layers at 8,192 positions (32 query heads
of 64 over 8 key-value heads) took 144 ms of a 487 ms step as plain JAX
(``ops/sequence.blocked_causal_attention``): every block's float32 scores
went to HBM and back, 4.3 GB a layer-pass, three passes a layer (forward,
the "cell" remat's forward again, the backward's recomputation). Here a
block's scores, probabilities and ``ds`` live and die in VMEM.

The arithmetic is the plain path's and the configuration's: bfloat16
operands, every product accumulated in float32, float32 maximum / sum /
log-sum-exp, ``delta = sum(d_out * out)`` and ``dp - delta`` in float32,
``p`` and ``ds`` rounded to bfloat16 only as operands of the next product.
The scale ``D^-0.5`` is a power of two at D = 64, so it is folded into the
keys (and into ``dk`` at the end) exactly.

Layout: keys run down the sublanes and queries along the lanes (scores
``[keys, queries]``), so a query's running maximum, sum, log-sum-exp and
``delta`` are lane-dense rows ``[1, block]``, the reductions over keys are
elementwise across vregs (no cross-lane reduce, no ``[block, 128]`` copies
of a row statistic), and the accumulators ``[D, block]`` and every block
that crosses HBM but a key block ``[block, D]`` fill their lanes. XLA
brings q, k, v and d_out into that blocked, transposed form and the results
back (0.6 ms of device time a layer's three passes, beside the kernels' 14.2).

* forward, grid (batch, key-value head, block of queries): the head's
  whole K and V stay in VMEM (fetched once a head); an in-kernel loop with
  a dynamic trip count runs the key blocks below the diagonal, then the
  diagonal block masked: blocks above the diagonal are never touched.
* backward, one kernel, grid (batch, key-value head, block of keys): the
  query blocks from the diagonal on; scores recomputed once, five products
  a block; ``dk`` / ``dv`` summed over the query blocks and the group in
  float32 and written once; ``dq`` of the head's whole sequence accumulates
  in its float32 output block, which stays in VMEM across the head's key
  blocks.
* grouped queries: a grid step takes the group's G query heads side by side
  against their one key-value head: no repeated K / V anywhere, the group's
  ``dk`` / ``dv`` sum is the kernel's own, and the G chains are independent
  work for the scheduler.

Timed at the cell's shape (q [1, 8192, 8, 4, 64], k, v [1, 8192, 8, 64],
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

Dispatch (``dispatchable``): TPU backend, not under ``vmap``, bfloat16,
head dim 64, a length of whole blocks whose group fits VMEM; everything
else takes the plain path, which is also the tests' oracle. No switch.
``tests/test_tpu_compile.py`` compiles the cell's shape for a described
v5e chip and fails if the kernels are not in the compiled text.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The pallas_calls' names: how the kernels are found in a compiled step's
# text and in a profiler trace (the benchmark's readers look for their
# common start, ``mpi4dl_attention``).
FWD_NAME = "mpi4dl_attention_fwd"
BWD_NAME = "mpi4dl_attention_bwd"
HEAD_DIM = 64
BLOCKS = (512, 256, 128)
_VMEM_LIMIT = 64 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _causal(block):
    """``[keys, queries]``: the key is not later than the query (both count
    from the same start: a diagonal block)."""
    shape = (block, block)
    return lax.broadcasted_iota(jnp.int32, shape, 0) <= lax.broadcasted_iota(jnp.int32, shape, 1)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _fwd_kernel(qt_ref, ks_ref, vt_ref, ot_ref, lse_ref, *, block):
    """One (batch, key-value head, block of queries): online softmax over
    the key blocks up to the diagonal, for the group's query heads side by
    side: their chains are independent, so one head's softmax arithmetic
    runs under another's products. Keys run down the sublanes and queries
    along the lanes, so a query's running maximum, sum and log-sum-exp are
    lane-dense rows ``[1, block]`` and the reductions over keys are
    elementwise across vregs.

    qt_ref ``[G, D, block]``; ks_ref ``[blocks, block, D]`` (keys x D^-0.5)
    and vt_ref ``[blocks, D, block]``: the key-value head's whole sequence;
    ot_ref ``[G, D, block]``; lse_ref ``[G, 1, block]`` float32."""
    i = pl.program_id(2)
    group, d, _ = qt_ref.shape
    f32 = jnp.float32

    def step(j, carry, diagonal):
        ks, vt = ks_ref[j], vt_ref[j]
        seen = _causal(block) if diagonal else None
        out = []
        for g, (top, total, acc) in enumerate(carry):
            s = _dot(ks, qt_ref[g])  # [keys, queries]
            if diagonal:
                s = jnp.where(seen, s, -jnp.inf)
            new_top = jnp.maximum(top, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - new_top)
            shrink = jnp.exp(top - new_top)
            total = shrink * total + jnp.sum(p, axis=0, keepdims=True)
            acc = shrink * acc + _dot(vt, p.astype(vt.dtype))
            out.append((new_top, total, acc))
        return tuple(out)

    carry = ((jnp.full((1, block), -jnp.inf, f32), jnp.zeros((1, block), f32),
              jnp.zeros((d, block), f32)),) * group
    carry = lax.fori_loop(0, i, functools.partial(step, diagonal=False), carry)
    for g, (top, total, acc) in enumerate(step(i, carry, True)):
        ot_ref[g] = (acc / total).astype(ot_ref.dtype)
        lse_ref[g] = top + jnp.log(total)


def _bwd_kernel(ks_ref, kst_ref, v_ref, qt_ref, dot_ref, lse_ref, delta_ref,
                dqt_ref, dk_ref, dv_ref, *, block, scale):
    """One (batch, key-value head, block of keys): the query blocks from the
    diagonal on, the group's query heads side by side. ``dk`` / ``dv`` of
    this key block are summed over the query blocks and the group in
    float32 and written once; ``dq`` of the whole sequence stays in VMEM
    across the key blocks of a head (zeroed at the first, written back after
    the last).

    ks_ref ``[block, D]`` and kst_ref ``[D, block]`` (keys x D^-0.5), v_ref
    ``[block, D]``; qt_ref, dot_ref ``[G, blocks, D, block]`` and lse_ref,
    delta_ref ``[G, blocks, 1, block]``: the group's whole sequence; dqt_ref
    ``[G, blocks, D, block]`` float32; dk_ref, dv_ref ``[block, D]``."""
    j = pl.program_id(2)
    group, blocks = qt_ref.shape[:2]
    ks, kst, v = ks_ref[...], kst_ref[...], v_ref[...]
    f32 = jnp.float32

    @pl.when(j == 0)
    def _():
        dqt_ref[...] = jnp.zeros(dqt_ref.shape, f32)

    def step(i, carry, diagonal):
        dk, dv = carry
        seen = _causal(block) if diagonal else None
        for g in range(group):
            qt, dot = qt_ref[g, i], dot_ref[g, i]
            s = _dot(ks, qt)  # [keys, queries]
            if diagonal:
                s = jnp.where(seen, s, -jnp.inf)
            p = jnp.exp(s - lse_ref[g, i])
            dp = _dot(v, dot)
            # without the D^-0.5: dq takes it from the keys, dk at the end
            ds = (p * (dp - delta_ref[g, i])).astype(qt.dtype)
            dv = dv + lax.dot_general(p.astype(dot.dtype), dot, _NT, preferred_element_type=f32)
            dk = dk + lax.dot_general(ds, qt, _NT, preferred_element_type=f32)
            dqt_ref[g, i] += _dot(kst, ds)
        return dk, dv

    carry = step(j, (jnp.zeros(ks.shape, f32), jnp.zeros(ks.shape, f32)), True)
    dk, dv = lax.fori_loop(j + 1, blocks, functools.partial(step, diagonal=False), carry)
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


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


def _scaled(k):
    """The keys x D^-0.5: a power of two at D = 64, so exact in bfloat16."""
    return k * jnp.asarray(k.shape[-1] ** -0.5, k.dtype)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)


def forward(q, k, v, block, interpret=False):
    """``(out [B, S, KV, G, D], log-sum-exp [B, KV, G, S] float32)``."""
    b, s, kv, g, d = q.shape
    blocks = s // block

    def group(*tail):  # the group's query heads, one block of queries
        return pl.BlockSpec((None, None, g, None) + tail, lambda n, h, i: (n, h, 0, i, 0, 0))

    def whole(*tail):  # their key-value head's whole sequence
        return pl.BlockSpec((None, None, blocks) + tail, lambda n, h, i: (n, h, 0, 0, 0))

    out_t, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block=block),
        grid=(b, kv, blocks),
        in_specs=[group(d, block), whole(block, d), whole(d, block)],
        out_specs=[group(d, block), group(1, block)],
        out_shape=[jax.ShapeDtypeStruct((b, kv, g, blocks, d, block), q.dtype),
                   jax.ShapeDtypeStruct((b, kv, g, blocks, 1, block), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
        name=FWD_NAME,
    )(_queries_t(q, block), _keys(_scaled(k), block), _keys_t(v, block))
    return _queries(out_t, q.shape), lse.reshape(b, kv, g, s)


def backward(q, k, v, out, lse, d_out, block, interpret=False):
    """``(dq, dk, dv)``, shaped and typed as ``q, k, v``."""
    b, s, kv, g, d = q.shape
    blocks = s // block
    scaled = _scaled(k)
    # per row, sum(d_out * out): what the softmax's backward subtracts
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.moveaxis(delta, 1, 3)  # [B, S, KV, G] -> [B, KV, G, S]

    def keys(*tail):  # the key-value head's block of keys
        return pl.BlockSpec((None, None, None) + tail, lambda n, h, j: (n, h, j, 0, 0))

    def whole(*tail):  # the group's query heads, their whole sequence
        return pl.BlockSpec((None, None, g, blocks) + tail, lambda n, h, j: (n, h, 0, 0, 0, 0))

    dq_t, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, block=block, scale=d ** -0.5),
        grid=(b, kv, blocks),
        in_specs=[keys(block, d), keys(d, block), keys(block, d),
                  whole(d, block), whole(d, block), whole(1, block), whole(1, block)],
        out_specs=[whole(d, block), keys(block, d), keys(block, d)],
        out_shape=[jax.ShapeDtypeStruct((b, kv, g, blocks, d, block), jnp.float32),
                   jax.ShapeDtypeStruct((b, kv, blocks, block, d), k.dtype),
                   jax.ShapeDtypeStruct((b, kv, blocks, block, d), v.dtype)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name=BWD_NAME,
    )(_keys(scaled, block), _keys_t(scaled, block), _keys(v, block),
      _queries_t(q, block), _queries_t(d_out, block), _rows_t(lse, block),
      _rows_t(delta, block))

    def keys_back(x):  # [B, KV, blocks, block, D] -> [B, S, KV, D]
        return x.transpose(0, 2, 3, 1, 4).reshape(k.shape)

    return _queries(dq_t.astype(q.dtype), q.shape), keys_back(dk), keys_back(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def attention(q, k, v, block, interpret=False):
    """Causal softmax attention with grouped queries through the kernels:
    ``q [B, S, KV, G, D]``, ``k, v [B, S, KV, D]`` -> ``[B, S, KV, G, D]``;
    ``S`` a multiple of ``block``, ``D`` 64."""
    return forward(q, k, v, block, interpret)[0]


def _attention_fwd(q, k, v, block, interpret):
    out, lse = forward(q, k, v, block, interpret)
    return out, (q, k, v, out, lse)


def _attention_bwd(block, interpret, residuals, d_out):
    return backward(*residuals, d_out, block, interpret)


attention.defvjp(_attention_fwd, _attention_bwd)


def block_for(length: int):
    """The largest block the kernels take that divides the sequence; None
    where none does."""
    return next((blk for blk in BLOCKS if length % blk == 0), None)


def supported(q_shape, k_shape, dtype) -> bool:
    """The shapes the kernels are written (and compiled, for a described
    chip) for: bfloat16, head dim 64, a sequence that is whole blocks and
    whose group of query heads (queries, cotangents and float32 ``dq``, each
    block buffered twice) fits half of the kernels' VMEM; the other half is
    for the scores of the group's blocks."""
    if len(q_shape) != 5 or len(k_shape) != 4:
        return False
    length, d = q_shape[1], q_shape[-1]
    return (dtype == jnp.bfloat16 and d == HEAD_DIM and block_for(length) is not None
            and 2 * q_shape[3] * length * d * (2 + 2 + 4) <= _VMEM_LIMIT // 2)


def dispatchable(q, k) -> bool:
    """TPU backend, shapes the kernels take, and not under a batched
    (vmapped) trace: a batched ``pallas_call`` compiles through an added
    grid dimension only sometimes, and the gate, which plans the un-batched
    shape, cannot vouch for it (``pool_pallas.dispatchable``'s policy)."""
    from mpi4dl_tpu.parallel.halo import _is_batch_tracer

    if jax.default_backend() != "tpu" or _is_batch_tracer(q) or _is_batch_tracer(k):
        return False
    return supported(tuple(q.shape), tuple(k.shape), q.dtype)
