"""Pallas TPU kernel: fused one-pass 1x1-conv backward (dx + dw).

Why: the round-3/4 AmoebaNet@1024 profiles put ~25-32% of the train step
in ``dot_general`` — dominated by the cells' input-reduce 1x1 conv
backwards, measured HBM-bound (OI 67-205 under the ~240 ridge,
docs/PERF.md round 3). Stock AD emits TWO dots per 1x1 conv backward —
``dx = dy . w^T`` and ``dw = x^T . dy`` (``fastconv._conv2d_s1_bwd``) —
and XLA cannot multi-output-fuse them, so ``dy`` streams from HBM twice.
This kernel computes both in ONE pass over ``dy``: per (batch, row
chunk) grid step it loads the ``x`` and ``dy`` blocks once, issues both
MXU contractions in VMEM, writes the ``dx`` block, and accumulates
``dw`` in a resident f32 block across the sequential TPU grid. HBM
traffic drops from ``2*dy + x + dx`` to ``dy + x + dx`` — the op's
roofline. The reference leaves the equivalent to cuDNN/cuBLAS
(``conv2d`` backward, ``models/amoebanet.py:365-398``); on TPU the
schedule is ours.

**Status: EXPERIMENTAL, off by default — recorded negative (round 5).**
Measured end-to-end @1024 (AmoebaNet bs2, scan_save): 6.957 vs 7.241
img/s baseline (−3.9%) with per-result caps at 32 MB; at 100 MB caps
the full program failed to compile (the VMEM-stack result wall,
docs/PERF.md round 4). The one-pass traffic win is real at
the op level but the custom-call boundaries un-fuse the surrounding
program — see ``dot1x1_mode`` for the ledger. Kept for a runtime whose
allocator handles custom-call results in HBM.

Dispatch discipline (the ``pool_pallas``/``wgrad_pallas`` playbook):
``dispatchable()`` = shape/VMEM plan gate; batched traces and
trainer-armed ``disable()`` contexts (>=2048px programs) take the stock
two-dot path. A shape the gate admits and the chip's compiler refuses
surfaces as the compiler's error (``tests/test_tpu_compile.py`` compiles
the admitted shapes for a described v5e chip).
``MPI4DL_TPU_DOT1X1=auto`` enables, ``=on`` additionally neutralizes the
trainer ``disable()``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# The pallas_call's name: how the kernel is found in a compiled step's
# text and in a profiler trace.
KERNEL_NAME = "mpi4dl_dot1x1_bwd"
# Of the 16 MiB a kernel's scoped VMEM may hold on a v5e chip.
_VMEM_BUDGET = 14 * 1024 * 1024


def dot1x1_mode() -> str:
    """Default OFF (recorded negative, round 5): with per-result caps at
    32 MB the @1024 program compiles, but the fused kernel measured
    6.957 vs 7.241 img/s end-to-end (−3.9%) — the relayout/fusion
    boundaries Pallas custom calls impose on the surrounding program
    cost more than the saved dy re-read, the same end-to-end shape the
    pool kernel only escaped via the 4-D carry interaction (docs/PERF.md
    rounds 4–5). At 100 MB caps the full program fails to compile
    (VMEM-stack-allocated results). Enable for A/B with
    ``MPI4DL_TPU_DOT1X1=auto`` (gates) or ``=on`` (also neutralizes
    trainer ``disable()``)."""
    mode = os.environ.get("MPI4DL_TPU_DOT1X1", "off")
    if mode not in ("auto", "off", "on"):
        raise ValueError(f"MPI4DL_TPU_DOT1X1 must be auto|off|on, got {mode!r}")
    return mode


_DISABLED = [False]


class disable:
    """Trace-time off-switch (same pattern as ``pool_pallas.disable``):
    ``Trainer.train_step`` arms it for >=2048px traces. ``=on`` makes it
    a no-op for A/B revalidation."""

    def __enter__(self):
        self._prev = _DISABLED[0]
        if dot1x1_mode() != "on":
            _DISABLED[0] = True

    def __exit__(self, *exc):
        _DISABLED[0] = self._prev
        return False


def _kernel(x_ref, dy_ref, w_ref, dx_ref, dw_ref):
    step = pl.program_id(0)
    dy = dy_ref[0]  # [hb, W, O]
    hb, wdim, o = dy.shape
    c = w_ref.shape[0]
    dyf = dy.reshape(hb * wdim, o)
    # dx block: [hb*W, O] x [C, O]^T on the MXU, f32 accumulate.
    dx = lax.dot_general(
        dyf, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dx_ref[0] = dx.reshape(hb, wdim, c).astype(dx_ref.dtype)
    # dw partial: [C, hb*W] x [hb*W, O]; resident f32 accumulator (the
    # TPU grid is sequential, so += across steps is well-defined).
    xf = x_ref[0].reshape(hb * wdim, c)
    dwp = lax.dot_general(
        xf, dyf, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(step == 0)
    def _init():
        dw_ref[...] = dwp

    @pl.when(step != 0)
    def _acc():
        dw_ref[...] += dwp


def _plan(b, h, w, c, o, itemsize):
    """Row-chunk height hb (divisor of h) fitting the VMEM budget."""
    for hb in (32, 16, 8, 4, 2, 1):
        if h % hb:
            continue
        # What the TPU compiler allocates, modelled on its own report for
        # x=[2,64,64,208] O=832 at hb=32 (17.87 MiB against a 16 MiB limit,
        # which the earlier single-buffered estimate put at 7.8 MB): the
        # streamed x/dy/dx blocks are double-buffered by the pipeline, and
        # the [hb*W, .] reshapes of x and dy are copies.
        streamed = hb * w * (c + o) * itemsize + hb * w * c * itemsize
        block = 2 * streamed
        block += hb * w * (c + o) * itemsize  # reshaped x, dy
        block += hb * w * c * 4  # f32 dx before the cast
        block += c * o * (itemsize + 4)  # w + dw accumulator
        if block < _VMEM_BUDGET:
            return hb
    return None


def supported(x_shape, o, itemsize=2) -> bool:
    b, h, w, c = x_shape
    # Lane-dim blocks carry whole C/O (no chunking): Mosaic accepts whole
    # dims of any width; tiny widths just waste lanes — require the
    # benchmark models' >=104-channel regime.
    if c < 104 or o < 104:
        return False
    # VMEM-stack-allocated result guard (docs/PERF.md round 4): the TPU
    # compiler stack-allocates custom-call results, and the budget
    # interacts with co-resident calls unmodelably — under a 100 MB cap
    # each shape compiled alone while the FULL @1024 program (many
    # engaged 27-54 MB dx results across the scanned cells) did not
    # (round 5). Cap per-result size hard.
    cap_mb = float(os.environ.get("MPI4DL_TPU_DOT1X1_CAP_MB", "32"))
    if b * h * w * c * itemsize > cap_mb * 1024 * 1024:
        return False
    return _plan(b, h, w, c, o, itemsize) is not None


def _bwd_impl(x, dy, w2, interpret=False):
    """(dx, dw_f32) from x [B,H,W,C], dy [B,H,W,O], w2 [C,O]."""
    b, h, wdim, c = x.shape
    o = dy.shape[-1]
    hb = _plan(b, h, wdim, c, o, x.dtype.itemsize)
    assert hb is not None, (x.shape, o)
    nh = h // hb
    grid = (b * nh,)

    def blk(i):
        return (i // nh, i % nh, 0, 0)

    dx, dw = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, wdim, c), blk),
            pl.BlockSpec((1, hb, wdim, o), blk),
            pl.BlockSpec((c, o), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, wdim, c), blk),
            pl.BlockSpec((c, o), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, wdim, c), x.dtype),
            jax.ShapeDtypeStruct((c, o), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(x, dy, w2)
    return dx, dw


def dispatchable(x, dy) -> bool:
    from mpi4dl_tpu.parallel.halo import _is_batch_tracer, _xla_only_active

    if dot1x1_mode() == "off":
        return False
    if _DISABLED[0] or _xla_only_active():
        return False
    if jax.default_backend() != "tpu":
        return False
    if _is_batch_tracer(x) or _is_batch_tracer(dy):
        return False
    if x.ndim != 4 or dy.ndim != 4:
        return False
    return supported(tuple(x.shape), dy.shape[-1], x.dtype.itemsize)


def bwd_1x1(x, dy, w2, interpret=False):
    """Fused (dx, dw) — callers gate with :func:`dispatchable`."""
    return _bwd_impl(x, dy, w2, interpret=interpret)
