"""Pallas TPU kernel: one-pass stride-1 max-pool backward (first-max-wins).

Why: the round-4 AmoebaNet@1024 profile puts ~16% of the train step in
max-pool backwards, most of it the stride-1 shifted-maximum tree's
select/accumulate chains (most of the 10.3% ``mul`` + 4.0% ``max``
classes; the genotype runs a 3x3 s1 max pool in every cell,
``models/amoebanet.py``): the kh+kw tree backward re-materializes the
select chain pass by pass at HBM. The reference leaves all of this to
cuDNN (``MaxPool2d`` inside ``Pool``, ``spatial.py:1416-1509``); on TPU
the op is ours to schedule.

This kernel computes dx in ONE streaming pass: per (batch, window-row
chunk, channel chunk) grid step it loads the padded input and the
cotangent once into VMEM, recomputes each window's winner in-register
(kh*kw compare/claim steps, row-major first-max-wins — the same tie
semantics as ``select_and_scatter``'s GE select; the row-major
first-claim decomposition was proved bit-equal to it on tie-heavy data in
``tests/test_spatial_layers.py``), and accumulates the scattered
contributions in VMEM. HBM traffic is x + dy read once, dx written once —
the roofline for this op.

Layout notes (mirrors ``wgrad_pallas``): blocks keep NHWC with C on
lanes and W on sublanes; all in-kernel shifts are static ``lax.slice`` /
``jnp.pad`` on values; window-chunk overlap rows arrive through a second
aligned BlockSpec ("tail"), and the per-chunk rows that spill past the
chunk (a window's last kh-1 rows) leave through a second output the
wrapper folds back in — Pallas index maps cannot express overlapping
blocks in either direction.

Stride 1 only. A strided variant (a parity-class decomposition, pre-round)
is refused by the installed chip compiler — its per-class results are
stack-allocated in VMEM and overflow it, [2,130,130,832] alone and
[2,130,130,416] inside the SP 2x2 step (PR 24, compiled for a described
v5e chip) — and was deleted; strided pools take XLA's
``select_and_scatter``.

Dispatch: ``usable()`` = TPU backend + shape gate (``supported``);
shapes the gate declines take the existing tree path. A shape the gate
admits and the chip's compiler refuses is a bug in the gate and surfaces
as the compiler's error (``tests/test_tpu_compile.py`` compiles the
admitted shapes of the full-width models for a described v5e chip).
``MPI4DL_TPU_POOL_PALLAS=off`` disables for A/B; ``=on`` additionally
neutralizes trainer-armed ``disable()`` heuristics (the >=2048px gate)
for A/B re-validation.
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
KERNEL_NAME = "mpi4dl_pool_bwd"
_NEG = float("-inf")
_VMEM_BUDGET = 10 * 1024 * 1024


def pool_pallas_mode() -> str:
    """auto: shape gates decide, and trainers may arm ``disable()``
    heuristics (e.g. the >=2048px gate). off: never dispatch. on: like
    auto but ``disable()`` becomes a no-op, so the >=2048px heuristic can
    be A/B-revalidated if the compiler/runtime VMEM behavior improves —
    correctness gates (shape plan, batched traces) still apply."""
    mode = os.environ.get("MPI4DL_TPU_POOL_PALLAS", "auto")
    if mode not in ("auto", "off", "on"):
        raise ValueError(
            f"MPI4DL_TPU_POOL_PALLAS must be auto|off|on, got {mode!r}"
        )
    return mode


_DISABLED = [False]


class disable:
    """Trace-time off-switch for the pool kernel dispatch (context
    manager, same pattern as ``fastconv.wgrad_taps_threshold``).

    ``Trainer.train_step`` arms this for images >= 2048px: per-shape the
    kernels pass their gates there, but injecting VMEM-stack-allocated
    custom-call results into a program already compiled against the HBM
    ceiling fails the compile (measured: AmoebaNet@2048 bs1 compiles
    with the kernels off, fails with them on — round 4). The
    @1024 headline regime, where the kernel is measured bit-exact at
    end-to-end parity, keeps the dispatch. ``MPI4DL_TPU_POOL_PALLAS=off``
    disables everywhere regardless; ``=on`` makes THIS switch a no-op so
    the heuristics that arm it can be A/B-revalidated."""

    def __enter__(self):
        self._prev = _DISABLED[0]
        if pool_pallas_mode() != "on":
            _DISABLED[0] = True

    def __exit__(self, *exc):
        _DISABLED[0] = self._prev
        return False


def _pool_bwd_kernel(*refs, kh, kw, to, wo):
    """One (batch, window-row chunk, channel chunk) grid step.

    refs: the main x ref [1, to, Wp, Cc] and — when kh > 1 — a tail ref
    [1, kh-1, Wp, Cc] with the rows the chunk's last windows reach into;
    the dy ref [1, to, Wo, Cc]; then the outputs: a main ref
    [1, to, Wp, Cc] and (kh > 1) a tail ref [1, kh-1, Wp, Cc] carved from
    a 4-D chunk-flattened [b, nrows*(kh-1), Wp, C] array (a 5-D
    [b, nrows, kh-1, Wp, C] form was rejected: the compiler assigned it
    VMEM memory space and stack-allocated the whole array — see the
    out_specs comment). Tap (u, v) of window (a, b) is input position
    (a + u, b + v), and scatters there — dx is in input coordinates.
    """
    xp = refs[0][0]
    ri = 1
    if kh > 1:
        xp = jnp.concatenate([xp, refs[ri][0]], axis=0)
        ri += 1
    dy = refs[ri][0]
    outs = refs[ri + 1 :]
    c = dy.shape[-1]
    zero = jnp.zeros((), dy.dtype)

    def tap(u, v):
        """This tap's value per window: a contiguous slice."""
        return lax.slice(xp, (u, v, 0), (u + to, v + wo, c))

    # Online argmax in window order: strict > keeps the FIRST maximum —
    # select_and_scatter's tie rule. Compares run in f32 (Mosaic on this
    # target rejects bf16 cmpf, 16-bit ordered cmpi, AND 16-bit cmpi-eq
    # whose mask feeds a bf16 select — all probed; docs/PERF.md round 4
    # has the full support matrix). The f32 widening unpacks the
    # (8,128,2) VMEM tiling and is the kernel's main device cost;
    # every leaner formulation tried (single whole-block convert,
    # 16-bit bit-equality claims, u16 radix keys, pltpu.roll W-shifts,
    # grouped pads, XLA-level chunked calls) either hits an unsupported
    # Mosaic op or trips the runtime's VMEM stack allocation of
    # custom-call operands/results — this exact structure is the one
    # that compiles. Measured ledger in docs/PERF.md round 4.
    best = tap(0, 0).astype(jnp.float32)
    idx = jnp.zeros(best.shape, jnp.int32)
    ti = 0
    for u in range(kh):
        for v in range(kw):
            if ti:
                x_uv = tap(u, v).astype(jnp.float32)
                better = x_uv > best
                best = jnp.where(better, x_uv, best)
                idx = jnp.where(better, ti, idx)
            ti += 1

    # Accumulation: static shifted adds inside VMEM.
    acc = None
    for u in range(kh):
        for v in range(kw):
            contrib = jnp.where(idx == (u * kw + v), dy, zero)
            term = jnp.pad(
                contrib, ((u, kh - 1 - u), (v, kw - 1 - v), (0, 0))
            )
            acc = term if acc is None else acc + term
    outs[0][0] = acc[:to]
    if kh > 1:
        outs[1][0] = acc[to:]


def _chunk_c(c: int) -> int:
    """Channel chunk: whole when narrow or not 128-divisible (Mosaic
    requires the lane-dim block size to be a multiple of 128 or the
    whole array dim — e.g. 416 and 832 stay whole and _plan's VMEM
    budget decides viability), else the smallest 128-multiple divisor;
    C on lanes means chunks are independent."""
    if c <= 256 or c % 128:
        return c
    for mult in range(128, c, 128):
        if c % mult == 0:
            return mult
    return c


def _plan(c, ho, wo, kh, kw, itemsize):
    """Pick (row chunk ``to``, channel chunk); None when nothing fits."""
    cc = _chunk_c(c)
    d, e = kh - 1, kw - 1
    for to in (32, 16, 8, 4, 2, 1):
        if ho % to:
            continue
        # The tail BlockSpec needs element row (i+1)*to to be a multiple
        # of its own block height kh-1.
        if d and to % d:
            continue
        x_bytes = (to + d) * (wo + e) * cc * itemsize
        dy_bytes = to * wo * cc * itemsize
        argmax_bytes = to * wo * cc * 8  # f32 best + i32 idx
        acc_bytes = x_bytes * 2  # acc + pad temp
        if x_bytes + dy_bytes + argmax_bytes + acc_bytes < _VMEM_BUDGET:
            return to, cc
    return None


def supported(x_shape, kh, kw, ph, pw, itemsize=2) -> bool:
    b, h, w, c = x_shape
    if kh == 1 and kw == 1:
        return False
    # The TPU compiler may stack-allocate a Pallas custom call's results in
    # VMEM (docs/PERF.md round 4) and then fails the compile when they
    # overflow ("Ran out of memory in memory space vmem while allocating
    # on stack"). Measured by compiling for a described v5e chip (jax
    # 0.9.0, libtpu 0.0.34; tests/test_tpu_compile.py holds the gate to
    # it): shapes compile alone up to 104.8 MiB of padded input (fail at
    # 211 MiB) and inside the whole AmoebaNet-D 18/416 @1024 step.
    hp, wp = h + 2 * ph, w + 2 * pw
    if b * hp * wp * c * itemsize > 100 * 2**20:
        return False
    if hp < kh or wp < kw:
        return False
    return _plan(c, hp - kh + 1, wp - kw + 1, kh, kw, itemsize) is not None


def usable(x, kh, kw, ph, pw) -> bool:
    if pool_pallas_mode() == "off":
        return False
    if jax.default_backend() != "tpu":
        return False
    if x.ndim != 4:
        return False
    return supported(tuple(x.shape), kh, kw, ph, pw, x.dtype.itemsize)


def dispatchable(x, kh, kw, ph, pw) -> bool:
    """``usable`` + not under a batched (vmapped) trace. The pipeline's
    micro-batched front vmaps the cell stack; a batched ``pallas_call``
    compiles through an added grid dimension only sometimes, and the
    shape gate (which plans the UN-batched shape) cannot vouch for
    it — so batched contexts keep the XLA/tree backward, exactly like the
    halo kernel's policy (``parallel/halo.py:124-146``). The sniffs are
    shared with that policy: the pipeline front's ``xla_halo_only``
    context, plus a direct batch-tracer check."""
    from mpi4dl_tpu.parallel.halo import _is_batch_tracer, _xla_only_active

    if _DISABLED[0] or _xla_only_active() or _is_batch_tracer(x):
        return False
    return usable(x, kh, kw, ph, pw)


def _bwd_padded(xp, dy, *, kh, kw, interpret=False):
    """dxp [B, Hp, Wp, C] from the padded input and the cotangent."""
    b, hp, wp, c = xp.shape
    _, ho, wo, _ = dy.shape
    plan = _plan(c, ho, wo, kh, kw, xp.dtype.itemsize)
    assert plan is not None, (xp.shape, kh, kw)
    to, cchunk = plan
    nrows = ho // to
    nc = c // cchunk
    d = kh - 1

    grid = (b * nrows * nc,)

    def idx(i):
        return (i // (nrows * nc), (i // nc) % nrows, i % nc)

    def main_block(width):
        return pl.BlockSpec(
            (1, to, width, cchunk),
            lambda i: (idx(i)[0], idx(i)[1], 0, idx(i)[2]),
        )

    in_specs, args = [main_block(wp)], [xp]
    if d:
        # Overlap rows [ (i+1)*to, +d ) as an aligned block of height d
        # (to % d == 0 via _plan).
        in_specs.append(
            pl.BlockSpec(
                (1, d, wp, cchunk),
                lambda i: (idx(i)[0], (idx(i)[1] + 1) * (to // d), 0, idx(i)[2]),
            )
        )
        args.append(xp)
    in_specs.append(main_block(wo))
    args.append(dy)

    out_specs = [main_block(wp)]
    out_shapes = [jax.ShapeDtypeStruct((b, ho, wp, c), dy.dtype)]
    if d:
        # 4-D, chunk-flattened: [b, nrows*d, wp, c] — a 5-D
        # [b, nrows, d, ...] form was assigned VMEM memory space by the
        # compiler and stack-allocated the whole array.
        out_specs.append(
            pl.BlockSpec(
                (1, d, wp, cchunk),
                lambda i: (idx(i)[0], idx(i)[1], 0, idx(i)[2]),
            )
        )
        out_shapes.append(jax.ShapeDtypeStruct((b, nrows * d, wp, c), dy.dtype))

    outs = pl.pallas_call(
        functools.partial(_pool_bwd_kernel, kh=kh, kw=kw, to=to, wo=wo),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
        name=KERNEL_NAME,
    )(*args)
    if not d:
        return outs[0]
    main, tails = outs
    # Chunk i's tail rows are rows (i+1)*to + [0, d) — the next chunk's
    # first rows (to >= d via _plan's choices). Lay the tails on a
    # to-strided grid shifted by to, add, crop back to hp = ho + d.
    dxp = jnp.concatenate([main, jnp.zeros((b, to, wp, c), dy.dtype)], axis=1)
    flat = jnp.pad(
        tails.reshape(b, nrows, d, wp, c),
        ((0, 0), (0, 0), (0, to - d), (0, 0), (0, 0)),
    )
    dxp = dxp.at[:, to : to + ho].add(flat.reshape(b, nrows * to, wp, c))
    return dxp[:, :hp]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def max_pool(x, kh, kw, ph, pw):
    """Stride-1 max pool (−inf edge padding, torch ``MaxPool2d`` parity)
    whose backward is the one-pass Pallas kernel. Forward ==
    ``lax.reduce_window(max)`` — the same values every other path here
    produces; only the backward's tie rule (first-max-wins) differs from
    the shifted-maximum tree's maximum-chain subgradients, which callers
    gate on (see ``max_pool_s1_valid``)."""
    return _fwd_val(x, kh, kw, ph, pw)


def _fwd_val(x, kh, kw, ph, pw):
    neg = jnp.asarray(_NEG, x.dtype)
    xp = lax.pad(x, neg, ((0, 0, 0), (ph, ph, 0), (pw, pw, 0), (0, 0, 0)))
    return lax.reduce_window(
        xp, neg, lax.max, (1, kh, kw, 1), (1, 1, 1, 1), "valid"
    )


def _fwd(x, kh, kw, ph, pw):
    # Residual is x alone: the backward recomputes each window's winner
    # in-register (online argmax), so the pooled output never needs to
    # be saved or re-read.
    return _fwd_val(x, kh, kw, ph, pw), x


def _bwd(kh, kw, ph, pw, x, dy):
    neg = jnp.asarray(_NEG, x.dtype)
    xp = lax.pad(x, neg, ((0, 0, 0), (ph, ph, 0), (pw, pw, 0), (0, 0, 0)))
    dxp = _bwd_padded(xp, dy, kh=kh, kw=kw)
    h, w = x.shape[1], x.shape[2]
    return (dxp[:, ph : ph + h, pw : pw + w, :],)


max_pool.defvjp(_fwd, _bwd)
