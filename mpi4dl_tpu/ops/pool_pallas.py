"""Pallas TPU kernel: one-pass max-pool backward (first-max-wins).

Why: the round-4 AmoebaNet@1024 profile puts ~16% of the train step in
max-pool backwards — ``select_and_scatter`` for the reduction cells'
stride-2 pools (6.9%) plus the stride-1 shifted-maximum tree's
select/accumulate chains (most of the 10.3% ``mul`` + 4.0% ``max``
classes; the genotype runs a 3x3 s1 max pool in every cell,
``models/amoebanet.py``). Both existing backwards are multi-pass at HBM:
``select_and_scatter`` walks windows sequentially, and the kh+kw tree
backward re-materializes the select chain pass by pass. The reference
leaves all of this to cuDNN (``MaxPool2d`` inside ``Pool``,
``spatial.py:1416-1509``); on TPU the op is ours to schedule.

This kernel computes dx in ONE streaming pass: per (batch, window-row
chunk, channel chunk) grid step it loads the padded input, the pooled
output and the cotangent once into VMEM, recomputes each window's winner
in-register (kh*kw compare/claim steps, row-major first-max-wins —
the same tie semantics as ``select_and_scatter``'s GE select; the
row-major first-claim decomposition was proved bit-equal to it on
tie-heavy data in ``tests/test_spatial_layers.py``), and accumulates the
scattered contributions in VMEM. HBM traffic is x + y + dy read once,
dx written once — the roofline for this op.

Layout notes (mirrors ``wgrad_pallas``): blocks keep NHWC with C on
lanes and W on sublanes; all in-kernel shifts are static ``lax.slice`` /
``jnp.pad`` on values; window-chunk overlap rows arrive through a second
aligned BlockSpec ("tail"), and the per-chunk rows that spill past the
chunk (a window's last kh-sh rows) leave through a second output the
wrapper folds back in — Pallas index maps cannot express overlapping
blocks in either direction.

Stride-2 support uses a parity ("polyphase") decomposition: dx rows/cols
of each residue class (r mod sh, c mod sw) are produced as separate
dense sub-arrays inside the kernel (taps grouped by parity; per class
the scatter offsets are plain static shifts), and the wrapper
interleaves the sh*sw classes back with one strided-set each — no
interior-padded full-resolution scatter terms (the failure mode that
made the XLA-level decomposition 32% SLOWER end-to-end,
``pool_bwd_impl``/docs/PERF.md round 4).

Dispatch: ``usable()`` = TPU backend + shape gate (``supported``);
shapes the gate declines take the existing tree / reduce_window paths. A
shape the gate admits and the chip's compiler refuses is a bug in the
gate and surfaces as the compiler's error (``tests/test_tpu_compile.py``
compiles the admitted shapes of the full-width models for a described
v5e chip). ``MPI4DL_TPU_POOL_PALLAS=off`` disables for A/B;
``=on`` additionally neutralizes trainer-armed ``disable()`` heuristics
(the >=2048px gate) for A/B re-validation.
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


def _class_geometry(kh, kw, sh, sw):
    """Per parity class (cr, cc): max row/col shift (D, E). Class (cr, cc)
    holds dx rows r ≡ cr (mod sh) / cols ≡ cc (mod sw); tap (u, v) with
    u ≡ cr, v ≡ cc scatters window (a, b) to class position
    (a + (u-cr)//sh, b + (v-cc)//sw) — a plain static shift."""
    geo = {}
    for cr in range(sh):
        for cc in range(sw):
            ups = [u for u in range(kh) if u % sh == cr]
            vps = [v for v in range(kw) if v % sw == cc]
            if not ups or not vps:
                continue
            geo[(cr, cc)] = (
                max((u - cr) // sh for u in ups),
                max((v - cc) // sw for v in vps),
            )
    return geo


def _pool_bwd_kernel(*refs, kh, kw, sh, sw, to, wo):
    """One (batch, window-row chunk, channel chunk) grid step.

    refs: per parity plane (in geometry order) a main x ref
    [1, to, Wp_p, Cc] and — when the plane has row spill D > 0 — a tail
    ref [1, D, Wp_p, Cc]; then the dy ref [1, to, Wo, Cc]; then the
    outputs: per class a main ref [1, to, Wc, Cc] and (D > 0) a tail ref
    [1, D, Wc, Cc] carved from a 4-D chunk-flattened [b, nrows*D, Wc, C]
    array (a 5-D [b, nrows, D, Wc, C] form was rejected: the compiler
    assigned it VMEM memory space and stack-allocated the whole array —
    see the out_specs comment). Input planes and output classes share the same
    parity geometry: tap (u, v) lives on plane (u%sh, v%sw) at offset
    (u//sh, v//sw), and scatters window (a, b) to dx class (u%sh, v%sw)
    at the same offset — dx is in input coordinates.
    """
    geo = _class_geometry(kh, kw, sh, sw)
    ri = 0
    planes = {}
    for key, (dmax, emax) in geo.items():
        xpl = refs[ri][0]
        ri += 1
        if dmax:
            xpl = jnp.concatenate([xpl, refs[ri][0]], axis=0)
            ri += 1
        planes[key] = xpl
    dy = refs[ri][0]
    outs = refs[ri + 1 :]
    c = dy.shape[-1]
    zero = jnp.zeros((), dy.dtype)

    def tap(u, v):
        """This tap's value per window: a contiguous plane slice."""
        xpl = planes[(u % sh, v % sw)]
        d, e = u // sh, v // sw
        return lax.slice(xpl, (d, e, 0), (d + to, e + wo, c))

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

    # Per-class accumulation: static shifted adds inside VMEM.
    oi = 0
    for (cr, cc), (dmax, emax) in geo.items():
        acc = None
        for u in range(cr, kh, sh):
            d = (u - cr) // sh
            for v in range(cc, kw, sw):
                e = (v - cc) // sw
                contrib = jnp.where(idx == (u * kw + v), dy, zero)
                term = jnp.pad(
                    contrib,
                    ((d, dmax - d), (e, emax - e), (0, 0)),
                )
                acc = term if acc is None else acc + term
        outs[oi][0] = acc[:to]
        oi += 1
        if dmax:
            outs[oi][0] = acc[to:]
            oi += 1


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


def _plan(c, ho, wo, kh, kw, sh, sw, itemsize):
    """Pick (row chunk ``to``, channel chunk); None when nothing fits."""
    cc = _chunk_c(c)
    geo = _class_geometry(kh, kw, sh, sw)
    for to in (32, 16, 8, 4, 2, 1):
        if ho % to:
            continue
        # Each plane's tail BlockSpec needs element row (i+1)*to to be a
        # multiple of its own block height D.
        if any(d > 0 and to % d for d, _ in geo.values()):
            continue
        plane_bytes = sum(
            (to + d) * (wo + e) * cc * itemsize for d, e in geo.values()
        )
        dy_bytes = to * wo * cc * itemsize
        argmax_bytes = to * wo * cc * 8  # f32 best + i32 idx
        acc_bytes = max(
            (to + d) * (wo + e) * cc * itemsize * 2  # acc + pad temp
            for d, e in geo.values()
        )
        if (
            plane_bytes + dy_bytes + argmax_bytes + acc_bytes
            < _VMEM_BUDGET
        ):
            return to, cc
    return None


def _out_geom(hp, wp, kh, kw, sh, sw):
    """(ho, wo, covered hp, covered wp) under reduce_window "valid"."""
    ho = (hp - kh) // sh + 1
    wo = (wp - kw) // sw + 1
    return ho, wo, (ho - 1) * sh + kh, (wo - 1) * sw + kw


def supported(x_shape, kh, kw, sh, sw, ph, pw, itemsize=2) -> bool:
    b, h, w, c = x_shape
    if kh <= sh and kw <= sw:
        return False  # non-overlapping: XLA's backward is already a reshape
    # The TPU compiler may stack-allocate a Pallas custom call's results in
    # VMEM (docs/PERF.md round 4) and then fails the compile when they
    # overflow ("Ran out of memory in memory space vmem while allocating
    # on stack"). Measured by compiling for a described v5e chip (jax
    # 0.9.0, libtpu 0.0.34; tests/test_tpu_compile.py holds the gate to
    # it). Stride-1 shapes compile alone up to 104.8 MiB of padded input
    # (fail at 211 MiB) and inside the whole AmoebaNet-D 18/416 @1024
    # step. Strided shapes — whose result set is the parity classes plus
    # their tails — are refused whatever their size: [2,130,130,832] fails
    # alone, and [2,130,130,416], which compiles alone, fails inside the
    # SP 2x2 step, as [2,130,130,832] does under "scan_save". They take
    # XLA's select_and_scatter.
    if (sh, sw) != (1, 1):
        return False
    if b * (h + 2 * ph) * (w + 2 * pw) * c * itemsize > 100 * 2**20:
        return False
    hp, wp = h + 2 * ph, w + 2 * pw
    if hp < kh or wp < kw:
        return False
    ho, wo, _, _ = _out_geom(hp, wp, kh, kw, sh, sw)
    return _plan(c, ho, wo, kh, kw, sh, sw, itemsize) is not None


def usable(x, kh, kw, sh, sw, ph, pw) -> bool:
    if pool_pallas_mode() == "off":
        return False
    if jax.default_backend() != "tpu":
        return False
    if x.ndim != 4:
        return False
    return supported(tuple(x.shape), kh, kw, sh, sw, ph, pw, x.dtype.itemsize)


def dispatchable(x, kh, kw, sh, sw, ph, pw) -> bool:
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
    return usable(x, kh, kw, sh, sw, ph, pw)


def _bwd_padded(xp, dy, *, kh, kw, sh, sw, interpret=False):
    """dxp [B, Hp, Wp, C] from the padded input and the cotangent."""
    b, hp, wp, c = xp.shape
    _, ho, wo, _ = dy.shape
    _, _, hp_eff, wp_eff = _out_geom(hp, wp, kh, kw, sh, sw)
    plan = _plan(c, ho, wo, kh, kw, sh, sw, xp.dtype.itemsize)
    assert plan is not None, (xp.shape, kh, kw, sh, sw)
    to, cchunk = plan
    nrows = ho // to
    nc = c // cchunk
    geo = _class_geometry(kh, kw, sh, sw)

    # Windows cover padded rows/cols [0, hp_eff) x [0, wp_eff); anything
    # past that (possible when the torch floor-mode output size leaves a
    # trailing pad row uncovered, e.g. k3 s2 p1 on even sizes) gets zero
    # gradient and is appended after the kernel. Parity planes are built
    # HERE (XLA-side strided slices): Mosaic rejects strided vector
    # extracts in-kernel, and planes make every kernel slice contiguous.
    xe = xp[:, :hp_eff, :wp_eff, :]

    grid = (b * nrows * nc,)

    def idx(i):
        return (i // (nrows * nc), (i // nc) % nrows, i % nc)

    in_specs, args = [], []
    for (pr, pc), (dmax, emax) in geo.items():
        plane = xe[:, pr::sh, pc::sw, :] if (sh, sw) != (1, 1) else xe
        wpl = wo + emax
        in_specs.append(
            pl.BlockSpec(
                (1, to, wpl, cchunk),
                lambda i: (idx(i)[0], idx(i)[1], 0, idx(i)[2]),
            )
        )
        args.append(plane)
        if dmax:
            # Overlap rows [ (i+1)*to, +dmax ) as an aligned block of
            # height dmax (to % dmax == 0 via _plan).
            in_specs.append(
                pl.BlockSpec(
                    (1, dmax, wpl, cchunk),
                    lambda i, d=dmax: (
                        idx(i)[0], (idx(i)[1] + 1) * (to // d), 0, idx(i)[2]
                    ),
                )
            )
            args.append(plane)
    in_specs.append(
        pl.BlockSpec(
            (1, to, wo, cchunk), lambda i: (idx(i)[0], idx(i)[1], 0, idx(i)[2])
        )
    )
    args.append(dy)

    out_specs, out_shapes = [], []
    for (cr, cc_), (dmax, emax) in geo.items():
        wc = wo + emax
        out_specs.append(
            pl.BlockSpec(
                (1, to, wc, cchunk),
                lambda i: (idx(i)[0], idx(i)[1], 0, idx(i)[2]),
            )
        )
        out_shapes.append(jax.ShapeDtypeStruct((b, ho, wc, c), dy.dtype))
        if dmax:
            # 4-D, chunk-flattened: [b, nrows*dmax, wc, c] — a 5-D
            # [b, nrows, dmax, ...] form was assigned VMEM memory space
            # by the compiler and stack-allocated the whole array.
            out_specs.append(
                pl.BlockSpec(
                    (1, dmax, wc, cchunk),
                    lambda i: (idx(i)[0], idx(i)[1], 0, idx(i)[2]),
                )
            )
            out_shapes.append(
                jax.ShapeDtypeStruct((b, nrows * dmax, wc, c), dy.dtype)
            )

    outs = pl.pallas_call(
        functools.partial(
            _pool_bwd_kernel, kh=kh, kw=kw, sh=sh, sw=sw, to=to, wo=wo
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
        name=KERNEL_NAME,
    )(*args)
    outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]

    # Reassemble: fold tails into each class, then interleave the classes
    # with one strided-set each (sh*sw sub-arrays, not kh*kw full-res
    # scatter terms).
    dxe = jnp.zeros((b, hp_eff, wp_eff, c), dy.dtype)
    oi = 0
    for (cr, cc_), (dmax, emax) in geo.items():
        main = outs[oi]
        oi += 1
        if dmax:
            tails = outs[oi]
            oi += 1
            wc = wo + emax
            # Chunk i's tail rows are class rows (i+1)*to + [0, dmax) —
            # the next chunk's first rows (to >= dmax via _plan's choices).
            # Lay the tails on a to-strided grid shifted by to, add, crop
            # back to the class extent ho + dmax.
            sub = jnp.concatenate(
                [main, jnp.zeros((b, to, wc, c), dy.dtype)], axis=1
            )
            flat = jnp.pad(
                tails.reshape(b, nrows, dmax, wc, c),
                ((0, 0), (0, 0), (0, to - dmax), (0, 0), (0, 0)),
            )
            flat = flat.reshape(b, nrows * to, wc, c)
            sub = sub.at[:, to : to + ho].add(flat)
            sub = sub[:, : ho + dmax]
        else:
            sub = main
        # Class (cr, cc_) rows/cols of dxe are exactly sub's extent:
        # ceil((hp_eff - cr)/sh) == ho + dmax, same in W.
        dxe = dxe.at[:, cr :: sh, cc_ :: sw, :].add(sub)
    if hp_eff < hp or wp_eff < wp:
        dxe = jnp.pad(
            dxe,
            (
                (0, 0),
                (0, hp - hp_eff),
                (0, wp - wp_eff),
                (0, 0),
            ),
        )
    return dxe


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def max_pool(x, kh, kw, sh, sw, ph, pw):
    """Max pool (−inf edge padding, torch ``MaxPool2d`` parity) whose
    backward is the one-pass Pallas kernel. Forward ==
    ``lax.reduce_window(max)`` — the same values every other path here
    produces; only the backward's tie rule (first-max-wins) differs from
    the shifted-maximum tree's maximum-chain subgradients, which callers
    gate on (see ``max_pool_s1_valid``)."""
    return _fwd_val(x, kh, kw, sh, sw, ph, pw)


def _fwd_val(x, kh, kw, sh, sw, ph, pw):
    neg = jnp.asarray(_NEG, x.dtype)
    xp = lax.pad(x, neg, ((0, 0, 0), (ph, ph, 0), (pw, pw, 0), (0, 0, 0)))
    return lax.reduce_window(
        xp, neg, lax.max, (1, kh, kw, 1), (1, sh, sw, 1), "valid"
    )


def _fwd(x, kh, kw, sh, sw, ph, pw):
    # Residual is x alone: the backward recomputes each window's winner
    # in-register (online argmax), so the pooled output never needs to
    # be saved or re-read.
    return _fwd_val(x, kh, kw, sh, sw, ph, pw), x


def _bwd(kh, kw, sh, sw, ph, pw, x, dy):
    neg = jnp.asarray(_NEG, x.dtype)
    xp = lax.pad(x, neg, ((0, 0, 0), (ph, ph, 0), (pw, pw, 0), (0, 0, 0)))
    dxp = _bwd_padded(xp, dy, kh=kh, kw=kw, sh=sh, sw=sw)
    h, w = x.shape[1], x.shape[2]
    return (dxp[:, ph : ph + h, pw : pw + w, :],)


max_pool.defvjp(_fwd, _bwd)
