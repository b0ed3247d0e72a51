"""Pallas TPU kernel: one-pass stride-1 max-pool backward (first-max-wins).

Why: AmoebaNet's genotype runs a 3x3 stride-1 max pool in every cell
(``models/amoebanet.py``), 40 backwards a step at 1024 px. XLA's own
paths re-materialize the select chain pass by pass at HBM: on the v5e the
shifted-maximum tree's backward costs the step about 34 ms and
``select_and_scatter`` more (PERF.md section 6, PR 29). The reference
leaves all of this to cuDNN (``MaxPool2d`` inside ``Pool``,
``spatial.py:1416-1509``); on TPU the op is ours to schedule.

This kernel computes dx in ONE streaming pass: x and dy are read once, dx
is written once — the roofline for this op. The winner of each window is
recomputed, row-major first-max-wins (``select_and_scatter``'s tie rule,
bit-equal to it on integer cotangents: ``tests/test_pool_pallas.py``),
and what the arithmetic needs per *column* tap is not done per *window*
tap:

* winner, separable: first maximum over the kw column taps inside each
  input row (made once per input row and shared by the kh windows that
  touch it), then first maximum over the kh rows. Strict ``>`` both times
  keeps the smallest u, then the smallest v: the row-major first maximum.
  One bf16 -> f32 widening and kw-1 W-shifted views per input row;
* scatter, separable: per column tap v the claimed dy summed over u (an H
  shift is an address offset), then kw-1 W shifts place the kw sums;
* one row [W, 128] at a time (``lax.fori_loop``, taps read from the refs):
  a row's chain ends before the next row is loaded. Compares run in f32,
  the scattered sum is f32 and rounded to dy's dtype once.

Layout: blocks keep NHWC with C on the 128 lanes (chunks of 128; the last
block ragged for 208 / 416 / 832) and W on sublanes. The rows a chunk's
last windows reach into arrive through a second aligned BlockSpec
("tail": Pallas index maps cannot express overlapping blocks); what they
scatter past the chunk is carried in a VMEM scratch to the next grid step
of the same (batch, channel chunk), so the call returns dx itself.

Probed on jax 0.9.0 / libtpu 0.0.34 (compiled for a described v5e chip,
then timed on one: per step of AmoebaNet-D 18/416 @1024, 40 calls, my chip
runs, PR 29; the pre-PR kernel, nine unaligned bf16 slices and nine pads
over a whole block, took 22.33 ms alone and 22.80 ms inside the step):

* as written — f32 value slices at sublane offsets 1 and 2, ``jnp.pad``
  of an f32 row: 6.66 ms. Of it: the blocks' DMA alone 1.36, + the row
  maxima 1.58, + the winners 2.95, + the scatter 6.66;
* ``pltpu.roll`` along the sublanes of an f32 [130, 128] row (not a
  multiple of 8): compiles; 6.67 for the views, 6.88 for the scatter;
* the shifts as unaligned loads / stores on an f32 scratch
  (``ref[pl.ds(v, wo), :]``): compile; 6.46-6.72, faster on the 130- and
  258-wide rows, slower on the 66- and 34-wide: not taken;
* whole-channel blocks ([8, W, 416], the pre-PR plan): 9.52 ms;
  256-channel chunks 8.96; 8 / 32 / 64 rows a grid step 7.32 / 6.45 /
  7.41 (32 needs more than the 16 MiB of scoped VMEM at 258 wide).

What the chip's compiler still refuses, each at [130, 128]: a bf16
``arith.cmpf`` and a 16-bit ``arith.cmpi`` ("Target does not support this
comparison" — why compares are f32), ``pltpu.roll`` of bf16 ("Rotate with
non-32-bit data"); and Pallas itself a partial ``fori_loop`` unroll ("Only
unroll=num_steps and unroll=1 supported", [2,130,130,416]).

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
admitted shapes of the full-width models for a described v5e chip, and
holds the step to its 40 dispatches).
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
from jax.experimental.pallas import tpu as pltpu

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
    ceiling failed the compile (AmoebaNet@2048 bs1 compiled with the
    kernels off, failed with them on — pre-round runtime, and no step at
    that size has been compiled on this one: ROADMAP S9). At 1024 px the
    kernel is bit-exact and faster than either of XLA's paths.
    ``MPI4DL_TPU_POOL_PALLAS=off`` disables everywhere regardless; ``=on``
    makes THIS switch a no-op so the heuristics that arm it can be
    A/B-revalidated."""

    def __enter__(self):
        self._prev = _DISABLED[0]
        if pool_pallas_mode() != "on":
            _DISABLED[0] = True

    def __exit__(self, *exc):
        _DISABLED[0] = self._prev
        return False


def _pool_bwd_kernel(*refs, kh, kw, to, wo, nrows):
    """One (batch, channel chunk, window-row chunk) grid step; the row
    chunks of one (batch, channel chunk) run in order.

    refs: the main x ref [1, to, Wp, Cc] and — when kh > 1 — a tail ref
    [1, kh-1, Wp, Cc] with the rows the chunk's last windows reach into;
    the dy ref [1, to, Wo, Cc]; the dx ref [1, to, Wp, Cc]; then scratch:
    ``hb`` / ``hi`` [to+kh-1, Wo, Cc] (each input row's first maximum over
    its kw column taps, and which tap), an ``idx`` ring [kh, Wo, Cc] (the
    last kh window rows' winners) and — kh > 1 — ``carry`` [kh-1, Wp, Cc],
    the f32 sums this chunk's windows scatter into the next chunk's first
    rows. Tap (u, v) of window (a, b) is input position (a + u, b + v), and
    scatters there — dx is in input coordinates.

    Everything walks one row [W, Cc] at a time: a row's whole chain
    (widen, compare, claim, sum, round) ends before the next row is
    loaded, so the compiler schedules ~70 vregs per value, not a block.
    """
    d, e = kh - 1, kw - 1
    x_ref, *refs = refs
    tail_ref, carry_ref = (refs.pop(0), refs.pop()) if d else (None, None)
    dy_ref, out_ref, hb_ref, hi_ref, idx_ref = refs
    f32 = jnp.float32

    def horizontal(r, row):
        """Row-major first-max inside one input row: strict > keeps the
        smallest column tap v. One widening, kw-1 W-shifted views."""
        xr = row.astype(f32)
        best = xr[0:wo]
        tap = jnp.zeros(best.shape, jnp.int32)
        for v in range(1, kw):
            xv = xr[v : v + wo]
            better = xv > best
            best = jnp.where(better, xv, best)
            tap = jnp.where(better, v, tap)
        hb_ref[r] = best
        hi_ref[r] = tap

    def winner(a):
        """Window row a's winners: first-max over the kh row maxima (an
        H shift is an address offset), strict > keeps the smallest u —
        with ``horizontal`` the row-major first maximum,
        ``select_and_scatter``'s tie rule."""
        best = hb_ref[a]
        idx = hi_ref[a]
        for u in range(1, kh):
            hb = hb_ref[a + u]
            better = hb > best
            best = jnp.where(better, hb, best)
            idx = jnp.where(better, hi_ref[a + u] + u * kw, idx)
        return idx

    def scatter(row_windows):
        """dx row from ``[(u, idx, dy)]``, the window rows that reach it:
        per column tap v the claimed dy summed over u, then kw-1 W shifts."""
        total = None
        for v in range(kw):
            part = None
            for u, idx, dy in row_windows:
                term = jnp.where(idx == u * kw + v, dy, 0.0)
                part = term if part is None else part + term
            part = jnp.pad(part, ((v, e - v), (0, 0)))
            total = part if total is None else total + part
        return total

    i = pl.program_id(2)

    if d:
        @pl.when(i == 0)
        def _():
            carry_ref[...] = jnp.zeros(carry_ref.shape, f32)

        @pl.when(i == nrows)
        def _():
            # The step past the last chunk: only its spill rows are left.
            out_ref[0, 0:d] = carry_ref[...].astype(out_ref.dtype)

    @pl.when(i < nrows)
    def _():
        def h_body(r, _):
            horizontal(r, x_ref[0, r])
            return 0

        lax.fori_loop(0, to, h_body, 0)
        for r in range(d):
            horizontal(to + r, tail_ref[0, r])

        def earlier(row, taps):
            """The window rows ``row - u`` of the chunk that reach ``row``."""
            return [
                (u, idx_ref[(row - u) % kh], dy_ref[0, row - u].astype(f32))
                for u in taps
            ]

        def dx_row(a, taps):
            """Window row a's winners into the ring, then dx row a: every
            window row that reaches it is known now."""
            idx = winner(a)
            idx_ref[a % kh] = idx
            return scatter(
                [(0, idx, dy_ref[0, a].astype(f32))] + earlier(a, taps)
            )

        # The first kh-1 rows: fewer window rows of this chunk reach them,
        # and the previous chunk's spill does.
        for a in range(d):
            row = dx_row(a, range(1, a + 1)) + carry_ref[a]
            out_ref[0, a] = row.astype(out_ref.dtype)

        def body(a, _):
            out_ref[0, a] = dx_row(a, range(1, kh)).astype(out_ref.dtype)
            return 0

        lax.fori_loop(d, to, body, 0)
        # Rows past the chunk: what its last windows spill into the next.
        for r in range(d):
            carry_ref[r] = scatter(earlier(to + r, range(r + 1, kh)))


def _round_up(n, m):
    return -(-n // m) * m


def _plan(c, ho, wo, kh, kw, itemsize):
    """Pick (row chunk ``to``, channel chunk ``cc``) from the shape; None
    when nothing fits. Channels are independent and sit on the 128 lanes:
    a chunk is 128 of them (a narrower array whole), the last block ragged
    where 128 does not divide C (208, 416, 832) — the lanes past C compute
    on what the block's padding holds and are never written. Rows: the
    most that fits the VMEM budget, since each chunk reads kh-1 rows of
    the next one again."""
    cc = c if c <= 128 else 128
    d, e = kh - 1, kw - 1
    lanes = _round_up(cc, 128)
    sub = 32 // itemsize  # rows of one packed sublane tile
    for to in (64, 32, 16, 8, 4, 2, 1):
        if ho % to or to < d:
            continue
        # The tail BlockSpec needs element row (i+1)*to to be a multiple
        # of its own block height kh-1.
        if d and to % d:
            continue
        row = _round_up(wo + e, sub) * lanes * itemsize
        blocks = 2 * (3 * to + d) * row  # x, dy, dx and the tail, double-buffered
        row32 = _round_up(wo + e, 8) * lanes * 4
        scratch = (2 * (to + d) + kh + d) * row32  # hb, hi, idx ring, carry
        live = 12 * row32  # one row's chain, spilled
        if blocks + scratch + live < _VMEM_BUDGET:
            return to, cc
    return None


def supported(x_shape, kh, kw, ph, pw, itemsize=2) -> bool:
    b, h, w, c = x_shape
    if kh == 1 and kw == 1:
        return False
    # The TPU compiler may stack-allocate a Pallas custom call's results in
    # VMEM and then fails the compile when they overflow ("Ran out of
    # memory in memory space vmem while allocating on stack"): with the
    # pre-PR-29 kernel's two results, shapes compiled alone for a described
    # v5e chip up to 104.8 MiB of padded input and failed at 211 MiB. This
    # kernel's one result compiles alone at 210 MiB ([2,514,514,208]) and
    # 211 MiB ([2,258,258,832]; my sandbox compiles, PR 29) and inside the
    # whole AmoebaNet-D 18/416 @1024 step, one chip and SP 2x2; no step
    # that holds a larger pool has been compiled, so the limit stays.
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
    it — so batched contexts keep the XLA/tree backward: the pipeline
    front's ``batched_trace`` context, plus a direct batch-tracer check."""
    from mpi4dl_tpu.parallel.halo import _in_batched_trace, _is_batch_tracer

    if _DISABLED[0] or _in_batched_trace() or _is_batch_tracer(x):
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
    d = kh - 1
    # One step more than there are row chunks where windows overlap rows:
    # dx has kh-1 rows past the last chunk, the last windows' spill.
    grid = (b, pl.cdiv(c, cchunk), nrows + (1 if d else 0))

    def chunk(i):
        return jnp.minimum(i, nrows - 1)  # the extra step reads nothing new

    def block(width, rows=chunk):
        return pl.BlockSpec(
            (1, to, width, cchunk), lambda n, j, i: (n, rows(i), 0, j)
        )

    in_specs, args = [block(wp)], [xp]
    if d:
        # Overlap rows [ (i+1)*to, +d ) as an aligned block of height d
        # (to % d == 0 via _plan).
        in_specs.append(
            pl.BlockSpec(
                (1, d, wp, cchunk),
                lambda n, j, i: (n, (chunk(i) + 1) * (to // d), 0, j),
            )
        )
        args.append(xp)
    in_specs.append(block(wo))
    args.append(dy)

    f32 = jnp.float32
    scratch = [
        pltpu.VMEM((to + d, wo, cchunk), f32),
        pltpu.VMEM((to + d, wo, cchunk), jnp.int32),
        pltpu.VMEM((kh, wo, cchunk), jnp.int32),
    ]
    if d:
        scratch.append(pltpu.VMEM((d, wp, cchunk), f32))

    return pl.pallas_call(
        functools.partial(
            _pool_bwd_kernel, kh=kh, kw=kw, to=to, wo=wo, nrows=nrows
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=block(wp, rows=lambda i: i),
        out_shape=jax.ShapeDtypeStruct((b, hp, wp, c), dy.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*args)


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
