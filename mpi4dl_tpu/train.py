"""Training engine: loss, optimizer, and the single-jit spatial(+DP) trainer.

This is the TPU-native counterpart of the reference's training orchestration
(``src/torchgems/train_spatial.py`` + the ``SyncAllreduce`` gradient engine,
``src/torchgems/comm.py:335-522``). The reference coordinates dozens of MPI
ranks with tagged isend/irecv and hand-rolled flat-gradient allreduces; here
one jitted SPMD program runs over a ``jax.sharding.Mesh`` and XLA inserts the
collectives:

- input ``split_input`` (``train_spatial.py:241-290``) → ``shard_map``
  in_specs sharding the batch over ``data`` and H/W over ``tile_h``/``tile_w``;
- join-rank tile merge (``train_spatial.py:1083-1188``) → tiled
  ``all_gather`` (:func:`mpi4dl_tpu.parallel.halo.gather_tiles`);
- ``SyncAllreduce`` flat-grad allreduce + ``divide_bs`` mean semantics
  (``comm.py:414-514``) → nothing: gradients come out of ``jax.grad``
  already globally correct because the loss is written as a *sum of
  per-device contributions* psum-ed over every mesh axis (see
  ``_local_loss``); XLA fuses the resulting reduction with the backward pass.

Optimizer parity: SGD lr=0.001 momentum=0.9 (``mp_pipeline.py:230-234``),
loss = cross entropy (``mp_pipeline.py:225-228``; we feed logits, not the
reference's double softmax — see ``models/resnet.py`` docstring).
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from jax.lax import axis_size, optimization_barrier
from mpi4dl_tpu.config import (
    AXIS_DATA,
    AXIS_PIPE,
    AXIS_TILE_H,
    AXIS_TILE_W,
    KERNEL_RESIDUAL,
    ParallelConfig,
)
from mpi4dl_tpu.parallel.halo import gather_tiles


def _conv_save_ckpt():
    """jax.checkpoint saving the ``conv_out``-tagged conv outputs — the one
    constructor for every conv-saving remat policy (scan_save / cell_save /
    group_save), so the tag name and policy cannot drift between them."""
    return functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names("conv_out"),
    )


def _cell_ckpt():
    """jax.checkpoint of one cell under remat "cell": the cell's input is
    kept and the cell replayed, but for what the cell's forward wrote that
    is dear to compute and cheap to hold, which is kept by name
    (``KERNEL_RESIDUAL``): a fused kernel's output and what its backward
    reads, and the expert layer's router product, choice, sorts, gathered
    rows and grouped products (``ops/sequence.ExpertFFN``), so the replay
    runs no kernel forward, router, sort or grouped product again. The
    values are named where they are made, one fixed set for every model;
    a cell without such a value (every image cell) keeps its input alone."""
    return functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(KERNEL_RESIDUAL),
    )


def _no_ckpt(fn):
    """The no-checkpoint tier of :meth:`Trainer._nockpt_grants`: residuals
    stored, nothing replayed."""
    return fn


def chain_quadratic(apply_fn, stacked, x0):
    """``fold(apply_fn, x0, stacked)`` whose backward holds O(1) live
    boundaries: cell k's input is recomputed from the run's INPUT anchor
    by a masked forward sweep (``j < k`` cells apply, the rest pass
    through at ~zero cost under ``lax.cond``), so the only full-size
    tensors alive during the backward are the anchor, one rolling
    recompute value, the cotangent, and ONE cell's vjp residuals —
    against "scan"'s n stored carries and "scanlog"'s ~log2(n) recursion
    boundaries (still 23.7 GB live at 4096px, docs/PERF.md round 4).

    Cost: ~n²/2 extra cell forwards across the whole backward (n/2 per
    cell), in a program whose size stays O(1) cell bodies (one forward
    scan + one fori-of-scan backward) — unlike nested-checkpoint
    formulations whose backward inlines O(n²) cell instances and failed
    to compile on program size (docs/PERF.md round 5). Numerics are
    exact: this is a scheduling choice, golden-tested like scan2/scanlog
    (``tests/test_train.py``). This is the "slice time, not space" answer
    to >3072px single-chip training (VERDICT r4 next #2): the reference
    reaches such sizes only by adding GPUs (spatial tiles,
    ``torchgems/spatial.py``); an exact single-chip H-strip decomposition
    is blocked by BatchNorm's whole-image statistics (docs/PERF.md
    round 5), while trading recompute for boundary storage is
    semantics-free."""
    n = jax.tree.leaves(stacked)[0].shape[0]
    # Static (numpy) so the closure holds a constant, not a tracer from
    # the forward trace — bwd runs under a DIFFERENT trace later.
    idx = np.arange(n)

    def _run(ps, h):
        def body(h, p):
            return apply_fn(p, h), None

        y, _ = lax.scan(body, h, ps)
        return y

    chain = jax.custom_vjp(_run)

    def fwd(ps, h):
        # Residuals are the anchor + params only — no per-cell boundaries.
        return _run(ps, h), (ps, h)

    def bwd(res, dy):
        ps, x0 = res

        def outer(i, carry):
            d_h, dps = carry
            k = n - 1 - i

            def rec_body(h, jp):
                j, p = jp
                h2 = lax.cond(
                    j < k, lambda: apply_fn(p, h), lambda: h
                )
                # Serialize the sweep so XLA holds ONE rolling value, not
                # several cells' temps (the scan2/scanlog discipline).
                return optimization_barrier(h2), None

            hk, _ = lax.scan(rec_body, x0, (idx, ps))
            pk = jax.tree.map(lambda a: a[k], ps)
            _, cell_vjp = jax.vjp(apply_fn, pk, hk)
            dp_k, d_h = cell_vjp(d_h)
            dps = jax.tree.map(lambda acc, g: acc.at[k].add(g), dps, dp_k)
            return optimization_barrier((d_h, dps))

        zeros = jax.tree.map(jnp.zeros_like, ps)
        d_h, dps = lax.fori_loop(0, n, outer, (dy, zeros))
        return dps, d_h

    chain.defvjp(fwd, bwd)
    return chain(stacked, x0)


def default_remat(image_size: int) -> "bool | str":
    """The ONE remat rule of the training entry points
    (``benchmarks/common.make_trainer`` and ``bench.py``): a fixed choice
    on image size, not a ladder of attempts — a policy whose compile
    fails, fails the run.

    - a model without an image (``image_size`` 0: a token-sequence model,
      ``ParallelConfig.sequence_length``): "cell", every cell's input kept
      and the cell recomputed in the backward pass, but for what its fused
      kernels' and its expert layer's forwards wrote, which is kept too
      (:func:`_cell_ckpt`: a kernel's forward, the router, the sorts and
      the grouped products run once a step). Such a model is sized
      so that parameters, gradients and momentum fill most of the chip
      (LFM2-8B-A1B's share: 11.5 of 16 GB), and one layer's activations at
      8,192 positions are what is left to hold.
    - below 2048 px everything is stored (``False``). Compiled for a
      described v5e chip (16 GB) on jax 0.9.0 / libtpu 0.0.34 with nothing
      rematerialized, AmoebaNet-D 18/416 @1024 bs2 bf16 needs 14.22 GiB
      (the pool kernel's 40 calls included) and ResNet-110 @1024 bs2
      13.23 GiB, so both of the reference's 1024 px configurations fit,
      and the first ran on the chip (PR 24). (On that compiler AmoebaNet's
      "scan_save", the pre-round headline policy, did not compile with
      the pool kernel on.)
    - 2048-3071 px: "scan"; 3072-4095 px: "scanlog" (4x "scan2");
      from 4096 px: "scanq" ("scanlog"'s ~23.7 GB live set is an OOM).
      UNVERIFIED on the installed compiler: these three are
      BENCH_r05.json / docs/PERF.md rounds 3-5, measured on one v5e chip
      before this round (an older jax) through ``bench.py``, which also
      gave AmoebaNet @2048 a ``grad_accum`` and a save budget that the
      benchmark entry points do not pass. No step at 2048 px or above has
      been compiled for, or run on, a chip since.
    """
    if not image_size:
        return "cell"
    if image_size < 2048:
        return False
    if image_size < 3072:
        return "scan"
    return "scanlog" if image_size < 4096 else "scanq"


def scan_unroll() -> int:
    """Resolved lax.scan unroll factor for scanned cell runs (default 3,
    ``MPI4DL_TPU_SCAN_UNROLL`` overrides — measurements in the
    ``_apply_scan_plan`` comment / docs/PERF.md). The single source of
    truth: anything keying compiled-program identity (bench known-fatal
    cache) must use THIS, not its own copy of the default."""
    return int(os.environ.get("MPI4DL_TPU_SCAN_UNROLL", "3"))


def make_optimizer(learning_rate: float = 0.001, momentum: float = 0.9):
    """Reference default optimizer (``mp_pipeline.py:230-234``)."""
    return optax.sgd(learning_rate, momentum=momentum)


def cross_entropy_sum(logits, labels) -> jax.Array:
    """Sum (not mean) of per-example CE — callers normalize explicitly so the
    psum-of-contributions bookkeeping stays exact under sharding."""
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels
    )
    return jnp.sum(ce)


def correct_count(logits, labels) -> jax.Array:
    return jnp.sum(jnp.argmax(logits, axis=-1) == labels)


def position_cross_entropy(logits, y, mean):
    """The loss a ``Trainer`` takes where its model brings none: softmax
    cross-entropy against one label an image, or one a position of a token
    sequence, and the share of them the logits name.

    A model's own loss has this signature. ``mean(total, labels)`` is the
    trainer's: this shard's sum over ``labels`` labels (a count known when
    the step is traced) as its part of the mean over the global batch, summed
    over the mesh. Returns ``(loss, accuracy, counters)``, the last a dict of
    this shard's counts (device scalars), which the trainer sums over the
    data axis and hands on with the step's metrics."""
    loss = mean(cross_entropy_sum(logits, y), y.size)
    accuracy = mean(correct_count(logits, y).astype(jnp.float32), y.size)
    return loss, accuracy, {}


@struct.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array


def cell_scope(first: int, last: "int | None" = None) -> str:
    """The ``jax.named_scope`` the device trace finds a model cell by: its
    index in ``Trainer.cells``, two digits; a scanned run of stacked cells,
    which one compiled body serves, is named by its first and last. The
    trace's readers match by substring (``chipbench/harness/
    step_classes.py``), so neither name holds the other."""
    if last is None:
        return f"mpi4dl_cell{first:02d}"
    return f"mpi4dl_cells{first:02d}to{last:02d}"


def _argument_shape(a) -> jax.ShapeDtypeStruct:
    """An argument without its buffer: shape, dtype and sharding."""
    return jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=getattr(a, "sharding", None))


def _same_arguments(kept, asked) -> bool:
    """Whether two trees of :func:`_argument_shape` describe the same call:
    the same structure and, leaf by leaf, shape, dtype and an equivalent
    sharding (``P()`` and ``P(None)`` place an array alike)."""
    if kept is None:
        return False
    kept, kept_tree = jax.tree.flatten(kept)
    asked, asked_tree = jax.tree.flatten(asked)
    if kept_tree != asked_tree:
        return False
    for a, b in zip(kept, asked):
        if a.shape != b.shape or a.dtype != b.dtype:
            return False
        if (a.sharding is None) != (b.sharding is None):
            return False
        if a.sharding is not None and not a.sharding.is_equivalent_to(
                b.sharding, len(a.shape)):
            return False
    return True


def apply_cells(cells: Sequence[Any], params: Sequence[Any], x):
    for cell, p in zip(cells, params):
        x = cell.apply(p, x)
    return x


class Trainer:
    """Single-program trainer for plain / DP / SP / SP+DP configs
    (``split_size == 1`` — no pipeline; the pipeline engine composes the same
    pieces over the ``pipe`` axis).

    cells: flat cell list (spatial flags baked in by the model builder).
    plain_cells: non-spatial twin with identical param structure, used for
        initialization and available to tests as the golden model. Required
        when ``num_spatial_cells > 0``.
    """

    def __init__(
        self,
        cells: Sequence[Any],
        num_spatial_cells: int,
        config: ParallelConfig,
        plain_cells: Sequence[Any] | None = None,
        mesh=None,
        learning_rate: float = 0.001,
        momentum: float = 0.9,
        remat: bool | str = False,
        grad_accum: int = 1,
        loss=None,
    ):
        """loss: the model's own, with :func:`position_cross_entropy`'s
        signature (None: that one).

        remat: False = store everything; True/"cell" = ``jax.checkpoint``
        per cell, which keeps the cell's input and what the cell's fused
        kernels' and expert layer's forwards wrote under the kept name
        (:func:`_cell_ckpt`) and recomputes the rest; "sqrt" = nested
        two-level remat (cells grouped into ~√N
        outer checkpoints, each cell checkpointed inside, so live residuals
        are ~2√N boundaries); "scan2" = "scan" with the same two-level
        nesting applied INSIDE each scan run (see :meth:`_scan_nested`) —
        carry storage drops from one boundary per cell to ~2√n per run;
        "scanq" = "scan" with each run's backward replaced by the
        anchored-quadratic sweep (:func:`chain_quadratic`, O(1) live
        boundaries per run at ~n/2 extra forwards per cell — the deepest
        memory tier, for >3072px); "scan" = the high-resolution
        workhorse:

        - consecutive cells with identical parameter structure and
          input==output shape (a ResNet stage's repeated blocks) run under
          ONE ``lax.scan`` with stacked parameters — XLA compiles a single
          checkpointed body, so conv working-set temps exist once instead of
          once per cell, and compile time drops with depth;
        - scan carries and residuals are stored as ``[B, H, W*C]`` — on TPU
          a small channel count (ResNet stage 1 has 16) otherwise sits in
          the 128-lane minormost tile dim and every stored activation pays
          up to 8x padding; flattening W*C removes that;
        - ``lax.optimization_barrier`` between the remaining un-scanned
          cells stops the scheduler from hoisting several rematerialized
          cell backwards into flight at once (each holds ~1GB of padded
          conv temps at 2048px).

        Measured on one v5e chip, ResNet-110 @1024px bs2: "scan" trains
        2.4x faster than "cell" (680 vs 278 img/s) and cuts peak HBM at
        2048px bs1 from 24.8G to 16.3G."""
        if num_spatial_cells > 0 and plain_cells is None:
            raise ValueError("spatial models need plain_cells for initialization")
        if remat not in (
            False, True, "cell", "sqrt", "scan", "scan2", "scanlog",
            "scanq", "scan_save", "cell_save", "group_save",
        ):
            raise ValueError(
                "remat must be False, True, 'cell', 'sqrt', 'scan', 'scan2', "
                f"'scanlog', 'scanq', 'scan_save', 'cell_save' or "
                f"'group_save', got {remat!r}"
            )
        if grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")

        self.grad_accum = grad_accum
        self.loss = loss or position_cross_entropy
        self.remat = remat
        self.cells = list(cells)
        self.plain_cells = list(plain_cells) if plain_cells is not None else self.cells
        self.n_spatial = num_spatial_cells
        self.config = config
        self.mesh = mesh if mesh is not None else config.make_mesh()
        self.tx = make_optimizer(learning_rate, momentum)
        if config.sequence_length:
            # token ids [batch, positions], a label at every position
            self.x_spec = P(AXIS_DATA, None)
        elif self.n_spatial > 0:
            self.x_spec = P(AXIS_DATA, AXIS_TILE_H, AXIS_TILE_W, None)
        else:
            # No spatial section → the input is only batch-sharded; any tile
            # axes in the mesh run the whole model redundantly (still correct
            # via the psum-of-contributions normalization).
            self.x_spec = P(AXIS_DATA, None, None, None)
        self.y_spec = P(AXIS_DATA, None) if config.sequence_length else P(AXIS_DATA)
        # What the newest step returned, left on the device: loss, accuracy
        # and the step's counters (``ops.sequence.step_counters``).
        self.last_metrics: dict = {}
        self._jit_step = jax.jit(self._train_step, donate_argnums=0)
        # what compiled_step last made, and the arguments it made it for
        self._compiled, self._compiled_for = None, None
        # Host-side step counter for XProf step annotation (profiling.
        # annotate_step): reading state.step would force a device sync.
        self._host_steps = 0

    # -- initialization ------------------------------------------------------
    def init(self, rng, sample_shape: Sequence[int], dtype=jnp.float32) -> TrainState:
        """Init on the plain twin (spatial cells can't trace outside a mesh
        context; param structure is identical — ``partition.init_cells``)."""
        from mpi4dl_tpu.parallel.partition import init_cells

        x = jnp.zeros(tuple(sample_shape), dtype)
        params = init_cells(self.plain_cells, rng, x)
        state = TrainState(
            params=params,
            opt_state=self.tx.init(params),
            step=jnp.zeros((), jnp.int32),
        )
        # Replicated on the mesh, as every state train_step returns is: a
        # state left on the default device gives step 2 other input
        # shardings than step 1, and the whole step compiles twice.
        return jax.device_put(state, NamedSharding(self.mesh, P()))

    def _plan_scan_runs(self, params, x):
        """Group consecutive cells into ``lax.scan`` runs: a run extends
        while the parameter structure+shapes repeat and the activation
        pytree (shape/dtype/treedef) is a fixed point of the cell — a
        ResNet stage's repeated blocks, or AmoebaNet's repeated normal
        cells, whose ``(concat, skip)`` tuple state is a pytree fixed point
        from the run's second cell on (round-1 VERDICT weak: the planner
        only accepted single-tensor fixed points, so AmoebaNet degenerated
        to per-cell checkpointing). Runs never span the SP→LP join.
        Returns a list of index lists."""

        def shapes_of(tree):
            return jax.tree.map(lambda a: (tuple(a.shape), jnp.asarray(a).dtype), tree)

        def fixed_point(o, h):
            """Same treedef + leaf shapes/dtypes: o can feed the same cell."""
            lo, to = jax.tree.flatten(o)
            lh, th = jax.tree.flatten(h)
            if to != th:
                return False
            return all(
                tuple(a.shape) == tuple(b.shape) and a.dtype == b.dtype
                for a, b in zip(lo, lh)
            )

        h = jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        at_join = self._at_join
        plans: list[list[int]] = []
        i, n = 0, len(self.cells)
        while i < n:
            h = at_join(i, h)
            o = jax.eval_shape(self.cells[i].apply, params[i], h)
            run = [i]
            if fixed_point(o, h) and jax.tree.leaves(params[i]):
                sig = shapes_of(params[i])
                j = i + 1
                while j < n and j != self.n_spatial:
                    # The run reuses cells[run[0]].apply for every
                    # iteration, so the modules must be configured
                    # identically, not merely shape-compatible (flax
                    # modules are dataclasses — == compares their config).
                    if self.cells[j] != self.cells[i]:
                        break
                    if shapes_of(params[j]) != sig:
                        break
                    oj = jax.eval_shape(self.cells[j].apply, params[j], o)
                    if not fixed_point(oj, o):
                        break
                    run.append(j)
                    j += 1
            plans.append(run)
            for k in run:
                h = jax.eval_shape(self.cells[k].apply, params[k], h)
            i = run[-1] + 1
        return plans

    def _at_join(self, i, h):
        """Account for the SP→LP tile merge in an abstract shape walk —
        shared by the scan planner and the save-budget estimator so their
        post-join footprints cannot drift apart."""
        if i == self.n_spatial and self.n_spatial > 0:

            def merge(a):
                b, hh, ww, c = a.shape
                th = self.mesh.shape[AXIS_TILE_H]
                tw = self.mesh.shape[AXIS_TILE_W]
                return jax.ShapeDtypeStruct((b, hh * th, ww * tw, c), a.dtype)

            return jax.tree.map(merge, h)
        return h

    def _apply_cells_scan(self, params, x):
        """The "scan" / "scan_save" remat policies (see ``__init__``): scan
        over repeated cells with compact ``[B, H, W*C]`` carries, barriers
        between the rest. "scan_save" additionally saves every conv output
        (tagged ``conv_out`` by ``FastConv``), so the backward recomputes
        only the elementwise/BN segments between convs — +25% conv FLOPs
        avoided for ~the activations' footprint in HBM."""
        key = (tuple(x.shape), x.dtype, self.remat)
        if getattr(self, "_scan_plan_key", None) != key:
            if self.remat == "cell_save":
                # "cell_save": per-cell checkpoints with conv-output saves,
                # NO stacked-parameter scans. Measured FASTER than
                # "scan_save" on the packed-layout bench (3.12 vs 2.35
                # img/s @1024px): separately-compiled cell bodies let XLA
                # optimize each stage globally, where the single scanned
                # body pays slicing/uniformity costs. "scan_save" remains
                # the leaner-memory / faster-compile fallback.
                self._scan_plan = [[i] for i in range(len(self.cells))]
            else:
                self._scan_plan = self._plan_scan_runs(params, x)
            self._scan_plan_key = key
        if self.remat in ("scan_save", "cell_save"):
            from mpi4dl_tpu.ops.fastconv import save_conv_outputs

            save_ckpt = _conv_save_ckpt()
            # MPI4DL_TPU_SAVE_BUDGET_MB caps TOTAL estimated conv-output
            # save bytes; runs beyond the budget fall back to plain
            # checkpoint (recompute). Full scan_save at >=2048px stores
            # ~8.5 GB of saves and reproducibly fails to compile against
            # the HBM ceiling (docs/PERF.md round 3) — a partial
            # budget keeps the save win where it is cheapest (the
            # small-activation late stages) while fitting the wall.
            # Numerics are identical either way (scheduling choice only).
            budget_mb = float(os.environ.get("MPI4DL_TPU_SAVE_BUDGET_MB", "0"))
            if budget_mb > 0:
                ckpts = self._budgeted_ckpts(params, x, budget_mb, save_ckpt)
            else:
                ckpts = [save_ckpt] * len(self._scan_plan)
            ckpts = self._nockpt_grants(params, x, ckpts)
            with save_conv_outputs():
                return self._apply_scan_plan(params, x, ckpts)
        return self._apply_scan_plan(
            params,
            x,
            self._nockpt_grants(
                params, x, [jax.checkpoint] * len(self._scan_plan)
            ),
        )

    def _budgeted_ckpts(self, params, x, budget_mb: float, save_ckpt):
        """Per-run checkpoint choice under a save-byte budget: estimate
        each run's conv-output save footprint as ~2x its input activation
        bytes per cell (bottleneck conv outputs sum to 1.5x the cell I/O
        channels; 2x is a safe planning bound), then grant saves to the
        cheapest runs first — maximum recompute avoided per saved byte."""
        def tree_bytes(t):
            return sum(
                int(np.prod(a.shape)) * a.dtype.itemsize
                for a in jax.tree.leaves(t)
            )

        shapes = []
        h = jax.ShapeDtypeStruct(x.shape, x.dtype)
        for run in self._scan_plan:
            h = self._at_join(run[0], h)  # SP→LP merge, like the planner
            shapes.append(2.0 * tree_bytes(h) * len(run))
            for k in run:
                h = jax.eval_shape(self.cells[k].apply, params[k], h)
        # Grant order (MPI4DL_TPU_SAVE_ORDER): "small" (default) packs the
        # most runs under the budget — late high-channel stages, the best
        # FLOPs-avoided-per-byte; "big" spends it on the early high-
        # resolution stages instead, whose absolute recompute time is
        # largest. An A/B lever for the >=2048px regime where the full
        # save set exceeds what compiles.
        order_pref = os.environ.get("MPI4DL_TPU_SAVE_ORDER", "small")
        if order_pref not in ("small", "big"):
            raise ValueError(
                f"MPI4DL_TPU_SAVE_ORDER must be small|big, got {order_pref!r}"
            )
        order = sorted(
            range(len(shapes)),
            key=lambda i: shapes[i],
            reverse=order_pref == "big",
        )
        budget = budget_mb * 1e6
        ckpts = [jax.checkpoint] * len(shapes)
        for i in order:
            if shapes[i] <= budget:
                ckpts[i] = save_ckpt
                budget -= shapes[i]
        return ckpts

    def _nockpt_grants(self, params, x, ckpts):
        """Third remat tier (``MPI4DL_TPU_NOCKPT_BUDGET_MB``, default off):
        runs whose FULL residual set fits the budget run with NO checkpoint
        at all — their backward replays nothing. Rationale: the AmoebaNet
        profile (docs/PERF.md round 4) shows the step is elementwise/HBM-
        bound, not FLOPs-bound, and checkpointing makes the backward re-run
        exactly those elementwise chains; the late stages' residuals are
        small (pixels shrink 4x per reduction while channels only double,
        so per-stage bytes HALVE), making them the cheapest recompute to
        buy back. Residual bytes are estimated from the cell jaxpr (sum of
        every equation output aval), cheapest runs first. Numerics are
        identical — checkpointing is a scheduling choice."""
        nockpt_mb = float(os.environ.get("MPI4DL_TPU_NOCKPT_BUDGET_MB", "0"))
        if nockpt_mb <= 0:
            return ckpts

        def eqn_out_bytes(jaxpr) -> float:
            total = 0.0
            for eqn in jaxpr.eqns:
                # Call-like equations (pjit / custom_vjp / remat wrappers):
                # count ONLY the sub-jaxpr — the outer eqn's outvars are the
                # sub-jaxpr's final outputs and would double-count.
                subs = [
                    val.jaxpr
                    for val in eqn.params.values()
                    if hasattr(val, "jaxpr")
                ]
                if subs:
                    total += sum(eqn_out_bytes(j) for j in subs)
                    continue
                for v in eqn.outvars:
                    aval = v.aval
                    if hasattr(aval, "shape"):
                        total += float(np.prod(aval.shape)) * aval.dtype.itemsize
            return total

        est = []
        h = jax.ShapeDtypeStruct(x.shape, x.dtype)
        for run in self._scan_plan:
            h = self._at_join(run[0], h)
            i = run[0]
            closed = jax.make_jaxpr(self.cells[i].apply)(params[i], h)
            est.append(eqn_out_bytes(closed.jaxpr) * len(run))
            for k in run:
                h = jax.eval_shape(self.cells[k].apply, params[k], h)

        budget = nockpt_mb * 1e6
        ckpts = list(ckpts)
        for i in sorted(range(len(est)), key=lambda i: est[i]):
            if est[i] <= budget:
                ckpts[i] = _no_ckpt
                budget -= est[i]
        return ckpts

    @staticmethod
    def _compact(tree):
        """[B, H, W, C] leaves → [B, H, W*C] (the 128-lane pad-tax dodge
        for scan carries/residuals) — but only where the tax is real:
        leaves whose stored padding factor ceil(C/128)*128/C is >= 2
        (ResNet stage carries: C=16/32/64 pay 8x/4x/2x; note C=65..127
        pays up to 1.97x and stays 4-D under this gate — a model carrying
        such widths at fit-barely resolutions trades carry HBM for the
        reshape cost below). AmoebaNet's >=104-channel carries pay at
        most 1.23x, and the flatten around them was far worse than its
        reshape self-time: Pallas custom calls can't fuse, so every pool
        kernel operand/result paid a full-res relayout at the carry
        boundary — un-flattening them measured +15.5% end-to-end on the
        @1024 headline (docs/PERF.md round-4 "flatten interaction").
        Other ranks pass through. Returns
        (compact_tree, (treedef, shape_list)) for :meth:`_restore`."""

        def pad_tax(c: int) -> float:
            return (-(-c // 128) * 128) / c

        leaves, treedef = jax.tree.flatten(tree)
        shapes = [tuple(a.shape) for a in leaves]
        out = [
            a.reshape(a.shape[0], a.shape[1], -1)
            if a.ndim == 4 and pad_tax(a.shape[-1]) >= 2
            else a
            for a in leaves
        ]
        return jax.tree.unflatten(treedef, out), (treedef, shapes)

    @staticmethod
    def _restore(tree, meta):
        treedef, shapes = meta
        leaves = jax.tree.leaves(tree)
        return jax.tree.unflatten(
            treedef, [a.reshape(s) for a, s in zip(leaves, shapes)]
        )

    def _apply_scan_plan(self, params, x, ckpts):
        h = x
        for ckpt, run in zip(ckpts, self._scan_plan):
            if len(run) == 1:
                i = run[0]
                with jax.named_scope(cell_scope(i)):
                    if i == self.n_spatial and self.n_spatial > 0:
                        h = jax.tree.map(gather_tiles, h)
                    h = ckpt(self.cells[i].apply)(params[i], h)
                h = optimization_barrier(h)
                continue
            # one body runs every cell of the run: the scope names the run
            with jax.named_scope(cell_scope(run[0], run[-1])):
                if run[0] == self.n_spatial and self.n_spatial > 0:
                    h = jax.tree.map(gather_tiles, h)
                stacked = jax.tree.map(
                    lambda *leaves: jnp.stack(leaves), *[params[k] for k in run]
                )
                cell = self.cells[run[0]]
                hc, shapes = self._compact(h)

                def apply_compact(p, hc, cell=cell, shapes=shapes):
                    o = cell.apply(p, self._restore(hc, shapes))
                    # Output compact-shapes equal the input's: the planner only
                    # groups fixed-point cells.
                    return self._compact(o)[0]

                def body(hc, p):
                    return ckpt(apply_compact)(p, hc), None

                # Unrolling amortizes the scan machinery (parameter
                # dynamic-slices, carry copies, loop overhead) at the cost of a
                # proportionally bigger program. Measured on one v5e (docs/
                # PERF.md round 3): unroll=3 takes AmoebaNet-D @1024 bs2 from
                # 4.92 to 6.37 img/s (+29%) and @2048 bs1 from 1.09 to 1.27
                # (+16%); ResNet is neutral (its hot path is cell_save, and its
                # @2048 scan is recompute-bound, 0.495 -> 0.492). unroll=6
                # matches unroll=3, so 3 is the default — the smallest program
                # that captures the win. MPI4DL_TPU_SCAN_UNROLL overrides.
                unroll = scan_unroll()
                if (
                    self.remat == "scanq"
                    and len(run) >= 3
                    and ckpt is not _no_ckpt
                    and not self._scanq_store_granted(run, params, x)
                ):
                    # Anchored-quadratic backward: O(1) live boundaries per
                    # run (the >3072px policy — chain_quadratic docstring).
                    # Short runs stay on the plain checkpointed scan: the
                    # masked-sweep machinery only pays past ~2 cells.
                    hc = chain_quadratic(apply_compact, stacked, hc)
                    hc = optimization_barrier(hc)
                elif (
                    self.remat == "scan2"
                    and len(run) >= 4
                    and ckpt is not _no_ckpt
                ):
                    # A _nockpt_grants grant overrides the nesting: the whole
                    # point of the no-checkpoint tier is to store residuals and
                    # replay nothing, which the plain scan body below (with
                    # ckpt == _no_ckpt) does.
                    hc = self._scan_nested(hc, stacked, apply_compact)
                else:
                    hc, _ = lax.scan(body, hc, stacked, unroll=unroll)
                h = self._restore(hc, shapes)
        return h

    def _scanq_store_granted(self, run, params, x) -> bool:
        """``MPI4DL_TPU_SCANQ_STORE_MB`` (default 0 = off): under "scanq",
        runs whose full carry set (len(run) x compact carry bytes) fits
        the budget keep the plain checkpointed scan — storing a cheap
        run's carries avoids its quadratic recompute while the expensive
        runs stay anchored. The budget is granted BACK-TO-FRONT over the
        scan plan (decided for every eligible run at the first call of a
        trace, via the same abstract shape walk as ``_budgeted_ckpts``):
        the late small-activation stages free their stored carries before
        the early stages' backward runs, so they are the safe grants —
        and the cheapest, so the budget covers more runs. (ADVICE-r5:
        consuming the budget front-to-back handed the storage to the
        EARLIEST fitting run — the opposite of this rationale.) A pure
        scheduling choice; golden-tested with the budget set.

        Caveat: a granted run later downgraded to the no-checkpoint tier
        by ``_nockpt_grants`` (both budgets set at once) keeps its
        deduction — the unused reservation wastes budget, never
        correctness."""
        budget_mb = float(os.environ.get("MPI4DL_TPU_SCANQ_STORE_MB", "0"))
        if budget_mb <= 0:
            return False
        # Keyed by run identity (its first cell index — stable for a given
        # scan plan), NOT by carry shape: two distinct same-shaped runs
        # must EACH deduct the budget, while retraces of the same plan
        # must reuse the original decision.
        if getattr(self, "_scanq_budget_key", None) != self._scan_plan_key:
            self._scanq_budget_key = self._scan_plan_key
            self._scanq_grants = {}
            self._scanq_grant_bytes = {}
            # Abstract walk over the plan (same shape math as the
            # planner / _budgeted_ckpts: _at_join then per-cell
            # eval_shape) — the carry at a run's input has the same byte
            # count compacted or not.
            carry_bytes_at: dict[int, int] = {}
            h = jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
            for r in self._scan_plan:
                h = self._at_join(r[0], h)
                carry_bytes_at[r[0]] = sum(
                    int(np.prod(a.shape)) * a.dtype.itemsize
                    for a in jax.tree.leaves(h)
                ) * len(r)
                for k in r:
                    h = jax.eval_shape(self.cells[k].apply, params[k], h)
            left = budget_mb * 1e6
            for r in reversed(self._scan_plan):
                if len(r) < 3:
                    continue  # short runs never take the scanq path
                granted = carry_bytes_at[r[0]] <= left
                if granted:
                    left -= carry_bytes_at[r[0]]
                    # Recorded per run for the analyzer's remat-
                    # effectiveness rule (Trainer.remat_report):
                    # grants vs budget vs peak.
                    self._scanq_grant_bytes[r[0]] = carry_bytes_at[r[0]]
                self._scanq_grants[r[0]] = granted
            self._scanq_budget_left = left
        return self._scanq_grants.get(run[0], False)

    def _run_cell(self, i, p, h):
        """Apply cell ``i`` (inserting the SP→LP tile merge before cell
        ``n_spatial``) — the one definition of the merge point, shared by
        every remat policy."""
        with jax.named_scope(cell_scope(i)):
            if i == self.n_spatial and self.n_spatial > 0:
                h = jax.tree.map(gather_tiles, h)
            return self.cells[i].apply(p, h)

    def _apply_cells_scanlog(self, params, x):
        """remat="scanlog": logarithmic recursive checkpointing over the
        WHOLE cell sequence — split in half, checkpoint the left half,
        recurse into both; leaves are per-cell checkpoints. Live saved
        boundaries are one per recursion level (~log2 N of MIXED sizes:
        the path into the expensive early-stage cells is mostly small
        early boundaries, and the later stages' saves are freed before
        the early stages' backward runs), versus scan2's ~2*sqrt(n)
        same-size set per run PLUS every singleton cell's pinned input.
        Measured @3072px (docs/PERF.md round 4): recursive structures
        pack with ~7% buffer-assignment fragmentation where scan runs
        fragment 36-46%. Cost: each cell's forward recomputes ~depth
        times (~5-6x at N=38). This is the deepest-memory policy — it is
        what lands 3072px on one 16 GB chip (0.165 img/s; its ~23.7 GB
        live set still exceeds HBM at 4096px, where the "scanq"
        anchored-quadratic tier — O(1) live boundaries per run,
        :func:`chain_quadratic` — takes over as the overall deepest
        memory policy, docs/PERF.md round 5); barriers keep one rematted
        backward in flight."""

        def rec(i, j, ps, h):
            if j - i == 1:
                h = jax.checkpoint(functools.partial(self._run_cell, i))(
                    ps[0], h
                )
                return optimization_barrier(h)
            mid = (i + j) // 2

            def left(ps_left, h):
                return rec(i, mid, ps_left, h)

            h = jax.checkpoint(left)(ps[: mid - i], h)
            h = optimization_barrier(h)
            return rec(mid, j, ps[mid - i :], h)

        return rec(0, len(self.cells), list(params), x)

    @staticmethod
    def _scan_nested(hc, stacked, apply_compact):
        """Two-level (~sqrt-depth) checkpointing over one scan run — the
        "scan2" policy's heart. The run's n cells split into ~sqrt(n)-sized
        chunks; an outer lax.scan carries only CHUNK boundaries and each
        chunk is one jax.checkpoint whose backward re-runs its inner
        (per-cell-checkpointed) scan. Live residuals drop from n cell
        boundaries ("scan") to ~2*sqrt(n), at the price of one extra
        forward recompute. This is what fits ResNet-110 @4096px bs=1 on one
        16 GB chip: under "scan" the three stages' stored carries alone are
        ~16 GB (18 x 512 MB + 18 x 256 MB + 18 x 128 MB, docs/PERF.md
        round 4), which the compiler rejects at buffer-assignment time —
        the 4096px "compile wall" was an out-of-memory program, not a
        compiler defect."""
        n = jax.tree.leaves(stacked)[0].shape[0]
        g = max(2, int(round(n ** 0.5)))
        m, rem = divmod(n, g)

        def chunk(hc, ps):
            def body(hc, p):
                # The barrier serializes consecutive cells' (rematted)
                # backwards — its transpose is also a barrier — so only
                # ONE cell's recompute temps are in flight. scan2 exists
                # to fit, not to overlap: without this the @3072 compile
                # holds ~2 cells' temps and misses HBM by ~400 MB
                # (docs/PERF.md round 4). Inner unroll stays 1 for the
                # same reason (MPI4DL_TPU_SCAN2_UNROLL overrides).
                hc = jax.checkpoint(apply_compact)(p, hc)
                return optimization_barrier(hc), None

            inner_unroll = int(os.environ.get("MPI4DL_TPU_SCAN2_UNROLL", "1"))
            hc, _ = lax.scan(body, hc, ps, unroll=inner_unroll)
            return hc

        if os.environ.get("MPI4DL_TPU_SCAN2_OFFLOAD") == "1":
            # Offload variant: the outer level is a Python loop whose
            # INTERIOR chunk boundaries are pinned-host tensors — each
            # chunk's jax.checkpoint then saves the host copy, so between
            # that chunk's forward and backward the boundary occupies zero
            # HBM (measured 5.9 GB/s effective roundtrip). The first and
            # last chunks keep device inputs: host values adjacent to the
            # program's entry/exit trip the XLA offloader ("moved to host
            # ... returned from the entry computation"), and the
            # optimization barriers around each transfer stop placement
            # propagation into neighboring fusions; memory-space transfers
            # (device_put to jax.memory.Space) preserve the traced
            # sharding, so the path is mesh-shape-agnostic. (A single outer
            # checkpoint with a save_and_offload policy was measured
            # WORSE — one big recompute region overlaps chunks'
            # backwards, docs/PERF.md round 4.)
            def chunk_off(hc_host, ps):
                hc = jax.device_put(hc_host, jax.memory.Space.Device)
                hc = optimization_barrier(hc)
                return chunk(hc, ps)

            chunk_off_ck = jax.checkpoint(chunk_off)
            chunk_ck_plain = jax.checkpoint(chunk)
            bounds = [0, rem] if rem else [0]
            while bounds[-1] < n:
                bounds.append(bounds[-1] + g)
            for i, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
                ps = jax.tree.map(lambda a: a[lo:hi], stacked)
                interior = 0 < i < len(bounds) - 2
                if interior:
                    hc = optimization_barrier(hc)
                    hc_host = jax.device_put(hc, jax.memory.Space.Host)
                    hc = chunk_off_ck(hc_host, ps)
                else:
                    hc = chunk_ck_plain(hc, ps)
            return hc

        chunk_ck = jax.checkpoint(chunk)
        if rem:
            head = jax.tree.map(lambda a: a[:rem], stacked)
            hc = chunk_ck(hc, head)
        tail = jax.tree.map(
            lambda a: a[rem:].reshape((m, g) + a.shape[1:]), stacked
        )
        hc, _ = lax.scan(lambda hc, ps: (chunk_ck(hc, ps), None), hc, tail)
        return hc

    def _apply_cells_remat(self, params, x):
        """Run all cells under the configured remat policy (inserting the
        SP→LP tile merge before cell ``n_spatial``)."""
        run_cell = self._run_cell

        if self.remat == "scanlog":
            return self._apply_cells_scanlog(params, x)
        if self.remat in ("scan", "scan2", "scanq", "scan_save", "cell_save"):
            return self._apply_cells_scan(params, x)
        if self.remat in (True, "cell"):
            h = x
            cell_ckpt = _cell_ckpt()
            for i in range(len(self.cells)):
                h = cell_ckpt(functools.partial(run_cell, i))(params[i], h)
            return h
        if self.remat == "sqrt":
            n = len(self.cells)
            g = max(int(np.sqrt(n)), 1)
            h = x
            for start in range(0, n, g):
                idx = list(range(start, min(start + g, n)))

                def run_group(group_params, h, idx=idx):
                    for i, p in zip(idx, group_params):
                        h = jax.checkpoint(functools.partial(run_cell, i))(p, h)
                    return h

                h = jax.checkpoint(run_group)([params[i] for i in idx], h)
            return h
        if self.remat == "group_save":
            # The scan-unroll lesson (docs/PERF.md round 3: +29% AmoebaNet)
            # applied to the no-scan path: checkpoint GROUPS of consecutive
            # cells (MPI4DL_TPU_GROUP_SIZE, default 3) with conv-output
            # saves, so XLA schedules/fuses across the cell boundaries that
            # per-cell checkpoints (cell_save) wall off, while the group
            # barrier still bounds how many rematerialized backwards are in
            # flight.
            from mpi4dl_tpu.ops.fastconv import save_conv_outputs

            g = max(int(os.environ.get("MPI4DL_TPU_GROUP_SIZE", "3")), 1)
            save_ckpt = _conv_save_ckpt()
            n = len(self.cells)
            h = x
            with save_conv_outputs():
                for start in range(0, n, g):
                    idx = list(range(start, min(start + g, n)))

                    def run_group(group_params, h, idx=idx):
                        for i, p in zip(idx, group_params):
                            h = run_cell(i, p, h)
                        return h

                    h = save_ckpt(run_group)([params[i] for i in idx], h)
                    h = optimization_barrier(h)
            return h
        h = x
        for i in range(len(self.cells)):
            h = run_cell(i, params[i], h)
        return h

    def _apply_counting_cells(self, params, x):
        """:meth:`_apply_cells_remat` for a model whose cells count what
        they did (a cell names the flax collection it sows into as its
        ``counters``: an expert layer's token-expert pairs). Returns the
        logits and ``{name: [one array per sowing module]}``. Such a model
        has no spatial section and runs under "cell" remat or none."""
        if self.remat not in (False, True, "cell") or self.n_spatial:
            raise ValueError(
                "cells that count run under remat False or 'cell' and have "
                f"no spatial section; got remat {self.remat!r}, "
                f"{self.n_spatial} spatial cells")

        def run_cell(i, p, h):
            cell = self.cells[i]
            collection = getattr(cell, "counters", None)
            with jax.named_scope(cell_scope(i)):
                if collection is None:
                    return cell.apply(p, h), {}
                y, sown = cell.apply(p, h, mutable=[collection])
            return y, sown.get(collection, {})

        counted: dict = {}
        h = x
        ckpt = _cell_ckpt() if self.remat else _no_ckpt
        for i in range(len(self.cells)):
            h, sown = ckpt(functools.partial(run_cell, i))(params[i], h)
            for path, leaf in jax.tree_util.tree_leaves_with_path(sown):
                name = next(k.key for k in reversed(path) if hasattr(k, "key"))
                counted.setdefault(name, []).append(leaf)
        return h, counted

    # -- loss ----------------------------------------------------------------
    def _local_loss(self, params, x, y):
        """Per-device loss contribution; runs inside shard_map.

        Contributions are scaled so that ``psum`` over every mesh axis equals
        the global batch mean — forward value and gradients are then exact
        regardless of how many devices redundantly compute the post-join
        (replicated) section. This one line replaces the reference's
        ``divide_bs`` case analysis (``comm.py:349-358``).
        """
        counted = {}
        if any(getattr(c, "counters", None) for c in self.cells):
            logits, counted = self._apply_counting_cells(params, x)
            # each data shard counted its own rows; the tile axes repeat them
            counted = jax.tree.map(lambda c: lax.psum(c, AXIS_DATA), counted)
        else:
            logits = self._apply_cells_remat(params, x)

        d = axis_size(AXIS_DATA)
        replicas = axis_size(AXIS_TILE_H) * axis_size(AXIS_TILE_W)
        axes = (AXIS_DATA, AXIS_TILE_H, AXIS_TILE_W)

        def mean(total, labels):
            # ``labels`` in this shard's batch (one an image, one a position
            # of a token sequence), ``d`` times as many in the global batch
            return lax.psum(total / (labels * d * replicas), axes)

        with jax.named_scope("mpi4dl_loss"):
            loss, acc, more = self.loss(logits, y, mean)
            more = jax.tree.map(lambda c: lax.psum(c, AXIS_DATA), more)
        return loss, (acc, counted, more)

    def _sharded_loss(self, params, x, y):
        fn = shard_map(
            self._local_loss,
            mesh=self.mesh,
            in_specs=(P(), self.x_spec, self.y_spec),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn(params, x, y)

    # -- step ----------------------------------------------------------------
    def _train_step(self, state: TrainState, x, y):
        from mpi4dl_tpu.ops.sequence import step_counters

        counted = {}
        if self.grad_accum == 1:
            def loss_fn(params):
                return self._sharded_loss(params, x, y)

            (loss, (acc, counted, more)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
        else:  # the chunks' counters are not kept
            loss, acc, grads = self._accum_grads(state.params, x, y)
            more = {}
        with jax.named_scope("mpi4dl_optimizer"):
            updates, opt_state = self.tx.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            params=params, opt_state=opt_state, step=state.step + 1
        )
        return new_state, {"loss": loss, "accuracy": acc, **step_counters(counted), **more}

    def _accum_grads(self, params, x, y):
        """Gradient accumulation: the batch runs as ``grad_accum`` equal
        chunks under ONE ``lax.scan`` — a bs=B/k working set and a bs=B/k
        program (one compiled chunk body). The update applies the MEAN of
        the per-chunk gradients (mean-of-chunk-means == global mean for
        equal chunks). BatchNorm statistics are per-chunk (a batch-of-B/k
        forward), so for BN models this is not bit-identical to the
        unchunked batch — it has exactly the semantics of the reference's
        GEMS ``--times`` chunks, each of which runs its own BN batch
        (``gems_master.py:72-103``), and of ``GemsMasterTrainer`` here.

        This is what lands large-image configs whose unchunked program
        kills the compile pipeline or HBM (e.g. AmoebaNet-D @2048px bs=2 —
        docs/PERF.md round 3): the per-step batch stays at the reference's
        published size while the device only ever holds one chunk. The
        reference's only equivalent is GEMS ``--times`` replication
        (``gems_master.py:72-103``), which requires the mirrored-model
        scheme; here it is a plain Trainer knob.

        Chunks are contiguous batch slices: on a DP-sharded batch axis the
        reshape may insert resharding collectives — grad_accum targets the
        single-chip / spatial-parallel memory wall, not DP scaling.
        """
        k = self.grad_accum
        b = x.shape[0]
        if b % k != 0:
            raise ValueError(f"batch {b} not divisible by grad_accum={k}")
        xs = x.reshape(k, b // k, *x.shape[1:])
        ys = y.reshape(k, b // k, *y.shape[1:])

        def chunk_loss(params, xc, yc):
            return self._sharded_loss(params, xc, yc)

        def body(carry, xy):
            gsum, lsum, asum = carry
            (l, (a, *_)), g = jax.value_and_grad(chunk_loss, has_aux=True)(
                params, *xy
            )
            carry = (jax.tree.map(jnp.add, gsum, g), lsum + l, asum + a)
            return carry, None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (gsum, lsum, asum), _ = lax.scan(
            body, (zeros, jnp.zeros(()), jnp.zeros(())), (xs, ys)
        )
        grads = jax.tree.map(lambda t: t / k, gsum)
        return lsum / k, asum / k, grads

    def shard_batch(self, x, y):
        """Place a host batch onto the mesh with the trainer's sharding
        (the ``split_input`` moment, minus the hand-slicing). Multi-process,
        (x, y) are this host's local batch shard
        (:func:`mpi4dl_tpu.parallel.multihost.put_global`)."""
        from mpi4dl_tpu.parallel.multihost import put_global

        return put_global(self.mesh, (self.x_spec, self.y_spec), x, y)

    # -- static analysis support (mpi4dl_tpu.analysis) -----------------------
    def halo_shift_count(self, params, x_shape, dtype=jnp.float32) -> int:
        """Forward halo shift ppermutes in ONE un-scanned pass over the
        cells — the partition-math floor the analyzer's permute rule checks
        the compiled inventory against (each shift lowers to exactly one
        ``collective-permute``; the backward at most doubles it). Counted
        by abstract tracing (``jax.eval_shape``) with the per-cell loop
        shared by every remat policy, so scan-carried cells are counted
        once per ITERATION, not once per compiled body."""
        from mpi4dl_tpu.parallel.halo import count_halo_shifts

        def local(ps, x):
            h = x
            for i in range(len(self.cells)):
                h = self._run_cell(i, ps[i], h)
            return jax.tree.map(lambda a: jnp.sum(a, dtype=jnp.float32), h)

        fn = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(), self.x_spec),
            out_specs=P(),
            check_vma=False,
        )
        x = jax.ShapeDtypeStruct(tuple(x_shape), dtype)
        with count_halo_shifts() as box:
            jax.eval_shape(fn, params, x)
        return box[0]

    def collective_deltas(self, params, x_shape, dtype=jnp.float32):
        """This trainer's layer deltas for the expectations algebra
        (:mod:`mpi4dl_tpu.analysis.expectations`): the spatial front's
        halo entitlement over the counted forward shifts when cells are
        spatially partitioned, else the pure-DP entitlement. Gate a
        compiled step with ``compose(*trainer.collective_deltas(...))``."""
        from mpi4dl_tpu.analysis.expectations import (
            data_parallel_delta,
            spatial_delta,
        )

        if self.n_spatial > 0:
            return (
                spatial_delta(
                    self.config.tile_shape,
                    self.halo_shift_count(params, x_shape, dtype=dtype),
                ),
            )
        return (data_parallel_delta(),)

    def publish_telemetry(
        self, registry=None, params=None, x_shape=None, dtype=jnp.float32
    ):
        """Publish the trainer's static facts as cataloged gauges
        (docs/OBSERVABILITY.md): the remat policy's store budget and
        granted bytes (:meth:`remat_report`), plus — when ``params`` and
        ``x_shape`` are given — the forward halo-shift count
        (:meth:`halo_shift_count`, an abstract trace; no device work).
        ``registry=None`` uses the process-wide default. Step-time series
        come from :class:`mpi4dl_tpu.profiling.StepTimer(registry=...)`,
        not from here. Returns the registry."""
        from mpi4dl_tpu import telemetry

        reg = registry if registry is not None else telemetry.default_registry()
        rep = self.remat_report()
        telemetry.declare(reg, "train_remat_store_budget_mb").set(
            rep["store_budget_mb"]
        )
        telemetry.declare(reg, "train_remat_granted_bytes").set(
            rep["granted_bytes"]
        )
        if params is not None and x_shape is not None:
            telemetry.declare(reg, "train_halo_shifts").set(
                self.halo_shift_count(params, x_shape, dtype=dtype)
            )
        return reg

    def capture_trace_attribution(
        self,
        state,
        x,
        y,
        steps: int = 3,
        logdir: "str | None" = None,
        registry=None,
        program: str = "train_step",
    ):
        """Capture an XProf trace of ``steps`` live train steps and
        attribute device time (:mod:`mpi4dl_tpu.analysis.trace`): per-step
        compute / collective / transfer / host-gap buckets plus the
        measured collective-overlap ratio — the runtime cross-check of
        hlolint's static start→done rule. With a ``registry``, publishes
        the cataloged ``trace_*`` gauges under ``program``.

        Returns ``(state, summary)`` — the state advances by ``steps``
        real optimizer updates (the capture measures the genuine step,
        not a replay)."""
        from mpi4dl_tpu import profiling

        box = {"state": state}

        def one_step(i):
            del i
            box["state"], metrics = self.train_step(box["state"], x, y)
            return metrics["loss"]

        cap = profiling.capture(one_step, steps=steps, logdir=logdir)
        summary = cap.attribution(registry=registry, program=program)
        return box["state"], summary

    def remat_report(self) -> dict:
        """Remat/store-budget metadata for the analyzer's effectiveness
        rule: the configured policy + scanq store budget, and the grant
        bytes actually recorded at the last trace (empty before tracing)."""
        grants = getattr(self, "_scanq_grant_bytes", {})
        return {
            "policy": self.remat if isinstance(self.remat, str) else str(self.remat),
            "store_budget_mb": float(
                os.environ.get("MPI4DL_TPU_SCANQ_STORE_MB", "0")
            ),
            "granted_bytes": sum(grants.values()),
            "grants": dict(grants),
        }

    def train_step(self, state: TrainState, x, y):
        from contextlib import ExitStack

        from mpi4dl_tpu.ops import pool_pallas
        from mpi4dl_tpu.ops.fastconv import wgrad_taps_threshold
        from mpi4dl_tpu.profiling import annotate_step

        step_id = self._host_steps
        self._host_steps += 1
        with ExitStack() as stack:
            # XProf step boundary carrying the same host-side step id the
            # telemetry layer records, so profiling.trace dumps align with
            # StepTimer/span data (docs/OBSERVABILITY.md).
            stack.enter_context(annotate_step("mpi4dl_train_step", step_id))
            if self.config.image_size >= 3072:
                # Arm the aggressive per-tap wgrad gate for this trace:
                # at these sizes the backward-filter conv's padded
                # operand copies are what OOMs the step (docs/PERF.md
                # round 4). A trace-time context, not process state —
                # other Trainers in the process keep the 3072 MB
                # default; the env override still wins inside
                # taps_min_mb.
                stack.enter_context(wgrad_taps_threshold(256))
            if self.config.image_size >= 2048:
                # Keep the Pallas pool backward out of large-image
                # programs: its VMEM-stack-allocated results fail the
                # compile against the HBM ceiling (measured:
                # AmoebaNet@2048 bs1 compiles with it off, fails with
                # it on — pool_pallas.disable docstring; re-validated
                # round 5 via MPI4DL_TPU_POOL_PALLAS=on).
                stack.enter_context(pool_pallas.disable())
            try:
                state, self.last_metrics = self._jit_step(state, x, y)
                return state, self.last_metrics
            except Exception as e:
                # OOM forensics (telemetry/memory.py): a RESOURCE_EXHAUSTED
                # train step emits a structured oom.report — the parsed HBM
                # table + largest buffers — into the env-gated JSONL log
                # before the exception surfaces. Three rounds of PERF.md
                # debugging were spent re-discovering what the truncated
                # message already carried; the report keeps it.
                from mpi4dl_tpu.telemetry import memory as memobs

                if memobs.is_oom_error(e):
                    from mpi4dl_tpu import telemetry

                    events = telemetry.JsonlWriter()  # env-gated; no-op
                    try:  # without MPI4DL_TPU_TELEMETRY_DIR
                        memobs.emit_oom_report(
                            e, program="train_step",
                            events=events if events.enabled else None,
                            attrs={
                                "image_size": self.config.image_size,
                                "remat": self.remat
                                if isinstance(self.remat, str)
                                else str(self.remat),
                            },
                        )
                    finally:
                        events.close()
                raise

    def compiled_step(self, state, x, y):
        """The compiled train step for arguments like these (arrays, or
        ``jax.ShapeDtypeStruct``s with their shardings); its ``as_text()``
        names every HLO instruction with the jax name stack it came from.
        For a step the process already ran this is one more trace and a
        cache hit; the object is kept, so asking again for arguments of the
        same shapes and shardings costs nothing."""
        asked = jax.tree.map(_argument_shape, (state, x, y))
        if not _same_arguments(self._compiled_for, asked):
            self._compiled = self._jit_step.lower(state, x, y).compile()
            self._compiled_for = asked
        return self._compiled

    def record_memory_footprint(
        self, state, x, y, ledger=None, registry=None,
        program: str = "train_step",
    ) -> dict:
        """Record the compiled train step's predicted peak into a
        :class:`~mpi4dl_tpu.telemetry.memory.FootprintLedger` (a fresh
        one when none is given), from :meth:`compiled_step`'s object: one
        more trace and a warm-cache compile for a step the process already
        ran, nothing where that object is already kept; before any
        execution it is the feasibility planner's compile-only
        prediction."""
        from mpi4dl_tpu.telemetry.memory import FootprintLedger

        if ledger is None:
            ledger = FootprintLedger(registry=registry)
        return ledger.record_compiled(program, self.compiled_step(state, x, y))


def single_device_step(cells: Sequence[Any], learning_rate=0.001, momentum=0.9, parts=1):
    """Golden single-device train step (tests compare distributed runs
    against this — the role the reference's sequential-conv golden runs play
    in ``benchmark_sp_halo_exchange_with_compute_val.py:704-780``).

    parts > 1 reproduces micro-batched semantics: each micro-batch flows
    through the model separately (so BatchNorm statistics are per
    micro-batch, exactly like the pipeline schedule and the reference's
    ``parts`` loop, ``mp_pipeline.py:509-534``), losses averaged.
    """
    tx = make_optimizer(learning_rate, momentum)

    @jax.jit
    def step(state: TrainState, x, y):
        def loss_fn(params):
            b = y.shape[0]
            xm = x.reshape((parts, b // parts) + tuple(x.shape[1:]))
            ym = y.reshape((parts, b // parts) + tuple(y.shape[1:]))
            ce = jnp.zeros((), jnp.float32)
            cc = jnp.zeros((), jnp.float32)
            for m in range(parts):
                logits = apply_cells(cells, params, xm[m])
                ce += cross_entropy_sum(logits, ym[m])
                cc += correct_count(logits, ym[m]).astype(jnp.float32)
            # over the labels: one an image, one a position of a sequence
            return ce / y.size, cc / y.size

        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return (
            TrainState(params=params, opt_state=opt_state, step=state.step + 1),
            {"loss": loss, "accuracy": acc},
        )

    return tx, step
