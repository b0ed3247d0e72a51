"""Shared benchmark runner.

Replaces the per-script boilerplate of the reference's 8 training benchmarks
(``benchmarks/*/benchmark_*.py``): parse the shared CLI, build the
``ParallelConfig`` + trainer for the requested parallelism mode, run epochs
with per-step timing, print images/sec mean/median at exit
(ref timing: ``benchmark_amoebanet_sp.py:322-367`` — CUDA events there,
host-side timing with ``block_until_ready`` here; both wall-clock).

Every benchmark is one SPMD program over however many devices JAX sees:
one real TPU chip, a CPU simulation
(``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=N``),
or a multi-host pod — ``build_config`` joins the distributed world
(``multihost.initialize_distributed``), ``make_trainer`` builds a DCN-aware
mesh, and ``run_training`` feeds each host only its data shard. There is no
``mpirun_rsh`` contract; single-host launch needs no launcher at all.
"""

from __future__ import annotations

import os
import statistics
import sys
import time

import numpy as np


def parse_csv_ints(s):
    if s is None:
        return None
    return [int(v) for v in str(s).split(",")]


def build_config(args, spatial: bool, num_cells: int | None = None):
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.elastic import maybe_supervise
    from mpi4dl_tpu.parallel import multihost
    from mpi4dl_tpu.utils import enable_compilation_cache

    # --max-restarts: re-exec under the fault-tolerance supervisor. Must
    # happen HERE — before make_mesh/init touch the accelerator, which a
    # supervisor process may not hold (TPU access is per-process exclusive).
    maybe_supervise(args)
    enable_compilation_cache()  # multi-minute XLA compiles amortize across runs
    # A training step's executable carries the jax name stacks it was compiled
    # with, and the device trace's readers find the program's
    # ``jax.named_scope``s there (``Trainer.compiled_step``). JAX's cache key
    # leaves them out unless told: a step whose scopes alone changed would
    # load the older executable, with the older names.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # Join the multi-host world if one is configured (no-op single-process;
    # the reference's dist.init_process_group moment, comm.py:154-159).
    multihost.initialize_distributed()
    return ParallelConfig(
        batch_size=args.batch_size,
        parts=args.parts,
        split_size=args.split_size,
        num_spatial_parts=tuple(parse_csv_ints(args.num_spatial_parts) or (4,)),
        spatial_size=args.spatial_size if spatial else 0,
        slice_method=args.slice_method,
        times=args.times,
        image_size=args.image_size,
        sequence_length=getattr(args, "sequence_length", 0),
        num_classes=args.num_classes,
        balance=parse_csv_ints(args.balance),
        halo_d2=args.halo_d2,
        fused_layers=args.fused_layers,
        local_dp=args.local_DP,
        precision=args.precision,
    )


def build_resnet(args, cfg, spatial_cells=0):
    """Returns (cells, plain_twin[, n_spatial_override]).

    --halo-D2 swaps the spatial region for the fused-halo design (one wide
    exchange per ``--fused-layers`` bottleneck cells)."""
    import jax.numpy as jnp

    from mpi4dl_tpu.models.resnet import get_resnet_v2, get_resnet_v2_d2
    from mpi4dl_tpu.utils import get_depth

    dtype = jnp.bfloat16 if args.precision == "bf16" else jnp.float32
    # The reference resnet benchmarks hardcode resnet_n=12 (ResNet-110,
    # e.g. benchmark_resnet_lp.py:92-94); MPI4DL_TPU_RESNET_N overrides the
    # same constant here so smoke tests/CI can drive the full script
    # plumbing without paying a 54-cell compile.
    depth = get_depth(2, int(os.environ.get("MPI4DL_TPU_RESNET_N", "12")))
    kw = dict(
        depth=depth,
        num_classes=args.num_classes,
        # Final feature map is image/4; pool it fully (1x1 output).
        pool_kernel=max(args.image_size // 4, 1),
    )
    if args.halo_d2 and spatial_cells:
        cells, plain, n_sp = get_resnet_v2_d2(
            spatial_cells=spatial_cells,
            fused_layers=args.fused_layers,
            dtype=dtype,
            **kw,
        )
        return cells, plain, n_sp
    return (
        get_resnet_v2(spatial_cells=spatial_cells, dtype=dtype, **kw),
        get_resnet_v2(dtype=jnp.float32, **kw),
    )


def build_amoebanet(args, cfg, spatial_cells=0):
    import jax.numpy as jnp

    from mpi4dl_tpu.models.amoebanet import amoebanetd

    dtype = jnp.bfloat16 if args.precision == "bf16" else jnp.float32
    kw = dict(
        num_classes=args.num_classes,
        num_layers=args.num_layers,
        num_filters=args.num_filters,
    )
    return (
        amoebanetd(
            spatial_cells=spatial_cells,
            halo_d2=args.halo_d2 and spatial_cells > 0,
            dtype=dtype,
            **kw,
        ),
        amoebanetd(dtype=jnp.float32, **kw),
    )


def token_model_args(argv, model: "dict | None" = None):
    """The parsed flags of a token model's run: the shared CLI plus
    ``--model-config`` (a JSON file of the model's ``config.json`` keys, read
    into ``args.model`` unless ``model`` hands them over) and
    ``--sequence-length``. There is no image (``--image-size`` 0) and
    ``--num-classes`` is the vocabulary the model holds."""
    import json

    from mpi4dl_tpu.parser import get_parser

    parser = get_parser()
    parser.add_argument(
        "--model-config",
        help="JSON file of the model's published config.json keys; a chip's "
        "share of a deployment counts what it holds and states the "
        "published values under `cut` (mpi4dl_tpu/models/lfm2.py, qwen3_next.py, "
        "nemotron_h.py, sdar.py)")
    parser.add_argument(
        "--sequence-length", type=int, default=8192,
        help="Tokens in a sequence (one document a sequence)")
    parser.set_defaults(image_size=0, split_size=1, batch_size=1)
    args = parser.parse_args(argv)
    if model is None:
        if args.model_config is None:
            parser.error("--model-config is required")
        with open(args.model_config) as f:
            model = json.load(f)
    args.model = model
    args.num_classes = int(model["vocab_size"])
    return args


def _token_cells(args, model, spatial_cells):
    """(cells, float32 twin) of a token model: ``model(config, dtype)``."""
    import jax.numpy as jnp

    if spatial_cells:
        raise ValueError("a token model has no spatial stages")
    dtype = jnp.bfloat16 if args.precision == "bf16" else jnp.float32
    return model(args.model, dtype), model(args.model, jnp.float32)


def build_lfm2(args, cfg, spatial_cells=0):
    """(cells, float32 twin) of the LFM2 model ``args.model`` describes."""
    from mpi4dl_tpu.models.lfm2 import lfm2

    return _token_cells(args, lfm2, spatial_cells)


def build_qwen3_next(args, cfg, spatial_cells=0):
    """(cells, float32 twin) of the Qwen3-Next model ``args.model`` describes."""
    from mpi4dl_tpu.models.qwen3_next import qwen3_next

    return _token_cells(args, qwen3_next, spatial_cells)


def build_nemotron_h(args, cfg, spatial_cells=0):
    """(cells, float32 twin) of the Nemotron-H model ``args.model`` describes."""
    from mpi4dl_tpu.models.nemotron_h import nemotron_h

    return _token_cells(args, nemotron_h, spatial_cells)


def build_sdar(args, cfg, spatial_cells=0):
    """(cells, float32 twin) of the SDAR model ``args.model`` describes."""
    from mpi4dl_tpu.models.sdar import sdar

    return _token_cells(args, sdar, spatial_cells)


def _token_trainer(config: dict, batch_size: int, build_model, loss=None):
    """``(trainer, cfg)`` of a benchmark configuration of a token model (its
    file as a dict: the model's keys and ``entry_point.argv``), built as its
    entry script builds it; ``loss``: the model's own, where it brings one."""
    argv = list(config["entry_point"]["argv"]) + ["--batch-size", str(batch_size)]
    args = token_model_args(argv, model=config)
    cfg = build_config(args, spatial=False)
    cells, plain = build_model(args, cfg)
    trainer, _ = make_trainer(args, cfg, cells, plain, loss=loss)
    return trainer, cfg


def lfm2_trainer(config: dict, batch_size: int):
    """As ``benchmarks/layer_parallelism/benchmark_lfm2_lp.py`` builds it: the
    builder such a configuration names (``entry_point.build_trainer``)."""
    return _token_trainer(config, batch_size, build_lfm2)


def qwen3_next_trainer(config: dict, batch_size: int):
    """As ``benchmarks/layer_parallelism/benchmark_qwen3_next_lp.py`` builds
    it (``entry_point.build_trainer``)."""
    return _token_trainer(config, batch_size, build_qwen3_next)


def nemotron_h_trainer(config: dict, batch_size: int):
    """As ``benchmarks/layer_parallelism/benchmark_nemotron_h_lp.py`` builds
    it (``entry_point.build_trainer``)."""
    return _token_trainer(config, batch_size, build_nemotron_h)


def sdar_trainer(config: dict, batch_size: int):
    """As ``benchmarks/layer_parallelism/benchmark_sdar_lp.py`` builds it
    (``entry_point.build_trainer``): the model's own loss handed to the
    trainer."""
    from mpi4dl_tpu.models.sdar import block_diffusion_loss

    return _token_trainer(config, batch_size, build_sdar, loss=block_diffusion_loss)


def token_input_stream(cfg, traffic: dict, seed: int):
    """The program's token pipeline under a benchmark's traffic mix, seeded
    by the run (``entry_point.input_stream``)."""
    from mpi4dl_tpu.data import SyntheticTokens

    return SyntheticTokens(
        int(traffic["batch_size"]), int(traffic["sequence_length"]),
        cfg.num_classes, seed=seed, prefetch=bool(traffic["prefetch"]))


lfm2_input_stream = token_input_stream  # the name the LFM2 configuration gives


def block_diffusion_input_stream(cfg, traffic: dict, seed: int):
    """The program's block-diffusion pipeline under a benchmark's traffic
    mix, seeded by the run (``entry_point.input_stream``)."""
    from mpi4dl_tpu.data import BlockDiffusionTokens

    return BlockDiffusionTokens(
        int(traffic["batch_size"]), int(traffic["sequence_length"]),
        cfg.num_classes, t_min=float(traffic["t_min"]),
        seed=seed, prefetch=bool(traffic["prefetch"]))


def block_diffusion_dataset(args, batch_size, num_classes, shard_id=0, num_shards=1):
    """``data.get_dataset``'s place in ``run_training`` for a model trained by
    block diffusion: noisy and clean copies, targets and weights."""
    from mpi4dl_tpu.data import BlockDiffusionTokens

    return BlockDiffusionTokens(
        batch_size, args.sequence_length, num_classes, seed=shard_id)


def make_trainer(args, cfg, cells, plain_cells, gems: bool = False, n_spatial=None,
                 loss=None):
    """Build the trainer the config asks for. The single-program
    ``Trainer`` runs under the fixed remat rule (``train.default_remat``);
    the pipeline trainers checkpoint per stage on their own. ``loss``: the
    model's own (``train.position_cross_entropy``'s signature); the
    single-program trainer alone takes one."""
    import jax

    from mpi4dl_tpu.parallel import multihost
    from mpi4dl_tpu.parallel.pipeline import GemsMasterTrainer, PipelineTrainer
    from mpi4dl_tpu.train import Trainer, default_remat

    n_dev = cfg.num_devices
    if len(jax.devices()) < n_dev:
        sys.exit(
            f"config needs {n_dev} devices (mesh {cfg.mesh_shape}); "
            f"have {len(jax.devices())}. For CPU simulation set "
            f"JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count={n_dev}"
        )
    # DCN-aware placement on multi-slice systems; identical to
    # cfg.make_mesh() on one slice (multihost.make_multihost_mesh docs).
    mesh = multihost.make_multihost_mesh(cfg)
    single_program = not gems and (cfg.split_size == 1 or cfg.spatial_size == cfg.split_size)
    if loss is not None and not single_program:
        raise ValueError(
            "a model that brings its own loss trains under the single-program "
            "Trainer; the pipeline trainers keep the per-position cross-entropy")
    override = n_spatial  # None → trainers derive from config stage bounds
    if n_spatial is None:
        n_spatial = (
            PipelineTrainer.spatial_cell_count(len(cells), cfg)
            if cfg.spatial_size
            else 0
        )
    if gems:
        if getattr(args, "enable_master_comm_opt", False):
            # Accepted for CLI parity (ref --enable-master-comm-opt,
            # train_spatial_master.py:229-455). The optimization it selects
            # there — pairwise flat param/grad P2P instead of ordered
            # allreduces — is the DEFAULT and only path here: the mirror
            # direction's params arrive by one pipe-axis ppermute and its
            # AD transpose is the paired grad reduce. Nothing to switch.
            print(
                "note: --enable-master-comm-opt is implied on TPU "
                "(mirror ppermute == the comm-opt pairwise exchange)"
            )
        return (
            GemsMasterTrainer(
                cells, cfg, plain_cells=plain_cells, num_spatial_cells=override,
                mesh=mesh,
            ),
            n_spatial,
        )
    if single_program:
        remat = default_remat(cfg.image_size)
        size = (f"{cfg.sequence_length} tokens" if cfg.sequence_length
                else f"{cfg.image_size}px")
        print(f"remat policy: {remat} (@{size})")
        return (
            Trainer(
                cells,
                num_spatial_cells=n_spatial,
                config=cfg,
                plain_cells=plain_cells,
                mesh=mesh,
                remat=remat,
                loss=loss,
            ),
            n_spatial,
        )
    return (
        PipelineTrainer(
            cells, cfg, plain_cells=plain_cells, num_spatial_cells=override,
            mesh=mesh,
        ),
        n_spatial,
    )


def run_training(args, trainer, tag: str, dataset=None):
    """Epoch loop with per-step wall-clock timing (ref
    ``benchmark_amoebanet_sp.py:315-367``), optional checkpoint/resume and
    ``jax.profiler`` tracing (TPU-native additions). ``dataset``: what makes
    the stream, called as ``data.get_dataset`` (the default) is."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu import checkpoint as ckpt
    from mpi4dl_tpu.data import get_dataset
    from mpi4dl_tpu.profiling import trace

    cfg = trainer.config
    chunks = getattr(trainer, "chunks", 1)
    global_batch = chunks * cfg.batch_size
    # Multi-process: every host loads ONLY its share of the global batch
    # (the data axis may span hosts; shard_batch assembles the global array
    # via make_array_from_process_local_data — multihost.put_global). The
    # reference instead loads the global batch on every rank and slices
    # (benchmark_amoebanet_sp.py:329-340).
    if jax.process_count() > 1:
        from mpi4dl_tpu.parallel.multihost import data_shard, local_batch_size

        host_batch = local_batch_size(trainer.mesh, global_batch)
        shard_id, num_shards = data_shard(trainer.mesh)
    else:
        host_batch, shard_id, num_shards = global_batch, 0, 1
    ds = (dataset or get_dataset)(
        args, host_batch, cfg.num_classes, shard_id=shard_id, num_shards=num_shards
    )

    if hasattr(trainer, "init_params") or not hasattr(trainer, "n_spatial"):
        state = trainer.init(jax.random.PRNGKey(0))
    else:
        state = trainer.init(jax.random.PRNGKey(0), *cfg.input_spec(global_batch))
    ckpt_dir = getattr(args, "checkpoint_dir", None)
    if ckpt_dir and getattr(args, "resume", False):
        try:
            state = ckpt.restore_checkpoint(ckpt_dir, state)
            print(f"resumed from step {int(state.step)}")
        except FileNotFoundError:
            pass

    from mpi4dl_tpu import elastic

    hb = elastic.heartbeat_path_from_env()  # supervised run (--max-restarts)
    # Test-only chaos knob: crash/hang the process once it reaches step N
    # on a fresh (non-resumed) run — exercises the supervisor's two failure
    # detectors end-to-end (tests/test_elastic.py).
    crash_at = int(os.environ.get("MPI4DL_TPU_CRASH_AT_STEP", "-1"))
    hang_at = int(os.environ.get("MPI4DL_TPU_HANG_AT_STEP", "-1"))

    # Resume honors the restored state.step as work ALREADY DONE: earlier
    # (epoch, step) slots are skipped — consuming their batches, so the
    # resumed run replays the identical data order — instead of re-running
    # the full step budget on top of the checkpointed weights (which would
    # train up to (max_restarts+1)x the requested duration under repeated
    # crashes).
    unit = "seq/s" if cfg.sequence_length else "img/s"
    done = int(state.step)
    seen = 0  # global (epoch, step) slots consumed, trained or skipped
    trained = 0
    perf = []
    with trace(getattr(args, "trace_dir", None)):
        for epoch in range(args.num_epochs):
            for step, (x, y) in enumerate(ds):
                max_steps = getattr(args, "max_steps", None)
                if max_steps is not None and step >= max_steps:
                    break
                seen += 1
                if seen <= done:
                    # The fast-forward replay is progress too: with a slow
                    # data loader a long skip phase must not read as a
                    # wedge to the supervisor.
                    if hb:
                        elastic.touch(hb)
                    continue
                if not getattr(args, "resume", False):
                    if int(state.step) == crash_at:
                        os._exit(3)
                    if int(state.step) == hang_at:
                        time.sleep(3600)
                xs, ys = trainer.shard_batch(jnp.asarray(x), jnp.asarray(y))
                t0 = time.perf_counter()
                state, metrics = trainer.train_step(state, xs, ys)
                loss = float(metrics["loss"])  # blocks
                dt = time.perf_counter() - t0
                if hb:
                    elastic.touch(hb)
                trained += 1
                if trained > 1:  # skip compile step, like the ref's warmup
                    perf.append(global_batch / dt)
                if args.verbose:
                    print(
                        f"epoch {epoch} step {step}: loss {loss:.4f} "
                        f"acc {float(metrics['accuracy']):.4f} "
                        f"({global_batch / dt:.3f} {unit})"
                    )
                if ckpt_dir and int(state.step) % args.checkpoint_every == 0:
                    ckpt.save_checkpoint(ckpt_dir, state)
    if hb:
        elastic.touch(hb)  # post-loop phases below must not read as a wedge
    if ckpt_dir:
        ckpt.save_checkpoint(ckpt_dir, state)
    if perf:
        mean_ips = statistics.mean(perf)
        line = (
            f"{tag}: Mean {mean_ips:.3f} {unit} "
            f"Median {statistics.median(perf):.3f} {unit}"
        )
        # MFU against the model's analytic FLOPs (BASELINE.json north star
        # is stated in MFU; the reference never reports it). Counted on the
        # plain twin — same math, no spatial collectives to trace.
        from mpi4dl_tpu.flops import mfu, train_flops_per_image

        if cfg.image_size:  # flops.py counts convs and dots off an image's jaxpr
            fpi = train_flops_per_image(trainer.plain_cells, cfg.image_size)
            util = mfu(mean_ips, fpi, n_devices=jax.device_count())
            if util is not None:  # None on CPU only
                line += f" MFU {100 * util:.1f}%"
        print(line)
    if getattr(args, "eval_batches", 0):
        # skip: the (epoch, step) slots training consumed, reduced modulo
        # the dataset's per-epoch length — `seen` accumulates across epochs
        # and resume fast-forwards, and skipping whole dataset revolutions
        # would just wrap the stream back to the same position after
        # pointless "dataset exhausted" warnings (ADVICE r3). The eval
        # stream starts past the trained prefix instead of presenting
        # train-set batches as "evaluation".
        try:
            per_epoch = len(ds)
        except TypeError:
            per_epoch = 0
        run_eval(
            args, trainer, state, ds, args.eval_batches,
            skip=seen % per_epoch if per_epoch else seen,
        )
    return state


def run_eval(args, trainer, state, ds, n: int, skip: int = 0):
    """BN-calibrate on ``n`` batches, evaluate on ``n`` more
    (mpi4dl_tpu/evaluate.py; the reference never evaluates).

    Spatial ``Trainer`` configs evaluate through the trainer's own sharded
    forward (``spatial_collect_batch_stats``/``spatial_evaluate``) — at the
    resolutions this framework targets the full-image plain twin cannot run
    on one device. Pipeline/GEMS configs evaluate on the plain twin with
    the trained params unstacked to the flat cell list (their stage-sharded
    forward exists for training; eval at their scale re-hosts the params).

    The first ``skip`` batches of the stream (the ones training consumed)
    are passed over so calibration/test data is fresh; if the dataset is
    too short the stream wraps with a warning (eval then overlaps train
    data — small datasets have nothing else to offer)."""
    import jax.numpy as jnp

    from mpi4dl_tpu import elastic
    from mpi4dl_tpu.evaluate import collect_batch_stats, evaluate

    hb = elastic.heartbeat_path_from_env()
    cells = trainer.plain_cells
    params = state.params
    if hasattr(trainer, "unstack_params"):
        params = trainer.unstack_params(params)
    spatial = (
        not hasattr(trainer, "unstack_params")
        and getattr(trainer, "n_spatial", 0) > 0
    )

    it = iter(ds)

    def take():
        nonlocal it
        try:
            b = next(it)
        except StopIteration:
            print(
                "eval: dataset exhausted — wrapping (eval batches overlap "
                "training data)",
                flush=True,
            )
            it = iter(ds)
            try:
                b = next(it)
            except StopIteration:
                raise ValueError("eval: dataset is empty") from None
        if hb:
            elastic.touch(hb)
        return b

    for _ in range(skip):
        take()
    cal = [jnp.asarray(take()[0]) for _ in range(n)]
    test = [
        (jnp.asarray(x), jnp.asarray(y)) for x, y in (take() for _ in range(n))
    ]
    if spatial:
        from mpi4dl_tpu.evaluate import (
            spatial_collect_batch_stats,
            spatial_evaluate,
        )

        stats = spatial_collect_batch_stats(trainer, params, cal)
        if hb:
            elastic.touch(hb)
        res = spatial_evaluate(trainer, params, stats, test)
    else:
        stats = collect_batch_stats(cells, params, cal)
        if hb:
            elastic.touch(hb)
        res = evaluate(cells, params, stats, test)
    print(
        f"eval ({n} cal / {n} test batches, {res['count']} images): "
        f"loss {res['loss']:.4f} acc {res['accuracy']:.4f}"
    )
    return res
