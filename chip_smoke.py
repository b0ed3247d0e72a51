#!/usr/bin/env python3
"""The quickest proof that the training main path still starts on the chip.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the SP 2x2 path and its comparison only

One process, no JAX platform set in code: it fails at once unless
``jax.devices()[0].platform == "tpu"``. The model is the reference's
headline one at full width — AmoebaNet-D, 18 layers, 416 filters (the
parser defaults), 1024x1024, batch 2, bf16, ``split_size=1`` — built by
``benchmarks/common.py``'s ``build_config`` / ``build_amoebanet`` /
``make_trainer`` and stepped by ``Trainer.train_step``: what
``benchmarks/layer_parallelism/benchmark_amoebanet_lp.py`` (default) and
``benchmarks/spatial_parallelism/benchmark_amoebanet_sp.py`` (``--chips 4``)
do. Weights come from ``PRNGKey(0)``, data from the seeded synthetic set.

Nothing is caught: any phase that raises ends the run non-zero with no
result line. Earlier lines are one JSON object each (plus the builders' own
prints); the last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import gc
import importlib.metadata
import itertools
import json
import math
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

STEPS = 3
BASE_ARGV = [
    "--batch-size", "2", "--image-size", "1024", "--split-size", "1",
    "--precision", "bf16", "--app", "3",
]
SP_ARGV = [
    "--num-spatial-parts", "4", "--slice-method", "square",
    "--spatial-size", "1",
]
# --chips 4 compares the first-step loss of SP 2x2 with the one-chip
# program's, relative, for the weights of PRNGKey(0) (the three steps) and
# of each seed in EXTRA_SEEDS. Same weights, same batch, same math: in f32
# the two agree to 6e-5 on the CPU mesh (16 seeds) and tests/test_train.py
# holds them to 2e-4 — THAT is the check which catches a wrong SP program
# (the head classified per tile, repaired in PR 24, is 3.6-6.2% off there).
# In bf16 no limit can, because the freshly initialised net amplifies what
# it is given: on the v5e chip a 1e-3 relative nudge of the input image
# (an eighth of a bf16 rounding step) moves this model's first-batch loss
# by up to 5.1% (mean 1.6%, 72 readings), and bf16 rounds by 2^-8 after
# every op, at places that differ between two programs of the same math.
# Readings of two sound bf16 programs against each other (PR 24, PERF.md
# section 6): CPU mesh, a 6-layer model, 16 seeds, SP against one device
# 0.3-4.8%, either sign (the planted head fault: 2.0-9.6%, overlapping);
# v5e, this model, one chip, packed against XLA conv 0.2-1.9% (4 seeds)
# and 2.1% across three programs of seed 0; SP 2x2 against the one-chip
# step 5.16%. The limit is twice the largest reading on the chip, rounded
# up: a check against a lost tile, a NaN or a wrong batch, nothing finer.
SP_LOSS_RTOL = 0.11
EXTRA_SEEDS = (1, 2)


def emit(**fields):
    print(json.dumps(fields), flush=True)


def kernel_table():
    """kernel name in the compiled text -> (module, the function that holds
    its one ``pallas_call``): a call of it while the step is traced is one
    ``tpu_custom_call`` the compiled step must hold."""
    from mpi4dl_tpu.ops import pool_pallas

    return {pool_pallas.KERNEL_NAME: (pool_pallas, "_bwd_padded")}


@contextlib.contextmanager
def count_calls(table):
    """Each kernel's ``pallas_call`` site wrapped with a call counter while
    a step is traced; yields the counts."""
    calls = dict.fromkeys(table, 0)
    entries = {n: getattr(m, a) for n, (m, a) in table.items()}
    for name, (module, attr) in table.items():

        def counted(*a, _name=name, **kw):
            calls[_name] += 1
            return entries[_name](*a, **kw)

        setattr(module, attr, counted)
    try:
        yield calls
    finally:
        for name, (module, attr) in table.items():
            setattr(module, attr, entries[name])


def kernels_in(text, table):
    """How many ``tpu_custom_call``s of each named kernel the text holds."""
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    return {name: sum(f"/{name}/" in l for l in calls) for name in table}


def peak_bytes(device):
    return device.memory_stats()["peak_bytes_in_use"]


def build(tag, argv, spatial):
    """The program as the benchmark entry points build it, its first STEPS
    batches on the mesh, and the step traced and lowered (the kernels'
    ``pallas_call``s counted meanwhile)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import build_amoebanet, build_config, make_trainer
    from mpi4dl_tpu.data import get_dataset
    from mpi4dl_tpu.parallel.pipeline import PipelineTrainer
    from mpi4dl_tpu.parser import get_parser

    args = get_parser().parse_args(argv)
    cfg = build_config(args, spatial=spatial)  # turns the compile cache on
    n_cells = len(build_amoebanet(args, cfg)[1])
    n_spatial = (
        PipelineTrainer.spatial_cell_count(n_cells, cfg)
        if cfg.spatial_size else 0
    )
    cells, plain = build_amoebanet(args, cfg, spatial_cells=n_spatial)
    trainer, _ = make_trainer(args, cfg, cells, plain)
    batches = [
        trainer.shard_batch(jnp.asarray(x), jnp.asarray(y))
        for x, y in itertools.islice(
            iter(get_dataset(args, cfg.batch_size, cfg.num_classes)), STEPS
        )
    ]
    shape = (cfg.batch_size, cfg.image_size, cfg.image_size, 3)
    state = trainer.init(jax.random.PRNGKey(0), shape)
    # At 1024 px Trainer.train_step arms no trace-time context, so this is
    # the program the steps below run.
    with count_calls(kernel_table()) as traced:
        lowered = trainer._jit_step.lower(state, *batches[0])
    return types.SimpleNamespace(
        tag=tag, cfg=cfg, trainer=trainer, batches=batches, shape=shape,
        state=state, lowered=lowered, kernels_traced=traced,
    )


CACHE_EVENTS = {"cache_hits": 0, "cache_misses": 0}


def count_cache_event(event, **_):
    key = event.rsplit("/", 1)[-1]
    if event.startswith("/jax/compilation_cache/") and key in CACHE_EVENTS:
        CACHE_EVENTS[key] += 1


def compile_steps(*phases):
    """Compile the lowered steps, side by side where there are two: the
    chip's compiler works one program on one core for minutes, and under
    --chips 4 four chips are held meanwhile."""
    import jax

    def compile_one(phase):
        t0 = time.perf_counter()
        phase.compiled = phase.lowered.compile()
        phase.compile_seconds = time.perf_counter() - t0

    before = dict(CACHE_EVENTS)
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(phases)) as pool:
        for done in [pool.submit(compile_one, p) for p in phases]:
            done.result()
    emit(
        phase="compile",
        seconds={p.tag: p.compile_seconds for p in phases},
        wall_seconds=time.perf_counter() - t0,
        compile_cache={k: CACHE_EVENTS[k] - before[k] for k in CACHE_EVENTS},
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
    )


def run_steps(phase):
    """Check the compiled step's kernels and take STEPS steps from the
    weights of PRNGKey(0); returns the losses."""
    import jax

    from mpi4dl_tpu import native
    from mpi4dl_tpu.flops import mfu, train_flops_per_image

    tag, trainer, cfg = phase.tag, phase.trainer, phase.cfg
    text = phase.compiled.as_text()
    found = kernels_in(text, kernel_table())
    if found != phase.kernels_traced:
        raise SystemExit(
            f"{tag}: pallas_calls traced {phase.kernels_traced}, "
            f"tpu_custom_calls in the compiled step {found}"
        )
    phase.collective_permutes = text.count(" collective-permute")

    if phase.state is None:
        phase.state = trainer.init(jax.random.PRNGKey(0), phase.shape)
    losses, step_s = [], []
    for xs, ys in phase.batches:
        t0 = time.perf_counter()
        phase.state, metrics = trainer.train_step(phase.state, xs, ys)
        losses.append(float(metrics["loss"]))  # host read ends the step
        step_s.append(time.perf_counter() - t0)
    if not all(math.isfinite(l) for l in losses):
        raise SystemExit(f"{tag}: non-finite loss in {losses}")
    if losses[2] == losses[0]:
        raise SystemExit(f"{tag}: loss did not move over {STEPS} steps: {losses}")

    devices = list(trainer.mesh.devices.flat)
    steady = sum(step_s[1:]) / (STEPS - 1)
    ips = cfg.batch_size / steady
    util = mfu(
        ips, train_flops_per_image(trainer.plain_cells, cfg.image_size),
        n_devices=len(devices),
    )  # raises for a TPU flops.peak_flops does not know
    emit(
        phase=tag,
        device_kind=devices[0].device_kind,
        n_devices=len(devices),
        mesh=dict(trainer.mesh.shape),
        remat=trainer.remat,
        compile_seconds=phase.compile_seconds,
        step_seconds=step_s,
        images_per_second=ips,
        mfu=util,
        losses=losses,
        kernels_traced=phase.kernels_traced,
        kernels_found=found,
        collective_permutes=phase.collective_permutes,
        peak_bytes_in_use=[peak_bytes(d) for d in devices],
        native_loader_used=native.available(),
    )
    return losses


def first_losses(phase, seeds):
    """The first-step loss from the weights of each seed. The trained state
    is dropped first: the one-chip step has no room for a second one."""
    import jax

    phase.state = None
    first = {}
    for seed in seeds:
        state = phase.trainer.init(jax.random.PRNGKey(seed), phase.shape)
        state, metrics = phase.trainer.train_step(state, *phase.batches[0])
        first[seed] = float(metrics["loss"])
        del state, metrics
    return first


def smoke(chips):
    """The default phase, or with ``chips == 4`` the SP 2x2 phase and its
    one-chip comparison and nothing else."""
    import jax

    if chips == 1:
        one = build("one_chip", BASE_ARGV, False)
        compile_steps(one)
        run_steps(one)
        return
    sp = build("sp_2x2", BASE_ARGV + SP_ARGV, True)
    sp.state = None  # chip 0 cannot hold it beside the one-chip step
    one = build("one_chip_reference", BASE_ARGV, False)
    compile_steps(sp, one)

    # The one-chip step first: it needs all but 1.5 GiB of chip 0, which
    # the SP state and a loaded SP executable would take from it.
    one_first = {0: run_steps(one)[0]} | first_losses(one, EXTRA_SEEDS)
    del one
    gc.collect()

    sp_first = {0: run_steps(sp)[0]}
    if not sp.collective_permutes:
        raise SystemExit("sp_2x2: no collective-permute in the compiled step")
    four = set(jax.devices()[:4])
    xs = sp.batches[-1][0]
    leaves = jax.tree.leaves(sp.state.params) + [xs]
    if not all({s.device for s in l.addressable_shards} == four for l in leaves):
        raise SystemExit("sp_2x2: a parameter or the batch is not on all four chips")
    b, h, w, c = xs.shape
    if tuple(xs.addressable_shards[0].data.shape) != (b, h // 2, w // 2, c):
        raise SystemExit("sp_2x2: the batch is not split into 2x2 tiles")
    if not all(peak_bytes(d) > 0 for d in four):
        raise SystemExit("sp_2x2: a chip reports no memory in use")
    del leaves, xs
    sp_first |= first_losses(sp, EXTRA_SEEDS)
    rel = {
        seed: abs(sp_first[seed] - one_first[seed]) / abs(one_first[seed])
        for seed in one_first
    }
    emit(
        phase="compare", sp_first_loss=sp_first, one_chip_first_loss=one_first,
        rel_diff=rel, rtol=SP_LOSS_RTOL,
    )
    if not all(math.isfinite(r) and r <= SP_LOSS_RTOL for r in rel.values()):
        raise SystemExit(
            f"first-step losses disagree: SP {sp_first} vs one chip "
            f"{one_first} (rel {rel}, limit {SP_LOSS_RTOL})"
        )


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run the SP 2x2 step and its one-chip comparison only",
    )
    opts = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; JAX found {devices[0].platform!r}"
        )
    if len(devices) < opts.chips:
        raise SystemExit(f"--chips {opts.chips}: JAX found {len(devices)}")

    import jaxlib

    from mpi4dl_tpu.flops import peak_flops

    emit(
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=importlib.metadata.version("libtpu"),
        device_kind=devices[0].device_kind,
        device_count=len(devices),
        peak_bf16_flops=peak_flops(devices[0]),
    )
    jax.monitoring.register_event_listener(count_cache_event)
    smoke(opts.chips)

    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
