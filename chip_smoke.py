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
import contextlib
import gc
import importlib.metadata
import itertools
import json
import math
import os
import sys
import time

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
# First-step loss, SP 2x2 against one chip, relative. Same weights, same
# batch, same math: in f32 the two agree to 1.7e-5 on the CPU mesh (3e-6
# with the packed conv forced), and tests/test_train.py holds that. What
# differs on the chips is bf16 rounding (8 mantissa bits) under another
# summation order — each conv sums over a 512x512 tile plus halo with pack
# factors chosen from the tile's shape, every BatchNorm's statistics are
# reduced per tile and then across the four chips — and at random init
# with batch 2 the loss is that sensitive. Measured on v5e (PR 24): the
# one-chip model's first loss is 2.3456 in f32 and 2.3236 / 2.2935 /
# 2.2750 in bf16 (forward only with the packed conv, forward only with
# XLA's conv, inside the train step): 3.0% spread from arithmetic alone.
# SP 2x2 read 2.1576, 5.2% from the one-chip train step. So this is a
# check against garbage (a lost tile, a NaN), not against a few percent.
SP_LOSS_RTOL = 1e-1


def emit(**fields):
    print(json.dumps(fields), flush=True)


def kernel_table():
    """kernel name in the compiled text -> (module, the entry its dispatch
    site calls once the gate has admitted a shape)."""
    from mpi4dl_tpu.ops import (
        dot1x1_pallas, halo_pallas, pool_pallas, wgrad_pallas,
    )

    return {
        pool_pallas.KERNEL_NAME: (pool_pallas, "max_pool"),
        wgrad_pallas.KERNEL_NAME: (wgrad_pallas, "wgrad"),
        dot1x1_pallas.KERNEL_NAME: (dot1x1_pallas, "bwd_1x1"),
        halo_pallas.KERNEL_NAME: (halo_pallas, "halo_exchange_pallas"),
    }


@contextlib.contextmanager
def count_dispatches(table):
    """Each kernel entry wrapped with a call counter while a step is traced;
    yields the counts."""
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


def run_phase(tag, argv, spatial, cache_events):
    """Build through the entry points' own functions, compile, take STEPS
    steps; returns the losses, the compiled step's collective-permute count
    and what was left on the devices."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import build_amoebanet, build_config, make_trainer
    from mpi4dl_tpu import native
    from mpi4dl_tpu.data import get_dataset
    from mpi4dl_tpu.flops import mfu, train_flops_per_image
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
    state = trainer.init(
        jax.random.PRNGKey(0),
        (cfg.batch_size, cfg.image_size, cfg.image_size, 3),
    )

    table = kernel_table()
    before = dict(cache_events)
    t0 = time.perf_counter()
    # At 1024 px Trainer.train_step arms no trace-time context, so this is
    # the program the steps below run.
    with count_dispatches(table) as admitted:
        compiled = trainer._jit_step.lower(state, *batches[0]).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    found = kernels_in(text, table)
    permutes = text.count(" collective-permute")
    for name in table:
        if bool(admitted[name]) != bool(found[name]):
            raise SystemExit(
                f"{tag}: kernel {name}: gate admitted {admitted[name]} "
                f"shapes while tracing, compiled step holds {found[name]} "
                "tpu_custom_calls"
            )

    losses, step_s = [], []
    for xs, ys in batches:
        t0 = time.perf_counter()
        state, metrics = trainer.train_step(state, xs, ys)
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
        compile_seconds=compile_s,
        step_seconds=step_s,
        images_per_second=ips,
        mfu=util,
        losses=losses,
        kernels_admitted=admitted,
        kernels_found=found,
        collective_permutes=permutes,
        compile_cache={
            k: cache_events[k] - before[k] for k in cache_events
        },
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        peak_bytes_in_use=[peak_bytes(d) for d in devices],
        native_loader_used=native.available(),
    )
    return losses, permutes, state, xs


def smoke(chips, base_argv, cache_events):
    """The default phase, or with ``chips == 4`` the SP 2x2 phase and its
    one-chip comparison and nothing else."""
    import jax

    if chips == 1:
        run_phase("one_chip", base_argv, False, cache_events)
        return
    sp_losses, permutes, state, xs = run_phase(
        "sp_2x2", base_argv + SP_ARGV, True, cache_events
    )
    if not permutes:
        raise SystemExit("sp_2x2: no collective-permute in the compiled step")
    four = set(jax.devices()[:4])
    leaves = jax.tree.leaves(state.params) + [xs]
    if not all({s.device for s in l.addressable_shards} == four for l in leaves):
        raise SystemExit("sp_2x2: a parameter or the batch is not on all four chips")
    b, h, w, c = xs.shape
    if tuple(xs.addressable_shards[0].data.shape) != (b, h // 2, w // 2, c):
        raise SystemExit("sp_2x2: the batch is not split into 2x2 tiles")
    if not all(peak_bytes(d) > 0 for d in four):
        raise SystemExit("sp_2x2: a chip reports no memory in use")
    # The comparison needs chip 0's memory back: drop the SP state, batch
    # and executable before the one-chip program is built.
    del state, xs, leaves
    gc.collect()
    jax.clear_caches()
    ref_losses, _, _, _ = run_phase(
        "one_chip_reference", base_argv, False, cache_events
    )
    rel = abs(sp_losses[0] - ref_losses[0]) / abs(ref_losses[0])
    emit(
        phase="compare", sp_first_loss=sp_losses[0],
        one_chip_first_loss=ref_losses[0], rel_diff=rel, rtol=SP_LOSS_RTOL,
    )
    if rel > SP_LOSS_RTOL:
        raise SystemExit(
            f"first-step losses disagree: SP {sp_losses[0]} vs one chip "
            f"{ref_losses[0]} (rel {rel} > {SP_LOSS_RTOL})"
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
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def on_event(event, **_):
        key = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and key in cache_events:
            cache_events[key] += 1

    jax.monitoring.register_event_listener(on_event)

    smoke(opts.chips, BASE_ARGV, cache_events)

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
