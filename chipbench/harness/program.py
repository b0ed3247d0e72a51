"""The system under test, built the way its entry points build it.

``benchmarks/common.py``'s ``build_config`` / the configuration's model
builder / ``make_trainer`` with the reference's command-line flags, so that
``train.default_remat`` picks the policy as it does for a user. The
benchmark takes from the program the trainer, its input pipeline (the
stream ``run_training`` feeds its loop from) and nothing else: the weights
are made by the benchmark from the seed (``reference/plain.make_params``)
and handed over as the initial state. A configuration of another family
names its own builder and stream under ``entry_point``.
"""

from __future__ import annotations

import importlib

from . import defaults


def resolve(dotted: str):
    """The object a configuration or a tiny file names as
    ``"<module>:<attribute>"``."""
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(module), attr)


def build_trainer(config: dict, batch_size: int):
    """``(trainer, cfg)`` for the configuration at this batch size, as the
    entry-point script named in the configuration file builds it:
    ``entry_point.build_trainer`` (``"<module>:<callable>"``, called with
    the configuration and the batch size) where the file names one, else
    the walk of ``benchmarks/common.py`` below."""
    entry = config["entry_point"]
    if "build_trainer" in entry:
        return resolve(entry["build_trainer"])(config, batch_size)

    from benchmarks.common import build_config, make_trainer
    from mpi4dl_tpu.parallel.pipeline import PipelineTrainer
    from mpi4dl_tpu.parser import get_parser

    argv = list(entry["argv"]) + ["--batch-size", str(batch_size)]
    args = get_parser().parse_args(argv)
    cfg = build_config(args, spatial=bool(entry["spatial"]))  # compile cache on
    build_model = resolve(entry["build_model"])
    n_cells = len(build_model(args, cfg)[1])
    n_spatial = (
        PipelineTrainer.spatial_cell_count(n_cells, cfg) if cfg.spatial_size else 0
    )
    built = build_model(args, cfg, spatial_cells=n_spatial)
    trainer, _ = make_trainer(
        args, cfg, built[0], built[1],
        n_spatial=built[2] if len(built) == 3 else None,
    )
    return trainer, cfg


def initial_state(trainer, params):
    """The trainer's state around the benchmark's weights, placed as
    ``Trainer.init`` places it (replicated on the mesh)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from mpi4dl_tpu.train import TrainState

    state = TrainState(
        params=params,
        opt_state=trainer.tx.init(params),
        step=jnp.zeros((), jnp.int32),
    )
    return jax.device_put(state, NamedSharding(trainer.mesh, PartitionSpec()))


def input_stream(config: dict, cfg, traffic: dict, seed: int):
    """The program's input pipeline under ``traffic``, seeded by the run: an
    iterable of host ``(x, y)`` batches. ``entry_point.input_stream``
    (``"<module>:<callable>"``, called with ``cfg``, the traffic mix's
    parameters and the seed) where the configuration names one."""
    named = config["entry_point"].get("input_stream")
    make = resolve(named) if named else defaults.input_stream
    return make(cfg, traffic, seed)
