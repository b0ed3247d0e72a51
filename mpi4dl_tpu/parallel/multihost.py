"""Multi-host / multi-slice execution: process init, DCN-aware meshes,
per-host data feeding.

The reference scales across nodes by launching one MPI process per GPU under
``mpirun_rsh`` and calling ``dist.init_process_group(backend="mpi")``
(``src/torchgems/comm.py:154-159``) over CUDA-aware MVAPICH2-GDR; every
cross-node pattern (halo P2P, pipeline send/recv, flat-grad allreduce) then
rides InfiniBand through MPI. The TPU-native equivalents here:

- :func:`initialize_distributed` — ``jax.distributed.initialize``: one
  process per host, after which ``jax.devices()`` is the *global* device
  list and every jitted collective spans hosts transparently;
- :func:`make_multihost_mesh` — a hybrid ICI/DCN mesh: the ``data`` axis
  spans slices over DCN while ``pipe``/``tile_h``/``tile_w`` stay inside a
  slice on ICI. That placement is the whole performance story: halo
  exchanges (per conv, per micro-batch — the innermost hot loop,
  SURVEY.md §3) and pipeline wire hops ride ICI; the only DCN traffic is
  the once-per-step DP gradient ``psum``, which is exactly the collective
  DCN bandwidth is provisioned for;
- :func:`host_local_batch` — builds the global sharded batch from each
  host's local shard (``jax.make_array_from_process_local_data``), the
  multi-host form of the reference's per-rank ``split_input``
  (``train_spatial.py:241-290``): each host loads only the examples its
  devices consume instead of materializing the global batch everywhere.

Single-process (one host, or CPU simulation) everything degrades to the
plain ``config.make_mesh()`` path, so the same training script runs
unchanged from a laptop CPU mesh to a multi-slice pod — the property the
reference approximates with its SPMD rank-branching scripts.
"""

from __future__ import annotations

import os
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from mpi4dl_tpu.config import (
    AXIS_DATA,
    AXIS_PIPE,
    AXIS_TILE_H,
    AXIS_TILE_W,
    ParallelConfig,
)

MESH_AXES = (AXIS_DATA, AXIS_PIPE, AXIS_TILE_H, AXIS_TILE_W)


# Env vars that mean "a multi-host world is configured" — if any is set and
# init still fails, that's an operator error we must surface, not swallow.
_COORDINATOR_ENV_VARS = (
    "JAX_COORDINATOR_ADDRESS",
    "COORDINATOR_ADDRESS",
    "MEGASCALE_COORDINATOR_ADDRESS",
)


# Environment markers that unambiguously mean "more than one process was
# launched" even when no coordinator address is spelled out (the launcher
# provides it, and jax's own cluster detection reads the rest). Checked
# besides that detection (:func:`_detected_process_count`) so a jax-internal
# move cannot silently turn these launches into single-host jobs.
_MULTIPROC_ENV_MARKERS = (
    "OMPI_COMM_WORLD_SIZE",
    "SLURM_NTASKS",
    "MEGASCALE_NUM_SLICES",
)


def _launcher_present() -> bool:
    """True when a multi-process launcher (Slurm, OpenMPI, a multi-slice
    runtime) started this process: no coordinator env var is set by the
    operator there, yet a multi-host world IS configured."""
    for k in _MULTIPROC_ENV_MARKERS:
        v = os.environ.get(k)
        try:
            if v is not None and int(v) > 1:
                return True
        except ValueError:
            pass
    return False


def _detected_process_count() -> int:
    """How many processes jax's own cluster detection finds for this launch
    (a GCE or GKE TPU pod, Slurm, OpenMPI, Kubernetes); 1 where it finds no
    cluster. On such a pod the operator sets nothing: the hosts are found
    from the metadata server or ``TPU_WORKER_HOSTNAMES``.

    The TPU probes ask the metadata server (GCE for the worker list, GKE
    for the slice count). Every host of a pod can reach one, so a probe
    that cannot connect means there is no more to this launch than the
    environment itself lists (a single TPU VM sealed off from the network
    lists itself in ``TPU_WORKER_HOSTNAMES``): that one error is passed
    over and the listed hosts are counted. Anything else a probe raises
    propagates."""
    import requests
    from jax._src.clusters import ClusterEnv

    for env in ClusterEnv._cluster_types:
        if env.opt_in_only_method:
            continue
        try:
            if env.is_env_present():
                return env.get_process_count()
        except requests.exceptions.ConnectionError:
            continue
    hosts = (
        os.environ.get("TPU_PROCESS_ADDRESSES")
        or os.environ.get("TPU_WORKER_HOSTNAMES")
        or ""
    )
    return max(1, len([h for h in hosts.split(",") if h]))


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Join the multi-host world (ref ``dist.init_process_group``,
    ``comm.py:154-159``; launcher contract ``README.md:121-125``).

    A world is there to join when one is configured — by argument, by a
    coordinator variable (``JAX_COORDINATOR_ADDRESS`` …) or by a launcher's
    marker (Slurm, OpenMPI, multi-slice) — or when jax's cluster detection
    finds more than one process (a multi-host TPU pod, where the operator
    sets none of these). Then this must run before anything that
    initializes the XLA backend (``jax.devices()``, array creation, …),
    what the arguments leave open jax fills in, and failures propagate —
    silently degrading a pod launch into N independent single-host jobs is
    the one outcome this wrapper must never produce. Otherwise it is a
    single-process run and nothing is joined. (Until PR 24 a bare
    ``jax.distributed.initialize()`` was tried regardless: on a one-chip
    TPU VM with no metadata server every benchmark entry point died
    here.) Calling it again once initialized is a no-op.
    """
    if jax.distributed.is_initialized():
        return
    configured = (
        coordinator_address is not None
        or num_processes is not None
        or process_id is not None
        or any(os.environ.get(k) for k in _COORDINATOR_ENV_VARS)
        or _launcher_present()
        or _detected_process_count() > 1
    )
    if not configured:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def num_slices(devices: Sequence[jax.Device] | None = None) -> int:
    """Count DCN-connected slices (granules). 1 on a single slice / CPU."""
    devices = jax.devices() if devices is None else list(devices)
    ids = {getattr(d, "slice_index", 0) for d in devices}
    return max(len(ids), 1)


def make_multihost_mesh(
    config: ParallelConfig, devices: Sequence[jax.Device] | None = None
) -> Mesh:
    """Hybrid ICI/DCN mesh for ``config`` over all (global) devices.

    The ``data`` axis factors as ``slices × per-slice replicas``: DP spans
    DCN first, and any remaining DP extent stays on ICI inside a slice.
    ``pipe``/``tile_h``/``tile_w`` never cross a slice boundary — pipeline
    wires and halo rings are latency-sensitive per-micro-batch traffic and
    must ride ICI. Falls back to ``config.make_mesh()`` when there is a
    single slice (including CPU simulation).
    """
    devices = jax.devices() if devices is None else list(devices)
    slices = num_slices(devices)
    if slices == 1:
        return config.make_mesh(devices)

    groups: dict[int, list] = {}
    for d in devices:
        groups.setdefault(getattr(d, "slice_index", 0), []).append(d)
    first_slice = groups[sorted(groups)[0]]

    dp, pipe, th, tw = config.mesh_shape
    if dp % slices:
        # DP doesn't factor over the slices. If the whole mesh fits inside
        # one slice, run it there (pure SP/LP configs on multi-slice
        # systems) — but only single-process: in a multi-process world the
        # processes on the other slices would own no devices of that mesh,
        # which JAX cannot execute; reject with a clear error instead.
        # Otherwise the config is genuinely unplaceable without non-data
        # axes crossing DCN, which we refuse.
        if config.num_devices <= len(first_slice) and jax.process_count() == 1:
            return config.make_mesh(first_slice)
        raise ValueError(
            f"data_parallel={dp} must divide by the {slices} DCN slices "
            "(the data axis is the only axis allowed to cross DCN) and "
            f"mesh {config.mesh_shape} does not fit inside one slice "
            f"({len(first_slice)} devices)"
        )
    from jax.experimental import mesh_utils

    per_slice = (dp // slices, pipe, th, tw)
    need = int(np.prod(per_slice))
    # Tolerate surplus devices (parity with config.make_mesh's prefix-take):
    # use the first `need` devices of every slice.
    chosen = []
    for idx in sorted(groups):
        g = groups[idx]
        if len(g) < need:
            raise ValueError(
                f"slice {idx} has {len(g)} devices but the config needs "
                f"{need} per slice (mesh {config.mesh_shape} spread over "
                f"{slices} slices)"
            )
        chosen.extend(g[:need])
    dev = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=per_slice,
        dcn_mesh_shape=(slices, 1, 1, 1),
        devices=chosen,
    )
    return Mesh(dev, MESH_AXES)


def host_local_batch(mesh: Mesh, spec, *arrays) -> tuple:
    """Assemble global arrays from per-host local data.

    Each host passes ONLY its local shard (its devices' slice of the global
    batch, in the global order implied by ``spec``); the returned
    ``jax.Array``s are global and feed ``train_step`` directly. This is the
    multi-host ``split_input`` / DataLoader contract: no host ever holds the
    global batch (the reference loads the full batch on every rank and
    slices, ``benchmark_amoebanet_sp.py:329-340``).

    Single-process, local == global and this is equivalent to
    ``jax.device_put`` with the same sharding. Always returns a tuple with
    one entry per input array.
    """
    spec = tuple(spec)
    if len(spec) != len(arrays):
        raise ValueError(
            f"host_local_batch got {len(arrays)} arrays but {len(spec)} specs"
        )
    return tuple(
        jax.make_array_from_process_local_data(NamedSharding(mesh, s), np.asarray(a))
        for s, a in zip(spec, arrays)
    )


def put_global(mesh: Mesh, spec, *arrays) -> tuple:
    """Place batches on the mesh, single- or multi-process.

    Single-process: plain ``device_put`` (the array IS the global batch).
    Multi-process: the arrays are each host's LOCAL shard and the global
    array is assembled without any host ever holding the global batch
    (:func:`host_local_batch`). Trainers route ``shard_batch`` through this,
    so the same training script scales from one chip to a pod.
    """
    spec = tuple(spec)
    if jax.process_count() > 1:
        return host_local_batch(mesh, spec, *arrays)
    if len(spec) != len(arrays):
        raise ValueError(
            f"put_global got {len(arrays)} arrays but {len(spec)} specs"
        )
    return tuple(
        jax.device_put(a, NamedSharding(mesh, s)) for s, a in zip(spec, arrays)
    )


def data_shard(mesh: Mesh, axis: str = AXIS_DATA) -> tuple[int, int]:
    """(shard_id, num_shards) of THIS process along the batch axis.

    Hosts whose devices sit at the same data coordinates must feed
    IDENTICAL data (they jointly assemble the same global-batch rows via
    ``make_array_from_process_local_data``), so the shard id is derived
    from the data coordinates this process owns — NOT from
    ``jax.process_index()``, which would hand model-parallel co-hosts
    disjoint data and silently corrupt the global batch."""
    if jax.process_count() == 1:
        return 0, 1
    local = mesh.local_mesh.shape
    glob = dict(mesh.shape)
    num_shards = glob[axis] // local[axis]
    axes = list(mesh.axis_names)
    dim = axes.index(axis)
    my_coords = sorted(
        {
            int(np.argwhere(mesh.devices == d)[0][dim])
            for d in mesh.local_devices
        }
    )
    return my_coords[0] // local[axis], num_shards


def local_batch_size(mesh: Mesh, global_batch: int, axis: str = AXIS_DATA) -> int:
    """This host's share of the global batch: the batch (``data``) axis may
    cross processes, every other axis must be process-local (the placement
    :func:`make_multihost_mesh` produces; anything else would mean pipeline
    wires / halo rings over DCN, which we refuse rather than silently run
    slow)."""
    local = mesh.local_mesh.shape
    glob = dict(mesh.shape)
    for name in glob:
        if name != axis and local[name] != glob[name]:
            raise ValueError(
                f"mesh axis {name!r} crosses process boundaries "
                f"(local {local[name]} != global {glob[name]}); only the "
                f"{axis!r} axis may span hosts"
            )
    if global_batch % glob[axis]:
        raise ValueError(
            f"global batch {global_batch} must divide by the {axis!r} axis "
            f"extent {glob[axis]}"
        )
    return global_batch * local[axis] // glob[axis]
