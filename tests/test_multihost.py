"""Multi-host plumbing, exercised on the single-process CPU mesh.

True multi-process coverage needs a pod; these tests pin down everything
testable in one process: slice detection, the single-slice mesh fallback,
DP-vs-slices divisibility validation, and that ``host_local_batch`` feeds a
trainer identically to ``shard_batch`` (local == global when there is one
process)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.config import ParallelConfig
from mpi4dl_tpu.models.resnet import get_resnet_v1
from mpi4dl_tpu.parallel import multihost
from mpi4dl_tpu.train import Trainer


def test_num_slices_single():
    assert multihost.num_slices() == 1


def test_make_multihost_mesh_falls_back_single_slice():
    cfg = ParallelConfig(
        batch_size=4, split_size=1, spatial_size=0, data_parallel=2
    )
    mesh = multihost.make_multihost_mesh(cfg)
    assert mesh.shape == dict(zip(multihost.MESH_AXES, cfg.mesh_shape))
    # Same device placement as the plain factory.
    assert (mesh.devices == cfg.make_mesh().devices).all()


def test_initialize_distributed_joins_only_a_configured_world(monkeypatch):
    """Single process with no coordinator: nothing is joined and jax is not
    even asked (on a one-chip TPU VM without a metadata server the bare
    call fails). With a coordinator configured (env, argument or launcher
    marker), a failure MUST propagate — swallowing it would silently
    degrade a pod launch to N independent single-host jobs."""
    calls = []

    def fake_init(coordinator_address=None, num_processes=None, process_id=None):
        calls.append(coordinator_address)
        raise RuntimeError("backend already initialized")

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    for var in multihost._COORDINATOR_ENV_VARS + multihost._MULTIPROC_ENV_MARKERS:
        monkeypatch.delenv(var, raising=False)
    multihost.initialize_distributed()  # unconfigured → nothing to join
    assert calls == []

    monkeypatch.setenv("SLURM_NTASKS", "2")  # a launcher's marker alone
    with pytest.raises(RuntimeError):
        multihost.initialize_distributed()
    monkeypatch.delenv("SLURM_NTASKS")

    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "badhost:1234")
    with pytest.raises(RuntimeError):
        multihost.initialize_distributed()
    with pytest.raises(RuntimeError):  # explicit argument, no env
        monkeypatch.delenv("JAX_COORDINATOR_ADDRESS")
        multihost.initialize_distributed(coordinator_address="badhost:1234")


def _metadata_of(endpoints=None):
    """A metadata server: a GCE TPU VM's when it lists worker endpoints,
    a GKE node's (no TPU worker attributes) when it does not."""
    attrs = {"tpu-env": "ACCELERATOR_TYPE: 'v5litepod-8'"}
    if endpoints:
        attrs |= {"agent-worker-number": "0",
                  "worker-network-endpoints": endpoints}

    def get_metadata(key):
        return (attrs[key], 200) if key in attrs else ("not found", 404)

    return get_metadata


def _no_metadata_server(key):
    import requests

    raise requests.exceptions.ConnectionError("metadata.google.internal")


@pytest.mark.parametrize(
    "tpu_vm, env, get_metadata, joins",
    [
        # GKE pod: the hosts are in the environment, the operator set nothing
        (True, {"TPU_WORKER_HOSTNAMES": "h0,h1", "TPU_WORKER_ID": "1"},
         _metadata_of(), True),
        (True, {"TPU_WORKER_HOSTNAMES": "h0", "TPU_WORKER_ID": "0"},
         _metadata_of(), False),
        # GCE pod: the hosts are on the metadata server
        (True, {}, _metadata_of("a:b:10.0.0.1,a:b:10.0.0.2"), True),
        (True, {}, _metadata_of("a:b:10.0.0.1"), False),
        # one TPU VM sealed off from the network: no metadata server at all;
        # the machine behind the chip tool also lists itself as the one worker
        (True, {}, _no_metadata_server, False),
        (True, {"TPU_WORKER_HOSTNAMES": "localhost", "TPU_WORKER_ID": "0"},
         _no_metadata_server, False),
        # ... while two listed hosts are a pod, metadata server or not
        (True, {"TPU_WORKER_HOSTNAMES": "h0,h1", "TPU_WORKER_ID": "1"},
         _no_metadata_server, True),
        # no TPU VM (the CPU sandbox): the probes are not even made
        (False, {}, None, False),
    ],
    ids=["gke-pod", "gke-one-host", "gce-pod", "gce-one-host",
         "sealed-one-host", "sealed-one-host-lists-itself",
         "sealed-two-hosts", "no-tpu-vm"],
)
def test_initialize_distributed_joins_a_detected_pod(
    monkeypatch, tpu_vm, env, get_metadata, joins
):
    """A single-slice multi-host TPU pod sets no coordinator variable and
    no launcher marker: jax finds its hosts itself. It must still be
    joined (and a failure there must propagate), while one host alone —
    with or without a metadata server — has nothing to join."""
    from jax._src.clusters import cloud_tpu_cluster

    for var in (
        multihost._COORDINATOR_ENV_VARS + multihost._MULTIPROC_ENV_MARKERS
        + ("TPU_WORKER_HOSTNAMES", "TPU_PROCESS_ADDRESSES", "TPU_WORKER_ID",
           "TPU_SKIP_MDS_QUERY", "MEGASCALE_COORDINATOR_ADDRESS")
    ):
        monkeypatch.delenv(var, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(cloud_tpu_cluster, "running_in_cloud_tpu_vm", tpu_vm)
    monkeypatch.setattr(cloud_tpu_cluster, "get_metadata", get_metadata)
    calls = []

    def fake_init(coordinator_address=None, num_processes=None, process_id=None):
        calls.append((coordinator_address, num_processes, process_id))
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    if joins:
        with pytest.raises(RuntimeError, match="coordinator unreachable"):
            multihost.initialize_distributed()
        assert calls == [(None, None, None)]  # jax fills in what it detected
    else:
        multihost.initialize_distributed()
        assert calls == []


def test_detection_probe_errors_other_than_no_server_propagate(monkeypatch):
    from jax._src.clusters import cloud_tpu_cluster

    def broken(key):
        raise RuntimeError("Getting metadata['agent-worker-number'] failed")

    monkeypatch.delenv("TPU_SKIP_MDS_QUERY", raising=False)
    monkeypatch.setattr(cloud_tpu_cluster, "running_in_cloud_tpu_vm", True)
    monkeypatch.setattr(cloud_tpu_cluster, "get_metadata", broken)
    with pytest.raises(RuntimeError, match="agent-worker-number"):
        multihost._detected_process_count()


def test_initialize_distributed_noop_when_initialized(monkeypatch):
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: True)

    def boom(*a, **k):  # must not be reached
        raise AssertionError("initialize called despite is_initialized()")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    multihost.initialize_distributed()


class _FakeSliceDev:
    def __init__(self, slice_index):
        self.slice_index = slice_index


def test_num_slices_counts_granules():
    devs = [_FakeSliceDev(0), _FakeSliceDev(0), _FakeSliceDev(1), _FakeSliceDev(1)]
    assert multihost.num_slices(devs) == 2


def test_multihost_mesh_indivisible_dp(monkeypatch):
    monkeypatch.setattr(multihost, "num_slices", lambda devices=None: 2)
    # dp doesn't factor over the slices, but the whole mesh fits inside one
    # slice → runs there (pure SP/LP configs on multi-slice systems).
    cfg = ParallelConfig(batch_size=3, split_size=1, spatial_size=0, data_parallel=3)
    mesh = multihost.make_multihost_mesh(cfg, jax.devices()[:6])
    assert mesh.shape == dict(zip(multihost.MESH_AXES, cfg.mesh_shape))
    # ...and when it does NOT fit in one slice either, reject.
    cfg2 = ParallelConfig(batch_size=3, split_size=4, spatial_size=0, data_parallel=3)
    with pytest.raises(ValueError, match="must divide"):
        multihost.make_multihost_mesh(cfg2, jax.devices()[:6])


def test_data_shard_single_process():
    cfg = ParallelConfig(batch_size=4, split_size=1, spatial_size=0, data_parallel=2)
    mesh = cfg.make_mesh()
    assert multihost.data_shard(mesh) == (0, 1)
    assert multihost.local_batch_size(mesh, 8) == 8


def test_host_local_batch_feeds_trainer():
    """host_local_batch == shard_batch in a single-process world: a train
    step from each must produce identical metrics."""
    cfg = ParallelConfig(
        batch_size=8, split_size=1, spatial_size=0, data_parallel=4, image_size=32
    )
    cells = get_resnet_v1(depth=8)
    trainer = Trainer(cells, num_spatial_cells=0, config=cfg)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=(8,)).astype(np.int32)

    state = trainer.init(jax.random.PRNGKey(0), x.shape)
    xs, ys = trainer.shard_batch(jnp.asarray(x), jnp.asarray(y))
    _, want = trainer.train_step(state, xs, ys)

    state2 = trainer.init(jax.random.PRNGKey(0), x.shape)
    xg, yg = multihost.host_local_batch(
        trainer.mesh, (trainer.x_spec, trainer.y_spec), x, y
    )
    assert xg.shape == x.shape and yg.shape == y.shape
    _, got = trainer.train_step(state2, xg, yg)
    assert np.allclose(float(want["loss"]), float(got["loss"]))
    assert np.allclose(float(want["accuracy"]), float(got["accuracy"]))
