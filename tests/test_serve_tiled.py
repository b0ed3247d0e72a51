"""Gigapixel tiled inference (:mod:`mpi4dl_tpu.serve.tiled`) — the
halo-correct tile-streaming forward and its ``/predict_tiled`` surfaces.

Covers the ISSUE's tentpole invariants and satellites:

- **stitch exactness**: the tiled forward is BIT-IDENTICAL to the
  monolithic single-chip forward at sizes where both fit, across tile
  grids (square/rect cores, ragged last tiles, the single-tile
  degenerate window), through the model's stride-2 cells, with
  global-boundary tiles exercised by every grid (windows clamp to the
  image edge, where the conv's own zero padding IS the monolithic
  padding) — the PR-9 ``overlap_decompose`` equivalence bar. The
  bitwise half runs on a one-device backend (the deployment topology)
  in a subprocess; in this process, whose conftest simulates an
  8-device mesh, cross-shape programs carry the repo's documented f32
  reduction-order boundary and the degenerate same-shape grid stays
  bitwise;
- the margin derivation (``record_windowed_ops`` partition math) and the
  axis-plan invariants (constant window extent, core partition, ≥ margin
  of real data at every interior window edge);
- **packed-layout refusal** (packed columns fold W into C — overlap
  windows cannot be sliced, so geometry refuses loudly);
- the engine surface: a tiled ``ServingEngine`` serves through the
  unchanged batcher/scheduler stack with its own ``tiled`` SLO class,
  tiled_* metrics, footprint-ledger entries (tile executable + head),
  and a clean single-chip lint gate;
- **bounded memory** (ISSUE acceptance, compile-predicted CPU half): the
  tile executable's peak is bounded by the TILE geometry — constant
  across image sizes — and far below the monolithic forward's peak at
  the same image size;
- the fleet passthrough: a spawned worker serves ``POST /predict_tiled``
  (geometry on ``/healthz``) and a Router routes ``submit(tiled=True)``
  to it with the ``tiled`` flag journaled for router-death replay.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.evaluate import aot_compile_predict, collect_batch_stats
from mpi4dl_tpu.models.resnet import get_resnet_v1, get_resnet_v2
from mpi4dl_tpu.parallel.partition import init_cells
from mpi4dl_tpu.serve.tiled import (
    TiledPredictor,
    _axis_plan,
    section_margin,
    tile_geometry,
    tiled_engine,
)

SIZE = 56
DEPTH = 8


@pytest.fixture(scope="module")
def model():
    """One calibrated plain ResNet-v1 triple at 56 px (ragged-friendly:
    not a multiple of the default tile), shared by every stitch check so
    all comparisons use one set of weights."""
    cells = get_resnet_v1(depth=DEPTH, num_classes=10, pool_kernel=SIZE // 4)
    rng = np.random.default_rng(0)
    params = init_cells(
        cells, jax.random.PRNGKey(0), jnp.zeros((1, SIZE, SIZE, 3))
    )
    cal = [jnp.asarray(rng.standard_normal((4, SIZE, SIZE, 3)), jnp.float32)]
    stats = collect_batch_stats(cells, params, cal)
    return cells, params, stats


@pytest.fixture(scope="module")
def monolithic(model):
    """The single-chip AOT forward (the engine's own executable path) at
    bucket 1 — the golden the stitched output must match bitwise."""
    cells, params, stats = model
    compiled = aot_compile_predict(
        cells, params, stats, (SIZE, SIZE, 3), [1]
    )[1]
    return lambda x: np.asarray(compiled(params, stats, x[None]))[0]


def _examples(n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.standard_normal((SIZE, SIZE, 3)).astype(np.float32)
        for _ in range(n)
    ]


# -- geometry: partition math + plan invariants -------------------------------


def test_geometry_margin_matches_partition_math(model):
    """The derived margin is the hand-computed cumulative receptive-field
    growth of ResNet-v1 depth-8: stem 3×3 (p1·d1) + stack0 (p1·d1 twice)
    + stack1 (p1·d1 + p1·d2) + stack2 (p1·d2 + p1·d4) = 12, stride 4."""
    cells, params, stats = model
    g = tile_geometry(cells, params, stats, (SIZE, SIZE, 3), 16)
    assert g.stride_hw == (4, 4)
    assert g.margin_hw == (12, 12)
    assert g.window_hw == (16 + 24, 16 + 24)
    assert g.grid == (4, 4)  # cores 16,16,16,8 — ragged last tile
    assert [t[1] for t in g.tiles_h] == [16, 16, 16, 8]
    # The recorded op stack is the forensic trail the margin came from.
    assert all(op["kind"] in ("conv", "pool") for op in g.ops)
    assert section_margin(g.ops, (SIZE, SIZE)) == (12, 12)


def test_section_margin_formula_units():
    """Per-op contribution is max(pad, kernel−1−pad) × downsampling —
    odd SAME convs contribute pad·d, a padding-0 even pool contributes
    (k−1)·d, and a packed op refuses."""
    ops = [
        {"kind": "conv", "kernel": (3, 3), "strides": (1, 1),
         "padding": (1, 1), "input_hw": (64, 64)},
        {"kind": "conv", "kernel": (3, 3), "strides": (2, 2),
         "padding": (1, 1), "input_hw": (64, 64)},
        {"kind": "pool", "kernel": (2, 2), "strides": (2, 2),
         "padding": (0, 0), "input_hw": (32, 32)},
        {"kind": "conv", "kernel": (1, 1), "strides": (1, 1),
         "padding": (0, 0), "input_hw": (16, 16)},
    ]
    # 1·1 + 1·1 + (2−1−0)·2 + 0·4 = 4 per dim.
    assert section_margin(ops, (64, 64)) == (4, 4)
    with pytest.raises(ValueError, match="packed"):
        section_margin(
            [{"kind": "packed", "kernel": (3, 3), "strides": (1, 1),
              "padding": (1, 1), "input_hw": (64, 8)}], (64, 64),
        )
    # Non-uniform extents (op input does not divide the image) refuse.
    with pytest.raises(ValueError, match="downsampling"):
        section_margin(
            [{"kind": "conv", "kernel": (3, 3), "strides": (1, 1),
              "padding": (1, 1), "input_hw": (48, 48)}], (64, 64),
        )


def test_axis_plan_invariants():
    """Every window has the SAME extent (one executable shape); cores
    partition [0, n) exactly; every interior window edge sits ≥ margin
    from its core (a window edge inside the image carries real data),
    while an edge AT the image boundary may touch the core (the conv's
    zero padding there is the monolithic padding)."""
    for n, tile, margin in [
        (64, 16, 12), (56, 16, 12), (128, 32, 12), (64, 64, 12),
        (48, 16, 20), (256, 64, 4),
    ]:
        entries, win = _axis_plan(n, tile, margin)
        assert sum(e[1] for e in entries) == n
        pos = 0
        for c0, clen, a in entries:
            assert c0 == pos
            pos += clen
            assert 0 <= a <= n - win
            lo, hi = c0 - a, (a + win) - (c0 + clen)
            assert lo >= (margin if a > 0 else 0)
            assert hi >= (margin if a + win < n else 0)
            if win < n:
                assert lo >= 0 and hi >= 0
        if tile + 2 * margin >= n:
            assert entries == ((0, n, 0),) and win == n


# -- stitch exactness ---------------------------------------------------------


def test_tiled_forward_bit_identical_single_device_subprocess():
    """ISSUE acceptance: on a SINGLE-device backend — the tiled
    predictor's actual deployment topology (one chip serving huge
    images) — the tiled forward equals the monolithic forward across
    tile grids (square/rect cores, ragged last tiles, the single-window
    degenerate) and model families (v1, and v2's pre-activation
    bottlenecks with 1×1 stride-2 shortcuts): BIT FOR BIT for the
    window == image plan (the section/head split is bitwise-safe), and
    at the cross-executable f32 boundary for every plan. On the
    installed XLA:CPU (jaxlib 0.9.0) the two shape-changing v1 plans are
    no longer bitwise: its wide 3x3 convs round by the window's pixel
    count (``tests/_tiled_equiv_check.py`` has the mechanism). Runs in a
    subprocess because this suite's conftest simulates an 8-device mesh,
    under which XLA:CPU also partitions intra-op work per SHAPE."""
    import re
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tests",
                                      "_tiled_equiv_check.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    line = next(
        (ln for ln in reversed(proc.stdout.splitlines())
         if ln.startswith("{")), None,
    )
    assert line is not None, (
        f"equiv check emitted no JSON (rc={proc.returncode}): "
        f"{proc.stderr[-500:]}"
    )
    verdict = json.loads(line)
    assert verdict["ok"], verdict
    assert len(verdict["bit_identical"]) == 4  # 3 v1 grids + v2
    assert verdict["bit_identical"]["v1_56_t48"]


@pytest.mark.parametrize("tile", [16, 48], ids=["t16-ragged", "t48-degen"])
def test_tiled_forward_matches_monolithic_under_mesh_harness(
    model, monolithic, tile
):
    """In-harness half of the equivalence suite (this process simulates
    an 8-device mesh): the tiled forward is deterministic run to run,
    agrees with the monolithic forward at the repo's documented
    cross-executable f32 boundary for shape-changing grids, and stays
    BITWISE for the degenerate single-window grid (window == image: the
    section program has the monolithic shape, which also pins that the
    section/head SPLIT itself is bitwise-safe)."""
    cells, params, stats = model
    pred = TiledPredictor(cells, params, stats, (SIZE, SIZE, 3), tile)
    handle = pred.compile_bucket(1)
    for i, x in enumerate(_examples(2, seed=3)):
        got = pred.run(handle, x[None])[0]
        want = monolithic(x)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, pred.run(handle, x[None])[0])
        if tile == 48:
            assert np.array_equal(got, want), f"example {i}"
        else:
            np.testing.assert_allclose(got, want, rtol=0, atol=5e-6)


def test_batched_tile_buckets_tolerance_and_determinism(model, monolithic):
    """``tile_batch>1`` is the opt-in throughput lever: windows batched
    into power-of-two tile buckets are deterministic run to run and
    agree with the monolithic forward at the repo's documented
    cross-executable f32 reduction-order boundary (a batch-2 window
    program is a DIFFERENT program — the same ~1e-7 boundary as
    cross-bucket rows in the plain engine; ``tile_batch=1``, the
    default, is the bitwise path asserted above)."""
    cells, params, stats = model
    pred = TiledPredictor(
        cells, params, stats, (SIZE, SIZE, 3), 16, tile_batch=2
    )
    handle = pred.compile_bucket(1)
    x = _examples(1, seed=11)[0]
    a = pred.run(handle, x[None])[0]
    b = pred.run(handle, x[None])[0]
    assert np.array_equal(a, b)
    np.testing.assert_allclose(a, monolithic(x), rtol=0, atol=5e-6)


def test_packed_layout_refused():
    """Packed activations fold image columns into channels; overlap-read
    windows cannot be sliced from that layout, so geometry refuses
    loudly instead of mis-stitching (structural check — fires before any
    tracing, so no params are needed)."""
    cells = get_resnet_v2(depth=11, pool_kernel=8, layout="packed")
    with pytest.raises(ValueError, match="packed"):
        tile_geometry(
            cells, [{}] * len(cells), [{}] * len(cells), (32, 32, 3), 8
        )


def test_misaligned_tile_and_image_refused(model):
    cells, params, stats = model
    with pytest.raises(ValueError, match="multiple of the section stride"):
        tile_geometry(cells, params, stats, (SIZE, SIZE, 3), 10)
    with pytest.raises(ValueError, match="does not divide"):
        tile_geometry(cells, params, stats, (SIZE - 2, SIZE - 2, 3), 16)


# -- engine surface -----------------------------------------------------------


def test_tiled_engine_serves_bit_identical_with_own_slo_class(
    model, monolithic
):
    """End to end through the UNCHANGED batcher/EDF stack: the tiled
    engine AOT-warms, serves bit-identical results, accounts requests
    under its own ``tiled`` SLO class, publishes the tiled_* series,
    records tile + head executables in the footprint ledger, and passes
    the single-chip lint gate."""
    cells, params, stats = model
    eng = tiled_engine(
        cells, params, stats, (SIZE, SIZE, 3), tile=16, max_queue=8,
    )
    try:
        eng.assert_warm()
        assert eng.buckets == (1,)
        assert [c.name for c in eng.slo_classes] == ["tiled"]
        eng.start()
        xs = _examples(3, seed=7)
        futs = [eng.submit(x) for x in xs]
        outs = [f.result(timeout=120) for f in futs]
        for x, got in zip(xs, outs):
            # Under the 8-device harness, cross-shape programs carry the
            # documented f32 boundary; the bitwise claim is pinned by the
            # single-device subprocess test above.
            np.testing.assert_allclose(got, monolithic(x), rtol=0,
                                       atol=5e-6)
        s = eng.stats()
        # Geometry + per-request facts ride stats() (the loadgen/CLI
        # report's `tiled` block).
        assert s["tiled"]["grid"] == [4, 4]
        assert s["tiled"]["requests"] == 3  # warm-up runs excluded
        assert s["tiled"]["tiles_total"] == 3 * 16
        assert s["tiled"]["stitch_s"]["p50"] is not None
        # tiled_* series are live on the engine registry.
        reg = eng.registry
        assert reg.get("tiled_tiles_total").value() == 3 * 16
        assert reg.get("tiled_tiles_per_request").value() == 16
        assert reg.get("tiled_tile_batches_total").value(bucket=1) == 3 * 16
        # Requests burned the tiled class's series, nobody else's.
        lat_series = reg.get("serve_class_latency_seconds").snapshot_series()
        assert [
            (s["labels"]["slo_class"], s["count"]) for s in lat_series
        ] == [("tiled", 3)]
        # Footprint ledger: the engine bucket entry IS the tile
        # executable's peak; the head is its own entry.
        bucket_e = eng.memory_ledger.get("serve_tiled", bucket=1)
        tile_e = eng.memory_ledger.get("serve_tiled_tile", bucket=1)
        head_e = eng.memory_ledger.get("serve_tiled_head")
        assert bucket_e["peak_bytes"] == tile_e["peak_bytes"]
        assert head_e["peak_bytes"] > 0
        # Per-request tiled facts ride the span events (flight ring).
        ev = [
            e for e in eng.flight.tail(100)
            if e.get("name") == "serve.request"
        ]
        assert ev and ev[-1]["attrs"]["tiled"]["tiles"] == 16
        rep = eng.lint_report()
        assert rep.ok, rep.findings
    finally:
        eng.stop()


def test_bounded_memory_tile_executable_not_image(model):
    """ISSUE acceptance (compile-predicted half — the live device_hbm_*
    gauges are absent-not-wrong on CPU): the tiled forward's peak is
    bounded by the TILE geometry. The section executable's predicted
    peak is IDENTICAL across image sizes (same window, same program) and
    far below the monolithic forward's peak at the same image, which
    grows with the image instead."""
    from mpi4dl_tpu.analysis.memory_plan import (
        predict_serve_peak,
        predict_tiled_peak,
    )

    cells = get_resnet_v1(depth=DEPTH, num_classes=10, pool_kernel=32)
    t128 = predict_tiled_peak(cells, 128, 32, tile_bucket=1)
    cells = get_resnet_v1(depth=DEPTH, num_classes=10, pool_kernel=64)
    t256 = predict_tiled_peak(cells, 256, 32, tile_bucket=1)
    # Bounded: the hot-loop executable does not grow with the image.
    assert t128["tile_peak_bytes"] == t256["tile_peak_bytes"]
    # The stitched-feature head is the image-bound residual term — it
    # grows with the image (1/stride² of it), the tile term does not.
    assert t256["head_peak_bytes"] > t128["head_peak_bytes"]
    # And the monolithic forward at the same image dwarfs both.
    mono256 = predict_serve_peak(cells, 256, 1)
    assert mono256["peak_bytes"] > 4 * t256["peak_bytes"]


# -- fleet passthrough --------------------------------------------------------


def test_journal_carries_tiled_flag(tmp_path):
    """A tiled accept survives a router death as a TILED orphan — the
    successor re-dispatches to /predict_tiled, never /predict."""
    from mpi4dl_tpu.fleet.journal import RouterJournal, scan

    path = str(tmp_path / "rt.journal")
    j = RouterJournal(path)
    j.accept("t-plain", np.zeros((2, 2, 3), np.float32), 30.0)
    j.accept("t-tiled", np.zeros((4, 4, 3), np.float32), 30.0, tiled=True)
    j.done("t-plain", "served")
    j.close()
    rec = scan(path)
    assert [o.trace_id for o in rec.orphans] == ["t-tiled"]
    assert rec.orphans[0].tiled is True


def test_worker_and_router_tiled_passthrough(tmp_path):
    """ISSUE satellite (spawned-worker tier-1): a worker spawned with
    ``--tiled 48x48`` serves POST /predict_tiled (geometry on /healthz),
    the ReplicaClient reaches it with ``tiled=True``, and a Router
    routes ``submit(tiled=True)`` through its normal dispatch/ledger
    machinery to the same surface — with the tiled flag journaled."""
    import urllib.request

    from mpi4dl_tpu.fleet.journal import scan
    from mpi4dl_tpu.fleet.replica import (
        ReplicaClient,
        ReplicaProcess,
        worker_cmd,
    )
    from mpi4dl_tpu.fleet.router import Router

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
    )
    proc = ReplicaProcess(
        "r0",
        worker_cmd(["--image-size", "16", "--max-batch", "1",
                    "--tiled", "48x48", "--tile", "16"]),
        base_dir=str(tmp_path / "fleet"),
        env=env,
        log_path=str(tmp_path / "r0.log"),
    )
    router = None
    try:
        proc.spawn()
        ports = proc.wait_ready(timeout_s=420.0)
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{ports['metrics_port']}/healthz", timeout=10
        ).read().decode())
        assert snap["tiled"]["image"] == [48, 48]
        assert snap["tiled"]["grid"] == [3, 3]
        client = ReplicaClient(
            "r0", f"http://127.0.0.1:{ports['predict_port']}"
        )
        x = np.zeros((48, 48, 3), np.float32)
        direct, payload = client.predict(
            x, trace_id="tiled-rpc-1", deadline_s=120.0, timeout_s=180.0,
            tiled=True,
        )
        assert np.asarray(direct).shape == (10,)
        # The interactive surface still answers at ITS example shape.
        plain, _ = client.predict(
            np.zeros((16, 16, 3), np.float32), trace_id="plain-rpc-1",
            deadline_s=60.0, timeout_s=120.0,
        )
        assert np.asarray(plain).shape == (10,)
        # Router passthrough: engine-shaped admission, tiled dispatch,
        # journaled tiled flag.
        journal = str(tmp_path / "router.journal")
        router = Router(
            example_shape=(16, 16, 3), journal_path=journal,
            default_deadline_s=120.0,
        )
        router.add_replica(
            "r0", f"http://127.0.0.1:{ports['predict_port']}",
            f"http://127.0.0.1:{ports['metrics_port']}",
        )
        fut = router.submit(x, tiled=True, trace_id="tiled-routed-1")
        routed = fut.result(timeout=180.0)
        # The worker's idempotency cache served trace-id tiled-rpc-1
        # already; this NEW id executed on the tiled engine — and must
        # equal the direct RPC result bitwise (same executable).
        assert np.array_equal(np.asarray(routed), np.asarray(direct))
        lines = [json.loads(ln) for ln in open(journal)]
        acc = next(
            ln for ln in lines
            if ln.get("kind") == "accept"
            and ln["trace_id"] == "tiled-routed-1"
        )
        assert acc["tiled"] is True and acc["shape"] == [48, 48, 3]
        assert not scan(journal).orphans  # completed → nothing to replay
    finally:
        if router is not None:
            router.stop(drain=False)
        proc.terminate()


# -- CLI ----------------------------------------------------------------------


def test_serve_cli_tiled_end_to_end(tmp_path):
    """``python -m mpi4dl_tpu.serve --tiled HxW`` — builds the tiled
    engine, drives the load generator at the large example shape, and
    reports per-request tile counts + stitch latency alongside
    p50/p90/p99, with the lint gate green."""
    from mpi4dl_tpu.serve.__main__ import main

    out_path = tmp_path / "tiled.json"
    rc = main([
        "--tiled", "48x48", "--tile", "16",
        "--requests", "3", "--concurrency", "2", "--serial", "0",
        "--deadline-ms", "120000", "--lint", "--json", str(out_path),
    ])
    assert rc == 0
    rep = json.load(open(out_path))
    assert rep["buckets"] == [1]
    assert rep["loadgen"]["served"] == 3
    assert rep["loadgen"]["errors"] == 0
    t = rep["tiled"]
    assert t["grid"] == [3, 3] and t["tiles_per_request"] == 9
    assert t["requests"] == 3 and t["tiles_total"] == 27
    assert t["stitch_s"]["p50"] is not None
    assert t["tile_stream_s"]["p50"] is not None
    assert rep["lint"]["ok"]
