"""Telemetry subsystem (:mod:`mpi4dl_tpu.telemetry`): registry semantics,
reservoir percentiles vs the shared ``percentiles()`` ground truth,
Prometheus exposition-format escaping, JSONL schema round-trip, thread
safety under concurrent load, the catalog↔docs↔exposed-names CI gates,
and the end-to-end acceptance invariants — a scraped endpoint whose
latency histogram agrees with the load generator's own report, and a JSONL
span log where per-request phase durations sum exactly to the observed
end-to-end latency.
"""

import json
import os
import re
import threading
import urllib.request

import numpy as np
import pytest

from mpi4dl_tpu import telemetry
from mpi4dl_tpu.profiling import StepTimer, percentiles
from mpi4dl_tpu.telemetry.catalog import CATALOG

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- registry semantics -------------------------------------------------------


def test_counter_semantics():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("requests_total", "help", labels=("outcome",))
    c.inc(outcome="ok")
    c.inc(2, outcome="ok")
    c.inc(outcome="err")
    assert c.value(outcome="ok") == 3
    assert c.value(outcome="err") == 1
    with pytest.raises(ValueError):  # counters are monotone
        c.inc(-1, outcome="ok")
    with pytest.raises(ValueError):  # label names are declared up front
        c.inc(bucket="4")
    # Same name, same signature → same object; different signature → error.
    assert reg.counter("requests_total", "help", labels=("outcome",)) is c
    with pytest.raises(ValueError):
        reg.counter("requests_total", "help", labels=("other",))
    with pytest.raises(ValueError):
        reg.gauge("requests_total")
    with pytest.raises(ValueError):  # invalid prometheus name
        reg.counter("bad-name")


def test_gauge_semantics():
    reg = telemetry.MetricsRegistry()
    g = reg.gauge("queue_depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value() == 6
    g.set(-3)  # gauges may be anything
    assert g.value() == -3


def test_histogram_buckets_and_snapshot():
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("lat", "h", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    (series,) = h.snapshot_series()
    assert series["count"] == 4
    assert series["sum"] == pytest.approx(5.555)
    # Cumulative le buckets, +Inf == count.
    assert series["buckets"] == {"0.01": 1, "0.1": 2, "1": 3, "+Inf": 4}


def test_reservoir_percentiles_match_ground_truth():
    rng = np.random.default_rng(0)
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("lat")
    small = rng.standard_exponential(200).tolist()
    for v in small:
        h.observe(v)
    # Below reservoir capacity the reservoir holds EVERY observation:
    # percentiles are bit-identical to the shared helper on the raw data.
    assert h.percentiles() == percentiles(small)

    # Above capacity it is a uniform sample: p50 within a loose tolerance.
    big = rng.standard_exponential(20_000).tolist()
    r = telemetry.Reservoir(size=1024)
    for v in big:
        r.observe(v)
    assert r.count == 20_000 and len(r.values) == 1024
    truth = percentiles(big)
    approx = r.percentiles()
    assert approx["p50"] == pytest.approx(truth["p50"], rel=0.15)
    assert approx["p90"] == pytest.approx(truth["p90"], rel=0.25)


def test_thread_safety_under_concurrent_load():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("hits_total", labels=("worker",))
    h = reg.histogram("obs")
    n_threads, n_iter = 8, 2000

    def work(wid):
        for i in range(n_iter):
            c.inc(worker=wid % 2)  # contended series
            h.observe(i * 1e-4)

    threads = [
        threading.Thread(target=work, args=(w,)) for w in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(worker=0) + c.value(worker=1) == n_threads * n_iter
    (series,) = h.snapshot_series()
    assert series["count"] == n_threads * n_iter
    assert series["buckets"]["+Inf"] == n_threads * n_iter


# -- Prometheus exposition format --------------------------------------------


def test_prometheus_rendering_shape():
    reg = telemetry.MetricsRegistry()
    reg.counter("req_total", "requests", labels=("outcome",)).inc(
        3, outcome="served"
    )
    reg.gauge("depth", "queue").set(7)
    reg.histogram("lat", "latency", buckets=(0.1, 1.0)).observe(0.5)
    text = telemetry.render_prometheus(reg)
    assert "# TYPE req_total counter" in text
    assert 'req_total{outcome="served"} 3' in text
    assert "# HELP depth queue" in text
    assert "depth 7" in text
    assert 'lat_bucket{le="0.1"} 0' in text
    assert 'lat_bucket{le="1"} 1' in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_sum 0.5" in text
    assert "lat_count 1" in text


def test_prometheus_escaping():
    reg = telemetry.MetricsRegistry()
    reg.counter(
        "esc_total", 'help with \\ and\nnewline', labels=("path",)
    ).inc(path='a"b\\c\nd')
    text = telemetry.render_prometheus(reg)
    assert r"# HELP esc_total help with \\ and\nnewline" in text
    assert r'esc_total{path="a\"b\\c\nd"} 1' in text
    # One logical line per sample — the newline really was escaped.
    assert len(text.strip().splitlines()) == 3


# -- JSONL schema + round-trip ------------------------------------------------


def test_jsonl_round_trip(tmp_path):
    w = telemetry.JsonlWriter(str(tmp_path))
    assert w.enabled
    reg = telemetry.MetricsRegistry()
    reg.counter("c_total").inc()
    reg.histogram("h").observe(0.25)
    spans = telemetry.spans_from_marks(
        [("submit", 1.0), ("queue_wait", 1.5), ("compute", 2.25)]
    )
    events = [
        telemetry.span_event("serve.request", "trace-1", spans,
                             attrs={"outcome": "served"}),
        telemetry.metrics_event(reg),
        {"ts": 3.0, "kind": "event", "name": "engine.start", "attrs": {}},
    ]
    for e in events:
        w.write(e)
    w.close()
    back = telemetry.read_events(w.path)  # validates every line
    assert back == json.loads(json.dumps(events))  # float-stable round trip
    assert back[0]["spans"][0]["duration_s"] == 0.5
    assert back[1]["metrics"]["c_total"]["series"][0]["value"] == 1


def test_jsonl_disabled_without_dir(monkeypatch):
    monkeypatch.delenv(telemetry.ENV_DIR, raising=False)
    w = telemetry.JsonlWriter()
    assert not w.enabled
    w.write({"ts": 0, "kind": "event", "name": "x"})  # silent no-op
    w.close()


def test_validate_event_rejects_malformed():
    ok = {"ts": 1.0, "kind": "event", "name": "x"}
    telemetry.validate_event(ok)
    bad = [
        {"kind": "event", "name": "x"},  # no ts
        {"ts": 1.0, "kind": "bogus", "name": "x"},  # unknown kind
        {"ts": 1.0, "kind": "span", "name": "x", "trace_id": "t",
         "spans": []},  # empty spans
        {"ts": 1.0, "kind": "span", "name": "x", "trace_id": "t",
         "spans": [{"phase": "p", "start_s": 2.0, "end_s": 1.0,
                    "duration_s": -1.0}]},  # ends before start
        {"ts": 1.0, "kind": "metrics",
         "metrics": {"m": {"type": "counter", "series": [{}]}}},
    ]
    for ev in bad:
        with pytest.raises(ValueError):
            telemetry.validate_event(ev)


def test_spans_from_marks_contiguity():
    spans = telemetry.spans_from_marks(
        [("t0", 0.0), ("a", 1.0), ("b", 1.0), ("c", 4.5)]
    )
    assert [s["phase"] for s in spans] == ["a", "b", "c"]
    for prev, nxt in zip(spans, spans[1:]):
        assert prev["end_s"] == nxt["start_s"]
    assert sum(s["duration_s"] for s in spans) == 4.5  # == end - anchor
    with pytest.raises(ValueError):  # clock running backwards
        telemetry.spans_from_marks([("t0", 1.0), ("a", 0.5)])
    with pytest.raises(ValueError):  # anchor alone is not a span
        telemetry.spans_from_marks([("t0", 1.0)])


# -- scrape endpoint ----------------------------------------------------------


def test_metrics_server_scrape():
    reg = telemetry.MetricsRegistry()
    reg.counter("up_total").inc(4)
    srv = telemetry.MetricsServer(reg, port=0)
    try:
        body = urllib.request.urlopen(srv.url, timeout=10).read().decode()
        assert "up_total 4" in body
        reg.counter("up_total").inc()  # live: next scrape sees the update
        body = urllib.request.urlopen(srv.url, timeout=10).read().decode()
        assert "up_total 5" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=10
            )
    finally:
        srv.close()


def test_metrics_server_root_is_an_endpoint_index():
    """ISSUE satellite: probing the bare port discovers the surface — a
    text index of the routes this server actually answers, not a 404
    (and not a surprise full scrape). Provider-less routes are absent."""
    reg = telemetry.MetricsRegistry()
    reg.counter("up_total").inc()
    srv = telemetry.MetricsServer(reg, port=0)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/", timeout=10
        ).read().decode()
        assert "/metrics" in body
        assert "up_total" not in body  # index, not a scrape
        assert "/healthz" not in body  # no provider wired
    finally:
        srv.close()
    srv = telemetry.MetricsServer(
        reg, port=0, health=lambda: {"healthy": True},
        debug=lambda: {}, alerts=lambda: {},
    )
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/", timeout=10
        ).read().decode()
        for route in ("/metrics", "/healthz", "/debugz", "/alertz"):
            assert route in body
    finally:
        srv.close()


def test_metrics_server_head_probe_gets_200():
    """ISSUE satellite: load-balancer/uptime probes use HEAD — they must
    get 200 with headers and no body, not http.server's default 501."""
    reg = telemetry.MetricsRegistry()
    reg.counter("up_total").inc(4)
    srv = telemetry.MetricsServer(reg, port=0)
    try:
        resp = urllib.request.urlopen(
            urllib.request.Request(srv.url, method="HEAD"), timeout=10
        )
        assert resp.status == 200
        assert int(resp.headers["Content-Length"]) > 0
        assert resp.read() == b""  # headers only
    finally:
        srv.close()


def test_metrics_server_non_get_head_is_405():
    """ISSUE satellite: the endpoints are read-only — writes answer 405
    (wrong method), not 404 (missing path) or 501 (unimplemented)."""
    reg = telemetry.MetricsRegistry()
    srv = telemetry.MetricsServer(reg, port=0)
    try:
        for method in ("POST", "PUT", "DELETE"):
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(urllib.request.Request(
                    srv.url, data=b"x" if method != "DELETE" else None,
                    method=method,
                ), timeout=10)
            assert exc.value.code == 405, method
    finally:
        srv.close()


def test_metrics_server_healthz_and_debugz():
    reg = telemetry.MetricsRegistry()
    state = {"healthy": True, "reason": "ok"}
    srv = telemetry.MetricsServer(
        reg, port=0, health=lambda: dict(state),
        debug=lambda: {"tail": [1, 2, 3]},
    )
    base = f"http://127.0.0.1:{srv.port}"
    try:
        assert urllib.request.urlopen(f"{base}/healthz", timeout=10).status == 200
        dbg = json.loads(
            urllib.request.urlopen(f"{base}/debugz", timeout=10).read()
        )
        assert dbg == {"tail": [1, 2, 3]}
        state["healthy"] = False
        state["reason"] = "watchdog tripped"
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{base}/healthz", timeout=10)
        assert exc.value.code == 503
        assert json.loads(exc.value.read())["reason"] == "watchdog tripped"
        # HEAD mirrors the status so probes need no body parsing.
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/healthz", method="HEAD"), timeout=10)
        assert exc.value.code == 503
    finally:
        srv.close()


def test_jsonl_close_flushes_partial_span_batch(tmp_path):
    """ISSUE satellite: span events flush in batches of 100; a writer
    closed with a partial batch (7 < 100) must still land every event."""
    w = telemetry.JsonlWriter(str(tmp_path))
    spans = telemetry.spans_from_marks([("t0", 0.0), ("phase", 1.0)])
    for i in range(7):
        w.write(telemetry.span_event("t", f"id-{i}", spans))
    w.close()
    assert len(telemetry.read_events(w.path)) == 7
    w.close()  # idempotent alongside the atexit hook


def test_steptimer_zero_dt_summary_does_not_raise():
    """ISSUE satellite: a step whose measured dt is 0 (clock too coarse)
    reports 0.0 img/s — the telemetry gauge's convention — instead of
    ZeroDivisionError inside summary()."""
    timer = StepTimer(batch_size=4, warmup=0)
    timer.times[:] = [0.0, 0.1]
    assert timer.images_per_sec == [0.0, 40.0]
    s = timer.summary()
    assert s["steps"] == 2
    assert s["images_per_sec_mean"] == 20.0


def test_prometheus_escaping_round_trips():
    """ISSUE satellite: HELP text and label values containing newlines,
    quotes, and backslashes survive escape → render → unescape exactly —
    including the sequences naive replace-chains corrupt (a literal
    backslash before an 'n', a trailing backslash)."""
    from mpi4dl_tpu.telemetry.export import (
        escape_help,
        escape_label_value,
        unescape_help,
        unescape_label_value,
    )

    nasty = [
        'plain',
        'a"b\\c\nd',
        'line1\nline2\n',
        'backslash-n literal \\n not newline',
        'trailing backslash \\',
        '\\\n"',
        '\\\\n',  # two backslashes then n — must not become \ + newline
    ]
    for s in nasty:
        assert unescape_label_value(escape_label_value(s)) == s, s
        assert unescape_help(escape_help(s)) == s, s
        # Escaped forms are single-line (the format's framing invariant).
        assert "\n" not in escape_label_value(s)
        assert "\n" not in escape_help(s)
    # And through a full render: the escaped sample parses back to the
    # original value from the exposition text itself.
    reg = telemetry.MetricsRegistry()
    reg.counter("rt_total", "h", labels=("path",)).inc(path='a"b\\c\nd')
    text = telemetry.render_prometheus(reg)
    (line,) = [l for l in text.splitlines() if l.startswith("rt_total{")]
    quoted = line[line.index('path="') + len('path="'):line.rindex('"')]
    assert unescape_label_value(quoted) == 'a"b\\c\nd'


def test_trace_ids_unique_across_processes(tmp_path):
    """ISSUE satellite: trace ids embed pid + a random component, so N
    replica processes minting ids concurrently cannot collide in the
    federated span stream — checked across two real spawned processes."""
    import subprocess
    import sys

    prog = (
        "from mpi4dl_tpu.telemetry import new_trace_id\n"
        "print('\\n'.join(new_trace_id('serve') for _ in range(200)))\n"
    )
    env = dict(
        os.environ,
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    outs = []
    for _ in range(2):
        outs.append(subprocess.run(
            [sys.executable, "-c", prog], env=env,
            capture_output=True, text=True, timeout=120, check=True,
        ).stdout.split())
    a, b = (set(o) for o in outs)
    assert len(a) == len(b) == 200
    assert not (a & b), "trace ids collided across processes"
    # Format: prefix-pidhex-rand32-counter; in-process ids stay ordered.
    assert outs[0][0].endswith("-0") and outs[0][199].endswith("-199")
    assert len(outs[0][0].split("-")) == 4


def test_metrics_server_snapshotz_is_machine_readable():
    """Tentpole seam: /snapshotz serves the registry as a schema-valid
    metrics event + the emitting pid — what the federation aggregator
    scrapes instead of parsing Prometheus text."""
    reg = telemetry.MetricsRegistry()
    reg.counter("up_total").inc(4)
    reg.histogram("lat", buckets=(0.1,)).observe(0.05)
    srv = telemetry.MetricsServer(reg, port=0)
    try:
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/snapshotz", timeout=10
        ).read())
        telemetry.validate_event(snap)
        assert snap["kind"] == "metrics"
        assert snap["pid"] == os.getpid()
        assert snap["metrics"]["up_total"]["series"][0]["value"] == 4
        index = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/", timeout=10
        ).read().decode()
        assert "/snapshotz" in index
    finally:
        srv.close()


def test_client_overhead_and_phase_shares_published(full_stack):
    """ISSUE satellites: the client-vs-engine latency gap is a real
    histogram (one observation per served request), and the engine's
    phase-share gauges mirror the span mix, summing to ~1."""
    reg, _, report, _, scraped = full_stack
    (ov,) = reg.get("serve_client_overhead_seconds").snapshot_series()
    assert ov["count"] == 48
    assert ov["sum"] >= 0
    assert report["client_overhead_s"] is not None
    assert report["client_overhead_s"]["p50"] >= 0
    assert "serve_client_overhead_seconds_bucket" in scraped

    shares = {
        s["labels"]["phase"]: s["value"]
        for s in reg.get("serve_phase_share").snapshot_series()
    }
    assert set(shares) == {
        "queue_wait", "batch_form", "h2d_stage", "device_compute"
    }
    assert sum(shares.values()) == pytest.approx(1.0, abs=1e-6)


# -- catalog gates: docs <-> catalog <-> what the stack exposes ---------------

_DOC_ROW = re.compile(r"^\|\s*`([a-z_][a-z0-9_]*)`\s*\|([^|]+)\|([^|]+)\|")


def _docs_catalog():
    path = os.path.join(REPO, "docs", "OBSERVABILITY.md")
    out = {}
    with open(path) as f:
        for line in f:
            m = _DOC_ROW.match(line.strip())
            if not m:
                continue
            name, mtype = m.group(1), m.group(2).strip()
            labels = tuple(re.findall(r"`([a-z_]+)`", m.group(3)))
            out[name] = (mtype, labels)
    return out


def test_docs_metric_table_matches_catalog():
    """CI satellite: docs/OBSERVABILITY.md lists exactly the cataloged
    metrics with matching types and labels — no silently undocumented and
    no stale documented names."""
    docs = _docs_catalog()
    assert set(docs) == set(CATALOG), (
        f"docs-only: {sorted(set(docs) - set(CATALOG))}, "
        f"catalog-only: {sorted(set(CATALOG) - set(docs))}"
    )
    for name, spec in CATALOG.items():
        assert docs[name] == (spec.type, spec.labels), (
            f"{name}: docs say {docs[name]}, catalog says "
            f"{(spec.type, spec.labels)}"
        )


def test_declare_refuses_uncataloged_names():
    reg = telemetry.MetricsRegistry()
    with pytest.raises(KeyError, match="CATALOG"):
        telemetry.declare(reg, "totally_new_metric")


# -- full stack: one registry, every publisher, every invariant ---------------


@pytest.fixture(scope="module")
def full_stack(tmp_path_factory):
    """One shared registry exercised by every publisher in the repo —
    serving engine (+ spans JSONL + scrape endpoint), load generator,
    StepTimer, Trainer.publish_telemetry, hlolint publish — then handed to
    the tests below as (registry, engine, loadgen report, jsonl events,
    scraped text)."""
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.evaluate import collect_batch_stats
    from mpi4dl_tpu.models.resnet import get_resnet_v2
    from mpi4dl_tpu.parallel.partition import init_cells
    from mpi4dl_tpu.serve import ServingEngine
    from mpi4dl_tpu.serve.loadgen import run_closed_loop
    from mpi4dl_tpu.train import Trainer
    from mpi4dl_tpu.utils import get_depth

    size = 16
    tdir = str(tmp_path_factory.mktemp("tele"))
    cells = get_resnet_v2(
        depth=get_depth(2, 1), num_classes=10, pool_kernel=size // 4
    )
    rng = np.random.default_rng(0)
    params = init_cells(
        cells, jax.random.PRNGKey(0), jnp.zeros((1, size, size, 3))
    )
    stats = collect_batch_stats(
        cells, params,
        [jnp.asarray(rng.standard_normal((4, size, size, 3)), jnp.float32)],
    )
    reg = telemetry.MetricsRegistry()
    engine = ServingEngine(
        cells, params, stats, example_shape=(size, size, 3), max_batch=4,
        default_deadline_s=30.0, registry=reg, metrics_port=0,
        telemetry_dir=tdir,
        # SLOs on (ISSUE CI satellite): the run must expose the slo_* /
        # alert_active / autoscale_desired_replicas names the catalog
        # now pins. headroom_alert_ratio arms memory_headroom_low — on
        # the CPU backend the gauge never publishes, so the alert is
        # armed but structurally untrippable (absent-not-wrong).
        slo=telemetry.SLOConfig(
            availability=0.999, latency_threshold_s=2.5, interval_s=0.2,
            headroom_alert_ratio=0.05,
        ),
    )
    engine.start()
    report = run_closed_loop(
        engine, 48, concurrency=12, deadline_s=30.0, events=engine.events,
    )
    scraped = urllib.request.urlopen(
        f"http://127.0.0.1:{engine.metrics_port}/metrics", timeout=10
    ).read().decode()
    # Federation publisher against the same registry: an aggregator
    # scraping this engine's own /snapshotz (the catalog pin must see
    # federation_replicas / federation_scrapes_total from a real scrape).
    from mpi4dl_tpu.telemetry.federation import FederatedAggregator

    agg = FederatedAggregator(
        replicas={"r0": f"http://127.0.0.1:{engine.metrics_port}"},
        registry=reg,
    )
    agg.scrape_once()
    assert agg.registry.get("federation_replicas").value(state="up") == 1
    # Fleet publisher (mpi4dl_tpu/fleet): the router/supervisor declare
    # the fleet_* names at construction; the one-call declare keeps the
    # catalog==runtime pin honest without spawning a fleet here (the
    # live series are exercised by tests/test_fleet.py).
    from mpi4dl_tpu import fleet

    fleet.declare_metrics(reg)
    # Tiled publisher (mpi4dl_tpu/serve/tiled.py): same pattern — the
    # tiled_* names declared in one call; the live series (a real tiled
    # engine streaming + stitching) are exercised by
    # tests/test_serve_tiled.py, and running a second engine against
    # THIS registry would perturb the counters the span/scrape tests
    # below reconcile against the loadgen report.
    from mpi4dl_tpu.serve import tiled as serve_tiled

    serve_tiled.declare_metrics(reg)
    engine.stop()
    engine.lint_report()  # hlolint_* gauges

    # Train-side publishers against the same registry.
    timer = StepTimer(batch_size=4, warmup=0, registry=reg)
    for _ in range(3):
        with timer.step():
            pass
    trainer = Trainer(
        cells, num_spatial_cells=0,
        config=ParallelConfig(
            batch_size=2, split_size=1, spatial_size=0, image_size=size
        ),
    )
    trainer.publish_telemetry(
        reg, params=params, x_shape=(2, size, size, 3)
    )
    # Footprint ledger, train side: the compiled step's predicted peak
    # under program_peak_hbm_bytes (the serve side recorded its buckets
    # at AOT warm-up above).
    state = trainer.init(jax.random.PRNGKey(0), (2, size, size, 3))
    xs, ys = trainer.shard_batch(
        jnp.zeros((2, size, size, 3), jnp.float32),
        jnp.zeros((2,), jnp.int32),
    )
    trainer.record_memory_footprint(state, xs, ys, registry=reg)
    # OOM forensics publisher: one canned-drill report so the counter
    # carries a real series in the full-stack run.
    from test_memory_obs import HBM_OOM

    telemetry.emit_oom_report(HBM_OOM, program="drill", registry=reg)

    # Trace-attribution publisher (profiling.capture -> analysis.trace):
    # a ppermute ring on the CPU mesh so the capture carries collective
    # slices and the overlap-ratio gauge gets a value.
    from jax.sharding import Mesh, PartitionSpec as P

    from mpi4dl_tpu import profiling
    from mpi4dl_tpu.analysis.trace import publish_attribution
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()), ("x",))
    n = len(jax.devices())

    def body(v):
        w = jax.lax.ppermute(v, "x", [(i, (i + 1) % n) for i in range(n)])
        m = v[0]
        return v * (m @ m.T).sum() + w

    g = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
    ))
    v = jnp.ones((n, 64, 64))
    g(v).block_until_ready()
    cap = profiling.capture(
        lambda i: g(v), steps=3, logdir=str(tmp_path_factory.mktemp("tr"))
    )
    summary = cap.attribution()
    if summary["collective"]["overlap_ratio"] is None:
        # tiny programs can finish their collectives with no concurrent
        # compute sampled; the gauge must still be exercised
        summary["collective"]["overlap_ratio"] = 0.0
    publish_attribution(summary, reg, program="unit")

    # Pipeline-lens publisher (analysis/trace.py): a canned summary keeps
    # the catalog==runtime pin honest without compiling a pipeline
    # trainer here — the live capture behind these numbers is exercised
    # by tests/test_pipeline_lens.py (the fleet.declare_metrics pattern).
    from mpi4dl_tpu.analysis.trace import publish_pipeline_attribution

    publish_pipeline_attribution(
        {"bubble_fraction": 0.2, "stage_device_seconds": [0.5, 0.7],
         "img_per_s": 7.9},
        reg, program="pipeline_gpipe",
    )

    # Cost-model publisher (analysis/costmodel.py): same canned-publish
    # pattern — the live pricing path is exercised by
    # tests/test_costmodel.py and the bench extras.
    from mpi4dl_tpu.analysis.costmodel import (
        predict_program as _cm_predict, publish_prediction,
    )

    cm_pred = _cm_predict(
        [{"opcode": "collective-permute", "bytes_moved": 1 << 20,
          "is_async": True, "compute_between": 2}],
        interconnect="ici", analytic_bubble=0.2,
    )
    cm_pred["program"] = "train_step"
    publish_prediction(cm_pred, reg)

    events = telemetry.read_events(
        os.path.join(tdir, os.listdir(tdir)[0])
    )
    return reg, engine, report, events, scraped


def test_full_stack_exposes_exactly_the_catalog(full_stack):
    """CI satellite, the other direction: a run touching every publisher
    exposes exactly the cataloged names — a stale catalog entry nothing
    publishes anymore fails here."""
    reg = full_stack[0]
    assert set(reg.names()) == set(CATALOG)


def test_span_durations_sum_to_e2e_latency(full_stack):
    """ISSUE acceptance: in the JSONL span log, queue+form+stage+compute
    sum to the observed end-to-end latency, per request, exactly — the
    spans are contiguous by construction."""
    events = full_stack[3]
    span_events = [
        e for e in events
        if e["kind"] == "span" and e["name"] == "serve.request"
    ]
    served = [e for e in span_events if e["attrs"]["outcome"] == "served"]
    assert len(served) == 48
    # The in-process client wrote its own span segments into the same
    # log, sharing trace ids with the engine's — the joined view the
    # trace exporter renders.
    client = [
        e for e in events
        if e["kind"] == "span" and e["name"] == "client.request"
    ]
    assert len(client) == 48
    assert {e["trace_id"] for e in client} == {e["trace_id"] for e in served}
    for e in served:
        phases = [s["phase"] for s in e["spans"]]
        assert phases == [
            "queue_wait", "batch_form", "h2d_stage", "device_compute"
        ]
        for prev, nxt in zip(e["spans"], e["spans"][1:]):
            assert prev["end_s"] == nxt["start_s"]
        total = sum(s["duration_s"] for s in e["spans"])
        assert total == pytest.approx(e["attrs"]["e2e_latency_s"], abs=1e-9)


def test_scraped_endpoint_carries_serving_signals(full_stack):
    """ISSUE acceptance: the Prometheus endpoint of a loadgen run exposes
    request counts by outcome, queue depth, bucket occupancy, and latency
    histograms whose percentiles agree with loadgen's own report."""
    reg, engine, report, _, scraped = full_stack
    assert 'serve_requests_total{outcome="served"} 48' in scraped
    assert "serve_queue_depth" in scraped
    assert "serve_batch_occupancy_bucket" in scraped
    assert "serve_request_latency_seconds_bucket" in scraped
    assert "loadgen_requests_total" in scraped

    # Engine-side e2e percentiles vs the loadgen client's own measurement:
    # same requests, so they differ only by client-side future overhead.
    hist = reg.get("serve_request_latency_seconds")
    engine_p = hist.percentiles()
    client_p = report["latency_s"]
    assert engine_p["p50"] <= client_p["p50"] + 1e-3  # server <= client
    for p in ("p50", "p99"):
        assert abs(engine_p[p] - client_p[p]) <= max(
            0.05, 0.5 * client_p[p]
        ), f"{p}: engine {engine_p[p]} vs client {client_p[p]}"

    # Registry mirrors the engine's own stats() counters.
    s = engine.stats()
    assert reg.get("serve_requests_total").value(outcome="served") == s["served"]
    occupancy = reg.get("serve_batch_occupancy").snapshot_series()
    assert sum(x["count"] for x in occupancy) == s["batches"]
    assert sum(s["bucket_dispatches"].values()) == s["batches"]


def test_memory_observability_exposed(full_stack):
    """ISSUE acceptance: the full-stack run exposes every new memory
    metric name (the catalog pin above covers exactness): per-bucket
    ledger peaks with real values, the train step's program peak, the
    drill's oom report count — and the device gauges declared but
    series-less on the CPU backend (absent-not-wrong)."""
    reg, engine = full_stack[0], full_stack[1]
    bucket_peaks = reg.get("serve_bucket_peak_hbm_bytes")
    for b in engine.buckets:
        assert bucket_peaks.value(bucket=b) > 0
        assert bucket_peaks.value(bucket=b) == engine.memory_ledger.get(
            "serve_predict", bucket=b
        )["peak_bytes"]
    assert reg.get("program_peak_hbm_bytes").value(program="train_step") > 0
    assert reg.get("oom_reports_total").value(program="drill") == 1
    for name in ("device_hbm_used_bytes", "device_hbm_limit_bytes",
                 "device_hbm_headroom_ratio"):
        assert reg.get(name).snapshot_series() == []  # declared, absent
    # The engine's stats()/debugz memory view mirrors the ledger.
    mem = engine.stats()["memory"]
    assert set(mem["bucket_peak_hbm_bytes"]) == {
        str(b) for b in engine.buckets
    }
    # memory_headroom_low is armed on /alertz but untrippable on CPU.
    alerts = {a["name"]: a["state"] for a in engine.slo.state()["alerts"]}
    assert alerts["memory_headroom_low"] == "inactive"


def test_trainer_and_hlolint_gauges_published(full_stack):
    reg = full_stack[0]
    assert reg.get("train_steps_total").value() == 3
    assert reg.get("train_halo_shifts").value() == 0  # no spatial cells
    assert (
        reg.get("hlolint_ok").value(program="serve_predict") == 1.0
    )
    assert (
        reg.get("hlolint_findings").value(
            program="serve_predict", severity="error"
        ) == 0
    )


# -- bench.py result-line schema ----------------------------------------------


def test_bench_emit_telemetry_matches_jsonl_schema(capsys):
    """CI satellite: bench.py result lines embed the registry snapshot in
    the JSONL metrics-event schema — validated with the same validator the
    writer enforces."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_for_telemetry", os.path.join(REPO, "bench.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    reg = telemetry.MetricsRegistry()
    telemetry.declare(reg, "train_steps_total").inc(5)
    telemetry.declare(reg, "train_step_seconds").observe(0.1)
    bench._REGISTRY = reg
    bench._RESULT.update(
        metric="unit_test", value=1.0, unit="images/sec", vs_baseline=None
    )
    bench._emit()
    line = [
        l for l in capsys.readouterr().out.splitlines() if l.startswith("{")
    ][-1]
    rec = json.loads(line)
    ev = telemetry.validate_event(rec["telemetry"])  # raises on drift
    assert ev["kind"] == "metrics"
    assert ev["metrics"]["train_steps_total"]["series"][0]["value"] == 5
