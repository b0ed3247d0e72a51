"""``python -m mpi4dl_tpu.analyze bench-history`` (ISSUE satellite): the
perf-trajectory comparator over committed bench round files — series
extraction from result lines, regression verdicts with a tolerance band,
CI exit codes, and the CLI dispatch through ``analysis.cli.main`` — plus
a run over the repo's real BENCH_r*.json history (it must parse, whatever
its verdict)."""

import glob
import json
import os

import pytest

from mpi4dl_tpu.analysis.bench_history import (
    compare,
    extract_series,
    main,
    render_table,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _round(n, rc, parsed):
    return {"n": n, "cmd": "python bench.py", "rc": rc, "tail": "",
            "parsed": parsed}


def _result(headline_value, extra_value, peak=None):
    extras = {"resnet110_2048px_bs1": {"value": extra_value, "remat": "scan"}}
    if peak is not None:
        extras["resnet_peak_pixels"] = {
            "peak_trainable_px_per_chip": peak, "img_per_sec_at_peak": 0.06,
        }
    return {
        "metric": "amoebanetd_1024px_bs2_train_tpu",
        "value": headline_value,
        "unit": "images/sec",
        "vs_baseline": None,
        "extras": extras,
    }


def _write_rounds(tmp_path, rounds):
    paths = []
    for i, r in enumerate(rounds, start=1):
        p = tmp_path / f"BENCH_r{i:02d}.json"
        p.write_text(json.dumps(r))
        paths.append(str(p))
    return paths


def test_extract_series_covers_headline_extras_and_peak():
    s = extract_series(_result(7.0, 0.5, peak=4096))
    assert s == {
        "amoebanetd_1024px_bs2_train_tpu": 7.0,
        "resnet110_2048px_bs1": 0.5,
        "resnet_peak_pixels.peak_px": 4096.0,
    }
    # A failed round (parsed value None) contributes nothing.
    assert extract_series({"metric": "m", "value": None}) == {}


def test_extract_series_memory_keys():
    """ISSUE satellite: the headline ``hlo`` block's peak and the
    serving extra's per-bucket predicted peaks become trend series."""
    r = _result(7.0, 0.5)
    r["hlo"] = {"peak_hbm_bytes": 17e9, "inventory": {}}
    r["extras"]["serving_amoebanet3_32px"] = {
        "value": 2000.0,
        "peak_hbm_bytes_by_bucket": {"1": 2.0e6, "32": 2.7e6},
    }
    s = extract_series(r)
    assert s["hlo.peak_hbm_bytes"] == 17e9
    assert s["serving_amoebanet3_32px"] == 2000.0
    assert s["serving_amoebanet3_32px.peak_hbm_bytes[b1]"] == 2.0e6
    assert s["serving_amoebanet3_32px.peak_hbm_bytes[b32]"] == 2.7e6


def test_fleet_recovery_series_trended_and_inverted(tmp_path):
    """ISSUE CI satellite: the fleet_2replica extra's recovery latency
    becomes a trend series with the regression sign inverted — a SLOWER
    death-to-replacement is the regression."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    r = _result(7.0, 0.5)
    r["extras"]["fleet_2replica"] = {
        "value": 350.0, "requeued": 4, "recovery_s": 7.1,
    }
    s = extract_series(r)
    assert s["fleet_2replica"] == 350.0            # rps: higher is better
    assert s["fleet_2replica.recovery_s"] == 7.1   # latency: lower is
    assert lower_is_better("fleet_2replica.recovery_s")
    assert not lower_is_better("fleet_2replica")
    fast, slow = _result(7.0, 0.5), _result(7.0, 0.5)
    fast["extras"]["fleet_2replica"] = {"value": 350.0, "recovery_s": 7.0}
    slow["extras"]["fleet_2replica"] = {"value": 350.0, "recovery_s": 9.0}
    paths = _write_rounds(tmp_path, [_round(1, 0, fast),
                                     _round(2, 0, slow)])
    assert main(paths) == 1  # +29% recovery latency: CI-visible
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(paths, [fast, slow]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["fleet_2replica.recovery_s"]["verdict"] == "regressed"


def test_numerics_series_trended_and_inverted(tmp_path):
    """ISSUE 19 satellite: the numerics extra's detection latency and
    canary-on throughput overhead become trend series with the
    regression sign INVERTED — slower corruption-to-fence detection or
    a grown canary tax is the regression, even when the headline rps
    holds. Rounds without the extra contribute nothing (absent-not-zero
    — a round benched before the sentinel existed is not a 0s detect)."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    r = _result(7.0, 0.5)
    r["extras"]["numerics"] = {
        "value": 340.0, "detect_s": 0.31, "rps_overhead_pct": 1.2,
        "detected": True, "canary_interval_s": 0.2,
    }
    s = extract_series(r)
    assert s["numerics"] == 340.0                  # rps: higher is better
    assert s["numerics.detect_s"] == 0.31
    assert s["numerics.rps_overhead_pct"] == 1.2
    assert lower_is_better("numerics.detect_s")
    assert lower_is_better("numerics.rps_overhead_pct")
    assert not lower_is_better("numerics")

    # Absent-not-zero: a pre-sentinel round has no numerics keys at all.
    old = extract_series(_result(7.0, 0.5))
    assert not any(k.startswith("numerics") for k in old)
    # An undetected corruption run records no detect_s rather than 0.0
    # (a vanishing detection latency must never read as an improvement).
    r2 = _result(7.0, 0.5)
    r2["extras"]["numerics"] = {"value": 340.0, "detected": False,
                                "rps_overhead_pct": 1.0}
    s2 = extract_series(r2)
    assert "numerics.detect_s" not in s2
    assert s2["numerics.rps_overhead_pct"] == 1.0

    # A slower detection across rounds is CI-visible as a regression.
    fast, slow = _result(7.0, 0.5), _result(7.0, 0.5)
    fast["extras"]["numerics"] = {"value": 340.0, "detect_s": 0.3,
                                  "rps_overhead_pct": 1.0}
    slow["extras"]["numerics"] = {"value": 340.0, "detect_s": 0.6,
                                  "rps_overhead_pct": 1.0}
    paths = _write_rounds(tmp_path, [_round(1, 0, fast),
                                     _round(2, 0, slow)])
    assert main(paths) == 1  # 2x detection latency: CI-visible
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(paths, [fast, slow]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["numerics.detect_s"]["verdict"] == "regressed"


def test_incident_series_trended_and_inverted(tmp_path):
    """ISSUE 20 satellite: the incident extra's MTTD (page→open) and
    MTTR (open→close) become trend series with the regression sign
    INVERTED — a slower-opening or slower-closing incident engine is
    the regression, even when the headline rps holds. Rounds without
    the extra contribute nothing, and a drill where the incident never
    opened (or never closed) records no mttd/mttr rather than 0.0
    (absent-not-zero: a vanishing time-to-detect must never read as an
    improvement)."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    r = _result(7.0, 0.5)
    r["extras"]["incident"] = {
        "value": 310.0, "mttd_s": 2.4, "mttr_s": 11.0,
        "incidents_opened": 1, "incidents_closed": 1,
        "blame_correct": True,
    }
    s = extract_series(r)
    assert s["incident"] == 310.0                  # rps: higher is better
    assert s["incident.mttd_s"] == 2.4
    assert s["incident.mttr_s"] == 11.0
    assert lower_is_better("incident.mttd_s")
    assert lower_is_better("incident.mttr_s")
    assert not lower_is_better("incident")

    # Absent-not-zero: a pre-engine round has no incident keys at all.
    old = extract_series(_result(7.0, 0.5))
    assert not any(k.startswith("incident") for k in old)
    # A drill whose incident never closed records no mttr_s.
    r2 = _result(7.0, 0.5)
    r2["extras"]["incident"] = {"value": 310.0, "mttd_s": 2.0,
                                "incidents_opened": 1,
                                "incidents_closed": 0}
    s2 = extract_series(r2)
    assert s2["incident.mttd_s"] == 2.0
    assert "incident.mttr_s" not in s2

    # A slower close across rounds is CI-visible as a regression.
    fast, slow = _result(7.0, 0.5), _result(7.0, 0.5)
    fast["extras"]["incident"] = {"value": 310.0, "mttd_s": 2.0,
                                  "mttr_s": 10.0}
    slow["extras"]["incident"] = {"value": 310.0, "mttd_s": 2.0,
                                  "mttr_s": 25.0}
    paths = _write_rounds(tmp_path, [_round(1, 0, fast),
                                     _round(2, 0, slow)])
    assert main(paths) == 1  # 2.5x MTTR: CI-visible
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(paths, [fast, slow]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["incident.mttr_s"]["verdict"] == "regressed"
    assert by_key["incident.mttd_s"]["verdict"] == "flat"


def test_coldstart_phase_series_trended_and_inverted(tmp_path):
    """ISSUE 18 satellite: the coldstart extra's per-arm per-phase
    recovery decomposition becomes ``{name}.phase_s.{arm}.{phase}``
    trend series with the INVERTED sign — a grown compile (or any
    other) phase is the regression, even when total recovery holds.
    Rounds without the extra contribute nothing (absent-not-zero)."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    def with_coldstart(compile_s):
        r = _result(7.0, 0.5)
        r["extras"]["coldstart"] = {
            "value": 700.0,
            "recovery_s": {"cold": 7.2, "promote": 0.01},
            "phases": {
                "cold": {"spawn": 0.7, "import": 0.3, "construct": 1.0,
                         "compile": compile_s, "warm": 0.1, "ready": 0.1},
                "promote": {"spawn": 0.0, "compile": 0.0, "ready": 0.01},
            },
        }
        return r

    s = extract_series(with_coldstart(5.0))
    assert s["coldstart.phase_s.cold.compile"] == 5.0
    assert s["coldstart.phase_s.cold.spawn"] == 0.7
    assert s["coldstart.phase_s.promote.compile"] == 0.0
    assert s["coldstart.recovery_s.cold"] == 7.2
    assert lower_is_better("coldstart.phase_s.cold.compile")
    assert lower_is_better("coldstart.recovery_s.promote")
    assert not lower_is_better("coldstart")  # the speedup headline

    # compile 5.0 → 7.0 across rounds: CI fails on the phase series.
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_coldstart(5.0)),
        _round(2, 0, with_coldstart(7.0)),
    ])
    assert main(paths) == 1
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(
             zip(paths, [with_coldstart(5.0), with_coldstart(7.0)])
         )],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["coldstart.phase_s.cold.compile"]["verdict"] == "regressed"
    assert by_key["coldstart.phase_s.promote.compile"]["verdict"] == "flat"

    # Absent-not-zero: an old round without the extra never reads as a
    # zero-second cold start.
    old = _result(7.0, 0.5)
    assert not any(".phase_s." in k for k in extract_series(old))


def test_tiled_gigapixel_series_trended_with_correct_signs(tmp_path):
    """ISSUE satellite: the tiled_gigapixel extra trends its capability
    point (peak_px — the largest image one chip served through the tile
    stream) with the NORMAL sign and its fixed-size per-request p99 with
    the INVERTED sign: a shrunk peak or a slower gigapixel request is
    the regression."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    def tiled(peak_px, p99):
        r = _result(7.0, 0.5)
        r["extras"]["tiled_gigapixel"] = {
            "peak_px": peak_px, "image_px": 8192, "tile": 2048,
            "latency_ms": {"p50": p99 / 2, "p99": p99},
        }
        return r

    s = extract_series(tiled(16384, 61000.0))
    assert s["tiled_gigapixel.peak_px"] == 16384.0
    assert s["tiled_gigapixel.latency_p99_ms"] == 61000.0
    assert not lower_is_better("tiled_gigapixel.peak_px")
    assert lower_is_better("tiled_gigapixel.latency_p99_ms")
    # The serving extra's own latency_ms stays UNtrended (its tail is
    # trended as the p99/p50 ratio; absolute latency is box noise) —
    # the extraction is gated on the tiled extra's peak_px shape.
    r = _result(7.0, 0.5)
    r["extras"]["serving_amoebanet3_32px"] = {
        "value": 2000.0, "latency_ms": {"p50": 10.0, "p99": 30.0},
    }
    assert "serving_amoebanet3_32px.latency_p99_ms" not in extract_series(r)
    # Shrunk capability regresses...
    good, shrunk = tiled(16384, 61000.0), tiled(8192, 61000.0)
    paths = _write_rounds(tmp_path, [_round(1, 0, good),
                                     _round(2, 0, shrunk)])
    assert main(paths) == 1
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(paths, [good, shrunk]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["tiled_gigapixel.peak_px"]["verdict"] == "regressed"
    # ...and so does a slower fixed-size request at a held peak.
    slow = tiled(16384, 75000.0)
    paths = _write_rounds(tmp_path, [_round(1, 0, good),
                                     _round(2, 0, slow)])
    assert main(paths) == 1
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(paths, [good, slow]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["tiled_gigapixel.latency_p99_ms"]["verdict"] == "regressed"


def test_fleet_recovery_by_domain_trended_and_inverted(tmp_path):
    """ISSUE CI satellite (HA front door): the fleet extra now records
    one recovery latency PER FAILURE DOMAIN ({"replica": ..., "router":
    ...} — warm-pool promotion vs router journal recovery); both become
    trend series with the regression sign inverted."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    r = _result(7.0, 0.5)
    r["extras"]["fleet_2replica"] = {
        "value": 350.0, "requeued": 4,
        "recovery_s": {"replica": 0.4, "router": 1.1},
        "journal_replays": {"deduped": 3, "redispatched": 1},
    }
    s = extract_series(r)
    assert s["fleet_2replica.recovery_s.replica"] == 0.4
    assert s["fleet_2replica.recovery_s.router"] == 1.1
    assert lower_is_better("fleet_2replica.recovery_s.replica")
    assert lower_is_better("fleet_2replica.recovery_s.router")
    # A None (unmeasured) domain contributes nothing rather than 0.0.
    r["extras"]["fleet_2replica"]["recovery_s"] = {
        "replica": 0.4, "router": None,
    }
    s = extract_series(r)
    assert "fleet_2replica.recovery_s.router" not in s
    # Regression drill: promotion recovery slipping back toward
    # cold-spawn time is CI-visible.
    fast, slow = _result(7.0, 0.5), _result(7.0, 0.5)
    fast["extras"]["fleet_2replica"] = {
        "value": 350.0, "recovery_s": {"replica": 0.4, "router": 1.0},
    }
    slow["extras"]["fleet_2replica"] = {
        "value": 350.0, "recovery_s": {"replica": 6.8, "router": 1.0},
    }
    paths = _write_rounds(tmp_path, [_round(1, 0, fast),
                                     _round(2, 0, slow)])
    assert main(paths) == 1
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(paths, [fast, slow]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["fleet_2replica.recovery_s.replica"]["verdict"] \
        == "regressed"
    assert by_key["fleet_2replica.recovery_s.router"]["verdict"] == "flat"


def test_tail_ratio_trended_and_inverted(tmp_path):
    """ISSUE 10 CI satellite: the serving extra's tail summary
    (p99/p50 ratio) becomes a trend series with the regression sign
    inverted — a GROWING tail fails CI even when mean throughput holds."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    def with_tail(ratio):
        r = _result(7.0, 0.5)
        r["extras"]["serving_amoebanet3_32px"] = {
            "value": 2000.0,
            "tail": {"p99_p50_ratio": ratio, "samples": 3,
                     "threshold_ms": 45.0},
        }
        return r

    s = extract_series(with_tail(1.8))
    assert s["serving_amoebanet3_32px"] == 2000.0
    assert s["serving_amoebanet3_32px.tail_p99_p50_ratio"] == 1.8
    assert lower_is_better("serving_amoebanet3_32px.tail_p99_p50_ratio")
    assert not lower_is_better("serving_amoebanet3_32px")

    # Same throughput, fatter tail: CI-visible regression.
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_tail(1.8)), _round(2, 0, with_tail(2.4)),
    ])
    assert main(paths) == 1
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(
             paths, [with_tail(1.8), with_tail(2.4)]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key[
        "serving_amoebanet3_32px.tail_p99_p50_ratio"
    ]["verdict"] == "regressed"
    # A shrinking tail is the improvement direction.
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_tail(2.4)), _round(2, 0, with_tail(1.8)),
    ])
    assert main(paths) == 0


def test_sched_ab_series_trended_and_inverted(tmp_path):
    """ISSUE 11 CI satellite: the serving extra's scheduler A/B embeds
    per-arm tight-class p99 under the fixed mixed-class load; bench-
    history trends it with the INVERTED sign (a growing tight-class p99
    fails CI) and the per-arm aggregate rps with the normal sign."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    def with_ab(edf_p99, fifo_p99=60.0, edf_rps=1700.0):
        r = _result(7.0, 0.5)
        r["extras"]["serving_amoebanet3_32px"] = {
            "value": 2000.0,
            "sched_ab": {
                "classes": "tight=250ms:99@10s,bulk=2.5s:99@60s",
                "arms": {
                    "edf": {"tight_p99_ms": edf_p99, "bulk_p99_ms": 70.0,
                            "rps": edf_rps, "deadline_misses": 0},
                    "fifo": {"tight_p99_ms": fifo_p99, "bulk_p99_ms": 55.0,
                             "rps": 1650.0, "deadline_misses": 0},
                },
                "tight_p99_improved": edf_p99 < fifo_p99,
            },
        }
        return r

    s = extract_series(with_ab(40.0))
    assert s["serving_amoebanet3_32px.sched_tight_p99_ms[edf]"] == 40.0
    assert s["serving_amoebanet3_32px.sched_tight_p99_ms[fifo]"] == 60.0
    assert s["serving_amoebanet3_32px.sched_rps[edf]"] == 1700.0
    assert lower_is_better(
        "serving_amoebanet3_32px.sched_tight_p99_ms[edf]"
    )
    assert not lower_is_better("serving_amoebanet3_32px.sched_rps[edf]")

    # Growing tight-class p99 on the EDF arm: CI-visible regression.
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_ab(40.0)), _round(2, 0, with_ab(55.0)),
    ])
    assert main(paths) == 1
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(
             paths, [with_ab(40.0), with_ab(55.0)]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key[
        "serving_amoebanet3_32px.sched_tight_p99_ms[edf]"
    ]["verdict"] == "regressed"
    # Shrinking tight p99 is the improvement; a dropped EDF rps is the
    # throughput regression (normal sign).
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_ab(55.0)), _round(2, 0, with_ab(40.0)),
    ])
    assert main(paths) == 0
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_ab(40.0, edf_rps=1700.0)),
        _round(2, 0, with_ab(40.0, edf_rps=1400.0)),
    ])
    assert main(paths) == 1


def test_peak_hbm_series_regresses_on_growth(tmp_path):
    """ISSUE satellite: memory series get the SAME verdict treatment as
    throughput — tolerance band, compare against the last round that
    measured — but with the sign inverted: a grown footprint regresses
    (CI exit 1), a shrunk one improves."""
    grown, shrunk = _result(7.0, 0.5), _result(7.0, 0.5)
    base = _result(7.0, 0.5)
    base["hlo"] = {"peak_hbm_bytes": 10e9}
    grown["hlo"] = {"peak_hbm_bytes": 12e9}     # +20% footprint
    shrunk["hlo"] = {"peak_hbm_bytes": 8e9}     # -20% footprint
    paths = _write_rounds(tmp_path, [_round(1, 0, base),
                                     _round(2, 0, grown)])
    assert main(paths) == 1  # growth is the regression
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(paths, [base, grown]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["hlo.peak_hbm_bytes"]["verdict"] == "regressed"
    # Throughput keys keep the normal direction in the same run.
    assert by_key["amoebanetd_1024px_bs2_train_tpu"]["verdict"] == "flat"

    cmp = compare(
        [{"path": "a", "n": 1, "rc": 0, "result": base},
         {"path": "b", "n": 2, "rc": 0, "result": shrunk}],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["hlo.peak_hbm_bytes"]["verdict"] == "improved"
    assert cmp["ok"] is True
    # Inside the band: flat, either direction.
    near = _result(7.0, 0.5)
    near["hlo"] = {"peak_hbm_bytes": 10.2e9}
    cmp = compare(
        [{"path": "a", "n": 1, "rc": 0, "result": base},
         {"path": "b", "n": 2, "rc": 0, "result": near}],
        tolerance=0.05, strict=False,
    )
    assert {k["key"]: k for k in cmp["keys"]}[
        "hlo.peak_hbm_bytes"
    ]["verdict"] == "flat"


def test_trend_improvement_exits_zero(tmp_path, capsys):
    paths = _write_rounds(tmp_path, [
        _round(1, 1, None),                      # failed round: no data
        _round(2, 0, _result(5.0, 0.50, peak=2048)),
        _round(3, 0, _result(7.0, 0.51, peak=4096)),
    ])
    rc = main(paths + ["--json", str(tmp_path / "cmp.json")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "improved" in out and "flat" in out
    assert "0 regression(s)" in out
    cmp = json.loads((tmp_path / "cmp.json").read_text())
    assert cmp["ok"] is True
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["amoebanetd_1024px_bs2_train_tpu"]["verdict"] == "improved"
    assert by_key["amoebanetd_1024px_bs2_train_tpu"]["values"] == [
        None, 5.0, 7.0,
    ]
    assert by_key["resnet110_2048px_bs1"]["verdict"] == "flat"  # +2% < 5%


def test_regression_beyond_tolerance_exits_nonzero(tmp_path, capsys):
    paths = _write_rounds(tmp_path, [
        _round(1, 0, _result(7.0, 0.50)),
        _round(2, 0, _result(6.0, 0.50)),        # -14% headline
    ])
    rc = main(paths)
    out = capsys.readouterr().out
    assert rc == 1
    assert "regressed" in out
    assert "1 regression(s)" in out
    # Inside a wider band the same delta passes.
    assert main(paths + ["--tolerance", "0.2"]) == 0


def test_regression_compares_against_last_round_that_measured(tmp_path):
    """A round that skipped a key (budget, failure) must not reset the
    baseline — the comparison reaches back to the last real measurement."""
    paths = _write_rounds(tmp_path, [
        _round(1, 0, _result(7.0, 0.50)),
        _round(2, 1, None),                      # nothing measured
        _round(3, 0, _result(6.0, 0.50)),        # vs r1, not vs nothing
    ])
    rounds = [json.load(open(p)) for p in paths]
    cmp = compare(
        [{"path": p, "n": r["n"], "rc": r["rc"], "result": r["parsed"]}
         for p, r in zip(paths, rounds)],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    head = by_key["amoebanetd_1024px_bs2_train_tpu"]
    assert head["previous"] == 7.0
    assert head["verdict"] == "regressed"
    assert cmp["ok"] is False
    render_table(cmp)  # renders with a None-valued middle round


def test_key_gone_is_reported_but_fails_only_in_strict(tmp_path):
    paths = _write_rounds(tmp_path, [
        _round(1, 0, _result(7.0, 0.50, peak=2048)),
        _round(2, 0, _result(7.0, 0.50)),        # peak walk skipped
    ])
    assert main(list(paths)) == 0
    assert main(list(paths) + ["--strict"]) == 1


def test_latest_round_without_result_fails(tmp_path):
    paths = _write_rounds(tmp_path, [
        _round(1, 0, _result(7.0, 0.50)),
        _round(2, 1, None),
    ])
    assert main(paths) == 1


def test_cli_dispatch_through_analyze(tmp_path, capsys):
    """ISSUE satellite (CLI smoke): the subcommand routes through the
    ``python -m mpi4dl_tpu.analyze`` front door without touching the
    lint path's jax setup."""
    from mpi4dl_tpu.analysis.cli import main as cli_main

    paths = _write_rounds(tmp_path, [
        _round(1, 0, _result(5.0, 0.50)),
        _round(2, 0, _result(7.0, 0.52)),
    ])
    rc = cli_main(["bench-history", *paths, "--tolerance", "0.1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "amoebanetd_1024px_bs2_train_tpu" in out


def test_runs_on_the_committed_round_files(capsys):
    """The real BENCH_r*.json history must parse and render end-to-end;
    the verdict is whatever the trajectory says (this test pins the
    reader, not the repo's perf)."""
    files = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    if not files:
        pytest.skip("no committed bench rounds in this checkout")
    rc = main(files)
    out = capsys.readouterr().out
    assert rc in (0, 1)
    assert "regression(s)" in out
    # Round labels come from the files' own "n" fields (r01 and r02 left
    # the tree in PR 24; the oldest record kept is whatever sorts first).
    with open(files[0]) as f:
        first = json.load(f)["n"]
    assert f"r{first:02d}" in out or "#0" in out

def test_overlap_series_trended_with_correct_signs(tmp_path):
    """ISSUE satellite: the sp2x2_overlap extra's per-arm measured
    overlap ratio and SP step time become trend series — a FALLING
    overlap ratio fails CI (normal higher-is-better direction), while
    the step time carries the inverted sign (growing fails), mirroring
    recovery_s/peak_hbm_bytes. The headline attribution's ratio is
    trended too."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    def with_overlap(mono_ratio, dec_ratio, dec_step):
        r = _result(7.0, 0.5)
        r["attribution"] = {
            "overlap": {"overlap_ratio": 0.61, "verdict": "overlapped"},
            "conv_impl": "monolithic",
        }
        r["extras"]["sp2x2_overlap"] = {"arms": {
            "monolithic": {"trace_overlap_ratio": mono_ratio,
                           "step_time_s": 0.9},
            "decomposed": {"trace_overlap_ratio": dec_ratio,
                           "step_time_s": dec_step},
        }}
        return r

    s = extract_series(with_overlap(0.60, 0.64, 1.4))
    assert s["attribution.trace_overlap_ratio"] == 0.61
    assert s["sp2x2_overlap.trace_overlap_ratio[monolithic]"] == 0.60
    assert s["sp2x2_overlap.trace_overlap_ratio[decomposed]"] == 0.64
    assert s["sp2x2_overlap.step_time_s[decomposed]"] == 1.4
    assert not lower_is_better("sp2x2_overlap.trace_overlap_ratio[decomposed]")
    assert not lower_is_better("attribution.trace_overlap_ratio")
    assert lower_is_better("sp2x2_overlap.step_time_s[decomposed]")

    # A falling decomposed overlap ratio is a CI-visible regression.
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_overlap(0.60, 0.64, 1.4)),
        _round(2, 0, with_overlap(0.60, 0.50, 1.4)),   # ratio fell 22%
    ])
    assert main(paths) == 1
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0,
          "result": r}
         for i, (p, r) in enumerate(zip(paths, [
             with_overlap(0.60, 0.64, 1.4), with_overlap(0.60, 0.50, 1.4),
         ]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key[
        "sp2x2_overlap.trace_overlap_ratio[decomposed]"
    ]["verdict"] == "regressed"

    # A grown SP step time regresses; a grown ratio improves.
    cmp = compare(
        [{"path": "a", "n": 1, "rc": 0,
          "result": with_overlap(0.60, 0.64, 1.4)},
         {"path": "b", "n": 2, "rc": 0,
          "result": with_overlap(0.60, 0.70, 1.8)}],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["sp2x2_overlap.step_time_s[decomposed]"][
        "verdict"] == "regressed"
    assert by_key["sp2x2_overlap.trace_overlap_ratio[decomposed]"][
        "verdict"] == "improved"
    assert cmp["ok"] is False


def test_pipeline_series_trended_with_correct_signs(tmp_path):
    """ISSUE 14 CI satellite: the pipeline extra's per-arm measured
    bubble fraction trends with the INVERTED sign (a grown bubble fails
    CI) and the per-arm img/s with the normal sign; rounds from before
    the extra existed contribute nothing (absent-not-zero)."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    def with_pipeline(fb_bubble, fb_ips):
        r = _result(7.0, 0.5)
        r["extras"]["pipeline"] = {"arms": {
            "gpipe": {"bubble_fraction": 0.2, "img_per_s": 5.6,
                      "analytic_bubble_fraction": 0.2},
            "1f1b": {"bubble_fraction": fb_bubble, "img_per_s": fb_ips,
                     "analytic_bubble_fraction": 0.1429},
        }, "bubble_improved": fb_bubble < 0.2}
        return r

    s = extract_series(with_pipeline(0.143, 4.8))
    assert s["pipeline.bubble_fraction[gpipe]"] == 0.2
    assert s["pipeline.bubble_fraction[1f1b]"] == 0.143
    assert s["pipeline.img_per_s[1f1b]"] == 4.8
    assert lower_is_better("pipeline.bubble_fraction[1f1b]")
    assert not lower_is_better("pipeline.img_per_s[1f1b]")

    # Absent-not-zero: an old round without the extra yields no pipeline
    # keys, and the comparison reaches past it to the last measurement.
    old = _result(7.0, 0.5)
    assert not any(k.startswith("pipeline.") for k in extract_series(old))
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_pipeline(0.143, 4.8)),
        _round(2, 0, old),
        _round(3, 0, with_pipeline(0.143, 4.8)),
    ])
    assert main(paths) == 0

    # A grown 1f1b bubble is a CI-visible regression even at flat img/s.
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_pipeline(0.143, 4.8)),
        _round(2, 0, with_pipeline(0.19, 4.8)),
    ])
    assert main(paths) == 1
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(
             paths, [with_pipeline(0.143, 4.8), with_pipeline(0.19, 4.8)]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["pipeline.bubble_fraction[1f1b]"]["verdict"] == "regressed"
    # A dropped img/s is the throughput regression (normal sign).
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_pipeline(0.143, 4.8)),
        _round(2, 0, with_pipeline(0.143, 3.9)),
    ])
    assert main(paths) == 1
    # A shrunk bubble is the improvement direction.
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_pipeline(0.19, 4.8)),
        _round(2, 0, with_pipeline(0.143, 4.8)),
    ])
    assert main(paths) == 0


def test_serving_sharded_series_trended_with_correct_signs(tmp_path):
    """ISSUE CI satellite: the serving_sharded extra's per-arm measured
    overlap ratio trends with the normal sign (falling fails), the
    per-arm per-request p99 latency with the INVERTED sign (growing
    fails), and the per-arm serving throughput with the normal sign."""
    from mpi4dl_tpu.analysis.bench_history import compare, lower_is_better

    def with_sharded(dec_ratio, dec_p99, dec_rps):
        r = _result(7.0, 0.5)
        r["extras"]["serving_sharded"] = {"arms": {
            "monolithic": {
                "trace_overlap_ratio": 0.27,
                "latency_ms": {"p50": 12.0, "p99": 26.0},
                "throughput_rps": 300.0,
            },
            "decomposed": {
                "trace_overlap_ratio": dec_ratio,
                "latency_ms": {"p50": 13.0, "p99": dec_p99},
                "throughput_rps": dec_rps,
            },
        }}
        return r

    s = extract_series(with_sharded(0.58, 24.0, 295.0))
    assert s["serving_sharded.trace_overlap_ratio[decomposed]"] == 0.58
    assert s["serving_sharded.latency_p99_ms[decomposed]"] == 24.0
    assert s["serving_sharded.rps[decomposed]"] == 295.0
    assert s["serving_sharded.latency_p99_ms[monolithic]"] == 26.0
    assert lower_is_better("serving_sharded.latency_p99_ms[decomposed]")
    assert not lower_is_better(
        "serving_sharded.trace_overlap_ratio[decomposed]"
    )
    assert not lower_is_better("serving_sharded.rps[decomposed]")

    # Growing p99 regresses (inverted); falling ratio regresses (normal);
    # growing rps improves.
    cmp = compare(
        [{"path": "a", "n": 1, "rc": 0,
          "result": with_sharded(0.58, 24.0, 295.0)},
         {"path": "b", "n": 2, "rc": 0,
          "result": with_sharded(0.40, 32.0, 340.0)}],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["serving_sharded.latency_p99_ms[decomposed]"][
        "verdict"] == "regressed"
    assert by_key["serving_sharded.trace_overlap_ratio[decomposed]"][
        "verdict"] == "regressed"
    assert by_key["serving_sharded.rps[decomposed]"]["verdict"] == "improved"
    assert cmp["ok"] is False

    # CI exit: a round whose sharded p99 grew past tolerance fails.
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_sharded(0.58, 24.0, 295.0)),
        _round(2, 0, with_sharded(0.58, 30.0, 295.0)),  # p99 +25%
    ])
    assert main(paths) == 1


def test_costmodel_series_trended_with_correct_signs(tmp_path):
    """ISSUE 16 satellite: bench lines embed the static cost model's
    predictions (hlo.costmodel per interconnect) and the predicted-vs-
    measured overlap drift (attribution.costmodel). bench-history trends
    the predicted overlap ceiling with the NORMAL sign (a falling ceiling
    means the compiled schedule lost hideability), predicted comms
    seconds with the INVERTED sign (more bytes / lost async pairs), and
    drift with the INVERTED sign — growing model divergence fails CI."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    def with_costmodel(ici_ratio, ici_comms, drift):
        r = _result(7.0, 0.5)
        r["hlo"] = {
            "peak_hbm_bytes": 10e9,
            "costmodel": {
                "cpu": {"comms_s": 3.1e-4, "exposed_s": 3.1e-4,
                        "predicted_overlap_ratio": 0.0,
                        "overlap_claim": False},
                "ici": {"comms_s": ici_comms, "exposed_s": 0.0,
                        "predicted_overlap_ratio": ici_ratio,
                        "overlap_claim": True},
            },
        }
        r["attribution"] = {
            "overlap": {"overlap_ratio": 0.61, "verdict": "overlapped"},
            "costmodel": {
                "interconnect": "cpu",
                "predicted_overlap_ratio": ici_ratio,
                "overlap_claim": drift is not None,
                "overlap_drift": drift,
                "crosscheck": [],
            },
        }
        return r

    s = extract_series(with_costmodel(0.85, 2.8e-5, 0.10))
    assert s["costmodel.predicted_overlap_ratio[ici]"] == 0.85
    assert s["costmodel.predicted_overlap_ratio[cpu]"] == 0.0
    assert s["costmodel.predicted_comms_s[ici]"] == 2.8e-5
    assert s["costmodel.overlap_drift"] == 0.10
    assert not lower_is_better("costmodel.predicted_overlap_ratio[ici]")
    assert lower_is_better("costmodel.predicted_comms_s[ici]")
    assert lower_is_better("costmodel.overlap_drift")
    # CPU-mesh rounds record null drift (no overlap claim): absent-not-
    # zero, so the series starts with the first round that claims.
    assert "costmodel.overlap_drift" not in extract_series(
        with_costmodel(0.85, 2.8e-5, None)
    )

    # Growing drift is the CI-visible regression even at flat headline.
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_costmodel(0.85, 2.8e-5, 0.05)),
        _round(2, 0, with_costmodel(0.85, 2.8e-5, 0.12)),
    ])
    assert main(paths) == 1
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(paths, [
             with_costmodel(0.85, 2.8e-5, 0.05),
             with_costmodel(0.85, 2.8e-5, 0.12),
         ]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["costmodel.overlap_drift"]["verdict"] == "regressed"
    # A falling predicted ceiling regresses (normal sign); grown
    # predicted comms time regresses (inverted sign).
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_costmodel(0.85, 2.8e-5, 0.05)),
        _round(2, 0, with_costmodel(0.60, 2.8e-5, 0.05)),
    ])
    assert main(paths) == 1
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_costmodel(0.85, 2.8e-5, 0.05)),
        _round(2, 0, with_costmodel(0.85, 6.0e-5, 0.05)),
    ])
    assert main(paths) == 1
    # Shrinking drift is the improvement direction.
    paths = _write_rounds(tmp_path, [
        _round(1, 0, with_costmodel(0.85, 2.8e-5, 0.12)),
        _round(2, 0, with_costmodel(0.85, 2.8e-5, 0.05)),
    ])
    assert main(paths) == 0


def test_multitenant_series_trended_with_correct_signs(tmp_path):
    """ISSUE 17 satellite: the multitenant extra trends the victim's
    flood/solo p99 ratio with the INVERTED sign (a grown ratio means
    tenant isolation regressed) and Jain's fairness index with the
    NORMAL sign (falling fairness regresses); the tenancy-on rps rides
    the generic ``value`` path."""
    from mpi4dl_tpu.analysis.bench_history import lower_is_better

    def multitenant(rps, ratio, jain):
        r = _result(7.0, 0.5)
        r["extras"]["multitenant"] = {
            "value": rps, "overhead_pct": 0.8,
            "victim_p99_ratio": ratio, "fairness_index": jain,
            "served_by_tenant": {"bully": 200, "victim": 20},
        }
        return r

    s = extract_series(multitenant(300.0, 1.12, 0.97))
    assert s["multitenant"] == 300.0
    assert s["multitenant.victim_p99_ratio"] == 1.12
    assert s["multitenant.fairness_index"] == 0.97
    assert lower_is_better("multitenant.victim_p99_ratio")
    assert not lower_is_better("multitenant.fairness_index")
    assert not lower_is_better("multitenant")
    # A grown victim ratio regresses (isolation lost under the flood)...
    good, worse = multitenant(300.0, 1.1, 0.97), multitenant(300.0, 1.4, 0.97)
    paths = _write_rounds(tmp_path, [_round(1, 0, good),
                                     _round(2, 0, worse)])
    assert main(paths) == 1
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(paths, [good, worse]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["multitenant.victim_p99_ratio"]["verdict"] == "regressed"
    # ...and so does falling fairness at a held ratio.
    unfair = multitenant(300.0, 1.1, 0.72)
    paths = _write_rounds(tmp_path, [_round(1, 0, good),
                                     _round(2, 0, unfair)])
    assert main(paths) == 1
    cmp = compare(
        [{"path": p, "n": i + 1, "rc": 0, "result": r}
         for i, (p, r) in enumerate(zip(paths, [good, unfair]))],
        tolerance=0.05, strict=False,
    )
    by_key = {k["key"]: k for k in cmp["keys"]}
    assert by_key["multitenant.fairness_index"]["verdict"] == "regressed"
    # An improving (shrinking) ratio exits clean.
    better = multitenant(300.0, 1.02, 0.99)
    paths = _write_rounds(tmp_path, [_round(1, 0, good),
                                     _round(2, 0, better)])
    assert main(paths) == 0
