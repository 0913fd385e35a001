"""The trace reduction on hand-made events and on a sample recorded from a
TPU v5e trace of the saturated cell."""
import json

import pytest

import devtrace
from conftest import HERE


def sweep_union(intervals, lo, hi):
    """Covered length by a sweep over sorted endpoints (independent of
    ``devtrace.union``)."""
    edges = sorted([(max(s, lo), 1) for s, e in intervals if e > s]
                   + [(min(e, hi), -1) for s, e in intervals if e > s])
    covered, depth, last = 0, 0, None
    for x, d in edges:
        x = min(max(x, lo), hi)
        if depth > 0:
            covered += x - last
        depth += d
        last = x
    return covered


def test_union_and_shares_by_hand():
    ev = {"device": {"/device:TPU:0": [("a", 10, 20), ("b", 15, 30),
                                       ("a", 50, 60), ("c", 95, 120)]},
          "host": [(devtrace.WINDOW, 0, 100), ("bench.dispatch", 0, 40),
                   ("bench.sync", 60, 100)]}
    r = devtrace.reduce(ev)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(35e-9)       # 10-30, 50-60, 95-100
    assert r["idle_pct"] == pytest.approx(65.0)
    assert dict(r["device_ops"]) == pytest.approx(
        {"a": 20e-9, "b": 15e-9, "c": 5e-9})
    # gaps: 0-10 and 30-50 (midpoints 5 and 40: dispatch, then none),
    # 60-95 (sync)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.dispatch": 10e-9, "host": 20e-9, "bench.sync": 35e-9})


def test_control_flow_ops_are_not_counted_twice():
    ev = {"device": {"/device:TPU:0": [("while", 0, 50), ("a", 5, 20),
                                       ("b", 25, 45), ("c", 60, 70)]},
          "host": [(devtrace.WINDOW, 0, 100)]}
    r = devtrace.reduce(ev)
    assert r["busy_s"] == pytest.approx(60e-9)
    assert dict(r["device_ops"]) == pytest.approx(
        {"a": 15e-9, "b": 20e-9, "c": 10e-9})


def test_no_window_or_no_device_reads_nothing():
    assert devtrace.reduce({"device": {}, "host": [
        (devtrace.WINDOW, 0, 10)]}) is None
    assert devtrace.reduce({"device": {"/device:TPU:0": [("a", 0, 5)]},
                            "host": []}) is None


def test_recorded_sample():
    ev = json.loads((HERE / "data" / "trace_sample.json").read_text())
    r = devtrace.reduce(ev)
    lo, hi = [(s, e) for n, s, e in ev["host"] if n == devtrace.WINDOW][-1]
    (evs,) = ev["device"].values()
    want = sweep_union([(s, e) for _, s, e in evs], lo, hi)
    assert r["busy_s"] == pytest.approx(want / 1e9, rel=1e-12)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert 0.0 <= r["idle_pct"] < 100.0
    assert sum(v for _, v in r["idle_gaps"]) <= \
        r["window_s"] - r["busy_s"] + 1e-12
    assert len(r["device_ops"]) <= devtrace.TOP


def test_peaks_known_and_unknown_device():
    assert devtrace.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        devtrace.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        devtrace.peaks("source")
