"""The trace reduction on small traces: a hand-made one whose numbers are
worked out below, and one recorded on a TPU v5e (a slice of a real
round's trace, ``data/trace_v5e.json``)."""
import json
import os

import pytest

import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6  # ns


def _hand():
    # window 0-100 ms; device 0: ops 10-30 (fusion), 20-40 (a Pallas kernel),
    # 60-70 (all-reduce) and 65-68 (fusion under it), 95-105 (clipped);
    # device 1: one op 0-50
    dev = [[0, "fusion.1", "", 10 * MS, 20 * MS],
           [0, "row_trimmed_stats_multilevel.3", "tpu_custom_call", 20 * MS,
            20 * MS],
           [0, "all-reduce.7", "", 60 * MS, 10 * MS],
           [0, "fusion.2", "", 65 * MS, 3 * MS],
           [0, "fusion.1", "", 95 * MS, 10 * MS],
           [1, "fusion.1", "", 0, 50 * MS]]
    host = [["bench.window", 0, 100 * MS],
            ["bench.dispatch", 0, 8 * MS],
            ["bench.wait", 40 * MS, 55 * MS]]
    return {"device": dev, "host": host}


def test_busy_and_idle():
    r = _hand()
    # device 0 busy: 10-40, 60-70, 95-100 = 45 ms; device 1: 50 ms
    assert trace.busy_ns(r, 0) == pytest.approx(45 * MS)
    assert trace.busy_s(r) == pytest.approx(0.0475)
    assert trace.window_s(r) == pytest.approx(0.1)
    assert trace.idle_share(r) == pytest.approx(1 - 0.475)


def test_kernel_time_by_name():
    r = _hand()
    assert trace.kernel_s(r, ["row_trimmed_stats_multilevel"]) == pytest.approx(
        0.020 / 2)
    assert trace.kernel_s(r, ["fusion"]) is None


def test_exposed_collective():
    r = _hand()
    # all-reduce 60-70 with fusion 65-68 under it: 7 ms exposed
    assert trace.collective_exposed_s(r, 0) == pytest.approx(0.007)
    assert trace.collective_exposed_s(r, 1) is None


def test_breakdown():
    r = _hand()
    top = dict(trace.top_ops(r))
    assert top["fusion"] == pytest.approx((20 + 3 + 5 + 50) / 1e3 / 2)
    gaps = trace.idle_gaps(r, 0)
    # gaps on device 0: 0-10 (dispatch open at 5), 40-60 (wait), 70-95
    # (wait open at 82.5)
    assert gaps[0] == ["bench.wait", pytest.approx(0.025)]
    assert gaps[1] == ["bench.wait", pytest.approx(0.020)]
    assert gaps[2] == ["bench.dispatch", pytest.approx(0.010)]


def test_union():
    assert trace.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]


RECORDED = os.path.join(DATA, "trace_v5e.json")


def test_recorded_v5e_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    want = rec.pop("expect")
    # "expect" was worked out on a 10 ns timeline of the same events, so
    # each number is good to 10 ns a boundary
    assert trace.busy_s(rec) == pytest.approx(want["busy_s"], abs=1e-5)
    assert trace.window_s(rec) == pytest.approx(want["window_s"], rel=1e-12)
    q = trace.kernel_s(rec, ["row_trimmed_stats",
                             "row_trimmed_stats_multilevel"])
    assert q == pytest.approx(want["quantile_s"], abs=1e-7)
    assert 0 < q < trace.busy_s(rec) <= trace.window_s(rec)
