"""The phase reduction on small traces: a hand-made one whose numbers are
worked out below, two slices recorded on a TPU v5e (``trace_v5e.json``
without scopes, ``trace_v5e_scoped.json`` in the profiler's own JSON
form, scoped), and a host trace taken here on the CPU."""
import gzip
import json
import os

import pytest

import phases
import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6  # ns
R = "jit(_round)/"


def _hand():
    # window 0-100 ms.  device 0: unflatten 0-10, train 10-40 (a while and
    # a dot inside it), flatten 40-43, an unscoped copy 43-44 between two
    # flatten ops (so flatten's), flatten 44-45, quantile kernel 50-70,
    # accumulate kernel 72-80, merge 80-84, an unscoped copy 84-86 between
    # merge and unflatten (so unscoped), unflatten 95-105 (clipped);
    # device 1: train 0-50.  Device 0 idles 45-50, 70-72, 86-95.
    ops = [(0, "fusion.1", "", 0, 10, "fedfa.unflatten/slice"),
           (0, "while.1", "", 10, 30, "fedfa.train/while"),
           (0, "dot.1", "", 12, 5, "fedfa.train/while/body/dot_general"),
           (0, "fusion.2", "", 40, 3, "fedfa.flatten/concatenate"),
           (0, "copy.9", "", 43, 1, None),
           (0, "fusion.3", "", 44, 1, "fedfa.flatten/concatenate"),
           (0, "row_trimmed_stats_multilevel.3", "tpu_custom_call", 50, 20,
            "fedfa.quantile/pallas_call"),
           (0, "accumulate.4", "tpu_custom_call", 72, 8,
            "fedfa.accumulate/pallas_call"),
           (0, "fusion.5", "", 80, 4, "fedfa.merge/div"),
           (0, "copy.6", "", 84, 2, None),
           (0, "fusion.7", "", 95, 10, "fedfa.unflatten/slice"),
           (1, "fusion.1", "", 0, 50, "fedfa.train/add")]
    dev = [[d, n, t, s * MS, dur * MS] for d, n, t, s, dur, _ in ops]
    scope = ["" if p is None else R + p for *_, p in ops]
    # the round that dispatches at 44 ms, and one cut by the window's end;
    # bench.dispatch is listed after the spans it holds
    host = [["bench.window", 0, 100 * MS],
            ["fedfa.round", 45 * MS, 14 * MS],
            ["fedfa.runtimes", 46 * MS, 3 * MS],
            ["fedfa.program", 49 * MS, 9 * MS],
            ["bench.dispatch", 44 * MS, 16 * MS],
            ["bench.wait", 60 * MS, 38 * MS],
            ["fedfa.round", 98 * MS, 5 * MS],
            ["fedfa.program", 99 * MS, 4 * MS]]
    return {"device": dev, "scope": scope, "host": host}


def test_phase_of():
    assert phases.phase_of(R + "fedfa.train/while/body/dot") == "fedfa.train"
    assert phases.phase_of("jit(f)/fedfa.a/fedfa.b/x") == "fedfa.b"
    assert phases.phase_of(R + "mul") == phases.UNSCOPED
    assert phases.phase_of("") == phases.UNSCOPED


def test_unscoped_op_between_two_of_one_phase_joins_it():
    r = _hand()
    lab = phases.labels(r)
    assert lab[4] == "fedfa.flatten"          # copy.9, between flatten ops
    assert lab[9] == phases.UNSCOPED          # copy.6, merge | unflatten
    assert lab[2] == "fedfa.train"            # its own scope


def test_phase_seconds():
    r = _hand()
    # train: device 0 10-40 (the dot lies inside the while), device 1 0-50
    assert phases.phase_s(r, ["fedfa.train"]) == pytest.approx(0.040)
    # unflatten 0-10 and 95-100 on device 0 only, and flatten 40-45
    assert phases.phase_s(r, ["fedfa.unflatten", "fedfa.flatten"]) == \
        pytest.approx(0.010)
    assert phases.phase_s(r, ["fedfa.graft"]) is None
    got = phases.device_phases(r)
    want = {"fedfa.train": 0.040, "fedfa.unflatten": 0.0075,
            "fedfa.quantile": 0.010, "fedfa.accumulate": 0.004,
            "fedfa.flatten": 0.0025, "fedfa.merge": 0.002,
            "unscoped": 0.001}
    assert got == pytest.approx(want)
    assert list(got)[0] == "fedfa.train"
    # each kernel runs inside its phase
    assert phases.phase_s(r, ["fedfa.quantile"]) >= trace.kernel_s(
        r, ["row_trimmed_stats_multilevel"])


def test_span_self_seconds():
    r = _hand()
    # fedfa.round 45-59 less fedfa.program 49-58, and 98-100 (clipped)
    # less 99-100
    assert phases.span_self_s(r, "fedfa.round", ["fedfa.program"]) == \
        pytest.approx(0.006)
    assert phases.span_self_s(r, "fedfa.round") == pytest.approx(0.016)
    assert phases.span_self_s(r, "fedfa.prepare") is None
    per = phases.per_round(r, 2, {"round_traces": 4}, {"round_traces": 4})
    assert per["driver_host_ms"] == pytest.approx(3.0)
    assert per["train_ms"] == pytest.approx(20.0)
    assert per["graft_ms"] is None
    assert per["window_retraces"] == 0


def test_idle_gaps_named_by_innermost_span():
    r = _hand()
    # 86-95 under bench.wait; 45-50 (midpoint 47.5) under bench.dispatch,
    # fedfa.round and fedfa.runtimes: the innermost names it, whatever the
    # list order; 70-72 under bench.wait
    assert phases.idle_gaps(r, 0) == [
        ["bench.wait", pytest.approx(0.009)],
        ["fedfa.runtimes", pytest.approx(0.005)],
        ["bench.wait", pytest.approx(0.002)]]
    assert [g for g, _ in trace.idle_gaps(r, 0)] != \
        [g for g, _ in phases.idle_gaps(r, 0)]


def test_records_without_scopes_read_no_phase():
    with open(os.path.join(DATA, "trace_v5e.json")) as f:
        rec = json.load(f)
    rec.pop("expect")
    assert phases.phase_s(rec, ["fedfa.quantile"]) is None
    assert phases.device_phases(rec) == {}
    assert phases.span_self_s(rec, "fedfa.round") is None
    assert [g for g, _ in phases.idle_gaps(rec)] == \
        [g for g, _ in trace.idle_gaps(rec)]


def test_load_keeps_the_round_spans(tmp_path):
    """A host trace taken here: the program's spans load beside the
    benchmark's; a trace with no TPU plane has no device ops."""
    import jax
    import jax.numpy as jnp
    from repro.core import obs
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("bench.window"):
        with obs.span("fedfa.round"):
            jax.block_until_ready(jnp.arange(4.0) + 1)
        with obs.span("other"):
            pass
    jax.profiler.stop_trace()
    rec = phases.load(str(tmp_path))
    assert sorted(n for n, _, _ in rec["host"]) == ["bench.window",
                                                    "fedfa.round"]
    assert rec["device"] == [] and rec["scope"] == []
    assert phases.span_self_s(rec, "fedfa.round") > 0


def test_recorded_scoped_v5e_trace(tmp_path):
    """A slice of the profiler's JSON trace of the scoped round on a v5e
    (compiled with full locations), cut at a round boundary: the end of one
    round, the device idle while the host prepares and enqueues the next,
    and the next round's first phases.  "expect" was worked out on a 10 ns
    timeline, with each op's phase found by a plain scan, so each number is
    good to 10 ns a boundary."""
    with open(os.path.join(DATA, "trace_v5e_scoped.json")) as f:
        raw = json.load(f)
    want = raw.pop("expect")
    with gzip.open(tmp_path / "host.trace.json.gz", "wt") as f:
        json.dump(raw, f)
    rec = phases.load(str(tmp_path))
    assert trace.window_s(rec) == pytest.approx(want["window_s"], rel=1e-9)
    assert trace.busy_s(rec) == pytest.approx(want["busy_s"], abs=1e-5)
    got = phases.device_phases(rec)
    assert set(got) == set(want["phases_s"])
    for name, sec in want["phases_s"].items():
        assert got[name] == pytest.approx(sec, abs=1e-6), name
    assert phases.span_self_s(rec, "fedfa.round", ["fedfa.program"]) == \
        pytest.approx(want["driver_host_s"], abs=1e-6)
    # the device idles while the host finishes the next round's runtimes,
    # prepares it and enqueues it
    assert {g for g, _ in phases.idle_gaps(rec, 0, 3)} == {
        "fedfa.runtimes", "fedfa.prepare", "fedfa.program"}
    # the accumulate kernel runs inside its phase
    assert got["fedfa.accumulate"] >= trace.kernel_s(rec, ["accumulate"])
