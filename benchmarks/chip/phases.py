"""The round's phases from a profiler trace, by the names the program gives
them (``repro.core.obs``): ``fedfa.*`` named scopes on the device ops and
``fedfa.*`` host spans in ``ResidentDriver.round``.

``load`` reads the ``*.trace.json.gz`` the profiler writes beside its
``.xplane.pb``: the same events, and for each device op also its
``tf_op``, the op's ``op_name`` path, which ``ProfileData`` does not
expose.  Its records are ``trace.load``'s, plus ``records["scope"]`` (one
path per device op, in the same order) and the ``fedfa.*`` host spans
beside ``bench.*``; every reduction of ``trace`` runs on them unchanged.
Records without ``scope`` (``trace.load``'s) read no phase.

On a TPU a scope reaches an op's ``op_name`` only where JAX put full
tracebacks in the op locations: with locations cut to one frame, as
``harness.enable_compile_cache`` cuts them, the ops of local training and
of the (m, N) packing carry no scope.  So the command below compiles the
round with full locations, a program of its own in the compile cache.

    python3 benchmarks/chip/phases.py --workload smollm135m.width.m3 \\
        --seed 7 --seconds 10

runs set-up and the window as ``run.py`` does, once untraced and once
traced, and prints one JSON line: the per-round time of each window, each
phase's device milliseconds a round, the driver's own host milliseconds a
round, the round programs traced inside the traced window, the device
seconds of every phase, and the idle gaps named by the innermost span.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

import trace

PREFIX = "fedfa."
UNSCOPED = "unscoped"
# metric -> the scopes whose device time it reads
PHASES = {"train_ms": ("fedfa.train",),
          "distribute_ms": ("fedfa.unflatten", "fedfa.flatten"),
          "graft_ms": ("fedfa.graft", "fedfa.density"),
          "merge_ms": ("fedfa.merge",)}


def load(log_dir: str) -> dict:
    """Records of the newest ``*.trace.json.gz`` under log_dir."""
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.trace.json.gz"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .trace.json.gz under {log_dir}")
    with gzip.open(paths[-1], "rt") as f:
        return from_events(json.load(f)["traceEvents"])


def from_events(events: List[dict]) -> dict:
    """Records from the events of a trace's JSON export (times there are
    microseconds; records keep nanoseconds, as ``trace.load`` does)."""
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    lines = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    dev, scope, host = [], [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = procs.get(e["pid"], "")
        m = trace.DEVICE_PLANE.match(proc)
        start, dur = 1e3 * e["ts"], 1e3 * e.get("dur", 0.0)
        if m and lines.get((e["pid"], e["tid"])) == trace.OPS_LINE:
            args = e.get("args", {})
            hlo = args.get("long_name", e["name"])
            dev.append([int(m.group(1)), trace.op_name(hlo),
                        trace.op_target(hlo), start, dur])
            scope.append(args.get("tf_op", ""))
        elif proc.startswith("/host") and e["name"].startswith(
                ("bench.", PREFIX)):
            host.append([e["name"], start, dur])
    return {"device": dev, "scope": scope, "host": host}


def phase_of(path: str) -> str:
    """The innermost ``fedfa.*`` component of an op_name path
    (``jit(_round)/fedfa.train/while/body/dot_general`` -> ``fedfa.train``),
    or ``unscoped``."""
    names = [p for p in path.split("/") if p.startswith(PREFIX)]
    return names[-1] if names else UNSCOPED


def labels(records: dict) -> List[str]:
    """The phase of each device op.  An op that carries no scope (one XLA
    made: a copy, an in-place update, a loop around them) takes the phase
    of the scoped ops before and after it on its device when the two
    agree: a device runs one program's ops one at a time, in order."""
    own = [phase_of(p) for p in records["scope"]]
    out = list(own)
    per_dev: Dict[int, List[int]] = {}
    for i, e in enumerate(records["device"]):
        per_dev.setdefault(e[0], []).append(i)
    for idx in per_dev.values():
        idx.sort(key=lambda i: records["device"][i][3])
        before, last = [], None
        for i in idx:
            before.append(last)
            if own[i] != UNSCOPED:
                last = own[i]
        after = None
        for k in range(len(idx) - 1, -1, -1):
            i = idx[k]
            if own[i] != UNSCOPED:
                after = own[i]
            elif after is not None and before[k] == after:
                out[i] = after
    return out


def _phase_s(records: dict, lab: List[str], want) -> Optional[float]:
    lo, hi = trace.window(records)
    ds = trace.devices(records)
    tot, hits = 0.0, 0
    for d in ds:
        iv = list(trace._clip(((s, s + dur) for (dd, _, _, s, dur), p
                               in zip(records["device"], lab)
                               if dd == d and p in want), lo, hi))
        hits += len(iv)
        tot += trace.length(trace.union(iv))
    return tot / len(ds) / 1e9 if hits else None


def phase_s(records: dict, scopes: Sequence[str]) -> Optional[float]:
    """Seconds in which an op of one of ``scopes`` ran, inside the window,
    averaged over the devices traced; None when none ran (or the records
    carry no scopes)."""
    if "scope" not in records:
        return None
    return _phase_s(records, labels(records), set(scopes))


def device_phases(records: dict) -> Dict[str, float]:
    """Device seconds in the window of every phase the trace names, and
    ``unscoped``, averaged over the devices traced; largest first."""
    if "scope" not in records:
        return {}
    lab = labels(records)
    out = {n: _phase_s(records, lab, {n}) or 0.0 for n in set(lab)}
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def _spans(records: dict, name: str) -> List[Tuple[float, float]]:
    lo, hi = trace.window(records)
    return trace.union(trace._clip(
        ((s, s + d) for n, s, d in records["host"] if n == name), lo, hi))


def _overlap(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    tot, j = 0.0, 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            tot += min(hi, b[k][1]) - max(lo, b[k][0])
            k += 1
    return tot


def span_self_s(records: dict, name: str,
                minus: Sequence[str] = ()) -> Optional[float]:
    """Seconds of the window in host span ``name``, less what the spans
    named in ``minus`` cover; None when the span never opened there."""
    own = _spans(records, name)
    if not own:
        return None
    other = trace.union(iv for n in minus for iv in _spans(records, n))
    return (trace.length(own) - _overlap(own, other)) / 1e9


def idle_gaps(records: dict, dev: int = 0, k: int = 10) -> List[List]:
    """``trace.idle_gaps``, each gap named by the innermost host span open
    at its midpoint: ``trace`` names a gap by the last open span in list
    order, which, with the spans in order of their start, is the one
    opened last."""
    host = sorted(records["host"], key=lambda h: h[1])
    return trace.idle_gaps({**records, "host": host}, dev, k)


def per_round(records: dict, rounds: int, counts_before: Dict[str, int],
              counts_after: Dict[str, int]) -> Dict[str, Optional[float]]:
    """The readings of one traced window of ``rounds`` rounds, in ms a
    round; ``window_retraces`` from the program's counters around it."""
    ms = lambda s: None if s is None else 1e3 * s / rounds
    out = {k: ms(phase_s(records, v)) for k, v in PHASES.items()}
    out["driver_host_ms"] = ms(span_self_s(records, "fedfa.round",
                                           ["fedfa.program"]))
    out["window_retraces"] = float(counts_after.get("round_traces", 0)
                                   - counts_before.get("round_traces", 0))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    import jax
    import harness
    import spec
    from repro.core import obs
    if jax.devices()[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {jax.devices()[0].platform!r}")
    wl = spec.workload(spec.load_benchmark(harness.ROOT), args.workload)
    harness.enable_compile_cache()
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    cell = harness.setup(spec.config(wl["config"]),
                         spec.traffic(wl["traffic"]), args.seed,
                         int(wl["chips"]))
    harness.warm_profiler()
    plain = harness.window(cell, args.seconds)
    before = obs.counts()
    with tempfile.TemporaryDirectory() as tdir:
        win = harness.window(cell, args.seconds, trace_dir=tdir)
        records = load(tdir)
    n = win["rounds"]
    line = {"round_s_untraced": plain["elapsed"] / plain["rounds"],
            "round_s_traced": win["elapsed"] / n, "rounds": n,
            **per_round(records, n, before, obs.counts()),
            "busy_s": trace.busy_s(records),
            "window_s": trace.window_s(records),
            "device_phases": device_phases(records),
            "idle_gaps": idle_gaps(records)}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
