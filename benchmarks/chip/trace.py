"""From a profiler trace to per-layer numbers.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps what
the reduction needs, as plain records: each device op (plane, line, name,
start, duration, and its custom-call target, if any) and
each of the benchmark's own host spans (``bench.*``).  The reduction below
works on those records alone, so it is checked on a small recorded trace
(``tests/data``) without a chip.

Times are nanoseconds on the profiler's clock, which the device planes and
the host plane share.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum", re.I)
CONTAINERS = re.compile(r"^(while|conditional|call)$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(hlo: str) -> str:
    """An op event's name on a TPU's "XLA Ops" line is its HLO text
    (``%row_trimmed_stats_multilevel.45 = (...) custom-call(...)``); keep
    the instruction name (``row_trimmed_stats_multilevel.45``)."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def op_target(hlo: str) -> str:
    """The custom-call target of an op (``tpu_custom_call`` for a Pallas
    kernel), or ""."""
    m = _TARGET.search(hlo)
    return m.group(1) if m else ""


def load(log_dir: str) -> dict:
    """{"device": [[dev, op name, custom-call target, start, dur], ...],
    "host": [[name, start, dur], ...]} from the newest trace in log_dir."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    dev, host = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                d = int(m.group(1))
                for ev in line.events:
                    dev.append([d, op_name(ev.name), op_target(ev.name),
                                float(ev.start_ns), float(ev.duration_ns)])
            elif not m and plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"device": dev, "host": host}


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def window(records: dict) -> Tuple[float, float]:
    """The measured window: the ``bench.window`` host span."""
    spans = [(s, s + d) for n, s, d in records["host"] if n == "bench.window"]
    if len(spans) != 1:
        raise ValueError(f"expected one bench.window span, found "
                         f"{len(spans)}")
    return spans[0]


def _clip(iv: Iterable[Tuple[float, float]], lo: float, hi: float):
    for a, b in iv:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def union(iv: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(iv: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in iv)


def devices(records: dict) -> List[int]:
    return sorted({e[0] for e in records["device"]})


def op_intervals(records: dict, dev: int, match=None):
    for d, name, target, s, dur in records["device"]:
        if d == dev and (match is None or match(name, target)):
            yield s, s + dur


def busy_ns(records: dict, dev: int) -> float:
    lo, hi = window(records)
    return length(union(_clip(op_intervals(records, dev), lo, hi)))


def busy_s(records: dict) -> float:
    """Seconds in which an op ran, averaged over the devices traced."""
    ds = devices(records)
    if not ds:
        return 0.0
    return sum(busy_ns(records, d) for d in ds) / len(ds) / 1e9


def window_s(records: dict) -> float:
    lo, hi = window(records)
    return (hi - lo) / 1e9


def idle_share(records: dict) -> Optional[float]:
    w = window_s(records)
    if w <= 0 or not devices(records):
        return None
    return 1.0 - busy_s(records) / w


def kernel_matcher(names: Sequence[str]):
    """Pallas kernels by the name their custom call carries in the trace
    (the name with XLA's numeric suffixes stripped)."""
    want = set(names)
    return lambda name, target: (target == "tpu_custom_call"
                                 and _base(name) in want)


def kernel_s(records: dict, names: Sequence[str]) -> Optional[float]:
    """Device seconds of the Pallas kernels named ``names``, inside the
    window, averaged over the devices traced; None when none ran."""
    lo, hi = window(records)
    match = kernel_matcher(names)
    ds = devices(records)
    tot, hits = 0.0, 0
    for d in ds:
        iv = list(_clip(op_intervals(records, d, match), lo, hi))
        hits += len(iv)
        tot += length(union(iv))
    if not hits:
        return None
    return tot / len(ds) / 1e9


def collective_exposed_s(records: dict, dev: int = 0) -> Optional[float]:
    """Seconds on ``dev`` in collective ops while no other op runs there;
    None when the trace holds no collective."""
    lo, hi = window(records)
    is_coll = lambda n, t: bool(COLLECTIVE.search(n))
    coll = union(_clip(op_intervals(records, dev, is_coll), lo, hi))
    if not coll:
        return None
    other = union(_clip(op_intervals(
        records, dev, lambda n, l: not is_coll(n, l)), lo, hi))
    covered, j = 0.0, 0
    for a, b in coll:            # both lists sorted and disjoint
        while j < len(other) and other[j][1] <= a:
            j += 1
        k = j
        while k < len(other) and other[k][0] < b:
            covered += min(b, other[k][1]) - max(a, other[k][0])
            k += 1
    return (length(coll) - covered) / 1e9


def _base(name: str) -> str:
    """An op's name without the numeric suffix XLA adds to copies."""
    return re.sub(r"(\.\d+)+$", "", name)


def top_ops(records: dict, k: int = 10) -> List[List]:
    """The ops (by base name) that took most device time in the window,
    averaged over devices: [[name, seconds], ...].  Control-flow ops
    (a ``while`` and the ops inside it are both on the ops line) are left
    out, so no time counts twice."""
    lo, hi = window(records)
    ds = devices(records) or [0]
    tot: Dict[str, float] = {}
    for d, name, target, s, dur in records["device"]:
        if CONTAINERS.match(_base(name)):
            continue
        for a, b in _clip([(s, s + dur)], lo, hi):
            key = _base(name)
            tot[key] = tot.get(key, 0.0) + (b - a) / 1e9 / len(ds)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(records: dict, dev: int = 0, k: int = 10) -> List[List]:
    """The longest gaps in which ``dev`` ran no op, each named by the
    benchmark's host span open at its midpoint (``host:other`` where none
    is): [[name, seconds], ...]."""
    lo, hi = window(records)
    busy = union(_clip(op_intervals(records, dev), lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [(n, s, s + d) for n, s, d in records["host"]
             if n != "bench.window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (a + b) / 2
        open_ = [n for n, s, e in spans if s <= mid <= e]
        out.append([open_[-1] if open_ else "host:other", (b - a) / 1e9])
    return out
