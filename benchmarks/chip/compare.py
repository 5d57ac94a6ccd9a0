"""The numbers that decide ``correct``, and the limits a cell holds them to.

The numbers compare the program's first three rounds with the
reference's over the same rounds, from the same weights and inputs:

* ``loss1_gap``: the relative gap between the program's and the
  reference's mean client loss in round 1, both from the seed's weights
  (local training's forward pass);
* ``loss_gap``: the largest such gap over the three rounds; rounds 2 and 3
  start from models that local SGD has already pulled apart by rounding,
  so it swings from seed to seed and is not compared (PERF.md);
* ``delta1_gap``: the global model's change in round 1, layer tensor by
  layer tensor (every layer of a stacked leaf counts apart): the gap
  between the program's change norm and the reference's, over the
  larger of the reference's norm and the median tensor's; the worst
  tensor (training and the whole aggregation of one round);
* ``delta3_gap``: the same for the change over three rounds;
* ``delta1_median_gap``, ``delta3_median_gap``: the same gaps, the median
  tensor's instead of the worst.

Tensors whose reference change in round 1 is under a thousandth of the
median tensor's are left out of the change numbers: their change is
round-off.

Which numbers a cell compares, and their limits, are the cell's own file
``limits/<workload>.json``: {number: {"limit", "lower", "upper"}}, with
the readings each limit was set from (PERF.md gives them).
"""
from __future__ import annotations

import json
import os
from typing import Dict, Sequence

import numpy as np

import weights as weights_mod

EXCLUDE_BELOW = 1e-3
LIMITS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "limits")


def limits(workload: str) -> Dict[str, float]:
    with open(os.path.join(LIMITS_DIR, workload + ".json")) as f:
        return {k: float(v["limit"]) for k, v in json.load(f).items()}


def segment_norms(buf: np.ndarray, leaves) -> np.ndarray:
    """L2 norm of every layer tensor (a stacked leaf gives one per
    layer) of a flat (N,) buffer."""
    out = []
    for l in leaves:
        x = np.asarray(buf[l.offset:l.offset + l.size], np.float64)
        rows = l.shape[0] if l.stacked else 1
        out.append(np.sqrt(np.sum(x.reshape(rows, -1) ** 2, axis=1)))
    return np.concatenate(out)


def norm_gaps(prog: np.ndarray, ref: np.ndarray,
              keep: np.ndarray) -> np.ndarray:
    floor = np.median(ref)
    return (np.abs(prog - ref) / np.maximum(ref, floor))[keep]


def numbers(cfg: dict, g0: np.ndarray, prog_losses: Sequence[float],
            prog_snaps: Dict[int, np.ndarray], ref_losses: Sequence[float],
            ref_snaps: Dict[int, np.ndarray]) -> Dict[str, float]:
    leaves, n = weights_mod.layout(cfg)
    g0 = np.asarray(g0[:n], np.float64)
    last = max(ref_snaps)
    r1 = segment_norms(ref_snaps[1] - g0, leaves)
    keep = r1 >= EXCLUDE_BELOW * np.median(r1)
    p1 = segment_norms(np.asarray(prog_snaps[1][:n], np.float64) - g0,
                       leaves)
    r3 = segment_norms(ref_snaps[last] - g0, leaves)
    p3 = segment_norms(np.asarray(prog_snaps[last][:n], np.float64) - g0,
                       leaves)
    gaps = [abs(p - r) / abs(r) for p, r in zip(prog_losses, ref_losses)]
    g1, g3 = norm_gaps(p1, r1, keep), norm_gaps(p3, r3, keep)
    return {"loss1_gap": float(gaps[0]), "loss_gap": float(max(gaps)),
            "delta1_gap": float(np.max(g1)),
            "delta3_gap": float(np.max(g3)),
            "delta1_median_gap": float(np.median(g1)),
            "delta3_median_gap": float(np.median(g3))}


def verdict(nums: Dict[str, float], lim: Dict[str, float]) -> bool:
    return all(np.isfinite(nums[k]) and nums[k] <= v for k, v in lim.items())


def report(nums: Dict[str, float],
           lim: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    return {k: {"value": nums[k], "limit": v} for k, v in lim.items()}


def worst(cfg: dict, g0: np.ndarray, prog_snaps, ref_snaps, k: int = 4):
    """The k tensors with the largest change-norm gap after each checked
    round: [name, layer, reference norm, program norm, gap]."""
    leaves, n = weights_mod.layout(cfg)
    names = [(l.name, r) for l in leaves
             for r in range(l.shape[0] if l.stacked else 1)]
    g0 = np.asarray(g0[:n], np.float64)
    out = {}
    for key in sorted(ref_snaps):
        r = segment_norms(ref_snaps[key] - g0, leaves)
        p = segment_norms(np.asarray(prog_snaps[key][:n], np.float64) - g0,
                          leaves)
        gap = np.abs(p - r) / np.maximum(r, np.median(r))
        top = np.argsort(-gap)[:k]
        out[key] = [[names[i][0], names[i][1], float(r[i]), float(p[i]),
                     float(gap[i])] for i in top]
    return out


def diff_numbers(cfg: dict, g0: np.ndarray, prog_snaps, ref_snaps):
    """Norms of the difference of the changes, tensor by tensor, over the
    larger of the reference's change norm and the median tensor's (the
    worst tensor).  Printed beside the compared numbers by
    ``calibrate.py``; not compared."""
    leaves, n = weights_mod.layout(cfg)
    g0 = np.asarray(g0[:n], np.float64)
    r1 = segment_norms(ref_snaps[1] - g0, leaves)
    keep = r1 >= EXCLUDE_BELOW * np.median(r1)
    out = {}
    for k in sorted(ref_snaps):
        r = segment_norms(ref_snaps[k] - g0, leaves)
        d = segment_norms(np.asarray(prog_snaps[k][:n], np.float64)
                          - ref_snaps[k], leaves)
        out[f"delta{k}_diff"] = float(np.max(
            (d / np.maximum(r, np.median(r)))[keep]))
    return out
