"""Work counts the per-layer metrics divide by: the FLOPs a client's
sub-model needs for local training (counted by the configuration's family,
``families/<family>.py``), the FedFA depth sections, and the least bytes
the trimmed-norm quantile and the (M', gamma) accumulate must move.
"""
from __future__ import annotations

import math
from typing import Sequence

import spec


def section_bounds(cfg: dict):
    """FedFA depth sections over the layer stack: contiguous groups, the
    first ``L % S`` one layer longer."""
    L = cfg["num_hidden_layers"]
    n = min(cfg["fedfa"]["n_sections"], L)
    base, extra = divmod(L, n)
    out, lo = [], 0
    for s in range(n):
        hi = lo + base + (1 if s < extra else 0)
        out.append((lo, hi))
        lo = hi
    return out


def section_depths(cfg: dict, depth_frac: float):
    """Depth class as layers kept per section: the first ceil(f * size)."""
    return tuple(max(1, int(math.ceil(depth_frac * (hi - lo))))
                 for lo, hi in section_bounds(cfg))


def train_flops(cfg: dict, width: float, depths: Sequence[int], batch: int,
                seq: int) -> float:
    """FLOPs of one local step (forward + backward) of one client's
    sub-model on a (batch, seq) batch, as the configuration's family
    counts them."""
    return spec.family_of(cfg).train_flops(cfg, width, depths, batch, seq)


def quantile_least_bytes(m: int, n: int) -> float:
    """One f32 read of the m x N cohort rows the trimmed norms trim."""
    return 4.0 * m * n


def quantile_least_ops(m: int, n: int) -> float:
    """A compare, a square and an add per element: selection plus the
    trimmed sum of squares."""
    return 3.0 * m * n


def accum_least_bytes(m: int, n: int) -> float:
    """One f32 read of the cohort rows and one f32 write of each of the
    two (N,) outputs, M' and gamma."""
    return 4.0 * m * n + 2 * 4.0 * n


def accum_least_ops(m: int, n: int) -> float:
    """A multiply-add per cohort element for M' and one for gamma."""
    return 4.0 * m * n


def least_seconds(ops: float, nbytes: float, peaks: dict):
    """(least time, which bound): the larger of ops over peak FLOP/s and
    bytes over peak HBM bandwidth."""
    t_ops = ops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_ops else (t_ops, "compute")
