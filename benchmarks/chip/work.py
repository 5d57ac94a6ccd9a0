"""Work counts the per-layer metrics divide by: the FLOPs a client's
sub-model needs for local training, and the least bytes the trimmed-norm
quantile and the (M', gamma) accumulate must move.

The FLOP count is a copy of the dense-attention part of the program's
analytic model (``launch/costs.macs_per_client``): matmul-level
accounting of the sub-model at the client's width class and section
depths, forward + backward = 3x forward, causal attention at half the
square.  Masked padding and recomputation are not counted: the padded
dense program does more, and that is what ``mfu`` is meant to show.
"""
from __future__ import annotations

import math
from typing import Sequence


def width_sizes(cfg: dict, w: float) -> dict:
    """Contiguous-prefix active sizes of width class ``w`` (HeteroFL-style
    structured pruning, as FedFA's width flexibility defines it)."""
    if not 0.0 < w <= 1.0:
        raise ValueError(f"width class must be in (0, 1], got {w!r}")
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    kv = max(1, int(round(w * K)))
    return {
        "d_model": D if w >= 1.0 else max(16, int(w * D) // 8 * 8),
        "n_heads": kv * (H // K),
        "n_kv_heads": kv,
        "d_ff": F if w >= 1.0 else max(8, int(w * F) // 8 * 8),
    }


def padded_vocab(cfg: dict) -> int:
    return (cfg["vocab_size"] + 127) // 128 * 128


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
    sub-model on a (batch, seq) batch: 2 x the MACs of
    ``costs.macs_per_client``."""
    sz = width_sizes(cfg, width)
    D, hd = sz["d_model"], cfg["head_dim"]
    H, K, F = sz["n_heads"], sz["n_kv_heads"], sz["d_ff"]
    B, S = float(batch), float(seq)
    proj = 2 * B * S * D * (H + 2 * K) * hd + 2 * B * S * H * hd * D
    attn = 2 * 2 * B * S * (S / 2) * H * hd
    ffn = 2 * 3 * B * S * D * F
    layers = sum(depths)
    fwd = layers * (proj + attn + ffn) + 2 * B * S * D * padded_vocab(cfg)
    return 3.0 * fwd


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
