"""Percent of its roofline that the trimmed-norm quantile reaches: the
least time of the work the algorithm needs (one f32 read of the m x N
cohort rows it trims, a compare, a square and an add per element; HBM
bound on a v5e) over the kernels' device time per round."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _kernels  # noqa: E402


def read(ctx):
    import work
    s = _kernels.per_round_s(ctx, _kernels.QUANTILE)
    if not s:
        return None
    cell = ctx["cell"]
    m, n = cell.m, cell.n
    t, _ = work.least_seconds(work.quantile_least_ops(m, n),
                              work.quantile_least_bytes(m, n), ctx["peaks"])
    return 100.0 * t / s
