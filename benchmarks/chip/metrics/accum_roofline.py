"""Percent of its roofline that the (M', gamma) accumulate reaches: the
least time of the work it needs (one f32 read of the m x N cohort rows,
one f32 write of each (N,) output, two multiply-adds per element; HBM
bound on a v5e) over the kernels' device time per round."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _kernels  # noqa: E402


def read(ctx):
    import work
    s = _kernels.per_round_s(ctx, _kernels.ACCUM)
    if not s:
        return None
    cell = ctx["cell"]
    m, n = cell.m, cell.n
    t, _ = work.least_seconds(work.accum_least_ops(m, n),
                              work.accum_least_bytes(m, n), ctx["peaks"])
    return 100.0 * t / s
