"""Device milliseconds per round in the trimmed-norm quantile kernels
(``row_trimmed_stats``, ``row_trimmed_stats_multilevel`` in the trace), averaged over the
chips used."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _kernels  # noqa: E402


def read(ctx):
    s = _kernels.per_round_s(ctx, _kernels.QUANTILE)
    return None if s is None else 1e3 * s
