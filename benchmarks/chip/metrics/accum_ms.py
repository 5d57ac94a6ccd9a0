"""Device milliseconds per round in the (M', gamma) accumulate kernel
(``_scaled_accum_kernel``, ``accumulate`` in the trace: one call for M'
and one for gamma), averaged over the chips used."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _kernels  # noqa: E402


def read(ctx):
    s = _kernels.per_round_s(ctx, _kernels.ACCUM)
    return None if s is None else 1e3 * s
