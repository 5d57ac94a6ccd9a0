"""Kernel names as they appear in the device trace, and the per-round
device time of a kernel family.  Shared by the kernel metrics of this
directory; a new kernel metric adds its names here only if it reads
these families."""

# A Pallas kernel's custom call is named in a v5e trace after the jitted
# function that issues it (read by hand from a trace of each cell):
# kernels/fedfa_quantile: ``row_trimmed_stats`` (the single-pass
# ``_quantile_fused_kernel``) and ``row_trimmed_stats_multilevel`` (the
# ``_hist_level_kernel`` levels); kernels/fedfa_agg: ``accumulate``
# (``_scaled_accum_kernel``, twice a round: M' and gamma).
QUANTILE = ("row_trimmed_stats", "row_trimmed_stats_multilevel")
ACCUM = ("accumulate",)


def per_round_s(ctx, names):
    s = ctx["trace"].kernel_s(ctx["records"], names)
    if s is None:
        return None
    return s / ctx["window"]["rounds"]
