"""jit'd wrapper for the fused trimmed-quantile kernel (padding + dispatch)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_pallas
from repro.kernels.fedfa_quantile import multilevel, ref
from repro.kernels.fedfa_quantile.kernel import quantile_fused

_LANES = 128
_BLOCK_ROWS = 8      # the sublane tile: a block is 8 rows or the whole axis
# Row length past which the SINGLE-PASS kernel hands over: one 8-row block
# holds the f32 rows, their int32 bit view and a few same-shaped
# intermediates in VMEM (~16B/element), so rows of up to 2^18 elements keep
# a block near 32 MiB of the chip's 128 MiB.  Longer rows dispatch to the
# two-stage multilevel kernel (still read-once, still sort-free) — NEVER to
# the jnp oracle.  The oracle runs only when the caller explicitly
# deselects the kernel path (use_kernel=False without interpret).
_SINGLE_PASS_ELEMS = 1 << 18


def single_pass_block(R: int, L: int):
    """(block_rows, padded rows, padded length) of the single-pass dispatch
    for (R, L) rows: lanes pad to 128, and a block is the whole row axis
    below 8 rows, else 8 rows — the TPU refuses any other sublane count."""
    Lp = -(-L // _LANES) * _LANES
    rb = min(_BLOCK_ROWS, R)
    return rb, -(-R // rb) * rb, Lp


def fused_quantile_contract(block_bytes=None, *, padded: bool = False):
    """Declared contract of the fused trimmed-quantile path (PR 4): the
    whole (threshold, trimmed Σw²) computation is ONE pallas_call, so the
    traced program reads the cohort row block exactly once and contains
    zero sort/top_k ops — the 31-step count-and-partition refinement
    happens in VMEM.  Checked on the jaxpr (``row_reads``/``sorts``), not
    on timing; see ``repro.analysis.jaxpr`` for the counting rules.

    With ``block_bytes`` (the (R, L) row-block byte size) the compiled
    program's statically estimated peak is budgeted at 6x the block —
    the block, its |.| copy and the interpret-mode staging buffers
    (measured ~4x on the canonical fixture).  A path that re-materializes
    per-refinement-step copies of the block blows it.

    ``padded=True`` declares the non-dividing dispatch shape: when (R, L)
    does not tile evenly, ``row_trimmed_stats`` stages the rows into a
    zero-initialized (Rp, Lp) block (one extra row-sized read feeding the
    pad scatter) and the compiled program keeps BOTH blocks live across
    the copy — the peak budget widens to 9x (measured ~6.2x on the
    canonical non-dividing fixture, vs ~4x divisible)."""
    from repro.analysis.contracts import Contract
    mult, reads = (9, (1, 2)) if padded else (6, 1)
    peak = {} if block_bytes is None else dict(
        peak_live_bytes_per_device=(None, mult * block_bytes))
    return Contract(name="quantile/fused-pad" if padded else "quantile/fused",
                    description="fused Pallas trimmed quantile"
                    + (" (non-dividing padded dispatch)" if padded else ""),
                    row_reads=reads, sorts=0, **peak)


def topk_tail_contract(block_bytes=None, *, padded: bool = False):
    """Declared shape of the top_k tail path the fused kernel replaced —
    kept as a pinned reference point: 7 row-block reads (abs, sort,
    compare, square-reduce chain) and exactly 1 sort.  If a jax upgrade
    shifts these counts the benchmark's fused-vs-topk comparison basis
    moved and the numbers need re-anchoring.  ``block_bytes`` budgets the
    compiled peak at 4x the block (measured ~2.1x); ``padded=True``
    re-anchors for the non-dividing fixture, where XLA's top_k scratch
    rounds the sorted copies up to the padded block (budget 5x)."""
    from repro.analysis.contracts import Contract
    mult = 5 if padded else 4
    peak = {} if block_bytes is None else dict(
        peak_live_bytes_per_device=(None, mult * block_bytes))
    return Contract(name="quantile/topk-pad" if padded else "quantile/topk",
                    description="top_k tail path (pre-PR 4 reference)",
                    row_reads=7, sorts=1, **peak)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def row_trimmed_stats(rows: jax.Array, q: jax.Array, *,
                      scale: jax.Array = None,
                      use_kernel=None, interpret: bool = False) -> tuple:
    """Fused per-row (quantile threshold, trimmed Σw²) in ONE pass.

    rows: (R, L) signed values (|.| is taken inside the kernel);
    q: (R,) quantile levels in [0, 1].  Returns f32 ((R,), (R,)):
    t[r] = jnp.quantile(|rows[r]|, q[r]) and
    ss[r] = Σ rows[r]²·[|rows[r]| <= t[r]].

    ``scale`` (R,) declares the rows quantized (int8/bf16): the kernel
    paths keep the admitted dtype in HBM and dequantize in VMEM through
    the per-row constant, preserving read-once; only the explicit-oracle
    path materializes the f32 product.

    Dispatch: rows that fit one VMEM block go to the single-pass kernel;
    longer rows (embedding-scale leaves) go to the two-stage multilevel
    kernel.  Both are read-once and sort-free; the jnp oracle runs ONLY
    when the kernel path is deselected (``use_kernel=False``, or None off
    a TPU).  ``use_kernel=True`` off a TPU raises unless ``interpret``.
    """
    R, L = rows.shape
    if not use_pallas(use_kernel, interpret):
        if scale is not None:
            rows = rows.astype(jnp.float32) \
                * scale[:, None].astype(jnp.float32)
        return ref.row_trimmed_stats_ref(rows, q)
    rb, Rp, Lp = single_pass_block(R, L)
    if Lp > _SINGLE_PASS_ELEMS:
        return multilevel.row_trimmed_stats_multilevel(
            rows, q, scale=scale, interpret=interpret)
    want = rows.dtype if scale is not None else jnp.float32
    if Lp == L and Rp == R:
        rows_p, q_p = rows.astype(want), q.astype(jnp.float32)
        s_p = None if scale is None else scale.astype(jnp.float32)
    else:
        # lane pads are masked out in-kernel (any value works); row pads get
        # q = 1 on zero rows (t = 0, ss = 0) and are sliced off below
        rows_p = jnp.zeros((Rp, Lp), want).at[:R, :L].set(rows.astype(want))
        q_p = jnp.ones((Rp,), jnp.float32).at[:R].set(q.astype(jnp.float32))
        s_p = None if scale is None else \
            jnp.ones((Rp,), jnp.float32).at[:R].set(
                scale.astype(jnp.float32))
    t, ss = quantile_fused(rows_p, q_p, L=L, block_rows=rb, scale=s_p,
                           interpret=interpret)
    return t[:R], ss[:R]
