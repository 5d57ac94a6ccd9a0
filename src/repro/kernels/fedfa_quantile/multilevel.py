"""Two-stage (histogram -> refine) trimmed quantile over sharded row slices.

The single-pass kernel in ``kernel.py`` needs the whole row resident in one
VMEM block, which caps row length and forces the norms pass to consume
model-replicated P("data") rows.  This module removes both limits with a
B-ary count-and-partition search over the IEEE-754 bit pattern of |x|:

  * stage 1 (level 0) bins every local element by the top nibble of its bit
    pattern into a per-(client, segment) 16-bin histogram and ``psum``s the
    HISTOGRAM (never the rows) over the model axis;
  * stage 2 (levels 1..7) refines one nibble per level inside the
    bracketing bin, so 8 levels resolve the full 32-bit pattern of the
    order statistic.

The radix is the kernel's cost: per level each element meets a (B, T) bin
one-hot, so the work per element grows with B while the level count grows
only with 32 / log2(B).  16 bins over 8 levels do the same exact select as
256 bins over 4 with about a fifth of the vector operations, for twice
the level passes over the rows.

For nonnegative f32 the bit pattern is monotone in the value, so after the
last level the accumulated pattern IS the exact r-th smallest magnitude —
thresholds are bit-equal to ``jnp.quantile``'s bracketing order statistics
(same f32 rank arithmetic as the single-pass kernel).  The trimmed Σw² rides
along: each level also accumulates per-bin Σx² planes, summed strictly below
the chosen bin at inner levels and inclusively at the last, which yields
S(v) = Σ x²·[x <= v] for both bracketing statistics v0, v1 without a second
pass.  Because no data value lies strictly between adjacent order statistics,
the trimmed sum at the interpolated threshold t is S(v0) when t < v1 and
S(v1) otherwise.

All eight levels call ONE pallas kernel inside a ``fori_loop`` (the level's
bit shift is a scalar input), so the traced program contains exactly one
row-sized read site: the read-once property survives arbitrary row length.
Per level the cross-shard traffic is the (rows, 2, segments, 16) count and
Σx² planes — histogram-sized, independent of row length, never O(N).

The kernel itself is segment-aware: it consumes the whole local flat slice
(rows, cols) at once with a static per-column segment id map (-1 marks inert
padding), building per-segment one-hot matrices so the histogram update is
two MXU-friendly (segments, tile) @ (tile, bins) matmuls per (client, rank
path).  Counts accumulate as int32 (exact past 2^24 elements).  The
in-bracket test compares ``bits >> (shift+4)`` against the resolved prefix,
which reaches 2^27 at the last level: past f32's exact integers, so the
prefix is compared as int32 — broadcast on the row path, and gathered as two
16-bit halves through the f32 segment one-hot on the segmented path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BITS = 4            # one nibble per level
_BINS = 1 << _BITS   # 16 bins: the (B, T) one-hot is 8 vregs a column tile
_LEVELS = 32 // _BITS   # 8 levels cover the 32-bit pattern
_PATHS = 2           # floor and ceil ranks bracketing the quantile position
TILE = 512          # column tile (lane-aligned); callers pad cols to this
_ROW_TILE = 8        # sublane tile: row blocks are 8 rows or the whole axis


def _hist_level_kernel(shift_ref, hi_ref, x_ref, seg_ref, sc_ref, cnt_ref,
                       sq_ref, *lane_sums):
    """One refinement level: per-(row, path, bin, segment) histogram planes.

    shift_ref (1, 1) i32 in SMEM: the level's bit shift (28, 24, ..., 0).
    hi_ref (rb*P, S) i32: expected resolved prefix ``lo >> (shift+4)``,
    rows ordered (row, path).
    x_ref (rb, T) column tile (f32, or the quantized admission dtype);
    seg_ref (1, T) i32 segment ids (-1 = pad).
    sc_ref (rb, S) f32 per-(row, segment) dequant scales: the nibble walk
    bins DEQUANTIZED magnitudes — the scale is gathered per column through
    the same segment one-hot the histograms use (all-ones on the f32 path,
    where the multiply is exact).
    cnt_ref (rb, P, B, S) i32 / sq_ref (rb, P, B, S) f32: accumulated over
    the column grid axis (zeroed on the first tile, += on revisits).
    lane_sums, on the row path only: (rb, P, B, 128) i32 / f32 VMEM planes
    that keep per-lane partial sums across the column tiles, reduced over
    the lanes into cnt_ref / sq_ref once, on the last tile.

    Every value stays 2-D with the column tile on the lanes, and the
    segment one-hot is (S, T), so the per-column gathers are (r, S) @ (S, T)
    matmuls and the histogram update is a (B, T) x (S, T) contraction over
    the lanes — the shapes Mosaic lays out natively.  One segment (the row
    path) needs no one-hot: gathers broadcast, and updates add the tile's
    128-lane slices into the lane partials, so no tile pays a cross-lane
    reduction or a read-modify-write of the narrow (B, 1) output block.
    Gathers and Σx² run at HIGHEST precision: the one-hot selects exactly
    one f32 value, and the Σx² planes keep f32 accuracy instead of the
    MXU's default bf16 pass.  The resolved prefix reaches 2^27, past f32's
    exact integers: the row path broadcasts it as int32, and the segmented
    path gathers its two 16-bit halves apart and joins them.
    """
    acc_cnt, acc_sq = lane_sums or (cnt_ref, sq_ref)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_cnt[...] = jnp.zeros_like(acc_cnt)
        acc_sq[...] = jnp.zeros_like(acc_sq)

    shift = shift_ref[0, 0]
    hs = jnp.minimum(shift + _BITS, 31)  # bit 31 of |x| patterns is 0
    rb, T = x_ref.shape
    _, P, B, S = cnt_ref.shape
    seg = seg_ref[...]                                        # (1, T)
    valid = seg >= 0
    hp = jax.lax.Precision.HIGHEST
    if S == 1:
        def gather(tab):                                      # (r, 1) -> (r, T)
            return jnp.broadcast_to(tab, (tab.shape[0], T))

        gather_prefix = gather                                # int32 as is
        lanes = acc_cnt.shape[-1]

        def update(a):                                        # (B, T) -> (B, 128)
            out = a[:, :lanes]
            for k in range(lanes, T, lanes):
                out = out + a[:, k:k + lanes]
            return out
    else:
        seg_oh = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (S, T), 0) == seg,
            1.0, 0.0)                                         # (S, T)

        def gather(tab):                                      # (r, S) -> (r, T)
            return jnp.dot(tab, seg_oh, precision=hp,
                           preferred_element_type=jnp.float32)

        def gather_prefix(tab):                               # i32 (r, S)
            half = jax.lax.shift_right_logical(tab, 16).astype(jnp.float32)
            low = (tab & 0xFFFF).astype(jnp.float32)
            return (jax.lax.shift_left(gather(half).astype(jnp.int32), 16)
                    | gather(low).astype(jnp.int32))

        def update(a):                                        # (B, T) -> (B, S)
            return jax.lax.dot_general(
                a, seg_oh, (((1,), (1,)), ((), ())), precision=hp,
                preferred_element_type=jnp.float32)
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (B, T), 0)
    for c in range(rb):
        # scales are nonnegative, so |x·scale| = |x|·scale; inert columns
        # are excluded from every histogram by ``valid``
        scl = gather(sc_ref[c:c + 1, :].astype(jnp.float32))  # (1, T)
        x = jnp.abs(x_ref[c:c + 1, :].astype(jnp.float32) * scl)
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)     # monotone
        binv = jax.lax.shift_right_logical(bits, shift) & (B - 1)
        hi = jax.lax.shift_right_logical(bits, hs)            # < 2^27
        hit = jnp.where(iota_b == binv, 1.0, 0.0)             # (B, T)
        x2 = x * x
        for p in range(P):
            r = c * P + p
            hi_e = gather_prefix(hi_ref[r:r + 1, :])          # (1, T) i32
            inb = jnp.where((hi == hi_e) & valid, 1.0, 0.0)   # (1, T)
            bin_oh = hit * inb                                # (B, T)
            acc_cnt[c, p] += update(bin_oh).astype(jnp.int32)
            acc_sq[c, p] += update(bin_oh * x2)

    if lane_sums:
        @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
        def _reduce_lanes():
            for c in range(rb):
                for p in range(P):
                    cnt_ref[c, p] = jnp.sum(acc_cnt[c, p], axis=1,
                                            keepdims=True)
                    sq_ref[c, p] = jnp.sum(acc_sq[c, p], axis=1,
                                           keepdims=True)


def _row_block(m: int) -> int:
    """Rows per kernel block: the whole row axis up to the 8-sublane tile,
    else one tile (callers pad the row axis to a multiple of it)."""
    return m if m <= _ROW_TILE else _ROW_TILE


def _hist_call(x, seg_id, sc, hi, shift, *, interpret: bool):
    """One level's histogram planes (m, P, S, B) over the (m, C) slice.
    m must be at most 8 or a multiple of 8, and C a multiple of the tile."""
    m, C = x.shape
    _, P, S = hi.shape
    T = min(C, TILE)
    rb = _row_block(m)
    assert C % T == 0 and m % rb == 0
    out_shape = [jax.ShapeDtypeStruct((m, P, _BINS, S), jnp.int32),
                 jax.ShapeDtypeStruct((m, P, _BINS, S), jnp.float32)]
    out_block = pl.BlockSpec((rb, P, _BINS, S), lambda i, j: (i, 0, 0, 0))
    lanes = 128 if T % 128 == 0 else T
    lane_sums = [] if S > 1 else [
        pltpu.VMEM((rb, P, _BINS, lanes), jnp.int32),
        pltpu.VMEM((rb, P, _BINS, lanes), jnp.float32)]
    # both planes stay resident across the column axis (x2 for the
    # pipeline's buffers), next to the row path's lane partials, the
    # double-buffered input tiles and the (B, T) / (S, T) one-hots of the
    # body
    resident = 2 * rb * P * _BINS * (2 * S + (S == 1) * lanes) * 4
    tiles = 2 * 4 * (rb * T + T + rb * S + rb * P * S)
    body = 4 * T * (4 * _BINS + 2 * S + 8 * rb)
    vmem = min(resident + tiles + body + (8 << 20), 100 << 20)
    cnt, sq = pl.pallas_call(
        _hist_level_kernel,
        grid=(m // rb, C // T),
        in_specs=[pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((rb * P, S), lambda i, j: (i, 0)),
                  pl.BlockSpec((rb, T), lambda i, j: (i, j)),
                  pl.BlockSpec((1, T), lambda i, j: (0, j)),
                  pl.BlockSpec((rb, S), lambda i, j: (i, 0))],
        out_specs=[out_block, out_block],
        out_shape=out_shape,
        scratch_shapes=lane_sums,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(shift.reshape(1, 1), hi.reshape(m * P, S), x, seg_id.reshape(1, C), sc)
    return jnp.swapaxes(cnt, 2, 3), jnp.swapaxes(sq, 2, 3)


def segmented_trimmed_stats(x, seg_id, seg_len, q_seg, *, scales=None,
                            axis_name=None, interpret: bool = False):
    """Exact per-(row, segment) (threshold, trimmed Σw²) over a flat slice.

    x (m, C): each row is one client's local slice of the flat cohort
    buffer (the model shard's columns when ``axis_name`` is set, the whole
    row otherwise).  seg_id (C,) i32 maps each local column to its global
    segment (-1 marks inert padding).  seg_len (S,) i32 holds the GLOBAL
    element count per segment; q_seg (m, S) f32 the quantile levels.

    ``scales`` (m, S) declares x quantized (int8/bf16): the rows stay in
    the admitted dtype and the kernel dequantizes per column through the
    per-segment constants, so the nibble walk operates on dequantized
    magnitudes with no extra row pass.  None keeps the f32 path (all-ones
    scales in-kernel; the multiply is exact).

    Returns (t, ss), both (m, S) f32 and replicated across ``axis_name``:
    t[c, s] = jnp.quantile(dequantized |x| restricted to segment s,
    q_seg[c, s]) — bit-equal to the single-pass kernel — and
    ss = Σ x²·[|x| <= t] in dequantized units.

    With ``axis_name`` every shard runs the same refinement trajectory on
    psum'd histograms, so no shard ever sees another shard's rows.
    """
    m, C = x.shape
    S = int(seg_len.shape[0])
    if scales is None:
        x = x.astype(jnp.float32)
        sc = jnp.ones((m, S), jnp.float32)
    else:
        sc = scales.astype(jnp.float32)
    m_real = m
    pad = (-m) % _row_block(m)
    if pad:      # whole sublane tiles: zero rows at q = 1, sliced off below
        x = jnp.pad(x, ((0, pad), (0, 0)))
        sc = jnp.pad(sc, ((0, pad), (0, 0)), constant_values=1.0)
        q_seg = jnp.pad(q_seg, ((0, pad), (0, 0)), constant_values=1.0)
        m += pad
    seg_id = seg_id.astype(jnp.int32)
    nseg = seg_len.astype(jnp.int32)
    p = q_seg.astype(jnp.float32) * (nseg - 1).astype(jnp.float32)[None, :]
    i0 = jnp.floor(p)
    frac = p - i0                                             # (m, S)
    r0 = i0.astype(jnp.int32)
    r1 = jnp.minimum(r0 + 1, (nseg - 1)[None, :])
    rank0 = jnp.stack([r0, r1], axis=1)                       # (m, P, S)
    lo0 = jnp.zeros((m, _PATHS, S), jnp.int32)
    sq0 = jnp.zeros((m, _PATHS, S), jnp.float32)

    def level(j, carry):
        lo, rank, sqb = carry
        shift = (32 - _BITS * (j + 1)).astype(jnp.int32)
        hi = jax.lax.shift_right_logical(lo, jnp.minimum(shift + _BITS, 31))
        cnt, sq = _hist_call(x, seg_id, sc, hi, shift, interpret=interpret)
        if axis_name is not None:
            cnt = jax.lax.psum(cnt, axis_name)
            sq = jax.lax.psum(sq, axis_name)
        cum = jnp.cumsum(cnt, axis=-1)                        # (m,P,S,B)
        # smallest bin with cumulative count > rank
        bstar = jnp.sum((cum <= rank[..., None]).astype(jnp.int32), axis=-1)
        prev = jnp.maximum(bstar - 1, 0)[..., None]
        below = jnp.where(
            bstar > 0, jnp.take_along_axis(cum, prev, axis=-1)[..., 0], 0)
        sq_cum = jnp.cumsum(sq, axis=-1)
        sq_below = jnp.where(
            bstar > 0, jnp.take_along_axis(sq_cum, prev, axis=-1)[..., 0], 0.0)
        sq_incl = jnp.take_along_axis(sq_cum, bstar[..., None], axis=-1)[..., 0]
        # inner levels: Σx² strictly below the bracket; last level: inclusive,
        # completing S(v) = Σ x²·[x <= v] for the resolved order statistic
        sqb = sqb + jnp.where(j == _LEVELS - 1, sq_incl, sq_below)
        rank = rank - below
        lo = lo + jax.lax.shift_left(bstar, shift)
        return lo, rank, sqb

    lo, _, sqb = jax.lax.fori_loop(0, _LEVELS, level, (lo0, rank0, sq0))
    v = jax.lax.bitcast_convert_type(lo, jnp.float32)         # (m, P, S)
    v0, v1 = v[:, 0], v[:, 1]
    # jnp.quantile's exact linear-interpolation arithmetic (bit-equal)
    t = v0 * (1.0 - frac) + v1 * frac
    # no data value lies strictly between adjacent order statistics
    ss = jnp.where(t < v1, sqb[:, 0], sqb[:, 1])
    return t[:m_real], ss[:m_real]


@functools.partial(jax.jit, static_argnames=("interpret",))
def row_trimmed_stats_multilevel(rows, q, *, scale=None,
                                 interpret: bool = False):
    """Drop-in for ``row_trimmed_stats`` on rows too long for one VMEM block.

    rows (R, L) signed, q (R,) levels.  Each row is its own single-segment
    client; column padding to the tile size is marked inert via seg id -1.
    ``scale`` (R,) is the per-row dequant scale of quantized rows (the rows
    keep their admitted dtype end to end).
    """
    R, L = rows.shape
    Cp = -(-L // TILE) * TILE
    Rp = R + (-R) % _row_block(R)
    if scale is None:
        rows = rows.astype(jnp.float32)
    if (Rp, Cp) != (R, L):     # one staging copy pads both axes
        rows = jnp.zeros((Rp, Cp), rows.dtype).at[:R, :L].set(rows)
        q = jnp.ones((Rp,), jnp.float32).at[:R].set(q.astype(jnp.float32))
        if scale is not None:
            scale = jnp.ones((Rp,), jnp.float32).at[:R].set(
                scale.astype(jnp.float32))
    col = jax.lax.iota(jnp.int32, Cp)
    seg_id = jnp.where(col < L, 0, -1)
    seg_len = jnp.full((1,), L, jnp.int32)
    t, ss = segmented_trimmed_stats(
        rows, seg_id, seg_len, q.reshape(Rp, 1).astype(jnp.float32),
        scales=None if scale is None else
        scale.reshape(Rp, 1).astype(jnp.float32),
        interpret=interpret)
    return t[:R, 0], ss[:R, 0]


def histogram_elems(rows: int, segs: int) -> int:
    """Upper bound on one level's cross-shard histogram payload in elements
    (count + Σx² planes, even if XLA merges them into one tuple all-reduce):
    independent of row length, never O(N).  ``rows`` is the per-data-shard
    client count."""
    return 2 * rows * _PATHS * segs * _BINS


def multilevel_quantile_contract(slice_bytes=None, *, padded: bool = False,
                                 name: str = "quantile/multilevel"):
    """Declared contract of the two-stage path: however long the row, the
    traced program contains exactly ONE row-sized read site (the histogram
    pallas_call inside the level loop — while bodies are recursed, the call
    is one static site) and zero sorts.  ``padded=True`` covers the
    non-tile-dividing wrapper, whose pad-copy adds one read + scatter.
    ``slice_bytes`` (the local (m, C) slice) budgets the compiled peak at 6x
    the slice: the slice, its padded copy and interpret staging."""
    from repro.analysis.contracts import Contract
    peak = {} if slice_bytes is None else dict(
        peak_live_bytes_per_device=(None, 6 * slice_bytes))
    return Contract(name=name,
                    description="two-stage multilevel trimmed quantile",
                    row_reads=(1, 2) if padded else 1, sorts=0, **peak)


def distributed_quantile_contract(rows: int, segs: int, slice_bytes=None,
                                  peak_mult: int = 8):
    """ISSUE 9 / PR 7 follow-up (b): the distributed trimmed-norm pass over
    P("data","model") rows.  Exactly 1 row read, 0 sorts, and ZERO gathers
    or re-layout collectives — the only cross-shard traffic is the psum of
    the per-level histogram planes, bounded at 2·rows·paths·segs·bins
    elements (count + Σx² planes; histogram-sized, never O(N)).  ``rows``
    is the PER-DATA-SHARD client count; ``slice_bytes`` the local
    (rows, N/model) slice, budgeting the peak WITHOUT the retired
    model-replicated (m/D, N) transient."""
    from repro.analysis.contracts import Contract
    hist = histogram_elems(rows, segs)
    peak = {} if slice_bytes is None else dict(
        peak_live_bytes_per_device=(None, peak_mult * slice_bytes))
    return Contract(name="quantile/dist",
                    description="distributed two-stage trimmed quantile",
                    row_reads=1, sorts=0,
                    all_gathers=0, reduce_scatters=0, all_to_alls=0,
                    collective_permutes=0,
                    allreduce_max_elems=hist, **peak)
