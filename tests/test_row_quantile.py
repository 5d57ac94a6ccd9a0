"""Property tests for the flat engine's exact tail quantile
(``flat._row_quantile``) against ``jnp.quantile`` at the edges the
top-(1-trim) tail trick could miss: endpoint quantile levels (f→0 and f=1
active fractions), L=1 rows, trim values where the tail size k clamps to
the full row, and bf16-cast rows (heavy ties)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flat


def _ref_quantile(rows_abs, q):
    """vmapped jnp.quantile: (m, R, L) rows + per-client q (m,) -> (m, R)."""
    return jax.vmap(lambda r, qq: jnp.quantile(r, qq, axis=-1))(rows_abs, q)


def _rows(m, R, L, seed=0):
    return jnp.abs(jax.random.normal(jax.random.PRNGKey(seed), (m, R, L)))


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("m,R,L", [(3, 2, 57), (2, 5, 260), (1, 1, 33)])
def test_row_quantile_matches_jnp_interior(seed, m, R, L):
    """Random shifted levels q in [trim, 1] — the production regime."""
    trim = 0.95
    rows = _rows(m, R, L, seed)
    q = jax.random.uniform(jax.random.PRNGKey(seed + 100), (m,),
                           minval=trim, maxval=1.0)
    np.testing.assert_allclose(
        np.asarray(flat._row_quantile(rows, q, trim)),
        np.asarray(_ref_quantile(rows, q)), rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("L", [1, 2, 50, 129])
def test_row_quantile_endpoint_q_one(L):
    """f→0 (all-inactive leaf) shifts the level to q=1: the row max, even
    though the interpolation indices sit at the very end of the tail."""
    rows = _rows(2, 3, L, seed=L)
    q = jnp.ones((2,))
    np.testing.assert_array_equal(
        np.asarray(flat._row_quantile(rows, q, 0.95)),
        np.asarray(rows.max(axis=-1)))


@pytest.mark.parametrize("trim", [0.95, 0.5])
def test_row_quantile_endpoint_q_trim(trim):
    """f=1 (fully active leaf) keeps q=trim — the lowest level the tail
    trick supports; the floor index is the deepest element the k-tail holds."""
    rows = _rows(3, 2, 101, seed=7)
    q = jnp.full((3,), trim)
    np.testing.assert_allclose(
        np.asarray(flat._row_quantile(rows, q, trim)),
        np.asarray(_ref_quantile(rows, q)), rtol=1e-6, atol=1e-7)


def test_row_quantile_single_element_rows():
    """L=1 (scalar leaves): every level returns the single element."""
    rows = _rows(4, 3, 1, seed=1)
    for qv in (0.95, 0.97, 1.0):
        np.testing.assert_array_equal(
            np.asarray(flat._row_quantile(rows, jnp.full((4,), qv), 0.95)),
            np.asarray(rows[..., 0]))


@pytest.mark.parametrize("L", [1, 2, 3])
def test_row_quantile_k_clamps_to_L(L):
    """Small rows where k = ceil((1-trim)(L-1))+2 >= L clamps to the full
    row: the 'tail' is the whole row and any q in [trim, 1] must be exact."""
    trim = 0.95
    assert min(L, int(np.ceil((1 - trim) * (L - 1))) + 2) == L
    rows = _rows(2, 4, L, seed=L + 10)
    q = jax.random.uniform(jax.random.PRNGKey(L), (2,), minval=trim,
                           maxval=1.0)
    np.testing.assert_allclose(
        np.asarray(flat._row_quantile(rows, q, trim)),
        np.asarray(_ref_quantile(rows, q)), rtol=1e-6, atol=1e-7)


def test_row_quantile_trim_zero_full_sort_regime():
    """trim=0 degenerates the tail to a full top_k: arbitrary q in [0, 1]
    must match jnp.quantile (k clamps to L for any L)."""
    rows = _rows(3, 2, 40, seed=2)
    q = jnp.asarray([0.0, 0.31, 1.0])
    np.testing.assert_allclose(
        np.asarray(flat._row_quantile(rows, q, 0.0)),
        np.asarray(_ref_quantile(rows, q)), rtol=1e-6, atol=1e-7)


def test_row_quantile_bf16_cast_rows():
    """bf16-cast rows tie heavily at bf16 resolution; the tail selection
    must still agree with the full-sort reference."""
    rows = _rows(3, 2, 300, seed=3).astype(jnp.bfloat16).astype(jnp.float32)
    q = jax.random.uniform(jax.random.PRNGKey(9), (3,), minval=0.95,
                           maxval=1.0)
    np.testing.assert_allclose(
        np.asarray(flat._row_quantile(rows, q, 0.95)),
        np.asarray(_ref_quantile(rows, q)), rtol=1e-6, atol=1e-7)


def test_row_quantile_all_zero_rows():
    """All-inactive (fully masked) leaves: zero rows give a zero threshold
    at every level, so the trimmed norm is 0 rather than NaN."""
    rows = jnp.zeros((2, 3, 64))
    out = flat._row_quantile(rows, jnp.asarray([0.95, 1.0]), 0.95)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


# ---------------------------------------------------------------------------
# two-stage multilevel kernel (ISSUE 9): exactness vs jnp.quantile
# ---------------------------------------------------------------------------

from repro.kernels.fedfa_quantile import multilevel as ml  # noqa: E402
from repro.kernels.fedfa_quantile import ops as qops  # noqa: E402


def _ulp_dist(a, b):
    """ulp distance between nonnegative f32 arrays (bit-pattern distance —
    monotone for same-sign floats)."""
    ai = np.asarray(a, np.float32).view(np.int32).astype(np.int64)
    bi = np.asarray(b, np.float32).view(np.int32).astype(np.int64)
    return np.abs(ai - bi)


def _check_multilevel(rows, q, rtol_ss=1e-5):
    """Pin the multilevel path against jnp.quantile on |rows|:

      * integral ranks (frac = 0) are pure order statistics — bit-equal;
      * interpolated thresholds are within 1 ulp of jnp's linear method
        (the reference's LAST ulp depends on whether XLA contracts the
        lerp into an fma, which is not part of the algorithm's contract);
      * t is bracketed by the 'lower'/'higher' order statistics, bitwise;
      * the fused trimmed Σw² matches a masked reference at the kernel's
        own threshold (rtol: summation order differs).
    """
    rows = jnp.asarray(rows, jnp.float32)
    q = jnp.asarray(q, jnp.float32)
    t, ss = ml.row_trimmed_stats_multilevel(rows, q, interpret=True)
    a_abs = jnp.abs(rows)
    ref = np.asarray(jax.vmap(jnp.quantile)(a_abs, q))
    lo = np.asarray(jax.vmap(
        lambda r, qq: jnp.quantile(r, qq, method="lower"))(a_abs, q))
    hi = np.asarray(jax.vmap(
        lambda r, qq: jnp.quantile(r, qq, method="higher"))(a_abs, q))
    t_np = np.asarray(t)
    # same f32 rank arithmetic as jnp.quantile: position, floor, fraction
    L = rows.shape[1]
    p = np.asarray(q, np.float32) * np.float32(L - 1)
    frac = p - np.floor(p)
    np.testing.assert_array_equal(t_np[frac == 0], ref[frac == 0])
    assert (_ulp_dist(t_np, ref) <= 1).all(), \
        f"threshold off by >1 ulp: {t_np} vs {ref}"
    assert (t_np >= lo).all() and (t_np <= hi).all()
    a_np = np.asarray(a_abs, np.float32)
    ref_ss = np.where(a_np <= t_np[:, None], a_np.astype(np.float64) ** 2,
                      0.0).sum(axis=1)
    np.testing.assert_allclose(np.asarray(ss), ref_ss, rtol=rtol_ss,
                               atol=1e-7)
    return t_np, frac


def test_multilevel_long_rows_vs_jnp():
    """L > 2**18 — past the single-pass VMEM budget, the regime the old
    dispatch silently handed to the jnp oracle.  q = 1 exercises an exact
    endpoint order statistic on the same long rows."""
    L = 2 ** 18 + 1536                       # tile-divisible: no pad column
    rows = jax.random.normal(jax.random.PRNGKey(0), (2, L), jnp.float32)
    _check_multilevel(rows, jnp.asarray([0.9731, 1.0]), rtol_ss=1e-4)


def test_multilevel_long_rows_integral_rank_bit_equal():
    """Integral-rank levels on L > 2**18 rows are pure order statistics and
    must be BIT-equal to jnp.quantile — the acceptance clause of ISSUE 9.
    Ranks are screened host-side with the same f32 arithmetic both sides
    use, so every case asserted is genuinely interpolation-free."""
    L = 2 ** 18 + 1536
    ks, qs = [], []
    for k in (0, 7919, L // 2, L - 2, L - 1):
        qv = np.float32(k) / np.float32(L - 1)
        if np.float32(qv) * np.float32(L - 1) == np.float32(k):
            ks.append(k)
            qs.append(qv)
    assert len(ks) >= 2, "no integral f32 ranks found"
    rows = jax.random.normal(jax.random.PRNGKey(1), (len(ks), L),
                             jnp.float32)
    t_np, frac = _check_multilevel(rows, jnp.asarray(qs), rtol_ss=1e-4)
    assert (frac == 0).all()                 # every case was exact-rank


def test_multilevel_one_bin_mass():
    """All mass in a single bit-pattern bin (constant rows): every level's
    bracketing bin holds the entire count and the resolved pattern is the
    constant itself, bit-equal, with ss = L·c²."""
    c = np.float32(3.14159)
    rows = jnp.full((2, 1024), c)
    t, ss = ml.row_trimmed_stats_multilevel(rows, jnp.asarray([0.95, 1.0]),
                                            interpret=True)
    np.testing.assert_array_equal(np.asarray(t), c)
    np.testing.assert_allclose(np.asarray(ss), 1024 * float(c) ** 2,
                               rtol=1e-5)


def test_multilevel_bf16_ties_across_bin_boundary():
    """bf16-cast rows tie heavily and pile up on byte-boundary bit
    patterns (a bf16 value's lower mantissa bytes are zero, landing ties
    exactly ON level boundaries): ranks must still resolve exactly."""
    rows = jax.random.normal(jax.random.PRNGKey(2), (3, 2048), jnp.float32) \
        .astype(jnp.bfloat16).astype(jnp.float32)
    _check_multilevel(rows, jnp.asarray([0.95, 0.9993, 1.0]))


def test_multilevel_all_zero_rows():
    """Fully masked rows: zero threshold and zero trimmed sum, not NaN."""
    rows = jnp.zeros((2, 1024))
    t, ss = ml.row_trimmed_stats_multilevel(rows, jnp.asarray([0.95, 1.0]),
                                            interpret=True)
    np.testing.assert_array_equal(np.asarray(t), 0.0)
    np.testing.assert_array_equal(np.asarray(ss), 0.0)


def test_multilevel_single_row_and_column_pad():
    """m = 1 with a non-tile-dividing length: the wrapper pads columns to
    the tile and marks them seg -1 — inert, never binned into segment 0."""
    rows = jax.random.normal(jax.random.PRNGKey(3), (1, 700), jnp.float32)
    _check_multilevel(rows, jnp.asarray([0.97]))


def test_multilevel_segmented_matches_per_segment_quantile():
    """The segment-aware entry point against per-segment jnp.quantile: one
    flat (m, C) slice holding three segments of different lengths, each
    with its own per-client level."""
    lens = (500, 260, 264)                   # sums to 2 * TILE
    C = sum(lens)
    assert C % ml.TILE == 0
    m, S = 2, len(lens)
    rows = jax.random.normal(jax.random.PRNGKey(4), (m, C), jnp.float32)
    seg_id = jnp.asarray(np.repeat(np.arange(S), lens).astype(np.int32))
    q_seg = jnp.asarray([[0.95, 1.0, 0.9737], [0.9871, 0.96, 1.0]],
                        jnp.float32)
    t, ss = ml.segmented_trimmed_stats(rows, seg_id,
                                       jnp.asarray(lens, jnp.int32), q_seg,
                                       interpret=True)
    t_np, ss_np = np.asarray(t), np.asarray(ss)
    start = 0
    for s, ln in enumerate(lens):
        seg = jnp.abs(rows[:, start:start + ln])
        ref = np.asarray(jax.vmap(jnp.quantile)(seg, q_seg[:, s]))
        lo = np.asarray(jax.vmap(
            lambda r, qq: jnp.quantile(r, qq, method="lower"))(seg, q_seg[:, s]))
        hi = np.asarray(jax.vmap(
            lambda r, qq: jnp.quantile(r, qq, method="higher"))(seg, q_seg[:, s]))
        assert (_ulp_dist(t_np[:, s], ref) <= 1).all()
        assert (t_np[:, s] >= lo).all() and (t_np[:, s] <= hi).all()
        a_np = np.asarray(seg, np.float32)
        ref_ss = np.where(a_np <= t_np[:, s][:, None],
                          a_np.astype(np.float64) ** 2, 0.0).sum(axis=1)
        np.testing.assert_allclose(ss_np[:, s], ref_ss, rtol=1e-5,
                                   atol=1e-7)
        start += ln


def test_dispatch_long_rows_take_multilevel_not_oracle():
    """ISSUE 9 bugfix pin: rows past the single-pass VMEM budget with the
    kernel path explicitly requested dispatch to the two-stage kernel —
    read-once, sort-free — NEVER to the jnp oracle (whose lowering sorts
    and re-reads the rows; see the companion contract test)."""
    from repro.analysis import jaxpr as jaxpr_mod
    L = 2 ** 18 + 512                        # Lp > _SINGLE_PASS_ELEMS
    rows = jax.random.normal(jax.random.PRNGKey(5), (2, L), jnp.float32)
    q = jnp.full((2,), 0.975, jnp.float32)
    c = jaxpr_mod.trace_counts(
        lambda r, qq: qops.row_trimmed_stats(r, qq, use_kernel=False,
                                             interpret=True),
        rows, q, row_elems=rows.size)
    assert (c.reads, c.sorts) == (1, 0)
    # and the result agrees with the multilevel path bit-for-bit
    t1, ss1 = qops.row_trimmed_stats(rows, q, use_kernel=False,
                                     interpret=True)
    t2, ss2 = ml.row_trimmed_stats_multilevel(rows, q, interpret=True)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(ss1), np.asarray(ss2))


# ---------------------------------------------------------------------------
# nibble levels: exact prefix compare, parted rank paths, Σx² over 8 levels
# ---------------------------------------------------------------------------

def _nibble_stats(path, segs, q):
    """(t, ss), both (m, S), over ``segs`` (a list of S (m, len) f32
    arrays) on the row path (S = 1) or through ``segmented_trimmed_stats``
    with the segments laid side by side and the slice padded to the tile
    with inert columns."""
    q = jnp.asarray(q, jnp.float32)
    if path == "row":
        assert len(segs) == 1
        t, ss = ml.row_trimmed_stats_multilevel(jnp.asarray(segs[0]),
                                                q[:, 0], interpret=True)
        return np.asarray(t)[:, None], np.asarray(ss)[:, None]
    lens = [s.shape[1] for s in segs]
    pad = (-sum(lens)) % ml.TILE
    x = np.concatenate(list(segs) + [np.zeros((q.shape[0], pad),
                                              np.float32)], axis=1)
    seg_id = np.concatenate([np.full(n, s, np.int32)
                             for s, n in enumerate(lens)]
                            + [np.full(pad, -1, np.int32)])
    t, ss = ml.segmented_trimmed_stats(
        jnp.asarray(x), jnp.asarray(seg_id), jnp.asarray(lens, jnp.int32), q,
        interpret=True)
    return np.asarray(t), np.asarray(ss)


def _check_nibble(path, segs, q, rtol_ss):
    """Each (row, segment) against jnp.quantile on its magnitudes: integral
    ranks bit-equal, every threshold within 1 ulp and bracketed by the
    'lower'/'higher' order statistics, and the trimmed Σx² against the
    sort-based sum of the squared magnitudes up to the threshold."""
    t, ss = _nibble_stats(path, segs, q)
    q = np.asarray(q, np.float32)
    for s, seg in enumerate(segs):
        a = np.abs(seg).astype(np.float32)
        qs = jnp.asarray(q[:, s])
        ref = np.asarray(jax.vmap(jnp.quantile)(jnp.asarray(a), qs))
        lo = np.asarray(jax.vmap(lambda r, qq: jnp.quantile(
            r, qq, method="lower"))(jnp.asarray(a), qs))
        hi = np.asarray(jax.vmap(lambda r, qq: jnp.quantile(
            r, qq, method="higher"))(jnp.asarray(a), qs))
        p = q[:, s] * np.float32(a.shape[1] - 1)
        exact = p == np.floor(p)
        np.testing.assert_array_equal(t[exact, s], ref[exact])
        assert (_ulp_dist(t[:, s], ref) <= 1).all(), (t[:, s], ref)
        assert (t[:, s] >= lo).all() and (t[:, s] <= hi).all()
        srt = np.sort(a, axis=1).astype(np.float64)
        for c in range(a.shape[0]):
            k = np.searchsorted(srt[c], t[c, s], side="right")
            np.testing.assert_allclose(ss[c, s], np.sum(srt[c, :k] ** 2),
                                       rtol=rtol_ss, atol=0)
    return t, ss


def _signed(a, rng):
    return np.where(rng.random(a.shape) < 0.5, -a, a).astype(np.float32)


def _last_nibble_seg(m, n, prefix_value, seed):
    """(m, n) rows whose magnitudes share every bit but the last nibble of
    ``prefix_value``, with a fifth of them zero (masked width): every order
    statistic past the zeros resolves only at the last level, where the
    prefix ``bits >> 4`` lies past 2^24."""
    rng = np.random.default_rng(seed)
    base = int(np.float32(prefix_value).view(np.int32)) & ~0xF
    assert base >> 4 >= 1 << 24
    bits = (base + rng.integers(0, 16, size=(m, n))).astype(np.int32)
    a = bits.view(np.float32)
    a = np.where(rng.random((m, n)) < 0.2, np.float32(0.0), a)
    return _signed(a, rng)


@pytest.mark.parametrize("path", ["row", "segmented"])
def test_multilevel_last_nibble_prefix_exact(path):
    """Order statistics that agree in all but their last nibble: the last
    level's in-bracket test compares a prefix past 2^24, which an f32
    gather would round onto a neighbour and so empty the bracket."""
    q_row = [0.95, 0.5, 1.0]
    if path == "row":
        segs = [_last_nibble_seg(3, 2000, 3.14159, seed=0)]
        q = np.asarray(q_row, np.float32)[:, None]
    else:
        segs = [_last_nibble_seg(3, n, v, seed=s) for s, (n, v) in
                enumerate([(500, 3.14159), (260, 1234.567), (264, 0.0271)])]
        q = np.asarray([q_row, [0.97, 1.0, 0.6], [0.51, 0.9737, 0.95]],
                       np.float32)
    t, _ = _check_nibble(path, segs, q, rtol_ss=1e-6)
    # the thresholds really are last-nibble neighbours of the prefix
    for s, seg in enumerate(segs):
        top = np.abs(seg).view(np.int32).max(axis=1) >> 4
        assert ((t[:, s].view(np.int32) >> 4) == top).all()


def _parted_seg(m, n, seed):
    """(m, n) rows with half their magnitudes in [1, 2) and half in [2, 4):
    the top nibble of the bit pattern is 3 for the first half and 4 for the
    second, and the level that splits them is the first."""
    rng = np.random.default_rng(seed)
    k = n // 2
    a = np.concatenate([rng.uniform(1.0, 2.0, (m, k)),
                        rng.uniform(2.0, 4.0, (m, n - k))], axis=1)
    a = np.take_along_axis(a, rng.permuted(
        np.tile(np.arange(n), (m, 1)), axis=1), axis=1)
    return _signed(a.astype(np.float32), rng), k


@pytest.mark.parametrize("path", ["row", "segmented"])
def test_multilevel_rank_paths_part_at_first_level(path):
    """The floor and ceil ranks fall in different bins of the first level
    (top nibbles 3 and 4), so the two rank paths refine different brackets
    from the start and the threshold interpolates across them."""
    lens = [1000] if path == "row" else [700, 324]
    segs, qs = [], []
    for s, n in enumerate(lens):
        seg, k = _parted_seg(2, n, seed=10 + s)
        q = np.float32((k - 0.5) / (n - 1))
        p = q * np.float32(n - 1)
        assert np.floor(p) == k - 1 and p > k - 1    # floor in [1, 2), ceil in [2, 4)
        segs.append(seg)
        qs.append(q)
    q = np.tile(np.asarray(qs, np.float32), (2, 1))
    t, _ = _check_nibble(path, segs, q, rtol_ss=1e-6)
    for s, seg in enumerate(segs):
        srt = np.sort(np.abs(seg), axis=1)
        k = lens[s] // 2
        v0, v1 = srt[:, k - 1], srt[:, k]
        assert ((v0.view(np.int32) >> 28) == 3).all()
        assert ((v1.view(np.int32) >> 28) == 4).all()
        assert ((t[:, s] > v0) & (t[:, s] < v1)).all()


def _every_level_seg(m, n, seed):
    """(m, n) rows built round a target bit pattern so that the bracket of
    every one of the 8 levels holds mass strictly below the target's bin and
    above it, plus ties of the target: each level's Σx² below-sum counts.
    Returns the rows and the target's rank (its first tie)."""
    rng = np.random.default_rng(seed)
    target = int(np.float32(0.7071).view(np.int32))
    out = np.empty((m, n), np.int32)
    ranks = []
    for c in range(m):
        vals = []
        for j in range(8):
            sh = 28 - 4 * j
            nib = (target >> sh) & 0xF
            keep = (target >> (sh + 4)) << (sh + 4) if sh + 4 < 32 else 0
            low = (1 << sh) - 1
            if nib > 0:      # same prefix, a smaller nibble: below at level j
                b = rng.integers(0, nib, 6)
                vals += [keep | (int(x) << sh) | int(rng.integers(0, low + 1))
                         for x in b]
            top = 5 if j == 0 else 16    # x² stays finite: below 2^33
            if nib + 1 < top:  # same prefix, a larger nibble: above at level j
                b = rng.integers(nib + 1, top, 6)
                vals += [keep | (int(x) << sh) | int(rng.integers(0, low + 1))
                         for x in b]
        ties = [target] * 3
        rest = n - len(vals) - len(ties)
        wide = np.abs(rng.standard_normal(rest)).astype(np.float32)
        row = np.concatenate([np.asarray(vals + ties, np.int32),
                              wide.view(np.int32)])
        row = row[(row >= 0) & (row < 0x7F800000)]   # finite magnitudes
        assert row.size == n
        out[c] = rng.permutation(row)
        ranks.append(int(np.sum(row < target)))
    return _signed(out.view(np.float32), rng), np.asarray(ranks)


@pytest.mark.parametrize("path", ["row", "segmented"])
def test_multilevel_trimmed_sum_over_all_levels(path):
    """Σx² at the threshold against the sort-based trimmed sum, on rows
    whose target order statistic has mass below its bin at each of the 8
    levels: the strictly-below sums of levels 0..6 and the inclusive sum of
    the last all enter, and their total matches to f32 rounding."""
    lens = [3000] if path == "row" else [1500, 548]
    segs, qs = [], []
    for s, n in enumerate(lens):
        seg, ranks = _every_level_seg(2, n, seed=20 + s)
        segs.append(seg)
        # a quantile level whose floor rank is the target's first tie
        qs.append([np.float32(r) / np.float32(n - 1) + np.float32(1e-7)
                   for r in ranks])
    q = np.asarray(qs, np.float32).T
    t, ss = _check_nibble(path, segs, q, rtol_ss=1e-6)
    for s, seg in enumerate(segs):
        target = np.float32(0.7071)
        a = np.abs(seg)
        assert (t[:, s] >= target).all()
        # mass strictly below the target's bin at every level is included
        below = np.where(a < target, a.astype(np.float64) ** 2, 0).sum(1)
        assert (ss[:, s] > below).all()
