"""Flat-buffer aggregation engine: Alg. 1 on one contiguous (m, N) buffer.

The tree engine in ``repro.core.fedfa`` runs Alg. 1 as per-leaf tree-maps
inside a ``lax.scan`` over clients — O(leaves x clients) tiny dispatches and
a serial reduction.  This module packs the parameter pytree into a single
contiguous f32 buffer per client (``FlatIndex`` records the static layout:
leaf offsets/shapes/dtypes, per-row segment ids, depth-stage info and graft
gather maps) and reimplements the algorithm as a handful of segment-wise
passes over the flat cohort buffer:

  * graft (Alg. 2)          — per-leaf row gathers per client,
  * trimmed norms (§4.3)    — per-(client, segment) quantile threshold AND
                              trimmed sum-of-squares fused into ONE pass
                              over each cohort row via the Pallas
                              ``fedfa_quantile`` kernel on TPU (jnp top_k
                              tail path elsewhere),
  * (M', γ) accumulation    — two fused weighted reductions over the client
                              axis via the Pallas ``scaled_accum`` kernel on
                              TPU (the jnp ``ref`` path elsewhere).

Per-client weights that vary only per (leaf, row) — depth gates, data
counts, scaling factors α — live in small (m, n_segments) tables broadcast
onto the buffer leaf by leaf (``_expand_segments``), so the elementwise
work is a single fused pass regardless of how many leaves the model has.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import tree_flatten_with_path

from repro.configs.base import ArchConfig
# one classification rule shared with the tree engine (fedfa imports this
# module only lazily, so no cycle)
from repro.core.fedfa import _path_stage_info
from repro.core.masking import (AX, active_fraction, axis_mask_tree,
                                mask_density)
from repro.kernels import use_pallas
from repro.kernels.fedfa_agg import ops as agg_ops
from repro.kernels.fedfa_quantile import multilevel as quant_ml
from repro.kernels.fedfa_quantile import ops as quant_ops
from repro.models.masks import WidthMasks

Params = Dict[str, Any]
_IS_AX = lambda x: isinstance(x, AX)


@dataclass(frozen=True)
class LeafSpec:
    path: Tuple
    shape: Tuple[int, ...]
    dtype: Any
    offset: int
    size: int
    stacked: bool            # has a leading repeat axis
    stage: Optional[int]     # stage index for "stages" leaves, else None
    lead: int                # rows R (1 for unstacked leaves)
    rest: int                # elements per row
    seg0: int                # first global segment id of this leaf


class FlatIndex:
    """Static flat layout of a parameter pytree (host-side numpy).

    Segments are (leaf, row) pairs: one per repeat of a depth-stacked leaf,
    one per unstacked leaf — exactly the granularity at which trimmed norms,
    scaling factors and depth gates vary.

    ``pad_to`` rounds the flat length up to a multiple of the mesh
    model-shard count (``n_padded``) so the (N,) axis divides evenly when
    sharded over ``model`` — mirroring the inert ``n_data = 0`` client rows
    of ``repro.sharding.cohort``.  The tail ``[n, n_padded)`` is an inert
    zero segment: buffers are zero there, the width-mask density is zero
    (so contrib/counts vanish and the γ = 0 rule keeps the merged global at
    zero), the graft map is the identity, and no ``LeafSpec`` covers it, so
    trimmed norms and α never see it.  All leaf offsets stay static and
    independent of the padding.
    """

    def __init__(self, params: Params, pad_to: int = 1):
        leaves, self.treedef = tree_flatten_with_path(params)
        specs, row_of, seg_row, seg_stage0 = [], [], [], []
        off = seg = 0
        for path, x in leaves:
            stacked, stage = _path_stage_info(path)
            shape = tuple(x.shape)
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            lead = shape[0] if stacked else 1
            rest = size // lead
            specs.append(LeafSpec(path, shape, jnp.result_type(x), off, size,
                                  stacked, stage, lead, rest, seg))
            row_of.append(np.repeat(
                np.arange(seg, seg + lead, dtype=np.int32), rest))
            seg_row.extend(range(lead))
            seg_stage0.extend([stacked and stage == 0] * lead)
            off += size
            seg += lead
        self.leaves = tuple(specs)
        self.n = off
        self.n_segments = seg
        pad = (-off) % max(int(pad_to), 1)
        self.n_padded = off + pad
        if pad:                      # inert tail: density 0, identity graft
            row_of.append(np.zeros(pad, np.int32))
        self.row_of = np.concatenate(row_of)
        self.seg_row = np.asarray(seg_row, np.int32)
        self.seg_stage0 = np.asarray(seg_stage0)


def _segment_maps(index: FlatIndex):
    """Static per-position segment map for the two-stage distributed
    quantile: (seg_id, seg_len, leaf_of_seg) numpy arrays, memoized on the
    index.  ``seg_id`` (n_padded,) is ``row_of`` with the inert pad tail
    remapped to -1 (``row_of`` stores 0 there so weight gathers stay
    in-bounds, but the quantile kernel must EXCLUDE pads, not bin them into
    segment 0); ``seg_len`` (S,) is the global element count per segment and
    ``leaf_of_seg`` (S,) maps each segment to its leaf (for per-leaf active
    fractions)."""
    maps = getattr(index, "_segment_maps", None)
    if maps is None:
        seg_id = index.row_of.astype(np.int32).copy()
        seg_id[index.n:] = -1
        seg_len = np.zeros(index.n_segments, np.int32)
        leaf_of = np.zeros(index.n_segments, np.int32)
        for li, spec in enumerate(index.leaves):
            seg_len[spec.seg0:spec.seg0 + spec.lead] = spec.rest
            leaf_of[spec.seg0:spec.seg0 + spec.lead] = li
        maps = (seg_id, seg_len, leaf_of)
        index._segment_maps = maps
    return maps


_INDEX_CACHE: "OrderedDict[Any, FlatIndex]" = OrderedDict()
_INDEX_CACHE_MAX = 64


def get_index(params: Params, pad_to: int = 1) -> FlatIndex:
    """Build (or fetch the cached) FlatIndex for this params structure.

    Keyed on the treedef *and* the leaf (shape, dtype) layout: two pytrees
    with different container structure can share the same flatten order (e.g.
    a tuple vs a list at the same path), and unflatten must restore the right
    one.  ``pad_to`` (the mesh model-shard count, see ``FlatIndex``)
    participates in the key — the same tree padded for different meshes has
    different buffer widths.  LRU-bounded so long-lived processes over many
    model configs don't grow the cache without limit.
    """
    leaves, treedef = tree_flatten_with_path(params)
    key = (treedef, int(pad_to),
           tuple((tuple(x.shape), jnp.result_type(x).name) for _, x in leaves))
    idx = _INDEX_CACHE.get(key)
    if idx is None:
        idx = _INDEX_CACHE[key] = FlatIndex(params, pad_to=pad_to)
        while len(_INDEX_CACHE) > _INDEX_CACHE_MAX:
            _INDEX_CACHE.popitem(last=False)
    else:
        _INDEX_CACHE.move_to_end(key)
    return idx


def _check_layout(index: FlatIndex, leaves, stacked: bool) -> None:
    """Trace-time guard: the tree being packed must have the leaf layout the
    index was built from (jax.tree.leaves order == tree_flatten_with_path
    order), else offsets would silently misalign."""
    drop = 1 if stacked else 0
    if len(leaves) != len(index.leaves) or any(
            tuple(x.shape[drop:]) != s.shape
            for x, s in zip(leaves, index.leaves)):
        raise ValueError("tree structure does not match FlatIndex layout")


def flatten(index: FlatIndex, tree: Params) -> jax.Array:
    """Pack one pytree into a contiguous (n_padded,) f32 buffer (the inert
    tail, if any, is zeros)."""
    leaves = jax.tree.leaves(tree)
    _check_layout(index, leaves, stacked=False)
    parts = [jnp.ravel(x).astype(jnp.float32) for x in leaves]
    if index.n_padded > index.n:
        parts.append(jnp.zeros((index.n_padded - index.n,), jnp.float32))
    return jnp.concatenate(parts)


def flatten_stacked(index: FlatIndex, tree: Params) -> jax.Array:
    """Pack a client-stacked pytree (leading axis m) into (m, n_padded) f32
    (zero inert tail)."""
    leaves = jax.tree.leaves(tree)
    _check_layout(index, leaves, stacked=True)
    m = leaves[0].shape[0]
    parts = [x.reshape(m, -1).astype(jnp.float32) for x in leaves]
    if index.n_padded > index.n:
        parts.append(jnp.zeros((m, index.n_padded - index.n), jnp.float32))
    return jnp.concatenate(parts, axis=1)


def unflatten(index: FlatIndex, buf: jax.Array) -> Params:
    """Unpack a (n_padded,) buffer back into the pytree (original leaf
    dtypes); the inert tail is dropped."""
    outs = [buf[s.offset:s.offset + s.size].reshape(s.shape).astype(s.dtype)
            for s in index.leaves]
    return jax.tree_util.tree_unflatten(index.treedef, outs)


def _density_and_fraction(cfg: ArchConfig, index: FlatIndex, mk: WidthMasks):
    """One client's flat 0/1 width-mask density (n_padded,) and per-leaf
    active fraction (n_leaves,).  The inert tail has density 0, which keeps
    the pad region out of both (M', γ) sums."""
    ax = axis_mask_tree(cfg, mk)
    by_path = dict(tree_flatten_with_path(ax, is_leaf=_IS_AX)[0])
    dens, fracs = [], []
    for spec in index.leaves:
        axl = by_path[spec.path]
        d = jnp.broadcast_to(mask_density(spec.shape, axl), spec.shape)
        dens.append(jnp.ravel(d).astype(jnp.float32))
        fracs.append(active_fraction(axl))
    if index.n_padded > index.n:
        dens.append(jnp.zeros((index.n_padded - index.n,), jnp.float32))
    return jnp.concatenate(dens), jnp.stack(fracs)


def _graft_flat(index: FlatIndex, buf: jax.Array, gmap: jax.Array) -> jax.Array:
    """Alg. 2 on the flat buffer: stage-0 row r of every stacked leaf takes
    row gmap[r]; everything else is the identity.  Each leaf's rows move
    as whole (lead, rest) row gathers, so no N-sized index is built."""
    parts = []
    for s in index.leaves:
        x = buf[s.offset:s.offset + s.size]
        if s.stacked and s.stage == 0:
            rows = jnp.take(gmap, jnp.arange(s.lead), mode="clip")
            x = jnp.take(x.reshape(s.lead, s.rest), rows, axis=0,
                         mode="clip").reshape(-1)
        parts.append(x)
    if index.n_padded > index.n:
        parts.append(buf[index.n:])
    return jnp.concatenate(parts)


def _expand_segments(index: FlatIndex, w: jax.Array,
                     fill: float = 0.0) -> jax.Array:
    """(m, S) per-segment values -> (m, n_padded) columns: each leaf row's
    value broadcast over its ``rest`` columns, ``fill`` on the inert tail.
    Broadcasts and one concatenate, which fuse into the consumer — a
    column gather through ``row_of`` would instead come out of XLA as an
    (N, m) gather whose minor m axis the TPU pads to 128 lanes."""
    m = w.shape[0]
    parts = [jnp.broadcast_to(w[:, s.seg0:s.seg0 + s.lead, None],
                              (m, s.lead, s.rest)).reshape(m, s.size)
             for s in index.leaves]
    if index.n_padded > index.n:
        parts.append(jnp.full((m, index.n_padded - index.n), fill, w.dtype))
    return jnp.concatenate(parts, axis=1)


def _segment_max(index: FlatIndex, x: jax.Array) -> jax.Array:
    """(m, n_padded) -> (m, S): per-(row, segment) max, leaf by leaf (the
    inert tail belongs to no segment)."""
    m = x.shape[0]
    return jnp.concatenate(
        [jnp.max(x[:, s.offset:s.offset + s.size].reshape(m, s.lead, s.rest),
                 axis=2) for s in index.leaves], axis=1)


# ---------------------------------------------------------------------------
# Quantized admission: per-(client, segment) symmetric scales
# ---------------------------------------------------------------------------

UPDATE_DTYPES = ("f32", "bf16", "int8")


def update_dtype_of(name: str):
    """jnp dtype for an ``--update-dtype`` name (the cohort admission tier)."""
    if name not in UPDATE_DTYPES:
        raise ValueError(f"update_dtype must be one of {UPDATE_DTYPES}, "
                         f"got {name!r}")
    return {"f32": jnp.float32, "bf16": jnp.bfloat16,
            "int8": jnp.int8}[name]


def quantize_cohort(index: FlatIndex, x: jax.Array,
                    update_dtype: str) -> Tuple[jax.Array, jax.Array]:
    """Quantize a grafted, density-masked (m, n_padded) f32 cohort to the
    admission dtype.  Returns (x_q, scales (m, S) f32).

    int8: symmetric per-(client, segment) scales — scale = max|x|/127 over
    the segment, one max-reduction per leaf.  All-zero segments keep scale
    0, so both quantize and dequantize map them to exact zeros, and the
    inert pad tail stores zeros.  bf16: a plain downcast; scales are
    all-ones so the fused consumers treat both tiers uniformly.  f32
    passes through (identity scales).
    """
    m = x.shape[0]
    S = index.n_segments
    if update_dtype == "f32":
        return x, jnp.ones((m, S), jnp.float32)
    if update_dtype == "bf16":
        return x.astype(jnp.bfloat16), jnp.ones((m, S), jnp.float32)
    seg_max = _segment_max(index, jnp.abs(x))                  # (m, S)
    scales = seg_max / 127.0
    safe = jnp.where(seg_max > 0, scales, 1.0)
    q = jnp.clip(jnp.round(x / _expand_segments(index, safe, fill=1.0)),
                 -127.0, 127.0)
    if index.n_padded > index.n:
        q = q.at[:, index.n:].set(0.0)
    return q.astype(jnp.int8), scales


def dequantize_cohort(index: FlatIndex, x_q: jax.Array,
                      scales: jax.Array) -> jax.Array:
    """f32 (m, n_padded) view of a quantized cohort: x_q · scale[col].  The
    inert pad tail expands to scale 0, so it dequantizes to exact zeros.  bf16 cohorts carry all-ones scales (plain upcast).  Used
    by error feedback, oracles and jnp fallbacks — the hot aggregation path
    never materializes this (m, N) product; dequantization is fused into
    the kernels via per-segment scale tables."""
    return x_q.astype(jnp.float32) \
        * _expand_segments(index, scales.astype(jnp.float32))


def _row_quantile(rows_abs: jax.Array, q: jax.Array, trim: float) -> jax.Array:
    """Per-row ``jnp.quantile(rows_abs, q, axis=-1)`` with per-client q,
    computed exactly from the top-(1-trim) tail via ``lax.top_k`` — the only
    part of the sorted order the threshold can touch, since q >= trim.
    O(L log k) instead of a full O(L log L) sort.  rows_abs (m, R, L),
    q (m,) -> (m, R)."""
    m, R, L = rows_abs.shape
    k = min(L, int(np.ceil((1.0 - trim) * (L - 1))) + 2)
    top = jax.lax.top_k(rows_abs, k)[0]            # (m, R, k) descending
    p = q * (L - 1)                                # fractional sort position
    i0 = jnp.floor(p)
    frac = (p - i0).astype(rows_abs.dtype)
    d0 = (L - 1) - i0.astype(jnp.int32)            # descending index of floor
    d1 = jnp.maximum(d0 - 1, 0)                    # descending index of ceil
    take = lambda d: jnp.take_along_axis(
        top, jnp.broadcast_to(d[:, None, None], (m, R, 1)), axis=-1,
        mode="clip")[..., 0]
    v0, v1 = take(d0), take(d1)
    return v0 + (v1 - v0) * frac[:, None]


def _rows_trimmed_sq(rows: jax.Array, t: jax.Array) -> jax.Array:
    """Σ w²·[|w|<=t] over the last axis. rows (m, R, L), t (m, R) -> (m, R).
    Companion of the jnp top_k path; the kernel path fuses this reduction
    into the quantile pass itself (``_rows_trimmed_stats``)."""
    return jnp.sum(jnp.where(jnp.abs(rows) <= t[..., None], rows * rows, 0.0),
                   axis=-1)


def _rows_trimmed_stats(rows: jax.Array, q: jax.Array, trim: float,
                        use_kernel: bool, interpret: bool,
                        scale: Optional[jax.Array] = None) -> Tuple:
    """Per-row (quantile threshold, trimmed Σw²) for SIGNED rows (m, R, L)
    with per-client q (m,) -> ((m, R), (m, R)).

    Kernel path (``use_kernel``/``interpret``): the fused Pallas
    ``fedfa_quantile`` kernel — threshold by bit-pattern count-and-partition
    plus the trimmed reduction in one read of each row.  jnp path: exact
    top-(1-trim) tail quantile (``_row_quantile``) then a masked reduction —
    separate passes over the data.

    ``scale`` (m, R) dequantizes quantized rows on the fly: the kernel path
    forwards it as a per-row constant (the rows stay in their admitted
    dtype, read once); the jnp path materializes the f32 product first.
    """
    m, R, L = rows.shape
    if use_pallas(use_kernel, interpret):
        t, sq = quant_ops.row_trimmed_stats(
            rows.reshape(m * R, L), jnp.repeat(q, R),
            scale=None if scale is None else scale.reshape(m * R),
            use_kernel=use_kernel, interpret=interpret)
        return t.reshape(m, R), sq.reshape(m, R)
    rows_f = rows.astype(jnp.float32)
    if scale is not None:
        rows_f = rows_f * scale[..., None].astype(jnp.float32)
    rows_abs = jnp.abs(rows_f)
    t = _row_quantile(rows_abs, q, trim)
    return t, _rows_trimmed_sq(rows_abs, t)


def _cohort_norms(index: FlatIndex, xm: jax.Array, fracs: jax.Array,
                  trim: float, use_kernel: bool, interpret: bool,
                  mesh=None, scales: Optional[jax.Array] = None) -> jax.Array:
    """Per-(client, segment) trimmed norms: (m, N) masked updates +
    (m, n_leaves) active fractions -> (m, S).

    Every op here — per-leaf slicing along N, |.|, the quantile threshold,
    the trimmed sum of squares — is independent per client, so under a mesh
    the whole pass runs inside ``shard_map`` on each device's client shard
    (the fused quantile kernel is per-row and adds no collective).  Left to
    sharding propagation, XLA's top_k partitioning instead all-gathers the
    client axis leaf by leaf, which re-materializes the cohort buffer on
    every device.

    With real model shards (and the kernel path selected) the pass is 2-D:
    each device runs the segmented two-stage quantile on its
    (m/D, N/n_model) slice of the P("data", "model") buffer and the only
    cross-shard traffic is the psum of per-level histogram planes over
    ``model`` (``kernels.fedfa_quantile.multilevel``) — the model-replicated
    (m/D, N) transient is gone.  Requires the index padded with
    ``sharding.cohort.pad_unit`` so the local slice tiles the kernel evenly;
    otherwise the pass falls back to the model-replicated layout.

    ``scales`` (m, S) declares ``xm`` quantized (int8/bf16): per-segment
    dequant scales ride into the quantile kernels as per-row / per-segment
    constants — the rows are never re-materialized as f32.
    """

    def norms_local(xm_l, fracs_l, *rest):
        sc_l = rest[0] if rest else None
        m_l = xm_l.shape[0]
        cols = []
        for li, spec in enumerate(index.leaves):
            rows = xm_l[:, spec.offset:spec.offset + spec.size] \
                .reshape(m_l, spec.lead, spec.rest)
            # shifted quantile: the trim-quantile of active magnitudes equals
            # the 1-(1-trim)·f quantile of the zero-padded row
            q = 1.0 - (1.0 - trim) * fracs_l[:, li]
            sc = None if sc_l is None else sc_l[:, spec.seg0:spec.seg0
                                                + spec.lead]
            _, sq = _rows_trimmed_stats(rows, q, trim, use_kernel, interpret,
                                        scale=sc)
            cols.append(jnp.sqrt(sq))
        return jnp.concatenate(cols, axis=1)

    from repro.sharding import cohort as csh
    extra = () if scales is None else (scales,)
    if not csh.shardable(mesh, xm.shape[0]):
        return norms_local(xm, fracs, *extra)
    from jax.sharding import PartitionSpec as P
    ms = csh.model_shards(mesh)
    extra_spec = () if scales is None else (P("data", None),)
    if (ms > 1 and use_pallas(use_kernel, interpret)
            and xm.shape[1] % (ms * quant_ml.TILE) == 0):
        seg_id, seg_len, leaf_of = _segment_maps(index)

        def norms_2d(xm_l, fracs_l, seg_l, *rest):
            q_seg = 1.0 - (1.0 - trim) * fracs_l[:, jnp.asarray(leaf_of)]
            _, sq = quant_ml.segmented_trimmed_stats(
                xm_l, seg_l[0], jnp.asarray(seg_len), q_seg,
                scales=rest[0] if rest else None,
                axis_name=csh.MODEL_AXIS, interpret=interpret)
            return jnp.sqrt(sq)

        # seg_id enters as a host constant (constvar, not a broadcast eqn)
        # so the traced program's only row-sized read is the kernel itself
        return jax.shard_map(
            norms_2d, mesh=mesh,
            in_specs=(P("data", "model"), P("data", None),
                      P(None, "model")) + extra_spec,
            out_specs=P("data", None), check_vma=False)(
                xm, fracs, np.asarray(seg_id)[None, :], *extra)
    return jax.shard_map(norms_local, mesh=mesh,
                     in_specs=(P("data", None), P("data", None)) + extra_spec,
                     out_specs=P("data", None), check_vma=False)(
                         xm, fracs, *extra)


def aggregate_buffers(index: FlatIndex, g_flat: jax.Array, x: jax.Array,
                      cfg: ArchConfig, masks: WidthMasks, gates: jax.Array,
                      gmaps: jax.Array, n_data: jax.Array, *,
                      graft: bool = True, pregrafted: bool = False,
                      scale: bool = True, scales: Optional[jax.Array] = None,
                      trim: float = 0.95, eps: float = 1e-12,
                      use_kernel: Optional[bool] = None,
                      interpret: bool = False, mesh=None) -> jax.Array:
    """Alg. 1 entirely in flat space: (N,) global + (m, N) cohort buffers in,
    (N,) new global out — no pytree packing/unpacking, so the resident
    multi-round driver (``repro.core.round``) can keep both buffers donated
    across rounds.  ``aggregate_flat`` below is the tree-in/tree-out wrapper.

    With ``mesh`` set, the client axis m is laid out over the mesh ``data``
    axis (``repro.sharding.cohort``).  With real model shards and the
    kernel path, the N axis splits EARLY: densities, the distributed
    two-stage trimmed-norm pass (histogram psums over ``model``, see
    ``_cohort_norms``) and both fused (M', γ) reductions consume
    P("data", "model") slices directly — per-shard partial sums finished
    by one N/n_model psum over ``data``, no reduce-scatter, so M', Γ, and
    the merged global below live as N/n_model slices per device — zero
    all-gathers in the lowering, with ``g_flat`` consumed shard-locally by
    the γ = 0 merge.  Only the graft gather (a data-dependent cross-shard
    row permutation) still opens a transient model-replicated window;
    ``pregrafted=True`` declares the rows were grafted upstream (the async
    admit does this), keeping graft-on weighting semantics while skipping
    the gather — the program is then 2-D end-to-end.  Cohorts padded
    with ``n_data = 0`` rows aggregate identically to the unpadded cohort:
    zero weight in both sums, and excluded from the α mean below.  The
    parameter axis's inert zero tail (``index.n_padded``, see ``FlatIndex``)
    is likewise invisible: density 0 in both sums and outside every norm
    segment.

    ``scales`` (m, S) switches the cohort to QUANTIZED admission: ``x`` is
    int8/bf16, already grafted AND density-masked (``quantize_cohort``
    quantizes x·dens, so the 0/1 width mask is baked into the stored
    values).  Dequantization is fused into every consumer — the trimmed
    norms read the rows through per-segment scale constants, and the (M')
    reduction folds scale·α·gate into the per-(client, segment) weight
    table of ``agg_ops.accumulate_quant`` — so no f32 (m, N) dequantized
    transient ever exists.  The γ counts side is mask data, identical to
    the f32 path.
    """
    from repro.sharding import cohort as csh
    if scales is not None and graft and not pregrafted:
        raise ValueError("quantized cohorts must be grafted before "
                         "quantization (pass pregrafted=True)")
    use_kernel = use_pallas(use_kernel, interpret)
    ms = csh.model_shards(mesh)
    two_d = (ms > 1 and csh.shardable(mesh, x.shape[0]) and use_kernel
             and index.n_padded % (ms * quant_ml.TILE) == 0)
    constrain = ((lambda a: csh.constrain_cohort_buffer(a, mesh)) if two_d
                 else (lambda a: csh.constrain_cohort(a, mesh)))

    dens_fn = jax.vmap(functools.partial(_density_and_fraction, cfg, index))
    with jax.named_scope("fedfa.density"):
        if two_d:
            # build each device's (m/D, N/n_model) density slice
            # SHARD-LOCALLY: left to propagation, GSPMD reshards the
            # per-leaf concatenate onto the model axis with a zero-pad +
            # row-width all-reduce — exactly the model-replicated (m/D, N)
            # transient this path retires
            from jax.sharding import PartitionSpec as P

            def _dens_local(mk):
                d, f = dens_fn(mk)
                cols = index.n_padded // ms
                k = jax.lax.axis_index(csh.MODEL_AXIS)
                return (jax.lax.dynamic_slice_in_dim(d, k * cols, cols,
                                                     axis=1), f)

            dens, fracs = jax.shard_map(
                _dens_local, mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P(csh.DATA_AXIS), masks),),
                out_specs=(P(csh.DATA_AXIS, csh.MODEL_AXIS),
                           P(csh.DATA_AXIS, None)),
                check_vma=False)(masks)
        else:
            dens, fracs = dens_fn(masks)
            dens = constrain(dens)
    with jax.named_scope("fedfa.graft"):
        x_g = x
        if graft and not pregrafted:
            x_g = jax.vmap(functools.partial(_graft_flat, index))(
                csh.constrain_cohort(x, mesh), gmaps)
        x_g = constrain(x_g)

    if graft:
        dwrow = None   # grafting weights every depth slot equally (1.0)
    else:  # depth gates weight stage-0 rows; everything else weight 1
        dwrow = jnp.where(jnp.asarray(index.seg_stage0)[None, :],
                          jnp.take(gates, jnp.asarray(index.seg_row), axis=1,
                                   mode="clip"),
                          1.0)

    alpha = None
    if scale:
        with jax.named_scope("fedfa.quantile"):
            # quantized rows arrive density-masked, so the mask multiply
            # (an f32 (m, N) transient) only exists on the f32 path
            xm = x_g if scales is not None else x_g * dens
            norms = _cohort_norms(index, xm, fracs, trim, use_kernel,
                                  interpret, mesh, scales=scales)   # (m, S)
            # cross-client mean weighted by row validity: pad rows
            # (n_data = 0) must not shift α; with every row valid this is
            # exactly the mean
            valid = (n_data > 0).astype(jnp.float32)                # (m,)
            mean_norms = jnp.sum(valid[:, None] * norms, axis=0,
                                 keepdims=True) \
                / jnp.maximum(jnp.sum(valid), 1.0)
            alpha = mean_norms / jnp.maximum(norms, eps)

    gather = functools.partial(_expand_segments, index)          # (m, N)
    if alpha is None:
        warow = dwrow
    else:
        warow = alpha if dwrow is None else dwrow * alpha
    ones_n = jnp.ones((index.n_padded,), jnp.float32)
    with jax.named_scope("fedfa.accumulate"):
        if scales is not None:
            # fused dequantize-accumulate: scale·gate·α collapse into one
            # (m, S) weight table gathered per column INSIDE the kernel —
            # the quantized rows are read exactly once, with no (m, N) f32
            # product
            seg_id, _, _ = _segment_maps(index)
            coeff = scales if warow is None else warow * scales
            Mp = agg_ops.accumulate_quant(
                x_g, n_data, coeff, jnp.asarray(seg_id), ones_n,
                use_kernel=use_kernel, interpret=interpret, mesh=mesh,
                cohort_2d=two_d)
        else:
            contrib = constrain(
                x_g * dens if warow is None else x_g * dens * gather(warow))
            Mp = agg_ops.accumulate(contrib, n_data, ones_n,
                                    use_kernel=use_kernel,
                                    interpret=interpret, mesh=mesh,
                                    cohort_2d=two_d)
        counts = constrain(
            dens if dwrow is None else dens * gather(dwrow))
        Gm = agg_ops.accumulate(counts, n_data, ones_n,
                                use_kernel=use_kernel, interpret=interpret,
                                mesh=mesh, cohort_2d=two_d)

    with jax.named_scope("fedfa.merge"):
        upd = Mp / jnp.maximum(Gm, eps)
        return jnp.where(Gm > 0, upd, g_flat)  # γ = 0 keeps the global


def aggregate_flat(global_params: Params, stacked_params: Params,
                   cfg: ArchConfig, masks: WidthMasks, gates: jax.Array,
                   gmaps: jax.Array, n_data: jax.Array, *, graft: bool = True,
                   scale: bool = True, trim: float = 0.95, eps: float = 1e-12,
                   use_kernel: Optional[bool] = None,
                   interpret: bool = False) -> Params:
    """Alg. 1 on the flat cohort buffer; numerically matches the tree engine
    (``fedfa.aggregate``) within float tolerance for every strategy preset."""
    index = get_index(global_params)
    g_flat = flatten(index, global_params)                          # (N,)
    x = flatten_stacked(index, stacked_params)                      # (m, N)
    out = aggregate_buffers(index, g_flat, x, cfg, masks, gates, gmaps,
                            n_data, graft=graft, scale=scale, trim=trim,
                            eps=eps, use_kernel=use_kernel,
                            interpret=interpret)
    return unflatten(index, out)
