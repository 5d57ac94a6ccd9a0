"""jit'd wrappers for the FedFA aggregation kernels (padding + dispatch)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import use_pallas
from repro.kernels.fedfa_agg import ref
from repro.kernels.fedfa_agg.kernel import (quant_accum, scaled_accum,
                                            trimmed_sumsq)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def trimmed_norm(w_flat: jax.Array, thresh: jax.Array, *,
                 use_kernel=None, interpret=False) -> jax.Array:
    """sqrt(Σ w²·[|w|<=t]) over a flat vector, any length (zero-padded)."""
    if not use_pallas(use_kernel, interpret):
        return jnp.sqrt(ref.trimmed_sumsq_ref(w_flat, thresh))
    lanes = 128
    n = w_flat.size
    padded = ((n + lanes - 1) // lanes) * lanes
    rows = padded // lanes
    block = min(2048, rows)
    rows_p = ((rows + block - 1) // block) * block
    w2 = jnp.zeros((rows_p * lanes,), w_flat.dtype).at[:n].set(w_flat)
    # padding zeros pass |0|<=t -> contribute 0 to the sum: safe.
    ss = trimmed_sumsq(w2.reshape(rows_p, lanes), thresh, block=block,
                       interpret=interpret)
    return jnp.sqrt(ss)


def _accum_local(x: jax.Array, weights: jax.Array, mask: jax.Array,
                 use_kernel: bool, interpret: bool) -> jax.Array:
    """The unsharded accumulate body: Σ_c weights[c]·x[c]·mask on whatever
    slice of the client axis this device holds."""
    if not use_kernel:
        return ref.scaled_accum_ref(x, weights, mask)
    m, n = x.shape
    block = 4096 if n >= 4096 else max(128, 1 << (n - 1).bit_length())
    pad = (-n) % block
    xp = jnp.pad(x, ((0, 0), (0, pad)))
    mp = jnp.pad(mask, (0, pad))
    out = scaled_accum(xp, weights, mp, block=block, interpret=interpret)
    return out[:n]


def _quant_accum_local(x: jax.Array, weights: jax.Array, wtab: jax.Array,
                       seg: jax.Array, mask: jax.Array,
                       use_kernel: bool, interpret: bool) -> jax.Array:
    """Unsharded fused dequantize-accumulate body: the per-client weight
    folds into the (m, S) table before the kernel, so the quantized rows
    are consumed by exactly one pass."""
    wt = wtab.astype(jnp.float32) * weights.astype(jnp.float32)[:, None]
    if not use_kernel:
        return ref.quant_accum_ref(x, wt, seg, mask)
    m, n = x.shape
    block = 4096 if n >= 4096 else max(128, 1 << (n - 1).bit_length())
    pad = (-n) % block
    xp = jnp.pad(x, ((0, 0), (0, pad)))
    sp = jnp.pad(seg, (0, pad), constant_values=-1)
    mp = jnp.pad(mask, (0, pad))
    out = quant_accum(xp, wt, sp, mp, block=block, interpret=interpret)
    return out[:n]


def accumulate_contract(n_padded: int, mesh=None, rows=None, segs=None):
    """Declared contract of the aggregation path built on ``accumulate``
    (``flat.aggregate_buffers`` lowered standalone on the round's own
    shardings — see ``repro.analysis.contracts``).

    Zero all-gathers, always: the (M', γ) reduction is a per-shard partial
    sum, never a replicated (m, n) re-gather.  On a multi-device data-only
    mesh the partial sums combine as 1-2 psums of exactly ``n_padded``
    elements and no all-reduce exceeds that.  With model shards the
    reductions consume the 2-D P("data", "model") cohort slices directly —
    the N axis is pre-split, so there is NO reduce-scatter: the partial
    sums finish with N-scale all-reduces of exactly ``n_padded / n_model``
    elements over ``data``, plus the distributed trimmed-quantile's
    histogram-plane psums over ``model`` (bounded via ``segs``, the
    segment count — histogram-sized, independent of N).

    With ``rows`` (the padded cohort row count) the contract also budgets
    the statically estimated per-device peak at ``(6 + 12*r) * N * 4``
    bytes, r = rows per data shard — the cohort shard plus the grafting /
    trimmed-norm / partial-sum intermediates (measured ~11-15 N-multiples
    on the canonical fixture; a replicated cohort blows it).
    """
    from repro.analysis.contracts import Contract
    from repro.kernels.fedfa_quantile.multilevel import histogram_elems
    from repro.sharding.cohort import data_shards, model_shards
    multi = mesh is not None and mesh.size > 1
    ms = model_shards(mesh)
    peak = {}
    r = max(1, (rows or 1) // data_shards(mesh))
    if rows is not None:
        peak = dict(
            peak_live_bytes_per_device=(None, (6 + 12 * r) * n_padded * 4))
    if not multi:
        return Contract(name="agg/1dev",
                        description="aggregation path, single device",
                        all_gathers=0, **peak)
    scale = n_padded // ms
    cap = scale
    if ms > 1:
        kw = dict(reduce_scatters=0)
        if segs is not None:
            cap = max(scale, histogram_elems(r, segs))
    else:
        kw = {}
    kw.update(allreduce_max_elems=cap, scale_allreduces=(1, 2),
              scale_elems=scale)
    return Contract(
        name=f"agg/ms{ms}",
        description="aggregation path: partial sums, no cohort re-gather",
        all_gathers=0, **kw, **peak)


@functools.partial(jax.jit,
                   static_argnames=("use_kernel", "interpret", "mesh",
                                    "cohort_2d"))
def accumulate(x: jax.Array, weights: jax.Array, mask: jax.Array, *,
               use_kernel=None, interpret=False, mesh=None,
               cohort_2d: bool = False) -> jax.Array:
    """Fused Σ_c weights[c]·x[c]·mask over the client axis. x: (m, n).

    With ``mesh`` set (and the client axis laid out over its ``data`` axis,
    see ``repro.sharding.cohort``), the reduction is expressed with
    ``shard_map``: each device reduces its own client shard — through the
    Pallas kernel on TPU — so the lowering never materializes a replicated
    (m, n) gather.  On a data-only mesh a single n-sized ``psum`` combines
    the partial sums (output replicated).

    ``cohort_2d=True`` declares x already lives in the resident
    P("data", "model") layout (the distributed-quantile norms pass keeps it
    there): each device reduces its own (m/D, n/n_model) slice and ONE
    n/n_model-sized ``psum`` over ``data`` finishes the sum — no
    reduce-scatter, no re-layout.  Otherwise, with model shards (and n
    divisible by them) the model-replicated reduction **reduce-scatters**:
    the model peers of each data shard split that shard's client rows
    between them (zeroing the other peers' weights — exact, any row
    count), a ``psum_scatter`` over ``model`` sums the partials while
    scattering the n axis, and the finishing ``psum`` over ``data`` moves
    only n/n_model elements per device.  Either way the output is sharded
    P("model") — exactly the resident global-buffer layout, so the
    caller's (M'/Γ, γ = 0) merge stays shard-local.
    """
    from repro.sharding.cohort import (DATA_AXIS, MODEL_AXIS, model_shards,
                                       shardable)
    use_kernel = use_pallas(use_kernel, interpret)
    if not shardable(mesh, x.shape[0]):
        return _accum_local(x, weights, mask, use_kernel, interpret)
    mo = model_shards(mesh)
    if x.shape[1] % mo != 0:     # non-divisible n: data-only reduction
        mo = 1

    if cohort_2d and mo > 1:
        def _shard2(xs, ws, msk):
            part = _accum_local(xs, ws, msk, use_kernel, interpret)
            return jax.lax.psum(part, DATA_AXIS)

        return jax.shard_map(_shard2, mesh=mesh,
                         in_specs=(P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS),
                                   P(MODEL_AXIS)),
                         out_specs=P(MODEL_AXIS), check_vma=False)(
                             x, weights, mask)

    def _shard(xs, ws, ms):
        if mo > 1:
            slot = (jnp.arange(xs.shape[0]) * mo) // xs.shape[0]
            ws = jnp.where(slot == jax.lax.axis_index(MODEL_AXIS), ws, 0.0)
        part = _accum_local(xs, ws, ms, use_kernel, interpret)
        if mo > 1:
            part = jax.lax.psum_scatter(part, MODEL_AXIS,
                                        scatter_dimension=0, tiled=True)
        return jax.lax.psum(part, DATA_AXIS)

    out_spec = P(MODEL_AXIS) if mo > 1 else P(None)
    return jax.shard_map(_shard, mesh=mesh,
                     in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(None)),
                     out_specs=out_spec, check_vma=False)(x, weights, mask)


@functools.partial(jax.jit,
                   static_argnames=("use_kernel", "interpret", "mesh",
                                    "cohort_2d"))
def accumulate_quant(x: jax.Array, weights: jax.Array, wtab: jax.Array,
                     seg: jax.Array, mask: jax.Array, *,
                     use_kernel=None, interpret=False, mesh=None,
                     cohort_2d: bool = False) -> jax.Array:
    """Fused dequantize + Σ_c weights[c]·wtab[c, seg[n]]·x[c, n]·mask[n].

    The quantized counterpart of ``accumulate``: ``x`` stays in its
    admission dtype (int8/bf16) end to end — dequant scales (times α and
    depth gates) enter through the per-(client, segment) table ``wtab``
    and are gathered per column inside the kernel, so the rows keep the
    read-once property and no (m, n) f32 dequant transient is ever
    materialized.  ``seg`` is the static per-column segment-id row ((n,)
    int32, -1 on the inert pad tail — those columns contribute zero).

    Sharding mirrors ``accumulate`` exactly: data-shard partial sums
    finished by one n-sized psum; ``cohort_2d`` consumes P("data",
    "model") slices with an n/n_model psum over ``data``; otherwise model
    peers split client rows and psum_scatter over ``model``.  Output is
    P("model") with model shards, replicated without.
    """
    from repro.sharding.cohort import (DATA_AXIS, MODEL_AXIS, model_shards,
                                       shardable)
    use_kernel = use_pallas(use_kernel, interpret)
    if not shardable(mesh, x.shape[0]):
        return _quant_accum_local(x, weights, wtab, seg, mask,
                                  use_kernel, interpret)
    mo = model_shards(mesh)
    if x.shape[1] % mo != 0:     # non-divisible n: data-only reduction
        mo = 1
    seg2 = seg.reshape(1, -1)

    if cohort_2d and mo > 1:
        def _shard2(xs, ws, wt, sg, msk):
            part = _quant_accum_local(xs, ws, wt, sg[0], msk,
                                      use_kernel, interpret)
            return jax.lax.psum(part, DATA_AXIS)

        return jax.shard_map(_shard2, mesh=mesh,
                         in_specs=(P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS),
                                   P(DATA_AXIS, None), P(None, MODEL_AXIS),
                                   P(MODEL_AXIS)),
                         out_specs=P(MODEL_AXIS), check_vma=False)(
                             x, weights, wtab, seg2, mask)

    def _shard(xs, ws, wt, sg, msk):
        if mo > 1:
            slot = (jnp.arange(xs.shape[0]) * mo) // xs.shape[0]
            ws = jnp.where(slot == jax.lax.axis_index(MODEL_AXIS), ws, 0.0)
        part = _quant_accum_local(xs, ws, wt, sg[0], msk,
                                  use_kernel, interpret)
        if mo > 1:
            part = jax.lax.psum_scatter(part, MODEL_AXIS,
                                        scatter_dimension=0, tiled=True)
        return jax.lax.psum(part, DATA_AXIS)

    out_spec = P(MODEL_AXIS) if mo > 1 else P(None)
    return jax.shard_map(_shard, mesh=mesh,
                     in_specs=(P(DATA_AXIS, None), P(DATA_AXIS),
                               P(DATA_AXIS, None), P(None, None), P(None)),
                     out_specs=out_spec, check_vma=False)(
                         x, weights, wtab, seg2, mask)
