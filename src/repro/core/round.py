"""Resident-buffer multi-round FL driver (Alg. 1, lines 4-25, over rounds).

PR 1 made a single aggregation call fast; this module removes the per-round
host overhead around it.  The whole round — vmapped local training
(``server.cohort_update``), grafting, trimmed norms and the (M', γ)
accumulation (``flat.aggregate_buffers``) — is ONE jitted program over the
resident ``(N,)`` f32 global buffer and an ``(m, N)`` f32 cohort buffer:

  * clients unpack the global model with ``flat.unflatten`` *inside* the
    trace (a slice + reshape + cast per leaf, fused by XLA),
  * the server side never leaves flat space,
  * both buffers are donated (``donate_argnums=(0, 1)`` with
    ``keep_unused=True`` so the scratch cohort buffer stays a parameter and
    XLA aliases it to the new ``(m, N)`` stacked-updates output), so the two
    allocations ping-pong across rounds instead of being re-allocated.

``run_rounds`` drives R rounds, compiling the round once per cohort shape
(m, batch shapes, attacker presence) and unflattening only at ``eval_every``
boundaries for eval/checkpoint.

With a mesh (``mesh=`` on ``run_rounds``/``ResidentDriver``/``flat_round``,
built by ``repro.launch.mesh.get_mesh``), the round is 2-D SPMD over the
``(data, model)`` axes (``repro.sharding.cohort``):

  * the ``(m, N)`` client axis is sharded over ``data`` — local training
    runs data-parallel over client shards; uneven cohorts are padded
    host-side with inert ``n_data = 0`` rows,
  * the ``(N,)`` parameter axis of both RESIDENT buffers is sharded over
    ``model`` — the global buffer lives as P("model") and the donated
    cohort scratch as P("data", "model"), each device keeping only its
    N/n_model slice between rounds (N is padded to a multiple of the model
    shards by ``flat.FlatIndex``, with an inert zero tail).

Inside the round the global model is (unavoidably) gathered once into
local training; the graft gather consumes the freshly trained cohort in
the pre-split P("data") layout (a data-dependent cross-shard row
permutation needs whole rows), and from there the N axis splits EARLY:
the distributed two-stage trimmed quantile
(``kernels.fedfa_quantile.multilevel``) runs the norms pass on
P("data", "model") slices — per-level histogram psums over ``model``,
never whole rows — and both (M', γ) reductions are per-shard partial
sums finished by an N/n_model-sized psum over ``data`` (no
reduce-scatter; ``kernels.fedfa_agg.ops.accumulate``).  The γ = 0 merge
runs on the slices, and the returned cohort buffer is constrained back
to the 2-D layout by a communication-free local slice.  The aggregation
path lowers with zero all-gathers; ``flat.unflatten`` re-gathers the
global buffer only at eval/checkpoint boundaries.  The donated
ping-pong of the two buffers is unchanged (matching in/out shardings
keep XLA aliasing them).

Slot-pool / donation contract (shared with ``repro.core.async_round``):
the (m, N) cohort scratch is a **slot pool** — m fixed rows whose content
is meaningful only where the per-row weight (``n_data``, or the async
engine's staleness-discounted weight) is positive; zero-weight rows are
inert in every reduction and in α, which is what makes partial cohorts,
mesh padding and partially-filled async pools exact.  The buffer's
*values* are never an input to a round program (``keep_unused=True``
keeps it a parameter solely so XLA aliases its allocation to the new
stacked-updates output), so any (m, N) f32 buffer of the right sharding
can be donated in, and the returned buffer must be treated as consumed
scratch: hand it back to the next program that writes all of its live
rows (the resident round overwrites every row; the async admit program
scatters into its dispatch slots and preserves the rest).  Per cohort
shape there is exactly ONE live scratch buffer — ``ResidentDriver`` keys
its pool on the PADDED row count so cohorts that pad to the same shape
ping-pong one allocation.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import flat, obs
from repro.core.fedfa import STRATEGIES
from repro.core.server import (ClientSpec, FLConfig, cohort_update,
                               default_class_masks, stack_runtimes)
from repro.sharding import cohort as cohort_sh

Params = Dict[str, Any]

# jitted round programs, keyed on everything the trace closes over; the
# FlatIndex participates by identity (the key keeps it alive).  Shapes and
# the cms-is-None structure are handled by jit's own cache underneath.
_ROUND_CACHE: "OrderedDict[Tuple, Any]" = OrderedDict()
_ROUND_CACHE_MAX = 16


def _fl_static(fl: FLConfig) -> Tuple:
    """The FLConfig fields the round trace closes over (FLConfig is mutable,
    so the compiled-program cache keys on a value snapshot).  The cohort
    admission dtype participates: an int8 and an f32 round of the same
    cohort shape are different programs with different buffer dtypes, and a
    key that omitted it would hand one the other's compiled round."""
    return (fl.strategy, fl.lr, fl.task, fl.trim, fl.attack_lambda,
            fl.use_kernel, fl.interpret, getattr(fl, "update_dtype", "f32"))


def eval_boundary(r: int, rounds: int, eval_every: int) -> bool:
    """True on rounds where eval/checkpoint fire: every ``eval_every``
    rounds AND on the final round; ``eval_every <= 0`` means final round
    only.  Note the predicate deliberately fires at r = 0 (``0 % k == 0``)
    so a fresh run logs a baseline point before any training signal —
    callers that want training-only curves should skip r = 0 themselves.
    One shared helper so the resident driver, the async engine and the
    per-round loop in ``launch.train`` cannot drift."""
    return (eval_every > 0 and r % eval_every == 0) or r == rounds - 1


def _mesh_key(mesh) -> Optional[Tuple]:
    """Value key for a mesh: reconstructing an identical mesh (same device
    ids, axis names, shape) must hit the round cache instead of recompiling
    every cohort shape — Mesh object identity is not stable across
    ``make_mesh`` calls."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


def _round_key(cfg: ArchConfig, fl: FLConfig, index: flat.FlatIndex, *,
               any_malicious: bool, donate: bool = True, mesh=None,
               m_real: Optional[int] = None) -> Tuple:
    """The ``_ROUND_CACHE`` key of one resident round program — everything
    the trace closes over.  Exposed so ``repro.analysis.passes
    .check_cache_keys`` can probe that mesh/pad/row-count variations map
    to DISTINCT keys (the PR 5/6 bug class was keys missing one of these
    dimensions)."""
    return (index, cfg, _fl_static(fl), bool(any_malicious), bool(donate),
            _mesh_key(mesh), m_real)


def round_contract(index: flat.FlatIndex, mesh=None, *, rows: int):
    """The resident round program's declared contract (see
    ``repro.analysis.contracts``), for a cohort padded to ``rows``.

    Always: the full (rows, N) cohort is never all-gathered, both
    resident buffers (params 0 = g_buf, 1 = cohort scratch) must have
    materialized donation aliases (the ping-pong), and the statically
    estimated per-device peak live bytes stay within a budget of
    ``(6 + 12*r) * N * 4`` where r is the per-data-shard row count —
    the resident state plus the vmapped training temporaries (grads,
    optimizer state, re-layout copies), measured ~11-16 N-multiples on
    the canonical fixture, with ~1.6x headroom.  A dropped donation or
    an accidentally materialized cohort replica blows the budget.

    On a multi-device data-only mesh the round has NO legitimate
    all-gather at all and the (M', γ) partial sums show up as >= 1
    N-sized all-reduce.  With model shards the strict communication
    bounds live on the aggregation path contract
    (``kernels.fedfa_agg.ops.accumulate_contract``); the *training*-side
    re-layout collectives GSPMD emits over the idle model axis are now
    bounded too (the PR 7 follow-up (c) — ``analysis/blame`` attributes
    them to the segment concatenates in ``flat.py``, the mask
    multiplies in ``masking.py`` and the optimizer all-to-alls): the
    measured inventory on the canonical 2x2 fixture is 38 all-gathers /
    24 all-to-alls / 12 collective-permutes, ceilinged at ~1.7x, and no
    single all-gather may exceed one full (N,) model row — a
    cohort-sized gather stays structurally impossible.  Since the
    distributed two-stage quantile landed, the aggregation tail has NO
    reduce-scatter either (the N axis pre-splits before the reductions);
    a small allowance remains for the re-layout ops GSPMD may still emit
    on the training side.
    """
    from repro.analysis.contracts import Contract
    multi = mesh is not None and mesh.size > 1
    ms = cohort_sh.model_shards(mesh)
    r = max(1, rows // cohort_sh.data_shards(mesh))
    kw: Dict[str, Any] = {}
    if multi and ms == 1:
        kw = dict(all_gathers=0, scale_allreduces=(1, None),
                  scale_elems=index.n_padded)
    elif multi:
        kw = dict(all_gathers=(None, 64), all_to_alls=(None, 48),
                  collective_permutes=(None, 24), reduce_scatters=(0, 8),
                  max_all_gather_elems=index.n_padded)
    return Contract(
        name=f"round/ms{ms}",
        description="resident round: donated ping-pong, no cohort gather",
        full_cohort_gathers=0, cohort_elems=rows * index.n_padded,
        peak_live_bytes_per_device=(None, (6 + 12 * r) * index.n_padded * 4),
        donated=frozenset({0, 1}), **kw)


def quantized_round_contract(index: flat.FlatIndex, mesh=None, *, rows: int):
    """Declared contract of the QUANTIZED resident round (``--update-dtype
    int8``/``bf16``; canonical report on the data-parallel mesh).

    Same structural guarantees as ``round_contract`` — no full-cohort
    gather, donated ping-pong of every resident buffer (g_buf + the
    quantized cohort/scale/error-feedback pools, params 0-4), zero
    all-gathers with >= 1 N-sized partial-sum all-reduce on a data mesh —
    plus the quantization-specific ones, checked on a standalone trace of
    the fused dequantize-accumulate (``agg_ops.accumulate_quant``):
    exactly 1 read of the quantized rows, 0 sorts, and because the rows
    enter the kernel in their admitted dtype there is no materialized f32
    (m, N) dequant transient.  Peak budget ``(6 + 10r) * N * 4``
    bytes/device: the RESIDENT inter-round pools drop ~4x (2 int8 (m, N)
    pools + 2 small scale tables vs one f32 (m, N) scratch) and the
    aggregation path reads int8 rows, but the in-program transient peak
    is a little above the f32 round's measurement — the f32 training
    rows can no longer alias into the (now int8) donated pool, and the
    error-feedback dequant + requantize chain keeps one extra f32 (m, N)
    tenant — measured 14.0 N-multiples at r = 1 on the canonical
    4-device fixture vs 11.0 for the f32 round (whose looser budget is
    ``(6 + 12r)``).
    """
    from repro.analysis.contracts import Contract
    multi = mesh is not None and mesh.size > 1
    kw: Dict[str, Any] = {}
    if multi:
        kw = dict(all_gathers=0, reduce_scatters=0,
                  scale_allreduces=(1, None), scale_elems=index.n_padded)
    r = max(1, rows // cohort_sh.data_shards(mesh))
    return Contract(
        name="round/quant",
        description="quantized round: int8 admission, fused dequantize",
        full_cohort_gathers=0, cohort_elems=rows * index.n_padded,
        peak_live_bytes_per_device=(None, (6 + 10 * r) * index.n_padded * 4),
        donated=frozenset({0, 1, 2, 3, 4}), row_reads=1, sorts=0, **kw)


def make_flat_round(cfg: ArchConfig, fl: FLConfig, index: flat.FlatIndex,
                    *, any_malicious: bool, donate: bool = True,
                    mesh=None, m_real: Optional[int] = None):
    """Build (or fetch) the jitted resident round program.

    Signature of the returned function:
      (g_buf (N,), c_buf (m, N) scratch, masks, gates, gmaps, nd, cms, mal,
       batches, keys (m, ...)) -> (g_buf' (N,), x (m, N) updates, mean loss)

    g_buf and c_buf are donated; the new cohort buffer x reuses c_buf's
    allocation and is what the caller donates back next round.

    ``keys`` are the per-client PRNG keys, split HOST-side by the caller
    (``flat_round``): splitting inside the traced program is not safe under
    a mesh — GSPMD may partition the threefry computation differently per
    mesh shape, changing the malicious label-shuffle bits (observed on
    (data, model) meshes) — and host-side keys match the per-round
    ``server.fl_round`` bit-for-bit.

    With ``mesh`` set the program carries explicit in/out shardings: the
    cohort-stacked arguments (keys, and x) over the mesh ``data`` axis,
    g_buf over ``model``, c_buf/x over ``(data, model)``, loss replicated.
    ``m_real`` (static) marks the number of real rows of a padded cohort —
    the reported loss averages over those only (pad rows are already inert
    in aggregation via ``n_data = 0``).
    """
    key = _round_key(cfg, fl, index, any_malicious=any_malicious,
                     donate=donate, mesh=mesh, m_real=m_real)
    fn = _ROUND_CACHE.get(key)
    if fn is not None:
        _ROUND_CACHE.move_to_end(key)
        return fn
    kw = STRATEGIES[fl.strategy]

    if fl.update_dtype != "f32":
        import functools
        do_graft = bool(kw.get("graft", False))
        dens_fn = jax.vmap(functools.partial(flat._density_and_fraction,
                                             cfg, index))

        def _round_q(g_buf, c_buf, s_buf, e_buf, es_buf, masks, gates,
                     gmaps, nd, cms, mal, batches, keys):
            obs.count("round_traces")
            with jax.named_scope("fedfa.unflatten"):
                g = flat.unflatten(index, g_buf)
            updated, losses = cohort_update(
                g, cfg, fl, masks, gates, batches, cms, mal, keys,
                any_malicious=any_malicious)
            with jax.named_scope("fedfa.flatten"):
                x = cohort_sh.constrain_cohort(
                    flat.flatten_stacked(index, updated), mesh)     # (m, N)
            if do_graft:
                with jax.named_scope("fedfa.graft"):
                    x = cohort_sh.constrain_cohort(
                        jax.vmap(functools.partial(flat._graft_flat, index))(
                            x, gmaps), mesh)
            with jax.named_scope("fedfa.density"):
                dens, _ = dens_fn(masks)
            # server-side error feedback: the residual of the PREVIOUS
            # quantized admission of this dispatch slot re-enters before
            # quantizing, so compression noise averages out across rounds
            # instead of biasing the trimmed mean.  The density mask wraps
            # the WHOLE sum: a slot's next occupant may cover a narrower
            # width, and residual components outside its mask must not
            # leak values into coordinates whose density (and hence γ
            # weight) is 0 — the stored rows stay in the client subspace
            with jax.named_scope("fedfa.quantize"):
                y = (x + flat.dequantize_cohort(index, e_buf, es_buf)) \
                    * cohort_sh.constrain_cohort(dens, mesh)
                x_q, scales = flat.quantize_cohort(index, y, fl.update_dtype)
                e = y - flat.dequantize_cohort(index, x_q, scales)
                e_q, e_s = flat.quantize_cohort(index, e, fl.update_dtype)
            g_new = flat.aggregate_buffers(
                index, g_buf, cohort_sh.constrain_cohort_buffer(x_q, mesh),
                cfg, masks, gates, gmaps, nd, trim=fl.trim, scales=scales,
                pregrafted=True, use_kernel=fl.use_kernel,
                interpret=fl.interpret, mesh=mesh, **kw)
            loss = jnp.mean(losses if m_real is None else losses[:m_real])
            return (g_new, cohort_sh.constrain_cohort_buffer(x_q, mesh),
                    scales, cohort_sh.constrain_cohort_buffer(e_q, mesh),
                    e_s, loss)

        jit_kw = {}
        if mesh is not None:
            jit_kw["in_shardings"], jit_kw["out_shardings"] = \
                cohort_sh.quantized_round_shardings(mesh)
        fn = jax.jit(_round_q,
                     donate_argnums=(0, 1, 2, 3, 4) if donate else (),
                     keep_unused=donate, **jit_kw)
        _ROUND_CACHE[key] = fn
        while len(_ROUND_CACHE) > _ROUND_CACHE_MAX:
            _ROUND_CACHE.popitem(last=False)
        return fn

    def _round(g_buf, c_buf, masks, gates, gmaps, nd, cms, mal, batches,
               keys):
        obs.count("round_traces")
        with jax.named_scope("fedfa.unflatten"):
            g = flat.unflatten(index, g_buf)       # leaf dtypes, inside trace
        updated, losses = cohort_update(
            g, cfg, fl, masks, gates, batches, cms, mal, keys,
            any_malicious=any_malicious)
        # the graft gather consumes x in the pre-split P("data") layout
        # (data-dependent row permutation needs whole rows); the norms and
        # reductions split N immediately after, and the RETURNED cohort
        # buffer is sliced down to the resident 2-D P("data", "model")
        # layout for free
        with jax.named_scope("fedfa.flatten"):
            x = cohort_sh.constrain_cohort(
                flat.flatten_stacked(index, updated), mesh)         # (m, N)
        g_new = flat.aggregate_buffers(
            index, g_buf, x, cfg, masks, gates, gmaps, nd, trim=fl.trim,
            use_kernel=fl.use_kernel, interpret=fl.interpret, mesh=mesh, **kw)
        loss = jnp.mean(losses if m_real is None else losses[:m_real])
        return g_new, cohort_sh.constrain_cohort_buffer(x, mesh), loss

    jit_kw = {}
    if mesh is not None:
        jit_kw["in_shardings"], jit_kw["out_shardings"] = \
            cohort_sh.round_shardings(mesh)
    fn = jax.jit(_round, donate_argnums=(0, 1) if donate else (),
                 keep_unused=donate, **jit_kw)
    _ROUND_CACHE[key] = fn
    while len(_ROUND_CACHE) > _ROUND_CACHE_MAX:
        _ROUND_CACHE.popitem(last=False)
    return fn


def _quant_state_ok(st, m: int, want) -> bool:
    """Is ``st`` a live quantized cohort state tuple for m rows of dtype
    ``want``?  (x_q, scales, e_buf, e_scales) — all four must be undeleted
    device arrays of the matching shape/dtype."""
    return (isinstance(st, tuple) and len(st) == 4
            and not any(b.is_deleted() for b in st)
            and st[0].shape[0] == m and st[0].dtype == want)


def fresh_quant_state(index: flat.FlatIndex, m: int, update_dtype: str):
    """Zero-initialized quantized cohort state: (x_q, scales, e_buf,
    e_scales).  Zero error-feedback pools are exact no-ops on the first
    round (scale 0 dequantizes to zeros)."""
    want = flat.update_dtype_of(update_dtype)
    S = index.n_segments
    return (jnp.zeros((m, index.n_padded), want),
            jnp.zeros((m, S), jnp.float32),
            jnp.zeros((m, index.n_padded), want),
            jnp.zeros((m, S), jnp.float32))


def flat_round(g_buf: jax.Array, c_buf, cfg: ArchConfig,
               fl: FLConfig, index: flat.FlatIndex, runtimes, batches, key,
               *, any_malicious: bool = False, mesh=None
               ) -> Tuple[jax.Array, Any, jax.Array]:
    """One resident round: ``flat_round(g_buf, ...) -> (g_buf', c_buf', loss)``.

    runtimes: the ``server.stack_runtimes`` tuple for the selected cohort.
    c_buf may be None (first round of a cohort shape) — a fresh (m, N)
    scratch buffer is allocated; afterwards pass the returned cohort buffer
    back in so its allocation is reused.  With a quantized admission dtype
    (``fl.update_dtype`` int8/bf16) the cohort state is the TUPLE
    (x_q, scales, e_buf, e_scales) — quantized rows, their per-segment
    scales, and the error-feedback residual pools — donated and returned
    as a unit.

    With ``mesh`` set the cohort axis is sharded over the mesh ``data``
    axis; a cohort whose m doesn't divide the data-shard count is padded
    host-side with inert rows (``sharding.cohort.pad_cohort``), so the
    returned cohort buffer has the padded row count.
    """
    with obs.span("fedfa.prepare"):
        masks, gates, gmaps, nd, cms, mal = runtimes
        m = int(nd.shape[0])
        m_real = None
        pad = cohort_sh.pad_rows(m, mesh)
        if pad:
            (masks, gates, gmaps, nd, cms, mal), batches = \
                cohort_sh.pad_cohort(runtimes, batches, pad)
            m_real, m = m, m + pad
        qmode = fl.update_dtype != "f32"
        if qmode:
            if not _quant_state_ok(c_buf, m, flat.update_dtype_of(
                    fl.update_dtype)):
                c_buf = fresh_quant_state(index, m, fl.update_dtype)
        elif c_buf is None or isinstance(c_buf, tuple) \
                or c_buf.is_deleted() or c_buf.shape[0] != m:
            # born in its resident layout: the next round's donated buffer
            # then matches this one's, and the program is not traced again
            c_buf = jnp.zeros((m, index.n_padded), jnp.float32, device=None
                              if mesh is None
                              else cohort_sh.cohort_buffer_sharding(mesh))
        cms_in = default_class_masks(cms, cfg, fl, m)
        # split per-client keys HOST-side (see make_flat_round), for the
        # REAL rows only: padded cohorts must hand row i the same key the
        # unpadded cohort would (the malicious label-shuffle consumes it),
        # so pad rows reuse key 0
        keys = jax.random.split(key, m if m_real is None else m_real)
        if m_real is not None and m > m_real:
            keys = jnp.concatenate(
                [keys, jnp.broadcast_to(keys[:1],
                                        (m - m_real,) + keys.shape[1:])])
        fn = make_flat_round(cfg, fl, index, any_malicious=any_malicious,
                             mesh=mesh, m_real=m_real)
    with obs.span("fedfa.program"):
        if qmode:
            g_buf, x_q, scales, e_q, e_s, loss = fn(
                g_buf, *c_buf, masks, gates, gmaps, nd, cms_in, mal,
                batches, keys)
            return g_buf, (x_q, scales, e_q, e_s), loss
        return fn(g_buf, c_buf, masks, gates, gmaps, nd, cms_in, mal,
                  batches, keys)


class ResidentDriver:
    """Multi-round driver state: the FlatIndex, per-shape scratch cohort
    buffers, the optional mesh, and the donated round programs (via the
    module cache).

    The scratch pool is keyed on the PADDED row count (``m +
    sharding.cohort.pad_rows(m, mesh)``) — the shape the buffer actually
    has — not the raw cohort size: under a mesh, distinct real sizes that
    pad to the same row count must ping-pong ONE allocation (keying on
    ``len(specs)`` held a separate, never-donated buffer per real size and
    kept dead donated buffers referenced).  The key ALSO carries the
    cohort admission dtype: an int8 and an f32 cohort of the same padded
    shape are different states (different buffer dtypes, and the quantized
    one is a (x_q, scales, e_buf, e_scales) tuple) and must never collide
    on one pool slot."""

    def __init__(self, cfg: ArchConfig, fl: FLConfig, index: flat.FlatIndex,
                 mesh=None):
        self.cfg, self.fl, self.index, self.mesh = cfg, fl, index, mesh
        self._cbufs: Dict[Tuple[int, str], Any] = {}

    def round(self, g_buf: jax.Array, specs: Sequence[ClientSpec], batches,
              key) -> Tuple[jax.Array, jax.Array]:
        """Run one round on the resident buffer: (g_buf', mean loss)."""
        with obs.span("fedfa.round"):
            with obs.span("fedfa.runtimes"):
                runtimes = stack_runtimes(self.cfg, specs)
            m = len(specs)
            m_rows = m + cohort_sh.pad_rows(m, self.mesh)
            pool_key = (m_rows, self.fl.update_dtype)
            g_buf, c_buf, loss = flat_round(
                g_buf, self._cbufs.get(pool_key), self.cfg, self.fl,
                self.index, runtimes, batches, key, mesh=self.mesh,
                any_malicious=any(s.malicious for s in specs))
            self._cbufs[pool_key] = c_buf
            # evict entries whose buffer was donated elsewhere (e.g. handed
            # to the async engine) — a deleted jax.Array is dead weight that
            # would otherwise stay referenced forever
            dead = lambda v: (any(b.is_deleted() for b in v)
                              if isinstance(v, tuple) else v.is_deleted())
            for k in [k for k, v in self._cbufs.items() if dead(v)]:
                del self._cbufs[k]
            return g_buf, loss


def run_rounds(global_params: Params, cfg: ArchConfig, fl: FLConfig,
               rounds: int, data_fn: Callable[[int], Tuple[Sequence[ClientSpec], Any]],
               key, *, eval_every: int = 5,
               eval_fn: Optional[Callable[[int, float, Params], None]] = None,
               ckpt_path: Optional[str] = None, mesh=None,
               on_round: Optional[Callable[[int, jax.Array], None]] = None
               ) -> Tuple[Params, List[float]]:
    """Drive R resident rounds; unflatten only at eval/checkpoint boundaries.

    data_fn(r) -> (selected ClientSpecs, stacked client batches) — called
    host-side once per round, exactly like the per-round loop, so client
    selection and batching match ``launch.train.run_fl`` round for round.
    The per-round key is ``jax.random.fold_in(key, r)`` (same as the
    per-round path, so the two drivers are loss-parity comparable).

    eval_fn(r, mean_loss, params_tree) runs at ``eval_boundary`` rounds
    (every ``eval_every`` rounds including r = 0, plus the final round;
    ``eval_every <= 0``: final round only); with ckpt_path set, a
    checkpoint is written from the resident buffer at the same boundaries
    (``checkpoint.save_from_buffer``).  on_round(r, loss) runs right after
    round r is dispatched, with its loss still on the device (a caller
    that times rounds blocks on it there).
    Returns (final params tree, per-round mean losses).  ``rounds <= 0``
    returns the input params untouched without flattening or compiling
    anything, so scripted sweeps can no-op cleanly.
    """
    if rounds <= 0:
        return global_params, []
    index = flat.get_index(global_params, pad_to=cohort_sh.pad_unit(mesh))
    driver = ResidentDriver(cfg, fl, index, mesh=mesh)
    g_buf = flat.flatten(index, global_params)
    if mesh is not None:
        # place the global buffer on its model-sharded layout up front so
        # the first round's donation isn't defeated by an implicit reshard
        g_buf = jax.device_put(g_buf, cohort_sh.global_sharding(mesh))
    # losses convert to host floats INCREMENTALLY, one round behind the
    # dispatch (converting round r-1 while round r is in flight keeps the
    # async-dispatch pipeline full but pins at most ONE device scalar,
    # instead of retaining all R per-round device arrays until the end)
    losses: List[float] = []
    pending_loss: Optional[jax.Array] = None
    for r in range(rounds):
        specs, batches = data_fn(r)
        g_buf, loss = driver.round(g_buf, specs, batches,
                                   jax.random.fold_in(key, r))
        if on_round is not None:
            on_round(r, loss)
        if pending_loss is not None:
            losses.append(float(pending_loss))
        pending_loss = loss
        if eval_boundary(r, rounds, eval_every):
            if eval_fn is not None:
                eval_fn(r, float(loss), flat.unflatten(index, g_buf))
            if ckpt_path is not None:
                from repro.checkpoint import checkpoint as ckpt_mod
                ckpt_mod.save_from_buffer(
                    f"{ckpt_path}_r{r:05d}", index, g_buf,
                    meta={"round": r, "strategy": fl.strategy})
    losses.append(float(pending_loss))
    return flat.unflatten(index, g_buf), losses
