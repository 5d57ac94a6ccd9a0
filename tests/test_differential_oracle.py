"""Tree engine as the differential oracle for the flat production engine.

The tree engine (``fedfa.aggregate(engine="tree")``) is no longer on any
hot path — its job is to be an independently-implemented Alg. 1 that the
flat engine is diffed against over randomized heterogeneous cohorts: all 7
strategy presets x random width/depth mixes x malicious flags x random
(possibly zero) data counts.  Randomization is hypothesis-driven when
hypothesis is installed and falls back to a fixed seeded sweep otherwise.

The suite carries the ``oracle`` marker so quick runs can deselect it
(``pytest -m "not oracle"``); it runs by default in tier-1.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import fl_round_fixture

from repro.core import fedfa, flat
from repro.models.masks import ClientArch, stack_masks

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

pytestmark = pytest.mark.oracle

CFG, PARAMS = fl_round_fixture()
_WIDTHS = (0.25, 0.5, 0.75, 1.0)
SEEDS = range(5)


@functools.lru_cache(maxsize=16)
def _random_cohort(seed: int):
    """Random hetero cohort: m in [1, 5] clients with random widths, random
    per-section depths, random malicious (+10 outlier) flags and random data
    counts including n_data = 0 clients."""
    rng = np.random.default_rng(seed)
    bounds = CFG.section_bounds()
    m = int(rng.integers(1, 6))
    archs = [ClientArch(float(rng.choice(_WIDTHS)),
                        tuple(int(rng.integers(1, hi - lo + 1))
                              for lo, hi in bounds))
             for _ in range(m)]
    malicious = rng.random(m) < 0.3
    nd = rng.integers(0, 5, m).astype(np.float32)
    if nd.sum() == 0:
        nd[int(rng.integers(m))] = 3.0

    ks = jax.random.split(jax.random.PRNGKey(seed + 1), m)
    clients = []
    for i, k in enumerate(ks):
        c = jax.tree.map(
            lambda x, kk=k: x + 0.05 * jax.random.normal(
                kk, x.shape, jnp.float32).astype(x.dtype), PARAMS)
        if malicious[i]:
            c = jax.tree.map(lambda x: x + 10.0, c)
        clients.append(c)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *clients)
    masks = stack_masks([a.masks(CFG) for a in archs])
    gates = jnp.stack([a.gates(CFG) for a in archs])
    gmaps = jnp.stack([a.graft(CFG) for a in archs])
    return stacked, masks, gates, gmaps, jnp.asarray(nd)


def _check_parity(seed: int, strategy: str, rtol=1e-4, atol=1e-5):
    stacked, masks, gates, gmaps, nd = _random_cohort(seed)
    kw = fedfa.STRATEGIES[strategy]
    out_tree = fedfa.aggregate(PARAMS, stacked, CFG, masks, gates, gmaps,
                               nd, engine="tree", **kw)
    out_flat = fedfa.aggregate(PARAMS, stacked, CFG, masks, gates, gmaps,
                               nd, engine="flat", **kw)
    for x, y in zip(jax.tree.leaves(out_tree), jax.tree.leaves(out_flat)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("strategy", sorted(fedfa.STRATEGIES))
@pytest.mark.parametrize("seed", SEEDS)
def test_flat_matches_tree_oracle(seed, strategy):
    """Flat == tree on random hetero cohorts for every strategy preset."""
    _check_parity(seed, strategy)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1),
           strategy=st.sampled_from(sorted(fedfa.STRATEGIES)))
    def test_flat_matches_tree_oracle_hypothesis(seed, strategy):
        """Hypothesis-driven sweep over the same cohort space."""
        _check_parity(seed, strategy)


def test_async_merges_match_tree_oracle():
    """Every async merge (parity fast path AND general bounded-staleness
    path, malicious straggler included) re-aggregated by the tree engine
    from the engine's own host snapshot — slot rows, staleness-discounted
    weights, per-row specs — must reproduce the merged global."""
    from conftest import assert_tree_allclose, make_cohort

    from repro.core.async_round import AsyncConfig, run_async
    from repro.core.server import FLConfig, stack_runtimes
    from repro.sim import ParitySource, TraceSource

    fl = FLConfig(local_steps=2, lr=0.05, strategy="fedfa", task="cls",
                  agg_engine="flat")
    index = flat.get_index(PARAMS)
    specs, data_fn = make_cohort(CFG, 4, local_steps=2, malicious_frac=0.3)
    key = jax.random.PRNGKey(3)
    rec = []
    # skewed trace -> partial, staleness-bearing merges (general path)
    run_async(PARAMS, CFG, fl, 3,
              TraceSource(data_fn, lambda i: 20.0 if i % 4 == 3 else 1.0),
              key, acfg=AsyncConfig(capacity=4, merge_k=2, staleness_max=3),
              eval_every=0, on_merge=rec.append)
    # full-cohort trace -> parity fast path merges
    run_async(PARAMS, CFG, fl, 2, ParitySource(data_fn), key,
              acfg=AsyncConfig.parity(4), eval_every=0, on_merge=rec.append)
    assert len(rec) == 5
    kw = fedfa.STRATEGIES[fl.strategy]
    saw_pregrafted = False
    for info in rec:
        g_before = flat.unflatten(index, jnp.asarray(info["g_before"]))
        rows = [flat.unflatten(index, jnp.asarray(r)) for r in info["x"]]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
        masks, gates, gmaps, _, _, _ = stack_runtimes(CFG, info["specs"])
        if info["pregrafted"]:
            # general-path rows were grafted at admission — an identity
            # graft map keeps graft-on weighting without permuting again
            gmaps = jnp.broadcast_to(jnp.arange(gmaps.shape[1]), gmaps.shape)
            saw_pregrafted = True
        out_tree = fedfa.aggregate(g_before, stacked, CFG, masks, gates,
                                   gmaps, jnp.asarray(info["w"]),
                                   engine="tree", **kw)
        assert_tree_allclose(out_tree,
                             flat.unflatten(index, jnp.asarray(info["g_after"])))
    assert saw_pregrafted  # the general bounded-staleness path was exercised


def _rel_drift(a, b):
    """Relative L2 distance between two pytrees/arrays (oracle in ``a``)."""
    num = den = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        num += float(np.sum((np.asarray(x, np.float32) -
                             np.asarray(y, np.float32)) ** 2))
        den += float(np.sum(np.asarray(x, np.float32) ** 2))
    return (num / max(den, 1e-30)) ** 0.5


@pytest.mark.parametrize("dt,bound", [("bf16", 0.03), ("int8", 0.08)])
@pytest.mark.parametrize("seed", range(3))
def test_quantized_aggregation_drift_vs_tree_oracle(seed, dt, bound):
    """Quantized admission (grafted, density-masked rows quantized with
    per-segment scales, fused dequantize in every consumer) stays within
    quantization drift of the f32 tree oracle on randomized heterogeneous
    cohorts — malicious +10 outliers included (``_random_cohort`` flags
    ~30% of clients)."""
    stacked, masks, gates, gmaps, nd = _random_cohort(seed)
    index = flat.get_index(PARAMS)
    g = flat.flatten(index, PARAMS)
    x = flat.flatten_stacked(index, stacked)
    x = jax.vmap(functools.partial(flat._graft_flat, index))(x, gmaps)
    dens, _ = jax.vmap(
        functools.partial(flat._density_and_fraction, CFG, index))(masks)
    y = x * dens                              # what _round_q admits
    x_q, scales = flat.quantize_cohort(index, y, dt)
    out_q = flat.aggregate_buffers(
        index, g, x_q, CFG, masks, gates, gmaps, nd, pregrafted=True,
        scales=scales, use_kernel=True, interpret=True,
        **fedfa.STRATEGIES["fedfa"])
    # oracle: the tree engine on the same pre-grafted f32 rows (identity
    # graft maps keep graft-on weighting without permuting again)
    rows = [flat.unflatten(index, y[i]) for i in range(y.shape[0])]
    stacked_g = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
    gmaps_id = jnp.broadcast_to(jnp.arange(gmaps.shape[1]), gmaps.shape)
    out_tree = fedfa.aggregate(PARAMS, stacked_g, CFG, masks, gates,
                               gmaps_id, nd, engine="tree",
                               **fedfa.STRATEGIES["fedfa"])
    drift = _rel_drift(out_tree, flat.unflatten(index, out_q))
    assert drift < bound, (dt, seed, drift)


@pytest.mark.parametrize("dt,bound", [("bf16", 0.02), ("int8", 0.08)])
def test_quantized_error_feedback_converges(dt, bound):
    """Multi-round sweep: with server-side error feedback the quantized
    resident trajectory stays within epsilon of the f32 trajectory after 6
    rounds — the per-round quantization residual must not compound."""
    import dataclasses

    from conftest import make_cohort
    from repro.core import round as round_mod
    from repro.core.server import FLConfig

    fl = FLConfig(local_steps=2, lr=0.05, strategy="fedfa", task="cls",
                  agg_engine="flat")
    _, data_fn = make_cohort(CFG, 3, local_steps=2, malicious_frac=0.34)
    key = jax.random.PRNGKey(9)
    p_f32, l_f32 = round_mod.run_rounds(PARAMS, CFG, fl, 6, data_fn, key,
                                        eval_every=0)
    fl_q = dataclasses.replace(fl, update_dtype=dt)
    p_q, l_q = round_mod.run_rounds(PARAMS, CFG, fl_q, 6, data_fn, key,
                                    eval_every=0)
    assert np.isfinite(l_q).all(), l_q
    drift = _rel_drift(p_f32, p_q)
    assert drift < bound, (dt, drift)


def test_quantized_async_merges_match_tree_oracle():
    """Async quantized admission: every bounded-staleness merge,
    re-aggregated by the TREE engine from the engine's own dequantized
    pool snapshot, must reproduce the merged global — the fused
    dequantize-accumulate and the explicit dequantize agree merge by
    merge (the density 0/1 mask is baked into the stored rows, so the
    oracle's re-application is idempotent)."""
    import dataclasses

    from conftest import assert_tree_allclose, make_cohort
    from repro.core.async_round import AsyncConfig, run_async
    from repro.core.server import FLConfig, stack_runtimes
    from repro.sim import TraceSource

    fl = FLConfig(local_steps=2, lr=0.05, strategy="fedfa", task="cls",
                  agg_engine="flat", update_dtype="int8")
    index = flat.get_index(PARAMS)
    _, data_fn = make_cohort(CFG, 4, local_steps=2, malicious_frac=0.3)
    rec = []
    run_async(PARAMS, CFG, fl, 3,
              TraceSource(data_fn, lambda i: 20.0 if i % 4 == 3 else 1.0),
              jax.random.PRNGKey(3),
              acfg=AsyncConfig(capacity=4, merge_k=2, staleness_max=3),
              eval_every=0, on_merge=rec.append)
    assert rec, "skewed trace produced no merges"
    kw = fedfa.STRATEGIES[fl.strategy]
    for info in rec:
        assert info["pregrafted"]
        g_before = flat.unflatten(index, jnp.asarray(info["g_before"]))
        rows = [flat.unflatten(index, jnp.asarray(r)) for r in info["x"]]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
        masks, gates, gmaps, _, _, _ = stack_runtimes(CFG, info["specs"])
        gmaps = jnp.broadcast_to(jnp.arange(gmaps.shape[1]), gmaps.shape)
        out_tree = fedfa.aggregate(g_before, stacked, CFG, masks, gates,
                                   gmaps, jnp.asarray(info["w"]),
                                   engine="tree", **kw)
        assert_tree_allclose(
            out_tree, flat.unflatten(index, jnp.asarray(info["g_after"])),
            rtol=5e-4, atol=5e-5)


def test_backdoor_robustness_row_int8():
    """Table-1-style robustness row at int8 admission: the clean-vs-
    attacked accuracy drop under the lambda=20 label-shuffle attack must
    survive quantization — int8's drop tracks f32's and the attacked int8
    run keeps a usable global (quantized admission must not hand the
    attacker a new amplification channel)."""
    from repro.launch.train import run_fl

    accs = {}
    for dt in ("f32", "int8"):
        for attack, frac in (("clean", 0.0), ("attacked", 0.4)):
            h = run_fl("smollm-135m", 4, 5, strategy="fedfa",
                       malicious_frac=frac, attack_lambda=20.0,
                       local_steps=1, batch=2, seq_len=8,
                       participation=1.0, eval_every=0, seed=0,
                       update_dtype=dt, reduced=True, quiet=True)
            assert np.isfinite(h["loss"]).all(), (dt, attack, h["loss"])
            accs[(dt, attack)] = h["final_acc"]
    drop_f32 = accs[("f32", "clean")] - accs[("f32", "attacked")]
    drop_int8 = accs[("int8", "clean")] - accs[("int8", "attacked")]
    assert abs(drop_int8 - drop_f32) <= 0.25, (drop_f32, drop_int8, accs)
    assert accs[("int8", "attacked")] >= accs[("f32", "attacked")] - 0.25, \
        accs


@pytest.mark.parametrize("seed", range(3))
def test_kernelized_cohort_norms_match_reference(seed):
    """The fused Pallas trimmed-norm pass (use_kernel=True, interpret=True:
    the TPU code path on CPU) is bit-tolerant-equal (<= 1e-5 rel) to the
    jnp reference path on differential-oracle cohorts."""
    stacked, masks, _, _, _ = _random_cohort(seed)
    index = flat.get_index(PARAMS)
    dens, fracs = jax.vmap(
        functools.partial(flat._density_and_fraction, CFG, index))(masks)
    xm = flat.flatten_stacked(index, stacked) * dens
    ref = flat._cohort_norms(index, xm, fracs, 0.95, False, False)
    ker = flat._cohort_norms(index, xm, fracs, 0.95, True, True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-7)
