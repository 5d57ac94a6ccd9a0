"""Flat-buffer aggregation engine: flatten/unflatten round-trip and
kernel-vs-reference parity against the tree engine for every strategy
preset over a heterogeneous cohort."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny

from repro.core import fedfa, flat
from repro.models import model as model_mod
from repro.models.masks import ClientArch, full_client, stack_masks

CFG = tiny("smollm-135m").replace(n_layers=4, n_sections=2)


def _cohort(cfg, archs, *, poison_last=False, seed=0):
    """Stacked runtimes for a cohort: per-client perturbed copies of the
    global model (the last client optionally a malicious +10 outlier)."""
    g = model_mod.init_params(cfg, jax.random.PRNGKey(seed))
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), len(archs))
    clients = [jax.tree.map(
        lambda x, kk=k: x + 0.05 * jax.random.normal(kk, x.shape, jnp.float32)
        .astype(x.dtype), g) for k in ks]
    if poison_last:
        clients[-1] = jax.tree.map(lambda x: x + 10.0, clients[-1])
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *clients)
    masks = stack_masks([a.masks(cfg) for a in archs])
    gates = jnp.stack([a.gates(cfg) for a in archs])
    gmaps = jnp.stack([a.graft(cfg) for a in archs])
    nd = jnp.asarray(np.arange(1, len(archs) + 1), jnp.float32)
    return g, stacked, masks, gates, gmaps, nd


def _assert_tree_allclose(a, b, rtol=1e-4, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


# Heterogeneous cohort: mixed widths 0.25/0.5/1.0, mixed section depths,
# and a malicious full-width full-depth client.
HETERO = [ClientArch(0.25, (1, 1)), ClientArch(0.5, (2, 1)),
          ClientArch(1.0, (1, 2)), full_client(CFG)]


@pytest.mark.parametrize("strategy", sorted(fedfa.STRATEGIES))
def test_flat_matches_tree_all_strategies(strategy):
    g, stacked, masks, gates, gmaps, nd = _cohort(
        CFG, HETERO, poison_last=True)
    kw = fedfa.STRATEGIES[strategy]
    out_tree = fedfa.aggregate(g, stacked, CFG, masks, gates, gmaps, nd,
                               engine="tree", **kw)
    out_flat = fedfa.aggregate(g, stacked, CFG, masks, gates, gmaps, nd,
                               engine="flat", **kw)
    _assert_tree_allclose(out_tree, out_flat)


def test_flat_matches_tree_under_jit():
    g, stacked, masks, gates, gmaps, nd = _cohort(CFG, HETERO)

    @jax.jit
    def both(g, s, mk, gt, gm, nd):
        t = fedfa.aggregate(g, s, CFG, mk, gt, gm, nd, engine="tree")
        f = fedfa.aggregate(g, s, CFG, mk, gt, gm, nd, engine="flat")
        return t, f
    out_tree, out_flat = both(g, stacked, masks, gates, gmaps, nd)
    _assert_tree_allclose(out_tree, out_flat)


def test_flat_keeps_global_where_no_client_updates():
    """γ = 0 case: with every client at width 0.25, channels outside the
    0.25 prefix receive no update and must keep the previous global value
    (and never become NaN)."""
    archs = [ClientArch(0.25, (1, 1))] * 3
    g, stacked, masks, gates, gmaps, nd = _cohort(CFG, archs)
    out = fedfa.aggregate(g, stacked, CFG, masks, gates, gmaps, nd,
                          engine="flat", graft=True, scale=True)
    assert not any(bool(jnp.isnan(x).any()) for x in jax.tree.leaves(out))
    # a fully-masked slice: the top d_ff channels of stage-0 ffn w_gate
    w_new = out["stages"][0][0]["ffn"]["w_gate"]
    w_old = g["stages"][0][0]["ffn"]["w_gate"]
    np.testing.assert_array_equal(np.asarray(w_new[..., -1]),
                                  np.asarray(w_old[..., -1]))
    # parity holds in the γ=0 regime too
    out_tree = fedfa.aggregate(g, stacked, CFG, masks, gates, gmaps, nd,
                               engine="tree", graft=True, scale=True)
    _assert_tree_allclose(out_tree, out)


def test_flat_gamma_zero_cohort_keeps_global_exactly():
    """Depth-gated partial aggregation: stage-0 rows no client holds keep
    the previous global value bit-for-bit."""
    archs = [ClientArch(1.0, (1, 1))] * 2      # depth slots 1 and 3 empty
    g, stacked, masks, gates, gmaps, nd = _cohort(CFG, archs)
    out = fedfa.aggregate(g, stacked, CFG, masks, gates, gmaps, nd,
                          engine="flat", graft=False, scale=False)
    wq = out["stages"][0][0]["attn"]["wq"]
    np.testing.assert_array_equal(np.asarray(wq[1]),
                                  np.asarray(g["stages"][0][0]["attn"]["wq"][1]))


def test_flatten_unflatten_roundtrip():
    g = model_mod.init_params(CFG, jax.random.PRNGKey(3))
    index = flat.get_index(g)
    buf = flat.flatten(index, g)
    assert buf.shape == (index.n,) and buf.dtype == jnp.float32
    back = flat.unflatten(index, buf)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(g)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_flatten_rejects_mismatched_tree():
    g = model_mod.init_params(CFG, jax.random.PRNGKey(3))
    index = flat.get_index(g)
    with pytest.raises(ValueError, match="does not match FlatIndex"):
        flat.flatten(index, {"embed": g["embed"]})


def test_flat_index_segments_consistent():
    g = model_mod.init_params(CFG, jax.random.PRNGKey(3))
    index = flat.get_index(g)
    assert index.row_of.shape == (index.n,)
    assert index.row_of.max() == index.n_segments - 1
    # segment ids are contiguous leaf-major runs
    assert (np.diff(index.row_of) >= 0).all()
    # grafting is the identity off stage 0: a reversed row map moves only
    # the stage-0 segments, and those exist in this config
    stage0 = index.seg_stage0[index.row_of]
    buf = jnp.arange(index.n, dtype=jnp.float32)
    gmap = jnp.arange(int(index.seg_row[index.seg_stage0].max()) + 1)[::-1]
    moved = np.asarray(flat._graft_flat(index, buf, gmap)) != np.asarray(buf)
    assert not moved[~stage0].any()
    assert moved[stage0].any() and (~stage0).any()


def test_flat_graft_matches_tree_graft():
    g = model_mod.init_params(CFG, jax.random.PRNGKey(4))
    index = flat.get_index(g)
    gmap = ClientArch(1.0, (1, 2)).graft(CFG)
    grafted_tree = fedfa.graft_stage0(g, gmap)
    grafted_flat = flat.unflatten(
        index, flat._graft_flat(index, flat.flatten(index, g), gmap))
    _assert_tree_allclose(grafted_tree, grafted_flat, rtol=0, atol=0)


def test_flat_engine_interpret_mode_matches_tree():
    """Full engine through the Pallas kernels in interpret mode (the TPU
    code path, executed on CPU) against the tree engine."""
    cfg = tiny("smollm-135m")          # smallest: interpret mode is slow
    archs = [ClientArch(0.5, (1,) * cfg.n_sections), full_client(cfg)]
    g, stacked, masks, gates, gmaps, nd = _cohort(cfg, archs)
    out_tree = fedfa.aggregate(g, stacked, cfg, masks, gates, gmaps, nd,
                               engine="tree", graft=True, scale=True)
    out_flat = fedfa.aggregate(g, stacked, cfg, masks, gates, gmaps, nd,
                               engine="flat", graft=True, scale=True,
                               use_kernel=True, interpret=True)
    _assert_tree_allclose(out_tree, out_flat)


def _random_mixed_tree(rng: np.random.Generator, depth=0):
    """Random nested dict/tuple/list pytree with mixed bf16/f32 leaves."""
    def leaf():
        shape = tuple(int(rng.integers(1, 5))
                      for _ in range(int(rng.integers(0, 3))))
        dtype = jnp.bfloat16 if rng.random() < 0.5 else jnp.float32
        return jnp.asarray(rng.standard_normal(shape), jnp.float32) \
            .astype(dtype)
    if depth >= 2:
        return leaf()
    kids = [_random_mixed_tree(rng, depth + 1)
            for _ in range(int(rng.integers(1, 4)))]
    kind = rng.integers(3)
    if kind == 0:
        return {f"k{i}": c for i, c in enumerate(kids)}
    return tuple(kids) if kind == 1 else list(kids)


@pytest.mark.parametrize("seed", range(8))
def test_roundtrip_mixed_dtypes_property(seed):
    """Property: flatten -> unflatten over arbitrary mixed-dtype pytrees is
    the identity — exact dtype restoration (bf16 embeds losslessly in the f32
    buffer) and exact structure."""
    tree = _random_mixed_tree(np.random.default_rng(seed))
    index = flat.get_index(tree)
    back = flat.unflatten(index, flat.flatten(index, tree))
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert b.dtype == a.dtype and b.shape == a.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_index_cache_distinguishes_treedefs():
    """Two pytrees with identical (path, shape, dtype) flatten order but
    different structure (tuple vs list share SequenceKey paths) must get
    distinct FlatIndexes — the old cache key collided here and unflatten
    returned the wrong container type."""
    x = jnp.ones((3,), jnp.float32)
    idx_tuple = flat.get_index({"a": (x,)})
    idx_list = flat.get_index({"a": [x]})
    assert idx_tuple is not idx_list
    assert idx_tuple.treedef != idx_list.treedef
    back = flat.unflatten(idx_list, flat.flatten(idx_list, {"a": [x]}))
    assert isinstance(back["a"], list)
    back_t = flat.unflatten(idx_tuple, flat.flatten(idx_tuple, {"a": (x,)}))
    assert isinstance(back_t["a"], tuple)


def test_index_cache_bounded():
    """The index cache is LRU-bounded instead of growing without limit."""
    for i in range(flat._INDEX_CACHE_MAX + 8):
        flat.get_index({f"leaf{i}": jnp.zeros((i + 1,), jnp.float32)})
    assert len(flat._INDEX_CACHE) <= flat._INDEX_CACHE_MAX
    # most-recent entries survive (LRU evicts from the front)
    i = flat._INDEX_CACHE_MAX + 7
    probe = {f"leaf{i}": jnp.zeros((i + 1,), jnp.float32)}
    before = len(flat._INDEX_CACHE)
    flat.get_index(probe)
    assert len(flat._INDEX_CACHE) == before


def test_single_client_cohort():
    """m=1: mean norm equals the client's own norm, α=1, aggregate returns
    the (masked, grafted) client update where γ>0."""
    archs = [full_client(CFG)]
    g, stacked, masks, gates, gmaps, nd = _cohort(CFG, archs)
    out_tree = fedfa.aggregate(g, stacked, CFG, masks, gates, gmaps, nd,
                               engine="tree", graft=True, scale=True)
    out_flat = fedfa.aggregate(g, stacked, CFG, masks, gates, gmaps, nd,
                               engine="flat", graft=True, scale=True)
    _assert_tree_allclose(out_tree, out_flat)
    client = jax.tree.map(lambda x: x[0], stacked)
    _assert_tree_allclose(client, out_flat, rtol=1e-4, atol=1e-4)
