"""Resident-buffer multi-round driver (repro.core.round): parity with the
per-round path, buffer donation, and one-compile-per-cohort-shape."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_tree_allclose as _assert_tree_allclose
from conftest import fl_round_fixture, make_cohort

from repro.core import flat, obs
from repro.core import round as round_mod
from repro.core.server import FLConfig, fl_round, fl_round_flat, \
    stack_runtimes

CFG, PARAMS = fl_round_fixture()
E, M = 2, 3
KEY = jax.random.PRNGKey(0)


def _fl(strategy):
    return FLConfig(local_steps=E, lr=0.05, strategy=strategy, task="cls",
                    agg_engine="flat")


@pytest.fixture(scope="module")
def cohort():
    return make_cohort(CFG, M, local_steps=E)


@pytest.mark.parametrize("strategy", ["fedfa", "heterofl"])
def test_resident_matches_sequential_fl_rounds(cohort, strategy):
    """R resident rounds == R per-round fl_round dispatches (same cohort,
    same per-round keys) within float tolerance."""
    specs, data_fn = cohort
    fl = _fl(strategy)
    R = 3
    p_res, losses = round_mod.run_rounds(PARAMS, CFG, fl, R, data_fn, KEY,
                                         eval_every=2)
    assert len(losses) == R

    p = PARAMS
    for r in range(R):
        p, loss = fl_round(p, CFG, fl, specs, data_fn(r)[1],
                           jax.random.fold_in(KEY, r))
        np.testing.assert_allclose(losses[r], float(loss), rtol=1e-5)
    _assert_tree_allclose(p, p_res)


def test_round_donates_both_buffers(cohort):
    """The jitted round consumes its donated inputs: the previous (N,) global
    and (m, N) cohort buffers are deleted after the call, and the returned
    cohort buffer can be donated back on the next round."""
    specs, data_fn = cohort
    fl = _fl("fedfa")
    index = flat.get_index(PARAMS)
    runtimes = stack_runtimes(CFG, specs)
    _, batches = data_fn(0)

    g_buf = flat.flatten(index, PARAMS)
    c_buf = jnp.zeros((M, index.n), jnp.float32)
    g2, c2, loss = round_mod.flat_round(
        g_buf, c_buf, CFG, fl, index, runtimes, batches, KEY)
    assert g_buf.is_deleted() and c_buf.is_deleted()
    assert g2.shape == (index.n,) and c2.shape == (M, index.n)

    g3, c3, _ = round_mod.flat_round(
        g2, c2, CFG, fl, index, runtimes, batches, KEY)
    assert g2.is_deleted() and c2.is_deleted()
    assert not (g3.is_deleted() or c3.is_deleted())


def test_round_compiles_once_per_cohort_shape(cohort):
    """Same cohort shape -> one executable; make_flat_round returns the
    cached program and jit adds exactly one cache entry.  The program's
    own ``round_traces`` counter says the same without jax internals: one
    more trace for a new cohort shape, none for a repeat round."""
    specs, data_fn = cohort
    fl = _fl("fedfa")
    index = flat.get_index(PARAMS)
    fn = round_mod.make_flat_round(CFG, fl, index, any_malicious=False)
    assert round_mod.make_flat_round(CFG, fl, index, any_malicious=False) is fn
    # private jax API: checked where this jax has it
    cache_size = getattr(fn, "_cache_size", None)
    traces = lambda: obs.counts().get("round_traces", 0)

    driver = round_mod.ResidentDriver(CFG, fl, index)
    g_buf = flat.flatten(index, PARAMS)
    g_buf, _ = driver.round(g_buf, specs, data_fn(0)[1], KEY)
    before = traces()
    assert before >= 1
    for r in range(1, 3):
        g_buf, _ = driver.round(g_buf, specs, data_fn(r)[1],
                                jax.random.fold_in(KEY, r))
    assert traces() == before             # repeat rounds trace nothing
    if cache_size:
        assert cache_size() == 1          # 3 rounds, same shape: 1 executable

    # a different cohort shape compiles exactly one more program
    _, b0 = data_fn(0)
    b2 = {k: v[:2] for k, v in b0.items()}
    g_buf, _ = driver.round(g_buf, specs[:2], b2, jax.random.fold_in(KEY, 99))
    assert traces() == before + 1
    g_buf, _ = driver.round(g_buf, specs[:2], b2, jax.random.fold_in(KEY, 98))
    assert traces() == before + 1
    if cache_size:
        assert cache_size() == 2


def test_fl_round_flat_matches_fl_round(cohort):
    """The server-level flat entry point shares stack_runtimes and matches
    the tree-in/tree-out round."""
    specs, data_fn = cohort
    fl = _fl("fedfa")
    index = flat.get_index(PARAMS)
    _, batches = data_fn(0)

    p_tree, loss_tree = fl_round(PARAMS, CFG, fl, specs, batches, KEY)
    g_buf = flat.flatten(index, PARAMS)
    g2, _, loss_flat = fl_round_flat(g_buf, CFG, fl, specs, batches, KEY,
                                     index=index)
    np.testing.assert_allclose(float(loss_tree), float(loss_flat), rtol=1e-6)
    _assert_tree_allclose(p_tree, flat.unflatten(index, g2))

    with pytest.raises(ValueError, match="FlatIndex"):
        fl_round_flat(g2, CFG, fl, specs, batches, KEY)


def test_checkpoint_from_resident_buffer(cohort, tmp_path):
    """save_from_buffer at an eval boundary == save of the unflattened tree;
    restore_to_buffer round-trips back onto the resident representation."""
    from repro.checkpoint import checkpoint as ckpt_mod
    index = flat.get_index(PARAMS)
    g_buf = flat.flatten(index, PARAMS)
    path = str(tmp_path / "resident")
    ckpt_mod.save_from_buffer(path, index, g_buf, meta={"round": 7})
    tree, meta = ckpt_mod.restore(path, PARAMS)
    assert meta["round"] == 7 and meta["flat_n"] == index.n
    _assert_tree_allclose(tree, PARAMS, rtol=0, atol=0)

    idx2, buf2, meta2 = ckpt_mod.restore_to_buffer(path, PARAMS)
    assert idx2 is index                      # same layout -> cached index
    np.testing.assert_array_equal(np.asarray(buf2), np.asarray(g_buf))


def test_run_rounds_eval_and_ckpt_boundaries(cohort, tmp_path):
    """eval_fn fires at eval_every boundaries + final round; checkpoints are
    written from the resident buffer at the same rounds."""
    import os
    specs, data_fn = cohort
    fl = _fl("heterofl")
    seen = []
    p, losses = round_mod.run_rounds(
        PARAMS, CFG, fl, 4, data_fn, KEY, eval_every=2,
        eval_fn=lambda r, loss, tree: seen.append(r),
        ckpt_path=str(tmp_path / "ck"))
    assert seen == [0, 2, 3]
    for r in seen:
        assert os.path.exists(tmp_path / f"ck_r{r:05d}.npz")
