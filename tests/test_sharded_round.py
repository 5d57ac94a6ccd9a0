"""Sharded resident round (repro.sharding.cohort + mesh-aware round driver):
host-mesh parity, pad-row inertness, donation under NamedSharding, the
forced-multi-device subprocess parity, and regressions for the checkpoint /
rounds=0 / sanitize_specs / stack_runtimes fixes that rode along."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_tree_allclose as _assert_tree_allclose
from conftest import fl_round_fixture, make_cohort

from repro.core import flat
from repro.core import round as round_mod
from repro.core import server as server_mod
from repro.core.server import FLConfig, stack_runtimes
from repro.launch.mesh import make_data_mesh
from repro.sharding import cohort as cohort_sh

CFG, PARAMS = fl_round_fixture()
E, M = 2, 3
KEY = jax.random.PRNGKey(0)


def _fl(strategy):
    return FLConfig(local_steps=E, lr=0.05, strategy=strategy, task="cls",
                    agg_engine="flat")


@pytest.fixture(scope="module")
def cohort():
    return make_cohort(CFG, M, local_steps=E)


# ---------------------------------------------------------------------------
# Sharded round: host mesh (however many devices this process sees)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["fedfa", "heterofl"])
def test_sharded_matches_unsharded_on_host_mesh(cohort, strategy):
    """run_rounds under the data mesh == run_rounds without a mesh."""
    specs, data_fn = cohort
    fl = _fl(strategy)
    p_un, l_un = round_mod.run_rounds(PARAMS, CFG, fl, 2, data_fn, KEY,
                                      eval_every=0)
    p_sh, l_sh = round_mod.run_rounds(PARAMS, CFG, fl, 2, data_fn, KEY,
                                      eval_every=0, mesh=make_data_mesh())
    np.testing.assert_allclose(l_un, l_sh, rtol=1e-4)
    _assert_tree_allclose(p_un, p_sh)


def test_donation_under_named_sharding(cohort):
    """The donated ping-pong of (N,)/(m, N) buffers survives explicit
    NamedShardings: inputs are consumed, outputs carry the cohort spec."""
    specs, data_fn = cohort
    fl = _fl("fedfa")
    mesh = make_data_mesh()
    index = flat.get_index(PARAMS)
    runtimes = stack_runtimes(CFG, specs)
    _, batches = data_fn(0)
    g_buf = jax.device_put(flat.flatten(index, PARAMS),
                           cohort_sh.replicated(mesh))
    g2, c2, _ = round_mod.flat_round(g_buf, None, CFG, fl, index, runtimes,
                                     batches, KEY, mesh=mesh)
    assert g_buf.is_deleted()
    g3, c3, _ = round_mod.flat_round(g2, c2, CFG, fl, index, runtimes,
                                     batches, KEY, mesh=mesh)
    assert g2.is_deleted() and c2.is_deleted()
    assert not (g3.is_deleted() or c3.is_deleted())
    assert c3.sharding.spec == jax.sharding.PartitionSpec("data")


def test_padded_cohort_aggregates_identically(cohort):
    """Pad rows are inert in Alg. 1: aggregate_buffers over the cohort
    padded with n_data = 0 rows equals the unpadded aggregation for both
    the scaled (fedfa: α mean must skip pads) and unscaled presets."""
    specs, data_fn = cohort
    index = flat.get_index(PARAMS)
    g_flat = flat.flatten(index, PARAMS)
    x = jnp.stack([g_flat * (1.0 + 0.01 * (i + 1)) for i in range(M)])
    runtimes = stack_runtimes(CFG, specs)
    (masks_p, gates_p, gmaps_p, nd_p, _, _), _ = cohort_sh.pad_cohort(
        runtimes, {"d": jnp.zeros((M, 1))}, pad=2)
    x_p = jnp.concatenate([x, jnp.broadcast_to(x[:1] * 7.0, (2,) + x.shape[1:])])
    masks, gates, gmaps, nd, _, _ = runtimes
    for graft, scale in [(True, True), (False, False), (True, False)]:
        out = flat.aggregate_buffers(index, g_flat, x, CFG, masks, gates,
                                     gmaps, nd, graft=graft, scale=scale)
        out_p = flat.aggregate_buffers(index, g_flat, x_p, CFG, masks_p,
                                       gates_p, gmaps_p, nd_p, graft=graft,
                                       scale=scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_p),
                                   rtol=1e-6, atol=1e-7)


def test_engines_agree_on_zero_data_client(cohort):
    """The flat engine's validity-weighted α mean and the tree engine's
    (scaling_factors with n_data) must stay parity-locked when a REAL
    client has n_data = 0, not just for sharding pad rows."""
    from repro.core import fedfa
    specs, _ = cohort
    masks, gates, gmaps, _, _, _ = stack_runtimes(CFG, specs)
    stacked = jax.tree.map(
        lambda l: jnp.stack([l * (1.0 + 0.02 * (i + 1)) for i in range(M)]),
        PARAMS)
    nd = jnp.asarray([120.0, 0.0, 90.0])
    out_flat = fedfa.aggregate(PARAMS, stacked, CFG, masks, gates, gmaps, nd,
                               graft=True, scale=True, engine="flat")
    out_tree = fedfa.aggregate(PARAMS, stacked, CFG, masks, gates, gmaps, nd,
                               graft=True, scale=True, engine="tree")
    _assert_tree_allclose(out_flat, out_tree)


def test_pad_cohort_rows():
    assert cohort_sh.pad_rows(3, None) == 0
    mesh = make_data_mesh()
    assert cohort_sh.pad_rows(3, mesh) == (-3) % mesh.shape["data"]
    nd = jnp.asarray([5.0, 7.0])
    mal = jnp.asarray([0.0, 1.0])
    gates = jnp.ones((2, 4))
    (_, gates_p, _, nd_p, cms_p, mal_p), batches_p = cohort_sh.pad_cohort(
        (gates, gates, gates, nd, None, mal), {"tokens": jnp.ones((2, 3))},
        pad=2)
    assert gates_p.shape == (4, 4) and batches_p["tokens"].shape == (4, 3)
    assert cms_p is None
    np.testing.assert_array_equal(np.asarray(nd_p), [5.0, 7.0, 0.0, 0.0])
    np.testing.assert_array_equal(np.asarray(mal_p), [0.0, 1.0, 0.0, 0.0])


def _run_forced_multidevice_child(*args):
    """Run tests/_force_multidevice_child.py on 4 forced CPU devices — in a
    subprocess because XLA_FLAGS is read once at jax init."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), os.path.join(root, "tests")] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(root, "tests", "_force_multidevice_child.py"), *args],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"child failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


def test_sharded_round_forced_multidevice():
    """Sharded-vs-unsharded parity on 4 forced CPU devices — uneven m=3
    cohort (one pad shard), malicious client, fedfa + heterofl, donation."""
    assert "MULTIDEVICE OK" in _run_forced_multidevice_child()


def test_kernelized_quantile_collectives_forced_multidevice():
    """The kernelized trimmed-norm pass (fused Pallas fedfa_quantile,
    interpret mode) keeps the sharded aggregation's collective structure:
    zero all-gathers, <= 2 N-sized all-reduces under the host mesh."""
    out = _run_forced_multidevice_child("--quantile-collectives")
    assert "QUANTILE COLLECTIVES OK" in out


def test_two_d_round_forced_multidevice():
    """2x2 (data, model) resident round on 4 forced CPU devices: parity vs
    the 1-device round (fedfa + heterofl, uneven m=3, malicious client),
    N-pad-segment inertness through the full round, model-sharded resident
    buffers (N/2 bytes per device) with ping-pong donation, and a
    checkpoint roundtrip from/to the sharded global layout."""
    assert "TWO-D OK" in _run_forced_multidevice_child("--two-d")


def test_agg_collectives_2d_forced_multidevice():
    """The 2x2 aggregation path lowers with ZERO all-gathers, >= 1
    reduce-scatter, and no all-reduce above N/n_model elements."""
    out = _run_forced_multidevice_child("--agg-collectives-2d")
    assert "AGG COLLECTIVES 2D OK" in out


def test_async_forced_multidevice():
    """Async engine on 4 forced CPU devices: parity-mode bit-equality with
    the sharded run_rounds (fedfa + heterofl, uneven malicious cohort),
    skewed-trace bounded-staleness merges, zero all-gathers in the merge
    program, and the ResidentDriver._cbufs padded-key regression (m=3 and
    m=4 cohorts ping-pong one padded scratch allocation)."""
    assert "ASYNC OK" in _run_forced_multidevice_child("--async")


def test_quantized_forced_multidevice():
    """Quantized admission on 4 forced CPU devices: bf16/int8 sharded
    rounds stay within quantization drift of the sharded f32 round, and
    the ResidentDriver._cbufs dtype-key regression — one driver serving
    f32 and int8 cohorts of the same padded size keeps one pool per
    admission dtype and never donates across dtypes."""
    assert "QUANT OK" in _run_forced_multidevice_child("--quant")


# ---------------------------------------------------------------------------
# N-padding (host-side, no mesh needed)
# ---------------------------------------------------------------------------

def test_flat_index_n_padding_roundtrip_and_inertness():
    """A pad_to that does not divide N grows an inert zero tail: offsets are
    unchanged, flatten/unflatten round-trips, the tail has zero density and
    the padded aggregation equals the unpadded one with a zero tail."""
    tree = {"a": jnp.arange(3.0), "b": jnp.arange(4.0).reshape(2, 2)}
    idx1 = flat.get_index(tree)
    idx8 = flat.get_index(tree, pad_to=8)
    assert idx1.n == idx8.n == 7
    assert idx1.n_padded == 7 and idx8.n_padded == 8
    assert [s.offset for s in idx1.leaves] == [s.offset for s in idx8.leaves]
    assert idx8.row_of.shape == (8,)
    buf = flat.flatten(idx8, tree)
    assert buf.shape == (8,)
    np.testing.assert_array_equal(np.asarray(buf)[7:], 0.0)
    _assert_tree_allclose(flat.unflatten(idx8, buf), tree, rtol=0, atol=0)
    st = jax.tree.map(lambda l: jnp.stack([l, 2.0 * l]), tree)
    sbuf = flat.flatten_stacked(idx8, st)
    assert sbuf.shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(sbuf)[:, 7:], 0.0)


def test_aggregate_buffers_pad_tail_is_inert():
    """On the real fixture, an aggregation through a padded index matches
    the unpadded aggregation on the logical prefix and keeps the tail 0."""
    index = flat.get_index(PARAMS)
    pad_to = 1024
    index_p = flat.get_index(PARAMS, pad_to=pad_to)
    assert index_p.n_padded > index_p.n, "fixture N divides pad_to"
    specs, _ = make_cohort(CFG, M, local_steps=E)
    masks, gates, gmaps, nd, _, _ = stack_runtimes(CFG, specs)
    g = flat.flatten(index, PARAMS)
    x = jnp.stack([g * (1.0 + 0.01 * (i + 1)) for i in range(M)])
    g_p = flat.flatten(index_p, PARAMS)
    x_p = jnp.pad(x, ((0, 0), (0, index_p.n_padded - index_p.n)))
    for graft, scale in [(True, True), (False, False)]:
        out = flat.aggregate_buffers(index, g, x, CFG, masks, gates, gmaps,
                                     nd, graft=graft, scale=scale)
        out_p = flat.aggregate_buffers(index_p, g_p, x_p, CFG, masks, gates,
                                       gmaps, nd, graft=graft, scale=scale)
        np.testing.assert_allclose(np.asarray(out_p)[:index.n],
                                   np.asarray(out), rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(np.asarray(out_p)[index.n:], 0.0)


def test_round_cache_hits_on_reconstructed_mesh():
    """_ROUND_CACHE keys the mesh by value: an identical mesh rebuilt from
    the same devices/axes must reuse the compiled round program instead of
    recompiling every cohort shape."""
    index = flat.get_index(PARAMS)
    fl = _fl("fedfa")
    fn1 = round_mod.make_flat_round(CFG, fl, index, any_malicious=False,
                                    mesh=make_data_mesh())
    fn2 = round_mod.make_flat_round(CFG, fl, index, any_malicious=False,
                                    mesh=make_data_mesh())
    assert fn1 is fn2
    assert round_mod._mesh_key(make_data_mesh()) \
        == round_mod._mesh_key(make_data_mesh())
    assert round_mod._mesh_key(None) is None


def test_mesh_shape_validation_and_parsing():
    """get_mesh validates the requested shape against the visible device
    count, naming both, and accepts explicit DxM shapes."""
    from repro.launch import mesh as mesh_mod
    n_dev = jax.device_count()
    with pytest.raises(ValueError, match=rf"256 devices.*{n_dev} are visible"):
        mesh_mod.get_mesh("production")
    with pytest.raises(ValueError, match=rf"needs {8 * n_dev} devices"):
        mesh_mod.get_mesh(f"{8 * n_dev}x1")
    assert mesh_mod.parse_mesh_shape("2x2") == (2, 2)
    assert mesh_mod.parse_mesh_shape(" 4X2 ") == (4, 2)
    for bad in ("2x", "x2", "0x2", "2x2x2", "host"):
        with pytest.raises(ValueError):
            mesh_mod.parse_mesh_shape(bad)
    m = mesh_mod.get_mesh(f"{n_dev}x1")
    assert m.shape["data"] == n_dev and m.shape["model"] == 1
    with pytest.raises(ValueError, match="unknown mesh"):
        mesh_mod.get_mesh("banana")


# ---------------------------------------------------------------------------
# Satellite regressions
# ---------------------------------------------------------------------------

def test_restore_raises_on_structure_mismatch(tmp_path):
    from repro.checkpoint import checkpoint as ckpt_mod
    tree = {"a": np.zeros((2, 3), np.float32), "b": np.ones(4, np.float32)}
    path = str(tmp_path / "ck")
    ckpt_mod.save(path, tree)
    with pytest.raises(ValueError, match="structure mismatch"):
        ckpt_mod.restore(path, {"a": tree["a"], "c": tree["b"]})
    with pytest.raises(ValueError, match=r"shape mismatch at .*a"):
        ckpt_mod.restore(path, {"a": np.zeros((3, 2), np.float32),
                                "b": tree["b"]})


def test_run_rounds_zero_rounds_is_a_noop():
    fl = _fl("fedfa")

    def data_fn(r):                                    # must never be called
        raise AssertionError("rounds=0 must not touch data or compile")
    params, losses = round_mod.run_rounds(PARAMS, CFG, fl, 0, data_fn, KEY)
    assert params is PARAMS and losses == []


def test_run_fl_zero_rounds_returns_empty_history():
    from repro.launch.train import run_fl
    hist = run_fl("smollm-135m", rounds=0, n_clients=4, local_steps=1,
                  batch=2, seq_len=8, reduced=True, quiet=True)
    assert hist["round"] == [] and hist["final_acc"] is None
    assert hist["final_local_acc"] is None


def test_sanitize_specs_missing_axis_falls_back_to_replication():
    from jax.sharding import PartitionSpec as P
    from repro.sharding.specs import sanitize_specs
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    spec = {"fsdp": P(("pod", "data"), None), "tp": P(None, "model"),
            "pod_only": P("pod")}
    avals = {"fsdp": jax.ShapeDtypeStruct((4, 4), jnp.float32),
             "tp": jax.ShapeDtypeStruct((4, 4), jnp.float32),
             "pod_only": jax.ShapeDtypeStruct((4,), jnp.float32)}
    out = sanitize_specs(spec, avals, mesh)
    assert out["fsdp"] == P(None, None)          # "pod" absent -> replicate
    assert out["tp"] == P(None, "model")         # known axes untouched
    assert out["pod_only"] == P(None)


def test_stack_runtimes_memoizes_per_arch(cohort):
    specs, _ = cohort
    server_mod._RUNTIME_CACHE.clear()
    calls = {"n": 0}
    orig = type(specs[0].arch).masks

    def counting(self, cfg):
        calls["n"] += 1
        return orig(self, cfg)

    try:
        type(specs[0].arch).masks = counting
        stack_runtimes(CFG, specs)
        first = calls["n"]
        assert first == len({s.arch for s in specs})   # one build per arch
        stack_runtimes(CFG, specs)
        assert calls["n"] == first                     # second round: cached
    finally:
        type(specs[0].arch).masks = orig
    assert len(server_mod._RUNTIME_CACHE) <= server_mod._RUNTIME_CACHE_MAX
