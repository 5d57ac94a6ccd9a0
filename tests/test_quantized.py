"""Quantized cohort admission: the quantize/dequantize pair, pad-tail
inertness under a non-dividing ``pad_to``, the fused dequantize consumers
(accumulate + trimmed-quantile), and the quantized resident round state.

Drift-vs-oracle bounds over heterogeneous/malicious cohorts and the
error-feedback convergence sweep live in ``test_differential_oracle.py``;
this file pins the unit-level contracts.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import fl_round_fixture, make_cohort

from repro.core import flat
from repro.core import round as round_mod
from repro.core.server import FLConfig, stack_runtimes


@pytest.fixture(scope="module")
def fixture():
    cfg, params = fl_round_fixture()
    return cfg, params, flat.get_index(params)


def _fl(**kw):
    return FLConfig(local_steps=2, lr=0.05, strategy="fedfa", task="cls",
                    agg_engine="flat", **kw)


def test_update_dtype_of():
    assert flat.update_dtype_of("f32") == jnp.float32
    assert flat.update_dtype_of("bf16") == jnp.bfloat16
    assert flat.update_dtype_of("int8") == jnp.int8
    with pytest.raises(ValueError, match="update_dtype"):
        flat.update_dtype_of("fp4")


def test_quantize_roundtrip_bound(fixture):
    """int8 roundtrip error is bounded by half a quantization step per
    element — step = seg_max/127 per (client, segment) — and all-zero
    rows/segments carry scale 0 and roundtrip to exact zeros."""
    _, _, index = fixture
    m = 3
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (m, index.n_padded), jnp.float32)
    x = x.at[:, index.n:].set(0.0)       # the inert N-pad tail
    x = x.at[1].set(0.0)                 # an all-zero (pad-like) row
    x_q, scales = flat.quantize_cohort(index, x, "int8")
    assert x_q.dtype == jnp.int8 and scales.shape == (m, index.n_segments)
    np.testing.assert_array_equal(np.asarray(scales[1]), 0.0)
    back = flat.dequantize_cohort(index, x_q, scales)
    seg_id, _, _ = flat._segment_maps(index)
    col = np.where(np.asarray(seg_id) < 0, index.n_segments,
                   np.asarray(seg_id))
    step = np.concatenate([np.asarray(scales),
                           np.zeros((m, 1), np.float32)], axis=1)[:, col]
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert (err <= 0.5 * step + 1e-7).all(), float(err.max())
    np.testing.assert_array_equal(np.asarray(back[1]), 0.0)

    # bf16 is a plain downcast with identity scales
    x_b, s_b = flat.quantize_cohort(index, x, "bf16")
    assert x_b.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(s_b), 1.0)
    rel = np.abs(np.asarray(flat.dequantize_cohort(index, x_b, s_b)) -
                 np.asarray(x))
    assert (rel <= np.abs(np.asarray(x)) * 2 ** -7 + 1e-7).all()


def test_pad_tail_inert_under_quantization(fixture):
    """Satellite: with a ``pad_to`` that does NOT divide N the index gains
    a real inert tail; quantization must keep it inert — the tail's scale
    slot is the implicit 0, its stored int8 bits stay 0, and even garbage
    written into the tail dequantizes to exact zeros."""
    _, params, _ = fixture
    index = flat.get_index(params, pad_to=1024)
    assert index.n_padded > index.n, "pick a pad_to that does not divide N"
    m = 2
    x = jax.random.normal(jax.random.PRNGKey(3), (m, index.n_padded),
                          jnp.float32)                 # garbage in the tail
    x_q, scales = flat.quantize_cohort(index, x, "int8")
    np.testing.assert_array_equal(np.asarray(x_q[:, index.n:]), 0)
    back = flat.dequantize_cohort(index, x_q, scales)
    np.testing.assert_array_equal(np.asarray(back[:, index.n:]), 0.0)
    # a hand-poked nonzero tail still dequantizes to zero: its scale
    # column is the dropped S slot
    poked = x_q.at[:, index.n:].set(17)
    np.testing.assert_array_equal(
        np.asarray(flat.dequantize_cohort(index, poked, scales)[:, index.n:]),
        0.0)


def test_quantized_round_keeps_pad_tail_inert(fixture):
    """The full quantized resident round on a non-dividing ``pad_to``
    index: the merged global's tail stays exactly zero and the quantized
    pool never stores tail bits (scale-0 segments on pad rows)."""
    cfg, params, _ = fixture
    index = flat.get_index(params, pad_to=1024)
    assert index.n_padded > index.n
    specs, data_fn = make_cohort(cfg, 3, local_steps=2)
    runtimes = stack_runtimes(cfg, specs)
    _, batches = data_fn(0)
    fl = _fl(update_dtype="int8")
    g_buf = flat.flatten(index, params)
    g2, state, loss = round_mod.flat_round(
        g_buf, None, cfg, fl, index, runtimes, batches,
        jax.random.PRNGKey(0), any_malicious=False)
    assert np.isfinite(float(loss))
    x_q, scales, e_q, e_s = state
    np.testing.assert_array_equal(np.asarray(g2)[index.n:], 0.0)
    np.testing.assert_array_equal(np.asarray(x_q)[:, index.n:], 0)
    np.testing.assert_array_equal(np.asarray(e_q)[:, index.n:], 0)
    assert np.isfinite(np.asarray(scales)).all()
    assert np.isfinite(np.asarray(e_s)).all()


def test_fresh_quant_state_shapes(fixture):
    _, _, index = fixture
    st = round_mod.fresh_quant_state(index, 4, "int8")
    assert round_mod._quant_state_ok(st, 4, jnp.int8)
    assert not round_mod._quant_state_ok(st, 4, jnp.bfloat16)
    assert not round_mod._quant_state_ok(st, 5, jnp.int8)
    assert not round_mod._quant_state_ok(st[0], 4, jnp.int8)
    x_q, scales, e_q, e_s = st
    assert x_q.shape == (4, index.n_padded) and x_q.dtype == jnp.int8
    assert scales.shape == (4, index.n_segments)
    # zero EF pools are exact no-ops: scale 0 dequantizes to zeros
    np.testing.assert_array_equal(
        np.asarray(flat.dequantize_cohort(index, e_q, e_s)), 0.0)


def test_fused_accumulate_quant_matches_dequant_oracle():
    """``accumulate_quant`` (rows stay int8, scales fold into the
    per-(client, segment) weight table) equals the explicit
    dequantize-then-accumulate f32 oracle."""
    from repro.kernels.fedfa_agg import ops as agg_ops

    m, n, S = 5, 4096, 3
    key = jax.random.PRNGKey(11)
    seg = np.repeat(np.arange(S), n // S).astype(np.int32)
    seg = np.pad(seg, (0, n - seg.size), constant_values=-1)   # inert tail
    x = jax.random.normal(key, (m, n), jnp.float32)
    scales = 0.01 + jax.random.uniform(jax.random.fold_in(key, 1), (m, S))
    x_q = jnp.clip(jnp.round(
        x / jnp.take(scales, jnp.clip(jnp.asarray(seg), 0, S - 1), axis=1)),
        -127, 127).astype(jnp.int8)
    w = jax.random.uniform(jax.random.fold_in(key, 2), (m,)) + 0.1
    gtab = jax.random.uniform(jax.random.fold_in(key, 3), (m, S)) + 0.5
    mask = (jnp.asarray(seg) >= 0).astype(jnp.float32)

    wtab = gtab * scales                       # dequant folds into the table
    for kernel in (False, True):
        got = agg_ops.accumulate_quant(
            x_q, w, wtab, jnp.asarray(seg), mask,
            use_kernel=kernel, interpret=kernel)
        segc = jnp.clip(jnp.asarray(seg), 0, S - 1)
        dq = x_q.astype(jnp.float32) * jnp.take(scales, segc, axis=1)
        want = jnp.einsum("m,mn->n", w,
                          dq * jnp.take(gtab, segc, axis=1)) * mask
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_trimmed_stats_scale_matches_dequant_oracle():
    """Both quantile paths accept quantized rows + a per-row scale and
    match the reference run on the explicitly dequantized f32 rows."""
    from repro.kernels.fedfa_quantile import ops as q_ops
    from repro.kernels.fedfa_quantile.multilevel import \
        row_trimmed_stats_multilevel
    from repro.kernels.fedfa_quantile.ref import row_trimmed_stats_ref

    R, L = 4, 1536
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (R, L), jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=1) / 127.0
    x_q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127).astype(jnp.int8)
    q = jnp.asarray([0.8, 0.85, 0.9, 0.95], jnp.float32)
    dq = x_q.astype(jnp.float32) * scale[:, None]
    t_ref, ss_ref = row_trimmed_stats_ref(dq, q)

    t, ss = q_ops.row_trimmed_stats(x_q, q, scale=scale,
                                    use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(t), np.asarray(t_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ss_ref),
                               rtol=1e-4, atol=1e-5)

    t_m, ss_m = row_trimmed_stats_multilevel(x_q, q, scale=scale,
                                             interpret=True)
    np.testing.assert_allclose(np.asarray(t_m), np.asarray(t_ref),
                               rtol=5e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ss_m), np.asarray(ss_ref),
                               rtol=5e-3, atol=1e-5)


def test_per_round_driver_falls_back_to_f32():
    """``--update-dtype`` needs a resident cohort state; the per-round
    driver has none, so run_fl refuses the combination up front instead of
    quietly running an f32 round that was not asked for."""
    from repro.launch.train import run_fl

    with pytest.raises(ValueError, match="resident cohort state"):
        run_fl("smollm-135m", 1, 2, driver="per-round", update_dtype="int8",
               local_steps=1, batch=2, seq_len=8, participation=1.0,
               eval_every=0, reduced=True)
