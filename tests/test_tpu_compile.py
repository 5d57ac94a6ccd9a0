"""The round's Pallas kernels compile for a TPU v5e at published widths.

Interpret mode runs on any backend and so never meets the TPU compiler's
rules (the (8, 128) block tiling, vector layouts, VMEM).  These tests
compile each kernel of the resident round with ``interpret=False`` for a
described ``v5e:2x2`` topology — no chip attached, nothing runs — at the
shapes a smollm-135m round (N = 134,515,008, 30 layers, d_model 576, a
3-client cohort) hands it.  The topology is described only inside a
fixture, so importing this module never loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fedfa_agg.kernel import quant_accum, scaled_accum
from repro.kernels.fedfa_quantile import multilevel
from repro.kernels.fedfa_quantile.kernel import quantile_fused
from repro.kernels.fedfa_quantile.ops import single_pass_block

N = 134_515_008              # smollm-135m flat length
M = 3                        # cohort rows the one-chip round holds
D, D_FF, D_KV, LAYERS = 576, 1536, 192, 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt
    return txt


@pytest.mark.parametrize("rows,cols,segs", [
    (96, D * D, 1),            # 1-D path: wq/wo rows of 30 layers x 3 clients
    (M, (N + 1023) // 1024 * 512, 272),   # 2-D path: one model shard of 2
])
def test_hist_call_compiles(one_chip, rows, cols, segs):
    def level(x, seg, sc, hi, shift):
        return multilevel._hist_call(x, seg, sc, hi, shift, interpret=False)

    _compile(level, one_chip, ((rows, cols), jnp.float32),
             ((cols,), jnp.int32), ((rows, segs), jnp.float32),
             ((rows, 2, segs), jnp.int32), ((), jnp.int32))


def test_hist_call_int8_row_path_compiles(one_chip):
    """int8 admission on the 1-D path: the rows stay int8 in HBM and each
    row's (rows, 1) scale dequantizes them in the kernel."""
    def level(x, seg, sc, hi, shift):
        return multilevel._hist_call(x, seg, sc, hi, shift, interpret=False)

    rows, cols = 96, D * D
    _compile(level, one_chip, ((rows, cols), jnp.int8),
             ((cols,), jnp.int32), ((rows, 1), jnp.float32),
             ((rows, 2, 1), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("rows,length", [
    (LAYERS, D * D_KV),        # wk/wv of one client: 30 x 110,592
    (M * LAYERS, D),           # the per-layer norm weights of the cohort
])
def test_quantile_fused_compiles(one_chip, rows, length):
    rb, rp, lp = single_pass_block(rows, length)

    def fused(r, q):
        return quantile_fused(r, q, L=length, block_rows=rb)

    _compile(fused, one_chip, ((rp, lp), jnp.float32), ((rp,), jnp.float32))


def test_scaled_accum_compiles(one_chip):
    n = -(-N // 4096) * 4096
    _compile(lambda x, w, m: scaled_accum(x, w, m), one_chip,
             ((M, n), jnp.float32), ((M,), jnp.float32), ((n,), jnp.float32))


def test_quant_accum_compiles(one_chip):
    n = -(-N // 4096) * 4096
    _compile(lambda x, w, s, m: quant_accum(x, w, s, m), one_chip,
             ((M, n), jnp.int8), ((M, 272), jnp.float32),
             ((n,), jnp.int32), ((n,), jnp.float32))
