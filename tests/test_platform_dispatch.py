"""No substitution that hides the device: a kernel asked for off the TPU
raises instead of interpreting, and run_fl refuses driver / engine /
admission / mesh combinations its drivers do not run instead of swapping
in another configuration."""
import jax.numpy as jnp
import pytest

from repro.kernels import use_pallas
from repro.kernels.fedfa_agg import ops as agg_ops
from repro.kernels.fedfa_quantile import ops as quant_ops
from repro.kernels.flash_attention.ops import attention
from repro.kernels.ssd import ops as ssd_ops

def test_use_pallas_modes():
    assert use_pallas(None, False) is False          # auto: jnp off the TPU
    assert use_pallas(False, False) is False
    assert use_pallas(True, True) and use_pallas(None, True)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        use_pallas(True, False)


_X = jnp.ones((4, 512), jnp.float32)
_KERNEL_CALLS = {
    "accumulate": lambda: agg_ops.accumulate(
        _X, jnp.ones((4,)), jnp.ones((512,)), use_kernel=True),
    "accumulate_quant": lambda: agg_ops.accumulate_quant(
        _X.astype(jnp.int8), jnp.ones((4,)), jnp.ones((4, 1)),
        jnp.zeros((512,), jnp.int32), jnp.ones((512,)), use_kernel=True),
    "trimmed_norm": lambda: agg_ops.trimmed_norm(
        _X[0], jnp.asarray(1.0), use_kernel=True),
    "row_trimmed_stats": lambda: quant_ops.row_trimmed_stats(
        _X, jnp.full((4,), 0.95), use_kernel=True),
    "attention": lambda: attention(
        jnp.ones((1, 8, 2, 64)), jnp.ones((1, 8, 2, 64)),
        jnp.ones((1, 8, 2, 64)), use_kernel=True),
    "ssd": lambda: ssd_ops.ssd(
        jnp.ones((1, 8, 2, 4)), jnp.ones((1, 8, 2)), -jnp.ones((2,)),
        jnp.ones((1, 8, 4)), jnp.ones((1, 8, 4)), 8, use_kernel=True),
}


@pytest.mark.parametrize("name", sorted(_KERNEL_CALLS))
def test_kernel_requested_off_tpu_raises(name):
    with pytest.raises(RuntimeError, match="needs a TPU"):
        _KERNEL_CALLS[name]()


@pytest.mark.parametrize("kw,match", [
    (dict(driver="resident", agg_engine="tree"), "flat engine only"),
    (dict(driver="async", agg_engine="tree"), "flat engine only"),
    (dict(driver="per-round", update_dtype="int8"), "resident cohort state"),
    (dict(driver="per-round", mesh="host"), "runs unsharded"),
])
def test_run_fl_refuses_substitutions(kw, match):
    from repro.launch.train import run_fl
    with pytest.raises(ValueError, match=match):
        run_fl("smollm-135m", 1, 2, local_steps=1, batch=2, seq_len=8,
               reduced=True, quiet=True, **kw)


def test_compile_cache_dir(monkeypatch):
    """The env var wins untouched; otherwise the fixed in-checkout path,
    which git ignores."""
    import os

    import jax
    from repro.launch import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    full = jax.config.jax_include_full_tracebacks_in_locations
    try:
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert not jax.config.jax_include_full_tracebacks_in_locations
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", full)
    root = os.path.dirname(compile_cache.CHECKOUT_CACHE)
    assert os.path.isfile(os.path.join(root, "chip_smoke.py"))
    with open(os.path.join(root, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
