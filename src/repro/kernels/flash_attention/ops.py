"""jit'd wrapper: pads to tile/lane boundaries, dispatches kernel vs oracle.

On TPU the Pallas kernel is the default; elsewhere the oracle runs, and
tests exercise the kernel with ``interpret=True``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import use_pallas
from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.kernel import flash_attention


def _pad_to(x: jax.Array, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "use_kernel", "interpret"))
def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              bq: int = 128, bk: int = 128,
              use_kernel: Optional[bool] = None,
              interpret: bool = False) -> jax.Array:
    """Public entry point; q (B,Sq,H,hd), k/v (B,Sk,K,hd)."""
    if not use_pallas(use_kernel, interpret):
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    B, Sq, H, hd = q.shape
    bq = min(bq, max(8, 1 << (Sq - 1).bit_length()))
    bk = min(bk, max(8, 1 << (k.shape[1] - 1).bit_length()))
    qp, Sq0 = _pad_to(q, 1, bq)
    kp, Sk0 = _pad_to(k, 1, bk)
    vp, _ = _pad_to(v, 1, bk)
    # pad head_dim to the 128-lane boundary for the MXU
    qp, hd0 = _pad_to(qp, 3, 128)
    kp, _ = _pad_to(kp, 3, 128)
    vp, _ = _pad_to(vp, 3, 128)
    out = flash_attention(qp, kp, vp, causal=causal, window=window,
                          bq=bq, bk=bk, kv_len=Sk0, scale=hd0 ** -0.5,
                          interpret=interpret)
    return out[:, :Sq0, :, :hd0]
