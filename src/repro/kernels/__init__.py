"""Pallas kernels of the FL round, each with a jnp reference (``ref.py``)."""
from __future__ import annotations

from typing import Optional

import jax


def use_pallas(use_kernel: Optional[bool], interpret: bool) -> bool:
    """Whether a kernel wrapper runs its Pallas path.

    ``interpret=True`` runs the kernel in interpret mode on any backend (the
    CPU test fixture).  Otherwise the kernel compiles for the chip:
    ``use_kernel=None`` picks it on a TPU and the jnp reference elsewhere,
    and ``use_kernel=True`` off a TPU raises instead of quietly
    interpreting — an interpreted kernel is not the program that runs on
    the chip.
    """
    if interpret:
        return True
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        return on_tpu
    if use_kernel and not on_tpu:
        raise RuntimeError(
            f"use_kernel=True needs a TPU (backend is "
            f"{jax.default_backend()!r}); pass interpret=True to run the "
            f"kernel in interpret mode, or use_kernel=False for the jnp path")
    return bool(use_kernel)
