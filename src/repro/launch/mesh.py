"""Production mesh construction (TPU v5e target).

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.

Every constructor validates the requested shape against the visible device
count and raises a ValueError naming both, instead of surfacing
``jax.make_mesh``'s opaque reshape failure.
"""
from __future__ import annotations

import re
from typing import Tuple

import jax


def _validated_mesh(shape, axes):
    need = 1
    for s in shape:
        need *= int(s)
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh shape {tuple(shape)} over axes {tuple(axes)} needs {need} "
            f"devices but only {have} are visible "
            f"(jax.device_count() == {have}); pick a shape whose product is "
            f"<= {have} or launch with more devices "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count=K on CPU)")
    # Auto axes: the round places its arrays with NamedShardings and
    # with_sharding_constraint, which Explicit axes (make_mesh's default)
    # refuse
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _validated_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU tests of the sharded code paths."""
    return _validated_mesh((1, 1), ("data", "model"))


def make_data_mesh(n_data=None):
    """All local devices on the ``data`` axis — the 1-D sharded resident
    round's mesh on CPU hosts (use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` to test
    multi-shard lowering without accelerators)."""
    n = jax.device_count() if n_data is None else n_data
    return _validated_mesh((n, 1), ("data", "model"))


def parse_mesh_shape(s: str) -> Tuple[int, int]:
    """``"DxM"`` -> (n_data, n_model), e.g. ``"2x2"`` -> (2, 2)."""
    m = re.fullmatch(r"(\d+)x(\d+)", s.strip().lower())
    if not m or int(m.group(1)) < 1 or int(m.group(2)) < 1:
        raise ValueError(f"mesh shape {s!r} is not of the form DxM "
                         f"(positive ints, e.g. 2x2)")
    return int(m.group(1)), int(m.group(2))


def make_mesh_2d(n_data: int, n_model: int):
    """Explicit (data, model) mesh — n_data client shards x n_model
    parameter shards (see ``repro.sharding.cohort``)."""
    return _validated_mesh((n_data, n_model), ("data", "model"))


def get_mesh(name):
    """CLI-level mesh selection: ``none`` | ``host`` | ``production`` | an
    explicit ``DxM`` shape (e.g. ``2x2``).

    ``host`` puts every local device on the data axis (degenerates to the
    1x1 host mesh on a single-device CPU); ``production`` is the TPU v5e
    pod mesh above; ``DxM`` builds a real 2-D (data, model) mesh — D client
    shards x M parameter shards.
    """
    if name is None or name == "none":
        return None
    if name == "host":
        return make_data_mesh()
    if name == "production":
        return make_production_mesh()
    if re.fullmatch(r"\d+x\d+", str(name).strip().lower()):
        return make_mesh_2d(*parse_mesh_shape(name))
    raise ValueError(f"unknown mesh {name!r} (none|host|production|DxM)")
