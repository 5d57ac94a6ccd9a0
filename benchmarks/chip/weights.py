"""The global model's parameters, made on the device from the seed, in the
tree layout the program consumes, and the flat layout of that tree.

The layout is the benchmark's own statement of the program's parameter
tree (global leaves beside ``"stages": ((block,),)``, each block's leaves
stacked over the layers), as the configuration's family
(``families/<family>.py``) gives it; ``harness`` checks it against the
program's ``init_params`` shapes before a run, so a change of the
program's layout stops the benchmark instead of misreading it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import spec

STACK = "stages/0/0/"


def shapes(cfg: dict) -> dict:
    """Parameter shapes, as the tree the program consumes: the
    configuration's family states it."""
    return spec.family_of(cfg).shapes(cfg)


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


@dataclass(frozen=True)
class Leaf:
    name: str                # "stages/0/0/attn/wq"
    shape: Tuple[int, ...]
    offset: int
    size: int
    stacked: bool            # leading axis = layers

    @property
    def short(self) -> str:
        """The name inside a block ("attn/wq"), or a global leaf's."""
        return self.name.split(STACK)[-1]


def layout(cfg: dict) -> Tuple[List[Leaf], int]:
    """Leaves in the order ``jax.tree.leaves`` visits them (dict keys
    sorted), with their offsets in the flat (N,) buffer; and N."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes(cfg),
                                                   is_leaf=_is_shape)
    leaves, off = [], 0
    for path, shp in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        size = int(np.prod(shp))
        leaves.append(Leaf(name, tuple(shp), off, size,
                           name.startswith("stages/")))
        off += size
    return leaves, off


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64 bits of it used."""
    seed = int(seed)
    k = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def make_params(cfg: dict, seed: int):
    """All parameters in one jitted call on the default device: every
    matrix N(0, initializer_range^2), norm scales 0, f32."""
    tree = shapes(cfg)
    std = float(cfg["initializer_range"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree,
                                                         is_leaf=_is_shape)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, shp) in zip(keys, flat):
            if str(getattr(path[-1], "key", "")) == "scale":
                out.append(jnp.zeros(shp, jnp.float32))
            else:
                out.append(std * jax.random.normal(k, shp, jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(seed_key(seed))
