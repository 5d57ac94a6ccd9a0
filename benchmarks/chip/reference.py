"""Plain reference of the timed FedFA round, written from the paper's
Alg. 1-3 and the configuration alone; it imports nothing of the program.

Each client trains its sub-model: the prefix of every axis that its width
class keeps (for the dense family the first ``d_model`` hidden channels,
heads and key/value heads and ``d_ff`` MLP channels), and the first d_s
layers of every depth section; the configuration's family
(``families/<family>.py``) gives those prefixes and the sub-model's
forward pass.  Local training is SGD with momentum and weight decay on
the next-token loss.  The server then grafts (a missing layer
takes the section's last trained layer), takes every client's trimmed
norm per layer tensor (L2 norm of the entries whose magnitude is at or
under the ``trim`` quantile of the client's active entries), scales each
client's tensor by alpha = mean norm / own norm, and merges
M = sum_c n_c alpha_c W_c / sum_c n_c over the clients holding each entry;
an entry no client holds keeps its value.

Every matmul of a family's forward pass runs at HIGHEST precision in
float32.  ``dtype=bfloat16`` computes the whole reference in bfloat16
instead: the control that a correct check must refuse.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import spec
import traffic as traffic_mod
import weights as weights_mod
import work


def loss(p: Dict[str, jax.Array], tokens: jax.Array, cfg: dict,
         sizes: Dict[str, Tuple[int, ...]]) -> jax.Array:
    """Mean next-token cross entropy of the sub-model ``p`` (its tensors
    by leaf name inside a block, ``sizes`` their active trailing sizes) on
    ``tokens`` (B, S): the configuration's family's forward pass."""
    return spec.family_of(cfg).loss(p, tokens, cfg, sizes)


def sub_sizes(cfg: dict, width: float) -> Dict[str, Tuple[int, ...]]:
    """Every leaf's active trailing sizes in a width class, by its name
    inside a block."""
    fam = spec.family_of(cfg)
    return {l.short: fam.axis_sizes(cfg, l, width)
            for l in weights_mod.layout(cfg)[0]}


def active_layers(cfg: dict, depths: Sequence[int]) -> List[int]:
    out = []
    for (lo, hi), d in zip(work.section_bounds(cfg), depths):
        out.extend(range(lo, lo + d))
    return out


def graft_rows(cfg: dict, depths: Sequence[int]) -> np.ndarray:
    """For every layer of the full stack, the index into the client's
    trained layers that fills it: itself where trained, else the
    section's last trained layer."""
    act = active_layers(cfg, depths)
    pos = {r: i for i, r in enumerate(act)}
    out = []
    for (lo, hi), d in zip(work.section_bounds(cfg), depths):
        for r in range(lo, hi):
            out.append(pos[min(r, lo + d - 1)])
    return np.asarray(out, np.int32)


@functools.lru_cache(maxsize=None)
def _trainer(cfg_key: str, width: float, lr: float, mom: float, wd: float,
             half_batch: bool):
    """Jitted local training of a group of clients of one width class:
    (shared start params, tokens (n, E, B, S)) -> (params (n, ...), mean
    losses (n,))."""
    import json
    cfg = json.loads(cfg_key)
    sizes = sub_sizes(cfg, width)

    def one(p0, toks):
        def step(carry, tok):
            p, m = carry
            if half_batch:
                tok = tok[: tok.shape[0] // 2]
            l, g = jax.value_and_grad(loss)(p, tok, cfg, sizes)
            m = jax.tree.map(lambda m_, g_, p_: mom * m_ + (g_ + wd * p_),
                             m, g, p)
            p = jax.tree.map(lambda p_, m_: p_ - lr * m_, p, m)
            return (p, m), l
        m0 = jax.tree.map(jnp.zeros_like, p0)
        (p, _), ls = jax.lax.scan(step, (p0, m0), toks)
        return p, jnp.mean(ls)

    return jax.jit(jax.vmap(one, in_axes=(None, 0)))


@functools.lru_cache(maxsize=None)
def _extractor(cfg_key: str, width: float, depths: Tuple[int, ...], dtype):
    """Jitted server -> client: a width class's prefix blocks of the
    first d_s layers of every section, as the sub-model's tensors."""
    import json
    cfg = json.loads(cfg_key)
    leaves, _ = weights_mod.layout(cfg)
    sizes = sub_sizes(cfg, width)
    rows = np.asarray(active_layers(cfg, depths))

    @jax.jit
    def extract(g):
        sub = {}
        for l in leaves:
            b = tuple(slice(0, n) for n in sizes[l.short])
            x = g[l.name]
            x = x[rows][(slice(None),) + b] if l.stacked else x[b]
            sub[l.short] = x.astype(dtype)
        return sub

    return extract


def _trimmed_norms(x: jax.Array, trim: float) -> jax.Array:
    """(n, rows, ...) active blocks -> (n, rows) trimmed L2 norms: the
    norm of the entries whose magnitude is at or under the ``trim``
    quantile of the row's."""
    a = jnp.abs(x.reshape(x.shape[0], x.shape[1], -1)).astype(jnp.float32)
    t = jnp.quantile(a, trim, axis=2, keepdims=True)
    return jnp.sqrt(jnp.sum(jnp.where(a <= t, a * a, 0.0), axis=2))


@functools.partial(jax.jit, static_argnames=("trim",))
def _merge_leaf(xs, grafts, nds, old, trim: float):
    """One layer tensor of the server's merge.  Per class of clients:
    ``xs`` their trained blocks (n, layers trained, ...) or (n, ...),
    ``grafts`` the full stack's source layer for each layer (or None),
    ``nds`` their sample counts.  ``old`` is the global tensor."""
    dt = old.dtype
    stacked = grafts[0] is not None
    rows = []
    for x, gr in zip(xs, grafts):
        rows.append(jnp.take(x, gr, axis=1) if stacked else x[:, None])
    norms = [_trimmed_norms(x, trim) for x in rows]            # (n, rows)
    m = sum(n.shape[0] for n in norms)
    mean = sum(jnp.sum(n, axis=0) for n in norms) / m
    full = old.shape if stacked else (1,) + old.shape
    Mp = jnp.zeros(full, dt)
    Gm = jnp.zeros(full, dt)
    for x, nd, nr in zip(rows, nds, norms):
        alpha = (mean / jnp.maximum(nr, 1e-12)).astype(dt)       # (n, rows)
        w = nd.astype(dt)[:, None] * alpha
        w = w.reshape(w.shape + (1,) * (x.ndim - 2))
        pad = [(0, f - k) for f, k in zip(full, x.shape[1:])]
        Mp = Mp + jnp.pad(jnp.sum(w * x, axis=0), pad)
        Gm = Gm + jnp.pad(jnp.full(x.shape[1:], jnp.sum(nd), dt), pad)
    new = jnp.where(Gm > 0, Mp / jnp.maximum(Gm, 1e-12), old.reshape(full))
    return new.reshape(old.shape)


def run_round(g: Dict[str, jax.Array], rnd: traffic_mod.Round, cfg: dict,
              traffic: dict, leaves, dtype=jnp.float32,
              half_batch: bool = False):
    """One reference round: (new global {leaf: array}, mean client loss)."""
    import json
    lr, trim = traffic["lr"], traffic["trim"]
    mom = float(cfg["fedfa"]["momentum"])
    wd = float(cfg["fedfa"]["weight_decay"])
    key = json.dumps(cfg, sort_keys=True)
    groups: Dict[Tuple, List[int]] = {}
    for i, c in enumerate(rnd.clients):
        groups.setdefault((c.width, c.depths), []).append(i)
    losses = np.zeros(len(rnd.clients))
    trained = []                       # (depths, params, counts) a class
    # classes in one fixed order, so the merge compiles once for a cell
    # and not once for every order in which a round's clients arrive
    for (w, depths), idx in sorted(groups.items()):
        sub = _extractor(key, w, depths, dtype)(g)
        toks = jnp.asarray(rnd.tokens[np.asarray(idx)])
        fn = _trainer(key, w, float(lr), mom, wd, half_batch)
        p, ls = fn(sub, toks)
        losses[idx] = np.asarray(ls, np.float64)
        nd = jnp.asarray([rnd.clients[i].n_data for i in idx], jnp.float32)
        trained.append((depths, p, nd))
    new = {}
    for l in leaves:
        s = l.short
        grafts = tuple(jnp.asarray(graft_rows(cfg, d)) if l.stacked else None
                       for d, _, _ in trained)
        new[l.name] = _merge_leaf(tuple(p[s] for _, p, _ in trained), grafts,
                                  tuple(nd for _, _, nd in trained),
                                  g[l.name].astype(dtype), trim=trim)
    return new, float(np.mean(losses))


def run(cfg: dict, traffic: dict, rounds: Sequence[traffic_mod.Round],
        params0, n_rounds: int = 3, dtype=jnp.float32,
        half_batch: bool = False):
    """The reference over the first ``n_rounds`` rounds from ``params0``
    (the tree ``weights.make_params`` made).  Returns (losses, {round
    number after which: host flat (N,) float64 buffer}) for rounds 1 and
    ``n_rounds``."""
    leaves, n = weights_mod.layout(cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(params0)
    g = {}
    for (path, x), l in zip(flat, leaves):
        g[l.name] = x.astype(dtype)
    snaps, losses = {}, []
    for r in range(n_rounds):
        g, lo = run_round(g, rounds[r], cfg, traffic, leaves, dtype=dtype,
                          half_batch=half_batch)
        losses.append(lo)
        if r + 1 in (1, n_rounds):
            snaps[r + 1] = np.concatenate(
                [np.asarray(g[l.name].astype(jnp.float32),
                            np.float64).ravel() for l in leaves])
    return losses, snaps
