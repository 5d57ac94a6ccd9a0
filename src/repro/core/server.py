"""Federated server: round orchestration (Alg. 1 lines 4-25).

The round is one SPMD program: selected clients' runtimes (width masks,
depth gates, graft maps, data counts, class masks, malicious flags) are
stacked along a leading client axis, local training is vmapped over it, and
the flat engine reduces over it.  The resident driver
(``repro.core.round``) shards that client axis over the mesh ``data`` axis
when given a mesh (``repro.sharding.cohort`` builds the NamedShardings;
``launch/train.py --mesh`` threads it through); the per-round path here
runs unsharded.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import attacks as attacks_mod
from repro.core import fedfa
from repro.core.client import local_update
from repro.models.masks import (ClientArch, WidthMasks, full_client,
                                max_section_depths, stack_masks)

Params = Dict[str, Any]


@dataclass
class ClientSpec:
    arch: ClientArch
    n_data: int
    malicious: bool = False
    class_mask: Optional[np.ndarray] = None   # (V,) non-IID logit zeroing


@dataclass
class FLConfig:
    participation: float = 0.1          # C
    local_steps: int = 5                # E (steps == epochs on synthetic data)
    lr: float = 0.01
    attack_lambda: float = 1.0          # λ in Eq. 1
    strategy: str = "fedfa"
    task: str = "lm"
    trim: float = 0.95
    agg_engine: str = "flat"            # "flat" (fused buffer) | "tree"
    use_kernel: Optional[bool] = None   # flat engine: Pallas kernels (None=auto)
    interpret: bool = False             # flat engine: interpret-mode kernels
    update_dtype: str = "f32"           # cohort admission dtype: f32|bf16|int8
    seed: int = 0


def select_clients(n_clients: int, frac: float, rng: np.random.Generator) -> np.ndarray:
    m = max(1, int(round(frac * n_clients)))
    return rng.choice(n_clients, size=m, replace=False)


_RUNTIME_CACHE: "OrderedDict[Tuple[ArchConfig, Any], Tuple]" = OrderedDict()
_RUNTIME_CACHE_MAX = 256


def _arch_runtime(cfg: ArchConfig, arch) -> Tuple:
    """Memoized (masks, gates, graft map) for one (cfg, arch) — ClientSpec
    architectures repeat across rounds, so cohort assembly shouldn't rebuild
    the same host-side device arrays every round.  LRU-bounded like
    ``flat._INDEX_CACHE``."""
    key = (cfg, arch)
    hit = _RUNTIME_CACHE.get(key)
    if hit is None:
        hit = _RUNTIME_CACHE[key] = (arch.masks(cfg), arch.gates(cfg),
                                     arch.graft(cfg))
        while len(_RUNTIME_CACHE) > _RUNTIME_CACHE_MAX:
            _RUNTIME_CACHE.popitem(last=False)
    else:
        _RUNTIME_CACHE.move_to_end(key)
    return hit


def stack_runtimes(cfg: ArchConfig, specs: Sequence[ClientSpec]):
    per_arch = [_arch_runtime(cfg, s.arch) for s in specs]
    masks = stack_masks([t[0] for t in per_arch])
    gates = jnp.stack([t[1] for t in per_arch])
    gmaps = jnp.stack([t[2] for t in per_arch])
    nd = jnp.asarray([float(s.n_data) for s in specs], jnp.float32)
    cms = None
    if any(s.class_mask is not None for s in specs):
        V = cfg.padded_vocab
        cms = jnp.stack([
            jnp.asarray(s.class_mask if s.class_mask is not None
                        else np.ones(V, np.float32)) for s in specs])
    mal = jnp.asarray([s.malicious for s in specs], jnp.float32)
    return masks, gates, gmaps, nd, cms, mal


# constant across rounds — cached so the resident round path doesn't
# re-allocate an (m, V) device array every round.  A plain dict (not
# lru_cache) keyed ALSO on the active backend, with deleted-array checks:
# a process-global lru_cache leaked stale-backend device arrays across
# forced-device-count subprocesses and mesh teardowns.
_MASK_CACHE: Dict[Tuple[int, int, str], jax.Array] = {}


def _ones_class_masks(m: int, vocab: int) -> jax.Array:
    key = (m, vocab, jax.default_backend())
    hit = _MASK_CACHE.get(key)
    if hit is None or hit.is_deleted():
        hit = _MASK_CACHE[key] = jnp.ones((m, vocab), jnp.float32)
    return hit


def clear_runtime_caches() -> None:
    """Drop every cached device array this module holds (the per-arch
    runtime tuples and the all-ones class masks).  Test fixtures call this
    between backend/mesh reconfigurations so arrays from a torn-down
    backend can't leak into the next test."""
    _MASK_CACHE.clear()
    _RUNTIME_CACHE.clear()


def default_class_masks(cms: Optional[jax.Array], cfg: ArchConfig,
                        fl: FLConfig, m: int) -> Optional[jax.Array]:
    """Stacked class masks for vmapped training: all-ones on the cls task when
    no client restricts its classes, None on tasks without class masking."""
    if cms is not None:
        return cms
    return _ones_class_masks(m, cfg.padded_vocab) if fl.task == "cls" else None


def cohort_update(global_params: Params, cfg: ArchConfig, fl: FLConfig,
                  masks: WidthMasks, gates: jax.Array, client_batches,
                  cms: Optional[jax.Array], mal: jax.Array, keys: jax.Array,
                  *, any_malicious: bool) -> Tuple[Params, jax.Array]:
    """Vmapped local training over the stacked cohort (Alg. 1 lines 7-10),
    including the malicious label-shuffle branch when the cohort has
    attackers.  Shared by the per-round path (``fl_round``) and the resident
    flat driver (``repro.core.round``).  Returns (stacked updated params with
    leading client axis m, (m,) mean local losses)."""

    def train_one(mk, gt, batches, cm, mal_flag, k):
        honest, losses = local_update(
            global_params, cfg, batches, masks=mk, gates=gt, lr=fl.lr,
            task=fl.task, class_mask=cm, optimizer=cfg.optimizer,
            momentum=cfg.momentum, weight_decay=cfg.weight_decay)
        if any_malicious:
            poisoned = attacks_mod.shuffle_labels(batches, k, fl.task)
            bad, _ = local_update(
                global_params, cfg, poisoned, masks=mk, gates=gt, lr=fl.lr,
                task=fl.task, class_mask=cm, optimizer=cfg.optimizer,
                momentum=cfg.momentum, weight_decay=cfg.weight_decay)
            attacked = attacks_mod.combine_malicious(
                global_params, honest, bad, fl.attack_lambda)
            out = jax.tree.map(
                lambda h, a: jnp.where(mal_flag > 0, a, h), honest, attacked)
        else:
            out = honest
        return out, jnp.mean(losses)

    with jax.named_scope("fedfa.train"):
        if cms is None:
            return jax.vmap(
                lambda mk, gt, b, fl_, k: train_one(mk, gt, b, None, fl_, k)
            )(masks, gates, client_batches, mal, keys)
        return jax.vmap(train_one)(masks, gates, client_batches, cms, mal,
                                   keys)


def fl_round(global_params: Params, cfg: ArchConfig, fl: FLConfig,
             specs: Sequence[ClientSpec], client_batches, key,
             *, any_malicious: Optional[bool] = None) -> Tuple[Params, jax.Array]:
    """One synchronized round over the given (already selected) clients.

    client_batches: pytree with leading axes (m, E, B, ...) — per-client
    local datasets for E local steps.  Returns (new_global, mean local loss).
    """
    masks, gates, gmaps, nd, cms, mal = stack_runtimes(cfg, specs)
    if any_malicious is None:
        any_malicious = any(s.malicious for s in specs)

    m = nd.shape[0]
    keys = jax.random.split(key, m)
    cms_in = default_class_masks(cms, cfg, fl, m)
    updated, losses = cohort_update(
        global_params, cfg, fl, masks, gates, client_batches, cms_in, mal,
        keys, any_malicious=any_malicious)

    new_global = fedfa.aggregate_strategy(
        fl.strategy, global_params, updated, cfg, masks, gates, gmaps, nd,
        trim=fl.trim, engine=fl.agg_engine, use_kernel=fl.use_kernel,
        interpret=fl.interpret)
    return new_global, jnp.mean(losses)


def fl_round_flat(g_buf: jax.Array, cfg: ArchConfig, fl: FLConfig,
                  specs: Sequence[ClientSpec], client_batches, key,
                  *, index=None, c_buf: Optional[jax.Array] = None,
                  any_malicious: Optional[bool] = None, mesh=None):
    """Flat-native counterpart of ``fl_round``: one round on the resident
    (N,) global buffer, sharing ``stack_runtimes`` with the per-round path.

    Dispatches to the donated, jitted round program in ``repro.core.round``
    (compiled once per cohort shape).  Returns (new g_buf, new (m, N) cohort
    buffer to donate back next round, mean local loss).  For multi-round
    training prefer ``repro.core.round.run_rounds``, which also manages the
    scratch cohort buffers.
    """
    from repro.core import round as round_mod
    if index is None:
        raise ValueError("fl_round_flat needs the FlatIndex the resident "
                         "buffer was flattened with (flat.get_index(params))")
    runtimes = stack_runtimes(cfg, specs)
    if any_malicious is None:
        any_malicious = any(s.malicious for s in specs)
    return round_mod.flat_round(g_buf, c_buf, cfg, fl, index, runtimes,
                                client_batches, key, mesh=mesh,
                                any_malicious=any_malicious)


# ---------------------------------------------------------------------------
# Scenario helpers (paper §5.1 experimental setup)
# ---------------------------------------------------------------------------

def make_client_specs(cfg: ArchConfig, n_clients: int, *,
                      archs: Sequence[ClientArch],
                      malicious_frac: float = 0.0,
                      n_data_range: Tuple[int, int] = (100, 250),
                      class_masks: Optional[Sequence[np.ndarray]] = None,
                      seed: int = 0) -> List[ClientSpec]:
    """Half the clients take the smallest architecture (paper §5.1), the
    rest get the supplied (e.g. NAS-chosen) architectures; attackers use the
    largest architecture (paper §3.1).  ``n_data_range`` is INCLUSIVE on
    both ends — the paper's 100-250 samples means 250 is drawable."""
    rng = np.random.default_rng(seed)
    smallest = min(archs, key=lambda a: (a.width_mult, sum(a.section_depths)))
    n_mal = int(round(malicious_frac * n_clients))
    mal_ids = set(rng.choice(n_clients, size=n_mal, replace=False).tolist()) \
        if n_mal else set()
    specs = []
    for i in range(n_clients):
        if i in mal_ids:
            arch = full_client(cfg)                    # largest architecture
        elif i % 2 == 0:
            arch = smallest
        else:
            arch = archs[int(rng.integers(len(archs)))]
        specs.append(ClientSpec(
            arch=arch,
            n_data=int(rng.integers(*n_data_range, endpoint=True)),
            malicious=i in mal_ids,
            class_mask=None if class_masks is None else class_masks[i]))
    return specs
