"""The one traffic generator: a traffic file's parameters plus a seed give
every round's cohort and batches, as host arrays.

A traffic file (``traffic/<name>.json``) states:

* ``population``: client classes, each a width class, a depth fraction
  (layers kept per section, ceil(f * section size)) and a count of clients;
* ``participation`` C: each round draws ``count * C`` clients of every
  class, so every round's cohort has the same class mix and the same work,
  and only which clients, their data counts and their tokens change with
  the seed (stratified selection);
* ``n_data_range``: each client's sample count, uniform on the inclusive
  range, the weight it carries in aggregation;
* ``local_steps``, ``batch``, ``seq_len``: each selected client's local
  work per round, ``(local_steps, batch, seq_len)`` tokens;
* ``token_zipf_a``: IID tokens, every client's from the same Zipf law
  over the vocabulary (the unigram shape of natural text), its ranks
  placed on the token ids by one permutation drawn from the seed;
* ``lr``, ``trim``, ``strategy``: the round's local learning rate, the
  trimmed-norm quantile and the aggregation rule;
* ``pool_rounds``: how many distinct rounds are made; the run cycles
  through them.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

import work


@dataclass(frozen=True)
class Client:
    width: float
    depths: Tuple[int, ...]
    n_data: int


@dataclass
class Round:
    clients: List[Client]
    tokens: np.ndarray          # (m, local_steps, batch, seq_len) int32


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per purpose, from any non-negative
    seed (Python ints of any size)."""
    return np.random.default_rng([int(seed), int(stream)])


def cohort_size(traffic: dict) -> int:
    m = 0
    for cls in traffic["population"]:
        k = cls["count"] * traffic["participation"]
        if abs(k - round(k)) > 1e-9:
            raise ValueError(f"class {cls} x C={traffic['participation']} "
                             f"is not a whole number of clients")
        m += int(round(k))
    return m


def population(cfg: dict, traffic: dict, seed: int) -> List[Client]:
    rng = rng_for(seed, 1)
    lo, hi = traffic["n_data_range"]
    out = []
    for cls in traffic["population"]:
        depths = work.section_depths(cfg, cls["depth"])
        for _ in range(cls["count"]):
            out.append(Client(float(cls["width"]), depths,
                              int(rng.integers(lo, hi, endpoint=True))))
    return out


def _zipf_cdf(vocab: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** a
    c = np.cumsum(p)
    return c / c[-1]


def make_rounds(cfg: dict, traffic: dict, seed: int) -> List[Round]:
    """``pool_rounds`` rounds of (cohort, tokens) from the seed."""
    pop = population(cfg, traffic, seed)
    by_class = {}
    for c in pop:
        by_class.setdefault((c.width, c.depths), []).append(c)
    C = traffic["participation"]
    E, B, S = traffic["local_steps"], traffic["batch"], traffic["seq_len"]
    V = cfg["vocab_size"]
    cdf = _zipf_cdf(V, traffic["token_zipf_a"])
    perm = rng_for(seed, 2).permutation(V).astype(np.int32)
    rng = rng_for(seed, 3)
    rounds = []
    for _ in range(traffic["pool_rounds"]):
        chosen = []
        for members in by_class.values():
            k = int(round(len(members) * C))
            idx = rng.choice(len(members), size=k, replace=False)
            chosen.extend(members[i] for i in idx)
        order = rng.permutation(len(chosen))
        chosen = [chosen[i] for i in order]
        ranks = np.searchsorted(cdf, rng.random((len(chosen), E, B, S)))
        ranks = np.minimum(ranks, V - 1)
        rounds.append(Round(chosen, perm[ranks]))
    return rounds
