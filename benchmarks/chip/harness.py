"""One run of one cell: set-up, the measured window over the program's
resident round, the optional trace, and the check against the plain
reference.  ``run.py`` is the command; tests and ``calibrate.py`` call
``setup``/``window``/``check`` directly.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import tempfile
import time
from contextlib import nullcontext
from typing import Callable, Dict, List, Optional

import numpy as np

import spec
import traffic as traffic_mod
import weights as weights_mod

ROOT = spec.repo_root()
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
N_CHECKED = 3

# fedfa block key -> the program's ArchConfig field
_FEDFA_FIELDS = {"n_sections": "n_sections", "momentum": "momentum",
                 "weight_decay": "weight_decay",
                 "local_optimizer": "optimizer"}


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), every program cached; MLIR locations
    cut to the innermost frame so a kernel's cache key does not carry
    its caller's stack."""
    import jax
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def program_config(cfg: dict):
    """The program's ArchConfig for a configuration file: the registered
    architecture, with the keys the file lists in ``reduced`` replaced;
    every other size has to agree with the file.  The architecture's keys
    are the configuration's family's (``families/<family>.py``); the
    ``fedfa`` block is every family's."""
    from repro.configs import get_arch
    pcfg = spec.family_of(cfg).program_config(cfg, get_arch(cfg["registry"]))
    fed = cfg["fedfa"]
    if "fedfa" in cfg.get("reduced", []):
        pcfg = pcfg.replace(**{f: fed[k] for k, f in _FEDFA_FIELDS.items()})
    bad = {f: (getattr(pcfg, f), fed[k]) for k, f in _FEDFA_FIELDS.items()
           if getattr(pcfg, f) != fed[k]}
    if bad:
        raise ValueError(f"the program's {cfg['registry']} differs from "
                         f"{cfg['name']}.json: {bad}")
    return pcfg


def check_layout(cfg: dict, pcfg) -> None:
    """The program's parameter tree must be the layout the benchmark
    reads (``weights.shapes``)."""
    import jax
    from repro.models import model as model_mod
    prog = jax.eval_shape(lambda k: model_mod.init_params(pcfg, k),
                          jax.random.PRNGKey(0))
    mine = weights_mod.shapes(cfg)
    a = jax.tree_util.tree_structure(jax.tree.map(lambda x: 0, prog))
    b = jax.tree_util.tree_structure(
        jax.tree.map(lambda x: 0, mine,
                     is_leaf=weights_mod._is_shape))
    shp_p = [tuple(x.shape) for x in jax.tree.leaves(prog)]
    shp_m = [tuple(s) for s in jax.tree.leaves(
        mine, is_leaf=weights_mod._is_shape)]
    if a != b or shp_p != shp_m:
        raise ValueError("the program's parameter tree is not the layout "
                         "the benchmark reads")


@dataclasses.dataclass
class Cell:
    """Everything set-up built, handed to the window as it is."""
    cfg: dict
    traffic: dict
    seed: int
    chips: int
    rounds: List[traffic_mod.Round]
    driver: object
    g: object
    specs: list
    batches: list
    n: int
    m: int
    g0: np.ndarray
    prog_losses: List[float]
    prog_snaps: Dict[int, np.ndarray]
    next_round: int


def round_key(seed: int, r: int):
    import jax
    return jax.random.fold_in(weights_mod.seed_key(seed), r)


def setup(cfg: dict, traffic: dict, seed: int, chips: int,
          round_fn: Optional[Callable] = None) -> Cell:
    """Build the program's resident driver over weights and rounds made
    from the seed, and drive it through the checked rounds; returns the
    same driver and state for the window.

    ``round_fn(driver, g, specs, batches, key) -> (g, loss)`` replaces
    ``driver.round`` (tests plant faults through it)."""
    import jax.numpy as jnp
    from repro.core import flat
    from repro.core.round import ResidentDriver
    from repro.core.server import ClientSpec, FLConfig
    from repro.models.masks import ClientArch
    from repro.sharding import cohort as cohort_sh

    pcfg = program_config(cfg)
    check_layout(cfg, pcfg)
    rounds = traffic_mod.make_rounds(cfg, traffic, seed)
    m = traffic_mod.cohort_size(traffic)
    params = weights_mod.make_params(cfg, seed)
    index = flat.get_index(params, pad_to=cohort_sh.pad_unit(None))
    _, n = weights_mod.layout(cfg)
    if index.n != n:
        raise ValueError(f"flat length {index.n} != layout {n}")
    g = flat.flatten(index, params)
    del params
    fl = FLConfig(participation=traffic["participation"],
                  local_steps=traffic["local_steps"], lr=traffic["lr"],
                  strategy=traffic["strategy"], task="lm",
                  trim=traffic["trim"], seed=seed)
    driver = ResidentDriver(pcfg, fl, index)
    specs = [[ClientSpec(arch=ClientArch(c.width, c.depths),
                         n_data=c.n_data) for c in r.clients]
             for r in rounds]
    batches = [{"tokens": jnp.asarray(r.tokens)} for r in rounds]
    step = round_fn or (lambda d, *a: d.round(*a))
    g0 = np.asarray(g)
    losses, snaps = [], {}
    for r in range(N_CHECKED):
        g, loss = step(driver, g, specs[r], batches[r], round_key(seed, r))
        losses.append(float(loss))
        if r + 1 in (1, N_CHECKED):
            snaps[r + 1] = np.asarray(g)
    return Cell(cfg, traffic, seed, chips, rounds, driver, g, specs,
                batches, n, m, g0, losses, snaps, N_CHECKED)


def window(cell: Cell, seconds: float, trace_dir: Optional[str] = None,
           round_fn: Optional[Callable] = None) -> dict:
    """Rounds back to back for ``seconds``, as ``run_rounds`` dispatches
    them: round r+1 is enqueued before the host waits on round r.  The
    window closes when the last round dispatched in it has finished."""
    import jax
    step = round_fn or (lambda d, *a: d.round(*a))
    ann = (jax.profiler.TraceAnnotation if trace_dir
           else (lambda name: nullcontext()))
    P = len(cell.rounds)
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    done, pending = 0, None
    g, r = cell.g, cell.next_round
    with ann("bench.window"):
        t0 = time.perf_counter()
        while True:
            with ann("bench.dispatch"):
                g, loss = step(cell.driver, g, cell.specs[r % P],
                               cell.batches[r % P], round_key(cell.seed, r))
            r += 1
            if pending is not None:
                with ann("bench.wait"):
                    pending.block_until_ready()
                done += 1
            pending = loss
            if time.perf_counter() - t0 >= seconds:
                break
        with ann("bench.wait"):
            jax.block_until_ready((g, pending))
        done += 1
        t1 = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
    cell.g, cell.next_round = g, r
    return {"elapsed": t1 - t0, "rounds": done,
            "round_ids": list(range(cell.next_round - done,
                                    cell.next_round))}


def warm_profiler() -> None:
    """One throwaway trace around a device op before the window: the
    first device execution under a process's first trace can stall the
    host for seconds (2.5 s once on a v5e host), which belongs to set-up,
    not to the traced window."""
    import jax
    import jax.numpy as jnp
    with tempfile.TemporaryDirectory() as tdir:
        jax.profiler.start_trace(tdir)
        jax.block_until_ready(jnp.arange(8.0) + 1)
        jax.profiler.stop_trace()


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


def free_program(cell: Cell) -> None:
    """Drop the program's device state before the reference runs."""
    import jax
    from repro.core.server import clear_runtime_caches
    cell.g = None
    cell.driver = None
    cell.batches = None
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    clear_runtime_caches()      # it held arrays deleted above


def check(cell: Cell, ref_dtype=None, half_batch: bool = False):
    """The reference over the checked rounds, from the seed's weights;
    returns the compared numbers."""
    import jax.numpy as jnp
    import compare
    import reference
    t0 = time.perf_counter()
    params0 = weights_mod.make_params(cell.cfg, cell.seed)
    ref_losses, ref_snaps = reference.run(
        cell.cfg, cell.traffic, cell.rounds, params0, N_CHECKED,
        dtype=ref_dtype or jnp.float32, half_batch=half_batch)
    del params0
    t1 = time.perf_counter()
    nums = compare.numbers(cell.cfg, cell.g0, cell.prog_losses,
                           cell.prog_snaps, ref_losses, ref_snaps)
    print(f"check: reference {t1 - t0:.1f} s, comparison "
          f"{time.perf_counter() - t1:.1f} s", file=sys.stderr, flush=True)
    return nums, (ref_losses, ref_snaps)


def flops_per_round(cell: Cell, r: int) -> float:
    import work
    t = cell.traffic
    rnd = cell.rounds[r % len(cell.rounds)]
    return t["local_steps"] * sum(
        work.train_flops(cell.cfg, c.width, c.depths, t["batch"],
                         t["seq_len"]) for c in rnd.clients)


def device_info(chips: int) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": min(chips, jax.device_count())}


def per_layer(bench: dict, name: str, cell: Cell, win: dict,
              records: dict) -> Dict[str, dict]:
    """Every per-layer metric of this cell whose reader finds something."""
    import peaks as peaks_mod
    import trace as trace_mod
    info = device_info(cell.chips)
    ctx = {"cell": cell, "window": win, "records": records,
           "trace": trace_mod, "peaks": peaks_mod.peaks_for(info["kind"]),
           "flops_per_round": lambda r: flops_per_round(cell, r)}
    out = {}
    for m in spec.cell_metrics(bench, name, "per_layer"):
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(name: str, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    """One benchmark run of workload ``name``; returns the result line."""
    import jax
    import compare
    bench = spec.load_benchmark(ROOT)
    wl = spec.workload(bench, name)
    chips = int(wl["chips"])
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"{name} needs {chips} chips, JAX found "
                         f"{len(devs)}")
    enable_compile_cache()
    cfg, traf = spec.config(wl["config"]), spec.traffic(wl["traffic"])
    cell = setup(cfg, traf, seed, chips)
    if trace:
        warm_profiler()
    setup_s = time.perf_counter() - t_start
    import trace as trace_mod
    with tempfile.TemporaryDirectory() as tdir:
        win = window(cell, seconds, trace_dir=tdir if trace else None)
        records = trace_mod.load(tdir) if trace else None
    peak = memory_peak_bytes(chips)
    result_dev = device_info(chips)
    result_dev["memory_peak_bytes"] = peak
    if trace:
        result_dev["busy_s"] = trace_mod.busy_s(records)
        result_dev["window_s"] = trace_mod.window_s(records)
        metrics = per_layer(bench, name, cell, win, records)
        breakdown = {"device_ops": trace_mod.top_ops(records),
                     "idle_gaps": trace_mod.idle_gaps(records)}
    else:
        e2e = {"round_s": win["elapsed"] / win["rounds"],
               "client_updates_per_s":
                   cell.m * win["rounds"] / win["elapsed"],
               "peak_hbm_gib": peak / 2 ** 30,
               "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.cell_metrics(bench, name, "end_to_end")}
    attempted = cell.m * win["rounds"]
    free_program(cell)
    nums, _ = check(cell)
    lim = compare.limits(name)
    ok = compare.verdict(nums, lim)
    line = {"correct": bool(ok), "attempted": attempted,
            "failed": 0 if ok else attempted, "metrics": metrics,
            "device": result_dev}
    if trace:
        line["breakdown"] = breakdown
    line["checks"] = compare.report(nums, lim)
    return line
