"""Smoke run of the federated round on TPU at smollm-135m's published widths.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded round only

One chip: a kernel-vs-jnp check of one resident round (compile seconds,
cold and from the persistent cache, and ``tpu_custom_call`` in the compiled
round), then three resident rounds through ``repro.launch.train.run_fl``,
the normal training entry point.  Four chips: the resident round on a 2x2
(data, model) mesh against the same round on a 4x1 data mesh.

Weights and batches come from a seed through ``repro.data``; nothing is
read from outside the checkout.  Progress lines go to stdout; the last line
is one JSON object ``{"ok": true, "device": {...}}``.  Any failed phase
raises, so the script exits non-zero with no ``ok`` line — as it does when
JAX finds no TPU, or when ``src/`` is not beside it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "smollm-135m"     # published widths: 30 layers, d_model 576, vocab 49152
M_ONE_CHIP = 3           # largest cohort whose round fits one 16 GB v5e
M_FOUR_CHIPS = 4         # largest cohort both the 2x2 and 4x1 rounds hold
LOCAL_STEPS, BATCH, SEQ = 2, 8, 64
LR, SEED, N_CLASSES = 0.05, 0, 10
# Kernel vs jnp round: the training halves are the same computation, and
# the two aggregations pick bit-equal quantile thresholds.  They differ in
# f32 summation order only — the kernels accumulate the trimmed sum of
# squares tile by tile over up to 5.5e4 column tiles, the jnp path reduces
# pairwise — which moves the trimmed norms, hence alpha and the merged
# update, at ~1e-5 relative.  1e-4 leaves a 10x margin and is still far
# below a wrong threshold or a dropped segment (a >= 1e-2 change of the
# update).
KERNEL_VS_JNP_RTOL = 1e-4
# 2x2 vs 4x1: the meshes also partition local training differently, and
# its matmuls run at the TPU's default precision (bf16 passes, 2^-9
# relative rounding), so the trained rows may differ at that level before
# aggregation.  Compared on the update, norm-wise.
MESH_RTOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def _cohort(cfg, m: int):
    """(params, specs, batches) of one m-client width-class cohort."""
    import jax
    import jax.numpy as jnp
    from repro.core.server import make_client_specs
    from repro.data import partition, pipeline, synthetic
    from repro.launch.train import client_arch_pool
    from repro.models import model as model_mod

    params = model_mod.init_params(cfg, jax.random.PRNGKey(SEED))
    specs = make_client_specs(cfg, m, archs=client_arch_pool(cfg, "width"),
                              seed=SEED)
    parts = partition.iid_partition(m, N_CLASSES, seed=SEED)
    profiles = synthetic.make_class_profiles(N_CLASSES, cfg.vocab_size,
                                             seed=SEED)
    b = pipeline.round_batches_cls(parts, list(range(m)), N_CLASSES,
                                   cfg.vocab_size, local_steps=LOCAL_STEPS,
                                   batch=BATCH, seq_len=SEQ,
                                   profiles=profiles, seed=SEED)
    return params, specs, {k: jnp.asarray(v) for k, v in b.items()}


def _round_inputs(cfg, fl, index, params, specs, batches, key, mesh=None):
    """The resident round program's arguments, laid out as
    ``round.flat_round`` lays them out, with a fresh (not donated-from)
    global buffer."""
    import jax
    import jax.numpy as jnp
    from repro.core import flat
    from repro.core.server import default_class_masks, stack_runtimes
    from repro.sharding import cohort as csh

    masks, gates, gmaps, nd, cms, mal = stack_runtimes(cfg, specs)
    m = len(specs)
    g = flat.flatten(index, params)
    c = jnp.zeros((m, index.n_padded), jnp.float32)
    if mesh is not None:
        g = jax.device_put(g, csh.global_sharding(mesh))
        c = jax.device_put(c, csh.cohort_buffer_sharding(mesh))
    return (g, c, masks, gates, gmaps, nd,
            default_class_masks(cms, cfg, fl, m), mal, batches,
            jax.random.split(key, m))


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def one_chip(dev) -> None:
    import dataclasses

    import jax
    import numpy as np
    from repro.configs import get_arch
    from repro.core import flat
    from repro.core import round as round_mod
    from repro.core.server import FLConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.train import run_fl

    cache = enable_compile_cache()
    log(f"compile cache: {cache} ({_cache_entries(cache)} entries before)")
    cfg = get_arch(ARCH)
    m = M_ONE_CHIP
    params, specs, batches = _cohort(cfg, m)
    index = flat.get_index(params)
    log(f"arch: {ARCH} N={index.n} segments={index.n_segments} m={m} "
        f"layers={cfg.n_layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab_size}")

    # --- phase 1: kernel vs jnp round on one cohort, same key ---------------
    fl_k = FLConfig(participation=1.0, local_steps=LOCAL_STEPS, lr=LR,
                    strategy="fedfa", task="lm", use_kernel=True, seed=SEED)
    fl_j = dataclasses.replace(fl_k, use_kernel=False)
    key = jax.random.PRNGKey(SEED + 1)
    out = {}
    for name, fl in (("kernel", fl_k), ("jnp", fl_j)):
        fn = round_mod.make_flat_round(cfg, fl, index, any_malicious=False)
        args = _round_inputs(cfg, fl, index, params, specs, batches, key)
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        log(f"{name} round: trace+lower {t1 - t0:.1f} s, compile "
            f"{t2 - t1:.1f} s")
        if name == "kernel":
            n_calls = compiled.as_text().count("tpu_custom_call")
            log(f"kernel round: tpu_custom_call present: {n_calls > 0} "
                f"({n_calls} sites)")
            if n_calls == 0:
                raise RuntimeError("the kernel round holds no Pallas kernel")
            jax.clear_caches()         # so the next compile asks the disk
            again = fn.lower(*args)
            t3 = time.perf_counter()
            again.compile()
            log(f"kernel round: compile again from the persistent cache "
                f"{time.perf_counter() - t3:.1f} s")
            del again
            mem = compiled.memory_analysis()
            log(f"kernel round: compiled temp "
                f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, args "
                f"{mem.argument_size_in_bytes / 2**30:.2f} GiB")
        g_old = np.asarray(args[0])
        g_new, x, loss = compiled(*args)
        g_new = np.asarray(g_new)
        out[name] = (g_new, np.asarray(x), float(loss))
        del args, x, compiled
        log(f"{name} round: loss {out[name][2]:.6f}")
    (g_k, x_k, l_k), (g_j, x_j, l_j) = out["kernel"], out["jnp"]
    d_k, d_j = g_k - g_old, g_j - g_old
    rel = float(np.linalg.norm(d_k - d_j) / np.linalg.norm(d_j))
    log(f"kernel vs jnp: max|g_k - g_j| {np.abs(g_k - g_j).max():.3e}, "
        f"update rel. error {rel:.3e} (tolerance {KERNEL_VS_JNP_RTOL}), "
        f"losses equal: {l_k == l_j}, cohort rows equal: "
        f"{bool((x_k == x_j).all())}")
    if not (np.isfinite(g_k).all() and rel <= KERNEL_VS_JNP_RTOL):
        raise AssertionError(f"kernel round disagrees with the jnp round: "
                             f"update rel. error {rel:.3e}")
    np.testing.assert_allclose(g_k, g_j, rtol=KERNEL_VS_JNP_RTOL, atol=1e-7)
    del out, g_k, g_j, g_old, d_k, d_j, params, batches

    # --- phase 2: the training entry point, 3 resident rounds ---------------
    stamps = []

    def on_round(r, loss):
        loss.block_until_ready()
        stamps.append(time.perf_counter())

    t0 = time.perf_counter()
    hist = run_fl(ARCH, rounds=3, n_clients=2 * m, participation=0.5,
                  task="lm", driver="resident", arch_mode="width",
                  local_steps=LOCAL_STEPS, batch=BATCH, seq_len=SEQ, lr=LR,
                  n_classes=N_CLASSES, eval_every=0, seed=SEED,
                  on_round=on_round)
    t_all = time.perf_counter() - t0
    losses = hist["round_loss"]
    per_round = np.diff(stamps)
    log(f"run_fl: round losses {[round(x, 6) for x in losses]}")
    log(f"run_fl: first round (trace, compile, run) {stamps[0] - t0:.1f} s; "
        f"wall per round after the first "
        f"{[round(float(x), 3) for x in per_round]} s; whole call "
        f"{t_all:.1f} s")
    log(f"run_fl: final-round next-token accuracy {hist['final_acc']:.4f}")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} "
        f"({stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
        f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB)")
    if len(losses) != 3 or not np.isfinite(losses).all():
        raise AssertionError(f"round losses not finite: {losses}")


def four_chips() -> None:
    import jax
    import numpy as np
    from repro.configs import get_arch
    from repro.core import flat
    from repro.core import round as round_mod
    from repro.core.server import FLConfig, stack_runtimes
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import get_mesh
    from repro.sharding import cohort as csh

    if jax.device_count() != 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found "
                           f"{jax.device_count()}")
    enable_compile_cache()
    cfg = get_arch(ARCH)
    m = M_FOUR_CHIPS
    params, specs, batches = _cohort(cfg, m)
    fl = FLConfig(participation=1.0, local_steps=LOCAL_STEPS, lr=LR,
                  strategy="fedfa", task="lm", seed=SEED)
    key = jax.random.PRNGKey(SEED + 1)
    runtimes = stack_runtimes(cfg, specs)
    res = {}
    for name in ("2x2", "host"):
        mesh = get_mesh(name)
        index = flat.get_index(params, pad_to=csh.pad_unit(mesh))
        g = jax.device_put(flat.flatten(index, params),
                           csh.global_sharding(mesh))
        g_old = np.asarray(g)[:index.n]
        t0 = time.perf_counter()
        g_new, c_buf, loss = round_mod.flat_round(
            g, None, cfg, fl, index, runtimes, batches, key, mesh=mesh)
        loss = float(loss)
        t1 = time.perf_counter()
        # the resident buffers are spread over every device of the mesh
        devs_g = {s.device.id for s in g_new.addressable_shards}
        devs_c = {s.device.id for s in c_buf.addressable_shards}
        shard_g = g_new.addressable_shards[0].data.shape
        shard_c = c_buf.addressable_shards[0].data.shape
        log(f"{name} mesh {dict(mesh.shape)}: first round (compile + run) "
            f"{t1 - t0:.1f} s, loss {loss:.6f}; g_buf {g_new.shape} shard "
            f"{shard_g} on devices {sorted(devs_g)}; cohort {c_buf.shape} "
            f"shard {shard_c} on devices {sorted(devs_c)}")
        if len(devs_g) != 4 or len(devs_c) != 4:
            raise AssertionError(f"{name}: buffers not on all four devices")
        want_c = (c_buf.shape[0] // mesh.shape["data"],
                  index.n_padded // mesh.shape["model"])
        if shard_c != want_c:
            raise AssertionError(f"{name}: cohort shard {shard_c}, expected "
                                 f"{want_c}")
        res[name] = (np.asarray(g_new)[:index.n], g_old, loss)
        walls = []
        for _ in range(2):
            t2 = time.perf_counter()
            g_new, c_buf, _ = round_mod.flat_round(
                g_new, c_buf, cfg, fl, index, runtimes, batches, key,
                mesh=mesh)
            g_new.block_until_ready()
            walls.append(round(time.perf_counter() - t2, 3))
        log(f"{name}: wall per round after the first {walls} s")
        del g, g_new, c_buf
    (g_2d, g_old, l_2d), (g_1d, _, l_1d) = res["2x2"], res["host"]
    rel = float(np.linalg.norm((g_2d - g_old) - (g_1d - g_old))
                / np.linalg.norm(g_1d - g_old))
    log(f"2x2 vs 4x1: update rel. error {rel:.3e} (tolerance {MESH_RTOL}), "
        f"max|g_2x2 - g_4x1| {np.abs(g_2d - g_1d).max():.3e}, losses "
        f"{l_2d:.6f} / {l_1d:.6f}")
    if not (np.isfinite(g_2d).all() and rel <= MESH_RTOL):
        raise AssertionError(f"2x2 and 4x1 rounds disagree: {rel:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{dev.platform!r}")
    log(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")
    if args.chips == 4:
        four_chips()
    else:
        one_chip(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
