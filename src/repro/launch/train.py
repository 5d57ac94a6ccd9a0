"""Federated training driver.

Two modes:
  * ``--mode fl``     — the paper's workload: synthetic federated rounds with
    heterogeneous client architectures, FedFA (or baseline) aggregation,
    optional backdoor attackers, at the architecture's published widths
    (``--reduced``: the CPU-sized preset that examples/ and benchmarks/
    drive).
  * ``--mode dense``  — plain distributed pretraining of one architecture
    (the e2e driver for (b): train a ~100M model for a few hundred steps).

The round runs on the default JAX backend; ``--mesh`` shards it over the
meshes from repro.launch.mesh.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Optional

import numpy as np


def run_dense(arch: str, steps: int, batch: int, seq_len: int,
              log_every: int = 10, reduced: bool = True,
              seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.data import synthetic
    from repro.launch.steps import make_train_step
    from repro.models import model as model_mod
    from repro.optim import init_opt

    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    cfg = cfg.replace(grad_accum=1)
    key = jax.random.PRNGKey(seed)
    params = model_mod.init_params(cfg, key)
    opt = init_opt(params, cfg.optimizer)
    step_fn = jax.jit(make_train_step(cfg, total_steps=steps))

    data = synthetic.lm_stream(cfg.vocab_size, steps * batch, seq_len, seed=seed)
    losses = []
    t0 = time.time()
    for s in range(steps):
        tok = jnp.asarray(data[s * batch:(s + 1) * batch])
        batch_d = {"tokens": tok}
        if cfg.vision is not None:
            batch_d["patches"] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, s),
                (batch, cfg.vision.n_patches, cfg.vision.vit_dim))
        if cfg.encoder is not None:
            batch_d["frames"] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, s),
                (batch, cfg.encoder.n_frames, cfg.d_model))
        params, opt, loss = step_fn(params, opt, batch_d, jnp.asarray(s))
        losses.append(float(loss))
        if s % log_every == 0:
            print(f"step {s:4d}  loss {losses[-1]:.4f}  "
                  f"({(time.time()-t0)/(s+1):.2f}s/step)", flush=True)
    return {"arch": arch, "losses": losses,
            "first": float(np.mean(losses[:5])),
            "last": float(np.mean(losses[-5:]))}


def _make_accuracy(cfg, task: str, n_classes: int, chunk: int = 16):
    """Jitted eval: accuracy(params, tokens, labels, masks=, gates=,
    class_mask=) -> float.  ``cls`` scores the mean-pooled class logits
    against the labels; ``lm`` scores next-token predictions.  Width masks
    extract a client's sub-model.  Sequences run ``chunk`` at a time, so
    the (chunk, S, vocab) logits are all the eval holds beside the
    training state at published widths."""
    import jax
    import jax.numpy as jnp
    from repro.core.masking import apply_mask_tree, axis_mask_tree
    from repro.models import model as model_mod

    @jax.jit
    def hits(p, tokens, labels, masks, gates, class_mask):
        if masks is not None:
            p = apply_mask_tree(p, axis_mask_tree(cfg, masks))

        def one(xs):
            tok, lab = xs
            logits, _ = model_mod.forward(p, cfg, {"tokens": tok},
                                          masks=masks, gates=gates,
                                          remat=False)
            if task == "lm":
                lg, tgt = logits[:, :-1], tok[:, 1:]
                cm = class_mask
            else:
                lg, tgt = jnp.mean(logits[..., :n_classes], axis=1), lab
                cm = None if class_mask is None else class_mask[:n_classes]
            if cm is not None:
                lg = jnp.where(cm > 0, lg, -1e30)
            return jnp.sum((jnp.argmax(lg, -1) == tgt).astype(jnp.float32))

        n = tokens.shape[0] // chunk
        return jnp.sum(jax.lax.map(one, (
            tokens.reshape(n, chunk, *tokens.shape[1:]),
            labels.reshape(n, chunk, *labels.shape[1:]))))

    def accuracy(p, tokens, labels, masks=None, gates=None, class_mask=None):
        per_seq = tokens.shape[1] - 1 if task == "lm" else 1
        h = hits(p, jnp.asarray(tokens), jnp.asarray(labels), masks, gates,
                 class_mask)
        return float(h) / (tokens.shape[0] * per_seq)
    return accuracy


def client_arch_pool(cfg, mode: str, fracs=(0.25, 0.5, 0.75, 1.0)):
    """Paper's three flexibility regimes: depth-only (vs FlexiFed),
    width-only (vs HeteroFL), both (vs NeFL)."""
    import numpy as np
    from repro.models.masks import ClientArch, max_section_depths
    maxd = max_section_depths(cfg)
    depths = lambda f: tuple(max(1, int(np.ceil(f * m))) for m in maxd)
    if mode == "width":
        return [ClientArch(w, maxd) for w in fracs]
    if mode == "depth":
        return [ClientArch(1.0, depths(f)) for f in fracs]
    pool = [ClientArch(w, depths(f)) for w, f in
            [(0.25, 0.5), (0.5, 0.5), (0.5, 1.0), (0.75, 0.75), (1.0, 1.0)]]
    return pool


def run_fl(arch: str, rounds: int, n_clients: int, *, strategy: str = "fedfa",
           malicious_frac: float = 0.0, attack_lambda: float = 1.0,
           noniid: bool = False, local_steps: int = 2, batch: int = 4,
           seq_len: int = 32, n_classes: int = 10, lr: float = 0.05,
           participation: float = 0.5, seed: int = 0,
           eval_every: int = 5, task: str = "cls",
           width_mults=(0.25, 0.5, 0.75, 1.0),
           arch_mode: str = "width", agg_engine: str = "flat",
           driver: str = "resident", merge_k: int = 0,
           staleness_max: int = 4,
           async_deadline: float = float("inf"),
           mesh: Optional[str] = None,
           use_kernel: Optional[bool] = None,
           interpret: bool = False, update_dtype: str = "f32",
           ckpt: Optional[str] = None, reduced: bool = False,
           on_round: Optional[Callable] = None,
           quiet: bool = False) -> dict:
    """Federated training of the registered ``arch`` at its published
    widths.  ``reduced=True`` takes the CPU-sized preset instead:
    ``ArchConfig.reduced()`` with 4 layers in 2 sections, so depth
    flexibility stays real.  The ``cls`` task replaces the vocabulary with
    its class slots; ``lm`` keeps the published vocabulary.

    Driver, engine, admission dtype and mesh combine only where the driver
    supports them; any other combination raises rather than running a
    configuration that was not asked for.  ``on_round(r, loss)`` (resident
    driver) runs after each round is dispatched, with the round's loss
    still on the device.  Returns the eval history plus ``round_loss``,
    every round's (async: every merge's) mean client loss."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core.server import (ClientSpec, FLConfig, fl_round,
                                   make_client_specs, select_clients)
    from repro.data import partition as part_mod
    from repro.data import pipeline, synthetic
    from repro.models import model as model_mod
    from repro.models.masks import ClientArch, max_section_depths

    if driver in ("resident", "async") and agg_engine != "flat":
        raise ValueError(f"the {driver} driver runs the flat engine only; "
                         f"agg_engine={agg_engine!r} needs driver='per-round'")
    if update_dtype != "f32" and driver == "per-round":
        raise ValueError(f"update_dtype={update_dtype!r} needs a resident "
                         "cohort state: driver='resident' or 'async'")
    if mesh not in (None, "none") and driver == "per-round":
        raise ValueError("a mesh shards the resident/async drivers' cohort "
                         "axis; the per-round driver runs unsharded "
                         "(mesh='none')")

    cfg = get_arch(arch)
    if reduced:
        # 4 layers / 2 sections so DEPTH flexibility is real (reduced()
        # alone gives 2 layers -> both sections have max depth 1 and the
        # depth pool degenerates to homogeneous clients)
        cfg = cfg.reduced().replace(n_layers=4, n_sections=2)
    if task == "cls":
        cfg = cfg.replace(vocab_size=max(64, n_classes), tie_embeddings=False)
    key = jax.random.PRNGKey(seed)
    rng = np.random.default_rng(seed)
    params = model_mod.init_params(cfg, key)

    archs = client_arch_pool(cfg, arch_mode, width_mults)
    parts = (part_mod.noniid_partition(n_clients, n_classes, seed=seed)
             if noniid else part_mod.iid_partition(n_clients, n_classes, seed=seed))
    class_masks = [part_mod.client_class_mask(p, cfg.padded_vocab) for p in parts] \
        if noniid else None
    specs = make_client_specs(cfg, n_clients, archs=archs,
                              malicious_frac=malicious_frac,
                              class_masks=class_masks, seed=seed)
    profiles = synthetic.make_class_profiles(n_classes, cfg.vocab_size, seed=seed)
    fl = FLConfig(participation=participation, local_steps=local_steps,
                  lr=lr, attack_lambda=attack_lambda, strategy=strategy,
                  task=task, agg_engine=agg_engine, use_kernel=use_kernel,
                  interpret=interpret, update_dtype=update_dtype, seed=seed)

    hist = {"round": [], "loss": [], "global_acc": [], "local_acc": []}
    test = pipeline.eval_batch_cls(n_classes, cfg.vocab_size, 256, seq_len,
                                   profiles, seed=seed + 99)
    accuracy = _make_accuracy(cfg, task, n_classes)

    # local personalization metric (non-IID): extracted client models on
    # class-restricted local test sets (paper's "average local accuracy")
    local_eval = []
    for ci in range(min(4, n_clients)):
        d = pipeline.eval_batch_cls(n_classes, cfg.vocab_size, 64, seq_len,
                                    profiles, classes=parts[ci]["classes"],
                                    seed=seed + 300 + ci)
        s = specs[ci]
        cm = None if s.class_mask is None else jnp.asarray(s.class_mask)
        local_eval.append((s.arch.masks(cfg), s.arch.gates(cfg), cm, d))

    def global_acc(p):
        return accuracy(p, test["tokens"], test["labels"])

    def local_acc(p):
        return float(np.mean([accuracy(p, d["tokens"], d["labels"], masks=mk,
                                       gates=gt, class_mask=cm)
                              for mk, gt, cm, d in local_eval]))

    def round_data(r):
        """Host-side per-round cohort selection + batch synthesis (shared by
        both drivers so they see identical rounds)."""
        sel = select_clients(n_clients, participation, rng)
        batches_np = pipeline.round_batches_cls(
            parts, sel, n_classes, cfg.vocab_size, local_steps=local_steps,
            batch=batch, seq_len=seq_len, profiles=profiles,
            seed=seed * 1000 + r)
        return ([specs[i] for i in sel],
                {k: jnp.asarray(v) for k, v in batches_np.items()})

    def record_eval(r, loss, p):
        acc = global_acc(p)
        lacc = local_acc(p)
        hist["round"].append(r)
        hist["loss"].append(loss)
        hist["global_acc"].append(acc)
        hist["local_acc"].append(lacc)
        if not quiet:
            print(f"[{strategy}/{arch_mode}] round {r:3d} "
                  f"loss {loss:.4f} global_acc {acc:.3f} "
                  f"local_acc {lacc:.3f}", flush=True)

    from repro.launch.mesh import get_mesh
    mesh_obj = get_mesh(mesh)

    if driver == "resident":
        from repro.core.round import run_rounds
        params, hist["round_loss"] = run_rounds(
            params, cfg, fl, rounds, round_data, key, eval_every=eval_every,
            eval_fn=record_eval, ckpt_path=ckpt, mesh=mesh_obj,
            on_round=on_round)
    elif driver == "async":
        # continuous arrivals from the trace-driven population simulator:
        # clients keep their round_data specs/batches, but WHEN they arrive
        # comes from hashed device-class latency/availability traces, and
        # merges fire on merge_k arrivals or the deadline (rounds counts
        # MERGES here, so histories line up with the sync drivers)
        from repro.core.async_round import AsyncConfig, run_async
        from repro.sim import ClientPopulation, PopulationSource
        population = ClientPopulation(n_clients, seed=seed)
        capacity = max(1, int(round(participation * n_clients)))

        def batch_fn(d, ids):
            batches_np = pipeline.round_batches_cls(
                parts, ids, n_classes, cfg.vocab_size,
                local_steps=local_steps, batch=batch, seq_len=seq_len,
                profiles=profiles, seed=seed * 1000 + d)
            return {k: jnp.asarray(v) for k, v in batches_np.items()}

        source = PopulationSource(
            population, lambda ids: [specs[int(i)] for i in ids], batch_fn)
        acfg = AsyncConfig(
            capacity=capacity,
            merge_k=merge_k if merge_k > 0 else max(1, capacity // 2),
            staleness_max=staleness_max, deadline=async_deadline)
        params, hist["round_loss"] = run_async(
            params, cfg, fl, rounds, source, key, acfg=acfg,
            eval_every=eval_every, eval_fn=record_eval, ckpt_path=ckpt,
            mesh=mesh_obj)
    else:
        from repro.checkpoint import checkpoint as ckpt_mod
        from repro.core.round import eval_boundary
        round_loss = []
        for r in range(rounds):
            sel_specs, batches = round_data(r)
            params, loss = fl_round(params, cfg, fl, sel_specs, batches,
                                    jax.random.fold_in(key, r))
            round_loss.append(loss)
            if eval_boundary(r, rounds, eval_every):
                record_eval(r, float(loss), params)
                if ckpt is not None:
                    ckpt_mod.save(f"{ckpt}_r{r:05d}", params,
                                  meta={"round": r, "strategy": strategy})
        hist["round_loss"] = [float(x) for x in round_loss]
    # rounds=0 (or eval_every configurations that never fire) leaves the
    # history empty — a scripted sweep no-op, not an IndexError
    hist["final_acc"] = hist["global_acc"][-1] if hist["global_acc"] else None
    hist["final_local_acc"] = hist["local_acc"][-1] if hist["local_acc"] else None
    return hist


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["fl", "dense"], default="fl")
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--strategy", default="fedfa")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--malicious-frac", type=float, default=0.0)
    ap.add_argument("--attack-lambda", type=float, default=1.0)
    ap.add_argument("--noniid", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--participation", type=float, default=0.5,
                    help="fraction C of clients selected per round")
    ap.add_argument("--local-steps", type=int, default=2,
                    help="E local SGD steps per round")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--arch-mode", choices=["width", "depth", "both"],
                    default="width",
                    help="client flexibility regime (paper §5.1)")
    ap.add_argument("--task", choices=["cls", "lm"], default="cls")
    ap.add_argument("--eval-every", type=int, default=5,
                    help="<=0: evaluate on the final round only")
    ap.add_argument("--agg-engine", choices=["flat", "tree"], default="flat",
                    help="flat: the production engine; tree: slower "
                         "test-only differential oracle, kept for debugging")
    ap.add_argument("--driver", choices=["resident", "async", "per-round"],
                    default="resident",
                    help="resident: one jitted round program with donated "
                         "(N,)/(m,N) buffers; async: continuous-arrival "
                         "slot pool with bounded-staleness merges "
                         "(--rounds counts merges); per-round: re-dispatch "
                         "each round")
    ap.add_argument("--merge-k", type=int, default=0,
                    help="async: merge when this many updates arrived "
                         "(0 = half the pool capacity)")
    ap.add_argument("--staleness-max", type=int, default=4,
                    help="async: drop updates staler than this many "
                         "global versions")
    ap.add_argument("--async-deadline", type=float, default=float("inf"),
                    help="async: merge whatever arrived after this much "
                         "simulated time since the last merge")
    ap.add_argument("--mesh", choices=["none", "host", "production"],
                    default="none",
                    help="shard the resident round over the mesh: client "
                         "axis over data, (N,) parameter axis over model "
                         "(host: all local devices on data)")
    ap.add_argument("--mesh-shape", default=None, metavar="DxM",
                    help="explicit (data, model) mesh shape for the "
                         "resident round, e.g. 2x2 — D client shards x M "
                         "parameter shards; overrides --mesh")
    ap.add_argument("--use-kernel", choices=["auto", "on", "off"],
                    default="auto",
                    help="flat engine: Pallas kernel dispatch (auto=TPU "
                         "only; on raises off a TPU unless --interpret)")
    ap.add_argument("--interpret", action="store_true",
                    help="flat engine: run Pallas kernels in interpret mode")
    ap.add_argument("--update-dtype", choices=["f32", "bf16", "int8"],
                    default="f32",
                    help="cohort admission dtype (resident/async drivers): "
                         "int8/bf16 admit quantized rows with per-segment "
                         "scales + server-side error feedback; the fused "
                         "kernels dequantize in VMEM")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint path prefix (written at eval boundaries)")
    ap.add_argument("--reduced", action="store_true",
                    help="fl mode: the CPU-sized preset (reduced widths, 4 "
                         "layers) instead of the published widths")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "dense":
        res = run_dense(args.arch, args.steps, args.batch, args.seq_len)
    else:
        res = run_fl(args.arch, args.rounds, args.clients,
                     strategy=args.strategy,
                     malicious_frac=args.malicious_frac,
                     attack_lambda=args.attack_lambda, noniid=args.noniid,
                     batch=args.batch, seq_len=args.seq_len,
                     participation=args.participation,
                     local_steps=args.local_steps, lr=args.lr,
                     arch_mode=args.arch_mode, task=args.task,
                     eval_every=args.eval_every,
                     agg_engine=args.agg_engine, driver=args.driver,
                     merge_k=args.merge_k,
                     staleness_max=args.staleness_max,
                     async_deadline=args.async_deadline,
                     mesh=args.mesh_shape or args.mesh,
                     use_kernel={"auto": None, "on": True,
                                 "off": False}[args.use_kernel],
                     interpret=args.interpret,
                     update_dtype=args.update_dtype, ckpt=args.ckpt,
                     reduced=args.reduced)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
