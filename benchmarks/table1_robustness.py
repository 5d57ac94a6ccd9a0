"""Table 1 analog: global/local test accuracy for FedFA vs FlexiFed /
HeteroFL / NeFL across depth / width / both flexibility, IID and non-IID,
clean and attacked (lambda=20, 20% malicious, attackers on the largest
architecture).  Synthetic classification stands in for CIFAR/FMNIST
(offline container; DESIGN.md).
"""
from __future__ import annotations

import json
import os
import time

MODES = [("depth", "flexifed"), ("width", "heterofl"), ("both", "nefl")]


def run(quick: bool = True, out: str = "results/table1.json",
        seed: int = 0, reuse: bool = True) -> dict:
    # the full 24-cell grid takes ~1 h on this single-core container; the
    # harness reuses a completed grid (delete results/table1.json or pass
    # reuse=False to force a fresh run).
    if reuse and os.path.exists(out):
        res = json.load(open(out))
        if sum(1 for k in res if "/drop/" in k) == 12:
            print(f"[table1] reusing completed grid from {out}")
            return res
    from repro.launch.train import run_fl
    rounds = 10 if quick else 40
    n_clients = 8 if quick else 24
    res = {}
    for mode, baseline in MODES:
        for dist in (["iid", "noniid"] if not quick else ["iid", "noniid"]):
            for attack in ["clean", "attacked"]:
                for strat in ["fedfa", baseline]:
                    tag = f"{mode}/{dist}/{attack}/{strat}"
                    t0 = time.time()
                    h = run_fl(
                        "smollm-135m", rounds, n_clients, strategy=strat,
                        arch_mode=mode, noniid=(dist == "noniid"),
                        malicious_frac=0.2 if attack == "attacked" else 0.0,
                        attack_lambda=20.0, local_steps=2, batch=4,
                        seq_len=32, lr=0.05, participation=0.5,
                        eval_every=max(rounds // 4, 1), seed=seed,
                        reduced=True, quiet=True)
                    res[tag] = dict(global_acc=h["final_acc"],
                                    local_acc=h["final_local_acc"],
                                    secs=round(time.time() - t0, 1))
                    import jax
                    jax.clear_caches()   # 24 configs x several jits: keep
                    # the single-core container's RSS bounded
                    print(f"{tag:38s} g={h['final_acc']:.3f} "
                          f"l={h['final_local_acc']:.3f}", flush=True)
    # accuracy drops under attack (the paper's robustness metric)
    for mode, baseline in MODES:
        for dist in ["iid", "noniid"]:
            for strat in ["fedfa", baseline]:
                c = res[f"{mode}/{dist}/clean/{strat}"]["global_acc"]
                a = res[f"{mode}/{dist}/attacked/{strat}"]["global_acc"]
                res[f"{mode}/{dist}/drop/{strat}"] = round(c - a, 4)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(res, f, indent=1)
    return res


if __name__ == "__main__":
    import sys
    run(quick="--full" not in sys.argv)
