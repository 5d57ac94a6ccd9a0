"""Backdoor robustness demo (paper Fig. 3, miniature).

Runs the same federated classification workload under FedFA and under
NeFL-style partial aggregation, with 20% malicious clients at attack
intensity lambda=20, and prints the accuracy drop of each.

Run:  PYTHONPATH=src python examples/backdoor_robustness.py  (~5 min CPU)
"""
from repro.launch.train import run_fl

ROUNDS, CLIENTS = 12, 8

print("=== clean runs ===")
clean = {s: run_fl("smollm-135m", ROUNDS, CLIENTS, strategy=s,
                   arch_mode="both", local_steps=2, batch=4, seq_len=32,
                   lr=0.05, eval_every=6, seed=0, reduced=True,
                   quiet=True)["final_acc"]
         for s in ["fedfa", "nefl"]}
print(clean)

print("=== attacked runs (20% malicious, lambda=20) ===")
attacked = {s: run_fl("smollm-135m", ROUNDS, CLIENTS, strategy=s,
                      arch_mode="both", malicious_frac=0.2,
                      attack_lambda=20.0, local_steps=2, batch=4,
                      seq_len=32, lr=0.05, eval_every=6, seed=0,
                      reduced=True, quiet=True)["final_acc"]
            for s in ["fedfa", "nefl"]}
print(attacked)

for s in ["fedfa", "nefl"]:
    print(f"{s:6s} clean={clean[s]:.3f} attacked={attacked[s]:.3f} "
          f"drop={clean[s]-attacked[s]:+.3f}")
print("expected (paper Table 1): FedFA's drop is smaller — layer grafting "
      "closes the incomplete-aggregation weak point.")
