"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time of the
benchmark payload; derived = the table's headline metric).
"""
from __future__ import annotations

import json
import os
import sys
import time


def _row(name, us, derived):
    print(f"{name},{us:.0f},{derived}", flush=True)


def bench_table1(quick=True):
    from benchmarks import table1_robustness
    t0 = time.time()
    res = table1_robustness.run(quick=quick)
    drops_fedfa = [v for k, v in res.items() if "/drop/fedfa" in k]
    drops_base = [v for k, v in res.items()
                  if "/drop/" in k and not k.endswith("fedfa")]
    d = (f"fedfa_mean_drop={sum(drops_fedfa)/len(drops_fedfa):.3f};"
         f"baseline_mean_drop={sum(drops_base)/len(drops_base):.3f}")
    _row("table1_robustness", (time.time() - t0) * 1e6, d)


def bench_table2():
    from benchmarks import table2_macs
    t0 = time.time()
    res = table2_macs.run()
    _row("table2_macs", (time.time() - t0) * 1e6,
         f"avg_TMACs_both={res['both']['avg_TMACs']:.4f}")


def bench_table3(quick=True):
    from benchmarks import table3_perplexity
    t0 = time.time()
    res = table3_perplexity.run(quick=quick)
    fed = sum(v for k, v in res.items() if "/fedfa" in k) / 3
    base = sum(v for k, v in res.items() if "/fedfa" not in k) / 3
    _row("table3_perplexity", (time.time() - t0) * 1e6,
         f"fedfa_ppl={fed:.1f};baseline_ppl={base:.1f}")


def bench_table10(quick=True):
    from benchmarks import table10_scale_variation
    t0 = time.time()
    res = table10_scale_variation.run(quick=quick)
    ratios = [v["dist_over_baseline_mag"] for k, v in res.items()
              if "dist_over_baseline_mag" in v]
    _row("table10_scale_variation", (time.time() - t0) * 1e6,
         f"dist_ratio_range={min(ratios):.2f}-{max(ratios):.2f}")


def bench_appendixB(quick=True):
    from benchmarks import appendixB_similarity
    t0 = time.time()
    res = appendixB_similarity.run(quick=quick)
    _row("appendixB_similarity", (time.time() - t0) * 1e6,
         f"cos_init={res['epoch0']['functional_cos']:.3f};"
         f"cos_trained={res['trained']['functional_cos']:.3f}")


def bench_kernels():
    """Micro-bench the attention oracle (CPU wall time — indicative only;
    the Pallas kernels target TPU and are validated in interpret mode)."""
    import jax
    from repro.kernels.flash_attention import ref as fa_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 512, 4, 64))
    k = jax.random.normal(ks[1], (2, 512, 2, 64))
    v = jax.random.normal(ks[2], (2, 512, 2, 64))
    f = jax.jit(lambda q, k, v: fa_ref.attention_ref(q, k, v))
    f(q, k, v).block_until_ready()
    t0 = time.time()
    for _ in range(5):
        f(q, k, v).block_until_ready()
    _row("kernel_attention_ref_cpu", (time.time() - t0) / 5 * 1e6, "oracle")


def bench_aggregation():
    """Server aggregation throughput (params/s) at CPU scale."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core import fedfa
    from repro.models import model as model_mod
    from repro.models.masks import full_client, stack_masks
    cfg = get_arch("smollm-135m").reduced()
    p = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    m = 8
    stacked = jax.tree.map(lambda x: jnp.stack([x] * m), p)
    fc = full_client(cfg)
    masks = stack_masks([fc.masks(cfg)] * m)
    gates = jnp.stack([fc.gates(cfg)] * m)
    gmaps = jnp.stack([fc.graft(cfg)] * m)
    nd = jnp.ones((m,))
    # flat engine = the production server path (see benchmarks/bench_aggregate
    # for the tree-vs-flat comparison)
    f = jax.jit(lambda g, s: fedfa.aggregate(g, s, cfg, masks, gates, gmaps,
                                             nd, graft=True, scale=True,
                                             engine="flat"))
    jax.block_until_ready(f(p, stacked))
    n_params = sum(x.size for x in jax.tree.leaves(p))
    t0 = time.time()
    for _ in range(3):
        jax.block_until_ready(f(p, stacked))
    dt = (time.time() - t0) / 3
    _row("fedfa_aggregate_8clients", dt * 1e6,
         f"params_per_s={m*n_params/dt:.2e}")


def check() -> None:
    """Tier-1 CI gate: the repo's fast test suite plus smoke benchmarks of
    the resident round driver, the sharded round path, and the fused
    trimmed-quantile path (structural row-read/sort/collective gates), so
    perf and sharding regressions fail loudly alongside correctness ones.
    Exits non-zero on any failure.

        PYTHONPATH=src python benchmarks/run.py --check
    """
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # sharded smoke runs on a forced-4-device CPU backend so the cohort-axis
    # collectives are actually in the lowering (XLA_FLAGS is read at jax
    # init, hence a subprocess env, not a runtime switch); pinned to the
    # CPU, since these are rehearsals and not chip runs
    shard_env = dict(env, JAX_PLATFORMS="cpu")
    shard_env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                              " --xla_force_host_platform_device_count=4"
                              ).strip()
    steps = [
        ("tier-1 tests", [sys.executable, "-m", "pytest", "-x", "-q"], env),
        ("round-path smoke bench",
         [sys.executable, os.path.join(root, "benchmarks", "bench_round.py"),
          "--smoke", "--min-speedup", "1.5"], env),
        ("sharded-round smoke bench (4 forced CPU devices)",
         [sys.executable, os.path.join(root, "benchmarks", "bench_shard.py"),
          "--smoke"], shard_env),
        # 2x2 (data, model) smoke: reduce-scattered aggregation — gates
        # 0 all-gathers in the aggregation path, >= 1 reduce-scatter, and
        # per-device all-reduce volume N/n_model
        ("2-D sharded-round smoke bench (2x2 on 4 forced CPU devices)",
         [sys.executable, os.path.join(root, "benchmarks", "bench_shard.py"),
          "--smoke", "--model-shards", "2",
          "--out", "results/BENCH_shard_2d_smoke.json"], shard_env),
        ("quantile-path smoke bench (4 forced CPU devices)",
         [sys.executable,
          os.path.join(root, "benchmarks", "bench_quantile.py"),
          "--smoke"], shard_env),
        # async engine smoke: parity mode bit-equal to run_rounds, >= 1.3x
        # simulated rounds/sec over the sync driver under the skewed
        # device-class trace, zero all-gathers in the merge aggregation
        ("async-engine smoke bench (4 forced CPU devices)",
         [sys.executable, os.path.join(root, "benchmarks", "bench_async.py"),
          "--smoke", "--min-ratio", "1.3"], shard_env),
        # program-contract check: every declared Contract (round, agg,
        # async admit/merge, quantile) evaluated on freshly lowered
        # programs, plus the cache-key / recompile-audit passes.  --json
        # emits the machine-readable report validated below — trusting
        # exit status alone would miss a check that silently skipped a
        # program or dropped the peak-bytes fields.
        ("program-contract check (4 forced CPU devices)",
         [sys.executable, "-m", "repro.analysis", "check", "--quiet",
          "--json", os.path.join(root, "results", "ANALYSIS.json")],
         shard_env),
        ("FL source lints",
         [sys.executable, "-m", "repro.analysis", "lint",
          os.path.join(root, "src")], env),
    ]
    for name, cmd, step_env in steps:
        print(f"== {name}: {' '.join(cmd)}", flush=True)
        rc = subprocess.call(cmd, cwd=root, env=step_env)
        if rc != 0:
            print(f"CHECK FAILED at {name} (exit {rc})", flush=True)
            sys.exit(rc)
    problems = _validate_analysis_json(
        os.path.join(root, "results", "ANALYSIS.json"))
    if problems:
        for p in problems:
            print(f"ANALYSIS.json invalid: {p}", flush=True)
        print("CHECK FAILED at ANALYSIS.json validation", flush=True)
        sys.exit(1)
    print("CHECK OK", flush=True)


def _validate_analysis_json(path: str) -> list:
    """Sanity-gate the machine-readable contract report: all fifteen
    canonical programs are present (including the quantized round and
    quantized admit), every one declares AND measures
    peak_live_bytes_per_device, nothing failed, and the sharded programs
    carry collective provenance (blame) rows."""
    problems = []
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError) as e:
        return [f"unreadable: {e}"]
    if not data.get("ok"):
        problems.append("top-level ok flag is false")
    progs = {p.get("program"): p for p in data.get("programs", [])}
    expected = ("round/ms1", "round/ms2", "round/quant",
                "agg/ms1", "agg/ms2",
                "async/admit", "async/admit-quant", "async/merge",
                "async/merge-ms2",
                "quantile/fused", "quantile/topk", "quantile/fused-pad",
                "quantile/topk-pad", "quantile/multilevel", "quantile/dist")
    for name in expected:
        p = progs.get(name)
        if p is None:
            problems.append(f"program {name} missing")
            continue
        if not p.get("ok") or p.get("violations"):
            problems.append(f"program {name} has violations: "
                            f"{p.get('violations')}")
        if "peak_live_bytes_per_device" not in p.get("spec", ""):
            problems.append(f"program {name} does not declare "
                            "peak_live_bytes_per_device")
        peak = p.get("measured", {}).get("peak_live_bytes_per_device")
        if not isinstance(peak, int) or peak <= 0:
            problems.append(f"program {name} measured no positive peak "
                            f"(got {peak!r})")
    if progs.get("round/ms2") and not progs["round/ms2"].get("blame"):
        problems.append("round/ms2 carries no collective blame rows "
                        "(metadata provenance lost?)")
    for pa in data.get("passes", []):
        if not pa.get("ok"):
            problems.append(f"pass {pa.get('name')} failed")
    return problems


def main() -> None:
    if "--check" in sys.argv:
        check()
        return
    quick = "--full" not in sys.argv
    os.makedirs("results", exist_ok=True)
    print("name,us_per_call,derived")
    bench_table2()
    bench_table10(quick)
    bench_appendixB(quick)
    bench_kernels()
    bench_aggregation()
    bench_table3(quick)
    bench_table1(quick)


if __name__ == "__main__":
    main()
