"""Readings the limits of a cell (``limits/<workload>.json``) are set from, at a cell's own
size, many seeds in one process (the benchmark's own runs never do this):

    python3 benchmarks/chip/calibrate.py readings --workload NAME \
        --seeds 1,2,3 --modes program,control,halfbatch [--out FILE]
    python3 benchmarks/chip/calibrate.py trace --workload NAME --seed 5 \
        --seconds 6 --out FILE

``readings`` prints one JSON line per (mode, seed) with the compared
numbers and the difference norms beside them:

* ``program``: the program's first three rounds against the reference;
* ``control``: the reference computed in bfloat16 put in the program's
  place;
* ``halfbatch``: the reference with half of every local batch left out
  (the mean taken over the rest) put in the program's place.

``trace`` runs set-up and a traced window and writes the trace records
(``trace.load``) with the distinct device op names, for reading a trace
by hand.  Both need the chip, as the benchmark does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _cell_files(args):
    import spec
    wl = spec.workload(spec.load_benchmark(), args.workload)
    return (spec.config(wl["config"]), spec.traffic(wl["traffic"]),
            int(wl["chips"]))


def readings(args, out) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import compare
    import harness
    import reference
    import traffic as traffic_mod
    import weights as weights_mod
    cfg, traf, chips = _cell_files(args)
    modes = args.modes.split(",")
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        if "program" in modes:
            cell = harness.setup(cfg, traf, seed, chips)
            t1 = time.perf_counter()
            harness.free_program(cell)
            nums, (ref_l, ref_s) = harness.check(cell)
            nums.update(compare.diff_numbers(cfg, cell.g0, cell.prog_snaps,
                                             ref_s))
            out({"mode": "program", "seed": seed, "numbers": nums,
                 "worst": compare.worst(cfg, cell.g0, cell.prog_snaps,
                                        ref_s),
                 "prog_losses": cell.prog_losses, "ref_losses": ref_l,
                 "setup_s": t1 - t0,
                 "check_s": time.perf_counter() - t1})
            g0 = cell.g0
        else:
            rounds = traffic_mod.make_rounds(cfg, traf, seed)
            p0 = weights_mod.make_params(cfg, seed)
            ref_l, ref_s = reference.run(cfg, traf, rounds, p0)
            g0 = np.concatenate([np.asarray(x).ravel()
                                 for x in jax.tree.leaves(p0)])
            del p0
        rounds = traffic_mod.make_rounds(cfg, traf, seed)
        for mode in modes:
            if mode == "program":
                continue
            p0 = weights_mod.make_params(cfg, seed)
            kw = {"control": {"dtype": jnp.bfloat16},
                  "halfbatch": {"half_batch": True}}[mode]
            l, s = reference.run(cfg, traf, rounds, p0, **kw)
            del p0
            nums = compare.numbers(cfg, g0, l, s, ref_l, ref_s)
            nums.update(compare.diff_numbers(cfg, g0, s, ref_s))
            out({"mode": mode, "seed": seed, "numbers": nums,
                 "worst": compare.worst(cfg, g0, s, ref_s),
                 "losses": l, "ref_losses": ref_l})


def trace_dump(args, out) -> None:
    import harness
    import trace as trace_mod
    cfg, traf, chips = _cell_files(args)
    harness.enable_compile_cache()
    cell = harness.setup(cfg, traf, args.seed, chips)
    with tempfile.TemporaryDirectory() as tdir:
        win = harness.window(cell, args.seconds, trace_dir=tdir)
        rec = trace_mod.load(tdir)
    names = {}
    for d, name, long, s, dur in rec["device"]:
        k = (name, long)
        names[k] = names.get(k, 0.0) + dur / 1e9
    top = sorted(names.items(), key=lambda x: -x[1])[:60]
    with open(args.out, "w") as f:
        json.dump({"records": rec, "window": win}, f)
    out({"rounds": win["rounds"], "elapsed": win["elapsed"],
         "busy_s": trace_mod.busy_s(rec), "window_s": trace_mod.window_s(rec),
         "n_device_events": len(rec["device"]),
         "devices": trace_mod.devices(rec),
         "top_names": [[n, l[:160], t] for (n, l), t in top],
         "top_ops": trace_mod.top_ops(rec),
         "idle_gaps": trace_mod.idle_gaps(rec)})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("readings", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--modes", default="program,control,halfbatch")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out")
    args = ap.parse_args()
    import harness
    harness.enable_compile_cache()
    sink = open(args.out, "a") if (args.out and args.what == "readings") \
        else None

    def out(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    (readings if args.what == "readings" else trace_dump)(args, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
