"""The benchmark's command: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload smollm135m.width.m3 \
        --seed 7 --seconds 10 --trace 0

Set-up (weights and rounds from the seed, the program's resident round
compiled or read from the persistent cache, the three checked rounds),
then the measured window, then the check against the plain reference.
The last line of standard output is the result as one JSON object; the
compared numbers, each beside its limit, are also the last lines of
standard error.  Exits non-zero, with no result, when JAX finds no TPU or
fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    import harness
    line = harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)
    for k, v in line["checks"].items():
        print(f"check {k}: {v['value']:.6g} (limit {v['limit']:.6g})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
