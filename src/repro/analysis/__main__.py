"""CLI: ``python -m repro.analysis {check,lint}``.

check   Lower + compile the canonical program set (sync round on data-only
        and 2x2 meshes, standalone aggregation, async admit + merge, fused
        quantile) and print every declared contract in one table, plus the
        cache/donation passes.  A CPU rehearsal: the command re-executes
        itself in a child pinned to the CPU with 4 forced host devices
        (XLA reads ``--xla_force_host_platform_device_count`` at jax init,
        so it cannot be set in-process), and the parent never initializes
        a backend, so it holds no accelerator the child would need.
        Exit 1 on any FAIL.  ``--json PATH``
        additionally writes the machine-readable report (measured values,
        violations, peak estimates, blame tables) to PATH — the flag
        rides through the re-exec, so the forced-device child writes it.

lint    Run the FL-specific AST lints (``repro.analysis.lint``) over the
        given paths (default ``src/``).  Exit 1 on any finding.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

_CHILD_ENV = "_REPRO_ANALYSIS_CHILD"
_FORCE_FLAG = "--xla_force_host_platform_device_count=4"


def _reexec_with_devices(argv) -> int:
    env = dict(os.environ)
    env[_CHILD_ENV] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"{env.get('XLA_FLAGS', '')} {_FORCE_FLAG}".strip()
    return subprocess.call([sys.executable, "-m", "repro.analysis"] + argv,
                           env=env)


def _cmd_check(args) -> int:
    if not os.environ.get(_CHILD_ENV):
        return _reexec_with_devices(sys.argv[1:])
    import jax
    if jax.device_count() < 4:
        print(f"ERROR: forced-device child still sees only "
              f"{jax.device_count()} device(s)", file=sys.stderr)
        return 2

    from repro.analysis import format_table
    from repro.analysis import programs

    progress = (lambda s: print(s, flush=True)) if not args.quiet \
        else (lambda s: None)
    reports = programs.canonical_reports(progress)
    print()
    print(format_table(reports))
    ok = all(r.ok for r in reports)

    print()
    passes = []
    for name, violations in programs.cache_checks():
        status = "PASS" if not violations else "FAIL"
        passes.append({"name": name, "ok": not violations,
                       "violations": list(violations)})
        print(f"{status}  {name}")
        for v in violations:
            print(f"      {v}")
            ok = False
    print()
    n_fail = sum(1 for r in reports if not r.ok)
    print(f"contracts: {len(reports) - n_fail}/{len(reports)} passed"
          + ("" if ok else "  [FAIL]"))
    if args.json:
        import json
        payload = {
            "ok": ok,
            "programs": [r.to_json() for r in reports],
            "passes": passes,
        }
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.json}")
    return 0 if ok else 1


def _cmd_lint(args) -> int:
    from repro.analysis import lint

    findings = lint.lint_paths(args.paths)
    for f in findings:
        print(f)
    print(f"{len(findings)} finding(s) over {len(args.paths)} path(s)")
    return 1 if findings else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.analysis",
                                 description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    ck = sub.add_parser("check", help="lower the canonical program set "
                                      "and report every contract")
    ck.add_argument("--quiet", action="store_true",
                    help="suppress per-program progress lines")
    ck.add_argument("--json", metavar="PATH", default=None,
                    help="also write the full machine-readable report "
                         "(per-program measured values, violations, peak "
                         "estimates, blame tables) to PATH")
    ck.set_defaults(fn=_cmd_check)
    ln = sub.add_parser("lint", help="run the FL-specific source lints")
    ln.add_argument("paths", nargs="*", default=["src/"],
                    help="files/directories to lint (default: src/)")
    ln.set_defaults(fn=_cmd_lint)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
