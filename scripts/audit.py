#!/usr/bin/env python
"""Graph auditor CLI: prove the hot path's comm/donation/recompile
invariants on the simulated (2,2,2) meshes.

    PYTHONPATH=src python scripts/audit.py                # all strategies
    PYTHONPATH=src python scripts/audit.py --strategy acesync --out AUDIT.json
    PYTHONPATH=src python scripts/audit.py --fail-on-violation   # CI gate

MUST set the host-device override before ANY import touches jax.  The
audit is a CPU tool: it pins the CPU backend, so it never takes a chip."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ.setdefault("REPRO_FORCE_INTERPRET", "1")

import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--strategy", action="append", default=None,
                    help="strategy to audit (repeatable; default: all "
                         "shipped strategies)")
    ap.add_argument("--out", default="AUDIT.json",
                    help="report path (default: AUDIT.json)")
    ap.add_argument("--fail-on-violation", action="store_true",
                    help="exit 1 when any pass reports an error")
    ap.add_argument("--no-compile", action="store_true",
                    help="source-level passes only (no step lowering)")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src"))
    from repro.analysis import run_audit

    report = run_audit(strategies=args.strategy,
                       skip_compile=args.no_compile)
    with open(args.out, "w") as fh:
        fh.write(report.to_json())
    print(report.summary())
    print(f"wrote {args.out}")
    if args.fail_on_violation and not report.ok:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
