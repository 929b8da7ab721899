"""CLI: python -m tidb_tpu.lint [--passes purity,plan,kernel] [--json]
[--update-baseline]

Exit code 0 iff every finding is covered by the checked-in baseline
allowlist.  Runs entirely host-side (JAX_PLATFORMS=cpu, 8 virtual
devices) so the result is meaningful with or without a TPU attached.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _pin_host_platform():
    # mirror tests/conftest.py BEFORE jax loads anywhere: lint never
    # needs the chip
    os.environ.setdefault("TIDB_TPU_TILE", "1024")
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tidb_tpu.lint")
    ap.add_argument("--passes",
                    default="purity,plan,kernel,metric,concur,chaos",
                    help="comma list of pass families to run")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--update-baseline", action="store_true",
                    help="refresh kernel-contract stats in baseline.json")
    args = ap.parse_args(argv)
    passes = [p.strip() for p in args.passes.split(",") if p.strip()]

    _pin_host_platform()
    from . import PASS_RULES, run_all
    from .baseline import apply, load_baseline, save_baseline

    ran_rules = set()
    for p in passes:
        ran_rules.update(PASS_RULES.get(p, ()))
    if args.update_baseline:
        ran_rules.update(PASS_RULES["kernel"])  # kernels run regardless

    baseline = load_baseline()
    if args.update_baseline:
        from . import assign_ordinals
        from .kernelcheck import lint_kernels

        stats: dict = {}
        # one kernel run does double duty: collects the fresh stats AND
        # reports baseline-independent contract breaks (trace failures,
        # recompile bombs) — re-running the pass would double the cost
        # of the slowest family for nothing
        findings = lint_kernels(collect_stats=stats)
        baseline["kernels"] = stats
        save_baseline(baseline)
        # stderr: --json promises machine-readable stdout
        print(f"baseline kernels refreshed: {json.dumps(stats)}",
              file=sys.stderr)
        rest = [p for p in passes if p != "kernel"]
        if rest:  # run_all treats an empty list as "all families"
            findings += run_all(passes=rest)
        findings = assign_ordinals(findings)
    else:
        findings = run_all(passes=passes)
    new, stale = apply(findings, baseline, ran_rules=ran_rules)

    if args.json:
        print(json.dumps({
            "findings": [vars(f) for f in new],
            "stale_baseline": stale,
            "allowlisted": len(findings) - len(new),
        }))
    else:
        for f in new:
            print(f.render())
        for k in stale:
            print(f"stale baseline entry (site fixed? remove it): {k}")
        print(f"tidb_tpu.lint: {len(new)} new finding(s), "
              f"{len(findings) - len(new)} allowlisted, "
              f"{len(stale)} stale baseline entr(ies)")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
