"""tidb_tpu.lint — project-native static analysis.

The reference TiDB leans on a correctness-tooling tier (go vet, errcheck,
the race detector, gofail) that a Python/JAX reproduction has no analog
for.  On a TPU stack the highest-value static checks are the ones tensor
runtimes need — and all of them run host-side under JAX_PLATFORMS=cpu, so
they need no chip:

1. purity    — AST hot-path lint over copr/, executor/, expr/, ops/:
               host-sync hazards (np.asarray / jax.device_get /
               .block_until_ready), Python row loops over chunk data,
               time/RNG inside jitted code, unhashable jit static args.
2. plancheck — a `vet` for physical plans: schema/dtype propagation of
               every operator against its children, plus the rule that
               every expression pushed into a cop DAG is in the
               TPU-executable registry (expr/pushdown.py).  Also wired
               into plan build time behind `tidb_check_plan`.
3. kernelcheck — abstract-traces every registered copr kernel on
               canonical shapes (jax.eval_shape / make_jaxpr): fails on
               shape/dtype breaks, on distinct-jit-signature growth
               (recompile bombs), and on int64-op-chain growth (the Q1
               VPU bottleneck named by VERDICT.md).

Findings on today's tree are either fixed or recorded in
``baseline.json`` with a one-line justification; `python -m
tidb_tpu.lint` exits non-zero on anything new.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class Finding:
    """One lint finding with a line-number-stable identity.

    ``key`` intentionally omits the line number: baselines must survive
    unrelated edits to the same file.  Identity is (rule, file, enclosing
    scope, flagged token, ordinal within that scope).
    """

    rule: str          # e.g. "host-sync", "plan-schema", "kernel-contract"
    path: str          # repo-relative path
    line: int
    scope: str         # qualified enclosing function/class ("" = module)
    token: str         # the flagged call/op text, e.g. "np.asarray"
    message: str
    ordinal: int = 0   # nth identical (rule, path, scope, token) hit

    @property
    def key(self) -> str:
        return f"{self.rule}:{self.path}:{self.scope}:{self.token}#{self.ordinal}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}] {self.message}"
                f"  (key: {self.key})")


class LintError(Exception):
    """Raised by check entry points when findings must abort the caller
    (the plan-build-time hook raises through PlanError instead)."""

    def __init__(self, findings: List[Finding]):
        self.findings = findings
        super().__init__(
            "; ".join(f.render() for f in findings[:8])
            + (f" ... and {len(findings) - 8} more" if len(findings) > 8
               else ""))


def assign_ordinals(findings: List[Finding]) -> List[Finding]:
    """Stamp per-(rule, path, scope, token) ordinals in line order so keys
    are unique and stable under line drift."""
    seen: dict = {}
    for f in sorted(findings, key=lambda f: (f.path, f.line)):
        k = (f.rule, f.path, f.scope, f.token)
        f.ordinal = seen.get(k, 0)
        seen[k] = f.ordinal + 1
    return findings


#: finding rules each pass family can emit — staleness of a baseline
#: entry is only decidable when its family actually ran
PASS_RULES = {
    "purity": ("host-sync", "tracer-coercion", "row-loop", "time-in-jit",
               "rng-in-jit", "static-unhashable"),
    "plan": ("plan-schema",),
    "kernel": ("kernel-contract",),
    "metric": ("metric-name",),
    "concur": ("lock-rank", "lock-order", "lock-blocking", "lock-guard",
               "lock-wait"),
    "chaos": ("chaos-cover",),
}


def run_all(repo_root: Optional[str] = None,
            passes: Optional[List[str]] = None) -> List[Finding]:
    """Run the requested pass families (default: all three) and return
    raw findings — baseline filtering is the caller's job
    (see baseline.apply)."""
    import os

    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    passes = passes or ["purity", "plan", "kernel", "metric", "concur",
                        "chaos"]
    findings: List[Finding] = []
    if "purity" in passes:
        from .purity import lint_tree

        findings += lint_tree(repo_root)
    if "concur" in passes:
        from .concur import lint_tree as lint_concur

        findings += lint_concur(repo_root)
    if "chaos" in passes:
        from .chaoscover import lint_tree as lint_chaos_cover

        findings += lint_chaos_cover(repo_root)
    if "metric" in passes:
        from .metricnames import lint_tree as lint_metric_names

        findings += lint_metric_names(repo_root)
    if "plan" in passes:
        from .plancheck import lint_canonical_plans

        findings += lint_canonical_plans()
    if "kernel" in passes:
        from .kernelcheck import lint_kernels

        findings += lint_kernels()
    return assign_ordinals(findings)
