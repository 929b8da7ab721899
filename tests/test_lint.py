"""Tier-1 gate for tidb_tpu.lint — the project-native static-analysis
suite (hot-path purity lint, plan/schema typechecker, kernel-contract
checker).

Two halves:

1. the GATE: the full suite over today's tree must produce zero findings
   outside the checked-in, justified baseline allowlist (the same check
   `python -m tidb_tpu.lint` runs in CI);
2. NEGATIVE tests: each pass family must catch a seeded violation —
   host-sync in copr code, a schema-mismatched plan node, a shape-broken
   kernel — otherwise the gate is a rubber stamp.

Everything runs host-side (conftest pins JAX_PLATFORMS=cpu) and needs no
chip.
"""

import textwrap

import pytest

from tidb_tpu.lint import assign_ordinals, run_all
from tidb_tpu.lint.baseline import apply, load_baseline
from tidb_tpu.lint.purity import lint_source


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------


def test_tree_clean_vs_baseline():
    """`python -m tidb_tpu.lint` semantics: no finding outside the
    baseline allowlist.  Stale entries are reported but non-fatal (a fix
    must never be punished) — they surface in the assertion message only
    when something else fails."""
    findings = run_all()
    new, stale = apply(findings, load_baseline())
    assert not new, (
        "new static-analysis findings (fix them or baseline with a "
        "justification):\n" + "\n".join(f.render() for f in new)
        + ("\nstale baseline entries: " + ", ".join(stale) if stale else "")
    )


def test_finding_keys_stable_under_line_drift():
    """Baseline keys must not contain line numbers: the same violation on
    a different line keeps its identity; a second identical one gets the
    next ordinal."""
    src = "import jax\nimport numpy as np\n\ndef f(x):\n    a = np.asarray(x)\n    b = np.asarray(x)\n    return a, b\n"
    shifted = "import jax\nimport numpy as np\n\n# pushed down two lines\n\ndef f(x):\n    a = np.asarray(x)\n    b = np.asarray(x)\n    return a, b\n"
    k1 = [f.key for f in assign_ordinals(lint_source(src, "tidb_tpu/copr/x.py"))]
    k2 = [f.key for f in assign_ordinals(lint_source(shifted, "tidb_tpu/copr/x.py"))]
    assert k1 == k2 and len(set(k1)) == 2


# ---------------------------------------------------------------------------
# purity: device-array provenance (lint follow-up (a))
# ---------------------------------------------------------------------------


def test_purity_no_jax_import_means_no_host_sync():
    """A module that never imports jax cannot hold a device array, so
    np.asarray there is a host normalization, not a sync — the rule that
    retired 11 baseline allowlist entries."""
    src = textwrap.dedent("""
        import numpy as np

        def route(vals):
            return np.asarray(sorted(vals), dtype=np.int64)
    """)
    assert lint_source(src, "tidb_tpu/executor/seeded.py") == []


def test_purity_jit_result_readback_is_boundary():
    """np.asarray on the direct result of a jit-bound callable is the
    designed readback boundary (program finished, single transfer) —
    not a hazard; any OTHER np.asarray in the same module still is."""
    src = textwrap.dedent("""
        import jax
        import numpy as np

        def make(fn):
            jitted = jax.jit(fn)

            def call(*args):
                out = jitted(*args)
                buf = np.asarray(out)            # designed readback
                also = np.asarray(jitted(args))  # direct-call form
                return buf, also

            return call

        def leak(x):
            return np.asarray(x)  # unknown provenance: still flagged
    """)
    fs = lint_source(src, "tidb_tpu/copr/seeded.py")
    assert [(f.rule, f.scope) for f in fs] == [("host-sync", "leak")]


def test_purity_boundary_names_are_function_scoped():
    """A boundary name in one function must not whitelist the SAME bare
    name holding a device array in a sibling function."""
    src = textwrap.dedent("""
        import jax
        import numpy as np

        def reader(fn):
            jitted = jax.jit(fn)
            out = jitted(1)
            return np.asarray(out)      # boundary: fine

        def other(device_array):
            out = device_array + 1
            return np.asarray(out)      # same name, NOT a boundary
    """)
    fs = lint_source(src, "tidb_tpu/copr/seeded.py")
    assert [(f.rule, f.scope) for f in fs] == [("host-sync", "other")]


# ---------------------------------------------------------------------------
# purity: seeded violations per rule
# ---------------------------------------------------------------------------


def _rules(findings):
    return {f.rule for f in findings}


def test_purity_catches_host_sync_in_copr():
    src = textwrap.dedent("""
        import jax
        import numpy as np

        def fetch_tile(buf):
            arr = jax.device_get(buf)
            arr.block_until_ready()
            return np.asarray(arr)
    """)
    fs = lint_source(src, "tidb_tpu/copr/seeded.py")
    assert _rules(fs) == {"host-sync"}
    assert {f.token for f in fs} == {"jax.device_get", ".block_until_ready",
                                     "np.asarray"}


def test_purity_catches_row_loops():
    """Python row loops over chunk data — the seeded specimen is the OLD
    ADMIN CHECKSUM implementation (per-row repr()/crc32 walk), replaced
    by the columnar digest in this PR: proof the rule catches exactly
    the hazard class the advisor flagged."""
    old_checksum = textwrap.dedent("""
        import zlib

        def _checksum_table(store, dele):
            crc = kvs = nbytes = 0
            n = store.base_rows
            step = 1 << 16
            for lo in range(0, n, step):
                chunk = store.base_chunk(range(store.n_cols), lo,
                                         min(lo + step, n))
                for off, row in enumerate(chunk.to_pylist()):
                    if lo + off in dele:
                        continue
                    raw = repr(row).encode()
                    crc ^= zlib.crc32(raw)
                    kvs += 1
                    nbytes += len(raw)
            return crc, kvs, nbytes
    """)
    fs = lint_source(old_checksum, "tidb_tpu/executor/seeded.py")
    assert any(f.rule == "row-loop" and f.token == ".to_pylist" for f in fs)
    # and the range(.num_rows) loop form
    loop = textwrap.dedent("""
        def agg(chunk):
            total = 0
            for i in range(chunk.num_rows):
                total += chunk.col(0).get(i)
            return total
    """)
    fs2 = lint_source(loop, "tidb_tpu/executor/seeded2.py")
    assert any(f.rule == "row-loop" and f.token == "range(num_rows)"
               for f in fs2)


def test_purity_catches_jit_hazards():
    src = textwrap.dedent("""
        import time
        import jax
        import numpy as np

        @jax.jit
        def kern(x):
            t = time.time()
            r = np.random.uniform()
            v = float(x)
            return x * t * r * v

        def host(x):
            return float(x) + time.time()  # NOT jitted: no finding
    """)
    fs = lint_source(src, "tidb_tpu/ops/seeded.py")
    assert _rules(fs) == {"time-in-jit", "rng-in-jit", "tracer-coercion"}
    assert all(f.scope == "kern" for f in fs)


def test_purity_catches_unhashable_static_args():
    """The spec binds to the JITTED name (build_j), not the wrapped
    original: build(x, dims=[...]) is a legal plain-Python call and must
    not be flagged; build_j(x, dims=[...]) raises at call time and must."""
    src = textwrap.dedent("""
        import jax

        def build(x, dims):
            return x

        build_j = jax.jit(build, static_argnames=("dims",))

        def run(x):
            return build_j(x, dims=[1, 2])

        def host(x):
            return build(x, dims=[1, 2])  # unjitted original: legal
    """)
    fs = lint_source(src, "tidb_tpu/copr/seeded.py")
    assert _rules(fs) == {"static-unhashable"}
    assert [f.token for f in fs] == ["build_j"]
    # decorator form with positional static args
    dec = textwrap.dedent("""
        from functools import partial

        import jax

        @partial(jax.jit, static_argnums=(1,))
        def kern(x, dims):
            return x

        def run(x):
            return kern(x, [1, 2])
    """)
    fs2 = lint_source(dec, "tidb_tpu/copr/seeded2.py")
    assert any(f.rule == "static-unhashable" and f.token == "kern"
               for f in fs2)


# ---------------------------------------------------------------------------
# plancheck: seeded schema-mismatched plan nodes
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def corpus_session():
    from tidb_tpu.lint.plancheck import _canonical_session

    return _canonical_session()


def _planned(s, sql):
    from tidb_tpu.parser import parse_one

    return s._plan(parse_one(sql))


def _first_reader(phys):
    from tidb_tpu.lint.kernelcheck import _reader_dags

    readers = _reader_dags(phys)
    assert readers, "expected a cop reader in the plan"
    return readers[0]


def test_plancheck_clean_plan_passes(corpus_session):
    from tidb_tpu.lint.plancheck import check_plan

    phys = _planned(corpus_session,
                    "select l_orderkey, l_quantity from lineitem"
                    " where l_quantity < 5")
    assert check_plan(phys) == []


def test_plancheck_catches_out_of_range_scan_offset(corpus_session):
    from tidb_tpu.lint.plancheck import check_plan

    phys = _planned(corpus_session,
                    "select l_orderkey, l_quantity from lineitem"
                    " where l_quantity < 5")
    _node, dag = _first_reader(phys)
    dag.executors[0].columns[0] = 999  # seed: scan points past storage
    problems = check_plan(phys)
    assert any("store offset 999 out of range" in p for p in problems)


def test_plancheck_catches_reader_schema_mismatch(corpus_session):
    from tidb_tpu.lint.plancheck import (PlanCheckError, assert_plan,
                                         check_plan)

    phys = _planned(corpus_session,
                    "select l_orderkey, l_quantity from lineitem"
                    " where l_quantity < 5")
    node, _dag = _first_reader(phys)
    node.schema.cols.pop()  # seed: reader schema narrower than its DAG
    problems = check_plan(phys)
    assert any("reader schema width" in p for p in problems)
    with pytest.raises(PlanCheckError):
        assert_plan(phys)


def test_plancheck_catches_unregistered_pushed_function(corpus_session):
    from tidb_tpu.lint.plancheck import check_plan

    phys = _planned(corpus_session,
                    "select l_orderkey from lineitem where l_quantity < 5")
    _node, dag = _first_reader(phys)
    from tidb_tpu.copr.ir import SelectionIR

    sel = next(ex for ex in dag.executors if isinstance(ex, SelectionIR))
    for e in sel.conditions:
        if getattr(e, "name", None):
            e.name = "totally_not_pushable"  # seed: rewrite broke registry
    problems = check_plan(phys)
    assert any("not in the TPU-executable registry" in p for p in problems)


def test_check_plan_session_var_wired(corpus_session):
    """tidb_check_plan (default on) feeds PhysicalContext.check_plan, the
    finish_plan hook that vets every planner rewrite's OUTPUT."""
    s = corpus_session
    assert s._pctx().check_plan is True
    s.execute("set tidb_check_plan = 0")
    try:
        assert s._pctx().check_plan is False
    finally:
        s.execute("set tidb_check_plan = 1")


def test_lint_canonical_plan_corpus_clean():
    from tidb_tpu.lint.plancheck import lint_canonical_plans

    assert lint_canonical_plans() == []


# ---------------------------------------------------------------------------
# kernelcheck: shape-broken kernels and regression guards
# ---------------------------------------------------------------------------


def _lineitem_table(s):
    dom = s.domain
    return dom.storage.table(
        dom.catalog.info_schema().table("test", "lineitem").id)


def test_kernelcheck_traces_clean_kernel(corpus_session):
    from tidb_tpu.lint.kernelcheck import trace_kernel

    phys = _planned(corpus_session,
                    "select sum(l_quantity) from lineitem"
                    " where l_discount < 0.05")
    _node, dag = _first_reader(phys)
    stats = trace_kernel(_lineitem_table(corpus_session), dag)
    assert stats["eqns"] > 0 and stats["i64_eqns"] >= 0


def test_kernelcheck_catches_shape_broken_kernel(corpus_session):
    from tidb_tpu.copr.ir import SelectionIR
    from tidb_tpu.expr.expression import ColumnExpr
    from tidb_tpu.lint.kernelcheck import trace_kernel

    phys = _planned(corpus_session,
                    "select sum(l_quantity) from lineitem"
                    " where l_discount < 0.05")
    _node, dag = _first_reader(phys)
    sel = next(ex for ex in dag.executors if isinstance(ex, SelectionIR))

    def break_refs(e):
        if isinstance(e, ColumnExpr):
            e.index = 99  # seed: ref past every scanned column
        for a in getattr(e, "args", ()):
            break_refs(a)

    for c in sel.conditions:
        break_refs(c)
    with pytest.raises(Exception):
        trace_kernel(_lineitem_table(corpus_session), dag)


def test_metric_name_pass_catches_violations():
    """ISSUE 13: every literal metric name must match [a-z0-9_]+ and
    carry a conventional suffix — the fleet merge keys sum-vs-gauge
    semantics off `_total`, so a misnamed counter silently becomes a
    per-host gauge."""
    from tidb_tpu.lint.metricnames import lint_source as lint_metrics

    src = textwrap.dedent("""
        from tidb_tpu.metrics import REGISTRY

        def f(cls):
            REGISTRY.inc("Bad-Name")
            REGISTRY.inc("queries_served")
            REGISTRY.inc("queries_served_total")
            REGISTRY.observe_hist("lat_ms", 1.0)
            REGISTRY.observe_hist("lat", 1.0)
            REGISTRY.set("queue_depth", 3)
            REGISTRY.inc(f"slo_{cls}_breach_total")
            REGISTRY.inc(f"trace_phase_{cls}")
    """)
    fs = lint_metrics(src, "tidb_tpu/x.py")
    tokens = {f.token for f in fs}
    assert "Bad-Name" in tokens                # charset violation
    assert "queries_served" in tokens          # counter missing _total
    assert "lat" in tokens                     # histogram missing unit
    assert "queries_served_total" not in tokens
    assert "lat_ms" not in tokens
    assert "queue_depth" not in tokens
    # f-strings: literal tail is checked, dynamic tail is skipped
    assert "slox_breach_total" not in tokens
    assert "trace_phase_x" not in tokens


def test_metric_name_pass_runs_in_cli_families():
    from tidb_tpu.lint import PASS_RULES

    assert PASS_RULES["metric"] == ("metric-name",)


def test_kernelcheck_detects_int64_chain_growth():
    """A tightened baseline must flip the suite red: this is the guard
    against reintroducing the int64-emulation chains VERDICT.md names as
    the Q1 VPU bottleneck (and a live negative test of the whole
    lint_kernels loop, recompile-bomb census included)."""
    from tidb_tpu.lint.kernelcheck import lint_kernels

    base = {name: {"i64_eqns": 0}
            for name in ("q1-dense-agg", "q6-scalar-agg", "filter-project",
                         "topn", "minmax-agg")}
    base["__signatures__"] = {"max": 10_000}
    findings = lint_kernels(baseline_kernels=base)
    growth = [f for f in findings if "int64 equation count grew" in f.message]
    assert growth, "expected int64-growth findings against a zeroed baseline"
    # and no OTHER finding kinds fired (kernels themselves are healthy)
    assert {f.rule for f in findings} == {"kernel-contract"}
    assert not [f for f in findings if "trace failed" in f.message]


def test_concur_catches_unregistered_lock():
    """ISSUE 16: every lock construction goes through util_concurrency
    with a declared rank — a raw threading.Lock is invisible to both
    the static order graph and the runtime witness."""
    from tidb_tpu.lint.concur import lint_source as lint_concur

    src = textwrap.dedent("""
        import threading

        class C:
            def __init__(self):
                self._mu = threading.Lock()
    """)
    fs = lint_concur(src, "tidb_tpu/mymod.py")
    assert [(f.rule, f.path, f.line) for f in fs] == \
        [("lock-rank", "tidb_tpu/mymod.py", 6)]


def test_concur_catches_rank_inverting_nested_with():
    from tidb_tpu.lint.concur import lint_source as lint_concur

    src = textwrap.dedent("""
        from tidb_tpu.util_concurrency import make_lock

        class C:
            def __init__(self):
                self._a = make_lock("mymod:C._a")
                self._b = make_lock("mymod:C._b")

            def f(self):
                with self._a:
                    with self._b:
                        pass
    """)
    fs = lint_concur(src, "tidb_tpu/mymod.py",
                     ranks={"mymod:C._a": 2, "mymod:C._b": 1})
    assert [(f.rule, f.path, f.line) for f in fs] == \
        [("lock-order", "tidb_tpu/mymod.py", 11)]
    assert "rank" in fs[0].message
    # same code under the consistent rank order is clean
    assert lint_concur(src, "tidb_tpu/mymod.py",
                       ranks={"mymod:C._a": 1, "mymod:C._b": 2}) == []


def test_concur_catches_sleep_under_lock():
    from tidb_tpu.lint.concur import lint_source as lint_concur

    src = textwrap.dedent("""
        import time

        from tidb_tpu.util_concurrency import make_lock

        class C:
            def __init__(self):
                self._mu = make_lock("mymod:C._mu")

            def f(self):
                with self._mu:
                    time.sleep(0.1)
    """)
    fs = lint_concur(src, "tidb_tpu/mymod.py", ranks={"mymod:C._mu": 1})
    assert [(f.rule, f.path, f.line, f.token) for f in fs] == \
        [("lock-blocking", "tidb_tpu/mymod.py", 12, "time.sleep")]


def test_concur_catches_guarded_attr_read_bare():
    from tidb_tpu.lint.concur import lint_source as lint_concur

    src = textwrap.dedent("""
        from tidb_tpu.util_concurrency import make_lock

        class C:
            def __init__(self):
                self._mu = make_lock("mymod:C._mu")
                self.x = 0

            def bump(self):
                with self._mu:
                    self.x += 1

            def peek(self):
                return self.x
    """)
    fs = lint_concur(src, "tidb_tpu/mymod.py", ranks={"mymod:C._mu": 1})
    assert [(f.rule, f.path, f.line, f.token) for f in fs] == \
        [("lock-guard", "tidb_tpu/mymod.py", 14, "x")]


def test_concur_cross_object_guard_catches_unheld_store():
    """ISSUE 20 satellite: a class declaring `_guarded_by_` puts its
    instance state under ANOTHER object's lock — plain stores and
    container-mutator calls through a ctor-typed local must hold it."""
    from tidb_tpu.lint.concur import lint_source as lint_concur

    src = textwrap.dedent("""
        from tidb_tpu.util_concurrency import make_lock

        class _Job:
            _guarded_by_ = "mymod:Plane._mu"

            def __init__(self):
                self.items = []
                self.closed = False

        class Plane:
            def __init__(self):
                self._mu = make_lock("mymod:Plane._mu")
                self._jobs = {}

            def good(self, key):
                with self._mu:
                    j = _Job()
                    j.items.append(key)
                    self._jobs[key] = j

            def bad(self, key):
                j = _Job()
                j.closed = True
                j.items.append(key)
                with self._mu:
                    self._jobs[key] = j
    """)
    fs = lint_concur(src, "tidb_tpu/mymod.py",
                     ranks={"mymod:Plane._mu": 1})
    hits = sorted((f.rule, f.line, f.token) for f in fs)
    assert hits == [("lock-guard", 24, "_Job.closed"),
                    ("lock-guard", 25, "_Job.items")], fs


def test_concur_cross_object_guard_allows_lockfree_loads():
    """Loads through a guarded-typed local (the batcher's lock-free
    Event handshake) never flag; annotated helper args are typed too,
    and *_locked helpers of the lock's owner count as held."""
    from tidb_tpu.lint.concur import lint_source as lint_concur

    src = textwrap.dedent("""
        import threading

        from tidb_tpu.util_concurrency import make_lock

        class _Job:
            _guarded_by_ = "mymod:Plane._mu"

            def __init__(self):
                self.items = []
                self.done = threading.Event()

        class Plane:
            def __init__(self):
                self._mu = make_lock("mymod:Plane._mu")

            def peek(self, j: "_Job"):
                return len(j.items), j.done.is_set()

            def _push_locked(self, j: "_Job", key):
                j.items.append(key)
    """)
    fs = lint_concur(src, "tidb_tpu/mymod.py",
                     ranks={"mymod:Plane._mu": 1})
    assert [f for f in fs if f.rule == "lock-guard"] == [], fs


def test_concur_catches_wait_whose_notifier_needs_held_lock():
    """ISSUE 17 concurrency (a): a `.wait()` under a held ranked lock
    whose notifier acquires a lock ranked at or below the waiter's is
    the classic condition-under-lock deadlock — the notifier blocks
    behind the very lock the waiter holds, so the wait never wakes."""
    from tidb_tpu.lint.concur import lint_source as lint_concur

    src = textwrap.dedent("""
        import threading

        from tidb_tpu.util_concurrency import make_lock

        class C:
            def __init__(self):
                self._mu = make_lock("mymod:C._mu")
                self._cv = threading.Condition()

            def consume(self):
                with self._mu:
                    with self._cv:
                        self._cv.wait()

            def produce(self):
                with self._mu:
                    with self._cv:
                        self._cv.notify()
    """)
    fs = lint_concur(src, "tidb_tpu/mymod.py", ranks={"mymod:C._mu": 1})
    waits = [(f.rule, f.line, f.token) for f in fs if f.rule == "lock-wait"]
    assert waits == [("lock-wait", 14, "self._cv")]


def test_concur_wait_clean_when_lock_released_first():
    from tidb_tpu.lint.concur import lint_source as lint_concur

    src = textwrap.dedent("""
        import threading

        from tidb_tpu.util_concurrency import make_lock

        class C:
            def __init__(self):
                self._mu = make_lock("mymod:C._mu")
                self._cv = threading.Condition()

            def consume(self):
                with self._cv:
                    self._cv.wait()

            def produce(self):
                with self._mu:
                    pass
                with self._cv:
                    self._cv.notify()
    """)
    fs = lint_concur(src, "tidb_tpu/mymod.py", ranks={"mymod:C._mu": 1})
    assert [f for f in fs if f.rule == "lock-wait"] == []


def test_concur_pass_runs_in_cli_families():
    from tidb_tpu.lint import PASS_RULES

    assert PASS_RULES["concur"] == (
        "lock-rank", "lock-order", "lock-blocking", "lock-guard",
        "lock-wait")


def test_chaoscover_flags_untested_failpoints(tmp_path):
    """ISSUE 20 satellite: every FAILPOINTS.hit site name must appear
    in at least one test — literal names, module-level constants and
    cross-module *_FAILPOINT imports all resolve; computed names are
    themselves findings."""
    from tidb_tpu.lint.chaoscover import lint_tree as lint_chaos

    pkg = tmp_path / "tidb_tpu"
    (pkg / "sub").mkdir(parents=True)
    (pkg / "names.py").write_text(
        'SHARED_FAILPOINT = "store/shared_site"\n')
    (pkg / "sub" / "mod.py").write_text(textwrap.dedent("""
        from ..names import SHARED_FAILPOINT

        LOCAL_FP = "store/local_site"

        def f(x):
            FAILPOINTS.hit("store/covered_site", a=1)
            FAILPOINTS.hit("store/orphan_site")
            FAILPOINTS.hit(LOCAL_FP)
            FAILPOINTS.hit(SHARED_FAILPOINT)
            FAILPOINTS.hit("x/" + x)
    """))
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_mod.py").write_text(
        '# arms store/covered_site and store/shared_site\n')
    fs = lint_chaos(str(tmp_path))
    by_token = {f.token: f for f in fs}
    assert "store/orphan_site" in by_token
    assert "store/local_site" in by_token  # constant resolved, untested
    assert "store/covered_site" not in by_token
    assert "store/shared_site" not in by_token  # cross-module resolved
    # the computed name is flagged as unresolvable
    unresolved = [f for f in fs if "not statically" in f.message]
    assert len(unresolved) == 1
    # rule family is registered for CLI/baseline staleness
    from tidb_tpu.lint import PASS_RULES

    assert PASS_RULES["chaos"] == ("chaos-cover",)


def test_chaoscover_clean_on_real_tree():
    """Every failpoint in the shipped tree is swept by some test — the
    acceptance the chaos archetype rides on (no baseline debt)."""
    from tidb_tpu.lint.chaoscover import lint_tree as lint_chaos

    assert lint_chaos() == []
