"""skylark-lint: fixture-corpus rule tests, baseline/suppression
mechanics, the runtime lock-order witness, and the static/runtime
lock-graph agreement (docs/analysis.rst).

The fixture corpus lives in ``tests/lint_fixtures/``: ``*_flag.py``
files must produce their rule's finding, ``*_pass.py`` files must
produce none. ``lock_inversion_flag.py`` doubles as the runtime
witness's deliberate two-lock inversion — the same file both halves of
the lock-discipline story must catch.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from libskylark_tpu.analysis import (
    Finding, Project, compare_to_baseline, registered_rules, run_rules,
)
from libskylark_tpu.analysis.rules.lock_discipline import (
    static_lock_graph, _find_cycles,
)
from libskylark_tpu.base import locks as _locks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")


def fixture_project(*names, root=FIXTURES):
    proj = Project(root)
    for n in names:
        proj.add_file(os.path.join(root, n))
    return proj


def findings_for(*names, rule, root=FIXTURES):
    proj = fixture_project(*names, root=root)
    return [f for f in run_rules(proj, only=[rule]) if f.rule == rule]


# ---------------------------------------------------------------------------
# rule family: env-registry
# ---------------------------------------------------------------------------


def test_env_rule_flags_raw_reads():
    got = findings_for("env_raw_read_flag.py", rule="env-registry")
    symbols = {f.symbol for f in got}
    assert "SKYLARK_BOGUS_FLAG" in symbols          # .get()
    assert "SKYLARK_BOGUS_SUBSCRIPT" in symbols     # [...]
    assert "SKYLARK_BOGUS_GETENV" in symbols        # os.getenv
    assert "SKYLARK_BOGUS_MEMBER" in symbols        # in os.environ
    assert "<dynamic>" in symbols                   # non-literal key


def test_env_rule_passes_registry_and_writes():
    assert findings_for("env_ok_pass.py", rule="env-registry") == []


def test_env_rule_suppressions():
    # both suppression forms (same-line, comment-line-above) hold
    assert findings_for("suppressed_pass.py", rule="env-registry") == []


def test_repo_has_no_raw_skylark_reads():
    """The acceptance invariant: a raw os.environ SKYLARK_* read
    anywhere in the package is a finding (everything live today is
    migrated; nothing outside the baseline)."""
    proj = Project.load(REPO)
    raw = [f for f in run_rules(proj, only=["env-registry"])
           if f.symbol.startswith("SKYLARK_")
           and "raw" in f.message]
    assert raw == [], [f.render() for f in raw]


def test_injected_raw_read_fails_gate(tmp_path):
    """A new raw read added to the package is caught as a NEW finding
    vs the committed baseline — what the CI lint gate enforces."""
    proj = Project.load(REPO)
    bad = tmp_path / "bad_module.py"
    bad.write_text(
        "import os\n\n\n"
        "def leak():\n"
        "    return os.environ.get('SKYLARK_TELEMETRY')\n")
    # place it logically inside the package tree
    mod = proj.add_file(str(bad))
    mod.relpath = "libskylark_tpu/bad_module.py"
    findings = run_rules(proj, only=["env-registry"])
    new, _stale = compare_to_baseline(findings)
    assert any(f.symbol == "SKYLARK_TELEMETRY" for f in new)


# ---------------------------------------------------------------------------
# rule family: jit-purity
# ---------------------------------------------------------------------------


def test_jit_rule_flags_impure_closure():
    got = findings_for("jit_impure_flag.py", rule="jit-purity")
    by_root = {}
    for f in got:
        kind = f.message.split("reaches ")[1].split(" impurity")[0]
        by_root.setdefault(f.symbol.split(":")[1], set()).add(kind)
    assert by_root.get("impure_root") == {
        "env", "clock", "host-rng", "mutable-global"}
    # the nested closure passed to jax.jit(...) is a root too, and
    # reaches the env helper transitively
    assert "env" in by_root.get("build.<locals>.inner", set())


def test_jit_rule_passes_pure():
    assert findings_for("jit_pure_pass.py", rule="jit-purity") == []


# ---------------------------------------------------------------------------
# rule family: lock-discipline (static)
# ---------------------------------------------------------------------------


def test_lock_rule_flags_inversion():
    got = findings_for("lock_inversion_flag.py", rule="lock-discipline")
    cycles = [f for f in got if f.symbol.startswith("cycle:")]
    assert cycles, [f.render() for f in got]
    assert any("fixture.alpha" in f.symbol and "fixture.beta" in f.symbol
               for f in cycles)


def test_lock_rule_flags_blocking_and_bare_locks():
    got = findings_for("lock_blocking_flag.py", rule="lock-discipline")
    msgs = "\n".join(f.message for f in got)
    assert "Future.result()" in msgs
    assert "time.sleep()" in msgs
    assert "callback fan-out" in msgs
    assert "direct threading.Lock()" in msgs


def test_lock_rule_passes_consistent_order():
    assert findings_for("lock_ok_pass.py", rule="lock-discipline") == []


def test_repo_static_lock_graph_acyclic():
    """Half of the agreement check: the package's static lock graph
    has no cycle (the runtime witness asserts the other half in
    test_witness_serve_leg_clean and the CI chaos battery)."""
    g = static_lock_graph(Project.load(REPO))
    assert _find_cycles({a: list(b) for a, b in g["edges"].items()}) == []
    # sanity: the graph actually sees the serving surface
    assert "serve.state" in g["sites"]


# ---------------------------------------------------------------------------
# rule family: metric-names
# ---------------------------------------------------------------------------


def _metrics_findings():
    root = os.path.join(FIXTURES, "metrics_proj")
    proj = Project(root)
    for rel in ("libskylark_tpu/telemetry/names.py", "app_ok.py",
                "app_flag.py"):
        proj.add_file(os.path.join(root, rel))
    return run_rules(proj, only=["metric-names"])


def test_metric_rule_flags():
    got = _metrics_findings()
    by_symbol = {}
    for f in got:
        by_symbol.setdefault(f.symbol, []).append(f.message)
    assert "demo.bogus" in by_symbol                       # undeclared
    assert any("declared as counter" in m
               for m in by_symbol.get("demo.requests", []))  # kind clash
    assert any("2 sites" in m
               for m in by_symbol.get("demo.requests", []))  # duplicate
    assert "Demo-Bad.Name" in by_symbol                    # prom chars
    assert "<dynamic>" in by_symbol                        # non-literal
    assert any("stale" in m
               for m in by_symbol.get("demo.never_created", []))


def test_metric_rule_passes_clean_creations():
    got = _metrics_findings()
    # the two clean creations in app_ok.py produce nothing anchored on
    # themselves (the demo.requests duplicate is charged to the second
    # site, which is a deliberate flag-file collision)
    assert not any(f.symbol == "demo.depth" for f in got)


def test_repo_metric_names_clean():
    proj = Project.load(REPO)
    assert run_rules(proj, only=["metric-names"]) == []


# ---------------------------------------------------------------------------
# framework: baseline + gate + CLI
# ---------------------------------------------------------------------------


def test_all_rule_families_registered():
    assert set(registered_rules()) >= {
        "jit-purity", "lock-discipline", "env-registry", "metric-names"}


def test_repo_gate_is_clean_via_cli():
    """script/lint (gate mode) exits 0 on the committed tree +
    baseline — what script/ci runs on every commit."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "script", "lint")],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr


def test_stale_baseline_entry_fails_gate():
    proj = Project.load(REPO)
    findings = run_rules(proj)
    fake = Finding("env-registry", "libskylark_tpu/gone.py", 1,
                   "SKYLARK_GONE", "was fixed; entry not removed")
    import libskylark_tpu.analysis.core as core
    base = core.baseline_load()
    base.append({"rule": fake.rule, "path": fake.path,
                 "symbol": fake.symbol, "message": fake.message})
    import json as _json
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as fh:
        _json.dump({"findings": base}, fh)
        tmp = fh.name
    try:
        new, stale = compare_to_baseline(findings, path=tmp)
        assert new == []
        assert len(stale) == 1 and stale[0]["symbol"] == "SKYLARK_GONE"
    finally:
        os.unlink(tmp)


def test_env_table_matches_committed(tmp_path):
    """docs/env_vars.rst is generated from the registry; drift fails
    (the CI lint gate re-emits and diffs)."""
    committed = os.path.join(REPO, "docs", "env_vars.rst")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "script", "lint"),
         "--env-table", str(tmp_path / "env_vars.rst")],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    with open(committed) as fh:
        want = fh.read()
    with open(tmp_path / "env_vars.rst") as fh:
        got = fh.read()
    assert got == want, "docs/env_vars.rst drifted — regenerate with " \
                        "script/lint --env-table"


# spelled in parts: the names appear nowhere in the tree any more
_REMOVED_KNOBS = (["SKYLARK_PALLAS_" + tail for tail in
                   ("PIPELINE", "MTILE", "VMEM_BUDGET", "SCRATCH_CAP")]
                  + ["SKYLARK_" + tail for tail in
                     ("PLAN_CACHE", "USE_PLAN_CACHE", "COST_CALIB")])


@pytest.mark.parametrize("name", _REMOVED_KNOBS)
def test_removed_pallas_knobs_are_gone_everywhere(name):
    """Four knobs left in PR 30 (the pipelined-generation switch, the
    m-tile's env twin, two budgets no caller set) and three in PR 59 (the
    plan cache's file, its gate and the cost model's calibration): each
    is gone from the registry, the generated table and the lint baseline
    together, so none can come back in one place and be read from
    another."""
    from libskylark_tpu.base import env as sk_env

    with open(os.path.join(REPO, "docs", "env_vars.rst")) as fh:
        table = fh.read()
    with open(os.path.join(REPO, "libskylark_tpu", "analysis",
                           "baseline.json")) as fh:
        baseline = fh.read()
    attr = name[len("SKYLARK_"):]
    assert name not in sk_env.REGISTRY, name
    assert not hasattr(sk_env, attr), attr
    assert name not in table, name
    assert attr not in baseline and attr.lower() not in baseline, attr


# ---------------------------------------------------------------------------
# layering: which package may import which when it is imported
# ---------------------------------------------------------------------------

_PKG = os.path.join(REPO, "libskylark_tpu")
_TOP_LEVEL = sorted(
    d for d in os.listdir(_PKG)
    if os.path.isfile(os.path.join(_PKG, d, "__init__.py")))


def _imports(path, module_level=False):
    """(line, statement, dotted names) of every import in one file, at
    any depth (a function-level import counts) or, with
    ``module_level``, only those that run when the file is imported."""
    import ast

    with open(path) as fh:
        tree = ast.parse(fh.read(), filename=path)

    def walk(node):
        for child in ast.iter_child_nodes(node):
            if module_level and isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
                continue
            yield child
            yield from walk(child)

    for node in walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names = [node.module] + [f"{node.module}.{a.name}"
                                     for a in node.names]
        else:
            continue
        yield node.lineno, ast.unparse(node), names


def _sibling_packages(names):
    """The top-level packages of libskylark_tpu among dotted names."""
    return {n.split(".")[1] for n in names
            if n.startswith("libskylark_tpu.") and
            n.split(".")[1] in _TOP_LEVEL}


def _py_files(top):
    for root, _dirs, files in os.walk(top):
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(root, f)


# What each package may import of its siblings WHEN IT IS IMPORTED (a
# function-level import is a call-time dependency and is not in this
# table; ROADMAP Queue 3 item 22 lists the ones that point upward).
# Written from the tree at PR 59; a package may use less than its set.
_MAY_IMPORT = {
    "analysis": set(), "base": set(), "cli": set(), "native": set(),
    "telemetry": {"base"},
    "utility": {"base"},
    "qos": {"base", "telemetry"},
    "resilience": {"base", "telemetry"},
    "sketch": {"base", "telemetry"},
    "sessions": {"base", "resilience", "telemetry"},
    "engine": {"base", "qos", "resilience", "telemetry"},
    "parallel": {"base", "sketch", "telemetry"},
    "algorithms": {"base", "engine"},
    "train": {"base", "resilience", "sessions", "telemetry"},
    "fleet": {"base", "engine", "qos", "resilience", "telemetry"},
    "net": {"base", "engine", "resilience", "telemetry"},
    "nla": {"algorithms", "base", "engine", "telemetry"},
    "ml": {"algorithms", "base", "engine", "nla", "resilience", "sketch",
           "telemetry", "utility"},
    "io": {"base", "ml", "resilience", "sketch", "telemetry"},
    "dist": {"base", "engine", "io", "qos", "resilience", "telemetry"},
}


def test_layering_covers_every_package():
    assert set(_MAY_IMPORT) == set(_TOP_LEVEL)
    assert "tune" not in _TOP_LEVEL


def test_the_layering_table_is_a_dag():
    """The stated sets order the packages: importing any one of them
    imports only packages below it, so no import order can deadlock on
    a half-initialised module. ``engine`` is below ``sketch`` nowhere:
    neither names the other at module level."""
    import graphlib

    order = list(graphlib.TopologicalSorter(_MAY_IMPORT).static_order())
    assert sorted(order) == _TOP_LEVEL
    assert "sketch" not in _MAY_IMPORT["engine"]
    assert "engine" not in _MAY_IMPORT["sketch"]


@pytest.mark.parametrize("package", _TOP_LEVEL)
def test_module_level_imports_stay_inside_the_layering(package):
    """The rule ``test_only_engine_imports_tune`` was one case of, until
    PR 59 took ``tune/`` out: a package's module-level imports of its
    siblings stay inside its stated set."""
    hits = []
    for path in _py_files(os.path.join(_PKG, package)):
        for line, stmt, names in _imports(path, module_level=True):
            extra = _sibling_packages(names) - {package} \
                - _MAY_IMPORT[package]
            if extra:
                hits.append((os.path.relpath(path, REPO), line, stmt))
    assert not hits, hits


# The compiled-program layer — what sketch/, base/sparse.py, parallel/,
# nla/, algorithms/ and ml/ all sit on — knows nothing above it, at any
# depth: a function-level import of one of these would be the arrow
# sketch -> engine.compiled -> (it) -> sketch that PR 59 removed.

@pytest.mark.parametrize("module", ["compiled", "cache", "aot", "bucket"])
def test_the_compiled_program_layer_reaches_nothing_above_it(module):
    path = os.path.join(_PKG, "engine", module + ".py")
    above = {"sketch", "nla", "ml", "dist", "fleet", "net", "tune"}
    hits = [(line, stmt) for line, stmt, names in _imports(path)
            if any(n.startswith("libskylark_tpu.") and
                   n.split(".")[1] in above for n in names)]
    assert not hits, hits


def _kernel_modules_imported(path):
    """The ``sketch/pallas_*`` modules one file imports."""
    return {part for _line, _stmt, names in _imports(path)
            for n in names for part in n.split(".")
            if part.startswith("pallas_")}


# The two kernels the v5e's compiler refused are gone with their pins (PR
# 47): the sparse table-and-gather body of pallas_sparse.py and
# pallas_fwht.py. What is left of the choice of a kernel: ``sketch/`` from
# the backend and the shapes for an eager apply, ``engine/serve.py`` over
# three batched kernels for a flush.

_GONE = ("pallas_fwht", "cwt_sparse_apply_batched", "SKYLARK_SPARSE_KERNEL",
         "SKYLARK_FWHT_KERNEL", "SKYLARK_FWHT_MIN_N",
         # PR 59: the tuner, its cache file, the key's fingerprint
         "libskylark_tpu.tune", "plan_cache.json", "plan_fingerprint",
         "record_ranked", "use_plan_cache")


def test_the_rows_kernel_stands_without_the_hash_kernel():
    import ast

    path = os.path.join(_PKG, "sketch", "pallas_sparse.py")
    assert _kernel_modules_imported(path) == {"pallas_dense"}
    with open(path) as fh:
        defined = {n.name for n in ast.parse(fh.read()).body
                   if isinstance(n, ast.FunctionDef)}
    assert {"rows_plan", "rows_visits", "hash_rows_apply"} <= defined
    assert not defined & {"qualify", "_kernel_sparse", "_sparse_call",
                          "cwt_sparse_apply", "cwt_sparse_apply_batched"}
    assert not os.path.exists(os.path.join(_PKG, "sketch", "pallas_fwht.py"))


def test_the_serve_flush_chooses_among_three_kernel_modules():
    assert _kernel_modules_imported(
        os.path.join(_PKG, "engine", "serve.py")) == {
            "pallas_dense", "pallas_hash", "pallas_fastfood"}


def test_no_module_or_document_names_what_was_removed():
    hits = []
    tops = [os.path.join(REPO, d) for d in
            ("libskylark_tpu", "docs", "benchmarks", "script")]
    paths = [os.path.join(REPO, "bench.py")]
    for top in tops:
        for root, dirs, files in os.walk(top):
            dirs[:] = [d for d in dirs if not d.startswith((".", "__"))]
            paths += [os.path.join(root, f) for f in files
                      if not f.endswith(".so")]
    for path in paths:
        with open(path, errors="replace") as fh:
            text = fh.read()
        hits += [(os.path.relpath(path, REPO), name) for name in _GONE
                 if name in text]
    assert not hits, hits


# ---------------------------------------------------------------------------
# runtime lock-order witness
# ---------------------------------------------------------------------------


@pytest.fixture()
def witness():
    _locks.enable_witness(True)
    _locks.reset_witness()
    yield
    _locks.enable_witness(False)
    _locks.reset_witness()


def _load_fixture_module(name):
    path = os.path.join(FIXTURES, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"lintfix_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_make_lock_plain_when_disabled():
    _locks.enable_witness(False)
    import threading
    lk = _locks.make_lock("test.plain")
    assert isinstance(lk, type(threading.Lock()))


def test_witness_detects_deliberate_inversion(witness):
    """The satellite contract: the deliberate two-lock inversion in
    the test-only module is detected at runtime — by the SAME file the
    static rule must flag (test_lock_rule_flags_inversion)."""
    mod = _load_fixture_module("lock_inversion_flag")
    assert mod.run_inversion() == 3
    rep = _locks.witness_report()
    assert rep["violations"], rep
    edge = rep["violations"][0]["edge"]
    assert set(edge) == {"fixture.alpha", "fixture.beta"}
    with pytest.raises(_locks.LockOrderError):
        _locks.check_witness()


def test_witness_clean_on_consistent_order(witness):
    a = _locks.make_lock("w.a")
    b = _locks.make_lock("w.b")
    for _ in range(3):
        with a:
            with b:
                pass
    rep = _locks.witness_report()
    assert rep["violations"] == []
    assert rep["edges"] == {"w.a": ["w.b"]}
    _locks.check_witness()  # no raise


def test_witness_condition_wait_tracks(witness):
    import threading
    lk = _locks.make_lock("w.cv_lock")
    cv = threading.Condition(lk)
    with cv:
        cv.wait(timeout=0.01)   # releases + reacquires through the
        #                         wrapper without corrupting the stack
    rep = _locks.witness_report()
    assert rep["violations"] == []
    _locks.check_witness()


def test_witness_serve_leg_clean(witness):
    """One full mini chaos leg under instrumented locks (the runtime
    half of the static/runtime agreement): a serve storm with an
    injected poison fault, forced flushes, and a drain — every lock
    the executor takes is witnessed, and no acquisition closes a
    cycle."""
    import numpy as np

    from libskylark_tpu import Context, engine
    from libskylark_tpu import sketch as sk
    from libskylark_tpu.resilience import faults

    rng = np.random.default_rng(0)
    ctx = Context(seed=0)
    T = sk.CWT(24, 8, ctx)
    ops = [rng.standard_normal((24, 3)).astype(np.float32)
           for _ in range(8)]
    plan = faults.FaultPlan({
        "seed": 3,
        "faults": [{"site": "serve.flush", "error": "SketchError",
                    "tag": "poison"}]})
    with faults.fault_plan(plan):
        ex = engine.MicrobatchExecutor(max_batch=4,
                                       linger_us=10_000_000)
        futs = []
        for i, A in enumerate(ops):
            if i == 2:
                with faults.tag("poison"):
                    futs.append(ex.submit_sketch(T, A))
            else:
                futs.append(ex.submit_sketch(T, A))
            if (i + 1) % 4 == 0:
                ex.flush()
        ex.flush()
        assert ex.drain(timeout=60.0)
        done = [f for f in futs if f.done()]
        assert len(done) == len(futs)       # zero orphans under chaos
    rep = _locks.witness_report()
    assert rep["acquisitions"] > 0          # the leg was instrumented
    assert rep["violations"] == [], rep["violations"]
    _locks.check_witness()
    # agreement: every witnessed edge between named sites is between
    # sites the static graph also knows (the static graph may know
    # MORE — it sees paths the storm didn't take)
    static = static_lock_graph(Project.load(REPO))
    static_sites = set(static["sites"]) | {
        "telemetry.metric", "telemetry.registry", "engine.cache",
        "engine.fn_stats", "serve.state", "serve.stats", "serve.pub",
        "serve.compiled", "resilience.health", "resilience.fault_plan",
        "resilience.fault_stack", "resilience.preemption",
        "tune.plan_cache", "tune.global_cache", "telemetry.sink"}
    for a, bs in rep["edges"].items():
        assert a in static_sites, a
        for b in bs:
            assert b in static_sites, b


def test_witness_report_shape():
    rep = _locks.witness_report()
    assert set(rep) == {"acquisitions", "edges", "violations"}
    json.dumps(rep)   # JSON-able (the chaos battery embeds it)
