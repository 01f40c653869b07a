"""Repo-health guard: no pyc-only ghost packages, ever again.

``chainermn_tpu/observability/`` once existed only as ``__pycache__`` (its
sources were lost but the stale bytecode kept the name importable as an
empty namespace package, silently).  This tier-1 guard fails on:

* any ``__pycache__`` entry whose adjacent source file is missing, and
* any package directory under ``chainermn_tpu/`` lacking ``__init__.py``
  (a namespace-package hole where a real package is expected).
"""

import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Non-package dirs that legitimately hold no sources.
_SKIP_DIRS = {os.path.join("chainermn_tpu", "_native", "build")}


def _walk(root):
    for dirpath, dirnames, filenames in os.walk(os.path.join(REPO, root)):
        rel = os.path.relpath(dirpath, REPO)
        if any(rel == s or rel.startswith(s + os.sep) for s in _SKIP_DIRS):
            dirnames[:] = []
            continue
        yield dirpath, dirnames, filenames


def test_every_pycache_has_adjacent_sources():
    orphans = []
    for root in ("chainermn_tpu", "tests"):
        for dirpath, dirnames, filenames in _walk(root):
            if os.path.basename(dirpath) != "__pycache__":
                continue
            parent = os.path.dirname(dirpath)
            for f in filenames:
                if not f.endswith(".pyc"):
                    continue
                src = f.split(".", 1)[0] + ".py"
                if not os.path.exists(os.path.join(parent, src)):
                    orphans.append(
                        os.path.relpath(os.path.join(dirpath, f), REPO)
                    )
    assert not orphans, (
        "stale bytecode with no adjacent source (a pyc-only ghost package "
        f"in the making — delete it): {orphans}"
    )


#: Dirs whose tests dominate tier-1 wall clock (the flash interpret
#: sweeps, model oracles, decode batteries): every test FILE here must
#: declare its tier explicitly — `pytestmark` with `slow` (full-CI only)
#: or `tier1` (fast, stays in --quick).  Without the marker, a new
#: long-pole lands in tier-1 by default and the budgeted verify command
#: times out mid-suite, which reads as mysterious breakage.
_TIERED_DIRS = (
    os.path.join("tests", "models_tests"),
    os.path.join("tests", "ops_tests"),
    os.path.join("tests", "observability_tests"),
    os.path.join("tests", "serving_tests"),
    os.path.join("tests", "resilience_tests"),
)
def test_long_pole_dirs_declare_test_tiers():
    undeclared = []
    for d in _TIERED_DIRS:
        for f in sorted(os.listdir(os.path.join(REPO, d))):
            if not (f.startswith("test_") and f.endswith(".py")):
                continue
            path = os.path.join(REPO, d, f)
            with open(path) as fh:
                src = fh.read()
            if not re.search(r"^pytestmark\s*=", src, re.M) or \
                    not re.search(r"pytest\.mark\.(slow|tier1)\b", src):
                undeclared.append(os.path.relpath(path, REPO))
    assert not undeclared, (
        "test files in tier-budgeted dirs without an explicit tier marker "
        "(add `pytestmark = pytest.mark.tier1` if it is fast, or "
        "`pytest.mark.slow` if it belongs to full CI only): "
        f"{undeclared}"
    )


#: ``reg.counter("...")`` / ``.gauge`` / ``.histogram`` literals (plain
#: or f-string; the call may wrap lines, hence DOTALL).
_METRIC_CALL_RE = re.compile(
    r"\.(?:counter|gauge|histogram)\(\s*(f?)[\"']([^\"']+)[\"']", re.S
)


def _normalize_metric(name):
    """Dynamic segments — ``{expr}`` in code f-strings, ``<placeholder>``
    in the doc catalog — both normalize to ``*`` so the two sides
    compare: ``host_op.{span.op}.ms`` == ``host_op.<op>.ms``."""
    return re.sub(r"(\{[^}]*\}|<[^>]*>)", "*", name)


def test_metric_names_match_doc_catalog():
    """Doc-drift lint: every metric published anywhere in
    ``chainermn_tpu/`` appears in the ``docs/observability.md`` metric
    catalog, and every catalog row names a metric the code actually
    publishes.  A metric missing from the catalog is invisible to
    operators; a stale catalog row documents a signal that no longer
    exists — both are silent drift."""
    code_names = {}
    for dirpath, dirnames, filenames in _walk("chainermn_tpu"):
        if os.path.basename(dirpath) == "__pycache__":
            continue
        for f in filenames:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            with open(path) as fh:
                src = fh.read()
            for m in _METRIC_CALL_RE.finditer(src):
                code_names.setdefault(
                    _normalize_metric(m.group(2)),
                    os.path.relpath(path, REPO),
                )
    assert code_names, "metric-literal scan found nothing — regex rot?"
    # Catalog side: table rows' FIRST cell, backticked dotted names
    # (slashes/spaces exclude file paths and prose).
    doc_path = os.path.join(REPO, "docs", "observability.md")
    doc_names = set()
    with open(doc_path) as fh:
        for line in fh:
            if not line.startswith("|"):
                continue
            first_cell = line.split("|")[1]
            for tok in re.findall(r"`([^`]+)`", first_cell):
                if "." in tok and "/" not in tok and " " not in tok:
                    doc_names.add(_normalize_metric(tok))
    undocumented = {
        n: where for n, where in code_names.items() if n not in doc_names
    }
    stale = doc_names - set(code_names)
    assert not undocumented, (
        "metrics published in code but missing from the "
        "docs/observability.md catalog (add a table row): "
        f"{undocumented}"
    )
    assert not stale, (
        "docs/observability.md catalog rows with no publishing code "
        f"(delete or fix the row): {sorted(stale)}"
    )


#: Env-var reads/sets in code: ``os.environ.get/[]/.setdefault`` plus the
#: SLO module's ``_env_float`` indirection, plain or f-string literal.
_ENV_CALL_RE = re.compile(
    r"(?:environ\.get\(|environ\[|environ\.setdefault\(|_env_float\()"
    r"\s*(f?)[\"']((?:CMN_|CHAINERMN_TPU_)[A-Za-z0-9_{}().]*)",
    re.S,
)


def test_env_knob_names_match_doc_tables():
    """Doc-drift lint, env-knob edition (ISSUE 8 satellite): every
    ``CMN_*``/``CHAINERMN_TPU_*`` env var the code reads appears in some
    docs/*.md knob-table row (first cell, backticked), and every
    documented knob is actually read somewhere — the same two-way
    contract the metric-catalog lint enforces.  F-string segments and
    doc ``<placeholder>`` s both normalize to ``*`` and compare by
    wildcard match (``CMN_SLO_*_P95_MS`` covers the per-stream rows)."""
    import fnmatch

    code_names = {}
    for dirpath, dirnames, filenames in _walk("chainermn_tpu"):
        if os.path.basename(dirpath) == "__pycache__":
            continue
        for f in filenames:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            with open(path) as fh:
                src = fh.read()
            for m in _ENV_CALL_RE.finditer(src):
                code_names.setdefault(
                    _normalize_metric(m.group(2)),
                    os.path.relpath(path, REPO),
                )
    assert code_names, "env-literal scan found nothing — regex rot?"
    doc_names = set()
    docs_dir = os.path.join(REPO, "docs")
    for doc in sorted(os.listdir(docs_dir)):
        if not doc.endswith(".md"):
            continue
        with open(os.path.join(docs_dir, doc)) as fh:
            for line in fh:
                if not line.startswith("|"):
                    continue
                first_cell = line.split("|")[1]
                for tok in re.findall(r"`([^`]+)`", first_cell):
                    if re.fullmatch(
                        r"(CMN_|CHAINERMN_TPU_)[A-Za-z0-9_<>]*", tok
                    ):
                        doc_names.add(_normalize_metric(tok))

    def covered(name, others):
        return any(
            fnmatch.fnmatch(name, o) or fnmatch.fnmatch(o, name)
            for o in others
        )

    undocumented = {
        n: where for n, where in code_names.items()
        if not covered(n, doc_names)
    }
    stale = {n for n in doc_names if not covered(n, set(code_names))}
    assert not undocumented, (
        "env knobs read in code but absent from every docs/*.md knob "
        f"table (add a table row): {undocumented}"
    )
    assert not stale, (
        "documented env knobs no code reads (delete or fix the row): "
        f"{sorted(stale)}"
    )


#: Offline observability analyzers: every ``python -m
#: chainermn_tpu.observability.<name>`` tool must keep supporting
#: ``--json`` and exit 0 on the repo's committed sample artifacts —
#: otherwise the offline half of the observability stack rots silently
#: (nothing else executes these CLIs in CI).  One row per analyzer:
#: (module, argv built from the repo checkout).
_ANALYZERS = (
    ("chainermn_tpu.observability.analyze",
     [os.path.join("result", "sample_fleet_trace.json")]),
    ("chainermn_tpu.observability.perf",
     ["--result-dir", "result"]),
    ("chainermn_tpu.observability.incident",
     ["report", os.path.join("result", "sample_incident_bundle")]),
    ("chainermn_tpu.observability.usage",
     ["report", os.path.join("result", "sample_usage_ledger.json")]),
)


def test_observability_analyzers_run_offline_with_json():
    import json
    import subprocess
    import sys

    for module, args in _ANALYZERS:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        r = subprocess.run(
            [sys.executable, "-m", module, *args, "--json"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=240,
        )
        assert r.returncode == 0, (module, r.stdout, r.stderr)
        report = json.loads(r.stdout)
        assert isinstance(report, dict) and report, module
        # And the human rendering exits 0 too.
        r2 = subprocess.run(
            [sys.executable, "-m", module, *args],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=240,
        )
        assert r2.returncode == 0, (module, r2.stdout, r2.stderr)
        assert r2.stdout.strip(), module


def test_every_package_dir_has_init():
    missing = []
    for dirpath, dirnames, filenames in _walk("chainermn_tpu"):
        if os.path.basename(dirpath) == "__pycache__":
            continue
        has_py = any(f.endswith(".py") for f in filenames)
        has_cache = "__pycache__" in dirnames
        if (has_py or has_cache) and "__init__.py" not in filenames:
            missing.append(os.path.relpath(dirpath, REPO))
    assert not missing, (
        f"package dirs importing as silent namespace packages: {missing}"
    )


def test_package_import_initialises_no_backend():
    """One process per chip: ``chainermn_tpu.launch``'s parent imports the
    package and then spawns the ranks that need the devices, so importing
    the package (launcher, serving and model modules included) must leave
    JAX's backends uninitialised."""
    import subprocess
    import sys

    code = (
        "import chainermn_tpu, chainermn_tpu.launch, chainermn_tpu.serving,"
        " chainermn_tpu.models, chainermn_tpu.ops, chainermn_tpu.parallel\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, dict(xla_bridge._backends)\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
