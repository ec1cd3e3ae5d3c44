"""Which way the package's arrows point, by AST alone (nothing of
``knn_tpu`` is imported): every ``import`` / ``from ... import`` of a
module, at any depth (a lazy import inside a function is an arrow too),
held to what its layer may reach.

- ``knn_tpu/tuning`` imports nothing of ``knn_tpu``: the knobs' defaults
  are a leaf.
- The rules the program decides by (``analysis/vmem.py``, ``hbm.py``,
  ``subbatch.py``, ``widths.py``) import nothing outside
  ``knn_tpu.analysis``; the other ``analysis`` modules (the lint) nothing
  of ``ops``, ``parallel``, ``serving``.
- ``knn_tpu/obs`` imports nothing of ``knn_tpu`` outside ``obs`` (so
  nothing of ``analysis``, ``tuning``, ``ops``), but for the one arrow
  in ``EXEMPT``.

``EXEMPT`` is the ledger of the back-edges still in these three
packages (ROADMAP D21 lists the ones outside them): an entry that no
longer matches an import fails, so the list can only shrink."""

import ast
import glob
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "knn_tpu")

#: (module, imported sub-package): the arrows against the rule that are
#: known and named.  ``obs/health.py`` reads the last cross-host merge's
#: report off ``parallel.multihost``, lazily (ROADMAP D7 / D21).
EXEMPT = {(os.path.join("obs", "health.py"), "parallel")}

RULE_MODULES = ("vmem", "hbm", "subbatch", "widths")


def modules_of(sub: str):
    if os.path.isfile(os.path.join(PKG, sub + ".py")):
        return [f"{sub}.py"]
    return sorted(
        os.path.relpath(p, PKG) for p in glob.glob(
            os.path.join(PKG, sub, "**", "*.py"), recursive=True))


def imported_subpackages(rel: str) -> set:
    """The first-level sub-packages of ``knn_tpu`` a module imports
    (absolute or relative, top level or inside a function)."""
    path = os.path.join(PKG, rel)
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    here = ["knn_tpu"] + rel.split(os.sep)[:-1]
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:  # relative: resolve against the package
                base = ".".join(here[:len(here) - node.level + 1]
                                + ([base] if base else []))
            # ``from knn_tpu import x`` / ``from . import x`` name the
            # sub-package in the alias, not in the module
            names = [f"{base}.{a.name}" for a in node.names]
        else:
            continue
        for name in names:
            parts = name.split(".")
            if parts[0] == "knn_tpu" and len(parts) > 1:
                found.add(parts[1])
    return found


def _cases():
    """(module, the only sub-packages it may import or None for any,
    the sub-packages it may not)."""
    for rel in modules_of("tuning"):
        yield rel, set(), set()
    for rel in modules_of("analysis"):
        if os.path.basename(rel)[:-len(".py")] in RULE_MODULES:
            yield rel, {"analysis"}, set()
        else:
            yield rel, None, {"ops", "parallel", "serving"}
    for rel in modules_of("obs"):
        yield rel, {"obs"}, set()


CASES = list(_cases())


def test_the_layers_listed_are_the_trees():
    """One tuning module, the four rule modules among analysis's, and
    obs without the model's four: a module added to a layer is a case
    here without an edit."""
    assert modules_of("tuning") == [os.path.join("tuning", "__init__.py")]
    stems = {os.path.basename(r)[:-3] for r in modules_of("analysis")}
    assert set(RULE_MODULES) <= stems
    obs = {os.path.basename(r)[:-3] for r in modules_of("obs")}
    assert not obs & {"roofline", "calibrate", "traceread", "profiler"}
    assert len(obs) == 14 and len(CASES) == 1 + len(stems) + len(obs)


@pytest.mark.parametrize("rel,only,never", CASES,
                         ids=[c[0] for c in CASES])
def test_a_module_imports_only_what_its_layer_may(rel, only, never):
    found = imported_subpackages(rel)
    against = (found & never) | (found - only if only is not None
                                 else set())
    exempt = {sub for mod, sub in EXEMPT if mod == rel}
    assert exempt <= found, f"{rel}: stale exemption {exempt - found}"
    assert against - exempt == set(), (
        f"knn_tpu/{rel} imports knn_tpu.{sorted(against - exempt)}")


def test_importing_obs_imports_no_module_of_analysis_or_tuning():
    """The AST rule's runtime twin, in a fresh interpreter."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, knn_tpu.obs\n"
         "bad = sorted(m for m in sys.modules if m.startswith(("
         "'knn_tpu.analysis', 'knn_tpu.tuning', 'knn_tpu.ops', 'jax')))\n"
         "assert not bad, bad"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr
