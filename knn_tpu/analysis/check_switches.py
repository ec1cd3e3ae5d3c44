"""``switch-lockstep`` — every env switch declared, documented,
consumed, and test-isolated.

Four invariants over the catalog (knn_tpu.analysis.switches):

1. every ``KNN_TPU_*`` string literal in source is a
   cataloged switch (or a declared family prefix — ``startswith``
   scans); an undeclared switch can't ship half-wired;
2. every cataloged switch appears in the docs (``docs/*.md`` or
   ``README.md``), and every switch-shaped doc token resolves back to
   the catalog (no phantom switches advertised);
3. every cataloged switch is actually read somewhere in source —
   judged on CODE literals only, never docstring mentions, so a
   deleted env read whose docstring survives still surfaces
   (``reserved`` families exempt) — the catalog can't rot into
   fiction;
4. ``tests/conftest.py`` GENERATES its isolation from
   :func:`knn_tpu.analysis.switches.isolation_names` — the gap this PR
   closed (65 switches in source, 13 isolated by hand) can never
   reopen, because the isolation list is derived, not maintained.

Doc/consumption/conftest checks only run when the corresponding files
exist under the lint root, so the checker also works over small fixture
trees in tests.  The catalog itself is read from the lint ROOT's
``knn_tpu/analysis/switches.py`` when present (``--root`` on another
checkout judges that tree against ITS declarations); fixture trees
without a catalog lint against the session's.
"""

from __future__ import annotations

import ast
import glob
import os
from typing import List, Set

from knn_tpu.analysis import switches as _session_sw
from knn_tpu.analysis.core import Context, Finding, checker

#: the catalog module itself holds every declaration as a literal
_CATALOG_REL = os.path.join("knn_tpu", "analysis", "switches.py")
_SKIP = {_CATALOG_REL}


def _docstring_consts(tree: ast.Module) -> Set[int]:
    """``id()`` of every Constant node sitting in docstring position
    (first statement of a module/class/function body)."""
    out: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and \
                    isinstance(body[0].value, ast.Constant) and \
                    isinstance(body[0].value.value, str):
                out.add(id(body[0].value))
    return out


def _source_literals(ctx: Context, sw):
    """(relpath, line, token, is_docstring) for every switch-shaped
    string constant in the source tree (AST-based: comments can't trip
    it, but docstrings — which document behavior — can and should).
    ``is_docstring`` lets invariant 3 judge CONSUMPTION on code
    literals only: a docstring that still names a deleted env read
    must not keep a phantom catalog row alive."""
    for relpath in ctx.py_files():
        if relpath in _SKIP:
            continue
        tree = ctx.parse(relpath)
        if tree is None:
            continue
        doc_ids = _docstring_consts(tree)
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                for token in sw.tokens_in_source(node.value):
                    yield relpath, node.lineno, token, \
                        id(node) in doc_ids


def _doc_files(ctx: Context) -> List[str]:
    out = [p for p in glob.glob(os.path.join(ctx.root, "docs", "*.md"))]
    readme = os.path.join(ctx.root, "README.md")
    if os.path.exists(readme):
        out.append(readme)
    return sorted(out)


@checker("switch-lockstep",
         "env-switch catalog <-> source <-> docs <-> conftest isolation")
def check_switches(ctx: Context) -> List[Finding]:
    # the lint root's own catalog when it carries one (an alternate
    # checkout is judged against ITS declarations); the session's for
    # fixture trees without a catalog
    sw = ctx.load_module(_CATALOG_REL, _session_sw)
    findings: List[Finding] = []
    consumed: Set[str] = set()

    # 1. source literals resolve to the catalog.  Consumption (for
    # invariant 3) is judged on CODE literals only: a docstring naming
    # a switch documents it, it doesn't read it.
    for relpath, line, token, is_doc in _source_literals(ctx, sw):
        if not is_doc:
            consumed.add(token)
        if sw.lookup(token) is None:
            kind = ("family prefix" if token.endswith("_")
                    else "switch")
            findings.append(Finding(
                checker="switch-lockstep", path=relpath, line=line,
                symbol=token,
                message=f"{kind} {token!r} is not declared in the "
                        f"switch catalog "
                        f"(knn_tpu/analysis/switches.py)",
                fix_hint="declare it there (kind, consumer, doc row, "
                         "isolation) — conftest isolation then follows "
                         "automatically"))

    # 2. docs <-> catalog, both directions
    doc_files = _doc_files(ctx)
    if doc_files:
        doc_tokens: Set[str] = set()
        doc_of = {}
        for path in doc_files:
            with open(path, encoding="utf-8") as f:
                for token in sw.tokens_in_source(f.read()):
                    doc_tokens.add(token)
                    doc_of.setdefault(token,
                                      os.path.relpath(path, ctx.root))
        for s in sw.SWITCHES:
            if s.name not in doc_tokens:
                findings.append(Finding(
                    checker="switch-lockstep", path=s.doc, line=0,
                    symbol=s.name,
                    message=f"cataloged switch {s.name} is missing "
                            f"from the docs (expected a row in "
                            f"{s.doc})",
                    fix_hint=f"add a row: {s.description}"))
        for token in sorted(doc_tokens):
            if sw.lookup(token) is not None:
                continue
            # docs may shorten a group of switches to a prefix token
            # (e.g. KNN_TPU_ADMISSION_...) — fine while it prefixes
            # real catalog rows
            if token.endswith("_") and any(
                    s.name.startswith(token) for s in sw.SWITCHES):
                continue
            findings.append(Finding(
                checker="switch-lockstep", path=doc_of[token], line=0,
                symbol=token,
                message=f"docs mention {token}, which is not a "
                        f"cataloged switch (phantom switch)"))

    # 3. every cataloged switch is consumed by source.  A non-family
    # switch also counts as consumed through its cataloged family
    # prefix appearing as a CODE literal: modules like
    # serving/admission.py read their whole family wholesale
    # (``{k for k in env if k.startswith(ENV_PREFIX)}`` + computed
    # member names), so the prefix literal is the real env read.
    # RESERVED families (the KNN_TPU_ root namespace, scanned
    # wholesale by the flight recorder and conftest) never count —
    # through them, every switch would read as consumed and
    # the invariant would be vacuous.
    if any(ctx.exists(r) for r in ctx.source_roots):
        family_prefixes_in_code = set()
        for c in consumed:
            if not c.endswith("_"):
                continue
            row = sw.lookup(c)
            if row is not None and row.family and not row.reserved:
                family_prefixes_in_code.add(c)
        for s in sw.SWITCHES:
            if s.reserved:
                continue
            if s.family:
                hit = s.name in consumed or any(
                    c.startswith(s.name) for c in consumed)
            else:
                hit = s.name in consumed or any(
                    s.name.startswith(p)
                    for p in family_prefixes_in_code)
            if not hit:
                findings.append(Finding(
                    checker="switch-lockstep",
                    path=os.path.join("knn_tpu", "analysis",
                                      "switches.py"),
                    line=0, symbol=s.name,
                    message=f"cataloged switch {s.name} is never read "
                            f"by source (declared consumer: "
                            f"{s.consumer}) — phantom catalog row",
                    fix_hint="delete the row, or mark the family "
                             "reserved=True if the namespace is held "
                             "for isolation"))

    # 4. conftest derives isolation from the catalog
    conftest = os.path.join("tests", "conftest.py")
    if os.path.isdir(os.path.join(ctx.root, "tests")):
        ok = False
        if ctx.exists(conftest):
            try:
                tree = ast.parse(ctx.read(conftest))
                for node in ast.walk(tree):
                    if isinstance(node, ast.Call):
                        fn = node.func
                        name = getattr(fn, "id", None) or \
                            getattr(fn, "attr", None)
                        if name == "isolation_names":
                            ok = True
            except SyntaxError:
                pass
        if not ok:
            findings.append(Finding(
                checker="switch-lockstep", path=conftest, line=0,
                message="tests/conftest.py does not derive its switch "
                        "isolation from knn_tpu.analysis.switches."
                        "isolation_names() — hand-listed isolation "
                        "reopens the 65-declared/13-isolated gap",
                fix_hint="pop every name isolation_names(os.environ) "
                         "returns before importing jax"))
    return findings
