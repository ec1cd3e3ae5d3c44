"""``artifact-lockstep`` — the artifact blocks in lockstep with their
schema catalog (:mod:`knn_tpu.analysis.artifacts`).

Four invariants over the catalog, each one a contract some PR used to
hand-check:

1. **emitter keys resolve** — every string key an emitter writes into a
   cataloged block literal (a dict literal matching one of the
   schema's fingerprints, in one of its declared emitter files)
   resolves in that schema.  An emitted-but-undeclared key is invisible
   to the validator — half-wired by construction;
2. **schema fields are emitted** — every declared field's leaf name
   appears in at least one emitter file, or carries a written
   ``emit_note`` justification (>= 10 chars, the suppression
   discipline).  The catalog can't rot into fiction;
3. **version tokens** — every declared version token resolves to an
   int constant and is consumed by exactly one schema, whose own field
   list declares it;
4. **docs anchors** — every block type's ``doc`` anchor names a real
   heading in a real doc file.

Checks 1, 2 and 4 only run against files that exist under the lint root
(fixture trees stay green); check 3 judges the catalog itself.  The
catalog is read from the lint ROOT's copy when present
(``Context.load_module``) so ``--root`` judges another checkout against
ITS declarations.
"""

from __future__ import annotations

import ast
import os
from typing import List, Set

from knn_tpu.analysis import artifacts as _session_artifacts
from knn_tpu.analysis.core import Context, Finding, checker

_CATALOG_REL = os.path.join("knn_tpu", "analysis", "artifacts.py")


def _string_constants(tree: ast.Module) -> Set[str]:
    return {node.value for node in ast.walk(tree)
            if isinstance(node, ast.Constant)
            and isinstance(node.value, str)}


def _dict_literals(tree: ast.Module):
    """(node, string-key set) for every dict literal with at least one
    string key (``**``-unpacked entries have no key and are skipped —
    their contents are separate literals of their own)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Dict):
            keys = {k.value for k in node.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)}
            if keys:
                yield node, keys


@checker("artifact-lockstep",
         "artifact-schema catalog <-> emitters <-> docs")
def check_artifacts(ctx: Context) -> List[Finding]:
    arts = ctx.load_module(_CATALOG_REL, _session_artifacts)
    findings: List[Finding] = []

    def err(path: str, msg: str, symbol: str = "",
            fix: str = "") -> None:
        findings.append(Finding(
            checker="artifact-lockstep", path=path, line=0,
            message=msg, symbol=symbol, fix_hint=fix))

    # --- 3. version tokens: unique, resolvable, self-declared ----------
    seen_versions = {}
    for schema in arts.CATALOG:
        if not schema.version_field:
            continue
        owner = seen_versions.setdefault(schema.version_field,
                                         schema.name)
        if owner != schema.name:
            err(_CATALOG_REL,
                f"version token {schema.version_field!r} is consumed "
                f"by two validators ({owner} and {schema.name}) — "
                f"every version token must belong to exactly one "
                f"block schema", schema.version_field)
        try:
            v = arts.version_value(schema.name)
        except Exception as e:  # noqa: BLE001 — unresolvable = finding
            err(_CATALOG_REL,
                f"schema {schema.name}: version_ref "
                f"{schema.version_ref!r} does not resolve: "
                f"{type(e).__name__}: {e}", schema.name)
            continue
        if not isinstance(v, int):
            err(_CATALOG_REL,
                f"schema {schema.name}: version_ref resolves to "
                f"{v!r}, not an int version token", schema.name)
        if schema.version_field not in {f.path for f
                                        in schema.fields}:
            err(_CATALOG_REL,
                f"schema {schema.name}: version field "
                f"{schema.version_field!r} is not among its own "
                f"declared fields", schema.name)

    # --- 1. emitter block literals resolve in their schemas ------------
    emitter_files = sorted({rel for s in arts.CATALOG
                            for rel in s.emitters})
    strings_of = {}
    for rel in emitter_files:
        if not ctx.exists(rel):
            continue
        tree = ctx.parse(rel)
        if tree is None:
            continue  # the framework already reported the parse error
        strings_of[rel] = _string_constants(tree)
        for node, keys in _dict_literals(tree):
            owners = [s for s in arts.CATALOG
                      if rel in s.emitters
                      and any(fp <= keys for fp in s.fingerprints)]
            if not owners:
                continue
            known = set()
            for s in owners:
                known |= arts.known_keys(s.name)
            for key in sorted(keys - known):
                err(rel,
                    f"emitter writes key {key!r} into a "
                    f"{'/'.join(s.name for s in owners)} block "
                    f"literal (line {node.lineno}), but no artifact "
                    f"schema declares it — the validator is blind "
                    f"to it", key,
                    fix="declare the field in the block's schema "
                        "entry (knn_tpu/analysis/artifacts.py)")

    # --- 2. every schema field emitted somewhere, or justified ---------
    # judged only when EVERY declared emitter file is present under the
    # lint root — a fixture tree carrying one emitter must not condemn
    # fields the absent emitters own.
    for schema in arts.CATALOG:
        present = [rel for rel in schema.emitters if rel in strings_of]
        complete = bool(schema.emitters) and \
            len(present) == len(schema.emitters)
        emitted: Set[str] = set()
        for rel in present:
            emitted |= strings_of[rel]
        for f in schema.fields:
            if f.emit_note:
                if len(f.emit_note.strip()) < 10:
                    err(_CATALOG_REL,
                        f"schema {schema.name}: field {f.path!r} "
                        f"suppresses the emitted check without a "
                        f"written justification (>= 10 chars)",
                        f.path)
                continue
            if complete and f.leaf not in emitted:
                err(_CATALOG_REL,
                    f"schema {schema.name}: field {f.path!r} is "
                    f"declared but no emitter "
                    f"({', '.join(schema.emitters)}) ever names it — "
                    f"phantom schema field", f.path,
                    fix="delete the field, or set emit_note with a "
                        "written justification")

    # --- 4. docs anchors ------------------------------------------------
    for schema in arts.CATALOG:
        doc_file, anchor = schema.doc.split("#", 1)
        if ctx.exists(doc_file):
            heading_hit = any(
                line.lstrip().startswith("#")
                and anchor.lower() in line.lower()
                for line in ctx.read(doc_file).splitlines())
            if not heading_hit:
                err(doc_file,
                    f"schema {schema.name}: docs anchor "
                    f"{schema.doc!r} names no heading in {doc_file} — "
                    f"every block type must keep its documentation "
                    f"anchor", schema.name)
    return findings
