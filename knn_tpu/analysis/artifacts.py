"""The artifact-schema registry — ONE declarative catalog for every
bench block the repo emits, and the generic engine that validates,
hoists, curates, and prints them.

Six PRs grew six hand-rolled ``validate_*_block`` functions (roofline,
calibration, campaign, knee, mutation, multihost), a hand-maintained
sentinel ``CURATED_FIELDS`` list, and six copy-pasted
validate→refuse→hoist→print stanzas in
``scripts/refresh_bench_artifacts.py``.  Each was one more hand-checked
contract between an emitter (bench.py / knee.py / roofline.py / the
campaign harness), the artifact refresher, the perf sentinel, and the
docs — exactly the class of drift PR 10's switch/metric catalogs killed
elsewhere.  This module applies the same cure to the artifact pipeline
itself:

- :data:`CATALOG` — one :class:`BlockSchema` per artifact block
  (roofline, calibration, campaign, loadgen_knee, mutation, multihost,
  sentinel verdict, tuning-cache entries, bench top-level lines,
  MULTICHIP driver records), each declaring its fields
  (types/required/ranges), version token, top-level hoist keys,
  sentinel curated-field direction, emitters + fingerprints (for the
  ``artifact-lockstep`` checker), and docs anchor;
- :func:`validate` — the generic engine replacing the six hand
  validators.  ``style="legacy"`` reproduces each legacy validator's
  error strings BYTE-IDENTICALLY (the six public ``validate_*`` entry
  points are now one-line shims over it, their refusal tests
  unmodified); ``style="normalized"`` is the engine's one canonical
  phrasing (``missing field: X`` / ``field X must be ..., got ...``) —
  the normalization the calibration/campaign validators' divergent
  styles fold into, behind the compat shims;
- :func:`curate_line` / :func:`apply_hoists` / :func:`line_summary` —
  the table-driven validate/refuse/hoist/print loop the refresher and
  ``bench.py`` run instead of six copies;
- :func:`curated_fields` — the sentinel's ``CURATED_FIELDS``, derived
  (the hand list is gone);
- :func:`sweep_records` / :func:`sweep_multichip` — the
  ``perf_sentinel --lint`` history sweep: every block in every
  checked-in ``BENCH_r*.json`` / ``TPU_BENCH_r*.jsonl`` /
  ``MULTICHIP_r*.json`` line validated against the catalog
  (exact-version schemas exempt blocks stamped with a strictly older
  version token — pre-schema rounds are reported, not condemned).

Everything here is stdlib-only and jax-free: the catalog must load on
the box that curates artifacts, not only the one with the accelerator.
Version tokens and choice sets stay in their owning modules
(``MODEL_VERSION`` lives with the model that bumps it) and are
referenced lazily through :class:`Ref` — the catalog declares, it never
duplicates.

Adding a bench block is ONE schema entry here (docs/ANALYSIS.md "Adding
a bench block"): the validator, the refresher's refusal + hoists, the
sentinel's curated baseline, the history sweep, and the
``artifact-lockstep`` checker all follow from the declaration.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib
import json
import os
from typing import Dict, List, Optional, Tuple

__all__ = [
    "CATALOG",
    "BY_NAME",
    "BlockSchema",
    "Field",
    "Gate",
    "Rule",
    "Hoist",
    "Curated",
    "Ref",
    "validate",
    "version_value",
    "required_keys",
    "element_required",
    "known_keys",
    "curated_fields",
    "apply_hoists",
    "apply_scope_hoists",
    "curate_line",
    "line_summary",
    "sweep_records",
    "sweep_multichip",
]


# --------------------------------------------------------------------------
# declaration primitives
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Ref:
    """A lazy pointer to a constant in its owning module (the version
    token, a choice tuple).  The catalog references the single source
    of truth instead of copying it — ``MODEL_VERSION`` still lives with
    the model whose bump invalidates caches."""

    module: str
    attr: str


_REF_MEMO: Dict[Tuple[str, str], object] = {}


def _resolve(ref):
    if not isinstance(ref, Ref):
        return ref
    key = (ref.module, ref.attr)
    if key not in _REF_MEMO:
        _REF_MEMO[key] = getattr(importlib.import_module(ref.module),
                                 ref.attr)
    return _REF_MEMO[key]


@dataclasses.dataclass(frozen=True)
class Field:
    """One declared block field.

    ``path`` is dotted into the block; ``kind`` is the value contract
    (``any`` declares the key without constraining it — the lockstep
    checker still tracks it).  ``legacy`` is the byte-identical message
    template of the hand validator this field migrated from
    (placeholders: ``{value!r}``, ``{path}``, ``{leaf}``, ``{vtype}``,
    ``{choices}``, ``{version}``); absent, the normalized phrasing is
    used in both styles.  ``emit_note`` is a written justification
    (>= 10 chars) for a field no emitter writes — the suppression
    discipline of the lint framework."""

    path: str
    kind: str = "any"  # any|int|number|str|bool|dict|list|version|nested
    required: bool = False
    nullable: bool = False
    #: the value must additionally be truthy (legacy ``if not
    #: block.get(...)`` semantics — campaign's ``arm``)
    truthy: bool = False
    ge: Optional[float] = None
    gt: Optional[float] = None
    le: Optional[float] = None
    choices: object = None  # tuple or Ref
    legacy: Optional[str] = None
    stop_on_error: bool = False
    nonempty: bool = False
    nested: Optional[str] = None
    element_style: str = ""  # "knee_steps" | "campaign_stages"
    element_required: Tuple[str, ...] = ()
    element_optional: Tuple[str, ...] = ()
    emit_note: str = ""

    @property
    def leaf(self) -> str:
        return self.path.rsplit(".", 1)[-1]


@dataclasses.dataclass(frozen=True)
class Gate:
    """Stop validating the remaining checks when ``path`` is falsy —
    an unapplied calibration carries no factors to judge."""

    path: str


@dataclasses.dataclass(frozen=True)
class Rule:
    """A named cross-field rule (see ``_RULES``) — the residue a
    per-field declaration cannot express (a knee claimed with no
    SLO-meeting step, a mutation line that never compacted)."""

    name: str


@dataclasses.dataclass(frozen=True)
class Hoist:
    """One block field hoisted to a top-level line key (setdefault
    semantics).  ``gate`` (default: ``src``) must be non-null — or
    truthy with ``truthy=True`` — for the hoist to fire; ``numeric``
    additionally requires the hoisted value to be a number.  ``bench``
    / ``refresher`` scope which loop performs it (bench flags
    ``roofline_estimated``; only the refresher back-fills
    ``multihost_hosts``)."""

    src: str
    dst: str
    gate: Optional[str] = None
    truthy: bool = False
    numeric: bool = False
    bench: bool = True
    refresher: bool = True


@dataclasses.dataclass(frozen=True)
class Curated:
    """One sentinel curated field contributed by this block: the
    hoisted top-level key, its good direction, and its rank in the
    legacy ``CURATED_FIELDS`` order (preserved so derived == hand
    list, element for element)."""

    field: str
    direction: str  # "higher" | "lower"
    rank: int


@dataclasses.dataclass(frozen=True)
class BlockSchema:
    """One cataloged artifact block."""

    name: str
    #: dotted path of the block on a bench line ("" = the line itself /
    #: a block that never rides bench lines)
    block_path: str
    #: docs anchor "docs/FILE.md#Heading text" — the artifact-lockstep
    #: checker requires the heading to exist
    doc: str
    #: ordered validation program: Field / Gate / Rule items
    checks: Tuple = ()
    version_field: Optional[str] = None
    version_ref: Optional[Ref] = None
    #: True: the version field must EQUAL the referenced constant;
    #: False: any int version token is accepted (the validator is
    #: version-tolerant, like roofline's)
    version_exact: bool = False
    #: legacy template for a non-dict block
    not_dict_legacy: Optional[str] = None
    #: "validator": an "error" key exempts inside validate() (knee,
    #: mutation); "curation": the refresher skips error blocks but the
    #: validator itself does not (roofline); "parent": exempt when the
    #: PARENT block carries "error" (calibration under roofline)
    error_exempt: str = "none"
    #: exact key-presence pass run first; ANY miss short-circuits
    #: (mutation's legacy contract) — also the public required list
    missing_order: Tuple[str, ...] = ()
    missing_legacy: Optional[str] = None
    hoists: Tuple[Hoist, ...] = ()
    curated: Tuple[Curated, ...] = ()
    #: repo-relative source files whose dict literals build this block
    emitters: Tuple[str, ...] = ()
    #: key sets identifying a dict literal as this block in an emitter
    fingerprints: Tuple[frozenset, ...] = ()
    #: the label in the refresher's refusal message ("malformed
    #: {refusal_label} block: ...")
    refusal_label: str = ""
    #: participates in the refresher's validate/refuse/hoist loop
    curate: bool = False
    #: participates in the perf_sentinel --lint history sweep
    sweep: bool = False
    #: name of the per-line print segment function (``_SUMMARIES``)
    summary: Optional[str] = None
    #: name of the pre-curation hook (``_PREPARES``) — roofline's
    #: back-derivation for pre-roofline lines
    prepare: Optional[str] = None
    #: legacy validator entry point, "module:function" (the shim)
    validator: str = ""

    @property
    def fields(self) -> Tuple[Field, ...]:
        return tuple(c for c in self.checks if isinstance(c, Field))


# --------------------------------------------------------------------------
# the validation engine
# --------------------------------------------------------------------------
_KIND_TYPES = {
    "int": int,
    "number": (int, float),
    "str": str,
    "bool": bool,
    "dict": dict,
    "list": list,
}


def _resolve_path(obj, path: str) -> Tuple[bool, object]:
    """Walk a dotted path; ``(present, value)`` with the legacy
    ``dict.get`` semantics (a missing/non-dict ancestor reads as an
    absent ``None``)."""
    cur = obj
    parts = path.split(".")
    for part in parts[:-1]:
        if not isinstance(cur, dict) or part not in cur:
            return False, None
        cur = cur[part]
    if not isinstance(cur, dict) or parts[-1] not in cur:
        return False, None
    return True, cur[parts[-1]]


def _fmt(template: Optional[str], normalized: str, style: str,
         **kw) -> str:
    if style == "legacy" and template is not None:
        return template.format(**kw)
    return normalized.format(**kw)


def _type_desc(f: Field, version) -> str:
    if f.kind == "version":
        return f"version {version}" if version is not None \
            else "an int version token"
    if f.choices is not None:
        return "one of {choices}"
    if f.kind == "int":
        if f.ge == 0:
            return "a non-negative int"
        if f.ge == 1:
            return "a positive int"
        if f.ge is not None:
            return f"an int >= {int(f.ge)}"
        if f.gt == 0:
            return "a positive int"
        return "an int"
    if f.kind == "number":
        if f.ge == 0 and f.le == 1:
            return "a number in [0, 1]"
        if f.gt == 0:
            return "a positive number"
        if f.ge == 0:
            return "a non-negative number"
        return "a number"
    if f.kind == "list":
        return "a non-empty list" if f.nonempty else "a list"
    return {"str": "a string", "bool": "a bool",
            "dict": "a dict"}.get(f.kind, "well-formed")


def _check_value(f: Field, value, version) -> bool:
    """True when ``value`` satisfies the field's contract (None already
    handled by the caller)."""
    if f.kind == "version":
        if version is not None:
            return value == version
        return isinstance(value, int)
    if f.choices is not None:
        return value in _resolve(f.choices)
    if f.truthy and not value:
        return False
    t = _KIND_TYPES.get(f.kind)
    if t is not None and not isinstance(value, t):
        return False
    if f.kind == "list" and f.nonempty and not value:
        return False
    if f.kind in ("int", "number"):
        if f.ge is not None and not value >= f.ge:
            return False
        if f.gt is not None and not value > f.gt:
            return False
        if f.le is not None and not value <= f.le:
            return False
    return True


def _field_error(schema: "BlockSchema", f: Field, value, style: str
                 ) -> str:
    version = version_value(schema.name) \
        if (f.kind == "version" and schema.version_exact) else None
    choices = _resolve(f.choices) if f.choices is not None else None
    desc = _type_desc(f, version)
    normalized = ("field {path} must be " + desc + ", got {value!r}")
    return _fmt(f.legacy, normalized, style, value=value, path=f.path,
                leaf=f.leaf, vtype=type(value).__name__,
                choices=choices, version=version)


def validate(name: str, block, style: str = "normalized") -> List[str]:
    """Validate one block against its schema; the list of violations
    (empty = valid).  ``style="legacy"`` renders each migrated
    validator's byte-identical error strings; ``"normalized"`` the
    engine's canonical phrasing."""
    schema = BY_NAME[name]
    if not isinstance(block, dict):
        return [_fmt(schema.not_dict_legacy,
                     "{name} block must be a dict, got {vtype}", style,
                     name=name, vtype=type(block).__name__)]
    errors: List[str] = []
    if schema.error_exempt == "validator" and "error" in block:
        return errors
    if schema.missing_order:
        for key in schema.missing_order:
            if key not in block:
                errors.append(_fmt(schema.missing_legacy,
                                   "missing field: {key}", style,
                                   key=key))
        if errors:
            return errors
    state: Dict[str, str] = {}
    for check in schema.checks:
        if isinstance(check, Gate):
            _, gval = _resolve_path(block, check.path)
            if not gval:
                break
            continue
        if isinstance(check, Rule):
            errors.extend(_RULES[check.name](block, style))
            continue
        f = check
        # a field under an errored (or optional-and-absent) declared
        # ancestor is skipped — the ancestor already told the story
        prefix_dead = False
        for p, st in state.items():
            if f.path.startswith(p + ".") and st in ("error", "absent"):
                prefix_dead = True
                break
        if prefix_dead:
            continue
        present, value = _resolve_path(block, f.path)
        if value is None:
            if f.nullable and f.required and not present:
                # null is allowed but ABSENCE is not: a required
                # nullable field must still be spelled out (mutation's
                # admitted_p99_ms reaches here only when present — its
                # missing_order pass already owns absence)
                errors.append(_fmt(schema.missing_legacy,
                                   "missing field: {key}", style,
                                   key=f.path))
                state[f.path] = "error"
                if f.stop_on_error:
                    return errors
                continue
            if f.nullable or not f.required:
                state[f.path] = "ok" if (present and f.nullable) \
                    else "absent"
                if f.nested is not None and present:
                    errors.extend(validate(f.nested, value, style))
                continue
            errors.append(_field_error(schema, f, value, style))
            state[f.path] = "error"
            if f.stop_on_error:
                return errors
            continue
        if f.nested is not None:
            state[f.path] = "ok"
            errors.extend(validate(f.nested, value, style))
            continue
        if not _check_value(f, value,
                            version_value(schema.name)
                            if (f.kind == "version"
                                and schema.version_exact) else None):
            errors.append(_field_error(schema, f, value, style))
            state[f.path] = "error"
            if f.stop_on_error:
                return errors
            continue
        state[f.path] = "ok"
        if f.kind == "list" and f.element_style:
            errors.extend(
                _ELEMENT_RULES[f.element_style](f, value, style))
    return errors


def version_value(name: str):
    """The resolved version constant a schema's version field is
    checked against (None when the schema declares no version)."""
    schema = BY_NAME[name]
    if schema.version_ref is None:
        return None
    return _resolve(schema.version_ref)


def required_keys(name: str) -> Tuple[str, ...]:
    """The exact key-presence list of a ``missing_order`` schema — the
    public ``MUTATION_REQUIRED`` tuple is derived from this."""
    return BY_NAME[name].missing_order


def element_required(name: str, path: str) -> Tuple[str, ...]:
    """The required per-element keys of a list field — the public
    ``STEP_FIELDS`` tuple is derived from this."""
    for f in BY_NAME[name].fields:
        if f.path == path:
            return f.element_required
    raise KeyError(f"{name} has no list field {path!r}")


# --- element rules --------------------------------------------------------
def _elements_knee_steps(f: Field, steps: list, style: str) -> List[str]:
    errs: List[str] = []
    for i, s in enumerate(steps):
        if not isinstance(s, dict):
            errs.append(f"rate_steps[{i}] must be a dict")
            continue
        for fld in f.element_required:
            if fld not in s:
                errs.append(f"rate_steps[{i}] missing {fld!r}")
    return errs


def _elements_campaign_stages(f: Field, stages: list, style: str
                              ) -> List[str]:
    for s in stages:
        if not isinstance(s, dict) or not s.get("stage") or \
                s.get("status") not in ("ok", "error", "skipped"):
            return [f"malformed stage record {s!r}"]
    return []


_ELEMENT_RULES = {
    "knee_steps": _elements_knee_steps,
    "campaign_stages": _elements_campaign_stages,
}


# --- cross-field rules ----------------------------------------------------
def _rule_knee_consistency(block: dict, style: str) -> List[str]:
    knee = block.get("knee_qps")
    steps = block.get("rate_steps")
    steps = steps if isinstance(steps, list) else []
    if knee is not None and steps:
        ok_steps = [s for s in steps
                    if isinstance(s, dict) and s.get("within_slo")]
        if not ok_steps:
            return ["knee_qps set but no step is within_slo"]
    return []


def _rule_mutation_compactions(block: dict, style: str) -> List[str]:
    # the acceptance bar the block exists to pin: a mixed-traffic line
    # that never swapped proves nothing about swap behavior
    if isinstance(block.get("compactions"), int) \
            and block["compactions"] < 1 \
            and "compactions_waived" not in block:
        return ["compactions must be >= 1 (a mutation line that "
                "never compacted measured nothing; set "
                "compactions_waived to curate one anyway)"]
    return []


_RULES = {
    "knee_consistency": _rule_knee_consistency,
    "mutation_compactions": _rule_mutation_compactions,
}


# --------------------------------------------------------------------------
# hoists, curation, printing
# --------------------------------------------------------------------------
def apply_hoists(rec: dict, block: dict, schema: BlockSchema,
                 scope: str) -> None:
    """Apply one schema's ``scope`` hoists from ``block`` onto ``rec``
    (setdefault semantics — an existing top-level value always wins)."""
    for h in schema.hoists:
        if scope == "bench" and not h.bench:
            continue
        if scope == "refresher" and not h.refresher:
            continue
        _, gval = _resolve_path(block, h.gate or h.src)
        if (not gval) if h.truthy else (gval is None):
            continue
        _, val = _resolve_path(block, h.src)
        if h.numeric and not isinstance(val, (int, float)):
            continue
        rec.setdefault(h.dst, val)


def _block_on_line(rec: dict, schema: BlockSchema):
    cur = rec
    for part in schema.block_path.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur


def _parent_block(rec: dict, schema: BlockSchema):
    parts = schema.block_path.split(".")
    if len(parts) < 2:
        return None
    cur = rec
    for part in parts[:-1]:
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur


def _curation_exempt(rec: dict, schema: BlockSchema, block) -> bool:
    if schema.error_exempt == "curation":
        return isinstance(block, dict) and "error" in block
    if schema.error_exempt == "parent":
        parent = _parent_block(rec, schema)
        return isinstance(parent, dict) and "error" in parent
    return False


def apply_scope_hoists(rec: dict, scope: str = "bench") -> None:
    """The one hoist loop ``bench.py`` runs over its assembled line:
    for every cataloged block present, hoist the declared keys."""
    for schema in CATALOG:
        if not schema.block_path or not schema.hoists:
            continue
        block = _block_on_line(rec, schema)
        if isinstance(block, dict):
            apply_hoists(rec, block, schema, scope)


def curate_line(rec: dict) -> Optional[str]:
    """The refresher's per-line loop: prepare (back-derive), validate
    (legacy error strings — the refusal message is byte-stable),
    and hoist every cataloged block on a fresh curated line.  Returns
    the refusal message for the first malformed block, None when the
    line curates clean."""
    for schema in CATALOG:
        if not schema.curate:
            continue
        needs_validation = True
        if schema.prepare is not None:
            block, needs_validation = _PREPARES[schema.prepare](rec)
        else:
            block = _block_on_line(rec, schema)
        if not isinstance(block, dict):
            continue
        if _curation_exempt(rec, schema, block):
            continue
        if needs_validation:
            errs = validate(schema.name, block, style="legacy")
            if errs:
                return (f"malformed {schema.refusal_label} block: "
                        f"{'; '.join(errs)}")
        apply_hoists(rec, block, schema, "refresher")
    return None


def _prepare_roofline(rec: dict):
    """Pre-roofline lines (measured before the in-bench block existed)
    back-derive a block from their own config fields; a derived block
    is trusted (the model built it), never re-validated — the legacy
    stanza's exact behavior."""
    block = rec.get("roofline")
    if block is not None:
        return block, True
    from knn_tpu.obs import roofline

    derived = roofline.block_for_bench_line(rec)
    if derived is not None:
        rec["roofline"] = dict(derived, derived=True)
        return rec["roofline"], False
    return None, False


_PREPARES = {"roofline_derive": _prepare_roofline}


# --- per-line print segments (the refresher's readout) --------------------
def _summary_roofline(r: dict) -> str:
    # percent-of-roofline + bound class beside the sentinel verdict:
    # the history says "slower than before", the model says "this far
    # from the hardware, bound by THIS"
    if isinstance(r.get("roofline_pct"), (int, float)):
        return (f" roofline={r['roofline_pct'] * 100:.1f}%"
                f"/{r.get('bound_class')}")
    return ""


def _summary_calibration(r: dict) -> str:
    # the analytic model's measured residual, when the line's roofline
    # block carries an applied calibration overlay
    if isinstance(r.get("model_residual_pct"), (int, float)):
        return f" calib={r['model_residual_pct']}%"
    return ""


def _summary_knee(r: dict) -> str:
    # the measured serving knee (loadgen sweep), when the session ran
    # one: max SLO-meeting sustained request rate
    if isinstance(r.get("knee_qps"), (int, float)):
        return f" knee={r['knee_qps']}q/s"
    return ""


def _summary_mutation(r: dict) -> str:
    # the mixed-traffic admitted-read p99 (mutation mode), when the
    # session ran one: the live-mutation tail beside read-only numbers
    if isinstance(r.get("mutation_admitted_p99_ms"), (int, float)):
        return f" mutation={r['mutation_admitted_p99_ms']}ms/p99"
    return ""


def _summary_ivf(r: dict) -> str:
    # the probe-pruned tier (ivf mode), when the session ran one:
    # certified qps beside the measured recall the certificate gates
    if isinstance(r.get("ivf_qps"), (int, float)):
        seg = f" ivf={r['ivf_qps']}q/s"
        if isinstance(r.get("recall_at_k"), (int, float)):
            seg += f"@recall{r['recall_at_k']}"
        return seg
    return ""


def _summary_multihost(r: dict) -> str:
    # the multi-host topology measurement, when the session ran one:
    # host count x DCN merge strategy + host-RAM tier sweep count
    if isinstance(r.get("multihost_hosts"), int):
        return (f" multihost={r['multihost_hosts']}x"
                f"{r.get('multihost_merge')}"
                + (f"/{r['hosttier_sweeps']}sweeps"
                   if isinstance(r.get("hosttier_sweeps"), int) else ""))
    return ""


_SUMMARIES = {
    "roofline": _summary_roofline,
    "calibration": _summary_calibration,
    "knee": _summary_knee,
    "mutation": _summary_mutation,
    "ivf": _summary_ivf,
    "multihost": _summary_multihost,
}


def line_summary(rec: dict) -> str:
    """The per-line artifact readout the refresher prints beside the
    sentinel verdict, one segment per cataloged block, catalog order —
    byte-identical to the six inline f-strings it replaced."""
    return "".join(_SUMMARIES[s.summary](rec) for s in CATALOG
                   if s.summary is not None)


def curated_fields() -> Tuple[Tuple[str, str], ...]:
    """The sentinel's ``CURATED_FIELDS``, derived from the catalog in
    the legacy hand-list's exact order (each block's contribution
    carries its rank)."""
    rows = [c for s in CATALOG for c in s.curated]
    rows.sort(key=lambda c: c.rank)
    return tuple((c.field, c.direction) for c in rows)


def known_keys(name: str) -> set:
    """Every key name a schema legitimizes in an emitter's block
    literal: all declared path segments plus per-element keys — the
    artifact-lockstep checker's resolution set."""
    schema = BY_NAME[name]
    out: set = set()
    for f in schema.fields:
        out.update(f.path.split("."))
        out.update(f.element_required)
        out.update(f.element_optional)
    out.update(schema.missing_order)
    return out


# --------------------------------------------------------------------------
# the history sweep (perf_sentinel --lint)
# --------------------------------------------------------------------------
def sweep_records(records, style: str = "normalized"):
    """Validate every cataloged block on every history record.  Returns
    ``(counts, problems)``: per-schema ``validated`` /
    ``advisory_error`` / ``version_exempt`` counts and a list of
    ``{"schema", "metric", "source", "error"}`` violations.

    Version exemption: a block whose exact-version schema finds an int
    version token STRICTLY below the current constant predates the
    schema — it is counted, not condemned (the validator it was emitted
    under is gone; judging it by today's shape would flag honest
    history).  Version-tolerant schemas (roofline accepts any int
    ``model_version``) validate every round — their validators are
    version-tolerant by construction."""
    counts = {s.name: {"validated": 0, "advisory_error": 0,
                       "version_exempt": 0}
              for s in CATALOG if s.sweep}
    problems: List[dict] = []
    for rec in records:
        if not isinstance(rec, dict):
            continue
        for schema in CATALOG:
            if not schema.sweep:
                continue
            if schema.block_path:
                block = _block_on_line(rec, schema)
                if block is None:
                    continue
            else:
                if schema.name != "bench_line":
                    continue
                block = rec
            if isinstance(block, dict) and "error" in block and \
                    schema.error_exempt == "curation":
                # bench's advisory degradation ({"error": ...}) is a
                # designed outcome, not a lint hit — the refresher's
                # carve-out
                counts[schema.name]["advisory_error"] += 1
                continue
            if _curation_exempt(rec, schema, block):
                continue
            if schema.version_exact and schema.version_field and \
                    isinstance(block, dict):
                tok = block.get(schema.version_field)
                if isinstance(tok, int) and \
                        tok < version_value(schema.name):
                    counts[schema.name]["version_exempt"] += 1
                    continue
            counts[schema.name]["validated"] += 1
            for err in validate(schema.name, block, style=style):
                problems.append({
                    "schema": schema.name,
                    "label": schema.refusal_label or schema.name,
                    "metric": rec.get("metric"),
                    "source": rec.get("_source"),
                    "error": err,
                })
    return counts, problems


def sweep_multichip(repo_dir: str):
    """Validate every checked-in ``MULTICHIP_r*.json`` driver record
    against its schema.  Returns ``(n_validated, problems)``."""
    n = 0
    problems: List[dict] = []
    for path in sorted(glob.glob(
            os.path.join(repo_dir, "MULTICHIP_r*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            problems.append({"schema": "multichip_record",
                             "label": "multichip",
                             "metric": None,
                             "source": os.path.basename(path),
                             "error": f"unreadable: {e}"})
            continue
        n += 1
        for err in validate("multichip_record", doc):
            problems.append({"schema": "multichip_record",
                             "label": "multichip", "metric": None,
                             "source": os.path.basename(path),
                             "error": err})
    return n, problems


# --------------------------------------------------------------------------
# THE CATALOG
# --------------------------------------------------------------------------
_RL = "knn_tpu.obs.roofline"
_CAL = "knn_tpu.obs.calibrate"
_XO = "knn_tpu.parallel.crossover"

#: sentinel verdict vocabulary (bench embeds "error" on a failed
#: verdict computation — a designed degradation, part of the contract)
SENTINEL_VERDICTS = ("ok", "warn", "regress", "no_baseline", "error")

CATALOG: Tuple[BlockSchema, ...] = (
    # --- bench top-level lines -----------------------------------------
    BlockSchema(
        name="bench_line",
        block_path="",
        doc="docs/ANALYSIS.md#The artifact-schema catalog",
        emitters=("bench.py", "scripts/refresh_bench_artifacts.py",
                  "knn_tpu/campaign.py"),
        fingerprints=(frozenset({"metric", "value", "unit"}),),
        sweep=True,
        curated=(
            Curated("value", "higher", 0),
            Curated("device_phase_qps", "higher", 1),
            Curated("serving_sustained_qps", "higher", 2),
            Curated("mfu", "higher", 3),
            Curated("mfu_device", "higher", 4),
        ),
        checks=(
            Field("metric", "str", required=True),
            Field("value", "number", nullable=True),
            Field("unit", "str", nullable=True),
            Field("vs_baseline", "number", nullable=True),
            Field("mode", "str", nullable=True),
            Field("device_phase_qps", "number", nullable=True),
            Field("serving_sustained_qps", "number", nullable=True),
            Field("serving_latency_ms", "dict", nullable=True),
            Field("obs_overhead_pct", "number", nullable=True),
            # the artifact blocks themselves (each validated under its
            # own schema; declared here so the emitters' line literals
            # resolve)
            Field("roofline", "any"),
            Field("loadgen_knee", "any"),
            Field("mutation", "any"),
            Field("ivf", "any"),
            Field("pq", "any"),
            Field("join", "any"),
            Field("quality", "any"),
            Field("multihost", "any"),
            Field("campaign", "any"),
            Field("sentinel", "any"),
            Field("tuning", "any"),
            # the hoisted keys (every Hoist dst is a declared line key)
            Field("roofline_pct", "number", nullable=True),
            Field("bound_class", "str", nullable=True),
            Field("roofline_estimated", "bool", nullable=True),
            Field("model_residual_pct", "number", nullable=True),
            Field("knee_qps", "number", nullable=True),
            Field("mutation_admitted_p99_ms", "number", nullable=True),
            Field("ivf_qps", "number", nullable=True),
            Field("bytes_streamed_ratio", "number", nullable=True),
            Field("join_rows_per_s", "number", nullable=True),
            Field("audit_recall_at_k", "number", nullable=True),
            Field("multihost_hosts", "int", nullable=True),
            Field("multihost_merge", "str", nullable=True),
            Field("multihost_qps", "number", nullable=True),
            Field("hosttier_sweeps", "int", nullable=True),
            # soundness gate + recall provenance
            Field("pallas_gate_ok", "bool", nullable=True),
            Field("gate_note", "str", nullable=True),
            Field("gate_queries", "int", nullable=True),
            Field("gate_rows", "int", nullable=True),
            Field("gate_stats", "dict", nullable=True),
            Field("session_gate_ok", "bool", nullable=True,
                  emit_note="stamped by the round-5 session driver "
                            "(2026-07-31, deleted in PR 21); declared "
                            "so history lines of that round sweep "
                            "clean, no live emitter writes it"),
            Field("recall_at_k", "number", nullable=True),
            Field("recall_unverified", "bool", nullable=True),
            Field("recall_below_one", "bool", nullable=True),
            # run shape / environment
            Field("compute_dtype", "str", nullable=True),
            Field("metric_fn", "str", nullable=True),
            Field("runs", "int", nullable=True),
            Field("qps_std", "number", nullable=True),
            Field("qps_labels_only", "number", nullable=True),
            Field("mfu", "number", nullable=True),
            Field("mfu_device", "number", nullable=True),
            Field("mfu_reason", "str", nullable=True),
            Field("peak_flops_assumed", "number", nullable=True),
            Field("selectors", "dict", nullable=True),
            Field("cpu_baseline_qps", "number", nullable=True),
            Field("cpu_baseline_cached", "bool", nullable=True),
            Field("cpu_queries", "int", nullable=True),
            Field("cpu_per_query_s", "number", nullable=True),
            Field("devices", "int", nullable=True),
            Field("device_kind", "str", nullable=True),
            Field("backend", "str", nullable=True),
            Field("cpu_baseline_cache_file", "str", nullable=True),
            Field("cpu_baseline_error", "str", nullable=True),
            Field("batch", "int", nullable=True),
            Field("train_tile", "int", nullable=True),
            Field("pallas_knobs", "dict", nullable=True),
            Field("approx_knobs", "dict", nullable=True),
            Field("precision", "str", nullable=True),
            Field("quant_bound_max", "number", nullable=True),
            Field("quant_scales_dtype", "str", nullable=True),
            Field("quant_bound_error", "str", nullable=True),
            Field("error", "str", nullable=True),
            # curation provenance (stamped by the refresher)
            Field("measured_round", "int", nullable=True),
            Field("measured_at_commit", "str", nullable=True),
            Field("stale", "bool", nullable=True),
        ),
    ),
    # --- roofline -------------------------------------------------------
    BlockSchema(
        name="roofline",
        block_path="roofline",
        doc="docs/PERF.md#Roofline model",
        validator="knn_tpu.obs.roofline:validate_block",
        emitters=("knn_tpu/obs/roofline.py", "bench.py"),
        fingerprints=(frozenset({"model_version", "terms"}),),
        version_field="model_version",
        version_ref=Ref(_RL, "MODEL_VERSION"),
        version_exact=False,
        not_dict_legacy="roofline block is {vtype}, not dict",
        error_exempt="curation",
        refusal_label="roofline",
        curate=True,
        sweep=True,
        summary="roofline",
        prepare="roofline_derive",
        hoists=(
            Hoist("roofline_pct", "roofline_pct"),
            # the refresher pairs bound_class with a non-null pct;
            # bench hoists it whenever the block names one
            Hoist("bound_class", "bound_class", gate="roofline_pct",
                  bench=False),
            Hoist("bound_class", "bound_class", truthy=True,
                  refresher=False),
            Hoist("estimated", "roofline_estimated", truthy=True,
                  refresher=False),
        ),
        curated=(Curated("roofline_pct", "higher", 5),),
        checks=(
            Field("model_version", "version", required=True,
                  legacy="missing/non-int model_version"),
            Field("bound_class", required=True,
                  choices=Ref(_RL, "BOUND_CLASSES"),
                  legacy="bound_class {value!r} not in {choices}"),
            Field("ceiling_qps", "number", required=True, gt=0,
                  legacy="ceiling_qps {value!r} is not a positive "
                         "number"),
            Field("roofline_pct", "number",
                  legacy="roofline_pct {value!r} is neither null nor "
                         "a number"),
            Field("terms", "dict", required=True,
                  legacy="missing terms breakdown"),
            Field("terms.hbm.time_s", "number", required=True, ge=0,
                  legacy="terms.hbm.time_s missing or negative"),
            Field("terms.mxu.time_s", "number", required=True, ge=0,
                  legacy="terms.mxu.time_s missing or negative"),
            Field("terms.vpu_select.time_s", "number", required=True,
                  ge=0,
                  legacy="terms.vpu_select.time_s missing or negative"),
            # the MODEL_VERSION-4 cross-host merge term: present only
            # on multi-host blocks, and then every field must hold —
            # a malformed DCN claim would poison curated baselines
            Field("terms.dcn", "dict",
                  legacy="terms.dcn is not a dict"),
            Field("terms.dcn.time_s", "number", required=True, ge=0,
                  legacy="terms.dcn.time_s missing or negative"),
            Field("terms.dcn.bytes", "int", required=True, ge=0,
                  legacy="terms.dcn.bytes missing or negative"),
            Field("terms.dcn.hosts", "int", required=True, ge=2,
                  legacy="terms.dcn.hosts must be an int >= 2"),
            Field("terms.dcn.strategy", required=True,
                  choices=Ref(_XO, "STRATEGIES"),
                  legacy="terms.dcn.strategy {value!r} not in "
                         "{choices}"),
            # the MODEL_VERSION-7 join h2d term: present only on join
            # blocks (join_cost_model), and then it must be priced
            Field("terms.h2d", "dict",
                  legacy="terms.h2d is not a dict"),
            Field("terms.h2d.time_s", "number", required=True, ge=0,
                  legacy="terms.h2d.time_s missing or negative"),
            Field("terms.h2d.bytes", "int", required=True, ge=0,
                  legacy="terms.h2d.bytes missing or negative"),
            # the join-shape annotations join_cost_model stamps
            Field("join", "any"),
            # MODEL_VERSION 3 blocks carry an explicit calibration
            # verdict; pre-calibration history (v1/v2) legitimately
            # lacks it, but one that IS present must be well-formed
            Field("calibration", nested="calibration"),
            # declared, engine-filled / advisory keys (unconstrained)
            Field("selector", "any"),
            Field("device_kind", "any"),
            Field("estimated", "any"),
            Field("peaks", "any"),
            Field("config", "any"),
            Field("measured_qps", "any"),
            Field("ceiling_qps_analytic", "any"),
            Field("select_overlapped", "any"),
            Field("term_times_s", "any"),
            Field("term_times_calibrated_s", "any"),
            Field("roofline_pct_e2e", "any"),
            Field("error", "any"),
            Field("derived", "any",
                  emit_note="stamped by the back-derivation hook as a "
                            "dict() keyword (dict(block, derived=True))"
                            ", never a key literal"),
        ),
    ),
    # --- calibration (nested under roofline) ----------------------------
    BlockSchema(
        name="calibration",
        block_path="roofline.calibration",
        doc="docs/PERF.md#Calibration & measured ceilings",
        validator="knn_tpu.obs.calibrate:validate_calibration",
        emitters=("knn_tpu/obs/roofline.py", "knn_tpu/obs/calibrate.py"),
        fingerprints=(frozenset({"applied", "factors"}),),
        not_dict_legacy="calibration is {vtype}, not dict",
        error_exempt="parent",
        refusal_label="calibration",
        curate=True,
        sweep=True,
        summary="calibration",
        hoists=(
            Hoist("model_residual_pct", "model_residual_pct",
                  gate="applied", truthy=True, numeric=True),
        ),
        curated=(Curated("model_residual_pct", "lower", 7),),
        checks=(
            # an absent overlay must still be EXPLICIT: applied is a
            # bool, never missing-and-implied
            Field("applied", "bool", required=True, stop_on_error=True,
                  legacy="calibration.applied {value!r} is not a bool"),
            Gate("applied"),
            Field("factors", "dict", required=True,
                  legacy="applied calibration missing factors dict"),
            Field("factors.hbm", "number", required=True, gt=0,
                  legacy="calibration factor {leaf} {value!r} is not "
                         "a positive number"),
            Field("factors.mxu", "number", required=True, gt=0,
                  legacy="calibration factor {leaf} {value!r} is not "
                         "a positive number"),
            Field("factors.vpu_select", "number", required=True, gt=0,
                  legacy="calibration factor {leaf} {value!r} is not "
                         "a positive number"),
            Field("source", required=True,
                  choices=Ref(_CAL, "SOURCES"),
                  legacy="calibration source {value!r} not in "
                         "{choices}"),
            Field("model_residual_pct", "number", required=True,
                  legacy="calibration.model_residual_pct {value!r} is "
                         "not a number"),
            # provenance the overlay carries (unconstrained)
            Field("method", "any"),
            Field("age_s", "any"),
            Field("samples", "any"),
            Field("term_residual_pct", "any"),
            Field("measured_at", "any"),
            Field("provenance", "any"),
            Field("note", "any"),
            Field("error", "any"),
        ),
    ),
    # --- campaign --------------------------------------------------------
    BlockSchema(
        name="campaign",
        block_path="campaign",
        doc="docs/PERF.md#Calibration & measured ceilings",
        validator="knn_tpu.obs.calibrate:validate_campaign_block",
        emitters=("knn_tpu/campaign.py",),
        fingerprints=(frozenset({"campaign_version", "stages"}),),
        version_field="campaign_version",
        version_ref=Ref("knn_tpu.campaign", "CAMPAIGN_VERSION"),
        version_exact=False,
        not_dict_legacy="campaign block is {vtype}, not dict",
        refusal_label="campaign",
        curate=True,
        sweep=True,
        checks=(
            Field("campaign_version", "version", required=True,
                  legacy="missing/non-int campaign_version"),
            Field("arm", "any", required=True, truthy=True,
                  legacy="missing arm name"),
            Field("stages", "list", required=True, nonempty=True,
                  element_style="campaign_stages",
                  element_required=("stage", "status"),
                  element_optional=("error", "winner", "winner_ms",
                                    "cache_key", "rehearse_note",
                                    "qps", "device_s", "source",
                                    "model_residual_pct", "factors",
                                    "store", "entry_key", "sentinel",
                                    "artifact", "note", "gates",
                                    "trace_dir", "events", "errors"),
                  legacy="missing stages list"),
            Field("rehearse", "bool", required=True,
                  legacy="missing/non-bool rehearse flag"),
            Field("round", "any"),
        ),
    ),
    # --- loadgen knee ----------------------------------------------------
    BlockSchema(
        name="loadgen_knee",
        block_path="loadgen_knee",
        doc="docs/serving.md#Load generation, admission control & "
            "brownout",
        validator="knn_tpu.loadgen.knee:validate_knee_block",
        emitters=("knn_tpu/loadgen/knee.py",),
        fingerprints=(frozenset({"rate_steps", "slo_p99_ms"}),
                      frozenset({"rate_qps", "within_slo"})),
        version_field="version",
        version_ref=Ref("knn_tpu.loadgen.knee", "BLOCK_VERSION"),
        version_exact=True,
        not_dict_legacy="knee block must be a dict, got {vtype}",
        error_exempt="validator",
        refusal_label="loadgen_knee",
        curate=True,
        sweep=True,
        summary="knee",
        hoists=(Hoist("knee_qps", "knee_qps"),),
        curated=(Curated("knee_qps", "higher", 6),),
        checks=(
            Field("version", "version", required=True,
                  legacy="version must be {version}, got {value!r}"),
            Field("slo_p99_ms", "number", required=True, gt=0,
                  legacy="slo_p99_ms must be a positive number, got "
                         "{value!r}"),
            Field("rate_steps", "list", required=True, nonempty=True,
                  element_style="knee_steps",
                  element_required=("rate_qps", "offered", "ok",
                                    "achieved_qps", "shed_fraction",
                                    "within_slo"),
                  element_optional=("rejected", "shed", "errors",
                                    "offered_qps", "admitted_p50_ms",
                                    "admitted_p95_ms",
                                    "admitted_p99_ms", "per_tenant",
                                    "slowest", "empty_schedule"),
                  legacy="rate_steps must be a non-empty list"),
            Field("knee_qps", "number",
                  legacy="knee_qps must be a number or null, got "
                         "{value!r}"),
            Rule("knee_consistency"),
            Field("knee_rate_qps", "any"),
        ),
    ),
    # --- mutation --------------------------------------------------------
    BlockSchema(
        name="mutation",
        block_path="mutation",
        doc="docs/serving.md#The write path",
        validator="knn_tpu.index.artifact:validate_mutation_block",
        emitters=("bench.py",),
        fingerprints=(frozenset({"mutation_version", "write_mix"}),),
        version_field="mutation_version",
        version_ref=Ref("knn_tpu.index.artifact", "MUTATION_VERSION"),
        version_exact=True,
        not_dict_legacy="mutation block must be a dict, got {vtype}",
        error_exempt="validator",
        refusal_label="mutation",
        curate=True,
        sweep=True,
        summary="mutation",
        missing_order=("mutation_version", "write_mix", "rate_qps",
                       "duration_s", "admitted_p99_ms", "compactions",
                       "epoch", "reads", "writes",
                       "slo_breach_transitions"),
        missing_legacy="missing {key!r}",
        hoists=(Hoist("admitted_p99_ms", "mutation_admitted_p99_ms"),),
        curated=(Curated("mutation_admitted_p99_ms", "lower", 8),),
        checks=(
            Field("mutation_version", "version", required=True,
                  legacy="mutation_version must be {version}, got "
                         "{value!r}"),
            Field("write_mix", "dict", required=True,
                  legacy="write_mix must be a dict, got {value!r}"),
            Field("write_mix.insert_fraction", "number", required=True,
                  ge=0, le=1,
                  legacy="write_mix.{leaf} must be a number in [0, 1],"
                         " got {value!r}"),
            Field("write_mix.delete_fraction", "number", required=True,
                  ge=0, le=1,
                  legacy="write_mix.{leaf} must be a number in [0, 1],"
                         " got {value!r}"),
            Field("rate_qps", "number", required=True, gt=0,
                  legacy="{path} must be a positive number, got "
                         "{value!r}"),
            Field("duration_s", "number", required=True, gt=0,
                  legacy="{path} must be a positive number, got "
                         "{value!r}"),
            Field("admitted_p99_ms", "number", required=True,
                  nullable=True, ge=0,
                  legacy="admitted_p99_ms must be a non-negative "
                         "number or null, got {value!r}"),
            Field("compactions", "int", required=True, ge=0,
                  legacy="{path} must be a non-negative int, got "
                         "{value!r}"),
            Field("epoch", "int", required=True, ge=0,
                  legacy="{path} must be a non-negative int, got "
                         "{value!r}"),
            Field("slo_breach_transitions", "int", required=True, ge=0,
                  legacy="{path} must be a non-negative int, got "
                         "{value!r}"),
            Rule("mutation_compactions"),
            Field("reads", "dict", required=True,
                  legacy="{path} must be a dict, got {value!r}"),
            Field("writes", "dict", required=True,
                  legacy="{path} must be a dict, got {value!r}"),
            Field("index_rows", "any"),
            Field("admitted_p50_ms", "any"),
            Field("achieved_qps", "any"),
            Field("swap_seconds_max", "any"),
            Field("validation_errors", "any"),
            Field("error", "any"),
            Field("compactions_waived", "any",
                  emit_note="operator escape hatch named only by the "
                            "validator's refusal message; never "
                            "machine-emitted"),
        ),
    ),
    # --- ivf -------------------------------------------------------------
    BlockSchema(
        name="ivf",
        block_path="ivf",
        doc="docs/PERF.md#IVF tier & certified recall",
        validator="knn_tpu.ivf.artifact:validate_ivf_block",
        emitters=("bench.py",),
        fingerprints=(frozenset({"ivf_version", "nprobe"}),),
        version_field="ivf_version",
        version_ref=Ref("knn_tpu.ivf.artifact", "IVF_VERSION"),
        version_exact=True,
        not_dict_legacy="ivf block must be a dict, got {vtype}",
        error_exempt="validator",
        refusal_label="ivf",
        curate=True,
        sweep=True,
        summary="ivf",
        missing_order=("ivf_version", "ncentroids", "nprobe", "queries",
                       "k", "probe_fraction", "recall_at_k",
                       "fallback_rate", "bytes_streamed_ratio", "qps"),
        missing_legacy="missing {key!r}",
        hoists=(
            Hoist("qps", "ivf_qps"),
            Hoist("bytes_streamed_ratio", "bytes_streamed_ratio"),
        ),
        curated=(
            Curated("recall_at_k", "higher", 9),
            Curated("ivf_qps", "higher", 10),
            # the compressed-tier headline: fraction of the brute-force
            # byte stream actually touched — the number the PQ
            # arm exists to shrink, so the sentinel baselines it
            # lower-is-better
            Curated("bytes_streamed_ratio", "lower", 11),
        ),
        checks=(
            Field("ivf_version", "version", required=True,
                  legacy="ivf_version must be {version}, got "
                         "{value!r}"),
            Field("ncentroids", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("nprobe", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("queries", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("k", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("probe_fraction", "number", required=True, ge=0,
                  le=1,
                  legacy="{path} must be a number in [0, 1], got "
                         "{value!r}"),
            Field("recall_at_k", "number", required=True, ge=0, le=1,
                  legacy="{path} must be a number in [0, 1], got "
                         "{value!r}"),
            Field("fallback_rate", "number", required=True, ge=0,
                  le=1,
                  legacy="{path} must be a number in [0, 1], got "
                         "{value!r}"),
            Field("bytes_streamed_ratio", "number", required=True,
                  ge=0,
                  legacy="{path} must be a non-negative number, got "
                         "{value!r}"),
            Field("qps", "number", required=True, nullable=True, ge=0,
                  legacy="qps must be a non-negative number or null, "
                         "got {value!r}"),
            Field("selector", "any"),
            Field("fallback_queries", "any"),
            Field("certified_queries", "any"),
            Field("genuine_misses", "any"),
            Field("epoch", "any"),
            Field("compactions", "any"),
            Field("validation_errors", "any"),
            Field("error", "any"),
        ),
    ),
    # --- pq (codebook-geometry provenance of precision="pq" lines) -------
    BlockSchema(
        name="pq",
        block_path="pq",
        doc="docs/PERF.md#Compressed tier: PQ",
        validator="knn_tpu.ops.pq_artifact:validate_pq_block",
        emitters=("bench.py",),
        fingerprints=(frozenset({"pq_version", "dsub"}),),
        version_field="pq_version",
        version_ref=Ref("knn_tpu.ops.pq_artifact", "PQ_VERSION"),
        version_exact=True,
        not_dict_legacy="pq block must be a dict, got {vtype}",
        error_exempt="validator",
        refusal_label="pq",
        curate=True,
        sweep=True,
        missing_order=("pq_version", "dsub", "ncodes", "nsub",
                       "lut_bytes", "bound_max", "queries"),
        missing_legacy="missing {key!r}",
        checks=(
            Field("pq_version", "version", required=True,
                  legacy="pq_version must be {version}, got {value!r}"),
            Field("dsub", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("ncodes", "int", required=True, ge=2,
                  legacy="{path} must be an int >= 2, got {value!r}"),
            Field("nsub", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("lut_bytes", "int", required=True, ge=0,
                  legacy="{path} must be a non-negative int, got "
                         "{value!r}"),
            # the certified bound's worst case over the bench query
            # set; null when the bound computation itself degraded
            # (the block then carries the error string)
            Field("bound_max", "number", required=True, nullable=True,
                  ge=0,
                  legacy="bound_max must be a non-negative number or "
                         "null, got {value!r}"),
            Field("queries", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("error", "any"),
        ),
    ),
    # --- multihost -------------------------------------------------------
    BlockSchema(
        name="multihost",
        block_path="multihost",
        doc="docs/PERF.md#Multi-host merge & host-RAM tier",
        validator="knn_tpu.parallel.crossover:validate_multihost_block",
        emitters=("bench.py",),
        fingerprints=(frozenset({"hosts", "merge"}),
                      frozenset({"sweeps", "budget_bytes",
                                 "segment_rows"})),
        not_dict_legacy="multihost block is {vtype}, not dict",
        refusal_label="multihost",
        curate=True,
        sweep=True,
        summary="multihost",
        hoists=(
            Hoist("hosts", "multihost_hosts", truthy=True,
                  bench=False),
            Hoist("merge.dcn.strategy", "multihost_merge", truthy=True,
                  bench=False),
            Hoist("hosttier.sweeps", "hosttier_sweeps", truthy=True),
        ),
        checks=(
            Field("hosts", "int", required=True, ge=1,
                  legacy="hosts {value!r} is not a positive int"),
            Field("chips_per_host", "int", ge=1,
                  legacy="chips_per_host {value!r} is not a positive "
                         "int"),
            Field("merge", "dict", required=True,
                  legacy="missing merge breakdown"),
            Field("merge.intra", "dict",
                  legacy="merge.intra is not a dict"),
            Field("merge.intra.strategy", required=True,
                  choices=Ref(_XO, "STRATEGIES"),
                  legacy="merge.intra.strategy {value!r} not in "
                         "{choices}"),
            Field("merge.intra.source", required=True,
                  choices=Ref(_XO, "SOURCES"),
                  legacy="merge.intra.source {value!r} not in "
                         "{choices}"),
            Field("merge.dcn", "dict",
                  legacy="merge.dcn is not a dict"),
            Field("merge.dcn.strategy", required=True,
                  choices=Ref(_XO, "STRATEGIES"),
                  legacy="merge.dcn.strategy {value!r} not in "
                         "{choices}"),
            Field("merge.dcn.source", required=True,
                  choices=Ref(_XO, "SOURCES"),
                  legacy="merge.dcn.source {value!r} not in "
                         "{choices}"),
            Field("dcn_merge_bytes", "int", ge=0,
                  legacy="dcn_merge_bytes {value!r} is not a "
                         "non-negative int"),
            Field("hosttier", "dict",
                  legacy="hosttier is not a dict"),
            Field("hosttier.sweeps", "int", required=True, ge=1,
                  legacy="hosttier.sweeps {value!r} is not a positive "
                         "int"),
            Field("hosttier.budget_bytes", "int", required=True, gt=0,
                  legacy="hosttier.budget_bytes {value!r} is not a "
                         "positive int"),
            Field("hosttier.segment_rows", "int", required=True, ge=1,
                  legacy="hosttier.segment_rows {value!r} is not a "
                         "positive int"),
            Field("hosttier.bytes_per_sweep", "any"),
            Field("hosttier.sweep_walls_s", "any"),
            Field("hosttier.qps", "any"),
            Field("error", "any"),
        ),
    ),
    # --- bulk kNN-join ---------------------------------------------------
    BlockSchema(
        name="join",
        block_path="join",
        doc="docs/PERF.md#Bulk kNN-join (MODEL_VERSION 7)",
        validator="knn_tpu.join.artifact:validate_join_block",
        emitters=("bench.py",),
        fingerprints=(frozenset({"join_version", "superblock_rows"}),),
        version_field="join_version",
        version_ref=Ref("knn_tpu.join.artifact", "JOIN_VERSION"),
        version_exact=True,
        not_dict_legacy="join block must be a dict, got {vtype}",
        error_exempt="validator",
        refusal_label="join",
        curate=True,
        sweep=True,
        missing_order=("join_version", "mode", "rows", "k",
                       "superblock_rows", "depth", "order",
                       "superblocks", "db_segments", "dispatches",
                       "rows_per_s", "overlap_ratio"),
        missing_legacy="missing {key!r}",
        hoists=(Hoist("rows_per_s", "join_rows_per_s"),),
        # the join headline the sentinel baselines: offline rows/s,
        # higher is better — the number the superblock amortization
        # exists to raise
        curated=(Curated("join_rows_per_s", "higher", 12),),
        checks=(
            Field("join_version", "version", required=True,
                  legacy="join_version must be {version}, got "
                         "{value!r}"),
            Field("mode", required=True,
                  choices=Ref("knn_tpu.join.engine", "JOIN_MODES"),
                  legacy="mode {value!r} not in {choices}"),
            Field("rows", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("k", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("superblock_rows", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("depth", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("order", required=True,
                  choices=("query_major", "db_major"),
                  legacy="order {value!r} not in {choices}"),
            Field("superblocks", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("db_segments", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("dispatches", "int", required=True, ge=1,
                  legacy="{path} must be a positive int, got "
                         "{value!r}"),
            Field("rows_per_s", "number", required=True, nullable=True,
                  ge=0,
                  legacy="rows_per_s must be a non-negative number or "
                         "null, got {value!r}"),
            # stream mode measures the dispatch-timeline overlap; the
            # certified loop reports null (it has no pipeline)
            Field("overlap_ratio", "number", required=True,
                  nullable=True, ge=0, le=1,
                  legacy="overlap_ratio must be a number in [0, 1] or "
                         "null, got {value!r}"),
            Field("baseline_rows_per_s", "any"),
            Field("speedup_vs_serving", "any"),
            Field("wall_s", "any"),
            Field("plan", "any"),
            Field("fallback_queries", "any"),
            Field("validation_errors", "any"),
            Field("error", "any"),
        ),
    ),
    # --- quality (shadow audit) ------------------------------------------
    BlockSchema(
        name="quality",
        block_path="quality",
        doc="docs/OBSERVABILITY.md#Quality observability",
        emitters=("bench.py",),
        fingerprints=(frozenset({"quality_version",
                                 "audit_recall_at_k"}),),
        version_field="quality_version",
        version_ref=Ref("knn_tpu.obs.audit", "QUALITY_VERSION"),
        version_exact=True,
        not_dict_legacy="quality block must be a dict, got {vtype}",
        error_exempt="validator",
        refusal_label="quality",
        curate=True,
        sweep=True,
        missing_order=("quality_version", "audit_rate",
                       "audit_sampled_requests",
                       "audit_replayed_queries",
                       "audit_deficient_queries",
                       "audit_dropped_records", "audit_recall_at_k"),
        missing_legacy="missing {key!r}",
        hoists=(Hoist("audit_recall_at_k", "audit_recall_at_k"),),
        # the quality headline the sentinel baselines: shadow-audited
        # recall@k against the f64 exact oracle, higher is better —
        # the number the whole audit pipeline exists to watch
        curated=(Curated("audit_recall_at_k", "higher", 13),),
        checks=(
            Field("quality_version", "version", required=True,
                  legacy="quality_version must be {version}, got "
                         "{value!r}"),
            Field("audit_rate", "number", required=True, ge=0, le=1,
                  legacy="audit_rate must be a number in [0, 1], got "
                         "{value!r}"),
            Field("audit_sampled_requests", "int", required=True,
                  ge=0,
                  legacy="{path} must be a non-negative int, got "
                         "{value!r}"),
            Field("audit_replayed_queries", "int", required=True,
                  ge=0,
                  legacy="{path} must be a non-negative int, got "
                         "{value!r}"),
            Field("audit_deficient_queries", "int", required=True,
                  ge=0,
                  legacy="{path} must be a non-negative int, got "
                         "{value!r}"),
            Field("audit_dropped_records", "int", required=True, ge=0,
                  legacy="{path} must be a non-negative int, got "
                         "{value!r}"),
            # null until the first replay lands (all sampled records
            # still queued or dropped)
            Field("audit_recall_at_k", "number", required=True,
                  nullable=True, ge=0, le=1,
                  legacy="audit_recall_at_k must be a number in "
                         "[0, 1] or null, got {value!r}"),
            Field("audit_rank_displacement_p99", "number",
                  nullable=True),
            Field("audit_distance_rel_error_p99", "number",
                  nullable=True),
            Field("wall_s", "any"),
            Field("error", "any"),
        ),
    ),
    # --- fleet observability (cross-host merge) --------------------------
    BlockSchema(
        name="fleet",
        block_path="fleet",
        doc="docs/OBSERVABILITY.md#Fleet observability",
        emitters=("knn_tpu/obs/fleet.py", "bench.py"),
        fingerprints=(frozenset({"fleet_version", "member_count"}),),
        version_field="fleet_version",
        version_ref=Ref("knn_tpu.obs.fleet", "FLEET_VERSION"),
        version_exact=True,
        not_dict_legacy="fleet block must be a dict, got {vtype}",
        error_exempt="validator",
        refusal_label="fleet",
        sweep=True,
        # the merged cross-host headline: how many members summed in,
        # how loudly partial the merge was, who the straggler is
        checks=(
            Field("fleet_version", "version", required=True),
            Field("catalog_version", "str", required=True),
            Field("member_count", "int", required=True, ge=0),
            Field("expected_members", "int", required=True, ge=0),
            Field("unreachable_count", "int", required=True, ge=0),
            Field("skewed_count", "int", required=True, ge=0),
            Field("partial", "bool", required=True),
            Field("staleness_s", "number", required=True, ge=0),
            Field("straggler_host", "int", nullable=True),
            Field("straggler_gap_s", "number", nullable=True, ge=0),
            Field("stitched_requests", "int", required=True, ge=0),
            Field("slo_breached", "int", required=True, ge=0),
            Field("wall_s", "any"),
            Field("error", "any"),
        ),
    ),
    # --- sentinel verdict ------------------------------------------------
    BlockSchema(
        name="sentinel",
        block_path="sentinel",
        doc="docs/OBSERVABILITY.md#Regression sentinel",
        emitters=("knn_tpu/obs/sentinel.py", "bench.py"),
        fingerprints=(frozenset({"verdict", "baseline_key"}),),
        sweep=True,
        checks=(
            Field("verdict", "str", required=True,
                  choices=SENTINEL_VERDICTS),
            Field("baseline_key", "str", nullable=True),
            Field("fields", "dict", nullable=True),
            Field("error", "str", nullable=True),
        ),
    ),
    # --- tuning-cache entries ---------------------------------------------
    BlockSchema(
        name="tuning_cache_entry",
        block_path="",
        doc="docs/PERF.md#Streaming kernel & autotuner",
        emitters=("knn_tpu/tuning/autotune.py",),
        fingerprints=(frozenset({"knobs", "winner", "timings_ms"}),),
        checks=(
            Field("knobs", "dict", required=True),
            Field("winner", "str", required=True),
            Field("winner_ms", "number", nullable=True),
            Field("timings_ms", "dict", required=True),
            Field("errors", "dict", nullable=True),
            Field("roofline_per_candidate", "dict", nullable=True),
            Field("gate", "str", required=True),
            # which knob-grid regime timed the entry: "latency" (the
            # serving default) or "throughput" (the bulk-join grid,
            # cache-keyed with a |throughput suffix)
            Field("profile", "str", nullable=True),
            Field("runs", "int", required=True, ge=1),
            Field("n_queries", "int", required=True, ge=1),
            Field("margin", "int", nullable=True),
            Field("device_kind", "str", nullable=True),
            Field("backend", "str", nullable=True),
            Field("jax_version", "str", nullable=True),
            Field("measured_at", "str", nullable=True),
            Field("pruning", "dict", nullable=True),
            Field("vmem", "dict", nullable=True),
            # the IVF autotuner's (autotune_ivf) entry rides the same
            # shape: its per-candidate probe/fallback stats and the
            # selector its searches ran under
            Field("selector", "str", nullable=True),
            Field("stats_per_candidate", "dict", nullable=True),
            Field("roofline", nested="roofline"),
            Field("roofline_pct", "number", nullable=True),
            Field("bound_class", "str", nullable=True),
            Field("trace_dir", "str", nullable=True),
            Field("cached", "bool", nullable=True),
            Field("cache_key", "str", nullable=True),
        ),
    ),
    # --- MULTICHIP driver records -----------------------------------------
    BlockSchema(
        name="multichip_record",
        block_path="",
        doc="docs/ANALYSIS.md#The artifact-schema catalog",
        emitters=(),
        checks=(
            Field("n_devices", "int", required=True, ge=1),
            Field("rc", "int", required=True),
            Field("ok", "bool", required=True),
            Field("skipped", "bool", required=True),
            Field("tail", "str", required=True, nullable=True),
        ),
    ),
)

#: name -> schema, for the engine and the checker
BY_NAME: Dict[str, BlockSchema] = {s.name: s for s in CATALOG}


def _validate_catalog() -> None:
    seen_versions: Dict[str, str] = {}
    for s in CATALOG:
        if len(BY_NAME) != len(CATALOG):
            raise ValueError("duplicate schema names")
        if s.version_field:
            if s.version_ref is None:
                raise ValueError(
                    f"{s.name}: version_field without version_ref")
            owner = seen_versions.setdefault(s.version_field, s.name)
            if owner != s.name:
                raise ValueError(
                    f"version token {s.version_field!r} consumed by "
                    f"both {owner} and {s.name}")
        if "#" not in s.doc:
            raise ValueError(f"{s.name}: doc anchor must be "
                             f"'file#heading'")


_validate_catalog()
