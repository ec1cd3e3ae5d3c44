"""The artifact-schema registry — ONE declarative catalog for every
artifact block the repo emits or validates, and the generic engine that
validates them.

Hand-rolled ``validate_*_block`` functions (knee, mutation,
multihost, ...) were each one more hand-checked
contract between an emitter (knee.py / fleet.py / ...), its
validator and the docs — the class of drift the
switch/metric catalogs killed elsewhere.  This module applies the same
cure:

- :data:`CATALOG` — one :class:`BlockSchema` per artifact block
  (loadgen_knee, mutation, ivf, pq, multihost,
  join, quality, fleet), each declaring its
  fields (types/required/ranges), version token, emitters +
  fingerprints (for the ``artifact-lockstep`` checker), and docs
  anchor;
- :func:`validate` — the generic engine behind the public
  ``validate_*`` entry points (one-line shims over it).
  ``style="legacy"`` reproduces each migrated validator's error strings
  BYTE-IDENTICALLY (their refusal tests unmodified);
  ``style="normalized"`` is the engine's one canonical phrasing
  (``missing field: X`` / ``field X must be ..., got ...``).

Everything here is stdlib-only and jax-free.  Version tokens and choice
sets stay in their owning modules (``BLOCK_VERSION`` lives with the
knee block that bumps it) and are referenced lazily through :class:`Ref` —
the catalog declares, it never duplicates.

Adding a block is ONE schema entry here (docs/ANALYSIS.md "Adding a
bench block"): the validator and the ``artifact-lockstep`` checker
follow from the declaration.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List, Optional, Tuple

__all__ = [
    "CATALOG",
    "BY_NAME",
    "BlockSchema",
    "Field",
    "Rule",
    "Ref",
    "validate",
    "version_value",
    "required_keys",
    "element_required",
    "known_keys",
]


# --------------------------------------------------------------------------
# declaration primitives
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Ref:
    """A lazy pointer to a constant in its owning module (the version
    token, a choice tuple).  The catalog references the single source
    of truth instead of copying it — a version token still lives with
    the module that bumps it."""

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
    kind: str = "any"  # any|int|number|str|bool|dict|list|version
    required: bool = False
    nullable: bool = False
    ge: Optional[float] = None
    gt: Optional[float] = None
    le: Optional[float] = None
    choices: object = None  # tuple or Ref
    legacy: Optional[str] = None
    stop_on_error: bool = False
    nonempty: bool = False
    element_style: str = ""  # "knee_steps"
    element_required: Tuple[str, ...] = ()
    element_optional: Tuple[str, ...] = ()
    emit_note: str = ""

    @property
    def leaf(self) -> str:
        return self.path.rsplit(".", 1)[-1]


@dataclasses.dataclass(frozen=True)
class Rule:
    """A named cross-field rule (see ``_RULES``) — the residue a
    per-field declaration cannot express (a knee claimed with no
    SLO-meeting step, a mutation line that never compacted)."""

    name: str


@dataclasses.dataclass(frozen=True)
class BlockSchema:
    """One cataloged artifact block."""

    name: str
    #: docs anchor "docs/FILE.md#Heading text" — the artifact-lockstep
    #: checker requires the heading to exist
    doc: str
    #: ordered validation program: Field / Rule items
    checks: Tuple = ()
    #: a "version" field must EQUAL the constant ``version_ref`` names
    version_field: Optional[str] = None
    version_ref: Optional[Ref] = None
    #: legacy template for a non-dict block
    not_dict_legacy: Optional[str] = None
    #: an "error" key exempts the block inside validate() (knee,
    #: mutation: a degraded run's block says so and is not judged)
    error_exempt: bool = False
    #: exact key-presence pass run first; ANY miss short-circuits
    #: (mutation's legacy contract) — also the public required list
    missing_order: Tuple[str, ...] = ()
    missing_legacy: Optional[str] = None
    #: repo-relative source files whose dict literals build this block
    emitters: Tuple[str, ...] = ()
    #: key sets identifying a dict literal as this block in an emitter
    fingerprints: Tuple[frozenset, ...] = ()
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
        return f"version {version}"
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
        return value == version
    if f.choices is not None:
        return value in _resolve(f.choices)
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
    version = version_value(schema.name) if f.kind == "version" else None
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
    if schema.error_exempt and "error" in block:
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
                continue
            errors.append(_field_error(schema, f, value, style))
            state[f.path] = "error"
            if f.stop_on_error:
                return errors
            continue
        if not _check_value(f, value,
                            version_value(schema.name)
                            if f.kind == "version" else None):
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


_ELEMENT_RULES = {
    "knee_steps": _elements_knee_steps,
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
# THE CATALOG
# --------------------------------------------------------------------------
_XO = "knn_tpu.parallel.crossover"

CATALOG: Tuple[BlockSchema, ...] = (
    # --- loadgen knee ----------------------------------------------------
    BlockSchema(
        name="loadgen_knee",
        doc="docs/serving.md#Load generation, admission control & "
            "brownout",
        validator="knn_tpu.loadgen.knee:validate_knee_block",
        emitters=("knn_tpu/loadgen/knee.py",),
        fingerprints=(frozenset({"rate_steps", "slo_p99_ms"}),
                      frozenset({"rate_qps", "within_slo"})),
        version_field="version",
        version_ref=Ref("knn_tpu.loadgen.knee", "BLOCK_VERSION"),
        not_dict_legacy="knee block must be a dict, got {vtype}",
        error_exempt=True,
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
        doc="docs/serving.md#The write path",
        validator="knn_tpu.index.artifact:validate_mutation_block",
        fingerprints=(frozenset({"mutation_version", "write_mix"}),),
        version_field="mutation_version",
        version_ref=Ref("knn_tpu.index.artifact", "MUTATION_VERSION"),
        not_dict_legacy="mutation block must be a dict, got {vtype}",
        error_exempt=True,
        missing_order=("mutation_version", "write_mix", "rate_qps",
                       "duration_s", "admitted_p99_ms", "compactions",
                       "epoch", "reads", "writes",
                       "slo_breach_transitions"),
        missing_legacy="missing {key!r}",
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
        doc="docs/PERF.md#IVF tier & certified recall",
        validator="knn_tpu.ivf.artifact:validate_ivf_block",
        fingerprints=(frozenset({"ivf_version", "nprobe"}),),
        version_field="ivf_version",
        version_ref=Ref("knn_tpu.ivf.artifact", "IVF_VERSION"),
        not_dict_legacy="ivf block must be a dict, got {vtype}",
        error_exempt=True,
        missing_order=("ivf_version", "ncentroids", "nprobe", "queries",
                       "k", "probe_fraction", "recall_at_k",
                       "fallback_rate", "bytes_streamed_ratio", "qps"),
        missing_legacy="missing {key!r}",
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
        doc="docs/PERF.md#Compressed tier: PQ",
        validator="knn_tpu.ops.pq_artifact:validate_pq_block",
        fingerprints=(frozenset({"pq_version", "dsub"}),),
        version_field="pq_version",
        version_ref=Ref("knn_tpu.ops.pq_artifact", "PQ_VERSION"),
        not_dict_legacy="pq block must be a dict, got {vtype}",
        error_exempt=True,
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
        doc="docs/PERF.md#Multi-host merge & host-RAM tier",
        validator="knn_tpu.parallel.crossover:validate_multihost_block",
        fingerprints=(frozenset({"hosts", "merge"}),
                      frozenset({"sweeps", "budget_bytes",
                                 "segment_rows"})),
        not_dict_legacy="multihost block is {vtype}, not dict",
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
        doc="docs/PERF.md#Bulk kNN-join",
        validator="knn_tpu.join.artifact:validate_join_block",
        fingerprints=(frozenset({"join_version", "superblock_rows"}),),
        version_field="join_version",
        version_ref=Ref("knn_tpu.join.artifact", "JOIN_VERSION"),
        not_dict_legacy="join block must be a dict, got {vtype}",
        error_exempt=True,
        missing_order=("join_version", "mode", "rows", "k",
                       "superblock_rows", "depth", "order",
                       "superblocks", "db_segments", "dispatches",
                       "rows_per_s", "overlap_ratio"),
        missing_legacy="missing {key!r}",
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
        doc="docs/OBSERVABILITY.md#Quality observability",
        fingerprints=(frozenset({"quality_version",
                                 "audit_recall_at_k"}),),
        version_field="quality_version",
        version_ref=Ref("knn_tpu.obs.audit", "QUALITY_VERSION"),
        not_dict_legacy="quality block must be a dict, got {vtype}",
        error_exempt=True,
        missing_order=("quality_version", "audit_rate",
                       "audit_sampled_requests",
                       "audit_replayed_queries",
                       "audit_deficient_queries",
                       "audit_dropped_records", "audit_recall_at_k"),
        missing_legacy="missing {key!r}",
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
        doc="docs/OBSERVABILITY.md#Fleet observability",
        emitters=("knn_tpu/obs/fleet.py",),
        fingerprints=(frozenset({"fleet_version", "member_count"}),),
        version_field="fleet_version",
        version_ref=Ref("knn_tpu.obs.fleet", "FLEET_VERSION"),
        not_dict_legacy="fleet block must be a dict, got {vtype}",
        error_exempt=True,
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
            Field("error", "any"),
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
