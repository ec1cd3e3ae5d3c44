"""The artifact-schema registry (knn_tpu.analysis.artifacts,
docs/ANALYSIS.md "The artifact-schema catalog"): the generic validation
engine's byte-identical legacy strings behind the shims, the
normalized canonical style, the derived step/required lists,
and the ``artifact-lockstep`` checker — known-good fixtures plus the
seeded regressions (an emitter key missing from its schema, a declared
field no emitter names, a lost docs anchor), each flipping ``cli lint``
red.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import pytest

from knn_tpu import analysis
from knn_tpu.analysis import artifacts as A

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_tree(root, files):
    for rel, text in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(text))


def run_on(root, checker="artifact-lockstep"):
    return analysis.run(str(root), names=[checker])


# --- reference blocks ----------------------------------------------------
GOOD_KNEE = {
    "version": 1, "slo_p99_ms": 50.0,
    "rate_steps": [{"rate_qps": 10.0, "offered": 5, "ok": 5,
                    "achieved_qps": 9.0, "shed_fraction": 0.0,
                    "within_slo": True}],
    "knee_qps": 9.0, "knee_rate_qps": 10.0,
}

GOOD_MUTATION = {
    "mutation_version": 1,
    "write_mix": {"insert_fraction": 0.1, "delete_fraction": 0.05},
    "rate_qps": 200.0, "duration_s": 2.0,
    "admitted_p99_ms": 12.5, "compactions": 2, "epoch": 2,
    "reads": {"offered": 380, "ok": 380},
    "writes": {"insert": {"ok": 40}},
    "slo_breach_transitions": 0,
}

GOOD_MULTIHOST = {
    "hosts": 2, "chips_per_host": 2,
    "merge": {"intra": {"strategy": "allgather", "source": "measured"},
              "dcn": {"strategy": "ring", "source": "env"}},
    "dcn_merge_bytes": 1024,
    "hosttier": {"sweeps": 3, "budget_bytes": 4096,
                 "segment_rows": 64},
}


# --- the engine: legacy style is byte-identical --------------------------
def test_legacy_style_reproduces_hand_validator_strings_exactly():
    """The migrated validators' exact strings, pinned byte-for-byte —
    the shims' refusal tests elsewhere assert substrings; this is the
    stronger contract the tentpole claims."""
    assert A.validate("loadgen_knee", {"version": 99}, style="legacy") \
        == ["version must be 1, got 99",
            "slo_p99_ms must be a positive number, got None",
            "rate_steps must be a non-empty list"]
    bad = dict(GOOD_MUTATION, write_mix={"insert_fraction": 2.0,
                                         "delete_fraction": 0.0})
    assert A.validate("mutation", bad, style="legacy") == \
        ["write_mix.insert_fraction must be a number in [0, 1], "
         "got 2.0"]
    assert A.validate("multihost", {"hosts": 0, "merge": {}},
                      style="legacy") == \
        ["hosts 0 is not a positive int"]


def test_shims_are_the_engine():
    """Each legacy entry point returns exactly the engine's legacy-style
    output, on good and bad blocks alike."""
    from knn_tpu.index.artifact import validate_mutation_block
    from knn_tpu.loadgen.knee import validate_knee_block
    from knn_tpu.parallel.crossover import validate_multihost_block

    cases = [
        ("loadgen_knee", validate_knee_block,
         [GOOD_KNEE, {"error": "boom"},
          dict(GOOD_KNEE, rate_steps=[{"rate_qps": 1.0}])]),
        ("mutation", validate_mutation_block,
         [GOOD_MUTATION, {"error": "boom"},
          dict(GOOD_MUTATION, compactions=0)]),
        ("multihost", validate_multihost_block,
         [GOOD_MULTIHOST, "nope"]),
    ]
    for name, fn, blocks in cases:
        for b in blocks:
            assert fn(b) == A.validate(name, b, style="legacy"), (name, b)


def test_normalized_style_is_one_uniform_phrasing():
    """The canonical engine style: one phrasing for every block — the
    normalization the migrated validators' divergent styles
    fold into (the compat shims keep the historical strings)."""
    errs = A.validate("mutation", {}, style="normalized")
    assert errs[0] == "missing field: mutation_version"
    bad = dict(GOOD_MULTIHOST, hosts=0,
               merge={"intra": {"strategy": "vibes", "source": "measured"},
                      "dcn": GOOD_MULTIHOST["merge"]["dcn"]})
    assert A.validate("multihost", bad, style="normalized")[0] == \
        "field hosts must be a positive int, got 0"
    errs = A.validate("multihost", dict(bad, hosts=2),
                      style="normalized")
    assert any(e.startswith("field merge.intra.strategy must be one of")
               for e in errs), errs
    # the legacy strings for the same block diverge in style — that is
    # exactly what the shims preserve
    assert A.validate("multihost", bad, style="legacy") == [
        "hosts 0 is not a positive int",
        "merge.intra.strategy 'vibes' not in ('allgather', 'ring')"]


def test_version_tokens_resolve_and_are_owned_once():
    owners = {}
    for s in A.CATALOG:
        if s.version_field:
            assert s.version_field not in owners, s.name
            owners[s.version_field] = s.name
            assert isinstance(A.version_value(s.name), int)
    assert owners == {"version": "loadgen_knee",
                      "mutation_version": "mutation",
                      "ivf_version": "ivf",
                      "pq_version": "pq",
                      "join_version": "join",
                      "quality_version": "quality",
                      "fleet_version": "fleet"}


def test_catalog_refuses_duplicate_version_tokens():
    knee = A.BY_NAME["loadgen_knee"]
    dup = dataclasses.replace(A.BY_NAME["mutation"], name="mutation2",
                              version_field="version",
                              version_ref=knee.version_ref)
    import knn_tpu.analysis.artifacts as mod

    saved_cat, saved_by = mod.CATALOG, mod.BY_NAME
    try:
        mod.CATALOG = saved_cat + (dup,)
        mod.BY_NAME = {s.name: s for s in mod.CATALOG}
        with pytest.raises(ValueError, match="consumed by"):
            mod._validate_catalog()
    finally:
        mod.CATALOG, mod.BY_NAME = saved_cat, saved_by


# --- derived public lists -------------------------------------------------
def test_step_fields_and_mutation_required_derived():
    from knn_tpu.index.artifact import MUTATION_REQUIRED
    from knn_tpu.loadgen.knee import STEP_FIELDS

    assert STEP_FIELDS == ("rate_qps", "offered", "ok", "achieved_qps",
                           "shed_fraction", "within_slo")
    assert STEP_FIELDS == A.element_required("loadgen_knee",
                                             "rate_steps")
    assert MUTATION_REQUIRED == (
        "mutation_version", "write_mix", "rate_qps", "duration_s",
        "admitted_p99_ms", "compactions", "epoch", "reads", "writes",
        "slo_breach_transitions")
    assert MUTATION_REQUIRED == A.required_keys("mutation")


def test_required_nullable_field_must_be_present(monkeypatch):
    """required=True nullable=True means the key may be null but never
    ABSENT — a truncated record missing 'tail' must not validate
    (review finding: absence used to read as null)."""
    schema = A.BlockSchema(
        name="record", doc="docs/ANALYSIS.md#x",
        checks=(A.Field("rc", "int", required=True),
                A.Field("tail", "str", required=True, nullable=True)))
    monkeypatch.setitem(A.BY_NAME, "record", schema)
    rec = {"rc": 0}
    assert A.validate("record", rec) == ["missing field: tail"]
    assert A.validate("record", dict(rec, tail=None)) == []
    assert A.validate("record", dict(rec, tail="")) == []


# --- the artifact-lockstep checker ----------------------------------------
def test_checker_green_on_repo():
    rep = run_on(REPO)
    assert rep.ok, rep.render_text()


def test_checker_green_on_empty_fixture_tree(tmp_path):
    write_tree(tmp_path, {"knn_tpu/ok.py": "x = 1\n"})
    rep = run_on(tmp_path)
    assert rep.ok, [f.message for f in rep.findings]


def test_seeded_regression_unschemad_emitter_key(tmp_path):
    """ISSUE regression 1: an emitter writing a key no schema declares
    into a cataloged block literal flips the checker red."""
    write_tree(tmp_path, {"knn_tpu/loadgen/knee.py": '''
        block = {
            "version": 1,
            "slo_p99_ms": 50.0,
            "rate_steps": [],
            "totally_undeclared_key": 42,
        }
        '''})
    rep = run_on(tmp_path)
    assert not rep.ok
    hits = [f for f in rep.findings
            if f.symbol == "totally_undeclared_key"]
    assert hits and "no artifact schema declares it" in hits[0].message
    assert hits[0].path == "knn_tpu/loadgen/knee.py"


def test_checker_emitted_check_is_not_vacuous_for_the_knee_block():
    """The catalog must never list itself as an emitter — every
    declared field is a string constant in artifacts.py, which would
    satisfy the emitted check by construction (review finding)."""
    knee = A.BY_NAME["loadgen_knee"]
    for s in A.CATALOG:
        assert "knn_tpu/analysis/artifacts.py" not in s.emitters
    # a genuinely-phantom field (no emit_note, named by no emitter)
    # goes red on the real tree
    phantom = A.Field("totally_phantom_knee_key", "any")
    patched = dataclasses.replace(
        knee, checks=knee.checks + (phantom,))
    import knn_tpu.analysis.artifacts as mod

    saved_cat, saved_by = mod.CATALOG, mod.BY_NAME
    try:
        mod.CATALOG = tuple(patched if s.name == "loadgen_knee" else s
                            for s in saved_cat)
        mod.BY_NAME = {s.name: s for s in mod.CATALOG}
        rep = run_on(REPO)
    finally:
        mod.CATALOG, mod.BY_NAME = saved_cat, saved_by
    assert any(f.symbol == "totally_phantom_knee_key"
               and "phantom schema field" in f.message
               for f in rep.findings)


def test_checker_flags_missing_docs_anchor(tmp_path):
    """A docs file that exists but lost the block's heading is a
    finding — anchors only bind when their file is present, so fixture
    trees stay green."""
    write_tree(tmp_path, {"docs/PERF.md": "# PERF\n\nno headings\n"})
    rep = run_on(tmp_path)
    assert not rep.ok
    assert any("docs anchor" in f.message and f.symbol == "join"
               for f in rep.findings)


def test_cli_lint_json_exit_code_contract_for_artifact_lockstep(
        tmp_path):
    """The subprocess exit-code contract: the seeded emitter-key
    regression flips ``cli lint --json`` to exit 1 with the finding in
    the JSON report; the checker rides --list."""
    write_tree(tmp_path, {"knn_tpu/loadgen/knee.py": '''
        block = {"rate_steps": [], "slo_p99_ms": 1.0,
                 "rogue_key": 1}
        '''})
    proc = subprocess.run(
        [sys.executable, "-m", "knn_tpu.cli", "lint", "--json",
         "--root", str(tmp_path), "--checker", "artifact-lockstep"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["checkers"] == ["artifact-lockstep"]
    assert any(f["symbol"] == "rogue_key" for f in payload["findings"])
    proc = subprocess.run(
        [sys.executable, "-m", "knn_tpu.cli", "lint", "--list"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0
    assert "artifact-lockstep" in proc.stdout
