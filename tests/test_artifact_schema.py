"""The artifact-schema registry (knn_tpu.analysis.artifacts,
docs/ANALYSIS.md "The artifact-schema catalog"): the generic validation
engine's byte-identical legacy strings behind the six shims, the
normalized canonical style, the derived sentinel/step/required lists,
the table-driven hoist + curation loops, the perf_sentinel history
sweep (version exemption, advisory-error carve-out, MULTICHIP records),
and the ``artifact-lockstep`` checker — known-good fixtures plus the
three seeded regressions the ISSUE names (an emitter key missing from
its schema, a declared hoist the refresher doesn't perform, a curated
field absent from the sentinel), each flipping ``cli lint`` red.
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
def good_roofline(qps=50.0):
    from knn_tpu.obs import roofline

    return roofline.attribute(
        roofline.pallas_cost_model(n=1000, d=16, k=5, nq=8), qps)


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

GOOD_CAMPAIGN = {
    "campaign_version": 1, "arm": "int8_fused", "round": 6,
    "rehearse": True,
    "stages": [{"stage": "tune", "status": "ok"}],
}


# --- the engine: legacy style is byte-identical --------------------------
def test_legacy_style_reproduces_hand_validator_strings_exactly():
    """The migrated validators' exact strings, pinned byte-for-byte —
    the shims' refusal tests elsewhere assert substrings; this is the
    stronger contract the tentpole claims."""
    assert A.validate("roofline", "nope", style="legacy") == \
        ["roofline block is str, not dict"]
    assert A.validate("roofline", {"bound_class": "gpu_bound"},
                      style="legacy")[0] == "missing/non-int model_version"
    from knn_tpu.obs.roofline import BOUND_CLASSES

    assert (f"bound_class 'gpu_bound' not in {BOUND_CLASSES}"
            in A.validate("roofline", {"bound_class": "gpu_bound"},
                          style="legacy"))
    assert A.validate("calibration", None, style="legacy") == \
        ["calibration is NoneType, not dict"]
    assert A.validate("calibration", {"applied": "yes"},
                      style="legacy") == \
        ["calibration.applied 'yes' is not a bool"]
    assert A.validate("campaign", {"arm": "a"}, style="legacy") == [
        "missing/non-int campaign_version",
        "missing stages list",
        "missing/non-bool rehearse flag",
    ]
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
    from knn_tpu.obs import calibrate, roofline
    from knn_tpu.parallel.crossover import validate_multihost_block

    cases = [
        ("roofline", roofline.validate_block,
         [good_roofline(), {}, dict(good_roofline(), terms="x")]),
        ("calibration", calibrate.validate_calibration,
         [{"applied": False}, {"applied": True},
          {"applied": True, "factors": {"hbm": 1, "mxu": 1,
                                        "vpu_select": 1},
           "source": "host_phase", "model_residual_pct": 2.0}]),
        ("campaign", calibrate.validate_campaign_block,
         [GOOD_CAMPAIGN, {"arm": ""}]),
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
    normalization the calibration/campaign validators' divergent styles
    fold into (the compat shims keep the historical strings)."""
    errs = A.validate("mutation", {}, style="normalized")
    assert errs[0] == "missing field: mutation_version"
    errs = A.validate("calibration",
                      {"applied": True, "factors": "x",
                       "source": "vibes", "model_residual_pct": "m"},
                      style="normalized")
    assert any(e.startswith("field factors must be a dict")
               for e in errs)
    assert any(e.startswith("field source must be one of")
               for e in errs)
    # the legacy strings for the same block diverge in style — that is
    # exactly what the shims preserve
    legacy = A.validate("calibration",
                        {"applied": True, "factors": "x",
                         "source": "vibes", "model_residual_pct": "m"},
                        style="legacy")
    assert "applied calibration missing factors dict" in legacy


def test_version_tokens_resolve_and_are_owned_once():
    owners = {}
    for s in A.CATALOG:
        if s.version_field:
            assert s.version_field not in owners, s.name
            owners[s.version_field] = s.name
            assert isinstance(A.version_value(s.name), int)
    assert owners == {"model_version": "roofline",
                      "campaign_version": "campaign",
                      "version": "loadgen_knee",
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
def test_sentinel_curated_fields_derived_in_legacy_order():
    from knn_tpu.obs.sentinel import CURATED_FIELDS

    assert CURATED_FIELDS == A.curated_fields()
    assert A.curated_fields() == (
        ("value", "higher"),
        ("device_phase_qps", "higher"),
        ("serving_sustained_qps", "higher"),
        ("mfu", "higher"),
        ("mfu_device", "higher"),
        ("roofline_pct", "higher"),
        ("knee_qps", "higher"),
        ("model_residual_pct", "lower"),
        ("mutation_admitted_p99_ms", "lower"),
        ("recall_at_k", "higher"),
        ("ivf_qps", "higher"),
        ("bytes_streamed_ratio", "lower"),
        ("join_rows_per_s", "higher"),
        ("audit_recall_at_k", "higher"),
    )


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


def test_tuning_cache_entry_schema_accepts_a_real_entry_shape():
    entry = {
        "knobs": {"kernel": "streaming"}, "winner": "defaults",
        "winner_ms": 1.2, "timings_ms": {"defaults": 1.2},
        "errors": {}, "roofline_per_candidate": {},
        "gate": "bitwise-vs-reference", "runs": 2, "n_queries": 8,
        "margin": 4, "device_kind": "cpu", "backend": "cpu",
        "jax_version": "0.9.0", "measured_at": "2026-08-04T00:00:00Z",
        "roofline": good_roofline(), "roofline_pct": 0.5,
        "bound_class": "hbm_bound",
    }
    assert A.validate("tuning_cache_entry", entry) == []
    assert A.validate("tuning_cache_entry", dict(entry, runs=0))


# --- hoists + curation ----------------------------------------------------
def test_bench_scope_hoists_match_legacy_inline_stanzas():
    rl = dict(good_roofline(), estimated=True,
              calibration={"applied": True,
                           "factors": {"hbm": 1, "mxu": 1,
                                       "vpu_select": 1},
                           "source": "host_phase",
                           "model_residual_pct": -3.2})
    line = {"metric": "m", "roofline": rl,
            "loadgen_knee": GOOD_KNEE, "mutation": GOOD_MUTATION,
            "multihost": GOOD_MULTIHOST}
    A.apply_scope_hoists(line, scope="bench")
    assert line["roofline_pct"] == rl["roofline_pct"]
    assert line["bound_class"] == rl["bound_class"]
    assert line["roofline_estimated"] is True
    assert line["model_residual_pct"] == -3.2
    assert line["knee_qps"] == 9.0
    assert line["mutation_admitted_p99_ms"] == 12.5
    assert line["hosttier_sweeps"] == 3
    # refresher-only hoists must NOT fire in bench scope
    assert "multihost_hosts" not in line
    assert "multihost_merge" not in line


def test_curate_line_validates_hoists_and_refuses():
    rec = {"metric": "m", "value": 1.0, "roofline": good_roofline(),
           "loadgen_knee": GOOD_KNEE, "mutation": GOOD_MUTATION,
           "multihost": GOOD_MULTIHOST, "campaign": GOOD_CAMPAIGN}
    assert A.curate_line(rec) is None
    assert rec["knee_qps"] == 9.0
    assert rec["multihost_hosts"] == 2
    assert rec["multihost_merge"] == "ring"
    assert rec["hosttier_sweeps"] == 3
    assert rec["mutation_admitted_p99_ms"] == 12.5
    assert rec["roofline_pct"] == rec["roofline"]["roofline_pct"]
    # an unapplied calibration hoists nothing
    assert "model_residual_pct" not in rec
    bad = {"metric": "m", "roofline": {"bound_class": "gpu_bound"}}
    msg = A.curate_line(bad)
    assert msg.startswith("malformed roofline block: ")
    bad = {"metric": "m", "mutation": dict(GOOD_MUTATION,
                                           compactions=0)}
    assert A.curate_line(bad).startswith("malformed mutation block: ")
    # advisory error blocks are the refresher's carve-out, not refusals
    assert A.curate_line({"metric": "m",
                          "roofline": {"error": "model gap"}}) is None


def test_curate_line_back_derives_pre_roofline_lines():
    rec = {"metric": "knn_qps_sift1m_n1000000_d128_k100",
           "value": 6110.0, "backend": "tpu",
           "mode": "certified_pallas", "device_phase_qps": 24199.3,
           "device_kind": "TPU v5 lite", "devices": 1, "batch": 4096,
           "pallas_knobs": {}}
    assert A.curate_line(rec) is None
    assert rec["roofline"]["derived"] is True
    assert rec["bound_class"] == "hbm_bound"


def test_line_summary_matches_legacy_print_segments():
    rec = {"roofline_pct": 0.206, "bound_class": "hbm_bound",
           "model_residual_pct": 1.5, "knee_qps": 171.3,
           "mutation_admitted_p99_ms": 14.2, "multihost_hosts": 2,
           "multihost_merge": "ring", "hosttier_sweeps": 4}
    assert A.line_summary(rec) == (
        " roofline=20.6%/hbm_bound calib=1.5% knee=171.3q/s"
        " mutation=14.2ms/p99 multihost=2xring/4sweeps")
    assert A.line_summary({}) == ""


# --- the history sweep ----------------------------------------------------
def test_sweep_records_counts_and_violations():
    recs = [
        {"metric": "m1", "value": 1.0, "backend": "tpu",
         "roofline": good_roofline(), "loadgen_knee": GOOD_KNEE,
         "sentinel": {"verdict": "ok", "baseline_key": "k",
                      "fields": {}}},
        {"metric": "m2", "value": 1.0,
         "roofline": {"error": "model gap"}},
        {"metric": "m3", "value": 1.0,
         "mutation": dict(GOOD_MUTATION, compactions=-1)},
        # an exact-version schema exempts a pre-schema round's block
        {"metric": "m4", "value": 1.0,
         "loadgen_knee": {"version": 0, "anything": "goes"}},
        {"metric": "m5", "value": 1.0,
         "sentinel": {"verdict": "vibes"}},
    ]
    counts, problems = A.sweep_records(recs)
    assert counts["roofline"] == {"validated": 1, "advisory_error": 1,
                                  "version_exempt": 0}
    assert counts["loadgen_knee"]["validated"] == 1
    assert counts["loadgen_knee"]["version_exempt"] == 1
    assert counts["mutation"]["validated"] == 1
    assert counts["sentinel"]["validated"] == 2
    assert counts["bench_line"]["validated"] == 5
    # the malformed mutation block trips both the int-range check and
    # the compactions>=1 rule; the bogus sentinel verdict trips one
    bad_schemas = sorted(p["schema"] for p in problems)
    assert bad_schemas == ["mutation", "mutation", "sentinel"]


def test_required_nullable_field_must_be_present():
    """required=True nullable=True means the key may be null but never
    ABSENT — a truncated MULTICHIP driver record missing 'tail' must
    not sweep clean (review finding: absence used to read as null)."""
    rec = {"n_devices": 2, "rc": 0, "ok": True, "skipped": False}
    assert A.validate("multichip_record", rec) == \
        ["missing field: tail"]
    assert A.validate("multichip_record", dict(rec, tail=None)) == []
    assert A.validate("multichip_record", dict(rec, tail="")) == []


def test_sweep_multichip_validates_driver_records(tmp_path):
    (tmp_path / "MULTICHIP_r01.json").write_text(json.dumps(
        {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
         "tail": ""}))
    (tmp_path / "MULTICHIP_r02.json").write_text(json.dumps(
        {"n_devices": 0, "rc": "x"}))
    n, problems = A.sweep_multichip(str(tmp_path))
    assert n == 2
    assert problems and all(p["schema"] == "multichip_record"
                            for p in problems)


def test_perf_sentinel_lint_flags_bad_history_and_exempts_old(tmp_path):
    script = os.path.join(REPO, "scripts", "perf_sentinel.py")

    def lint(lines):
        (tmp_path / "TPU_BENCH_r01.jsonl").write_text(
            "".join(json.dumps(r) + "\n" for r in lines))
        return subprocess.run(
            [sys.executable, script, "--lint", "--repo",
             str(tmp_path)], capture_output=True, text=True,
            timeout=120)

    base = {"metric": "knn_qps_x_n1000_d16_k5", "value": 10.0,
            "backend": "tpu", "measured_round": 1,
            "measured_at_commit": "abc"}
    r = lint([dict(base, mutation=GOOD_MUTATION),
              dict(base, multihost=GOOD_MULTIHOST)])
    assert r.returncode == 0, r.stderr
    assert "mutation blocks: OK (1 validated)" in r.stdout
    assert "multihost blocks: OK (1 validated)" in r.stdout
    r = lint([dict(base, mutation=dict(GOOD_MUTATION, epoch=-1))])
    assert r.returncode == 1
    assert "mutation block" in r.stderr
    # a pre-schema round's exact-version block is exempt, and loudly so
    r = lint([dict(base, loadgen_knee={"version": 0})])
    assert r.returncode == 0, r.stderr
    assert "1 version-exempt" in r.stdout


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
    write_tree(tmp_path, {"bench.py": '''
        block = {
            "mutation_version": 1,
            "write_mix": {"insert_fraction": 0.1,
                          "delete_fraction": 0.0},
            "totally_undeclared_key": 42,
        }
        '''})
    rep = run_on(tmp_path)
    assert not rep.ok
    hits = [f for f in rep.findings
            if f.symbol == "totally_undeclared_key"]
    assert hits and "no artifact schema declares it" in hits[0].message
    assert hits[0].path == "bench.py"


def test_seeded_regression_refresher_drops_a_hoist(tmp_path):
    """ISSUE regression 2: a hand-rolled refresher that performs every
    hoist except the declared knee_qps goes red (a catalog-speaking
    refresher is green by construction)."""
    dsts = sorted({h.dst for s in A.CATALOG for h in s.hoists
                   if h.refresher} - {"knee_qps"})
    hand = ("import json\n"
            + "".join(f'_H{i} = "{d}"\n' for i, d in enumerate(dsts)))
    write_tree(tmp_path,
               {"scripts/refresh_bench_artifacts.py": hand})
    rep = run_on(tmp_path)
    assert not rep.ok
    hits = [f for f in rep.findings if f.symbol == "knee_qps"]
    assert hits and "not performed by the refresher" in hits[0].message
    # the catalog-driven refresher passes
    write_tree(tmp_path, {"scripts/refresh_bench_artifacts.py": '''
        from knn_tpu.analysis import artifacts
        '''})
    rep2 = run_on(tmp_path)
    assert rep2.ok, [f.message for f in rep2.findings]


def test_seeded_regression_sentinel_misses_curated_field(tmp_path):
    """ISSUE regression 3: a hand-listed sentinel CURATED_FIELDS
    missing a catalog-declared curated field goes red; deriving from
    the catalog is green."""
    kept = [c for c in A.curated_fields()
            if c[0] != "model_residual_pct"]
    hand = "CURATED_FIELDS = " + repr(tuple(kept)) + "\n"
    write_tree(tmp_path, {"knn_tpu/obs/sentinel.py": hand})
    rep = run_on(tmp_path)
    assert not rep.ok
    hits = [f for f in rep.findings
            if f.symbol == "model_residual_pct"]
    assert hits and "absent from the sentinel" in hits[0].message
    write_tree(tmp_path, {"knn_tpu/obs/sentinel.py": '''
        from knn_tpu.analysis.artifacts import curated_fields

        CURATED_FIELDS = curated_fields()
        '''})
    rep2 = run_on(tmp_path)
    assert rep2.ok, [f.message for f in rep2.findings]


def test_checker_emitted_check_is_not_vacuous_for_bench_line():
    """The catalog must never list itself as a bench_line emitter —
    every declared field is a string constant in artifacts.py, which
    would satisfy the emitted check by construction (review finding).
    Hoist destinations are the one sanctioned exemption: the
    catalog-driven hoist loops write them, and check 3 proves the
    refresher runs those loops."""
    bench_line = A.BY_NAME["bench_line"]
    assert os.path.join("knn_tpu", "analysis", "artifacts.py").replace(
        os.sep, "/") not in bench_line.emitters
    # a genuinely-phantom field (not a hoist dst, no emit_note, named
    # by no emitter) goes red on the real tree
    phantom = A.Field("totally_phantom_line_key", "any")
    patched = dataclasses.replace(
        bench_line, checks=bench_line.checks + (phantom,))
    import knn_tpu.analysis.artifacts as mod

    saved_cat, saved_by = mod.CATALOG, mod.BY_NAME
    try:
        mod.CATALOG = tuple(patched if s.name == "bench_line" else s
                            for s in saved_cat)
        mod.BY_NAME = {s.name: s for s in mod.CATALOG}
        rep = run_on(REPO)
    finally:
        mod.CATALOG, mod.BY_NAME = saved_cat, saved_by
    assert any(f.symbol == "totally_phantom_line_key"
               and "phantom schema field" in f.message
               for f in rep.findings)


def test_checker_flags_missing_docs_anchor(tmp_path):
    """A docs file that exists but lost the block's heading is a
    finding — anchors only bind when their file is present, so fixture
    trees stay green."""
    write_tree(tmp_path, {"docs/PERF.md": "# PERF\n\nno headings\n"})
    rep = run_on(tmp_path)
    assert not rep.ok
    assert any("docs anchor" in f.message and f.symbol == "roofline"
               for f in rep.findings)


def test_cli_lint_json_exit_code_contract_for_artifact_lockstep(
        tmp_path):
    """The subprocess exit-code contract: the seeded emitter-key
    regression flips ``cli lint --json`` to exit 1 with the finding in
    the JSON report; the checker rides --list."""
    write_tree(tmp_path, {"bench.py": '''
        block = {"mutation_version": 1, "write_mix": {},
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
