"""The perf-regression sentinel (knn_tpu.obs.sentinel +
scripts/perf_sentinel.py): on recorded bench-history fixtures a
synthetic 20% qps regression is flagged ``regress``, jitter within the
historical MAD stays ``ok``, and stale-marked lines never enter the
baseline — the acceptance surface of the sentinel ISSUE."""

import json
import subprocess
import sys

import pytest

from knn_tpu.obs import sentinel

REPO = __file__.rsplit("/tests/", 1)[0]

#: a tight recorded history: sift-shaped TPU lines across three rounds,
#: ~6000 q/s with ~±60 jitter (MAD 60 -> sigma ~89, sigma_rel ~1.5%)
HISTORY = [
    {"metric": "knn_qps_sift1m_n1000000_d128_k100", "value": 6000.0,
     "device_phase_qps": 24000.0, "mfu": 0.03, "backend": "tpu",
     "measured_round": 1, "measured_at_commit": "aaa1111"},
    {"metric": "knn_qps_sift1m_n1000000_d128_k100", "value": 6060.0,
     "device_phase_qps": 24100.0, "mfu": 0.031, "backend": "tpu",
     "measured_round": 2, "measured_at_commit": "bbb2222"},
    {"metric": "knn_qps_sift1m_n1000000_d128_k100", "value": 5940.0,
     "device_phase_qps": 23900.0, "mfu": 0.029, "backend": "tpu",
     "measured_round": 3, "measured_at_commit": "ccc3333"},
    {"metric": "knn_qps_sift1m_n1000000_d128_k100", "value": 6120.0,
     "device_phase_qps": 24150.0, "mfu": 0.031, "backend": "tpu",
     "measured_round": 4, "measured_at_commit": "ddd4444"},
]

#: a stale republication with an absurd value: must NEVER enter
STALE_LINE = {"metric": "knn_qps_sift1m_n1000000_d128_k100",
              "value": 60000.0, "backend": "tpu", "stale": True,
              "measured_round": 1, "measured_at_commit": "aaa1111"}


def _baselines(extra=()):
    return sentinel.build_baselines(list(HISTORY) + list(extra))


def test_synthetic_20pct_regression_flagged_regress():
    base = _baselines()
    med = base["knn_qps_sift1m_n1000000_d128_k100|tpu|default"][
        "value"]["median"]
    line = {"metric": "knn_qps_sift1m_n1000000_d128_k100",
            "backend": "tpu", "value": med * 0.8}
    v = sentinel.verdict_for_line(line, baselines=base)
    assert v["verdict"] == "regress"
    f = v["fields"]["value"]
    assert f["drop_rel"] == pytest.approx(0.2, abs=1e-6)
    assert f["effect_sigmas"] > 4


def test_jitter_within_historical_mad_stays_ok():
    base = _baselines()
    stats = base["knn_qps_sift1m_n1000000_d128_k100|tpu|default"]["value"]
    # one MAD below the median is, by construction, historical jitter
    line = {"metric": "knn_qps_sift1m_n1000000_d128_k100",
            "backend": "tpu", "value": stats["median"] - stats["mad"]}
    v = sentinel.verdict_for_line(line, baselines=base)
    assert v["verdict"] == "ok"
    # and a faster-than-baseline run is trivially ok
    line["value"] = stats["median"] * 1.3
    assert sentinel.verdict_for_line(
        line, baselines=base)["verdict"] == "ok"


def test_between_the_bars_is_warn():
    base = _baselines()
    stats = base["knn_qps_sift1m_n1000000_d128_k100|tpu|default"]["value"]
    # ~6% below median: past max(2*sigma_rel~3%, 2%), short of the 10%
    # regression floor
    line = {"metric": "knn_qps_sift1m_n1000000_d128_k100",
            "backend": "tpu", "value": stats["median"] * 0.94}
    v = sentinel.verdict_for_line(line, baselines=base)
    assert v["fields"]["value"]["verdict"] == "warn"


def test_stale_lines_never_enter_the_baseline():
    with_stale = _baselines(extra=[STALE_LINE])
    clean = _baselines()
    key = "knn_qps_sift1m_n1000000_d128_k100|tpu|default"
    assert with_stale[key]["value"] == clean[key]["value"]
    assert with_stale[key]["value"]["n"] == len(HISTORY)
    assert 60000.0 not in with_stale[key]["value"]["values"]


def test_same_commit_same_value_counts_once():
    dup = dict(HISTORY[0])  # same commit, same value: a republication
    base = _baselines(extra=[dup])
    key = "knn_qps_sift1m_n1000000_d128_k100|tpu|default"
    assert base[key]["value"]["n"] == len(HISTORY)
    # same commit, DIFFERENT value = a genuine re-measurement: counts
    remeasured = dict(HISTORY[0], value=6010.0)
    base = _baselines(extra=[remeasured])
    assert base[key]["value"]["n"] == len(HISTORY) + 1


def test_backend_and_precision_key_separately():
    cpu_line = {"metric": "knn_qps_sift1m_n1000000_d128_k100",
                "value": 50.0, "backend": "cpu"}
    int8_line = {"metric": "knn_qps_sift1m_n1000000_d128_k100",
                 "value": 9000.0, "backend": "tpu", "precision": "int8"}
    base = _baselines(extra=[cpu_line, int8_line] * 3)
    key_tpu = "knn_qps_sift1m_n1000000_d128_k100|tpu|default"
    # the CPU/int8 lines landed under their OWN keys, leaving the tpu
    # f32-family baseline untouched
    assert base[key_tpu]["value"]["n"] == len(HISTORY)
    assert "knn_qps_sift1m_n1000000_d128_k100|cpu|default" in base
    assert "knn_qps_sift1m_n1000000_d128_k100|tpu|int8" in base
    # and a cpu line is judged against the cpu baseline, never the tpu
    v = sentinel.verdict_for_line(dict(cpu_line), baselines=base)
    assert v["baseline_key"].endswith("|cpu|default")
    assert v["fields"]["value"]["verdict"] == "ok"


def test_short_history_yields_no_baseline():
    base = sentinel.build_baselines(HISTORY[:2])
    assert base == {}
    v = sentinel.verdict_for_line(
        {"metric": "knn_qps_other", "backend": "tpu", "value": 1.0},
        baselines=_baselines())
    assert v["verdict"] == "no_baseline"


def test_iter_history_reads_round_files(tmp_path):
    _write_history(tmp_path, {i + 1: [HISTORY[i]] for i in range(4)})
    records = list(sentinel.iter_history_lines(str(tmp_path)))
    assert len(records) == 4
    assert all(r["metric"].startswith("knn_qps_sift1m") for r in records)
    # max_round excludes the round being judged
    bounded = list(sentinel.iter_history_lines(str(tmp_path), max_round=4))
    assert len(bounded) == 3
    assert all(sentinel._file_round(r["_source"]) < 4 for r in bounded)
    # the history builds baselines without raising
    sentinel.build_baselines(records)
    # a checkout with no round files is an empty history, not an error
    assert list(sentinel.iter_history_lines(str(tmp_path / "none"))) == []


def _write_history(tmp_path, rounds):
    for rnd, lines in rounds.items():
        p = tmp_path / f"TPU_BENCH_r{rnd:02d}.jsonl"
        p.write_text("".join(json.dumps(ln) + "\n" for ln in lines))


def test_perf_sentinel_cli_lint_and_strict_gate(tmp_path):
    # rounds 1-4: the tight history; round 5: a 20% regression
    _write_history(tmp_path, {
        i + 1: [HISTORY[i]] for i in range(4)})
    _write_history(tmp_path, {5: [
        {"metric": "knn_qps_sift1m_n1000000_d128_k100", "value": 4800.0,
         "backend": "tpu", "measured_round": 5,
         "measured_at_commit": "eee5555"}]})
    script = f"{REPO}/scripts/perf_sentinel.py"
    r = subprocess.run(
        [sys.executable, script, "--repo", str(tmp_path), "--lint"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    # warn-only by default: verdict printed, exit 0
    r = subprocess.run(
        [sys.executable, script, "--repo", str(tmp_path),
         "--check-latest"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "regress" in r.stdout
    # --strict turns the regress verdict into a hard failure
    r = subprocess.run(
        [sys.executable, script, "--repo", str(tmp_path),
         "--check-latest", "--strict"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    # a healthy latest round passes strict
    _write_history(tmp_path, {5: [
        {"metric": "knn_qps_sift1m_n1000000_d128_k100", "value": 6050.0,
         "backend": "tpu", "measured_round": 5,
         "measured_at_commit": "eee5555"}]})
    r = subprocess.run(
        [sys.executable, script, "--repo", str(tmp_path),
         "--check-latest", "--strict"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_refresher_stamps_sentinel_verdicts(tmp_path):
    import shutil

    # a self-contained repo dir: history rounds 1-4 + this round's
    # session lines, with the refresher copied alongside (it resolves
    # paths relative to its own location)
    scripts_dir = tmp_path / "scripts"
    scripts_dir.mkdir()
    shutil.copy(f"{REPO}/scripts/refresh_bench_artifacts.py",
                scripts_dir / "refresh_bench_artifacts.py")
    (tmp_path / "knn_tpu").symlink_to(f"{REPO}/knn_tpu")
    _write_history(tmp_path, {i + 1: [HISTORY[i]] for i in range(4)})
    (tmp_path / "tpu_bench_lines.jsonl").write_text(json.dumps(
        {"metric": "knn_qps_sift1m_n1000000_d128_k100", "value": 4700.0,
         "backend": "tpu", "pallas_gate_ok": True,
         "measured_at_commit": "fff6666"}) + "\n")
    r = subprocess.run(
        [sys.executable, str(scripts_dir / "refresh_bench_artifacts.py"),
         "5"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    out = [json.loads(ln) for ln in
           (tmp_path / "TPU_BENCH_r05.jsonl").read_text().splitlines()]
    rec = next(x for x in out
               if x["metric"] == "knn_qps_sift1m_n1000000_d128_k100")
    # fresh line (21% below the tight baseline) carries its verdict
    assert rec["sentinel"]["verdict"] == "regress"
    assert "sentinel=regress" in r.stdout


def test_bench_line_sentinel_block_shape():
    # the block bench.py embeds: verdict + per-field classifications
    v = sentinel.verdict_for_line(
        {"metric": "knn_qps_sift1m_n1000000_d128_k100",
         "backend": "tpu", "value": 6000.0, "mfu": 0.030,
         "device_phase_qps": 24000.0},
        baselines=_baselines())
    assert v["verdict"] == "ok"
    assert set(v["fields"]) == {"value", "mfu", "device_phase_qps"}
    for f in v["fields"].values():
        assert f["verdict"] == "ok"
        assert {"baseline_median", "baseline_n", "drop_rel",
                "ok_bar", "regress_bar"} <= set(f)
