"""Every cell of BENCHMARK.json end to end at a tiny size on the CPU, in
both trace modes, through the real drivers, readers and the last-line
validator: which is also each configuration's comparison of the system
with the plain reference.  Only the harness's look for a chip is
skipped (``run.py`` makes it; ``harness.run_cell`` is called directly),
and the device's memory reading, which the CPU backend does not give,
is supplied.  Then the same run with the timed path broken underneath
has to come out ``correct: false``."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import harness
import lastline
import tinyroot

BENCH = tinyroot.load_bench()
CELLS = [c["name"] for c in BENCH["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(autouse=True)
def cpu_memory_reading(monkeypatch):
    real = harness.device_info

    def info(resident):
        return real(resident or 1)

    monkeypatch.setattr(harness, "device_info", info)


def run(root, workload, traced, seed=2**31 + 17, seconds=1.5):
    lines = []
    parsed = harness.run_cell(root, workload, seed, seconds, traced,
                              time.perf_counter(), emit=lines.append)
    assert len(lines) == 1
    # what was printed is what the validator passes, against the REAL
    # BENCHMARK.json
    assert lastline.validate(lines[0], BENCH, workload, traced) == parsed
    return parsed


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_end_to_end(root, workload, traced):
    out = run(root, workload, traced)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in
            lastline.required_metrics(BENCH, workload, traced)}
    assert set(out["metrics"]) == want
    if traced:
        assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
        assert out["breakdown"]["device_ops"]
        # the idle time is laid against the program's own spans (the
        # serving path opens none: PERF.md section 7)
        gaps = dict(out["breakdown"]["idle_gaps"])
        if harness.load_cell(root, workload).traffic["kind"] != "openloop":
            assert any(name.startswith("knn.certified.") for name in gaps)
        assert sum(gaps.values()) <= (out["device"]["window_s"]
                                      - out["device"]["busy_s"]) * (1 + 1e-9)
    else:
        assert "breakdown" not in out and "window_s" not in out["device"]


def _break_sweep(monkeypatch):
    """A token altered where it is produced: one neighbour of one query
    of every answer is swapped for another row."""
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN.search_certified

    def broken(self, queries, **kw):
        d, i, stats = real(self, queries, **kw)
        i = np.array(i)
        i[:, -1] = (i[:, -1] + 1) % self.n_train
        return d, i, stats

    monkeypatch.setattr(ShardedKNN, "search_certified", broken)


def _break_sweep_range(monkeypatch):
    """An answer altered where it is produced: every list that holds a
    row loses its last one.  (``_break_sweep`` would not do: it alters
    the first pass's k-th index, which a range answer holds only where
    the completion does not replace it.)"""
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN.range_search_certified

    def broken(self, queries, **kw):
        lims, idx, dist, stats = real(self, queries, **kw)
        lims = np.asarray(lims)
        sizes = np.diff(lims)
        keep = np.ones(len(idx), dtype=bool)
        keep[lims[1:][sizes > 0] - 1] = False
        cut = np.concatenate([[0], np.cumsum(sizes - (sizes > 0))])
        return (cut.astype(lims.dtype), np.asarray(idx)[keep],
                np.asarray(dist)[keep], stats)

    monkeypatch.setattr(ShardedKNN, "range_search_certified", broken)


def _break_serve(monkeypatch):
    """The engine answers from a placement whose rows were halved: every
    distance is off, which the recall may survive and the distance gap
    may not."""
    from knn_tpu.serving import queue as squeue

    real = squeue.QueryQueue._resolve

    def broken(fut, value=None, exc=None):
        if value is not None:
            d, idx = value
            value = (np.asarray(d) * 0.5, idx)
        return real(fut, value, exc)

    monkeypatch.setattr(squeue.QueryQueue, "_resolve", staticmethod(broken))


BREAKERS = {"sweep": _break_sweep, "sweep_ip": _break_sweep,
            "sweep_range": _break_sweep_range, "openloop": _break_serve}


@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_timed_path_reads_not_correct(root, workload, monkeypatch):
    BREAKERS[harness.load_cell(root, workload).traffic["kind"]](monkeypatch)
    out = run(root, workload, False)
    assert out["correct"] is False


def test_a_sweep_that_skips_part_of_the_batch_is_not_correct(root,
                                                             monkeypatch):
    """Part of the batch left out: the last rows of every answer come
    back unset, and the counts no longer add up."""
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN.search_certified

    def lazy(self, queries, **kw):
        d, i, stats = real(self, queries, **kw)
        i = np.array(i)
        i[len(i) // 2:] = 0
        stats = dict(stats, certified=stats["certified"] - 1)
        return d, i, stats

    monkeypatch.setattr(ShardedKNN, "search_certified", lazy)
    out = run(root, CELLS[0], False)
    assert out["correct"] is False


def test_a_reader_that_finds_nothing_leaves_no_valid_line(root, tmp_path):
    import shutil

    broken = str(tmp_path / "root")
    shutil.copytree(root, broken)
    path = os.path.join(broken, "benchmark", "layers", "kernel_ms.json")
    with open(path) as f:
        layer = json.load(f)
    layer["reader"]["pattern"] = "no-operation-has-this-name"
    with open(path, "w") as f:
        json.dump(layer, f)
    with pytest.raises(harness.BenchError, match="kernel_ms"):
        run(broken, "bigann5m.sweep", True)


def test_a_layer_file_that_disagrees_with_benchmark_json_is_refused(
        root, tmp_path):
    import shutil

    broken = str(tmp_path / "root")
    shutil.copytree(root, broken)
    path = os.path.join(broken, "benchmark", "layers", "tail_ms.json")
    with open(path) as f:
        layer = json.load(f)
    layer["moves"] = "setup_s"
    with open(path, "w") as f:
        json.dump(layer, f)
    with pytest.raises(harness.BenchError, match="moves"):
        harness.load_cell(broken, "bigann5m.sweep")


def _run_py(cwd_root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd_root, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0", *args],
        cwd=cwd_root, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_run_py_fails_and_prints_no_result():
    res = _run_py(tinyroot.ROOT)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr
    assert not any(ln.lstrip().startswith("{") for ln in
                   res.stdout.splitlines())


def test_the_benchmark_files_alone_fail_and_print_no_result(tmp_path):
    import shutil

    alone = str(tmp_path / "alone")
    os.makedirs(alone)
    shutil.copy(os.path.join(tinyroot.ROOT, "BENCHMARK.json"), alone)
    shutil.copytree(tinyroot.BENCH_DIR, os.path.join(alone, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_py(alone)
    assert res.returncode != 0
    assert "knn_tpu" in res.stderr
    assert not any(ln.lstrip().startswith("{") for ln in
                   res.stdout.splitlines())
