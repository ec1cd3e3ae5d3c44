"""End-to-end job tests: the reference's whole main() (knn_mpi.cpp:86-399)
through the library pipeline and the CLI, on the 8-virtual-device mesh."""

import json

import numpy as np
import pytest

from knn_tpu.cli import main as cli_main
from knn_tpu.data.csv_io import read_labels
from knn_tpu.data.datasets import make_blobs, save_labeled_csv, save_unlabeled_csv
from knn_tpu.pipeline import run_job
from knn_tpu.utils.config import JobConfig


@pytest.fixture
def job_files(tmp_path):
    """Separable 3-class blob dataset in the reference's CSV formats."""
    feats, labels = make_blobs(240, 6, 3, cluster_std=0.3, seed=7)
    train_f, train_l = feats[:160], labels[:160]
    val_f, val_l = feats[160:200], labels[160:200]
    test_f, test_l = feats[200:], labels[200:]
    paths = {
        "train": str(tmp_path / "train.csv"),
        "val": str(tmp_path / "val.csv"),
        "test": str(tmp_path / "test.csv"),
        "out": str(tmp_path / "Test_label.csv"),
    }
    save_labeled_csv(paths["train"], train_f, train_l)
    save_labeled_csv(paths["val"], val_f, val_l)
    save_unlabeled_csv(paths["test"], test_f)
    return paths, test_l


def _config(paths, **kw):
    base = dict(
        train_file=paths["train"],
        test_file=paths["test"],
        val_file=paths["val"],
        output_file=paths["out"],
        k=5,
        query_shards=4,
        db_shards=2,
    )
    base.update(kw)
    return JobConfig(**base)


def test_run_job_end_to_end(job_files):
    paths, test_l = job_files
    result = run_job(_config(paths))
    # separable blobs: near-perfect accuracy, like the reference's MNIST
    # oracle check (SURVEY.md §4 point 1)
    assert result.val_accuracy is not None and result.val_accuracy >= 0.95
    assert np.mean(result.test_labels == test_l) >= 0.95
    # Test_label.csv written in the reference's format (knn_mpi.cpp:385-393)
    np.testing.assert_array_equal(read_labels(paths["out"]), result.test_labels)
    # per-phase timing recorded
    for phase in ("ingest", "normalize", "knn_val", "knn_test", "output"):
        assert phase in result.phase_times
    assert result.total_time > 0
    assert result.n_train == 160 and result.n_test == 40 and result.n_val == 40


def test_run_job_no_validation(job_files):
    paths, _ = job_files
    result = run_job(_config(paths, validation=False, val_file=None))
    assert result.val_accuracy is None and result.val_labels is None
    assert "knn_val" not in result.phase_times
    assert result.n_val == 0


def test_run_job_no_normalize(job_files):
    paths, test_l = job_files
    result = run_job(_config(paths, normalize=False))
    assert "normalize" not in result.phase_times
    assert np.mean(result.test_labels == test_l) >= 0.9


def test_run_job_ring_merge_same_labels(job_files):
    paths, _ = job_files
    a = run_job(_config(paths))
    b = run_job(_config(paths, merge="ring"))
    np.testing.assert_array_equal(a.test_labels, b.test_labels)


def test_run_job_batched_matches_unbatched(job_files):
    paths, _ = job_files
    a = run_job(_config(paths))
    b = run_job(_config(paths, batch_size=7, train_tile=13))
    np.testing.assert_array_equal(a.test_labels, b.test_labels)
    np.testing.assert_array_equal(a.val_labels, b.val_labels)


def test_run_job_rejects_bad_k(job_files):
    paths, _ = job_files
    with pytest.raises(ValueError, match="k=9999"):
        run_job(_config(paths, k=9999))


def test_run_job_rejects_out_of_range_labels(job_files, tmp_path):
    paths, _ = job_files
    # num_classes=2 but blobs have 3 classes: both backends must fail loudly
    with pytest.raises(ValueError, match="outside"):
        run_job(_config(paths, num_classes=2))


def test_cli_parsing_does_not_import_jax():
    # flag parsing must stay light: building the parser and validating a
    # config cannot pull JAX into the process
    import subprocess, sys

    # spy on *new* imports rather than inspecting sys.modules (an
    # interpreter start-up hook may have pre-imported jax)
    code = (
        "import sys\n"
        "class Spy:\n"
        "    def find_spec(self, name, path=None, target=None):\n"
        "        if name == 'jax' or name.startswith('jax.'):\n"
        "            raise AssertionError('jax imported during CLI parsing')\n"
        "        return None\n"
        "sys.meta_path.insert(0, Spy())\n"
        "from knn_tpu.cli import build_parser\n"
        "from knn_tpu.utils.config import JobConfig\n"
        "build_parser().parse_args(['--train','t','--test','q'])\n"
        "JobConfig()\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, cwd="/root/repo"
    )
    assert proc.returncode == 0, proc.stderr


def test_metrics_json_structure(job_files):
    paths, _ = job_files
    result = run_job(_config(paths))
    m = json.loads(result.metrics_json())
    assert m["n_train"] == 160
    assert m["queries_per_sec"] > 0
    assert m["config"]["k"] == 5
    assert "knn_test" in m["phase_times_s"]


def test_cli_end_to_end(job_files, tmp_path, capsys):
    paths, test_l = job_files
    metrics_path = str(tmp_path / "metrics.json")
    rc = cli_main(
        [
            "--train", paths["train"],
            "--test", paths["test"],
            "--val", paths["val"],
            "--out", paths["out"],
            "--k", "5",
            "--query-shards", "2",
            "--db-shards", "4",
            "--metrics-json", metrics_path,
        ]
    )
    assert rc == 0
    out = capsys.readouterr().out
    # the reference's two printed lines (knn_mpi.cpp:348,398)
    assert "accuracy = " in out and "Running time is " in out
    assert np.mean(read_labels(paths["out"]) == test_l) >= 0.95
    m = json.load(open(metrics_path))
    assert m["val_accuracy"] >= 0.95


def test_config_validation():
    with pytest.raises(ValueError, match="metric"):
        JobConfig(metric="chebyshev")
    with pytest.raises(ValueError, match="backend"):
        JobConfig(backend="cuda")
    with pytest.raises(ValueError, match="k must be"):
        JobConfig(k=0)
    with pytest.raises(ValueError, match="requires val_file"):
        JobConfig(validation=True, val_file=None)
    cfg = JobConfig()
    assert JobConfig.from_json(cfg.to_json()) == cfg


def test_run_job_serving_buckets_matches_direct(job_files):
    """--serve-buckets routes classification through the bucketed
    serving engine: identical labels, serving metrics (per-bucket
    compile counts + latency percentiles) in JobResult.metrics()."""
    paths, test_l = job_files
    direct = run_job(_config(paths))
    served = run_job(_config(paths, serve_buckets="8,16,32", batch_size=13))
    np.testing.assert_array_equal(direct.test_labels, served.test_labels)
    np.testing.assert_array_equal(direct.val_labels, served.val_labels)
    assert "serving_warmup" in served.phase_times
    m = served.metrics()["serving"]
    assert m["buckets"] == [8, 16, 32]
    # warmup compiled every bucket; the job loop added NO compiles
    assert m["compile_count"] <= len(m["buckets"])
    assert sum(m["per_bucket_dispatches"].values()) == m["requests"]
    assert m["latency_ms"]["count"] == m["requests"]
    assert m["latency_ms"]["p50"] <= m["latency_ms"]["p99"]
    assert m["max_wait_ms"] == 2.0
    # direct runs carry no serving block
    assert "serving" not in direct.metrics()


def test_cli_serve_buckets_flag(job_files, tmp_path, capsys):
    paths, test_l = job_files
    metrics_path = str(tmp_path / "metrics_serving.json")
    rc = cli_main(
        [
            "--train", paths["train"],
            "--test", paths["test"],
            "--val", paths["val"],
            "--out", paths["out"],
            "--k", "5",
            "--serve-buckets", "8,32",
            "--max-wait-ms", "1.5",
            "--metrics-json", metrics_path,
        ]
    )
    assert rc == 0
    assert np.mean(read_labels(paths["out"]) == test_l) >= 0.95
    m = json.load(open(metrics_path))
    assert m["serving"]["buckets"] == [8, 32]
    assert m["serving"]["max_wait_ms"] == 1.5
    assert m["config"]["serve_buckets"] == "8,32"


def test_config_serving_validation():
    with pytest.raises(ValueError, match="bad bucket spec"):
        JobConfig(serve_buckets="8,x")
    with pytest.raises(ValueError, match="does not compose"):
        JobConfig(serve_buckets="auto", mode="certified")
    with pytest.raises(ValueError, match="jax backend"):
        JobConfig(serve_buckets="auto", backend="native")
    with pytest.raises(ValueError, match="max_wait_ms"):
        JobConfig(max_wait_ms=-0.5)
    # empty spec disables serving instead of erroring
    assert JobConfig(serve_buckets="").serve_buckets is None
    cfg = JobConfig(serve_buckets="16,64", max_wait_ms=3.0)
    assert JobConfig.from_json(cfg.to_json()) == cfg
