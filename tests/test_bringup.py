"""Bring-up contracts that hold without a chip: where the compile cache
goes, that ``chip_smoke.py`` refuses to stand in for a chip run on CPU,
and (slow) that the library-default geometries still compile for the
v5e — ``scripts/aot_compile_check.py``, deviceless — so the next default
promotion meets GIST and GloVe before it meets the chip."""

import os
import subprocess
import sys
import tempfile

import jax
import pytest

from knn_tpu.utils import compat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_writes(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    writes = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: writes.__setitem__(name, value))
    return writes


def test_compile_cache_env_var_set_means_no_path_in_code(
        monkeypatch, config_writes):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compat.enable_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in config_writes
    # the thresholds still drop, so serving buckets are cached there too
    assert config_writes["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert config_writes["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_compile_cache_unset_is_the_fixed_checkout_path(
        monkeypatch, config_writes):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compat.enable_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    assert config_writes["jax_compilation_cache_dir"] == first
    # fixed: a second call (another process, another day) names the same
    # directory, and it is never a temp path
    assert compat.enable_compile_cache() == first
    assert not first.startswith(tempfile.gettempdir())
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_smoke_refuses_to_run_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert "Traceback" not in proc.stderr
    # no verdict line: the last stdout line is the device report
    last = proc.stdout.strip().splitlines()[-1]
    assert "platform cpu" in last and '"ok"' not in proc.stdout


@pytest.mark.slow
def test_default_geometries_compile_for_v5e_deviceless():
    """The table a bare ``aot_compile_check.py`` prints: the knobs the
    library resolves on its own, for each of the three kernels at SIFT,
    GIST and GloVe — and fused at block_q=256 REFUSED by the library
    before Mosaic is asked.  Any Mosaic refusal (or a library refusal
    that went away) fails."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "aot_compile_check.py")],
        capture_output=True, text=True, timeout=1500, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln[:4] in ("OK  ",
                                                                 "FAIL")]
    assert len(lines) == 11 and all(ln.startswith("OK") for ln in lines)
    assert "sift fused block_q=256: refused" in proc.stdout
    # the one kernel outside the coarse pass: the final select's
    # bin-merge at a 5M-row chip's 78,336 candidate columns
    assert "bigann20m select-merge kernel (4096, 78336): compiles" in (
        proc.stdout)


def test_stats_report_the_interpret_value_the_kernel_was_given(monkeypatch):
    """``stats["pallas_knobs"]["interpret"]`` is the value
    ``ShardedKNN._pallas_setup`` resolved and handed down to the kernel,
    not a second reading of the backend beside it."""
    import numpy as np

    from knn_tpu.parallel import ShardedKNN, make_mesh
    from knn_tpu.parallel import sharded as sh

    given = []
    real = sh._pallas_certified_program

    def spy(*args, **kwargs):
        given.append(kwargs["interpret"])
        return real(*args, **kwargs)

    monkeypatch.setattr(sh, "_pallas_certified_program", spy)
    rng = np.random.default_rng(3)
    db = (rng.random((600, 16)) * 64).astype(np.float32)
    q = (rng.random((8, 16)) * 64).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=5)
    _, _, stats = prog.search_certified(q, selector="pallas", margin=6)
    # the CPU suite runs Pallas in interpret mode; chip_smoke.py asserts
    # False for the same key on the chip
    assert given == [True]
    assert stats["pallas_knobs"]["interpret"] is given[0]
    # and nothing on ShardedKNN's surface lets a caller choose it
    with pytest.raises(TypeError):
        prog.search_certified(q, selector="pallas", interpret=True)
