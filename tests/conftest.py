"""Test harness: force an 8-device CPU mesh so every multi-device test runs
on virtual CPU devices — these play the role MPI ranks play in the reference
(SURVEY.md §4) — without touching TPU hardware.

Two mechanisms, because each covers what the other cannot:
  1. env vars, for any subprocess the tests spawn;
  2. ``jax.config.update``, which wins in this process as long as no
     backend has been initialized yet (JAX initializes them lazily).
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# Switch isolation is GENERATED from the central env-switch catalog
# (knn_tpu.analysis.switches — jax-free, so this import is safe before
# the backend config below): every cataloged mutable switch plus any
# ambient variable under a cataloged family prefix is scrubbed, so a
# developer shell's KNN_TPU_* can never silently steer the
# suite.  Never hand-list switches here again — declare them in the
# catalog and isolation follows on the next run (the switch-lockstep
# checker fails the lint if this derivation is removed).  Tests that
# exercise a switch set their own value AFTER this scrub, per-test.
from knn_tpu.analysis.switches import isolation_names

for _knob in isolation_names(os.environ):
    os.environ.pop(_knob, None)
# tests see real compiles: JAX's persistent compilation cache stays off
# for this process and every subprocess, so an entry point under test
# (cli.main) that calls utils.compat.enable_compile_cache
# leaves nothing in the checkout and warms nothing for the next test
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest

assert len(jax.devices()) >= 8, "test harness requires 8 virtual CPU devices"

# benchmark/tests/tinyroot.py shrinks every traffic file by a literal
# table of driver kinds and raises KeyError on one it lacks; three files
# here call tinyroot.make, and the benchmark's file is not theirs to
# edit.  benchmark/tests/tiny_filter.py adds the ``sweep_filter`` entry,
# benchmark/tests/tiny_vote.py the ``sweep_vote`` one and
# benchmark/tests/tiny_graph.py the ``graph_build`` one,
# benchmark/tests/tiny_topk.py the ``sweep_topk`` one and
# benchmark/tests/tiny_cosfilter.py the ``sweep_cos_filter`` one (ROADMAP R0 item 0
# asks the next benchmark issue for the one-line repair, which deletes
# this block)
import sys

_bench_tests = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests")
sys.path.insert(0, _bench_tests)
import tiny_cosfilter  # noqa: E402,F401
import tiny_filter  # noqa: E402,F401
import tiny_graph  # noqa: E402,F401
import tiny_topk  # noqa: E402,F401
import tiny_vote  # noqa: E402,F401

sys.path.remove(_bench_tests)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def call_order(monkeypatch):
    """What a certified call maps and launches, in the order it does: a
    list of ``("map", lo, hi)`` for every range of queries a cosine
    call (or a placement) maps to unit rows and ``("launched",
    program)`` for every launch its account is told of."""
    from knn_tpu import obs
    from knn_tpu.parallel import sharded as sh

    log = []
    real_map, real_launched = sh._map_unit_rows, obs.trace.CallAccount.launched

    def mapped(x, unit, norms, lo, hi):
        log.append(("map", lo, hi))
        return real_map(x, unit, norms, lo, hi)

    def launched(self, program):
        log.append(("launched", program))
        return real_launched(self, program)

    monkeypatch.setattr(sh, "_map_unit_rows", mapped)
    monkeypatch.setattr(obs.trace.CallAccount, "launched", launched)
    return log


@pytest.fixture
def mh_spawn(tmp_path):
    """The 2-process CPU ``jax.distributed`` subprocess harness
    (tests/mh_harness.py), pre-gated on the coordinator/KV-store probe:
    ``mh_spawn(child_src, n_proc=2)`` spawns the processes and returns
    {pid: parsed RESULT json}, skipping ONLY when the harness itself
    probes red (the distributed-init probe fails on this jaxlib)."""
    import mh_harness

    def spawn(child_src: str, n_proc: int = 2, timeout_s: int = 180):
        verdict = mh_harness.distributed_init_supported()
        if not verdict["ok"]:
            pytest.skip("jax.distributed coordinator/KV store "
                        f"unsupported: {verdict['reason']}")
        return mh_harness.spawn_jax_procs(tmp_path, child_src, n_proc,
                                          timeout_s)

    return spawn
