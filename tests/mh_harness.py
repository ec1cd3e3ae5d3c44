"""The 2-process CPU ``jax.distributed`` subprocess harness — ONE home
for every real-multi-process test (tests/test_multihost.py) and for the
``scripts/check_tier1.sh --multihost`` lane.

Two capabilities, probed separately because they fail separately:

- ``multiprocess_cpu_supported()`` — whether this jaxlib can EXECUTE
  XLA computations spanning jax.distributed CPU processes (green on
  the installed jaxlib 0.9.0; a build without it raises "Multiprocess
  computations aren't implemented on the CPU backend").  Tests that
  run process-spanning SPMD programs skip with the probe's actual
  error when red.
- ``distributed_init_supported()`` — whether ``jax.distributed``
  processes can merely JOIN a coordinator and use its key-value store.
  This holds on every supported jaxlib (the store lives beside XLA,
  not inside it), so the host-mediated DCN merge tests
  (parallel.multihost.MultiHostKNN) run as REAL 2-process lanes even
  where the first probe is red — they are pinned tests, not skips.

Both probes run ONCE per session; ``spawn_jax_procs`` is the shared
spawner: write the child script, pick a free coordinator port, launch
N one-device CPU processes, parse one ``RESULT <json>`` line each, and
kill every sibling on any failure so a bad child can never strand the
rest of the pytest run on the coordinator barrier.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import textwrap

#: one-shot probe verdicts: {"ok": bool, "reason": str} once populated
_MULTIPROC_PROBE: dict = {}
_DIST_INIT_PROBE: dict = {}

_PROBE_CHILD = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, n_proc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=n_proc, process_id=pid)
import numpy as np
from jax.experimental import multihost_utils

# the minimal computation that spans processes: the broadcast psum —
# exactly the op an unsupported jaxlib rejects with
# "Multiprocess computations aren't implemented on the CPU backend"
out = multihost_utils.broadcast_one_to_all(np.int32(7))
assert int(out) == 7
print("PROBE_OK", flush=True)
"""

_INIT_PROBE_CHILD = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
pid, n_proc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=n_proc, process_id=pid)
from jax._src import distributed
c = distributed.global_state.client
c.key_value_set(f"probe/{pid}", str(pid))
got = c.blocking_key_value_get(f"probe/{1 - pid}", 30000)
assert int(got) == 1 - pid
print("PROBE_OK", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _child_env() -> dict:
    return dict(
        os.environ,
        PYTHONPATH=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        JAX_PLATFORMS="cpu",
    )


def _run_probe(cache: dict, child_src: str) -> dict:
    if cache:
        return cache
    import tempfile

    with tempfile.TemporaryDirectory(prefix="knn_tpu_mh_probe_") as td:
        child = os.path.join(td, "probe_child.py")
        with open(child, "w") as f:
            f.write(textwrap.dedent(child_src))
        procs = [
            subprocess.Popen(
                [sys.executable, child, str(p), "2", str(_PORT[0])],
                env=_child_env(), stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )
            for p in range(2)
        ]
        ok, reason = True, "supported"
        try:
            for proc in procs:
                out, err = proc.communicate(timeout=120)
                if proc.returncode != 0 or "PROBE_OK" not in out:
                    ok = False
                    tail = [ln for ln in err.splitlines() if ln.strip()]
                    reason = tail[-1] if tail else f"rc={proc.returncode}"
                    break
        except subprocess.TimeoutExpired:
            ok, reason = False, "probe timed out after 120s"
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.kill()
    cache.update({"ok": ok, "reason": reason})
    return cache


#: mutable single-slot port holder so _run_probe's closure stays simple
_PORT = [0]


def multiprocess_cpu_supported() -> dict:
    """Probe ONCE whether this jaxlib executes computations across
    jax.distributed CPU processes: spawn two 1-device CPU processes and
    run the smallest cross-process collective.  The verdict (and the
    failing error line, as the skip reason) is cached for the session."""
    _PORT[0] = _free_port()
    return _run_probe(_MULTIPROC_PROBE, _PROBE_CHILD)


def distributed_init_supported() -> dict:
    """Probe ONCE whether 2 jax.distributed CPU processes can join a
    coordinator and exchange through its KV store — the only
    capability the host-mediated DCN merge lane needs."""
    _PORT[0] = _free_port()
    return _run_probe(_DIST_INIT_PROBE, _INIT_PROBE_CHILD)


def spawn_jax_procs(tmp_path, child_src: str, n_proc: int,
                    timeout_s: int = 180) -> dict:
    """Shared harness for the real-multi-process tests: write the child
    script, pick a free coordinator port, spawn ``n_proc``
    jax.distributed CPU processes, and return {pid: parsed RESULT
    json}.  Children get (process_id, n_proc, port) as argv.  All
    children are killed on ANY failure — a single bad child must not
    strand its siblings on the coordinator barrier for the rest of the
    pytest run."""
    child = tmp_path / "mh_child.py"
    child.write_text(textwrap.dedent(child_src))
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, str(child), str(p), str(n_proc), str(port)],
            env=_child_env(), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        for p in range(n_proc)
    ]
    results = {}
    try:
        for p, proc in enumerate(procs):
            out, err = proc.communicate(timeout=timeout_s)
            assert proc.returncode == 0, f"process {p} failed:\n{err[-2000:]}"
            line = [ln for ln in out.splitlines()
                    if ln.startswith("RESULT ")][-1]
            results[p] = json.loads(line[len("RESULT "):])
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
    return results
