"""The one place the benchmark touches the system under test: placing a
configuration's corpus as a ``ShardedKNN``, listening for compiles, and
reading the program's own counters and span totals.  Everything else in
``benchmark/`` is the yardstick and imports nothing of ``knn_tpu``.
"""

from __future__ import annotations

import time
from typing import Dict, Tuple

import numpy as np

#: jax.monitoring tallies: how many programs XLA compiled, and how many
#: the persistent cache answered
COMPILES = {"backend_compiles": 0, "backend_compile_s": 0.0,
            "cache_hits": 0, "cache_misses": 0}
_listening = False


def listen_to_compiles() -> None:
    global _listening
    if _listening:
        return
    import jax

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILES["backend_compiles"] += 1
            COMPILES["backend_compile_s"] += float(duration)

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            COMPILES["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            COMPILES["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _listening = True


def enable_compile_cache() -> str:
    from knn_tpu.utils.compat import enable_compile_cache as enable

    return enable()


def place(config: dict, db: np.ndarray, chips: int, **kw):
    """``ShardedKNN`` over ``db`` on a (1, chips) mesh of the first
    ``chips`` devices, with the configuration's k, metric and exact-path
    tile; waits until the rows are on the device.  ``kw`` passes a
    public constructor argument through (the control's lower-precision
    path; no benchmark run passes any)."""
    import jax

    from knn_tpu.parallel import ShardedKNN, make_mesh

    mesh = make_mesh(1, chips, devices=jax.devices()[:chips])
    prog = ShardedKNN(db, mesh=mesh, k=int(config["k"]),
                      metric=config["metric"],
                      train_tile=config.get("train_tile"), **kw)
    jax.block_until_ready(prog._tp)
    return prog


def require(config: dict, stats: dict) -> None:
    """Hold one ``search_certified`` call to what the configuration's
    ``require`` entry says of it: where its knobs came from, and whether
    the kernel was compiled or interpreted."""
    want = config.get("require", {})
    src = stats["tuning"]["source"]
    if "tuning_source" in want and src != want["tuning_source"]:
        raise RuntimeError(
            f"knobs came from {src!r} ({stats['tuning'].get('cache_path')}),"
            f" not {want['tuning_source']!r}")
    interp = stats["pallas_knobs"]["interpret"]
    if "interpret" in want and interp is not want["interpret"]:
        raise RuntimeError(
            f"kernel ran with interpret={interp!r}; the configuration "
            f"requires {want['interpret']!r}")


def registry_snapshot() -> Dict[Tuple[str, Tuple[Tuple[str, str], ...]], tuple]:
    """The program's telemetry registry, flat: ``(metric, sorted label
    items) -> (value,)`` for counters and gauges, ``(count, sum)`` for
    histograms."""
    from knn_tpu import obs

    if not obs.enabled():
        raise RuntimeError(
            "the program's telemetry is off (KNN_TPU_OBS), so its "
            "counters and spans cannot be read")
    out = {}
    for name, m in obs.snapshot().items():
        for s in m["series"]:
            key = (name, tuple(sorted(s["labels"].items())))
            v = s["value"]
            out[key] = ((v["count"], v["sum"]) if m["type"] == "histogram"
                        else (v,))
    return out


def registry_delta(before: dict, after: dict) -> dict:
    """Per series, what was added between two snapshots."""
    out = {}
    for key, v in after.items():
        b = before.get(key, (0,) * len(v))
        out[key] = tuple(x - y for x, y in zip(v, b))
    return out


def now() -> float:
    return time.perf_counter()
