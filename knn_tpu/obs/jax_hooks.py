"""JAX compile-event hook: every trace, lowering, backend compile and
persistent-cache lookup of the process, via ``jax.monitoring``, and the
record of a device program's first call.

JAX reports named durations (``/jax/core/compile/...``,
``/jax/compilation_cache/...``) through
``jax.monitoring.record_event_duration_secs`` and plain occurrences
(``/jax/compilation_cache/cache_hits``, ``cache_misses``) through
``record_event``; registering listeners is the supported way to observe
every XLA compile in the process — inline jit compiles, AOT
``lower().compile()`` calls, and cache lookups alike — without wrapping
any call site.  The listeners keep every key under
:data:`KEPT_PREFIXES` and mirror it into
``knn_tpu_jax_compiles_total`` / ``knn_tpu_jax_compile_seconds_total``,
labeled by the sanitized event key (a small, version-bounded set), and
into the process's running :func:`tallies`.

**A program's first call.**  The function that launches a device
program asks :func:`first_call_begin` just before the launch and hands
the answer to :func:`first_call_end` just after: two clock reads and a
subtraction of the tallies in the launching function itself, no frame
between it and the traced function.  Where JAX traced or compiled
nothing in between, the launch ran an executable the process already
had and nothing is recorded.  Where it did, this was the first call of
the program at this shape (a jitted program is one executable a shape
of its arguments), and the difference says what it did: traced,
lowered, compiled, or loaded from the persistent cache, and for how
long.  It becomes one ``program.first_call.<program>`` span, with the
key its builder gave the program (:func:`mark_built`: what it was built
for, shortened to what differs between deployments) and whatever the
launching site adds (the rows of the call).

:func:`install_compile_hook` is idempotent and safe to call from every
instrumented entry point (engine construction, ``run_job``);
it no-ops when the subsystem is disabled or the monitoring API is
absent (older jaxlibs), so no caller needs a guard.
"""

from __future__ import annotations

import re
import threading
import time
import weakref
from collections import deque
from typing import Optional

from knn_tpu.obs import names, registry, trace

_lock = threading.Lock()
_installed = False

_SANITIZE = re.compile(r"[^a-z0-9_]+")

#: the ``jax.monitoring`` keys kept (``compilation`` does not contain
#: ``compile``: a filter on that word alone loses every cache key)
KEPT_PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/")

#: monitoring key -> (tally counted, tally of its seconds or None)
_TALLY_OF = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (None, "lower_s"),
    "/jax/core/compile/backend_compile_duration": (
        "backend_compiles", "compile_s"),
    "/jax/compilation_cache/cache_hits": ("cache_hits", None),
    "/jax/compilation_cache/cache_misses": ("cache_misses", None),
    "/jax/compilation_cache/cache_retrieval_time_sec": (
        None, "cache_load_s"),
}
#: the process's running totals since the hook was installed.  A
#: backend compile is counted whether XLA compiled the program or the
#: persistent cache answered (JAX times both under one key): the hits
#: and misses beside it tell them apart.
_TALLIES = {key: zero for count, seconds in _TALLY_OF.values()
            for key, zero in ((count, 0), (seconds, 0.0)) if key}

#: per thread and monitoring key, the events seen lately as (when it
#: ended, its length): JAX times a jit traced inside another's trace
#: under the same key, so an event's own seconds are its length less
#: those of the events that ended inside it
_nested = threading.local()
_NESTED_KEPT = 4096

#: program object -> the key its builder gave it
_KEYS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _event_label(key: str) -> str:
    return _SANITIZE.sub("_", key.lower()).strip("_")


def _record(event: str, duration: Optional[float]) -> None:
    if not event.startswith(KEPT_PREFIXES):
        return
    try:
        label = _event_label(event)
        registry.counter(names.JAX_COMPILES, event=label).inc()
        if duration is not None:
            registry.counter(names.JAX_COMPILE_SECONDS,
                             event=label).inc(float(duration))
        count, seconds = _TALLY_OF.get(event, (None, None))
        own = 0.0
        if seconds and duration is not None:
            own = _own_seconds(event, float(duration))
        with _lock:
            if count:
                _TALLIES[count] += 1
            if seconds:
                _TALLIES[seconds] += own
    except Exception:  # noqa: BLE001 - a hook must never break compiles
        pass


def _own_seconds(event: str, duration: float) -> float:
    """``duration`` less the events of the same key that this thread saw
    end inside it (the listener runs as an event ends): summed, the own
    seconds are wall time, where the raw lengths count a nested trace
    once for every trace around it."""
    seen = _nested.__dict__.setdefault(event, deque(maxlen=_NESTED_KEPT))
    now = time.perf_counter()
    inside = 0.0
    while seen and seen[-1][0] >= now - duration:
        inside += seen.pop()[1]
    seen.append((now, duration))
    return max(duration - inside, 0.0)


def _on_duration(event: str, duration: float, **_kw) -> None:
    # **_kw: newer jax versions pass extra keyword context; ignore it
    _record(event, duration)


def _on_event(event: str, **_kw) -> None:
    _record(event, None)


def tallies() -> dict:
    """A copy of the running totals: ``traces``, ``trace_s``,
    ``lower_s``, ``backend_compiles``, ``compile_s``, ``cache_hits``,
    ``cache_misses``, ``cache_load_s``.  The seconds are each event's
    own (:func:`_own_seconds`), so they add up to wall time."""
    with _lock:
        return dict(_TALLIES)


def mark_built(program, key: str) -> None:
    """Called by a device program's builder (so once a program object):
    ``key`` is what it was built for, shortened to what differs between
    deployments; a first-call record of ``program`` carries it."""
    _KEYS[program] = key


def first_call_begin():
    """Just before a launch: what :func:`first_call_end` needs, or None
    when telemetry is off."""
    if not registry.enabled():
        return None
    return tallies(), time.perf_counter()


def first_call_end(begun, program, name: str,
                   trace_id: Optional[str] = None, **shape) -> None:
    """Just after the launch has returned to the host.  Nothing unless
    JAX traced or compiled over the bracket (on this thread or, rarely
    and then wrongly laid here, another); else one
    ``program.first_call.<name>`` span of the bracket's length with the
    program's ``key`` (its builder's, then ``shape``: what the launching
    site knows of this call, ``rows=...``) and the change of every tally
    over the bracket.  No-op for ``begun`` None."""
    if begun is None:
        return
    before, t0 = begun
    after = tallies()
    if (after["traces"] == before["traces"]
            and after["backend_compiles"] == before["backend_compiles"]):
        return
    seconds = time.perf_counter() - t0
    delta = {k: round(v - before[k], 6) if isinstance(v, float)
             else v - before[k] for k, v in after.items()}
    key = ",".join([_KEYS.get(program, "")]
                   + [f"{k}={v}" for k, v in shape.items()]).strip(",")
    trace.record_span(f"program.first_call.{name}", trace_id, seconds,
                      program=name, key=key, **delta)


def install_compile_hook() -> bool:
    """Register the listeners once per process; returns whether the hook
    is (now) active."""
    global _installed
    if not registry.enabled():
        return False
    with _lock:
        if _installed:
            return True
        try:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
        except Exception:  # noqa: BLE001 - older jax: no monitoring API
            return False
        _installed = True
        return True
