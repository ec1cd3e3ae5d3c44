"""Opt-in device trace capture — the deep-dive companion of the
roofline model.

The roofline names WHICH resource bounds a config; an on-chip XLA
trace shows WHERE inside the program the time actually goes (the
round-5 finding: the remaining gap needs on-chip profiling, not
another geometry sweep).  :func:`device_trace` wraps a code block in
``jax.profiler.trace`` (TensorBoard-loadable) and records the capture
as a ``profiler.trace`` telemetry event, so the emitted
tuning entry can carry its trace directory.

Gating — OFF by default, two ways in:

- ``KNN_TPU_PROFILE_DIR=<dir>``: the ambient env gate.  Honored only
  while telemetry is enabled (``KNN_TPU_OBS=0`` makes it a no-op,
  like every other obs surface).
- an explicit ``base_dir`` argument: an explicit flag is an explicit request and
  captures regardless of the obs switch (only the telemetry event is
  skipped when obs is off).

JAX imports lazily inside the context — this module stays importable
(and a no-op) in jax-free consumers."""

from __future__ import annotations

import contextlib
import os
import re
import threading
import time
from typing import Dict, Iterator, Optional

from knn_tpu.obs import registry, trace

#: env gate: a directory under which each capture gets its own
#: ``<section>`` subdirectory
PROFILE_ENV = "KNN_TPU_PROFILE_DIR"

_SECTION_RE = re.compile(r"[^A-Za-z0-9._-]+")

_cap_lock = threading.Lock()
#: sanitized section -> last capture directory in this process.
#: Introspection only (doctor/tests ask "what did this process
#: capture, where?"); the reconciler matches events to configs by the
#: on-disk convention (traceread.read_section resolves
#: ``<dir>/<sanitized section>``), never through this map.  Bounded:
#: sections are config shapes, finite in practice.
_CAPTURES: Dict[str, str] = {}
_CAPTURES_MAX = 64


def captures() -> Dict[str, str]:
    """Every section captured in this process and its trace directory
    (newest last).  Process-local introspection; event→config matching
    itself rides the capture-directory convention traceread reads."""
    with _cap_lock:
        return dict(_CAPTURES)


def reset_captures() -> None:
    """Drop the capture registry (test isolation)."""
    with _cap_lock:
        _CAPTURES.clear()


def profile_dir() -> Optional[str]:
    """The ambient capture directory, or None when unset or telemetry
    is disabled."""
    if not registry.enabled():
        return None
    return os.environ.get(PROFILE_ENV) or None


def sanitize_section(section: str) -> str:
    """Filesystem-safe capture name (cache keys carry ``|`` and
    spaces)."""
    return _SECTION_RE.sub("_", section).strip("_") or "trace"


@contextlib.contextmanager
def device_trace(section: str,
                 base_dir: Optional[str] = None) -> Iterator[Optional[str]]:
    """Capture an XLA device trace of the wrapped block under
    ``<dir>/<section>``; yields the trace directory, or None when no
    gate is open (the caller can skip its extra instrumented run
    entirely)."""
    d = base_dir if base_dir is not None else profile_dir()
    if not d:
        yield None
        return
    path = os.path.join(d, sanitize_section(section))
    import jax

    t0 = time.perf_counter()
    with jax.profiler.trace(path):
        yield path
    with _cap_lock:
        _CAPTURES.pop(sanitize_section(section), None)
        _CAPTURES[sanitize_section(section)] = path
        while len(_CAPTURES) > _CAPTURES_MAX:
            _CAPTURES.pop(next(iter(_CAPTURES)))
    trace.emit_event("profiler.trace", section=sanitize_section(section),
                     trace_dir=path,
                     dur_s=round(time.perf_counter() - t0, 4))
