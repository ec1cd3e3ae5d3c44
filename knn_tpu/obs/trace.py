"""Request-scoped trace spans + the structured JSONL event log.

A **trace id** is minted where a request enters the system
(``ServingEngine.submit`` for direct callers, ``QueryQueue.submit`` for
queued ones) and rides the request through micro-batching, dispatch,
and result join — so ONE request's queue-wait / compile / device / join
times are attributable end-to-end even when the request was coalesced
into a batch with strangers (each batch member keeps its own id; the
batch dispatch event lists the member ids it carried).

A **span** is a timed scope: ``with span("serving.dispatch",
trace_id=tid, op="search"):`` records wall duration into the
``knn_tpu_span_seconds{span=...}`` histogram and emits one structured
event.  Events land in a bounded in-memory ring (always, when enabled)
and, when ``KNN_TPU_OBS_LOG`` names a path, as JSON lines on disk —
machine-scrapable, one object per line, append-only.  A child span
carries its parent's name as a ``parent`` attribute, so the log
rebuilds the tree of one trace id and a stage's self time is its
length less its children's.

The same scope is also a ``jax.profiler.TraceAnnotation`` named
``knn.<span>``: in a profiler capture (``obs.profiler.device_trace``)
the program's stages lie on the host planes of the ``.xplane.pb``, on
the clock of the device's ``XLA Ops`` line, so a device idle gap can be
laid against the stage the host was in.  Outside a capture an
annotation costs one flag test.  The class is looked up only once JAX
is imported: this package imports without it.

A bulk call that launches device programs keeps a :class:`CallAccount`
(:func:`call_account`): told of every launch and of every result's
arrival, it records once, when the call ends, the seconds in flight by
program and the exposed seconds in which nothing was, with the sums of
any stage pieces measured in parts (:func:`phase`) — one record a call
however many sub-batches the call was cut into.

Disabled mode (``KNN_TPU_OBS=0``): :func:`span` yields a shared inert
span and opens no annotation, :func:`new_trace_id` returns None,
:func:`call_account` hands out the shared inert account, and
:func:`emit_event` drops — zero allocation on the hot path.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import sys
import threading
import time
import types
import uuid
from collections import defaultdict, deque
from typing import Optional

from knn_tpu.obs import ident, names, registry

#: env var naming the JSONL sink (unset = in-memory ring only)
LOG_ENV = "KNN_TPU_OBS_LOG"

#: env var capping the JSONL sink's size before rotation (bytes)
LOG_MAX_BYTES_ENV = "KNN_TPU_OBS_LOG_MAX_BYTES"

#: default rotation cap: a long-running serving process must not grow
#: the event log unboundedly; at ~200 bytes/event this holds ~300k
#: events live plus one rotated generation
DEFAULT_LOG_MAX_BYTES = 64 * 1024 * 1024

#: in-memory event ring size — enough to hold a serving trace's worth of
#: spans for tests/debugging without unbounded growth
RING_SIZE = 8192


def new_trace_id() -> Optional[str]:
    """A 16-hex-char request id, or None when the subsystem is off (so
    propagation sites can thread it unconditionally)."""
    if not registry.enabled():
        return None
    return uuid.uuid4().hex[:16]


class EventLog:
    """Bounded ring + optional size-capped JSONL file sink.  ``emit`` is
    thread-safe and never raises into the instrumented path: a failing
    sink counts ``knn_tpu_events_dropped_total`` instead.

    The file sink ROTATES: when appending the next line would push the
    file past ``max_bytes`` (``KNN_TPU_OBS_LOG_MAX_BYTES``), the current
    file is atomically renamed to ``<path>.1`` (replacing any previous
    generation) and a fresh file begins — so a long-running serving
    process holds at most two generations on disk, and because rotation
    happens on LINE boundaries (never mid-write), both sides of the cut
    are always valid JSONL."""

    def __init__(self, path: Optional[str] = None, ring: int = RING_SIZE,
                 max_bytes: Optional[int] = None):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=int(ring))
        self._path = path
        self._fh = None
        self._size = 0  # bytes in the current generation (set on open)
        if max_bytes is None:
            try:
                max_bytes = int(os.environ.get(
                    LOG_MAX_BYTES_ENV, DEFAULT_LOG_MAX_BYTES))
            except ValueError:
                max_bytes = DEFAULT_LOG_MAX_BYTES
        self._max_bytes = max(1, int(max_bytes))

    @property
    def path(self) -> Optional[str]:
        return self._path

    def emit(self, event: dict) -> None:
        evt = {"ts": round(time.time(), 6), **event}
        # serialize OUTSIDE the lock: concurrent serving threads must
        # contend only for the append/write, not for json encoding.
        # FILE lines additionally carry the process identity stamp
        # (knn_tpu.obs.ident): rotated/merged multi-process logs must
        # stay attributable to a host, and the fleet trace stitcher
        # keys cross-host segments off it.  The in-memory ring stays
        # unstamped — it never leaves the process.
        line = (json.dumps({**evt, "identity": ident.identity()}) + "\n"
                if self._path is not None else None)
        with self._lock:
            self._ring.append(evt)
            if line is not None:
                try:
                    if self._fh is None:
                        self._fh = open(self._path, "a")
                        self._fh.seek(0, 2)
                        self._size = self._fh.tell()
                    # json.dumps default is ASCII-escaped, so character
                    # count == byte count for the size accounting
                    if (self._size > 0
                            and self._size + len(line) > self._max_bytes):
                        # rotate BETWEEN lines: close, atomic rename to
                        # the .1 generation, start fresh — a reader of
                        # either file only ever sees whole JSON lines
                        self._fh.close()
                        self._fh = None
                        os.replace(self._path, self._path + ".1")
                        self._fh = open(self._path, "a")
                        self._size = 0
                    self._fh.write(line)
                    self._fh.flush()
                    self._size += len(line)
                except OSError:
                    registry.counter(names.EVENTS_DROPPED).inc()

    def recent(self, n: Optional[int] = None) -> list:
        """Newest-last copy of the ring (``n`` trailing events)."""
        with self._lock:
            evts = list(self._ring)
        return evts if n is None else evts[-n:]

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                try:
                    self._fh.close()
                except OSError:
                    pass
                self._fh = None


_state_lock = threading.Lock()
_log: Optional[EventLog] = None


def get_event_log() -> EventLog:
    global _log
    log = _log
    if log is None:
        with _state_lock:
            if _log is None:
                _log = EventLog(os.environ.get(LOG_ENV) or None)
            log = _log
    return log


def reset_event_log(path: Optional[str] = None,
                    from_env: bool = False,
                    max_bytes: Optional[int] = None) -> EventLog:
    """Swap in a fresh event log (tests; ``from_env`` re-reads
    ``KNN_TPU_OBS_LOG``; ``max_bytes`` overrides the rotation cap)."""
    global _log
    with _state_lock:
        if _log is not None:
            _log.close()
        _log = EventLog(
            os.environ.get(LOG_ENV) or None if from_env else path,
            max_bytes=max_bytes)
        return _log


def emit_event(name: str, **fields) -> None:
    """One structured event (non-span), dropped when disabled."""
    if not registry.enabled():
        return
    get_event_log().emit({"type": "event", "name": name, **fields})


class Span:
    """A live span: mutate ``attrs`` (via :meth:`set`) before the scope
    closes and the attributes ride the emitted event."""

    __slots__ = ("name", "trace_id", "attrs")

    def __init__(self, name: str, trace_id: Optional[str], attrs: dict):
        self.name = name
        self.trace_id = trace_id
        self.attrs = attrs

    def set(self, key: str, value) -> None:
        self.attrs[key] = value


class _NoopSpan:
    __slots__ = ()
    name = None
    trace_id = None
    attrs = types.MappingProxyType({})  # nothing is ever set

    def set(self, key: str, value) -> None:
        pass


NOOP_SPAN = _NoopSpan()

_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "current_span", default=NOOP_SPAN)


def current_span():
    """The innermost :func:`span` scope open on this thread, for code
    below a stage that alone knows how much work the stage did (it
    ``.set``s attributes; the scope's owner still emits the event).  The
    inert span when none is open or tracing is off."""
    return _CURRENT.get()


def record_span(name: str, trace_id: Optional[str], dur_s: float,
                **attrs) -> None:
    """Record an already-measured span (the engine's latency join points
    measure durations themselves): histogram observe + one event."""
    if not registry.enabled():
        return
    registry.histogram(names.SPAN_SECONDS, span=name).observe(dur_s)
    evt = {"type": "span", "span": name, "dur_s": round(dur_s, 6), **attrs}
    if trace_id is not None:
        evt["trace_id"] = trace_id
    get_event_log().emit(evt)


#: prefix of every span's profiler annotation
ANNOTATION_PREFIX = "knn."

_NO_ANNOTATION = contextlib.nullcontext()


def _annotation(name: str):
    """The profiler-clock twin of a span: a ``TraceAnnotation`` named
    ``knn.<name>``, or a shared null scope while JAX is not imported
    (nothing this package does imports it)."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return _NO_ANNOTATION
    return prof.TraceAnnotation(ANNOTATION_PREFIX + name)


@contextlib.contextmanager
def phase(seconds: dict, key: str, annotation: Optional[str] = None):
    """A piece of a stage that is summed and recorded once a call, not
    once a scope: the scope's length is ADDED to ``seconds[key]`` (made
    at 0.0 where missing), and with ``annotation`` the scope is a
    ``knn.<annotation>`` profiler annotation on the calling thread, so a
    device idle gap can be laid against the piece.  No span, no event:
    the owner of ``seconds`` records the sum (:func:`record_span`,
    :class:`CallAccount`).  Disabled mode runs the body and touches
    nothing."""
    if not registry.enabled():
        yield
        return
    t0 = time.perf_counter()
    try:
        if annotation is None:
            yield
        else:
            with _annotation(annotation):
                yield
    finally:
        seconds[key] = seconds.get(key, 0.0) + time.perf_counter() - t0


class CallAccount:
    """One OUTERMOST call's account of the device programs it launched,
    kept by the call itself (a local of the call, handed down beside its
    trace id: two threads' calls never share one) and recorded ONCE when
    the call ends, however many sub-batches it was cut into.

    Two entry points: :meth:`launched` at the moment a program call has
    returned to the host, :meth:`ready` where the host learns its result
    is there.  From these, per call: the seconds during which at least
    one launch of a program was outstanding (``inflight``, by program),
    the launches by program, and the **exposed** seconds, in which
    nothing the call launched was in flight: call start to the first
    launch, every stretch from the moment the last outstanding result
    was ready to the next launch, and from then to the call's end.  By
    construction ``exposed + (union of the in-flight intervals) = the
    call's length``; exposed is at most the device's idle time of the
    call, since the device may also idle while a program is "in
    flight" (launch latency, the gap before the host asks).

    :meth:`add` sums any other piece of the call that is measured in
    parts (a stage's phases over the sub-batches), for the same
    once-a-call record.  ``stages`` (``{span: its parent}``) are the
    pieces that are the call's own stages, closed once a sub-batch
    (:func:`stage`): scopes that do not overlap, so their sum is a
    child of the call like any other and is recorded with ``parent``,
    where every other record of the account names the call as
    ``account_of``.  :meth:`close` records everything through
    :func:`record_span`: ``<root>.exposed``, ``<root>.inflight.<program>``
    for every program named at construction, which are the programs
    any call of its kind may launch (0.0 where one did not run, so a
    reader never finds a series missing) and for any other the call did
    launch (a placement's one-time build: in the call that made it and
    in no other), one span for every summed piece, and
    ``knn_tpu_program_launches_total``."""

    __slots__ = ("root", "_t0", "_idle_from", "_busy_from", "_out",
                 "_n_out", "_since", "inflight", "launches", "exposed",
                 "union", "before_first", "between", "sums", "sum_attrs",
                 "stages")

    def __init__(self, root: str, programs, pieces=(), stages=None):
        self.root = root
        self._t0 = self._idle_from = time.perf_counter()
        self._busy_from = 0.0
        # outstanding, by program
        self._out = defaultdict(int, dict.fromkeys(programs, 0))
        self._n_out = 0  # outstanding, all programs
        self._since = {}
        self.inflight = defaultdict(float, dict.fromkeys(programs, 0.0))
        self.launches = defaultdict(int, dict.fromkeys(programs, 0))
        self.exposed = self.union = self.between = 0.0
        self.before_first = None
        self.stages = dict(stages or {})
        self.sums = dict.fromkeys((*self.stages, *pieces), 0.0)
        self.sum_attrs = {}

    def launched(self, program: str) -> None:
        now = time.perf_counter()
        if not self._n_out:
            gap = now - self._idle_from
            self.exposed += gap
            if self.before_first is None:
                self.before_first = gap
            else:
                self.between += gap
            self._busy_from = now
        if not self._out[program]:
            self._since[program] = now
        self._out[program] += 1
        self._n_out += 1
        self.launches[program] += 1

    def ready(self, program: str) -> None:
        # a fetch that failed and was re-dispatched reports once: a
        # result nobody launched here is nobody's
        if not self._out[program]:
            return
        now = time.perf_counter()
        self._out[program] -= 1
        self._n_out -= 1
        if not self._out[program]:
            self.inflight[program] += now - self._since[program]
        if not self._n_out:
            self.union += now - self._busy_from
            self._idle_from = now

    def add(self, piece: str, seconds: float, **attr_sums) -> None:
        self.sums[piece] = self.sums.get(piece, 0.0) + seconds
        if attr_sums:
            kept = self.sum_attrs.setdefault(piece, {})
            for k, v in attr_sums.items():
                # a count stays whole: no 0.0 to start from
                kept[k] = kept[k] + v if k in kept else v

    def close(self, trace_id: Optional[str], of: str) -> None:
        """Record the account (class docstring).  ``of`` is the call's
        own span: the records name it as ``account_of``, not as
        ``parent``, since they are sums over the call that overlap its
        stages, and a reader of self times must not subtract them."""
        now = time.perf_counter()
        after = 0.0
        if self._n_out:  # launched and never waited for
            for program, n in self._out.items():
                if n:
                    self.inflight[program] += now - self._since[program]
            self.union += now - self._busy_from
        else:
            after = now - self._idle_from
            self.exposed += after
        before = self.before_first
        if before is None:  # nothing was launched: all of it came "before"
            before, after = after, 0.0
        record_span(
            f"{self.root}.exposed", trace_id, self.exposed, account_of=of,
            call_s=now - self._t0, inflight_union_s=self.union,
            before_first_launch_s=before, after_last_ready_s=after,
            between_s=self.between, launches=sum(self.launches.values()))
        for program, seconds in self.inflight.items():
            record_span(f"{self.root}.inflight.{program}", trace_id,
                        seconds, account_of=of,
                        launches=self.launches[program])
            registry.counter(names.PROGRAM_LAUNCHES, program=program).inc(
                self.launches[program])
        for piece, seconds in self.sums.items():
            whose = ({"parent": self.stages[piece]} if piece in self.stages
                     else {"account_of": of})
            record_span(piece, trace_id, seconds, **whose,
                        **self.sum_attrs.get(piece, {}))


class BlockAccount:
    """The stage sums of ONE block of a pipelined call (the bulk
    self-join's, knn_tpu.join.engine): what :func:`stage` and ``add``
    hand it is summed over the block's launches and recorded once when
    the block ends, as :class:`CallAccount` records a call's, so the
    series of a stage counts blocks, each the size of one closed-loop
    call.  Launches and fetches are passed on to the CALL's account:
    two blocks are in flight at once, so the seconds in flight and the
    exposed seconds are no property of a block."""

    __slots__ = ("call", "sums", "sum_attrs")

    def __init__(self, call, stages):
        self.call = call
        self.sums = dict.fromkeys(stages, 0.0)
        self.sum_attrs = {}

    def launched(self, program: str) -> None:
        self.call.launched(program)

    def ready(self, program: str) -> None:
        self.call.ready(program)

    add = CallAccount.add

    def close(self, trace_id: Optional[str], of: str) -> None:
        """Record every summed stage, a child of the block's span
        ``of``."""
        for piece, seconds in self.sums.items():
            record_span(piece, trace_id, seconds, parent=of,
                        **self.sum_attrs.get(piece, {}))


class _NoopAccount:
    __slots__ = ()

    def launched(self, program: str) -> None:
        pass

    def ready(self, program: str) -> None:
        pass

    def add(self, piece: str, seconds: float, **attr_sums) -> None:
        pass

    def close(self, trace_id, of: str) -> None:
        pass


NOOP_ACCOUNT = _NoopAccount()


def call_account(root: str, programs, pieces=(), stages=None):
    """A :class:`CallAccount` whose clock starts now, or the shared inert
    one when the subsystem is off."""
    if not registry.enabled():
        return NOOP_ACCOUNT
    return CallAccount(root, programs, pieces, stages)


def block_account(call, stages):
    """A :class:`BlockAccount` under the call's account ``call``, or the
    shared inert one when the subsystem is off."""
    if not registry.enabled():
        return NOOP_ACCOUNT
    return BlockAccount(call, stages)


@contextlib.contextmanager
def span(name: str, trace_id: Optional[str] = None, **attrs):
    """Timed scope -> ``knn_tpu_span_seconds{span=name}`` + one event +
    a ``knn.<name>`` profiler annotation.  Yields the :class:`Span`
    (``.trace_id``, ``.set``); disabled mode yields the shared inert
    span and records nothing.

    ``trace_id`` is PROPAGATED, never minted here: ids are created where
    a request enters the system (``new_trace_id()`` at the submit
    sites), so a span without one (a warmup compile, a background task)
    emits without a trace_id field instead of fabricating a phantom
    single-span request."""
    if not registry.enabled():
        yield NOOP_SPAN
        return
    sp = Span(name, trace_id, dict(attrs))
    token = _CURRENT.set(sp)
    t0 = time.perf_counter()
    try:
        with _annotation(name):
            yield sp
    finally:
        _CURRENT.reset(token)
        record_span(name, sp.trace_id, time.perf_counter() - t0,
                    **sp.attrs)


@contextlib.contextmanager
def stage(acct, name: str, trace_id: Optional[str] = None, **attrs):
    """One occurrence of a stage that a call closes once a SUB-BATCH: the
    scope of :func:`span` (the current :class:`Span` for code below to
    ``.set`` on and, with ``trace_id``, to record its own children
    under; the ``knn.<name>`` profiler annotation, one an
    occurrence, so a device idle gap is still laid against each
    stretch), but no record of its own.  Its length and its attributes,
    which are all counts or seconds, are ADDED to the call's account
    ``acct`` (:meth:`CallAccount.add`), which records the stage ONCE
    when the call ends: the series ``knn_tpu_span_seconds{span=name}``
    counts calls whatever the sub-batches, and a call of one sub-batch
    records what a :func:`span` would have.  (The body is
    :func:`span`'s but for its last line; that one is on the serving
    path's every request and stays one generator deep.)"""
    if not registry.enabled():
        yield NOOP_SPAN
        return
    sp = Span(name, trace_id, dict(attrs))
    token = _CURRENT.set(sp)
    t0 = time.perf_counter()
    try:
        with _annotation(name):
            yield sp
    finally:
        _CURRENT.reset(token)
        acct.add(name, time.perf_counter() - t0, **sp.attrs)
