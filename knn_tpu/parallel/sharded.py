"""Distributed KNN over a 2-D device mesh — the SPMD program that replaces
the reference's rank-parallel main loop (knn_mpi.cpp:224-227,308-393).

Two sharded axes (see parallel.mesh):

- **query axis** — the reference's strategy: queries scattered, train
  replicated, zero inter-device traffic during the distance phase, results
  stay sharded (the gather at knn_mpi.cpp:340,383 is just an output spec).
- **db axis** — beyond the reference: train rows sharded too.  Each device
  computes a *local* top-k against its train shard with globalized indices,
  then the shards merge.  Two merge strategies, bitwise-identical results:

    * ``allgather``: one `lax.all_gather` of the [Qs, k] candidate lists
      over the db axis, one lexicographic re-select.  One collective, P*k
      candidate volume — the right choice when k*P is small.
    * ``ring``: P-1 `lax.ppermute` steps passing a constant [Qs, k] buffer
      around the db ring, merging locally each step — the KNN analogue of
      ring attention (SURVEY.md §5 long-context row).  Constant memory,
      overlappable with compute; the right shape when P or k is large.

  The merge is the lexicographic (distance, index) top-k (ops.topk), which
  is associative + commutative, so both strategies and any device count
  agree bitwise with the single-device result.

The reference's distributed min-max normalize (knn_mpi.cpp:229-306) maps to
:func:`sharded_minmax`: local extrema + `lax.pmin`/`lax.pmax` over the mesh
— its two `MPI_Allreduce` calls (knn_mpi.cpp:276-277) verbatim.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from knn_tpu import obs
from knn_tpu.analysis.widths import lane_tiled
from knn_tpu.obs import jax_hooks as _hooks
from knn_tpu.obs import names as _mn
from knn_tpu.ops import refine as _refine
from knn_tpu.ops.normalize import local_minmax, minmax_apply
from knn_tpu.ops.topk import knn_search_tiled, merge_topk, topk_pairs
from knn_tpu.ops.vote import majority_vote
from knn_tpu.parallel import crossover
from knn_tpu.parallel.collectives import (
    allreduce_max,
    allreduce_min,
    gather,
    replicate,
    shard,
)
from knn_tpu.parallel.mesh import (
    DB_AXIS,
    HOST_AXIS,
    QUERY_AXIS,
    db_axes,
    db_topology,
    pad_to_multiple,
)

_INT_SENTINEL = jnp.iinfo(jnp.int32).max

#: queries a masked re-select takes at a time (the repair of a
#: filtered call): one compiled shape whatever the fallbacks, and
#: a block's unpacked validity is this many bytes a shard row
_MASKED_RESELECT_ROWS = 64

#: Module-level jitted rescale so repeated jobs hit the jit cache.
_minmax_apply_jit = jax.jit(minmax_apply)


def _ring_merge(d, i, k: int, axis_name: str, n_shards: int):
    """P-1 ppermute steps around the ring; each device ends with the global
    top-k.  Order-independent thanks to the lexicographic merge."""
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def body(_, carry):
        acc_d, acc_i, buf_d, buf_i = carry
        buf_d = lax.ppermute(buf_d, axis_name, perm)
        buf_i = lax.ppermute(buf_i, axis_name, perm)
        acc_d, acc_i = merge_topk(acc_d, acc_i, buf_d, buf_i, k)
        return acc_d, acc_i, buf_d, buf_i

    acc_d, acc_i, _, _ = lax.fori_loop(1, n_shards, body, (d, i, d, i))
    return acc_d, acc_i


def _allgather_merge(d, i, k: int, axis_name: str):
    ad = gather(d, axis_name, axis=0, tiled=False)  # [P, Qs, k]
    ai = gather(i, axis_name, axis=0, tiled=False)
    qs = d.shape[0]
    ad = jnp.moveaxis(ad, 0, 1).reshape(qs, -1)
    ai = jnp.moveaxis(ai, 0, 1).reshape(qs, -1)
    return topk_pairs(ad, ai, k)


def _db_shard_index(hosts: int, chips: int):
    """This device's GLOBAL db-shard index inside shard_map: the flat
    db-axis position, or host-major ``host * chips + chip`` on a
    hierarchical mesh — the row-block order ``P((HOST_AXIS, DB_AXIS))``
    shards with."""
    idx = lax.axis_index(DB_AXIS)
    if hosts > 1:
        idx = lax.axis_index(HOST_AXIS) * chips + idx
    return idx


def _merge_shards(d, gi, keep: int, hosts: int, chips: int,
                  merge: str, dcn_merge: Optional[str]):
    """The hierarchical top-k merge tree, inside shard_map: per-chip
    candidate lists reduce per-host over the ICI db axis first (the
    ``merge`` strategy), then per-host lists merge globally over the
    DCN host axis (``dcn_merge``; strategies may differ — the measured
    crossover picks each level by its own shard count).  Flat meshes
    (hosts == 1) run the single-level merge unchanged.  The
    lexicographic (distance, index) merge is associative + commutative
    (ops.topk), so the two-level tree is bitwise-identical to the flat
    merge — pinned in tests/test_multihost.py.  Every op of it carries
    the device scope ``knn.merge``; on one shard that scope holds
    nothing."""
    with jax.named_scope(SCOPE_MERGE):
        if chips > 1:
            if merge == "ring":
                d, gi = _ring_merge(d, gi, keep, DB_AXIS, chips)
            else:
                d, gi = _allgather_merge(d, gi, keep, DB_AXIS)
        if hosts > 1:
            strat = dcn_merge or merge
            if strat == "ring":
                d, gi = _ring_merge(d, gi, keep, HOST_AXIS, hosts)
            else:
                d, gi = _allgather_merge(d, gi, keep, HOST_AXIS)
    return d, gi


def _pack_bits_u32(mask: jax.Array) -> jax.Array:
    """[Q, B] bool -> [Q, ceil(B/32)] uint32, bit j of word w = column
    32*w + j.  Shrinks the near-tie mask's device->host transfer
    32x."""
    n_q, b = mask.shape
    nw = -(-b // 32)
    padded = jnp.pad(mask.astype(jnp.uint32), ((0, 0), (0, nw * 32 - b)))
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(padded.reshape(n_q, nw, 32) * weights, axis=-1,
                   dtype=jnp.uint32)


def unpack_bits_u32(words: np.ndarray, n_bits: int) -> np.ndarray:
    """Host inverse of :func:`_pack_bits_u32`: [Q, nw] uint32 -> [Q,
    n_bits] bool."""
    w = np.asarray(words, dtype=np.uint32)
    bits = (w[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(w.shape[0], -1)[:, :n_bits].astype(bool)


def _analysis_window(k: int, m: int) -> int:
    """Width of the device rank-analysis window: the packed program
    output's column layout and _certify_pallas's unpack
    both derive from THIS — one home, or unpack_certified
    silently slices shifted columns."""
    return min(k + 17, m + 1)


def _overlap_ratio(intervals) -> float:
    """Fraction of a bounded-depth dispatch loop's wall time during
    which >= 2 batches were simultaneously in flight (interval =
    dispatch start to result-repair end; join.engine's superblock
    pipelines) — the honest, host-measurable overlap number: it reports
    dispatch-timeline concurrency, not device-internal overlap (which
    needs a hardware trace: jax.profiler).  0.0 for < 2 batches."""
    if len(intervals) < 2:
        return 0.0
    events = []
    for s, e in intervals:
        events.append((s, 1))
        events.append((e, -1))
    events.sort()
    in_flight, overlapped, prev = 0, 0.0, None
    for t, delta in events:
        if prev is not None and in_flight >= 2:
            overlapped += t - prev
        in_flight += delta
        prev = t
    wall = max(e for _, e in intervals) - min(s for s, _ in intervals)
    return overlapped / wall if wall > 0 else 0.0


#: db-axis merge strategies — the canonical home is
#: parallel.crossover.STRATEGIES (the measured-crossover module)
_MERGES = crossover.STRATEGIES

#: Certified-path coarse selectors.  "exact" ranks every row (float32
#: lexicographic top-k); "approx" uses the hardware bin-reduction behind
#: lax.approx_max_k (count-below certificate); "pallas" routes to the
#: one-pass self-certifying kernel program (_pallas_certified_program) —
#: it never reaches _local_topk/_knn_program.
SELECTORS = ("exact", "approx", "pallas")
#: ``predict_certified``'s votes: the reference's unweighted majority and
#: the k-NN evaluation protocol's weighted one
VOTES = ("majority", "softmax")


def _local_topk(q, t, k, metric, n_train, train_tile, compute_dtype, selector,
                recall_target=None, hosts=1, chips=1):
    """Local shard top-k with global train indices.

    The last db shard may contain zero-padding rows; their distances are
    forced to +inf *inside* the exact/approx selection (``n_valid``) so a
    pad row can never displace a real neighbor.  The pallas selector masks
    after its bin reduction — a pad row can then shadow one bin of the
    last shard, which the certified pipeline detects and repairs.
    """
    db_idx = _db_shard_index(hosts, chips)
    n_local_valid = jnp.clip(n_train - db_idx * t.shape[0], 0, t.shape[0])
    if selector == "exact":
        d, i = knn_search_tiled(
            q, t, k, metric, train_tile=train_tile, compute_dtype=compute_dtype,
            n_valid=n_local_valid,
        )
    elif selector == "approx":
        from knn_tpu.ops.topk import knn_search_approx

        kw = {} if recall_target is None else {"recall_target": recall_target}
        d, i = knn_search_approx(
            q, t, k, compute_dtype=compute_dtype, n_valid=n_local_valid, **kw
        )
    else:
        raise ValueError(f"unknown selector {selector!r}; expected one of {SELECTORS}")
    pad = i >= n_local_valid
    gi = jnp.where(pad, _INT_SENTINEL, i + db_idx * t.shape[0])
    return jnp.where(pad, jnp.inf, d), gi


def _merged_topk(q, t, k, metric, merge, n_train, train_tile, compute_dtype,
                 hosts, chips, selector="exact", recall_target=None,
                 dcn_merge=None):
    """Shared SPMD body: local shard top-k, then the (hierarchical)
    merge across the db sharding."""
    d, gi = _local_topk(q, t, k, metric, n_train, train_tile, compute_dtype,
                        selector, recall_target, hosts, chips)
    return _merge_shards(d, gi, k, hosts, chips, merge, dcn_merge)


@functools.lru_cache(maxsize=64)
def _knn_program(
    mesh: Mesh,
    k: int,
    metric: str,
    merge: str,
    n_train: int,
    train_tile: Optional[int],
    compute_dtype,
    selector: str = "exact",
    recall_target: Optional[float] = None,
    dcn_merge: Optional[str] = None,
):
    hosts, chips = db_topology(mesh)

    def spmd(q, t):
        return _merged_topk(
            q, t, k, metric, merge, n_train, train_tile, compute_dtype,
            hosts, chips, selector, recall_target, dcn_merge,
        )

    prog = jax.jit(
        jax.shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(QUERY_AXIS), P(db_axes(mesh))),
            out_specs=(P(QUERY_AXIS), P(QUERY_AXIS)),
            check_vma=False,  # merged output is replicated along db by construction
        ),
    )
    # at the repair's re-select k is the widened width
    _hooks.mark_built(prog, f"k={k},selector={selector},tile={train_tile}")
    return prog


@functools.lru_cache(maxsize=32)
def _hosttier_program(
    mesh: Mesh,
    k: int,
    metric: str,
    merge: str,
    train_tile: Optional[int],
    compute_dtype,
    dcn_merge: Optional[str] = None,
):
    """The per-sweep program of the host-RAM shard tier: one db SEGMENT
    (streamed host->device this sweep) searched exactly like a resident
    placement, except the valid-row count rides as a TRACED ``[1]``
    operand — so the ragged tail segment pads to the same shape as
    every full segment and all sweeps share ONE compiled executable
    (the flat-per-sweep-latency contract)."""
    hosts, chips = db_topology(mesh)

    def spmd(q, t, n_valid):
        db_idx = _db_shard_index(hosts, chips)
        n_local = jnp.clip(n_valid[0] - db_idx * t.shape[0], 0, t.shape[0])
        d, i = knn_search_tiled(
            q, t, k, metric, train_tile=train_tile,
            compute_dtype=compute_dtype, n_valid=n_local,
        )
        pad = i >= n_local
        gi = jnp.where(pad, _INT_SENTINEL, i + db_idx * t.shape[0])
        d = jnp.where(pad, jnp.inf, d)
        return _merge_shards(d, gi, k, hosts, chips, merge, dcn_merge)

    return jax.jit(
        jax.shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(QUERY_AXIS), P(db_axes(mesh)), P()),
            out_specs=(P(QUERY_AXIS), P(QUERY_AXIS)),
            check_vma=False,
        ),
    )


def segment_search_program(
    mesh: Mesh,
    k: int,
    metric: str = "l2",
    merge: Optional[str] = None,
    *,
    train_tile: Optional[int] = None,
    compute_dtype=None,
    dcn_merge: Optional[str] = None,
):
    """Public handle on the host-tier segment program for callers that
    stream GATHERED row blocks instead of contiguous db segments — the
    IVF probed-list path (knn_tpu.ivf.index): the gather of probed list
    extents pads to a fixed rung and masks via the same traced
    ``n_valid`` operand, so probing shrinks streamed bytes without new
    kernels or a recompile per probe set.  ``merge`` resolves through
    the same crossover table a :class:`ShardedKNN` placement uses;
    the returned callable is ``prog(qp, tp, n_valid)`` with the
    :func:`_hosttier_program` contract (shared lru compile cache)."""
    _, chips = db_topology(mesh)
    merge, _src = crossover.resolve_merge(merge, k, chips)
    dtype_key = (
        None if compute_dtype is None else jnp.dtype(compute_dtype).name
    )
    return _hosttier_program(mesh, k, metric, merge, train_tile,
                             dtype_key, dcn_merge=dcn_merge)


def query_stream_program(
    mesh: Mesh,
    k: int,
    n_train: int,
    metric: str = "l2",
    merge: Optional[str] = None,
    *,
    train_tile: Optional[int] = None,
    compute_dtype=None,
    dcn_merge: Optional[str] = None,
):
    """Public handle on the resident-db search program for callers that
    stream QUERY superblocks instead of serving one request batch — the
    bulk kNN-join engine (knn_tpu.join): superblock i+1's host->device
    query transfer overlaps superblock i's device compute under the
    bounded-depth drain-oldest discipline.  The returned callable is
    ``prog(qp, tp)`` with the :func:`_knn_program` contract (shared lru
    compile cache: a join stream and a serving placement of the same
    shape share one executable)."""
    _, chips = db_topology(mesh)
    merge, _src = crossover.resolve_merge(merge, k, chips)
    dtype_key = (
        None if compute_dtype is None else jnp.dtype(compute_dtype).name
    )
    return _knn_program(mesh, k, metric, merge, n_train, train_tile,
                        dtype_key, dcn_merge=dcn_merge)


#: bounded-retry policy for transient device failures inside long sweeps
#: (SURVEY §5 failure row; the same per-batch unit streaming.py uses).
#: ValueError/TypeError are caller bugs and never retried.  Waits double
#: per attempt so the window can outlast a real hiccup, not just an
#: instantaneous glitch.
_RETRY_ATTEMPTS = 3
_RETRY_WAIT_S = 0.5

#: error-text signatures that identify a DETERMINISTIC failure — one a
#: retry can only repeat (ADVICE r4: a Mosaic compile error or an OOM
#: was retried 3x with ~3.5 s of backoff per batch of a long sweep
#: before surfacing).  Matched case-insensitively against
#: "TypeName: message".
_DETERMINISTIC_SIGNATURES = (
    "resource_exhausted", "resource exhausted", "out of memory",
    "invalid_argument", "invalid argument", "failed_precondition",
    "failed precondition", "unimplemented", "mosaic",
)
#: signatures of KNOWN-transient failures (the gRPC status vocabulary a
#: lost device connection surfaces): these always get the full
#: bounded-retry window, even when consecutive attempts fail
#: identically.  Checked BEFORE the
#: deterministic set: a flake whose text happens to also embed a
#: deterministic token (e.g. "UNAVAILABLE: peer ran out of memory")
#: must keep its retry window — erring toward retry costs seconds,
#: erring toward fail-fast kills a recoverable sweep.
_TRANSIENT_SIGNATURES = (
    "unavailable", "deadline_exceeded", "deadline exceeded", "aborted",
    "cancelled", "connection", "socket", "data_loss", "data loss",
)


def _classify_failure(e: Exception) -> str:
    """'transient' (full retry window) | 'deterministic' (never retry) |
    'unknown' (retry, but stop once the identical error repeats)."""
    s = f"{type(e).__name__}: {e}".lower()
    if any(sig in s for sig in _TRANSIENT_SIGNATURES):
        return "transient"
    if any(sig in s for sig in _DETERMINISTIC_SIGNATURES):
        return "deterministic"
    return "unknown"


def _retry_wait(attempt: int) -> None:
    import time

    time.sleep(_RETRY_WAIT_S * (2 ** attempt))


def _should_give_up(cls: str, e: Exception,
                    prev: Optional[Exception]) -> bool:
    """True when retrying ``e`` (already classified as ``cls``) cannot
    help: an unknown error whose repr exactly repeats the previous
    attempt's is deterministic in effect, whatever its name."""
    return (cls == "unknown" and prev is not None
            and repr(e) == repr(prev))


def _retry_transient(fn, what: str = "device call",
                     attempts: int = _RETRY_ATTEMPTS):
    """Call ``fn`` with bounded retries on transient (non-ValueError/
    TypeError) failures — the dispatch-side half of the retry story.
    Deterministic failures (compile errors, OOM — _classify_failure)
    propagate immediately; an unrecognized error that repeats verbatim
    stops retrying early."""
    err = None
    for attempt in range(attempts):
        try:
            return fn()
        except (ValueError, TypeError):
            raise  # caller bug: retry cannot help
        except Exception as e:
            cls = _classify_failure(e)
            if cls == "deterministic":
                raise
            if _should_give_up(cls, e, err):
                raise RuntimeError(
                    f"{what} failed after {attempt + 1} attempts "
                    f"(identical error repeated)") from e
            err = e
            if attempt + 1 < attempts:
                _retry_wait(attempt)
    raise RuntimeError(f"{what} failed after {attempts} attempts") from err


def _fetch_or_redispatch(out, redo, what: str = "device fetch",
                         attempts: int = _RETRY_ATTEMPTS,
                         fetch=np.asarray):
    """``np.asarray(out)``, re-dispatching via ``redo()`` on transient
    failure — the fetch-side half: async device errors surface at the
    host transfer, after the original dispatch call already returned.
    Same give-up policy as :func:`_retry_transient`.  ``fetch`` is the
    blocking device->host read itself (:func:`_staged_fetch` splits it
    into its wait and its copy); every attempt goes through it."""
    try:
        return fetch(out)
    except (ValueError, TypeError):
        raise
    except Exception as e:
        if _classify_failure(e) == "deterministic":
            raise
        err = e
    for attempt in range(attempts - 1):
        _retry_wait(attempt)
        try:
            return fetch(redo())
        except (ValueError, TypeError):
            raise
        except Exception as e:
            cls = _classify_failure(e)
            if cls == "deterministic":
                raise
            if _should_give_up(cls, e, err):
                raise RuntimeError(
                    f"{what} failed after {attempt + 2} attempts "
                    f"(identical error repeated)") from e
            err = e
    raise RuntimeError(f"{what} failed after {attempts} attempts") from err


#: root span of one ``search_certified`` call; its stages name it as
#: their ``parent`` (docs/OBSERVABILITY.md "Span lifecycle")
_CALL_SPAN = "certified.call"
#: root span of one ``range_search_certified`` call: the first pass's
#: ``certified.call`` tree hangs under it (same trace id), beside
#: ``certified.range_complete`` and ``certified.range_pack``
_RANGE_SPAN = "certified.range_call"
#: what a metric other than l2 adds to a call on either side of the l2
#: machinery: ONE span a call, the sum of both sides (inner product: the
#: zero column before, the float64 scores after; cosine: the batch's
#: float64 norms and its float32 unit rows before, nothing after), and
#: each side as a span and a profiler annotation of its own: only what
#: lies BEFORE the first launch can go under it, and a cosine call that
#: is cut into sub-batches puts it there: ``.before`` is its FIRST
#: sub-batch's map, ``.under`` the later ones', each made with the
#: earlier sub-batches' launches already queued (:class:`_UnitQueries`)
_METRIC_SPAN = "certified.metric_map"
_METRIC_BEFORE = "certified.metric_map.before"
_METRIC_UNDER = "certified.metric_map.under"
_METRIC_AFTER = "certified.metric_map.after"
#: the seconds a call's ``map_s`` holds, by the child span each is
_METRIC_SIDES = {"before_s": _METRIC_BEFORE, "under_s": _METRIC_UNDER,
                 "after_s": _METRIC_AFTER}
#: the completion of a range call's truncated queries, and where its host
#: time goes: the phases are summed over the completion's sub-batches by
#: the call's account and recorded once a call, children of the span
_RANGE_COMPLETE_SPAN = "certified.range_complete"
_RANGE_WAIT = "certified.range_complete.wait"
_RANGE_DECODE = "certified.range_complete.decode"
_RANGE_SCORE = "certified.range_complete.score"
_RANGE_HOST_SCAN = "certified.range_complete.host_scan"
_RANGE_ORDER = "certified.range_complete.order"
_RANGE_PHASES = (_RANGE_WAIT, _RANGE_DECODE, _RANGE_SCORE, _RANGE_HOST_SCAN,
                 _RANGE_ORDER)


#: root of the once-a-call records of a call's account
#: (``obs.trace.CallAccount``): ``certified.exposed``,
#: ``certified.inflight.<program>``
_ACCOUNT_ROOT = "certified"
#: the stage pieces a ``selector="pallas"`` call sums over its
#: sub-batches and records once, at 0.0 where a piece did not run
_UNPACK_COPIES = "certified.unpack.copies"
_PALLAS_PIECES = (_refine.PHASE_BUFFERS, _refine.PHASE_SCORE,
                  _refine.PHASE_ORDER, _UNPACK_COPIES)
#: the stages such a call closes once a SUB-BATCH (``obs.trace.stage``):
#: each is summed by the call's account too and recorded once a call, a
#: child of the call, so its series counts calls however the call was cut
_PALLAS_STAGES = ("certified.dispatch", "certified.device_wait",
                  "certified.d2h", "certified.unpack",
                  "certified.rank_correct")
#: a voted call's (``predict_certified(vote="softmax")``) host stage in
#: ``certified.rank_correct``'s place: the float64 re-vote of the
#: flagged queries, once a sub-batch and once more for the uncertified
_VOTE_STAGE = "certified.vote_repair"


#: one block of a bulk self-join call (knn_tpu.join.engine), launch to
#: answer; its stages' parent
_BLOCK_SPAN = "join.block"
#: what a block sums over its launches and records once
#: (``obs.trace.BlockAccount``): the search call's stages and pieces,
#: and the repair (the re-select's launch, then its settling)
_BLOCK_SUMS = _PALLAS_STAGES + ("certified.repair",) + _PALLAS_PIECES
#: queries a block's re-select takes at a time: ONE compiled shape a
#: placement whatever the count of flagged queries (a search call's
#: re-select is compiled a count: 5-11 s each on the chip, root PERF.md
#: section 7), run once by the placement's first self-join call, so
#: that no block of any call meets a compile
_SELF_RESELECT_ROWS = 32


def _call_account(selector: str, *more: str, voted: bool = False):
    """The account of one outermost certified call: the device programs
    a call of this ``selector`` can launch (``more``: those of the call
    it is the first pass of), and the pieces it sums (a ``voted`` call's
    ``certified.vote_repair`` among its stages: it records every stage
    of the search's too, at 0.0 where it has none, so a reader never
    finds a series missing; a range call's completion phases, children
    of ``certified.range_complete``, likewise)."""
    stages = (dict.fromkeys(_RANGE_PHASES, _RANGE_COMPLETE_SPAN)
              if "range" in more else {})
    if selector == "pallas":
        stages.update(dict.fromkeys(
            _PALLAS_STAGES + ((_VOTE_STAGE,) if voted else ()), _CALL_SPAN))
        return obs.trace.call_account(
            _ACCOUNT_ROOT, ("certified", "reselect") + more, _PALLAS_PIECES,
            stages)
    return obs.trace.call_account(
        _ACCOUNT_ROOT, ("counted", "count", "reselect") + more,
        stages=stages)


def _metric_map_seconds() -> dict:
    """A call's ``map_s`` before anything is mapped: the seconds of
    ``certified.metric_map`` by side, and ``under_batches``, the
    sub-batches mapped with a launch already queued."""
    return {**dict.fromkeys(_METRIC_SIDES, 0.0), "under_batches": 0}


def _record_metric_map(trace_id, metric: str, map_s: dict) -> None:
    """A dot, cosine or voted call's ``certified.metric_map``: the sum of
    its sides, a child of the call, and each side (``before_s``,
    ``under_s``, ``after_s`` of ``map_s``) as a child of the sum, 0.0
    where a metric or a call has nothing on that side."""
    obs.record_span(_METRIC_SPAN, trace_id,
                    sum(map_s[key] for key in _METRIC_SIDES),
                    parent=_CALL_SPAN, metric=metric, **map_s)
    for key, span in _METRIC_SIDES.items():
        obs.record_span(span, trace_id, map_s[key], parent=_METRIC_SPAN,
                        metric=metric)


def _staged_fetch(acct=obs.trace.NOOP_ACCOUNT):
    """A ``fetch`` for :func:`_fetch_or_redispatch` that reads a
    certified sub-batch's packed output in two stages at the one point
    where the host blocks anyway: ``certified.device_wait`` until the
    device has finished it (which the call's account ``acct`` is told),
    then ``certified.d2h`` for the copy; both are the account's to
    record, once a call."""

    def fetch(out):
        with obs.trace.stage(acct, "certified.device_wait"):
            jax.block_until_ready(out)
            acct.ready("certified")
        with obs.trace.stage(acct, "certified.d2h") as sp:
            arr = np.asarray(out)
            sp.set("d2h_bytes", arr.nbytes)
        return arr

    return fetch


#: inner-product placements: the most, as a share of M (the largest
#: squared row norm), by which the exact augmented squared distances of
#: two placed rows can disagree with the order of their inner products.
#: :func:`_augment_dot` appends a_t = fl32(sqrt(M - n_t)), n_t the
#: float64 squared norm, so a_t = sqrt(M - n_t) (1 + x) with |x| <=
#: 2^-24 and the placed row's squared norm is M + c_t,
#:   c_t = (M - n_t)(2x + x^2) + (|t|^2 - n_t),  |c_t| < 2^-23 M (1 + 2^-20)
#: (the float64 sum, difference and root err by under 2^-45 M together).
#: The exact distance between the augmented query (q, 0) and row is then
#:   D'(t) = |q|^2 + M - 2 q.t + c_t,
#: and two rows with D'(u) - D'(v) > |c_u - c_v| have q.u < q.v.  Per
#: row 2^-22 M is twice the bound; a pair gets 2^-21 M.
DOT_AUG_SLACK = 2.0 ** -21
#: rows a block of :func:`_augment_dot`'s float64 norm pass widens
_NORM_BLOCK_ROWS = 8192


def _augment_dot(train: np.ndarray):
    """MIPS -> squared L2 by norm augmentation: ``train`` [n, d] float32
    with one more column ``sqrt(M - |t|^2)``, M the largest squared row
    norm, so that against a query with a zero appended
    ``|q' - t'|^2 = |q|^2 + M - 2 q.t`` but for the appended column's
    rounding (``DOT_AUG_SLACK``).  Norms are taken in float64 a block of
    rows at a time (the only float64 temporaries, 8192 rows each); the
    augmented array is the one copy made.  Returns (augmented rows, M,
    the largest squared norm of an augmented row, in float64)."""
    train = np.asarray(train, np.float32)
    n, d = train.shape
    norm2 = np.empty(n)
    for lo in range(0, n, _NORM_BLOCK_ROWS):
        sq = train[lo:lo + _NORM_BLOCK_ROWS].astype(np.float64)
        np.multiply(sq, sq, out=sq)
        np.sum(sq, axis=-1, out=norm2[lo:lo + _NORM_BLOCK_ROWS])
    shift = float(norm2.max()) if n else 0.0
    out = np.empty((n, d + 1), np.float32)
    out[:, :d] = train
    out[:, d] = np.sqrt(np.maximum(shift - norm2, 0.0))
    norm2 += out[:, d].astype(np.float64) ** 2
    return out, shift, float(norm2.max()) if n else 0.0


def _row_normalize_f64(x: np.ndarray) -> np.ndarray:
    """Unit rows, float64 norms -> float32 result (accuracy: the cast is
    the only f32 rounding, ~2^-24 relative per entry)."""
    n = np.linalg.norm(x.astype(np.float64), axis=-1, keepdims=True)
    return (x / np.maximum(n, 1e-300)).astype(np.float32)


#: cosine placements: the most by which the exact squared distances D'
#: of two PLACED rows (float32 unit rows, against the float32 unit
#: query) can disagree with the order of the cosines of the rows and the
#: query AS GIVEN; absolute, since everything placed has unit length.
#: With u = q/|q| and v = t/|t| exact, :func:`_unit_rows` places q^ =
#: u + e_q and t^ = v + e_t: every entry is the float64 quotient (off by
#: under 2^-32 relative for any width under 2^20) rounded once to
#: float32, so |e| <= x := 2^-24 (1 + 2^-8), the room covering the
#: float64 part and an entry under 2^-126 (rounded, or flushed by the
#: device, by at most 2^-126).  With c(t) = 1 - u.v the cosine distance,
#:   D'(t) = |u - v|^2 + 2 (u - v).(e_q - e_t) + |e_q - e_t|^2
#:         = 2 c(t) + p_t,   |p_t| <= 2 * 2 * 2x + 4x^2 < 2^-21 (1 + 2^-7)
#: for one row, and for two rows the query's own rounding meets both:
#:   p_a - p_b = 2 (v_b - v_a).e_q - 2 (u - v_a).e_a + 2 (u - v_b).e_b
#:               + |e_q - e_a|^2 - |e_q - e_b|^2,
#:   |p_a - p_b| <= 12x + 4x^2 < 0.76 * 2^-20.
#: So two placed rows with D'(a) - D'(b) > 2^-20 have c(a) > c(b), and a
#: row with D'(t) > 2 c + 2^-20 has c(t) > c for any float64 c.  A row
#: of zero norm has no direction: it is placed as it is (D' = |q^|^2,
#: about 1) and ranked by the host at cosine 0 (search_certified).
COS_UNIT_SLACK = 2.0 ** -20

#: the weighted vote on a cosine placement (``predict_certified(vote=
#: "softmax")``): the most by which the device's cosine distance c32 =
#: d32 / 2 of a candidate can differ from the float64 c of the rows as
#: given.  d32 is within RANK_SLACK D' / 3 of the exact placed distance
#: D' = 2 c + p_t, |p_t| < 2^-21 (1 + 2^-7) (above), so
#:   |c32 - c| <= 2^-19 (2 c + 2^-20) / 3 + 2^-22 (1 + 2^-7)
#:             <  2^-18 (c + 1/8),
#: the bound ``search_certified`` states for its cosine distances, and
#: with c <= 2 under ``VOTE_COS_ERR`` for every candidate.
VOTE_COS_ERR = 2.0 ** -18 * (2.0 + 0.125)
#: float32 roundings allowed a weight besides its distance's error, in
#: units of 2^-24: the device's ``exp`` itself (ops.vote.exp_weight, 2
#: ulp: float32 multiplies and adds, not the chip's transcendental unit)
#: and the comparison of two totals (a difference and a product); the argument's own two roundings
#: (1 / T as float32, then c32 times it, |argument| <= 2 / T) are counted
#: in :func:`vote_delta` as ``4 * 2^-24 / T``.
_VOTE_EXP_ULPS = 32
#: the least temperature the device's float32 weights can hold: a
#: device weight is exp(-c32 / T) (the factor exp(1 / T) is the host's,
#: in float64), at least exp(-2 / T), and exp(-64) is a normal float32.
VOTE_MIN_TEMPERATURE = 2.0 ** -5


def vote_delta(temperature: float, k: int) -> float:
    """The relative bound ``delta`` of the vote certificate's margin
    test: a device total S32 of a class (a float32 sum of at most ``k``
    weights ``exp(-c32 / T)``) is within ``delta * S`` of the float64
    total S of the same neighbours (both without the factor ``exp(1 /
    T)``, which the host applies in float64).  A weight's distance is off
    by at most ``VOTE_COS_ERR``, so the weight by a factor within
    ``exp(+-VOTE_COS_ERR / T)``; its argument's roundings add ``4 * 2^-24
    / T``, the ``exp`` and the float32 sum of k terms ``(k +
    _VOTE_EXP_ULPS) 2^-24``.  1.2e-4 at T = 0.07, k = 20.  Two totals a >=
    b on the device with ``a - b > 2 delta a`` have ``A (1 - delta) > B
    (1 + delta)`` in float64: the classes rank as the device ranked
    them."""
    return (float(np.expm1(VOTE_COS_ERR / temperature))
            + 4 * 2.0 ** -24 / temperature
            + (k + _VOTE_EXP_ULPS) * 2.0 ** -24)


#: the seed of every interleaved placement's row order
_INTERLEAVE_SEED = 0x1D5
#: the metrics under which a placement handed ``row_attr`` is
#: interleaved: those a filtered call runs under (_CallFilter)
_INTERLEAVED_METRICS = ("l2", "sql2", "euclidean", "cosine")


def _interleave_order(n: int) -> np.ndarray:
    """The row order of an INTERLEAVED placement of ``n`` rows, int32
    ``[n]``: position ``p`` on the device holds the caller's row
    ``order[p]``.  ONE pseudo-random permutation a row count (a
    fixed-seed generator: a function of ``n`` alone), so that the rows
    any predicate on an attribute keeps fall into the kernel's bins
    (row tile, lane) independently of the attribute's order among the
    rows, which is what ops.pallas_knn.bin_overflow_share assumes.  A
    stride would spread a contiguous range as well and be undone by an
    attribute of the stride's period; a random order has no such
    adversary."""
    return np.random.default_rng(_INTERLEAVE_SEED).permutation(n).astype(
        np.int32)


def _rows_at(table: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """``table[rows]`` wherever ``rows`` names a placed row, and
    ``rows`` itself wherever it does not (-1, a pad row, the sentinel):
    device positions to the caller's ids through an interleaved
    placement's order, or ids to positions through its inverse."""
    rows = np.asarray(rows)
    placed = (rows >= 0) & (rows < table.size)
    return np.where(placed, table.take(rows, mode="clip"), rows)


def _map_unit_rows(x: np.ndarray, unit: np.ndarray, norms: np.ndarray,
                   lo: int, hi: int,
                   order: Optional[np.ndarray] = None) -> np.ndarray:
    """Rows ``lo .. hi`` of the cosine map of ``x`` [n, d] float32 into
    ``unit`` [n, d] float32 and ``norms`` [n] float64.  The norm is
    ops.refine.norms_of_f64's (so the host's float64 cosines divide by
    the very numbers the placement divided by), the quotient is taken in
    float64 and rounded ONCE to float32; a row of zero norm stays zero.
    Every row's arithmetic is its own, so the values are the same bits
    however the rows are cut into calls.  With ``order`` (an interleaved
    placement, :func:`_interleave_order`) ``unit[p]`` is the unit row of
    ``x[order[p]]``, gathered a block at a time on its way to float64,
    and ``norms`` stays in the order of ``x``.  Returns the
    float64 squares' buffer, [hi - lo, d], for the caller to reuse."""
    if order is None:
        rows, nb = x[lo:hi].astype(np.float64), norms[lo:hi]
    else:
        rows = x[order[lo:hi]].astype(np.float64)
        nb = np.empty(rows.shape[0])
    sq = _refine.norms_of_f64(rows, nb)
    np.divide(rows, nb[:, None], out=rows, where=nb[:, None] > 0)
    unit[lo:hi] = rows
    if order is not None:
        norms[order[lo:hi]] = nb
    return sq


def _unit_rows(x: np.ndarray, order: Optional[np.ndarray] = None):
    """Cosine placement of float32 rows ``x`` [n, d]: ``(unit rows [n, d]
    float32, norms [n] float64, the largest squared norm of a unit row
    as rounded, whether every rounded value is bf16-exact)``, the rows
    and norms :func:`_map_unit_rows`'s (``order``: the unit rows in an
    interleaved placement's order, the norms in the order given).  A
    block of rows at a time
    (ops.refine._block_rows; the only float64 temporaries), the blocks
    shared among the re-score pool's threads where there are several:
    each writes its own rows."""
    from knn_tpu.ops.pallas_knn import lo_halves_zero

    x = np.asarray(x, np.float32)
    n, d = x.shape
    unit = np.empty((n, d), np.float32)
    norms = np.empty(n)
    block = _refine._block_rows(d)
    starts = range(0, n, block)
    # (the map's own signature where the rows keep their order)
    ordered = () if order is None else (order,)

    def fill(lo: int):
        sq = _map_unit_rows(x, unit, norms, lo, lo + block, *ordered)
        out = unit[lo : lo + block]
        np.multiply(out, out, out=sq, dtype=np.float64)
        return float(sq.sum(-1).max()), lo_halves_zero(out)

    parts = _refine.pool_map(fill, starts)
    return (unit, norms, max((m for m, _ in parts), default=0.0),
            all(z for _, z in parts))


#: float32 values below which a part of a sub-batch's map is not worth a
#: thread of its own: handing a part to the pool costs some tens of
#: microseconds, which is what the map of this many values takes
_MAP_PART_ELEMS = 1 << 16


def _even_parts(lo: int, hi: int, d: int) -> list:
    """Rows ``lo .. hi`` of width ``d`` cut EVENLY into ``(lo, hi)``
    parts for the re-score pool: as many parts as blocks of
    ops.refine._block_rows (no float64 temporary outgrows one), made up
    to whole rounds of the pool's threads, so that the map is as long
    as its rows' share a thread and not as its largest block (1,024
    rows of 1,536 columns are one block of 682 and one of 342, and four
    parts of 256 here); one part where the rows are too few to share
    (``_MAP_PART_ELEMS``)."""
    n, threads = hi - lo, _refine._POOL_THREADS
    parts = -(-n // _refine._block_rows(d))
    parts = -(-parts // threads) * threads
    parts = max(1, min(parts, n * d // _MAP_PART_ELEMS))
    step = -(-n // parts)
    return [(at, min(at + step, hi)) for at in range(lo, hi, step)]


class _UnitQueries:
    """A cosine call's queries on their way to unit rows: ``rows`` [n, d]
    float32 and ``norms`` [n] float64 (:func:`_unit_rows`' of the whole
    batch, to the bit) are allocated at once and FILLED in row order as
    the call asks (:meth:`fill`), which :class:`_QueryBatches` does a
    sub-batch at a time, each immediately before its dispatch: the
    device is idle only for the first sub-batch's map, and the later
    ones run on the host under the launches already queued.  What reads
    all of ``rows`` or ``norms`` does so after the last sub-batch is
    dispatched, or asks for all of them first (``_kernel_terms``).

    The call's ``map_s`` gets the seconds: the first fill's to
    ``before_s`` (phase ``certified.metric_map.before``: nothing of the
    call is launched yet), every later one's to ``under_s``
    (``certified.metric_map.under``) and one more ``under_batches``."""

    def __init__(self, host_q: np.ndarray, map_s: dict):
        self._given, self._map_s = host_q, map_s
        self.rows = np.empty(host_q.shape, np.float32)
        self.norms = np.empty(host_q.shape[0])
        self._filled = 0

    def _map(self, part):
        _map_unit_rows(self._given, self.rows, self.norms, *part)

    def fill(self, hi: int) -> None:
        """Map the rows not yet mapped up to row ``hi`` (clipped to the
        batch), shared evenly among the pool's threads."""
        lo, hi = self._filled, min(hi, self.rows.shape[0])
        if lo >= hi:
            return
        key = "under_s" if lo else "before_s"
        with obs.trace.phase(self._map_s, key, _METRIC_SIDES[key]):
            _refine.pool_map(self._map,
                             _even_parts(lo, hi, self.rows.shape[1]))
        if lo:
            self._map_s["under_batches"] += 1
        self._filled = hi


class _QueryBatches:
    """The launches of one certified call: ``(lo, chunk, pad)`` a batch
    of ``bs`` queries, in order: the placed queries ``q_np[lo : lo +
    bs]``, the last batch padded with ``pad`` zero rows to the one
    compiled shape.  A sequence that cuts a batch when the loop that
    dispatches it reaches it, and hands the same batches to every later
    pass; with ``unit`` (a cosine call, ``q_np`` its ``unit.rows``) the
    batch's rows are mapped first (:class:`_UnitQueries`), so a
    sub-batch's map lies immediately before its dispatch."""

    def __init__(self, q_np: np.ndarray, bs: int,
                 unit: Optional[_UnitQueries] = None):
        self._q, self._bs, self._unit = q_np, bs, unit
        self._starts = range(0, q_np.shape[0], bs)
        self._cut = []

    def __len__(self) -> int:
        return len(self._starts)

    def __iter__(self):
        for n, lo in enumerate(self._starts):
            if n == len(self._cut):
                if self._unit is not None:
                    self._unit.fill(lo + self._bs)
                chunk = self._q[lo : lo + self._bs]
                pad = self._bs - chunk.shape[0]
                if pad:  # one compiled shape for the tail too
                    chunk = np.pad(chunk, ((0, pad), (0, 0)))
                self._cut.append((lo, chunk, pad))
            yield self._cut[n]


class _CallFilter:
    """What one ``search_certified`` call holds its queries to, and the
    ONE place that knows which maker of validity words serves it:
    ``filter_tags`` against the placed tag index (the Pallas kernel
    ``filter_mask``) or ``filter_range`` against the placed attribute
    (the kernel ``range_mask``).  Which it is follows from the call's
    arguments
    and nothing else.  Checked at construction (a call that cannot be
    answered raises there, with what is left); :meth:`place` resolves
    the maker's placement at the kernel's row tile; :meth:`words` is
    the maker of a batch's words for the dispatch loop and the repair
    (``ShardedKNN._filter_words``, whichever the maker);
    :meth:`valid_rows` the host's statement of the predicate;
    :meth:`told` says what the call was.

    POSITIONS ON THE DEVICE, IDS ON THE HOST (:class:`ShardedKNN`).
    What this class hands the DEVICE is laid out as the placed rows
    are: the attribute through ``ShardedKNN._attr_rows`` (an
    interleaved placement's in its row order), the words bit for row
    position.  What it hands the HOST is in the caller's ids:
    ``spec``, :meth:`valid_rows` (ascending ids, off ``_row_attr`` as
    given), :meth:`told`'s counts.  It applies no map itself: the
    repair's re-select maps its result (``search_certified``), and the
    host scan gathers ``db_np[valid_rows(pos)]`` from the rows as
    given.  The tag index is the same two-sided thing: its ``host``
    bags by id, its ``device`` bitmaps and lists by position
    (``ShardedKNN._tag_index``)."""

    def __init__(self, knn: "ShardedKNN", selector: str, filter_tags,
                 filter_range):
        if filter_tags is not None and filter_range is not None:
            raise ValueError(
                "filter_tags and filter_range together are not built: "
                "the AND of the two makers' words has no test or cell "
                "yet; pass one")
        self.maker = "tags" if filter_tags is not None else "range"
        name = f"filter_{self.maker}"
        if self.maker == "tags" and knn._row_tags is None:
            raise ValueError(
                "filter_tags needs the rows' tag bags: construct "
                "ShardedKNN with row_tags=(indptr, tags)")
        if self.maker == "range" and knn._row_attr is None:
            raise ValueError(
                "filter_range needs the rows' attribute: construct "
                "ShardedKNN with row_attr=<one whole number a row>")
        if selector != "pallas" or knn.metric == "dot":
            raise ValueError(
                f"{name} is applied inside the certified kernel: "
                f"selector='pallas' on a cosine or l2 placement only "
                f"(got selector={selector!r}, metric={knn.metric!r}); "
                f"the counted selectors' two passes and a dot "
                f"placement's augmented rows take no validity words")
        self._knn, self._given = knn, (
            filter_tags if filter_range is None else filter_range)

    def check(self, n_q: int) -> None:
        """The caller's array, held to the call's ``n_q`` queries: int32
        ``[n_q, 2]``, tag ids or INCLUSIVE attribute bounds."""
        from knn_tpu.ops import tagfilter

        self.spec = (tagfilter.check_filter_tags(self._given, n_q)
                     if self.maker == "tags"
                     else tagfilter.range_bounds(self._given, n_q))

    def require_tiled(self, kernel: str) -> None:
        if kernel != "tiled":
            raise ValueError(
                f"filter_{self.maker}: kernel={kernel!r} takes no "
                f"per-query validity words, only the tiled kernel's body "
                f"applies them; use kernel='tiled'")

    def place(self, tile: int, interpret: bool, trace_id, acct) -> None:
        """The maker's placement in the layout of the resolved row tile
        (built by the first call that needs it): the tag index or the
        placed attribute, each what ``ShardedKNN._filter_words`` takes
        as ``index``."""
        knn = self._knn
        self.index = (knn._tag_index(tile) if self.maker == "tags"
                      else knn._attr_rows(tile))
        self._launch = (interpret, trace_id, acct)

    def words(self, sel=None):
        """``mask(lo, take, rows)`` over the call's queries (``sel``: a
        subset of them by position, the repair's flagged):
        ``ShardedKNN._filter_words`` over their tag ids or bounds."""
        return self._knn._filter_words(
            self.spec if sel is None else self.spec[sel], self.index,
            *self._launch)

    def valid_rows(self, pos: int) -> np.ndarray:
        """Rows (ascending) query ``pos`` of the call may return."""
        from knn_tpu.ops import tagfilter

        if self.maker == "tags":
            return tagfilter.valid_rows(
                *self.index["host"], self._knn.n_train, *self.spec[pos])
        return tagfilter.range_valid_rows(self._knn._row_attr,
                                          *self.spec[pos])

    def told(self, found: np.ndarray) -> dict:
        """What the call was held to, in ``stats["filter"]``'s form,
        from how many rows each query's answer holds (``found``), the
        counters told."""
        knn, n_q = self._knn, found.shape[0]
        told = {"filter": self.maker,
                "short": int(((found > 0) & (found < knn.k)).sum()),
                "empty": int((found == 0).sum())}
        if self.maker == "tags":
            from knn_tpu.ops import tagfilter

            queries = _mn.FILTER_QUERIES
            told.update(tagfilter.lookup_forms(
                self.index["slots"], self.index["counts"], self.spec))
            obs.counter(_mn.FILTER_LIST_IDS).inc(told["list_ids"])
        else:
            # how many placed rows the ranges hold, summed over the
            # queries: what selectivity the call saw
            queries = _mn.FILTER_RANGE_QUERIES
            ordered = knn._attr_sorted()
            told["valid_rows"] = int((
                np.searchsorted(ordered, self.spec[:, 1], side="right")
                - np.searchsorted(ordered, self.spec[:, 0], side="left")
            ).clip(0).sum())
            obs.counter(_mn.FILTER_RANGE_VALID_ROWS).inc(told["valid_rows"])
        # whether the placement spread the rows the predicate keeps over
        # the kernel's bins (ShardedKNN: an interleaved placement)
        told["interleaved"] = knn._row_order is not None
        for outcome, n_out in (
                ("full", n_q - told["short"] - told["empty"]),
                ("short", told["short"]), ("empty", told["empty"])):
            obs.counter(queries, outcome=outcome).inc(n_out)
        return told


def _tier_budget(explicit: Optional[int]) -> Optional[int]:
    """The per-host HBM budget of the host-RAM tier: the constructor's
    argument, else ``KNN_TPU_HOSTTIER_BUDGET_BYTES``, else None (no
    bound: everything is placed resident)."""
    budget = explicit
    if budget is None:
        import os as _os

        env_b = _os.environ.get("KNN_TPU_HOSTTIER_BUDGET_BYTES", "").strip()
        if env_b:
            try:
                budget = int(env_b)
            except ValueError as e:
                raise ValueError(
                    f"KNN_TPU_HOSTTIER_BUDGET_BYTES={env_b!r} is not "
                    f"an int") from e
    if budget is not None and budget <= 0:
        raise ValueError(f"hbm_budget_bytes must be > 0, got {budget}")
    return budget


def _outgrows(budget: Optional[int], hosts: int, rows: int, width: int,
              itemsize: int) -> bool:
    """Whether a ``[rows, width]`` placement is over the hosts' HBM
    budgets together (None: no budget, never)."""
    from knn_tpu.analysis import hbm

    return budget is not None and hbm.placement_bytes(
        rows, width, itemsize) > budget * hosts


class ShardedKNN:
    """A placed distributed-KNN program: the database is padded, sharded
    along the db axis, and transferred **once** at construction; every
    subsequent :meth:`search`/:meth:`predict` call reuses the placement and
    the compiled SPMD program.  This is the handle long-running services and
    the batched pipeline use — the one-shot :func:`sharded_knn` /
    :func:`sharded_knn_predict` wrappers construct a throwaway instance.

    The reference has no equivalent: its train set is re-broadcast every
    process launch (knn_mpi.cpp:224-225).

    ``_tp`` is the placed rows, a ``jax.Array`` ``[rows, placed width]``
    sharded along the db axis (None under the host-RAM tier).  The placed
    width is the width given (a dot placement's with its augmentation
    column) where that is a whole number of 128-column lane tiles, and
    the next such number where it is not (192 -> 256, 960 -> 1,024), the
    columns past the given width all zero: a narrower array lies
    column-major on a TPU and every program that reads it row-major
    copies the whole of it first, in every call.  Zero columns add
    nothing to a squared difference, an absolute difference, an inner
    product or a norm, so no answer moves; queries are widened to match
    where they are placed (:meth:`_place_queries`), the host's copies
    stay at the given width (:meth:`_host_train`), and ``dim_in`` is the
    caller's.  A pre-placed ``jax.Array`` is used as it is handed in.

    **An interleaved placement: positions on the device, ids on the
    host.**  Host rows handed ``row_attr`` under l2 or cosine (the
    metrics a filtered call runs under), resident, are laid out on the
    device in ONE fixed pseudo-random order, a function of the row
    count alone (:func:`_interleave_order`, ``_row_order``: position
    ``p`` holds the caller's row ``_row_order[p]``; pad rows stay at
    the end and ``n_train`` means what it meant): the rows, the placed
    attribute (:meth:`_attr_rows`), a quantized placement made from
    them.  A kernel bin is (row tile, lane), and the certificate's
    survivor depth assumes a query's nearest valid rows fall into the
    bins independently (ops.pallas_knn.bin_overflow_share); a range on
    an attribute that follows the rows' order (an id, a time stamp)
    keeps rows of few tiles and broke that for every query of a narrow
    range; in a random order the rows ANY range keeps lie over all the
    bins.  No argument, environment variable or knob: the
    constructor's ``row_attr`` is the whole statement, a shuffled
    attribute loses nothing by it, and the order on the device is
    private.  Everything the HOST holds stays in the caller's order
    (``_train_host`` / :meth:`_host_train`, ``_row_attr``,
    ``_cos_norms``, ``_labels_host``), so every float64 stage, the tie
    order (distance, then the caller's index) and the filter's host
    scan work on ids as they always did.  A row index becomes an id at
    the ONE place a path brings it to the host (:meth:`_row_ids`): the
    unpacked window in ``_certify_pallas``, the counted selectors'
    coarse candidates, the repair's re-select (plain and masked), the
    range completion's decoded words, the voted call's candidate
    windows, and the float32 top-k launches' one exit
    (:meth:`_answers_by_id`: :meth:`search` and :meth:`radius_search`,
    the serving engine's buckets, ``knn_join(mode="stream")``; it puts
    equal distances in id order, and says what k columns cannot give
    back where more copies of a row tie than there are columns left);
    what reads a row's BIN from an answer takes the inverse first
    (:meth:`_row_places`).  What the DEVICE gathers by position lies as
    the rows do: the attribute, the labels (``predict``, the weighted
    vote's program), and with ``row_tags`` beside ``row_attr`` the tag
    index's bitmaps and lists (:meth:`_tag_index`; the host's bags stay
    by id).  What joins the placed rows to themselves by position has
    no map built and refuses: the self-join (:meth:`self_join_call`).
    A placement without ``row_attr``, a dot placement,
    a pre-placed array (it keeps its order, and with a sorted attribute
    its full bins) and the host-RAM tier are laid out as given and hold
    no map: ``_row_order`` is None and :meth:`_row_ids` returns its
    argument.
    """

    def __init__(
        self,
        train: jax.Array,
        *,
        mesh: Mesh,
        k: int,
        metric: str = "l2",
        merge: Optional[str] = None,
        dcn_merge: Optional[str] = None,
        train_tile: Optional[int] = None,
        compute_dtype=None,
        labels=None,
        num_classes: Optional[int] = None,
        n_train: Optional[int] = None,
        hbm_budget_bytes: Optional[int] = None,
        row_tags=None,
        row_attr=None,
    ):
        # merge strategies resolve explicit > env (KNN_TPU_MERGE /
        # KNN_TPU_DCN_MERGE) > the SCALING.json-measured crossover table
        # (parallel.crossover) — results are bitwise-identical either
        # way, so the default is free to chase the measured wall clock.
        # On hierarchical meshes ``merge`` is the per-host ICI level and
        # ``dcn_merge`` the cross-host level, each resolved by its own
        # shard count.
        hosts, chips = db_topology(mesh)
        self.merge, self.merge_source = crossover.resolve_merge(
            merge, k, chips)
        self.dcn_merge, self.dcn_merge_source = (
            crossover.resolve_merge(
                dcn_merge, k, hosts, env_name=crossover.DCN_MERGE_ENV)
            if hosts > 1 else (None, None))
        obs.counter(_mn.MERGE_SELECTED, level="intra",
                    strategy=self.merge, source=self.merge_source).inc()
        if self.dcn_merge is not None:
            obs.counter(_mn.MERGE_SELECTED, level="dcn",
                        strategy=self.dcn_merge,
                        source=self.dcn_merge_source).inc()
        merge = self.merge
        # XLA compile events (count + seconds) from every program this
        # placement builds land in the registry; idempotent, no-op when
        # telemetry is off
        obs.install_compile_hook()
        metric = metric.lower()  # dispatch below compares lowercase names
        self._cosine_unit = False  # db rows normalized at placement?
        #: cosine placements: the float64 norms of the rows as given, and
        #: the (ascending) indices of the rows that have none
        self._cos_norms: Optional[np.ndarray] = None
        self._cos_zero_rows = np.empty(0, np.int64)
        self._dot_aug = False  # db rows norm-augmented at placement?
        self._dot_shift = 0.0  # M = max f64 squared row norm (dot only)
        #: uint8 source rows (SIFT-style bvecs payloads): kept so an int8
        #: coarse pass reuses the bytes EXACTLY (unit scale, -128 shift —
        #: ops.quantize.from_uint8) instead of round-tripping through f32
        #: quantization.  Cosine normalizes rows at placement and dot
        #: appends a non-byte augmentation column, so the byte-exact
        #: shortcut doesn't apply there.
        self._uint8_train = None
        if (isinstance(train, np.ndarray) and train.dtype == np.uint8
                and metric not in ("cosine", "dot")):
            self._uint8_train = train
            train = train.astype(np.float32)
        #: lazily built int8 db placement (quantized values + scales +
        #: row norms + bound consts), cached per instance — "quantize
        #: once at placement time", the int8 arm's whole HBM story
        self._int8_cache = None
        #: the pq arm's placements, same lazy discipline, keyed by the
        #: (dsub, ncodes) codebook geometry so two grids can coexist
        self._pq_cache: dict = {}
        #: the default precision's placement, same lazy discipline: the
        #: "bf16x3" kernel's row operands at ONE (tile, low half wanted)
        #: geometry at a time, ``parts`` None where the device had no
        #: room for them (_row_operands)
        self._operands_cache: Optional[dict] = None
        #: the tag index, same lazy discipline: the rows' bags by tag on
        #: the host (once) and the device form at ONE row tile at a time
        #: (_tag_index); None without ``row_tags``
        self._tag_index_cache: Optional[dict] = None
        #: the rows' attribute on the device, same lazy discipline: at
        #: ONE row tile's layout at a time (_attr_rows); None without
        #: ``row_attr``
        self._attr_rows_cache: Optional[dict] = None
        self._attr_sorted_cache: Optional[np.ndarray] = None
        #: an INTERLEAVED placement's row order (class docstring): device
        #: position -> the caller's row id, and its inverse (made by the
        #: first reader, _bin_overflows); None wherever the rows lie in
        #: the order given
        self._row_order: Optional[np.ndarray] = None
        self._row_place_cache: Optional[np.ndarray] = None
        db_shards = hosts * chips
        budget = _tier_budget(hbm_budget_bytes)
        pre_placed = (
            isinstance(train, jax.Array)
            and train.sharding.is_equivalent_to(
                NamedSharding(mesh, P(db_axes(mesh))), train.ndim
            )
        )
        #: the rows were handed in already placed, at their own width
        self._pre_placed = bool(pre_placed)
        # a cosine placement's rows as given, and what the walk that made
        # their unit rows saw of those (below)
        given, placed_norm_max, lo_zero = None, None, False
        if pre_placed:
            # already a db-sharded global array (e.g. assembled across
            # hosts by parallel.multihost.shard_across_hosts) — use the
            # placement as-is.  ``n_train`` tells the search programs how
            # many leading rows are real when the caller padded before
            # placing (pad rows past n_train are masked out of every
            # selection, exactly like the host-array path).
            if train.shape[0] % db_shards:
                raise ValueError(
                    f"pre-placed train rows {train.shape[0]} must be a "
                    f"multiple of db_shards={db_shards}; pad before placing"
                )
            self._train_host = None
            tp = train
            n_train = train.shape[0] if n_train is None else n_train
            if not 0 < n_train <= train.shape[0]:
                raise ValueError(
                    f"n_train={n_train} outside (0, {train.shape[0]}]"
                )
        else:
            if n_train is not None:
                raise ValueError("n_train is only for pre-placed arrays")
            if not isinstance(train, jax.Array):
                train = np.asarray(train)  # host padding streams shards on placement
            # host rows handed an attribute lie INTERLEAVED wherever a
            # filter can run on them (class docstring): resident, under
            # l2 or cosine.  The order is drawn here, the rows follow it
            # below (cosine: inside the unit-row map; l2: one gather)
            t_order = time.perf_counter()
            if (row_attr is not None and isinstance(train, np.ndarray)
                    and metric in _INTERLEAVED_METRICS
                    and not _outgrows(
                        budget, hosts,
                        -(-train.shape[0] // db_shards) * db_shards,
                        train.shape[1],
                        4 if metric == "cosine" else train.dtype.itemsize)):
                self._row_order = _interleave_order(train.shape[0])
            order_s = time.perf_counter() - t_order
            if metric == "cosine" and isinstance(train, np.ndarray):
                # cosine distance on row-normalized vectors is squared L2
                # (||q^-t^||^2 = 2(1-q^.t^)): normalizing ONCE at placement
                # (float64 norms, f32 result) makes the whole certified-
                # exact machinery available to cosine (search_certified),
                # and pairwise_cosine's internal re-normalization is
                # idempotent so plain search is unchanged.  Zero rows keep
                # themselves.  The device finds the candidates and proves
                # none is missing among the UNIT rows, with
                # COS_UNIT_SLACK added wherever it compares two of them
                # for the rounding of the normalisation; the host ranks
                # by the float64 cosine of the rows AS GIVEN, which it
                # keeps with their norms in the unit rows' place once
                # those are on the device (below).
                t_unit = time.perf_counter()
                given = np.asarray(train, np.float32)
                train, self._cos_norms, placed_norm_max, lo_zero = (
                    _unit_rows(given, self._row_order))
                self._cos_zero_rows = np.flatnonzero(self._cos_norms == 0)
                self._cosine_unit = True
                obs.emit_event(
                    "placement.cosine_normalize", rows=int(train.shape[0]),
                    dim=int(train.shape[1]),
                    zero_rows=int(self._cos_zero_rows.size),
                    slack=COS_UNIT_SLACK,
                    seconds=time.perf_counter() - t_unit)
            elif metric == "dot" and isinstance(train, np.ndarray):
                # MIPS -> L2 by norm augmentation, ONCE at placement
                # (_augment_dot): against a query with a zero appended,
                # the augmented squared L2 is
                #   ||q'-t'||^2 = ||q||^2 + M - 2 q.t
                # but for the appended column's float32 rounding — an
                # affine, decreasing map of the inner product per query,
                # so the whole certified machinery (search_certified,
                # any precision x kernel) finds the candidates and
                # proves none is missing, with DOT_AUG_SLACK * M added
                # wherever it compares two rows; the host then ranks
                # by the float64 inner product itself.  Plain search
                # rides too: _place_queries appends the zero column and
                # the extra 0*aug term leaves pairwise_dot values
                # unchanged.
                t_aug = time.perf_counter()
                train, self._dot_shift, dot_norm_max = _augment_dot(train)
                self._dot_aug = True
                obs.emit_event(
                    "placement.dot_augment", rows=int(train.shape[0]),
                    dim=int(train.shape[1]) - 1, shift=self._dot_shift,
                    seconds=time.perf_counter() - t_aug)
            # host copy (unpadded) for certified-path float64 refinement
            self._train_host = train if isinstance(train, np.ndarray) else None
            # pad rows with a huge fill: every selector also masks them by
            # index, but the pallas kernel's exclusion bound stays sharp
            # only if pad rows score far away (ops.pallas_knn.PAD_VAL)
            from knn_tpu.ops.pallas_knn import PAD_VAL

            if self._row_order is not None:
                if not self._cosine_unit:
                    # l2: the host keeps the rows as given (above), the
                    # device gets them in the placement's order
                    t_order = time.perf_counter()
                    train = self._as_placed(train)
                    order_s += time.perf_counter() - t_order
                obs.emit_event("placement.interleave",
                               rows=int(train.shape[0]), seconds=order_s)
            tp, n_train = pad_to_multiple(train, db_shards, fill=PAD_VAL)
        # --- host-RAM shard tier (the super-HBM escape hatch) ----------
        # When the placement's per-host share exceeds the HBM budget
        # (explicit arg > KNN_TPU_HOSTTIER_BUDGET_BYTES env > unbounded),
        # the database stays in HOST memory partitioned into
        # budget-sized segments (analysis.hbm.plan_segments); search()
        # then streams the segments through the device placement
        # sweep-by-sweep with dispatch-ahead overlap, merging each
        # sweep's candidates into a running top-k carry.  Every segment
        # pads to ONE shape, so all sweeps share one compiled program.
        self._host_tier: Optional[dict] = None
        if budget is not None and not isinstance(tp, np.ndarray):
            # the tier streams from HOST memory; a pre-placed /
            # device-resident array has no host rows to stream from.
            # Refuse loudly when it would not fit rather than silently
            # placing a super-budget corpus resident.
            if _outgrows(budget, hosts, tp.shape[0], tp.shape[1],
                         int(jnp.dtype(tp.dtype).itemsize)):
                raise ValueError(
                    f"hbm_budget_bytes={budget} per host cannot hold this "
                    f"{tp.shape[0]}-row placement, and the host-RAM tier "
                    f"needs a host-array construction to stream from; "
                    f"pass the rows as a numpy array (or raise the budget)")
        if budget is not None and isinstance(tp, np.ndarray):
            from knn_tpu.analysis import hbm

            itemsize = int(tp.dtype.itemsize)
            if _outgrows(budget, hosts, tp.shape[0], tp.shape[1], itemsize):
                import os as _os

                env_d = _os.environ.get(
                    "KNN_TPU_HOSTTIER_DEPTH", "").strip()
                try:
                    depth = int(env_d) if env_d else 2
                except ValueError as e:
                    # strict-env discipline (admission/merge switches):
                    # a typo'd knob raises instead of silently running
                    # at the default
                    raise ValueError(
                        f"KNN_TPU_HOSTTIER_DEPTH={env_d!r} is not an "
                        f"int") from e
                segments = hbm.plan_segments(
                    n_train, tp.shape[1], budget, itemsize=itemsize,
                    hosts=hosts, shard_multiple=db_shards)
                seg_rows = segments[0][1] - segments[0][0]
                self._host_tier = {
                    "segments": segments,
                    "segment_rows": seg_rows,
                    "budget_bytes": int(budget),
                    "bytes_per_sweep": hbm.placement_bytes(
                        seg_rows, tp.shape[1], itemsize),
                    "depth": max(1, depth),
                    "itemsize": itemsize,
                }
                obs.gauge(_mn.HOSTTIER_SEGMENT_ROWS).set(float(seg_rows))
        if self._row_order is not None and self._host_tier is not None:
            # the order was drawn on the word of _outgrows over the
            # shape the rows were going to take: the tier streams its
            # segments in the order given and maps nothing
            raise RuntimeError(
                f"the host-RAM tier took a {tp.shape} placement that was "
                f"laid out interleaved as one that fits its budget")
        shard_rows = (
            self._host_tier["segment_rows"] if self._host_tier is not None
            else tp.shape[0]
        ) // db_shards
        if k > shard_rows:
            raise ValueError(
                f"k={k} exceeds db shard size {shard_rows}; use fewer db shards"
            )
        if k > n_train:
            raise ValueError(f"k={k} > n_train={n_train}")
        self.mesh = mesh
        self.k = k
        self.metric = metric
        # an inner-product placement has just taken every row's norm
        # (the appended column is no bf16-exact value, so its kernel
        # forms every product), and a cosine placement every unit row's;
        # any other walks its rows at the first certified call
        # (_db_norm_max)
        self._db_norm_max_cache: Optional[float] = (
            dot_norm_max if self._dot_aug else placed_norm_max)
        # whether every placed row value is bf16-exact as float32: the
        # same walk's (_db_norm_max), read by _kernel_terms
        self._rows_lo_zero = lo_zero
        self.train_tile = train_tile
        self.n_train = n_train
        #: ``(indptr, tags)``, CSR row -> sorted tag ids: what a
        #: ``filter_tags`` query of search_certified is held against
        self._row_tags = None
        if row_tags is not None:
            from knn_tpu.ops.tagfilter import check_bags

            self._row_tags = check_bags(*row_tags, n_train)
        #: one whole number a row (int32 ``[n_train]``, kept on the host
        #: for the repair's scan): what a ``filter_range`` query of
        #: search_certified is held against
        self._row_attr = None
        if row_attr is not None:
            from knn_tpu.ops.tagfilter import check_row_attr

            self._row_attr = check_row_attr(row_attr, n_train)
        #: user-facing query/input dim — dot placements append one norm-
        #: augmentation column, so the rows to place are ``dim_in + 1``
        #: wide
        self.dim_in = int(tp.shape[1]) - (1 if self._dot_aug else 0)
        #: the columns of the rows as given to the placement, and as
        #: placed (every placed batch's too): whole 128-column lane tiles
        #: where this placement lays the rows out itself (class
        #: docstring), the width handed in where it does not (a
        #: pre-placed array, the host-RAM tier's segments)
        self._given_width = self._placed_width = int(tp.shape[1])
        if not pre_placed and self._host_tier is None:
            self._placed_width = lane_tiled(self._given_width)
        self._dtype_key = (
            None if compute_dtype is None else jnp.dtype(compute_dtype).name
        )
        if self._host_tier is not None:
            self._tp = None  # segments stream per sweep; nothing resident
            self._last_hosttier: Optional[dict] = None
        else:
            # the reference's Scatter, once (host-major over hosts x
            # chips on hierarchical meshes); the call returns with the
            # transfer on its way, and the wait for it is the caller's
            t0 = time.perf_counter()
            self._tp = shard(tp, mesh, db_axes(mesh))
            if self._placed_width != self._given_width:
                # whole lane tiles, once and on the device: the rows go
                # over as they are, one small program writes them out
                # with zero columns after them (the copy every call
                # made, made here), and the compact array is dropped
                widen = _lane_tile_program(mesh, self._placed_width)
                begun = _hooks.first_call_begin()
                self._tp = widen(self._tp)
                _hooks.first_call_end(begun, widen, "lane_tile",
                                      rows=int(tp.shape[0]))
            obs.emit_event("placement.device_put", rows=int(tp.shape[0]),
                           bytes=int(tp.nbytes), width=self._given_width,
                           placed_width=self._placed_width,
                           seconds=time.perf_counter() - t0)
            if self._cosine_unit:
                # the unit rows live on the device from here on (the
                # transfer keeps its own hold on them until it is done);
                # what the host ranks by is the rows as given
                # (_host_train).  A host-tier placement streams the unit
                # rows from the host and keeps those.
                self._train_host = given
        #: (k, placed query rows) -> dispatch count: every distinct pair is
        #: one traced/compiled XLA program shape (compile_cache_stats)
        self._dispatch_shapes: dict = {}
        # (kernel's candidate width, width the final top-k sees) of the
        # last resolved pallas program, per shard (_pallas_setup)
        self._select_widths: Tuple[int, int] = (0, 0)
        # the lane-rows the bin-merge's last group is short of its grid
        # (0: the groups tile the kernel's width, or no merge runs)
        self._select_merge_short = 0
        # (columns of one dim chunk, chunks a row tile is cut into) that
        # _pallas_setup handed its last program's kernel
        self._dim_chunking: Tuple[int, int] = (0, 0)
        self._row_blocking: Tuple[int, int] = (0, 0)
        # what runs the last program's final top-(m+2) on each shard:
        # "pallas" or "xla" (_pallas_setup)
        self._final_select_stage = "xla"
        # where the last program's kernel gets its row operands:
        # "resident" (the placement's, _row_operands) or "per_call"
        # (formed in the program, every call) (_pallas_setup)
        self._operands_source = "per_call"
        # the last pallas call's sub-batch and why it is that
        # (_pallas_setup, analysis.subbatch): (rows, why)
        self._sub_batch = (None, "explicit")
        # the row tile the last program's kernel runs (_pallas_setup):
        # the layout a batch's validity words are made in
        self._kernel_tile = 0
        # what _pallas_setup last resolved, whole (certified_plan), and
        # the form of it the last ``certified.plan`` event said
        self._plan: dict = {}
        self._plan_told: Optional[dict] = None
        #: lazily built serving engines, keyed by ladder spec
        #: (buckets, min_bucket, max_bucket) — search_bucketed; the lock
        #: keeps concurrent cold calls from double-building an engine
        #: (each build AOT-compiles executables — seconds on hardware)
        self._serving_engines: dict = {}
        self._engines_lock = threading.Lock()
        #: widths at which the self-join's re-select has run once on
        #: this placement (_SelfJoinCall: its one compiled shape)
        self._self_reselect_warm: set = set()
        self._labels = self._labels_host = self._vote_labels_dev = None
        self.num_classes = num_classes
        if labels is not None:
            if num_classes is None:
                raise ValueError("labels given without num_classes")
            labels = np.asarray(labels, dtype=np.int32)
            if labels.shape != (n_train,):
                raise ValueError(
                    f"labels shape {labels.shape} != (n_train,) = ({n_train},)"
                )
            # the reference's Bcast; the device gathers a neighbour's
            # label by its position, so they lie as the rows do
            self._labels = replicate(self._as_placed(labels), mesh)
            #: the host's copy, kept, by row id: ``predict_certified``
            #: votes from it
            self._labels_host = labels

    @property
    def db_shards(self) -> int:
        """Total db shards: hosts x chips on hierarchical meshes."""
        hosts, chips = db_topology(self.mesh)
        return hosts * chips

    def _shard_rows(self) -> int:
        """Rows per db shard of the resident placement (or of one
        host-tier segment)."""
        if self._host_tier is not None:
            return self._host_tier["segment_rows"] // self.db_shards
        return self._tp.shape[0] // self.db_shards

    def _require_resident(self, what: str) -> None:
        """The paths that read the whole placed database (certified
        pipeline, radius counts, votes, bucketed serving) need it
        RESIDENT; the host-RAM tier only ever has one segment on
        device."""
        if self._tp is None:
            raise ValueError(
                f"{what} needs the full database resident on device, but "
                f"this placement runs the host-RAM shard tier (corpus "
                f"exceeds the {self._host_tier['budget_bytes']}-byte "
                f"per-host HBM budget); use search(), or raise the budget")

    def _row_ids(self, positions: np.ndarray) -> np.ndarray:
        """Row indices as they leave the device -> the caller's row ids:
        ``positions`` itself wherever the rows lie in the order given,
        the interleaved placement's order over them otherwise (-1, pad
        rows and the sentinel kept).  Applied ONCE a path, where its
        indices reach the host (class docstring)."""
        order = self._row_order
        return positions if order is None else _rows_at(order, positions)

    def _as_placed(self, per_row: np.ndarray) -> np.ndarray:
        """A host array that holds one entry a row in the caller's
        order (rows, bytes, the attribute), in the order the rows lie
        on the device: itself, or a gathered copy where the placement
        is interleaved."""
        order = self._row_order
        return per_row if order is None else per_row[order]

    def _row_places(self, ids: np.ndarray) -> np.ndarray:
        """The inverse of :meth:`_row_ids`: where the caller's rows
        ``ids`` lie on the device (what reads a row's shard, tile or
        bin from an answer)."""
        if self._row_order is None:
            return ids
        if self._row_place_cache is None:
            place = np.empty_like(self._row_order)
            place[self._row_order] = np.arange(place.size, dtype=place.dtype)
            self._row_place_cache = place
        return _rows_at(self._row_place_cache, ids)

    def _answers_by_id(self, d, i):
        """The ONE exit of the float32 top-k launches (:meth:`search`
        and through it :meth:`radius_search`, the serving engine's
        buckets, ``knn_join(mode="stream")``): a launch's ``(d, i)``
        [Q, k], pad queries cut off, as ``(d, ids)``.  Wherever the
        rows lie in the order given that is the launch's own arrays.
        On an interleaved placement (host arrays) the positions become
        ids and each query's equal distances are put in id order; the
        distances are the launch's, column for column.  What a launch
        of k columns cannot give back: where MORE rows lie at exactly
        the k-th float32 distance than there are columns left (copies
        of one row, as a rule), the device kept those of lowest
        position, which a placement laid out as given makes those of
        lowest id and this one does not: the ids named are rows at
        that distance all the same.  ``search_certified`` ranks on the
        host and keeps the rule whole."""
        if self._row_order is None:
            return d, i
        d, ids = np.asarray(d), self._row_ids(np.asarray(i))
        order = np.lexsort((ids, d), axis=-1)
        return (np.take_along_axis(d, order, axis=-1),
                np.take_along_axis(ids, order, axis=-1))

    def _record_merge_bytes(self, n_rows: int, k: int) -> int:
        """Mirror the modeled per-level merge volume into the registry
        (crossover.merge_bytes, the measured crossover table's byte
        model); returns the bytes counted, over both levels."""
        hosts, chips = db_topology(self.mesh)
        total = 0
        if chips > 1:
            intra = crossover.merge_bytes(n_rows, k, chips, self.merge)
            obs.counter(_mn.MERGE_BYTES, level="intra",
                        strategy=self.merge).inc(intra)
            total += intra
        if hosts > 1 and self.dcn_merge is not None:
            dcn = crossover.merge_bytes(n_rows, k, hosts, self.dcn_merge)
            obs.counter(_mn.MERGE_BYTES, level="dcn",
                        strategy=self.dcn_merge).inc(dcn)
            total += dcn
        return total

    def hosttier_stats(self) -> Optional[dict]:
        """The host-RAM tier plan plus the last sweep's measurements
        (sweeps, per-sweep walls, bytes/sweep); None when the placement
        is fully resident."""
        if self._host_tier is None:
            return None
        out = {k: v for k, v in self._host_tier.items() if k != "segments"}
        out["sweeps"] = len(self._host_tier["segments"])
        if self._last_hosttier is not None:
            out["last_search"] = dict(self._last_hosttier)
        return out

    def _place_queries(self, queries):
        """``(placed, rows)``: a batch padded to the query shards and
        sent to them, at the PLACED width: a batch that arrives at the
        caller's width (or, from search_certified, with a dot
        placement's zero column already appended) gets zero columns up
        to ``_placed_width``: the dot placement's augmentation column
        (q'.t' == q.t) and the lane-tile columns of the class docstring
        alike."""
        if not isinstance(queries, jax.Array):
            queries = np.asarray(queries)
            if self._dot_aug:
                queries = np.asarray(queries, np.float32)
        if (queries.ndim == 2
                and self.dim_in <= queries.shape[1] < self._placed_width):
            xp = jnp if isinstance(queries, jax.Array) else np
            queries = xp.pad(queries, (
                (0, 0), (0, self._placed_width - queries.shape[1])))
        qp, n_q = pad_to_multiple(queries, self.mesh.shape[QUERY_AXIS])
        return shard(qp, self.mesh, QUERY_AXIS), n_q

    def search(
        self, queries: jax.Array, *, k: Optional[int] = None,
        return_sqrt: bool = False,
    ) -> Tuple[jax.Array, jax.Array]:
        """(distances, global indices) [Q, k] of the k nearest database rows.

        ``k`` overrides the constructor's k for this call (e.g. fetching
        k+margin candidates for host refinement) while reusing the same
        device placement; each distinct k compiles its own cached program.

        L2-family distances are SQUARED by default (ranking-equivalent,
        the monotone sqrt at knn_mpi.cpp:48 dropped); ``return_sqrt=True``
        returns true Euclidean values matching the reference / sklearn.

        The indices are the caller's row ids on every placement; an
        interleaved one (``row_attr``) answers with host arrays, and
        :meth:`_answers_by_id` says which copies of a row it names
        where more of them tie at the k-th distance than fit.
        """
        k = self.k if k is None else k
        shard_rows = self._shard_rows()
        if k > min(self.n_train, shard_rows):
            raise ValueError(f"k={k} exceeds shard rows {shard_rows}")
        if self._host_tier is not None:
            return self._search_host_tier(queries, k, return_sqrt)
        qp, n_q = self._place_queries(queries)
        fn = _knn_program(
            self.mesh, k, self.metric, self.merge, self.n_train,
            self.train_tile, self._dtype_key, dcn_merge=self.dcn_merge,
        )
        shape_key = (k, qp.shape[0])
        self._dispatch_shapes[shape_key] = (
            self._dispatch_shapes.get(shape_key, 0) + 1
        )
        self._record_merge_bytes(qp.shape[0], k)
        d, i = _retry_transient(lambda: fn(qp, self._tp), "search dispatch")
        d, i = self._answers_by_id(d[:n_q], i[:n_q])
        if return_sqrt:
            from knn_tpu.ops.distance import metric_values

            d = metric_values(d, self.metric)
        return d, i

    def _search_host_tier(self, queries, k: int, return_sqrt: bool):
        """The host-RAM tier sweep: stream budget-sized db segments
        host->device one per sweep (ALL sweeps share one compiled
        program — the ragged tail pads to the same shape and masks via
        the traced ``n_valid`` operand), with up to ``depth`` sweeps in
        flight (the PR-1/PR-9 bounded-depth dispatch-ahead discipline:
        drain the oldest before admitting a new one, so segment s+1's
        h2d transfer and distance stream overlap segment s's fetch and
        host merge).  Each fetched sweep's candidates merge into the
        running top-k carry by the SAME lexicographic (distance, index)
        order the device merge uses, so results are bitwise-identical
        to the all-in-HBM placement (per-pair distances are
        placement-invariant; tests/test_hosttier.py pins it).  Returns
        host arrays — the carry lives on host by construction."""
        import time as _time

        from knn_tpu.ops.pallas_knn import PAD_VAL

        ht = self._host_tier
        host = self._train_host
        seg_rows = ht["segment_rows"]
        prog = _hosttier_program(
            self.mesh, k, self.metric, self.merge, self.train_tile,
            self._dtype_key, dcn_merge=self.dcn_merge)
        qp, n_q = self._place_queries(queries)
        shape_key = (k, qp.shape[0])
        self._dispatch_shapes[shape_key] = (
            self._dispatch_shapes.get(shape_key, 0) + 1
        )

        def launch(lo: int, hi: int):
            seg = host[lo:hi]
            if seg.shape[0] < seg_rows:
                seg = np.pad(seg, ((0, seg_rows - seg.shape[0]), (0, 0)),
                             constant_values=PAD_VAL)
            tp = shard(seg, self.mesh, db_axes(self.mesh))
            nv = replicate(np.asarray([hi - lo], np.int32), self.mesh)
            return prog(qp, tp, nv)

        best_d: Optional[np.ndarray] = None
        best_i: Optional[np.ndarray] = None
        pending: list = []
        sweep_walls: list = []
        t_wall0 = _time.perf_counter()

        def collect() -> None:
            nonlocal best_d, best_i
            lo, hi, t0, out = pending.pop(0)
            # d and i MUST come from the same execution: a transient
            # fetch failure relaunches the sweep and rebinds BOTH
            # outputs (a d from the relaunch paired with an i from the
            # dead original would silently mis-rank)
            cur = {"out": out}

            def redo():
                cur["out"] = launch(lo, hi)
                return cur["out"][0]

            d = _fetch_or_redispatch(out[0], redo, "host-tier fetch")
            i = np.asarray(cur["out"][1])
            sweep_walls.append(_time.perf_counter() - t0)
            # globalize within-segment indices; sentinel rows stay put
            pad = i == _INT_SENTINEL
            gi = np.where(pad, _INT_SENTINEL, i.astype(np.int64) + lo)
            self._record_merge_bytes(qp.shape[0], k)
            obs.counter(_mn.HOSTTIER_SWEEPS).inc()
            obs.histogram(_mn.HOSTTIER_SWEEP_SECONDS).observe(
                sweep_walls[-1])
            if best_d is None:
                best_d, best_i = np.asarray(d), gi
                return
            # ONE home for the host-side lexicographic merge — the same
            # order the device merge tree applies
            from knn_tpu.parallel.multihost import merge_topk_host

            best_d, best_i = merge_topk_host(
                [best_d, np.asarray(d)], [best_i, gi], k)

        for lo, hi in ht["segments"]:
            while len(pending) >= ht["depth"]:
                collect()
            t0 = _time.perf_counter()
            out = _retry_transient(lambda lo=lo, hi=hi: launch(lo, hi),
                                   "host-tier dispatch")
            pending.append((lo, hi, t0, out))
        while pending:
            collect()
        self._last_hosttier = {
            "sweeps": len(ht["segments"]),
            "wall_s": round(_time.perf_counter() - t_wall0, 4),
            "sweep_walls_s": [round(w, 4) for w in sweep_walls],
            "k": k,
            "queries": int(n_q),
        }
        d_out, i_out = best_d[:n_q], best_i[:n_q]
        if return_sqrt:
            from knn_tpu.ops.distance import metric_values

            d_out = np.asarray(metric_values(jnp.asarray(d_out),
                                             self.metric))
        return d_out, i_out

    def search_bucketed(
        self, queries, *, buckets=None, min_bucket: int = 32,
        max_bucket: int = 4096, return_sqrt: bool = False,
    ):
        """Bucketed exact search (numpy results; same neighbors and
        tie-break order as :meth:`search`, and bitwise-identical to a
        :meth:`search` call of the same padded batch — see
        knn_tpu.serving.engine for the exactness contract): the query
        batch pads up to a geometric ladder of
        bucket sizes so ANY traffic pattern of batch shapes hits at most
        ``len(buckets)`` compiled programs, instead of one compile per
        distinct batch size.  The engine behind it (built lazily per
        ladder, reused across calls) AOT-compiles buckets on first use and
        keeps compile/dispatch/latency accounting — see
        :meth:`compile_cache_stats` and :mod:`knn_tpu.serving` for the
        full serving surface (warmup, micro-batching queue, trace
        replay)."""
        self._require_resident("search_bucketed")
        from knn_tpu.serving.buckets import normalize_ladder
        from knn_tpu.serving.engine import ServingEngine

        ladder = (
            None if buckets is None else normalize_ladder(buckets)
        )
        # an explicit ladder fully determines the engine — min/max are
        # ignored then and must not key duplicate engines that would
        # re-AOT-compile identical executables
        key = ladder if ladder is not None else (None, min_bucket, max_bucket)
        with self._engines_lock:
            engine = self._serving_engines.get(key)
            if engine is None:
                # construction is cheap (no compiles happen here); holding
                # the lock just prevents duplicate engines whose separate
                # AOT caches would re-compile identical executables
                engine = ServingEngine(
                    self, buckets=ladder, min_bucket=min_bucket,
                    max_bucket=max_bucket,
                )
                self._serving_engines[key] = engine
        return engine.search(queries, return_sqrt=return_sqrt)

    def compile_cache_stats(self) -> dict:
        """Compile-cache observability for serving: the module program
        cache (shared across instances — ``_knn_program``'s lru_cache) and
        THIS placement's dispatched program shapes.  Each distinct
        ``(k, placed_rows)`` pair is one XLA trace/compile of the search
        program; a healthy bucketed stream keeps ``distinct_shapes``
        bounded by its ladder size while ``dispatches`` grows."""
        info = _knn_program.cache_info()
        out = {
            "program_cache": {
                "hits": info.hits,
                "misses": info.misses,
                "size": info.currsize,
            },
            "distinct_shapes": len(self._dispatch_shapes),
            "dispatches": int(sum(self._dispatch_shapes.values())),
            "shape_counts": {
                f"k{k}xq{q}": int(c)
                for (k, q), c in sorted(self._dispatch_shapes.items())
            },
        }
        if self._serving_engines:
            out["serving_engines"] = [
                e.stats() for e in self._serving_engines.values()
            ]
        return out

    def radius_search(self, queries, radius: float, *, max_neighbors: int):
        """All db rows within ``radius`` per query, bounded at
        ``max_neighbors`` — the sharded form of ops.radius.radius_search.
        (Which of the two range calls a caller wants:
        this one for a bounded, fixed-shape answer in float32 — at most
        ``max_neighbors`` rows a query, truncation reported, a Euclidean
        radius, membership at the boundary by float32 arithmetic;
        :meth:`range_search_certified` for the COMPLETE answer — every
        row at or under a squared radius, nothing capped, membership
        and distances decided in float64, variable-length lists.)

        Returns ``(dists [Q, M], idx [Q, M], counts [Q])``: the sharded
        nearest-M select masked to the radius (beyond-radius slots
        ``+inf`` / ``-1``) plus the within-radius count from the
        distributed count program (psum over the db axis) — truncation
        (``counts > M``, with ``M = min(max_neighbors, n_train)``) is
        always visible.  l2 family (Euclidean-units radius, squared
        ranking values) and cosine (cosine-distance radius; db rows were
        unit-normalized at placement, queries here; the count runs on
        the unit-vector squared-L2 equivalent ``2 * (1 - sim)``).  L1
        has no sharded count program; when the placement kept a host
        copy of the train array (any host-array construction) it falls
        back to the single-device ops.radius path — mask and count share
        ONE pairwise computation there, so L1 results have the stronger
        single-program boundary contract — and raises for pre-placed
        multi-process arrays (no host copy to fall back to).

        Boundary contract: the mask (the sharded select's values) and
        the count (the count program) are DIFFERENT XLA programs, so a
        row within a float32 ulp of the radius can land on different
        sides in each — counts may differ from the visible in-radius
        entries by such boundary rows, and near-tied in-radius entries
        may ORDER differently than the single-device path (each program
        is lexicographic over its own f32 values).  Decisive semantics
        need a radius off the data's distance values (cf. tests'
        _safe_radius); this is inherent to f32 multi-program arithmetic,
        unlike the single-device ops.radius path whose mask and count
        share one pairwise computation.  bf16 placements are refused outright —
        a bf16-ranked mask against an f32 count would widen the
        boundary band ~2000x."""
        self._require_resident("radius_search")
        from knn_tpu.ops.radius import SENTINEL_IDX, radius_threshold

        if self._dtype_key is not None:
            raise ValueError(
                f"radius_search needs a float32 placement; this program "
                f"was built with compute_dtype={self._dtype_key!r} and "
                f"its mask/count arithmetics would disagree at the "
                f"radius boundary"
            )
        if self.metric in ("l1", "manhattan", "cityblock"):
            # single-device fallback: no sharded L1 count program exists,
            # but ops.radius runs mask and count off ONE pairwise pass
            from knn_tpu.ops.radius import radius_search as _radius_single

            if int(max_neighbors) < 1:
                raise ValueError(
                    f"max_neighbors must be >= 1, got {max_neighbors}")
            try:
                db_host = self._host_train()
            except ValueError as e:
                raise ValueError(
                    "sharded radius_search has no L1 count program and the "
                    "single-device fallback needs a host copy of the "
                    "database; construct ShardedKNN from a host array, or "
                    "use ops.radius.radius_search directly"
                ) from e
            d, i, counts = _radius_single(
                np.asarray(queries, np.float32), db_host, radius,
                max_neighbors=min(int(max_neighbors), self.n_train),
                metric="l1", train_tile=self.train_tile,
            )
            return np.asarray(d), np.asarray(i), np.asarray(counts)
        thr = radius_threshold(radius, self.metric)  # ranking space
        if self.metric == "cosine":
            if not self._cosine_unit:
                raise ValueError(
                    "cosine radius_search needs the database normalized at "
                    "placement; construct ShardedKNN from a host array"
                )
            count_thr = 2.0 * thr  # unit rows: ||q^-t^||^2 = 2 (1 - sim)
            q_count = _row_normalize_f64(np.asarray(queries, np.float32))
        elif self.metric in ("l2", "sql2", "euclidean"):
            count_thr = thr
            q_count = queries
        else:
            raise ValueError(
                f"sharded radius_search supports l2/cosine, not "
                f"{self.metric!r}; use ops.radius.radius_search"
            )
        shard_rows = self._shard_rows()
        m = min(int(max_neighbors), self.n_train)
        if m < 1:
            raise ValueError(f"max_neighbors must be >= 1, got {max_neighbors}")
        if m > shard_rows:
            # NEVER silently narrow: a caller testing counts > M for
            # truncation would read a shard-clamped result as complete
            # (same contract as search()'s k check above)
            raise ValueError(
                f"max_neighbors={m} exceeds db shard size {shard_rows}; "
                f"use fewer db shards"
            )
        d, i = self.search(queries, k=m)
        d, i = np.asarray(d), np.asarray(i)
        # counts: the distributed count-below pass (strictly <);
        # nextafter lifts it to <= in float32.  The l2 branch pays a
        # second h2d placement of the same queries (search placed its
        # own copy internally) — only the cosine branch genuinely needs
        # a different (renormalized) placement; accepted because the
        # count pass needs a query placement either way and search()
        # does not expose its internal one.
        count_fn = _count_program(self.mesh, self.n_train, self.train_tile)
        qp, n_q = self._place_queries(np.asarray(q_count, np.float32))
        thr_vec = np.full(
            qp.shape[0],
            np.nextafter(np.float32(count_thr), np.float32(np.inf)),
            np.float32,
        )
        out = _retry_transient(
            lambda: count_fn(qp, self._tp, thr_vec), "radius count dispatch")
        counts = _fetch_or_redispatch(
            out, lambda: count_fn(qp, self._tp, thr_vec),
            "radius count fetch",
        )[:n_q]
        within = d <= thr
        return (
            np.where(within, d, np.inf),
            np.where(within, i, SENTINEL_IDX),
            counts,
        )

    # -- certified-exact path (ops.certified, distributed) -----------------
    def _placed_host(self) -> np.ndarray:
        """The (unpadded) rows AS PLACED, on the host: what a quantized
        placement is made from.  :meth:`_host_train` itself but for a
        cosine placement, whose host copy is the rows as given: its unit
        rows are read back from the device."""
        if self._cosine_unit and self._tp is not None:
            return self._fetch_rows()
        return self._as_placed(self._host_train())

    def _fetch_rows(self) -> np.ndarray:
        """The (unpadded) rows read back from the device, cut to the
        width they were given at."""
        rows = np.asarray(self._tp)[: self.n_train]
        return (rows if self._given_width == rows.shape[1]
                else np.ascontiguousarray(rows[:, : self._given_width]))

    def _host_train(self) -> np.ndarray:
        """Host copy of the (unpadded) database for float64 refinement;
        fetched from the mesh once and cached when the caller didn't keep
        a host array around.  A cosine placement's is the rows AS GIVEN
        (``_cos_norms`` beside them), not the unit rows it placed."""
        if self._train_host is None:
            if not self._tp.is_fully_addressable:
                raise ValueError(
                    "certified search needs a host copy of the database, but "
                    "the pre-placed global array spans multiple processes; "
                    "construct ShardedKNN from a host array instead"
                )
            t0 = time.perf_counter()
            self._train_host = self._fetch_rows()
            obs.emit_event("placement.host_copy", rows=int(self.n_train),
                           seconds=time.perf_counter() - t0)
        return self._train_host

    def _db_norm_max(self) -> float:
        """Largest float64 squared row norm of the database — the
        query-independent half of the certificate tolerance; a full-DB
        float64 pass, so computed once per placement and cached."""
        if self._db_norm_max_cache is None:
            from knn_tpu.ops.pallas_knn import lo_halves_zero

            db = self._host_train()
            t0 = time.perf_counter()
            # row chunks: the same per-row arithmetic, without float64
            # temporaries the size of the whole database (15 GB and a
            # minute of page faults at GIST 1M x 960).  The same walk
            # asks each chunk whether its values are all bf16-exact,
            # until one is not (uniform floats: the first), for the
            # kernel's choice of products (_kernel_terms)
            best, exact = 0.0, True
            for lo in range(0, db.shape[0], 8192):
                chunk = db[lo:lo + 8192]
                exact = exact and lo_halves_zero(chunk)
                sq = chunk.astype(np.float64)
                np.multiply(sq, sq, out=sq)
                best = max(best, float(sq.sum(-1).max()))
            self._rows_lo_zero = exact
            self._db_norm_max_cache = best
            obs.emit_event("placement.norm_walk", rows=int(db.shape[0]),
                           rows_lo_zero=exact,
                           seconds=time.perf_counter() - t0)
        return self._db_norm_max_cache

    def _kernel_terms(self, q_np: np.ndarray, precision: str,
                      unit: Optional[_UnitQueries] = None) -> str:
        """Which products of the "bf16x3" split this call's kernel forms
        (ops.pallas_knn.BF16X3_TERMS), from what the placement's walk
        saw of the rows and what ``q_np`` (the call's queries as the
        program will get them: normalized, augmented) says of itself.
        Every other precision forms what it always did.  The queries
        are read only where the rows' low halves are all zero, which no
        unit-row placement of real data has: there a cosine call
        (``unit``, ``q_np`` its rows to be) maps all of them first."""
        from knn_tpu.ops.pallas_knn import bf16x3_terms, lo_halves_zero

        self._db_norm_max()
        rows = precision == "bf16x3" and self._rows_lo_zero
        if rows and unit is not None:
            unit.fill(q_np.shape[0])
        return bf16x3_terms(rows, rows and lo_halves_zero(q_np))

    def _int8_placement(self) -> dict:
        """The quantized db placement for the int8 coarse pass, built
        LAZILY on first use and cached: per-row symmetric int8 values +
        f32 scales + f32 shifted-space row norms live on device sharded
        along the db axis (1/4 the coarse-pass HBM traffic of the f32
        db), plus the replicated bound-consts vector the certificate
        widens its threshold with (ops.quantize.bound_consts).  uint8
        sources (bvecs payloads) ride byte-exact at unit scale; anything
        else quantizes the host f32 rows once.  The f32 placement
        (``self._tp``) stays — the rescore gather, the fallback
        programs, and every non-int8 selector still read it."""
        if self._int8_cache is None:
            from knn_tpu.ops import quantize as qz

            with self._engines_lock:
                if self._int8_cache is not None:
                    return self._int8_cache
                host = self._placed_host()
                if self._uint8_train is not None:
                    original = self._as_placed(self._uint8_train)
                    qr = qz.from_uint8(original)
                else:
                    qr = qz.quantize_rows_np(host)
                    original = host
                stats = qz.db_bound_stats(qr, original)
                # pad to the f32 placement's row count: zero rows at zero
                # scale with a huge norm score ~PAD_VAL — never candidates
                # (the kernel masks them by index anyway), never deflating
                # an exclusion bound
                rows = self._tp.shape[0]
                pad = rows - qr.values.shape[0]
                vals = np.pad(qr.values, ((0, pad), (0, 0)))
                scl = np.pad(qr.scales, (0, pad)).astype(np.float32)
                # shifted-space f32 row norms, computed in f64 then cast
                # (error < 1 ulp — tighter than an f32 reduction tree)
                tn = np.empty(rows, dtype=np.float32)
                for lo in range(0, host.shape[0], 65536):
                    hs = host[lo : lo + 65536].astype(np.float64) - qr.offset
                    tn[lo : lo + hs.shape[0]] = (hs ** 2).sum(-1)
                from knn_tpu.ops.pallas_knn import PAD_VAL

                tn[host.shape[0]:] = PAD_VAL
                self._int8_cache = {
                    "values": shard(vals, self.mesh, DB_AXIS),
                    "scales": shard(scl, self.mesh, DB_AXIS),
                    "norms": shard(tn, self.mesh, DB_AXIS),
                    "consts": replicate(qz.bound_consts(stats), self.mesh),
                    "offset": float(qr.offset),
                    "stats": stats,
                }
        return self._int8_cache

    def _pq_placement(self, dsub: Optional[int] = None,
                      ncodes: Optional[int] = None) -> dict:
        """The product-quantized db placement for the pq coarse pass:
        per-subspace codebooks trained ONCE with the IVF tier's seeded
        deterministic k-means (ops.pq.train_pq) and the corpus encoded
        as a list-major [N, m] byte tensor — ``ceil(d/dsub)`` B/row.
        Codes shard along the db axis; the codebooks and the
        per-subspace bound-consts vector replicate (they are tiny).
        Cached per (dsub, ncodes) geometry; defaults come from
        KNN_TPU_PQ_DSUB / KNN_TPU_PQ_NCODES env, else the classic
        (4, 256) point (analysis.widths)."""
        import os as _os

        from knn_tpu.analysis import widths
        from knn_tpu.ops import pq as pqm

        def _env_int(name, fallback):
            raw = _os.environ.get(name, "").strip()
            if not raw:
                return int(fallback)
            try:
                return int(raw)
            except ValueError as e:
                raise ValueError(f"{name}={raw!r} is not an int") from e

        dsub = int(dsub) if dsub else _env_int(
            "KNN_TPU_PQ_DSUB", widths.PQ_DSUB_DEFAULT)
        ncodes = int(ncodes) if ncodes else _env_int(
            "KNN_TPU_PQ_NCODES", widths.PQ_NCODES_DEFAULT)
        key = (dsub, ncodes)
        if key not in self._pq_cache:
            with self._engines_lock:
                if key in self._pq_cache:
                    return self._pq_cache[key]
                host = self._placed_host()
                res = pqm.train_pq(host, mesh=self.mesh, dsub=dsub,
                                   ncodes=ncodes)
                rows = self._tp.shape[0]
                # zero-code pad rows reconstruct to an ordinary point;
                # they can transiently occupy candidate slots but the
                # global-index mask (n_train) keeps them out of every
                # answer, and any crowding a tiny pad tail causes lands
                # in the bad-flag -> fallback repair, never silently
                codes = np.pad(res.codes,
                               ((0, rows - res.codes.shape[0]), (0, 0)))
                self._pq_cache[key] = {
                    "codes": shard(codes, self.mesh, DB_AXIS),
                    "books": replicate(res.codebooks, self.mesh),
                    "consts": replicate(pqm.bound_consts_pq(res.stats),
                                        self.mesh),
                    "stats": res.stats,
                    "dsub": dsub,
                    "ncodes": ncodes,
                }
        return self._pq_cache[key]

    def _row_operands(self, tile: int, with_lo: bool, *,
                      trace_id: Optional[str] = None,
                      acct=obs.trace.NOOP_ACCOUNT,
                      memory_stats: Optional[dict] = None,
                      ) -> Optional[tuple]:
        """The "bf16x3" kernel's row operands as a RESIDENT placement:
        ``(th[, tl], norms)`` of every shard's rows at the resolved tile
        ``tile`` (ops.pallas_knn.row_operands: the bf16 high half, the
        low half where ``with_lo``, the float32 row norms as a vector),
        each db-sharded as the rows are, or ``None`` where the device
        has no room to keep them and the program forms them in every
        call as it always did.

        Built LAZILY, the first time a certified call resolves this
        geometry (inside ``certified.prepare``, so in a caller's warm-up
        and never once the geometry stands), ON THE DEVICE from the
        placed rows by one small program (``_row_operands_program``: the
        kernel prologue's own operations, so every operand is that
        prologue's bit for bit).  The rows never change while the
        placement lives, so neither do these.  ONE form at a time: a
        call that resolves another geometry drops the old form before
        it builds anew.  The f32 placement (``self._tp``) stays: the
        rescore gather, the repair's re-select and the range completion
        read it.

        Whether they are kept is read off the device, by no knob
        (analysis.hbm.resident_operands_room over the device's own
        ``memory_stats()`` — ``memory_stats`` stands in for that reading
        where a test is to tell the rule the device is full; the first
        addressable chip's, and under several processes its
        ``bytes_limit`` alone, so that all of them decide alike), and
        decided once a geometry: the decision is cached with the form.
        The build is the call account's program ``operands``; the
        decision, either way, is one ``placement.operands`` event with
        the terms the rule compared (``held``, ``form_bytes``,
        ``temporaries``, ``limit``, ``kept``: one chip's bytes)."""
        key = (int(tile), bool(with_lo))
        held = self._operands_cache
        if held is not None and held["key"] == key:
            return held["parts"]
        from knn_tpu.analysis import hbm

        with self._engines_lock:
            held = self._operands_cache
            if held is not None and held["key"] == key:
                return held["parts"]
            self._operands_cache = None  # the old form goes first
            shards = self.db_shards
            rows_p = -(-self._shard_rows() // tile) * tile
            dim_p = lane_tiled(self._tp.shape[1])
            form_bytes = hbm.row_operand_bytes(rows_p, dim_p, with_lo)
            if memory_stats is None:
                dev = self._tp.addressable_shards[0].device
                memory_stats = dev.memory_stats() or {}
                if jax.process_count() > 1:
                    # the build and the program are collective, so every
                    # process must decide alike: only what equal chips
                    # read equal counts
                    memory_stats = {
                        "bytes_limit": memory_stats.get("bytes_limit")}
            parts = None
            # the rule's terms, by how the rows lie: whole lane tiles
            # (every placement this class lays out) or a pre-placed
            # array's own width, whose programs still copy it
            room = hbm.resident_operands_room(
                form_bytes, self._tp.nbytes // shards, memory_stats,
                width=int(self._tp.shape[1]))
            seconds = 0.0
            if room["kept"]:
                build = _row_operands_program(self.mesh, *key)
                begun = _hooks.first_call_begin()
                parts = build(self._tp)
                acct.launched("operands")
                _hooks.first_call_end(begun, build, "operands", trace_id,
                                      rows=int(self._tp.shape[0]))
                # seconds: the wait for the pass itself (its program's
                # first call is the record above)
                t0 = time.perf_counter()
                jax.block_until_ready(parts)
                acct.ready("operands")
                seconds = time.perf_counter() - t0
            obs.emit_event(
                "placement.operands", rows=rows_p * shards,
                tile=key[0], parts="th+tl" if with_lo else "th",
                bytes=form_bytes * shards if parts else 0,
                seconds=seconds, **room)
            # the reading stays with the form: what a launch may hold
            # beside it is decided once a geometry too (_pallas_setup)
            self._operands_cache = {"key": key, "parts": parts,
                                    "room": room}
        return parts

    def _tag_index(self, tile: int) -> dict:
        """The rows' tag bags as a RESIDENT placement, the fifth lazy
        one: built the first time a ``filter_tags`` call resolves the
        row tile ``tile`` (inside ``certified.prepare``, so in a
        caller's warm-up), each shard over its own rows
        (ops.tagfilter.place_arrays): a bitmap in the kernel's words'
        layout for every tag that at least one row in
        ``tagfilter.BITMAP_ROW_SHARE`` of a shard holds, the listed
        rows (CSR by tag) of every other.  That split is the rule of
        ``tagfilter.bitmap_min_rows`` on a list's length and nothing
        sets it.  The host keeps the bags by tag (``host``: one sort,
        once a placement) for the repair's exact scan, by row id; the
        device form names a row by its position (the same but on an
        interleaved placement, tagfilter.bags_at) and
        is kept at ONE tile at a time, as the row operands are.  One
        ``placement.tag_index`` event a build.

        Returns ``{"tile", "host": (inv_indptr, inv_rows), "slots",
        "counts"`` (the host's slot table and the tags' list lengths),
        ``"device": (slots, bitmaps, list_ptr, list_rows), "list_cap",
        "stats"}``."""
        held = self._tag_index_cache
        if held is not None and held["tile"] == tile:
            return held
        from knn_tpu.ops import tagfilter

        with self._engines_lock:
            held = self._tag_index_cache
            if held is not None and held["tile"] == tile:
                return held
            t0 = time.perf_counter()
            host = (held["host"] if held is not None
                    else tagfilter.invert_bags(*self._row_tags))
            self._tag_index_cache = None  # the old form goes first
            shards = self.db_shards
            # the device's bitmaps and lists name a row by where it LIES
            # (an interleaved placement: row_attr beside row_tags), the
            # host's bags by its id
            placed = host if self._row_order is None else tagfilter.bags_at(
                host[0], self._row_places(host[1]))
            arrays = tagfilter.place_arrays(
                *placed, n_train=self.n_train, shards=shards,
                shard_rows=self._shard_rows(), tile_n=tile)
            dbp = db_axes(self.mesh)
            device = (replicate(arrays["slots"], self.mesh),) + tuple(
                shard(arrays[key].reshape((-1,) + arrays[key].shape[2:]),
                      self.mesh, dbp)
                for key in ("bitmaps", "list_ptr", "list_rows"))
            jax.block_until_ready(device)
            stats = {key: arrays[key] for key in (
                "tags", "pairs", "bitmap_tags", "list_ids", "bytes")}
            obs.emit_event("placement.tag_index", tile=int(tile), **stats,
                           seconds=time.perf_counter() - t0)
            held = {"tile": tile, "host": host, "slots": arrays["slots"],
                    "counts": np.diff(host[0]), "device": device,
                    "list_cap": arrays["list_cap"], "stats": stats}
            self._tag_index_cache = held
        return held

    def _attr_rows(self, tile: int) -> dict:
        """The rows' attribute as a RESIDENT placement, the sixth lazy
        one: int32, db-sharded, each shard its own rows' in the layout
        the range maker reads at row tile ``tile``
        (ops.tagfilter.place_attr), built the first time a
        ``filter_range`` call resolves that tile (inside
        ``certified.prepare``, so in a caller's warm-up) and kept at ONE
        tile at a time, as the tag index is.  One ``placement.row_attr``
        event a build.  Returns what :meth:`_filter_words` takes as
        ``index``: ``{"maker": "range", "tile", "device": (rows,)}``."""
        held = self._attr_rows_cache
        if held is not None and held["tile"] == tile:
            return held
        from knn_tpu.ops.tagfilter import place_attr

        with self._engines_lock:
            held = self._attr_rows_cache
            if held is not None and held["tile"] == tile:
                return held
            t0 = time.perf_counter()
            self._attr_rows_cache = None  # the old form goes first
            # (the host's copy stays in the caller's order)
            rows = place_attr(self._as_placed(self._row_attr),
                              shards=self.db_shards,
                              shard_rows=self._shard_rows(), tile_n=tile)
            placed = shard(rows.reshape(-1, rows.shape[2]), self.mesh,
                           db_axes(self.mesh))
            jax.block_until_ready(placed)
            obs.emit_event("placement.row_attr", tile=int(tile),
                           rows=int(self.n_train), bytes=int(rows.nbytes),
                           seconds=time.perf_counter() - t0)
            held = {"maker": "range", "tile": tile, "device": (placed,)}
            self._attr_rows_cache = held
        return held

    def _filter_words(self, ft: np.ndarray, index: dict, interpret: bool,
                      trace_id, acct):
        """``mask(lo, take, rows)`` for a filtered call: launches the
        program ``filter_mask`` (the call account's name for the maker
        of validity words, whichever it is: the tag maker over
        :meth:`_tag_index`'s ``index``, or the range maker over
        :meth:`_attr_rows`') on ``ft[lo:lo + take]`` (tag ids, or
        inclusive attribute bounds) padded to ``rows`` queries (then to
        the query shards) with queries no row answers, and returns the
        words still in flight.  The host side of the launch is the span
        ``certified.filter_mask`` (``maker``: ``tags`` / ``range``);
        the flight is the call account's to close."""
        from knn_tpu.ops.tagfilter import ATTR_MAX

        maker = index.get("maker", "tags")
        if maker == "range":
            prog = _range_mask_program(self.mesh, interpret)
            none = (ATTR_MAX, -ATTR_MAX)  # bounds that hold no value
        else:
            prog = _filter_mask_program(self.mesh, index["tile"],
                                        index["list_cap"], interpret)
            none = (np.iinfo(np.int32).max,) * 2  # a tag no row holds
        q_shards = self.mesh.shape[QUERY_AXIS]

        def mask(lo: int, take: int, rows: int):
            with obs.span("certified.filter_mask", trace_id,
                          parent=_CALL_SPAN, queries=take, maker=maker):
                given = np.tile(np.asarray(none, np.int32),
                                (-(-rows // q_shards) * q_shards, 1))
                given[:take] = ft[lo : lo + take]
                tp = shard(given, self.mesh, QUERY_AXIS)
                begun = _hooks.first_call_begin()
                words = _retry_transient(
                    lambda: prog(tp, *index["device"]), "filter_mask dispatch")
                acct.launched("filter_mask")
                _hooks.first_call_end(begun, prog, "filter_mask", trace_id,
                                      rows=tp.shape[0])
            return words

        return mask

    def _attr_sorted(self) -> np.ndarray:
        """The rows' attribute in ascending order (once a placement):
        how many rows a range holds is two binary searches."""
        if self._attr_sorted_cache is None:
            self._attr_sorted_cache = np.sort(self._row_attr)
        return self._attr_sorted_cache

    def _pallas_operands(self, precision: str) -> tuple:
        """The operand tail of the pallas certified program after
        ``(queries, db)`` — ONE home, beside :meth:`_pallas_setup`,
        so that no caller of the program can hand it an operand list
        of the wrong arity: int8 passes the quantized placement;
        pq passes (codes, codebooks, consts);
        the f32 precisions pass the scalar db-norm bound, and "bf16x3"
        after it the resident row operands where the program
        :meth:`_pallas_setup` last built takes them (ask after it); an
        inner-product or cosine placement appends its pair slack
        (:meth:`_pair_slack`) to any of them."""
        if precision == "int8":
            pl = self._int8_placement()
            tail = (pl["values"], pl["scales"], pl["norms"],
                    pl["consts"])
        elif precision == "pq":
            plq = self._pq_placement()
            tail = (plq["codes"], plq["books"], plq["consts"])
        else:
            tail = (np.float32(self._db_norm_max()),)
            if precision == "bf16x3" and self._operands_source == "resident":
                tail += self._operands_cache["parts"]
        if self._dot_aug or self._cosine_unit:
            # _certify_pack_spmd's aug_slack, rounded up to float32
            tail += (np.nextafter(np.float32(self._pair_slack()),
                                  np.float32(np.inf)),)
        return tail

    def _pair_slack(self) -> float:
        """What every comparison of two placed rows' squared distances
        allows for what the placement itself rounded, in the placed
        space's units: 0 for l2 (the rows are placed as given),
        ``DOT_AUG_SLACK * M`` for an inner-product placement (the
        appended column), ``COS_UNIT_SLACK`` for a cosine placement (the
        unit rows and the unit query)."""
        if self._dot_aug:
            return DOT_AUG_SLACK * self._dot_shift
        return COS_UNIT_SLACK if self._cosine_unit else 0.0

    def search_certified(
        self, queries, *, margin: Optional[int] = None,
        selector: str = "approx",
        batch_size: Optional[int] = None, tile_n: Optional[int] = None,
        precision: Optional[str] = None, return_distances: bool = True,
        survivors: Optional[int] = None,
        block_q: Optional[int] = None, final_select: Optional[str] = None,
        recall_target: Optional[float] = None,
        final_recall_target: Optional[float] = None,
        grid_order: Optional[str] = None,
        kernel: Optional[str] = None,
        tune_cache: Optional[str] = None,
        return_sqrt: bool = False,
        filter_tags=None,
        filter_range=None,
        _under: Optional[tuple] = None,
    ):
        """Exact lexicographic top-k via the certified pipeline, sharded.
        Returns (dists_f64, idx, stats).  L2, cosine and dot: the
        certificate is a squared-L2 bound, which cosine runs on unit
        rows and dot on norm-augmented ones (below).  L1 has no
        squared-L2-style bound and stays uncertified.

        **cosine** has l2's contract: the INDICES equal float64 brute
        force in lexicographic (1 - q.t / (|q| |t|), index) order over
        the float32 rows and queries AS GIVEN, whatever the selector.  A
        row or a query of zero norm has cosine 0 (distance 1) to
        everything.  The device runs the l2 machinery on float32 UNIT
        rows (normalised at construction with float64 norms, the
        queries here, ``_unit_rows``) to find the candidates and to
        prove none is missing; every inequality that compares two rows
        there allows ``COS_UNIT_SLACK`` (2^-20, absolute) for the
        rounding of the normalisation on both sides, and whatever it
        cannot tell apart the host ranks by the float64 cosine of the
        values as given (``rank_correct_runs``, ``refine_exact``,
        ``repair_uncertified``), never by the distance of the rounded
        unit rows.  The host keeps the rows as given and one float64
        norm a row, not a second copy of unit rows.  A zero row is
        placed as it is (the device sees it at half its distance), so a
        query that has one among its candidates is repaired like an
        uncertified one.  The returned DISTANCES are cosine distances c
        = 1 - cos: the counted selectors' are float64 values of the rows
        as given; the pallas selector's are the device's float32
        direct-difference values of the unit rows, halved, within
        ``2^-18 * (c + 1/8)`` of c (2^-18 c the device's own relative
        error, ops.pallas_knn.RANK_SLACK; 2^-21 covers the
        normalisation's 2^-22 twice), float64 of the rows as given
        wherever the host re-scored (near-tied and repaired entries).
        The span ``certified.metric_map``, one a call, is what the
        metric adds to the l2 machinery: the batch's float64 norms and
        its unit rows, made a sub-batch at a time, each immediately
        before its dispatch.  ``before_s`` is the FIRST sub-batch's map,
        the one the device idles through (the whole batch's where the
        call is one launch); ``under_s`` the later ones', made with the
        earlier launches queued, ``under_batches`` of them; ``after_s``
        0: nothing is scored after the repair.  ``stats["pair_slack"]``
        is the slack the call ran with, and
        ``stats["slack_fallback_queries"]`` (pallas selector) the
        queries whose certificate holds without it and fails with it.

        **dot / MIPS** has l2's contract: the INDICES equal float64
        brute force in lexicographic (-q.t, index) order over the
        float32 rows and queries AS GIVEN, whatever the selector.  The
        device runs the l2 machinery on the norm-augmented rows placed
        at construction (one more column, ``_augment_dot``) to find the
        candidates and to prove none is missing; every inequality that
        compares two rows there allows ``DOT_AUG_SLACK * M`` for the
        appended column's float32 rounding (M the largest squared row
        norm), and whatever it cannot tell apart the host ranks by the
        float64 inner product itself (``rank_correct_runs``,
        ``repair_uncertified``), never by the augmented difference.
        The returned SCORES are ``-q.t`` computed on the host in
        float64 from the float32 values (every product exact, pairwise
        sum): off by less than ``(D + 1) * 2^-53 * |q| |t|``, which is
        under ``(D + 1) * 2^-54 * (|q|^2 + M)``; the device's distance
        block is not fetched.  The span ``certified.metric_map``, one
        a call, is what the metric adds on both sides of the l2
        machinery (``before_s``: the zero column; ``after_s``: the
        scores).

        Two certificate strategies by ``selector``:

        - ``"approx"`` / ``"exact"``: coarse top-(k+margin), float64 host
          refine, then a distributed count-below pass (psum over the db
          axis) proves no neighbor was missed — two database passes.
        - ``"pallas"``: the fused kernel's exclusion bound IS the
          certificate (ops.pallas_knn) — ONE database pass; ``tile_n`` and
          ``precision`` tune the kernel.  ``precision="int8"`` streams a
          per-row-quantized int8 db (placed lazily, once — ops.quantize;
          ~2x bf16 MXU throughput, 1/4 the coarse HBM traffic) and widens
          the certify threshold by the PROVABLE per-query quantization
          bound ε, so quantization misses land in the fallback, never in
          the answer; uint8 (bvecs) databases ride byte-exact at unit
          scale.  The f32 placement stays resident for the rescore
          gather and the fallback/count programs.

        Queries failing certification rerun exactly either way; the
        returned INDICES are the exact lexicographic top-k regardless of
        selector.  Distances: the counted selectors return float64-exact
        values (unconditional host refine); the pallas selector returns
        device f32 direct-difference values (relative error <
        ops.pallas_knn.RANK_SLACK = 2^-18) except for near-tied or
        repaired entries, which are float64-exact — the cost of skipping
        the host refine that would otherwise cap throughput at ~4k q/s.

        ``return_distances=False`` returns ``(None, idx, stats)`` for any
        selector; on the pallas selector it also skips the top-k distance
        block's device->host transfer — worth ~20-25% at SIFT shape
        through a slow link, negligible when the sweep is
        compute-dominated (the published gist1m numbers differ only
        within run-to-run noise).

        ``margin``: m = k + margin candidates are kept a query.  None
        is the rule of what the placement can see
        (ops.pallas_knn.default_margin): 28, and from k = 232 an eighth
        of k, because the certificate needs the (m+2)-th neighbour
        further beyond the k-th than the kernel's tolerance and
        neighbours a fixed number of ranks apart lie closer the deeper
        the rank.

        ``batch_size`` streams the queries in fixed-size batches with the
        device stages pipelined against the host stages: every batch's
        device program is dispatched up front (one compiled shape, the
        last batch padded to it), so the host's share of batch b (the
        refine, or the copy down, unpack and tie repair) overlaps the
        device work of batches > b; the fallback repair runs once, over
        the whole call's flagged queries.  The answer does not depend
        on it.  None: the counted selectors run one batch (all queries
        at once); ``selector="pallas"`` asks the rule of what the call
        can see (``analysis.subbatch.certified_sub_batch``, no knob):
        ``SUB_BATCHES`` equal sub-batches of whole query blocks where a
        further launch costs the device next to nothing (resident row
        operands, placed rows a whole number of 128-column tiles wide)
        and the call holds that many sub-batches of 1,024 queries; one
        batch everywhere else, so a call of a few hundred queries is
        the launch it always was.  ``stats["batches"]`` says how many
        ran and ``stats["sub_batch"]`` why (``resident`` the rule's cut;
        ``per_call_operands``, ``layout_copy``, ``small`` what kept it
        to one; ``explicit`` this argument).

        Pallas-selector tuning knobs (``tile_n``, ``block_q``,
        ``survivors``, ``precision``, ``final_select``,
        ``grid_order``, ``final_recall_target``, ``kernel``): any knob
        left at None resolves through ``knn_tpu.tuning.resolve`` to
        the library default (``tuning.DEFAULT_KNOBS``), whatever the
        shape or the device; an EXPLICIT value wins.  No file and no
        environment variable names a knob: ``tune_cache`` has one legal
        value, None, and anything else raises (the winner cache it
        named went in PR 59).  ``kernel`` picks the db-streaming
        strategy (ops.pallas_knn.KERNELS: "tiled" | the one-launch
        double-buffered "streaming").  ``recall_target`` tunes the
        counted "approx" selector's per-element ApproxTopK recall
        (None = its default 0.95; raise toward 0.9999 with a wider
        ``margin`` to push the fallback rate below 1%).  The resolved
        knob set and its provenance land in
        ``stats["pallas_knobs"]`` / ``stats["tuning"]``.

        ``filter_tags`` (int ``[queries, 2]``, -1 for an absent tag; a
        placement built with ``row_tags``; l2 or cosine,
        ``selector="pallas"``, ``kernel="tiled"``) holds each query to
        the rows whose bag holds EVERY tag it names: the answer is the
        first k, in the metric's lexicographic (float64 distance,
        index) order, of those rows alone, padded with index -1 and
        distance +inf where fewer than k qualify; no returned row lacks
        a tag.  The
        predicate is applied INSIDE the kernel, between the MXU product
        and the bin-select: the program ``filter_mask`` turns the
        batch's tag ids into per-query validity words from the tag
        index on the device (:meth:`_tag_index`), the certified program
        takes them as one more operand, and a masked row is neither a
        candidate nor part of any bound, so the certificate is the one
        it was over the query's valid rows (``_certify_pack_spmd``).
        The repair's re-select lays the same words over its exact
        distances and its host scan reads the query's valid rows only.
        ``stats["filter"]`` says what the batch was: ``filter``
        (``tags`` / ``range`` / ``none``), ``bitmap_lookups``,
        ``list_lookups``, ``list_ids``, and how many answers came back
        ``short`` (1 to k-1 rows) and ``empty``.  ``None`` is the call
        it always was.

        ``filter_range`` (int ``[queries, 2]``, a half-open ``[lo,
        hi)`` a query; a placement built with ``row_attr``, one whole
        number a row, compared by VALUE: the rows need be in no order)
        holds each query to the rows whose attribute lies in its range,
        under the same contract and through the same kernel, certificate
        and repair; ``lo >= hi`` is a query no row answers.  What
        differs is the maker of the words (:class:`_CallFilter`): the
        program ``range_mask`` compares every (query, row) pair of a
        sub-batch on the device, a shard over its own rows, and needs no
        index.  ``stats["filter"]`` then carries ``valid_rows``, the
        placed rows in range summed over the queries.  A causal prefix
        ``row <= t`` is this with the row's position as its attribute,
        ``lo = 0`` and ``hi = t + 1``.

        Under **cosine** either filter runs on the unit rows with the
        pair slack as an unfiltered call does.  The slack is a bound A
        PAIR of placed rows: for any two rows u, v the order of their
        exact placed distances agrees with the order of their cosines
        as given once the distances differ by more than
        ``COS_UNIT_SLACK``, whatever other rows exist.  Every inequality
        of the certificate compares a kept row with ONE row outside the
        candidates, so over the valid rows alone each is the inequality
        it was, over fewer pairs.

        What still refuses either filter, with what is left to build:
        both at once (the AND of two word arrays), the counted
        selectors (their two passes take no words), ``kernel=
        "streaming"`` and ``"fused"`` (only the tiled kernel's body
        applies the words), a dot placement (the augmented rows'
        certificate under a mask has no test), a pre-placed array under
        cosine, the self-join, the weighted vote and range search
        (none of them takes the argument).

        Where a range's rows LIE: a placement built from host rows
        with ``row_attr`` is interleaved (class docstring), so a range
        on an attribute in the rows' order (``id >= N``) fills no
        kernel bin more than any other predicate, and the indices
        returned are the caller's as ever; ``stats["filter"]
        ["interleaved"]`` says so, ``stats["bin_overflow_queries"]``
        counts the flagged queries a full bin explains.  A PRE-PLACED
        array handed ``row_attr`` keeps the order it came in: with a
        sorted attribute a narrow range's valid rows share one row
        tile's 128 bins and every such query is repaired (exact, and
        ten times slower: PERF.md section 6, PRs 57 and 58); shuffle
        the rows before placing them.
        """
        self._require_resident("search_certified")
        if self.metric == "cosine":
            # runs the l2 certificate on the unit rows placed at
            # construction, COS_UNIT_SLACK allowed for their rounding
            # wherever two rows are compared; the host ranks by the
            # float64 cosine of the rows as given (docstring).  L1 stays
            # uncertified: the count-below / exclusion-bound certificates
            # are squared-L2 inequalities and |q-t|_1 admits no
            # gram-matrix form to bound (SURVEY §7 step 1).
            if not self._cosine_unit:
                raise ValueError(
                    "cosine search_certified ranks by the float64 cosine "
                    "of the rows as given, which it keeps on the host "
                    "beside the unit rows it places: construct ShardedKNN "
                    "from a host array (a pre-placed array arrives already "
                    "sharded and leaves neither; normalising it yourself "
                    "and using metric='l2' answers for the rounded unit "
                    "rows, not for the rows as given)"
                )
        elif self.metric == "dot":
            # MIPS runs the l2 certificate in the norm-augmented space
            # built at placement (__init__), with the augmentation's
            # rounding allowed for wherever two rows are compared; the
            # host ranks and scores by float64 inner product (docstring)
            if not self._dot_aug:
                raise ValueError(
                    "dot search_certified needs the norm-augmented "
                    "placement built at construction; construct ShardedKNN "
                    "from a host array (pre-placed arrays arrive already "
                    "sharded — augment the rows yourself and use "
                    "metric='l2' instead)"
                )
        elif self.metric not in ("l2", "sql2", "euclidean"):
            raise ValueError(
                "search_certified supports the l2, cosine and dot "
                "metrics only")
        if selector not in SELECTORS:
            raise ValueError(f"unknown selector {selector!r}; expected {SELECTORS}")
        # what the call's queries are held to, if anything: checked
        # here, before anything is timed or placed
        ft = None
        if filter_tags is not None or filter_range is not None:
            ft = _CallFilter(self, selector, filter_tags, filter_range)
        from knn_tpu.ops.certified import repair_uncertified

        # a call that is another's first pass runs under that call's
        # trace id, names its span as parent and adds to its account
        # (_under, private); any other keeps one of its own
        tid, parent, acct = _under or (obs.new_trace_id(), None, None)
        dot = self.metric == "dot"
        cosine = self.metric == "cosine"
        # what the host ranks by: the placement's own metric, on the
        # values as given
        rank_metric = self.metric if dot or cosine else "l2"
        slack = self._pair_slack()
        with obs.span(_CALL_SPAN, tid, selector=selector,
                      **({"parent": parent} if parent else {})) as call:
            if _under is None:
                acct = _call_account(
                    selector, *(() if ft is None else ("filter_mask",)))
            q_np = np.asarray(queries, dtype=np.float32)
            masked, mask = ft is not None, None
            if masked:
                ft.check(q_np.shape[0])
            map_s = _metric_map_seconds()
            if dot:
                with obs.trace.phase(map_s, "before_s", _METRIC_BEFORE):
                    # the zero column matching the placed rows'
                    # augmentation
                    q_np = np.concatenate(
                        [q_np, np.zeros((q_np.shape[0], 1), np.float32)],
                        axis=1)
            # the queries the host ranks with and, for cosine, the
            # float64 norms on both sides: (queries', rows')
            host_q, norms, unit = q_np, None, None
            if cosine:
                # the unit queries matching the placed unit rows: room
                # for them and their norms here, each sub-batch's filled
                # immediately before its dispatch (_QueryBatches)
                unit = _UnitQueries(host_q, map_s)
                q_np, q_norms = unit.rows, unit.norms
                norms = (q_norms, self._cos_norms)
            with obs.span("certified.prepare", tid, parent=_CALL_SPAN,
                          first_call=self._db_norm_max_cache is None):
                # every certified stage runs in squared-L2 space (for
                # cosine: on the unit vectors placed at construction /
                # normalized above; for dot: on the norm-augmented vectors)
                cert_metric = "l2" if dot or cosine else self.metric
                n_q = q_np.shape[0]
                shard_rows = self._shard_rows()
                # margin is bounded by both the db size and the per-shard
                # rows the coarse/fallback programs select from (k itself
                # fits: __init__ checks k <= shard_rows), as the
                # repair's widened re-select is
                max_widen = min(self.n_train, shard_rows)
                m = min(self.k + self._margin(margin), max_widen)
                db_np = self._host_train()

                if batch_size is not None and batch_size < 1:
                    raise ValueError(
                        f"batch_size must be >= 1, got {batch_size}")
                # the counted selectors: one batch unless the caller
                # cuts; the pallas selector's is setup's to resolve
                bs = n_q if batch_size is None else batch_size
                # the db-side term of the certificate tolerance is
                # query-independent and cached across calls (a float64
                # pass over all N rows)
                db_norm_max = self._db_norm_max()

                d = np.empty((n_q, self.k))
                i = np.empty((n_q, self.k), dtype=np.int64)

                tune_info = None
                if selector == "pallas":
                    # ONE knob-resolution home (knn_tpu.tuning): explicit
                    # args > library defaults, and nothing between them
                    # (a tune_cache that is not None raises there)
                    from knn_tpu import tuning

                    knobs, tune_info = tuning.resolve_full(
                        self.n_train, self._given_width, self.k,
                        metric=cert_metric, dtype=self._dtype_key,
                        cache_path=tune_cache,
                        overrides=dict(
                            tile_n=tile_n, precision=precision,
                            survivors=survivors, block_q=block_q,
                            final_select=final_select,
                            final_recall_target=final_recall_target,
                            grid_order=grid_order, kernel=kernel,
                        ),
                    )
                    # kernel geometry, the compiled program and its
                    # operand tail: resolved in this stage, so the spans
                    # below time batches only
                    terms = self._kernel_terms(q_np, knobs["precision"],
                                               unit)
                    # an inner-product call's scores are made on the
                    # host (below): no distance block leaves the device
                    device_d = return_distances and not dot
                    if masked:
                        ft.require_tiled(knobs["kernel"])
                    prog, m_prog, w, interpret = self._pallas_setup(
                        m - self.k, include_distances=device_d,
                        terms=terms, batch_rows=batch_size, call_rows=n_q,
                        trace_id=tid, acct=acct, **knobs,
                        **({"masked": True} if masked else {}))
                    # the sub-batch: the caller's, or the rule's reading
                    # of what setup resolved (analysis.subbatch)
                    bs, sub_why = self._sub_batch
                    ops_tail = self._pallas_operands(knobs["precision"])
                    if masked:
                        # the maker's placement in the resolved tile's
                        # layout (built by the first call that needs
                        # it), and the maker of each batch's words
                        words_tile = self._kernel_tile
                        ft.place(words_tile, interpret, tid, acct)
                        mask = ft.words()
                batches = _QueryBatches(q_np, bs, unit)
            n_batches = len(batches)
            call.set("queries", n_q)
            call.set("batches", n_batches)
            call.set("metric", self.metric)
            call.set("pair_slack", slack)
            # what the cross-shard merges of this call move: every batch
            # is one program whose merge keeps m+1 columns a query (the
            # pallas program, setup's m) or m (the counted coarse
            # select), over the query rows as placed
            q_shards = self.mesh.shape[QUERY_AXIS]
            launch_rows = -(-bs // q_shards) * q_shards
            merge_bytes = self._record_merge_bytes(
                n_batches * launch_rows,
                m_prog + 1 if selector == "pallas" else m)
            if selector == "pallas":
                bad, n_corrected, n_by_slack = self._certify_pallas(
                    batches, bs, d, i, q_np, db_np, prog=prog, w=w,
                    ops_tail=ops_tail, precision=knobs["precision"],
                    trace_id=tid, want_distances=device_d,
                    rank_metric=rank_metric, host_q=host_q, norms=norms,
                    acct=acct,
                    **({"mask": mask} if mask is not None else {}),
                )
            else:
                bad = self._certify_counted(
                    batches, bs, m, d, i, q_np, db_np, db_norm_max,
                    selector, recall_target=recall_target,
                    metric=cert_metric, rank_metric=rank_metric,
                    host_q=host_q, norms=norms, trace_id=tid, acct=acct,
                )

            def _select(qb, widen):
                # widened exact-selector re-select (bounded by the
                # per-shard rows the SPMD select can fetch); the returned
                # f32 scores carry the re-certification exclusion value,
                # so the select must run in f32 (dtype_key None) even when
                # the main path is bf16 — certification_tolerance only
                # covers f32 error
                exact = _knn_program(
                    self.mesh, widen, cert_metric, self.merge, self.n_train,
                    self.train_tile, None, "exact",
                    dcn_merge=self.dcn_merge,
                )
                nonlocal merge_bytes
                # scan_rows_copied: the exact scan reads the placed rows
                # where they lie (ops.topk.knn_search_tiled)
                with obs.span("certified.repair.reselect", tid,
                              parent="certified.repair", widen=widen,
                              rows=qb.shape[0], scan_rows_copied=0):
                    bq, _ = self._place_queries(qb)
                    merge_bytes += self._record_merge_bytes(
                        bq.shape[0], widen)
                    begun = _hooks.first_call_begin()
                    fs, fi = exact(bq, self._tp)
                    acct.launched("reselect")
                    _hooks.first_call_end(begun, exact, "reselect", tid,
                                          rows=bq.shape[0])
                    n_b = qb.shape[0]
                    fs = np.asarray(fs)
                    acct.ready("reselect")
                    return fs[:n_b], self._row_ids(np.asarray(fi)[:n_b])

            def _select_masked(qb, widen):
                # the same re-select held to the flagged queries' words:
                # blocks of _MASKED_RESELECT_ROWS queries (their unpacked
                # validity is rows x shard rows bytes), each block's
                # words made anew by the call's maker
                exact = _masked_reselect_program(
                    self.mesh, widen, self.merge, self.n_train,
                    self.train_tile, words_tile, self.dcn_merge)
                nonlocal merge_bytes
                fs, fi = [], []
                with obs.span("certified.repair.reselect", tid,
                              parent="certified.repair", widen=widen,
                              rows=qb.shape[0], masked=True):
                    for lo in range(0, qb.shape[0], _MASKED_RESELECT_ROWS):
                        part = qb[lo : lo + _MASKED_RESELECT_ROWS]
                        pad = _MASKED_RESELECT_ROWS - part.shape[0]
                        bq, _ = self._place_queries(
                            np.pad(part, ((0, pad), (0, 0))))
                        merge_bytes += self._record_merge_bytes(
                            bq.shape[0], widen)
                        words = select_mask(lo, part.shape[0],
                                            _MASKED_RESELECT_ROWS)
                        begun = _hooks.first_call_begin()
                        ps, pi = exact(bq, self._tp, words)
                        acct.launched("reselect")
                        _hooks.first_call_end(begun, exact, "reselect", tid,
                                              rows=bq.shape[0])
                        ps = np.asarray(ps)
                        acct.ready("filter_mask")
                        acct.ready("reselect")
                        fs.append(ps[: part.shape[0]])
                        fi.append(np.asarray(pi)[: part.shape[0]])
                return np.concatenate(fs), self._row_ids(np.concatenate(fi))

            # a filtered call's repair: the maker over the flagged
            # queries alone, and the host's statement of the predicate
            select_mask = valid_rows_fn = None
            if masked:
                select_mask, valid_rows_fn = ft.words(bad), ft.valid_rows
            with obs.span("certified.repair", tid, parent=_CALL_SPAN,
                          fallback_queries=int(bad.size),
                          **({"masked": True} if masked else {})) as sp:
                repair = repair_uncertified(
                    d, i, self.k, m, bad, q_np, db_np,
                    select_fn=_select_masked if masked else _select,
                    max_widen=max_widen,
                    db_norm_max=db_norm_max, metric=rank_metric,
                    pair_slack=slack, dot_shift=self._dot_shift,
                    rank_queries=host_q, norms=norms,
                    valid_rows_fn=valid_rows_fn,
                )
                sp.set("host_exact_queries",
                       repair.get("host_exact_queries", 0))
            # which merge answered, where the choice came from, and what
            # it moved: on the call's event and in the caller's stats
            merged = {"db_shards": self.db_shards, "merge": self.merge,
                      "merge_source": self.merge_source,
                      "merge_bytes": merge_bytes}
            if selector == "pallas":
                # the width the kernel handed each shard's final select
                # and the width its top-k scanned: equal unless the
                # bin-merge engaged (ops.pallas_knn.select_merge_geometry)
                width, merged_width = self._select_widths
                merged["select_width"] = width
                merged["select_merged_width"] = merged_width
                # the lane-rows the merge's last group hangs over the
                # kernel's width and masks by index (_select_merge)
                merged["select_merge_short"] = self._select_merge_short
                obs.counter(
                    _mn.SELECT_MERGE_CALLS,
                    engaged="true" if merged_width < width else "false",
                ).inc(n_batches)
                # the products the kernel's bf16 split formed, read off
                # the rows and the batch (_kernel_terms): 3 MXU passes,
                # or 2 or 1 where a low half was all zero
                merged["terms"] = terms
                merged["mxu_passes"] = terms.count("+") + 1
                obs.counter(_mn.KERNEL_TERMS, terms=terms).inc(
                    n_batches)
                # how the kernel cut a row tile, by setup's reading:
                # the columns (one chunk under the tiled kernel,
                # 128-column chunks under the other two) and the rows
                # (the whole tile a grid step wherever it fits VMEM at
                # that width, else the row blocks setup handed the
                # program)
                merged["dim_chunk"], merged["dim_chunks"] = (
                    self._dim_chunking)
                merged["row_block"], merged["row_steps"] = (
                    self._row_blocking)
                obs.counter(_mn.KERNEL_DIM_CHUNKS,
                            chunks=str(merged["dim_chunks"]),
                            row_steps=str(merged["row_steps"])).inc(
                    n_batches)
                # what ran the top-(m+2) over that width: the Pallas
                # stage or XLA's top_k and gather
                # (ops.pallas_knn.final_select_geometry)
                merged["final_select_stage"] = self._final_select_stage
                obs.counter(_mn.FINAL_SELECT_CALLS,
                            stage=self._final_select_stage).inc(
                    n_batches)
                # where the kernel got its row operands: the resident
                # placement, or the program's own prologue in every call
                # (_row_operands)
                merged["operands"] = self._operands_source
                obs.counter(_mn.KERNEL_OPERANDS,
                            source=self._operands_source).inc(n_batches)
                # how many launches the call was cut into and why
                # (analysis.subbatch.REASONS): the rule's cut, what kept
                # the rule from cutting, or the caller's batch_size
                merged["sub_batch"] = sub_why
                obs.counter(_mn.CERTIFIED_SUB_BATCH_CALLS,
                            why=sub_why).inc()
                # the launches the call made, by the kernel's survivor
                # depth and the final select's stage, and the flagged
                # queries a full kernel bin explains (_bin_overflows)
                merged["survivor_depth"] = self._plan["survivor_depth"]
                merged["bin_overflow_queries"] = self._bin_overflows(
                    i[bad])
                self._count_launches(n_batches)
            for key, value in merged.items():
                call.set(key, value)
            stats = {
                "fallback_queries": int(bad.size),
                "certified": n_q - int(bad.size),
                "batches": n_batches,
                "metric": self.metric,
                "pair_slack": slack,
                **repair,
                **merged,
            }
            # what the call was held to: nothing, or each query's tags
            # (the lookups by the form their tag is kept in, the ids the
            # listed ones named, the answers that ran out of valid rows)
            told = {"filter": "none"}
            if masked:
                # rows past the valid ones come back as the sentinel at
                # +inf: the contract's padding is index -1
                gone = i >= self.n_train
                i[gone] = -1
                d[gone] = np.inf
                found = self.k - gone.sum(axis=1)  # rows an answer holds
                told = ft.told(found)
            for key, value in told.items():
                call.set(key, value)
            stats["filter"] = told
            # which queries the repair answered, by position in the call
            stats["fallback_positions"] = bad.tolist()
            if selector == "pallas":
                stats["rank_corrected_queries"] = n_corrected
                # interpret: the value _pallas_setup resolved and the
                # kernel ran with, so a caller can tell which one answered
                stats["pallas_knobs"] = {
                    **knobs, "interpret": interpret, "terms": terms,
                    "mxu_passes": merged["mxu_passes"],
                    "dim_chunk": merged["dim_chunk"],
                    "dim_chunks": merged["dim_chunks"],
                    "row_block": merged["row_block"],
                    "row_steps": merged["row_steps"],
                    "final_select_stage": merged["final_select_stage"],
                    "select_merge_short": merged["select_merge_short"],
                    "operands": merged["operands"],
                    "sub_batch": sub_why, "batches": n_batches,
                    "survivor_depth": merged["survivor_depth"]}
                stats["tuning"] = tune_info
            # mirror the quality signals into the telemetry registry —
            # the per-call stats dict stays the API, the registry
            # accumulates the process-lifetime truth a scraper reads
            # (docs/OBSERVABILITY.md)
            obs.counter(_mn.CERTIFIED_QUERIES, selector=selector).inc(n_q)
            obs.counter(_mn.CERTIFIED_METRIC_QUERIES,
                        metric=self.metric).inc(n_q)
            obs.counter(_mn.CERTIFIED_FALLBACKS, selector=selector).inc(
                int(bad.size))
            obs.counter(_mn.CERTIFIED_GENUINE_MISSES,
                        selector=selector).inc(
                repair.get("fallback_genuine_misses", 0))
            obs.counter(_mn.CERTIFIED_FALSE_ALARMS,
                        selector=selector).inc(
                repair.get("fallback_false_alarms", 0))
            obs.counter(_mn.CERTIFIED_HOST_EXACT, selector=selector).inc(
                repair.get("host_exact_queries", 0))
            if selector == "pallas":
                obs.counter(_mn.CERTIFIED_RANK_CORRECTED).inc(n_corrected)
                if cosine:
                    # the one program that tells a certificate the pair
                    # slack failed from one that fails without it
                    stats["slack_fallback_queries"] = n_by_slack
                    call.set("slack_fallback_queries", n_by_slack)
                    for outcome, n_out in (
                            ("certified", n_q - int(bad.size)),
                            ("uncertified", int(bad.size) - n_by_slack),
                            ("uncertified_by_slack", n_by_slack)):
                        obs.counter(_mn.CERTIFIED_SLACK_QUERIES,
                                    outcome=outcome).inc(n_out)
            if return_distances and dot:
                # pairwise_dot values (negative inner product) of the
                # rows the indices name, in float64 on the host: the
                # query's appended column is an exact zero, so the
                # product over the placed columns is q.t itself.  No
                # back-map of a float32 squared distance (a difference
                # of nearly equal numbers) is involved.
                from knn_tpu.ops.refine import exact_scores

                with obs.trace.phase(map_s, "after_s", _METRIC_AFTER):
                    d = exact_scores(db_np, q_np, i, "dot")
            if dot or cosine:
                _record_metric_map(tid, self.metric, map_s)
            if return_distances and return_sqrt:
                # true Euclidean values (knn_mpi.cpp:48 / sklearn
                # convention); indices and certification are unaffected
                # (monotone map)
                from knn_tpu.ops.distance import metric_values

                d = metric_values(d, self.metric)
            if _under is None:
                acct.close(tid, _CALL_SPAN)
            return (d if return_distances else None), i, stats

    def range_search_certified(self, queries, *, radius_sq: float,
                               selector: str = "pallas"):
        """Exact, complete range search: every db row within a squared
        radius of each query, through the certified path.  Returns
        ``(lims, idx, dist, stats)`` in big-ann-benchmarks' range-search
        format: ``lims`` int64 ``[Q + 1]``, query ``i``'s results are
        ``idx[lims[i]:lims[i + 1]]`` (int64) with ``dist`` (float64)
        beside them, each list in (distance, index) order.

        **Contract.**  For every query the list is exactly ``{t :
        d64(q, t) <= radius_sq}``, INCLUSIVE, ``d64`` the float64
        squared L2 distance over the float32 rows and queries as given.
        Nothing is capped and nothing is dropped; ``radius_sq`` is the
        threshold in ranking space (squared) and is compared as given,
        never squared from a root.  l2 family only: the certificate is
        a squared-L2 bound and a cosine or inner-product radius has no
        part in it.  (:meth:`radius_search` is the bounded, float32
        counterpart: a Euclidean radius, at most ``max_neighbors`` rows
        a query, truncation reported, membership at the boundary by
        float32 arithmetic.)

        Three steps, and no knob (the one collect width is read off the
        placement's ``k``):

        1. **First pass**: :meth:`search_certified` at the placed ``k``,
           as it stands.  That top-k is exact in float64 (distance,
           index) order, so every row it does NOT return is at least as
           far as its k-th: a query whose k-th distance is over
           ``radius_sq`` is **known complete**, and its answer is the
           prefix at or under the radius.
        2. **Completion** of the truncated queries (k-th distance at or
           under ``radius_sq``): gathered into sub-batches of
           ``ops.radius.RANGE_SUB_BATCH`` and finished on the device by
           ONE program (``_range_program``): a pass over the placed
           rows (``within_words``: count and mark every row whose
           float32 distance is at or under ``radius_sq`` plus
           ``certification_tolerance``, a superset) and the marked
           words compacted at ``ops.radius.range_width(k)``
           (``compact_words``), compiled by the first call that has a
           truncated query.  A query whose count passes that width is
           finished by an exact host scan
           (``ops.refine.host_exact_range``) and counted as such.
        3. **Pack**: every returned pair's distance is float64
           (``ops.refine.exact_pair_scores``) and membership is decided
           on that value, so rows inside float32's error band of the
           threshold are decided in float64 too.  The complete queries'
           prefixes are scored while the completion's first sub-batch
           is on the device.

        ``stats`` is the first pass's (``certified``,
        ``fallback_queries``, ``pallas_knobs``, ``tuning`` ...) plus
        ``stats["range"]``: ``queries``, ``complete``, ``truncated``,
        ``host_scan`` (each query is one of the three), ``results``,
        ``width`` (the collect width; 0 where nothing was truncated),
        ``sub_batches`` and ``radius_sq``.  One span
        ``certified.range_call`` a call, with the first pass's
        ``certified.call`` tree, ``certified.range_complete`` and
        ``certified.range_pack`` under it (docs/OBSERVABILITY.md)."""
        self._require_resident("range_search_certified")
        if self.metric not in ("l2", "sql2", "euclidean"):
            raise ValueError(
                f"range_search_certified supports the l2 family only, not "
                f"{self.metric!r}: radius_sq is a squared-L2 threshold")
        radius_sq = float(radius_sq)
        if not 0.0 <= radius_sq < np.inf:
            raise ValueError(
                f"radius_sq must be finite and >= 0, got {radius_sq}")
        from knn_tpu.ops.pallas_knn import RANK_SLACK
        from knn_tpu.ops.radius import RANGE_SUB_BATCH
        from knn_tpu.ops.refine import exact_pair_scores

        tid = obs.new_trace_id()
        with obs.span(_RANGE_SPAN, tid, selector=selector,
                      radius_sq=radius_sq) as call:
            # the outermost call's account: the first pass adds to it
            acct = _call_account(selector, "range")
            q_np = np.asarray(queries, dtype=np.float32)
            n_q, k = q_np.shape[0], self.k
            d, i, stats = self.search_certified(
                q_np, selector=selector, _under=(tid, _RANGE_SPAN, acct))
            db_np = self._host_train()
            # the k-th row decides: in float32 where its value is clear
            # of the threshold by the device's rank slack, in float64
            # where it is not (the first pass's near-tied and repaired
            # entries are float64 already)
            maybe = d <= radius_sq / (1.0 - 2.0 * RANK_SLACK)
            inside = d[:, k - 1] <= radius_sq / (1.0 + 2.0 * RANK_SLACK)
            band = np.flatnonzero(~inside & maybe[:, k - 1])
            if band.size:
                inside[band] = exact_pair_scores(
                    db_np, q_np, band, i[band, k - 1]) <= radius_sq
            # all the rows there are leave nothing to complete
            truncated = inside if k < self.n_train else np.zeros_like(inside)
            tq = np.flatnonzero(truncated)
            # the completion's first sub-batch is sent off before the
            # pack, which then has the host while the device works
            first = self._range_launch(
                q_np, db_np, tq[:RANGE_SUB_BATCH], radius_sq, acct, tid)
            with obs.span("certified.range_pack", tid,
                          parent=_RANGE_SPAN) as sp:
                # the complete queries' prefix: whatever could be in by
                # the float32 value is scored and decided in float64;
                # nonzero() walks it in the first pass's own (distance,
                # index) order
                pq, cols = np.nonzero(maybe & ~truncated[:, None])
                pi = i[pq, cols]
                pd = exact_pair_scores(db_np, q_np, pq, pi)
                keep = pd <= radius_sq
                pq, pi, pd = pq[keep], pi[keep], pd[keep]
                sp.set("rows_returned", int(pi.size))
            with obs.span("certified.range_complete", tid,
                          parent=_RANGE_SPAN, queries=int(tq.size)) as sp:
                cq, ci, cd, done = self._range_complete(
                    q_np, db_np, tq, radius_sq, first, acct, tid)
                sp.set("rung", done["width"])
                sp.set("sub_batches", done["sub_batches"])
                sp.set("host_scan_queries", done["host_scan"])
                sp.set("rows_returned", int(ci.size))
            # both are sorted by query, so a stable sort of the queries
            # alone interleaves them
            rows = np.concatenate([pq, cq])
            order = np.argsort(rows, kind="stable")
            idx = np.concatenate([pi, ci])[order]
            dist = np.concatenate([pd, cd])[order]
            lims = np.zeros(n_q + 1, np.int64)
            np.cumsum(np.bincount(rows, minlength=n_q), out=lims[1:])
            n_long = int(tq.size)
            rng_stats = {
                "queries": n_q, "radius_sq": radius_sq,
                "complete": n_q - n_long,
                "truncated": n_long - done["host_scan"],
                "host_scan": done["host_scan"],
                "results": int(idx.size), "width": done["width"],
                "sub_batches": done["sub_batches"],
            }
            for key in ("complete", "truncated", "host_scan", "results"):
                call.set(key, rng_stats[key])
            for outcome in ("complete", "truncated", "host_scan"):
                obs.counter(_mn.RANGE_QUERIES, outcome=outcome).inc(
                    rng_stats[outcome])
            obs.counter(_mn.RANGE_RESULTS).inc(idx.size)
            acct.close(tid, _RANGE_SPAN)
            return lims, idx, dist, {**stats, "range": rng_stats}

    def _range_launch(self, q_np, db_np, sub, radius_sq: float, acct,
                      trace_id=None):
        """The completion's device program (``_range_program``) for the
        queries ``sub`` (at most a sub-batch of positions in ``q_np``),
        sent to the device and not waited for: its ``(counts,
        compact)``, or None for no query.  The call's account ``acct``
        is told of the launch."""
        from knn_tpu.ops.certified import certification_tolerance
        from knn_tpu.ops.radius import RANGE_SUB_BATCH, range_width

        if not sub.size:
            return None
        chunk = np.zeros((RANGE_SUB_BATCH, q_np.shape[1]), np.float32)
        chunk[:sub.size] = q_np[sub]
        # a negative threshold marks nothing (distances are clamped at
        # 0): the sub-batch's unused rows
        thr = np.full(RANGE_SUB_BATCH, -1.0, np.float32)
        # the float32 pass errs by under this (ops.certified), so the
        # widened threshold marks a superset; rounded up to float32
        tol = certification_tolerance(
            q_np[sub], db_np, db_norm_max=self._db_norm_max())
        thr[:sub.size] = np.nextafter(
            (radius_sq + tol).astype(np.float32), np.float32(np.inf))
        # the pass's row tile, which the decode has to know too
        prog = _range_program(self.mesh, self.n_train,
                              self.train_tile or 131072,
                              range_width(self.k))
        qp, _ = self._place_queries(chunk)
        thr_p, _ = self._place_queries(thr)
        begun = _hooks.first_call_begin()
        out = _retry_transient(
            lambda: prog(qp, self._tp, thr_p), "range completion dispatch")
        acct.launched("range")
        _hooks.first_call_end(begun, prog, "range", trace_id,
                              rows=qp.shape[0])
        return out

    def _range_complete(self, q_np, db_np, tq, radius_sq: float, first,
                        acct, trace_id=None):
        """Step 2 of :meth:`range_search_certified`: the complete result
        lists of the truncated queries ``tq`` (positions in ``q_np``),
        as flat ``(query positions, db rows, float64 distances)`` sorted
        by (query, distance, index), and what was done: the collect
        width (0 where nothing was sent), the sub-batches sent and the
        queries finished by the host scan.  ``first`` is the first
        sub-batch's answer, already on its way (:meth:`_range_launch`);
        ``acct`` learns when each sub-batch's answer is there."""
        from knn_tpu.ops.radius import (RANGE_SUB_BATCH, decode_words,
                                        range_width)
        from knn_tpu.ops.refine import exact_pair_scores, host_exact_range

        width = range_width(self.k)
        shards, shard_rows = self.db_shards, self._shard_rows()
        tile = self.train_tile or 131072
        done = {"width": width if tq.size else 0, "sub_batches": 0,
                "host_scan": 0}
        found = []  # (query positions, db rows, float64 distances)
        secs = {}  # the phases' seconds, summed over the sub-batches
        for lo in range(0, tq.size, RANGE_SUB_BATCH):
            sub = tq[lo:lo + RANGE_SUB_BATCH]
            counts, compact = first if lo == 0 else self._range_launch(
                q_np, db_np, sub, radius_sq, acct, trace_id)
            done["sub_batches"] += 1
            with obs.trace.phase(secs, _RANGE_WAIT, _RANGE_WAIT):
                counts = np.asarray(counts)  # the host waits here
                acct.ready("range")
                # a query that marks no more rows than the width marks no
                # more words than it on any shard
                over = counts[:sub.size] > width
                if not over.all():
                    compact = np.asarray(compact)
            if not over.all():
                with obs.trace.phase(secs, _RANGE_DECODE, _RANGE_DECODE):
                    per = compact.shape[2] // shards
                    qs, ts = [], []
                    for s in range(shards):
                        qi, ri = decode_words(
                            compact[:, :, s * per:(s + 1) * per], shard_rows,
                            tile)
                        qs.append(qi)
                        ts.append(ri + s * shard_rows)
                    qi, ti = np.concatenate(qs), self._row_ids(
                        np.concatenate(ts))
                with obs.trace.phase(secs, _RANGE_SCORE, _RANGE_SCORE):
                    sel = ~over[qi]
                    qi, ti = sub[qi[sel]], ti[sel]
                    dd = exact_pair_scores(db_np, q_np, qi, ti)
                    sel = dd <= radius_sq
                    found.append((qi[sel], ti[sel], dd[sel]))
            if over.any():
                # last resort: more marked rows than the width holds
                hq = sub[over]
                done["host_scan"] += int(hq.size)
                with obs.trace.phase(secs, _RANGE_HOST_SCAN,
                                     _RANGE_HOST_SCAN):
                    qi, ti, dd = host_exact_range(db_np, q_np[hq], radius_sq)
                    found.append((hq[qi], ti, dd))
        if not found:
            cq = ci = np.empty(0, np.int64)
            cd = np.empty(0)
        else:
            with obs.trace.phase(secs, _RANGE_ORDER, _RANGE_ORDER):
                cq, ci, cd = (np.concatenate(x) for x in zip(*found))
                ci = ci.astype(np.int64)
                # (distance, index) order within each query: one short
                # sort a query, cheaper than one three-key sort over all
                # of them
                order = np.argsort(cq, kind="stable")
                cq, ci, cd = cq[order], ci[order], cd[order]
                starts = np.flatnonzero(np.diff(cq, prepend=-1))
                for a, b in zip(starts, np.append(starts[1:], cq.size)):
                    seg = np.lexsort((ci[a:b], cd[a:b]))
                    ci[a:b], cd[a:b] = ci[a:b][seg], cd[a:b][seg]
        for name, seconds in secs.items():
            acct.add(name, seconds)
        return cq, ci, cd, done

    def _certify_counted(
        self, batches, bs, m, d, i, q_np, db_np, db_norm_max, selector,
        *, host_q, norms, recall_target: Optional[float] = None,
        metric: Optional[str] = None, rank_metric: str = "l2",
        trace_id=None, acct=obs.trace.NOOP_ACCOUNT,
    ):
        """Two-pass certificate: coarse select + refine, then the
        distributed count-below program proves completeness.  Returns the
        flagged query indices.  The call's account ``acct`` is told of
        both passes' launches and fetches (programs ``counted`` and
        ``count``).

        The count threshold is ADAPTIVE: the refine already produced the
        float64 distances of every candidate, so each query counts
        against the midpoint of the first inter-neighbor gap at rank
        j >= k that exceeds twice the count pass's float32 tolerance
        (count <= j proves no outsider sits at or below the j-th
        candidate, and ranks <= j are float64-refined).  The fixed
        ``d_k + tol`` threshold false-alarmed whenever ANY point sat
        within tol of d_k — at SIFT1M scale ~2.4% of queries
        (2026-07-30 probe: 100/4096 fallbacks, all false alarms at
        recall_target 0.9999); a gap beyond which the midpoint clears
        tol almost always exists inside the margin window, so the
        adaptive form certifies those queries instead.

        ``rank_metric="dot"`` (an inner-product placement: rows and
        queries norm-augmented, ``metric`` l2): the refine ranks by the
        float64 negated inner product s, and the thresholds are made
        from ``|q|^2 + M + 2 s``, s in the count program's own space.  A
        placed row's exact augmented distance is that plus c_t, |c_t| <=
        ``_pair_slack()`` / 2 (``DOT_AUG_SLACK``), so the tolerance
        grows by ``_pair_slack()``: a row the count found at or above a
        threshold is then above every refined candidate below it in
        inner product too.

        ``rank_metric="cosine"`` (a cosine placement: ``q_np`` the unit
        queries the programs run on, ``host_q`` the queries as given,
        ``norms`` the float64 norms of both sides): the refine ranks by
        the float64 cosine distance c of the values as given, and the
        thresholds are made from ``2 c``.  A placed row's exact distance
        is that plus p_t, |p_t| < ``_pair_slack()``
        (``COS_UNIT_SLACK``), and the tolerance grows by it likewise.  A
        query with a zero row among its candidates is flagged: the
        count program sees that row at |q^|^2, not at the 2 its cosine
        distance of 1 stands for."""
        from knn_tpu.ops.certified import certification_tolerance
        from knn_tpu.ops.refine import refine_exact

        n_q = q_np.shape[0]
        k = self.k
        coarse = _knn_program(
            self.mesh, m, metric or self.metric, self.merge, self.n_train,
            self.train_tile, self._dtype_key, selector,
            recall_target=recall_target, dcn_merge=self.dcn_merge,
        )
        count_fn = _count_program(self.mesh, self.n_train, self.train_tile)

        # stage 1: dispatch every batch's coarse select (async on device)
        coarse_out = []
        for lo, chunk, pad in batches:
            qp, _ = self._place_queries(chunk)
            begun = _hooks.first_call_begin()
            coarse_out.append((
                qp, _retry_transient(lambda q=qp: coarse(q, self._tp),
                                     "coarse dispatch")))
            acct.launched("counted")
            _hooks.first_call_end(begun, coarse, "counted", trace_id,
                                  rows=qp.shape[0])

        # stage 2: per batch — sync its candidates, float64 host refine
        # (overlapping later batches' device work), dispatch its count
        count_out = []
        zero_hit = []  # cosine: queries with a zero row among the candidates
        for (lo, chunk, pad), (qp, (_, ci)) in zip(batches, coarse_out):
            take = bs - pad
            ci = self._row_ids(_fetch_or_redispatch(
                ci, lambda q=qp: coarse(q, self._tp)[1], "coarse fetch"
            )[:take])
            acct.ready("counted")
            m_avail = ci.shape[1]
            # refine ALL candidates: ranks k..m feed the gap search
            d_m, i_m = refine_exact(
                db_np, host_q[lo : lo + take], ci, m_avail, rank_metric,
                _refine.norms_rows(norms, slice(lo, lo + take)))
            d[lo : lo + take], i[lo : lo + take] = d_m[:, :k], i_m[:, :k]
            tol = certification_tolerance(
                q_np[lo : lo + take], db_np, db_norm_max=db_norm_max
            ) + self._pair_slack()
            # the refined values in the count program's own space
            if rank_metric == "dot":
                q_norm = (q_np[lo : lo + take].astype(np.float64) ** 2
                          ).sum(-1)
                d_m = q_norm[:, None] + self._dot_shift + 2.0 * d_m
            elif rank_metric == "cosine":
                d_m = 2.0 * d_m
                if self._cos_zero_rows.size:
                    zero_hit.append(lo + np.flatnonzero(np.isin(
                        ci, self._cos_zero_rows).any(axis=1)))
            # first rank j in [k, m_avail) whose gap d[j] - d[j-1]
            # exceeds 2*tol (js = that j, or k when none does — the
            # fixed-threshold behavior)
            gaps = d_m[:, k:] - d_m[:, k - 1 : -1]  # [take, m_avail - k]
            # the midpoint is cast to f32 for the count program: demand
            # the gap also clear that rounding, and never use a gap to a
            # sentinel (+inf) rank
            f32_round = 4.0 * float(np.finfo(np.float32).eps) * np.abs(
                d_m[:, k:])
            open_gap = (gaps > 2.0 * tol[:, None] + f32_round) & np.isfinite(
                d_m[:, k:])
            if open_gap.shape[1] == 0:  # m == k: no window, fixed threshold
                has = np.zeros(take, dtype=bool)
                js = np.full(take, k)
            else:
                has = open_gap.any(axis=-1)
                js = np.where(has, k + open_gap.argmax(axis=-1), k)
            dj = np.take_along_axis(d_m, js[:, None] - 1, axis=-1)[:, 0]
            # js == m_avail only when has is False (np.where evaluates
            # both branches): clip the gather, the fixed arm wins anyway
            d_js = np.take_along_axis(
                d_m, np.minimum(js, m_avail - 1)[:, None], axis=-1
            )[:, 0]
            mid = np.where(has, 0.5 * (dj + d_js), dj + tol)
            thr_p = np.full(qp.shape[0], -np.inf, dtype=np.float32)
            thr_p[:take] = mid
            thr_s = shard(thr_p, self.mesh, QUERY_AXIS)
            begun = _hooks.first_call_begin()
            count_out.append((
                lo, take, js, qp, thr_s, mid, d_m[:, k - 1].copy(),
                _retry_transient(lambda q=qp, t=thr_s: count_fn(q, self._tp, t),
                                 "count dispatch"),
            ))
            acct.launched("count")
            _hooks.first_call_end(begun, count_fn, "count", trace_id,
                                  rows=qp.shape[0])

        # stage 3: collect certificates (count <= per-query rank bound)
        flagged = []
        for lo, take, js, qp, thr_s, mid, d_k, c in count_out:
            c_np = _fetch_or_redispatch(
                c, lambda q=qp, t=thr_s: count_fn(q, self._tp, t),
                "count fetch")
            acct.ready("count")
            over = c_np[:take] > js
            flagged.append(lo + np.flatnonzero(over))
            # certificate-margin telemetry: per certified query, the
            # headroom between the k-th refined distance and the count
            # threshold it was proven against (relative; ~0 = one
            # near-boundary point away from a fallback)
            ok = ~over
            if obs.enabled() and ok.any():
                denom = np.maximum(np.abs(mid[ok]), 1e-30)
                obs.histogram(_mn.CERTIFIED_MARGIN, path="sharded"
                              ).observe_many(
                    ((mid[ok] - d_k[ok]) / denom).tolist())
        flagged += zero_hit
        return (np.unique(np.concatenate(flagged)) if flagged
                else np.empty(0, np.int64))

    def _margin(self, margin: Optional[int]) -> int:
        """A certified call's ``margin``: the caller's, or the rule of
        what the placement can see (ops.pallas_knn.default_margin: 28
        up to k = 231, an eighth of k from there)."""
        if margin is not None:
            return int(margin)
        from knn_tpu.ops.pallas_knn import default_margin

        return default_margin(self.k)

    def _count_launches(self, n: int) -> None:
        """``n`` more launches of the last resolved plan's program."""
        obs.counter(
            _mn.CERTIFIED_LAUNCHES,
            survivor_depth=str(self._plan["survivor_depth"]),
            final_select_stage=self._final_select_stage).inc(n)

    def _bin_overflows(self, top: np.ndarray) -> int:
        """How many of the flagged queries, whose exact top-k after the
        repair is ``top`` [F, k] (global row ids; a filtered call's
        padding and sentinels past the rows), failed their certificate
        on a FULL BIN: some kernel bin holds more of the k than the
        survivor depth of the last resolved plan, so at least one of
        them was no candidate and the bin's bound lay inside the top-k.
        A row's bin is (its shard, its row tile there, its lane): lane
        ``r % 128`` of tile ``r // row_tile`` for shard-local row ``r``
        (ops.pallas_knn: members strided 128 apart).  Counted, and
        added to ``knn_tpu_certified_bin_overflow_queries_total``."""
        tile, depth = self._plan["row_tile"], self._plan["survivor_depth"]
        top = self._row_places(top)  # a bin is where a row LIES
        shard, local = np.divmod(top, self._shard_rows())
        bins = (shard * -(-self._shard_rows() // tile) + local // tile
                ) * 128 + local % 128
        bins = np.sort(np.where((top >= 0) & (top < self.n_train), bins, -1),
                       axis=1)
        # a run of depth + 1 equal bins among the sorted
        full = ((bins[:, depth:] == bins[:, :-depth])
                & (bins[:, depth:] >= 0)).any(axis=1)
        n = int(full.sum())
        obs.counter(_mn.CERTIFIED_BIN_OVERFLOW).inc(n)
        return n

    def _note_plan(self, plan: dict) -> None:
        """Keep what :meth:`_pallas_setup` just resolved
        (:meth:`certified_plan` hands it out) and say it, as one
        ``certified.plan`` event, when it is not what the last event of
        this placement said: once a placement for a caller whose calls
        are alike, once more where a call of another size or with
        another knob resolves otherwise."""
        self._plan = plan
        if plan != self._plan_told:
            self._plan_told = plan
            obs.emit_event("certified.plan", **plan)

    def certified_plan(self, n_queries: int, *, margin: Optional[int] = None,
                       batch_size: Optional[int] = None,
                       tune_cache: Optional[str] = None, **knobs) -> dict:
        """What ``search_certified(queries, selector="pallas")`` would
        run for a call of ``n_queries`` queries on this placement,
        before any is made: a dict, resolved by the code the call
        itself resolves with (``knn_tpu.tuning.resolve_full``, then
        :meth:`_pallas_setup`; ``margin``, ``batch_size``,
        ``tune_cache`` and the kernel's ``knobs`` as the call takes
        them), so it compiles and launches nothing but what the first
        call's ``certified.prepare`` would: the resident row operands
        are built here where the device keeps them.  A batch is taken to
        have inexact float32 values (``terms``: all the products the
        rows ask for).

        ``k``, ``m`` (the candidates kept a query: k + margin, capped),
        ``row_tile``, ``survivor_depth`` and the ``overflow_share``
        modelled at it (``ops.pallas_knn.survivor_depth``),
        ``select_width`` (the kernel's candidate columns a shard),
        ``select_merged_width`` and ``select_merge_stage`` (``pallas``
        where the bin-merge engages, else ``none``),
        ``final_select_stage`` (``pallas`` / ``xla``), ``operands``
        (``resident`` / ``per_call``), ``dim_chunk(s)``, ``row_block`` /
        ``row_steps``, ``queries``, ``sub_batch_rows`` and ``sub_batch``
        (why: ``analysis.subbatch.REASONS``), ``batches`` (launches a
        call), ``launch_bytes`` (what one launch's queries hold on a
        chip, ``analysis.hbm.certified_query_bytes``), ``room_bytes``
        (what the chip had left for them when the operands were placed;
        0: the backend reports no limit) and ``interpret``.  A call's
        ``stats["pallas_knobs"]`` reports the same values under the
        names the two share."""
        if self.metric not in ("l2", "sql2", "euclidean", "cosine", "dot"):
            raise ValueError(
                "certified_plan: search_certified supports the l2, cosine "
                "and dot metrics only")
        self._require_resident("certified_plan")
        from knn_tpu import tuning

        resolved, _ = tuning.resolve_full(
            self.n_train, self._given_width, self.k,
            metric="l2" if self.metric in ("cosine", "dot") else self.metric,
            dtype=self._dtype_key, cache_path=tune_cache, overrides=knobs)
        self._db_norm_max()  # the placement's walk: what the rows are
        from knn_tpu.ops.pallas_knn import bf16x3_terms

        terms = bf16x3_terms(
            resolved["precision"] == "bf16x3" and self._rows_lo_zero, False)
        m = min(self.k + self._margin(margin), self.n_train,
                self._shard_rows())
        self._pallas_setup(
            m - self.k, include_distances=self.metric != "dot", terms=terms,
            batch_rows=batch_size, call_rows=int(n_queries), **resolved)
        return dict(self._plan)

    def _pallas_setup(self, margin: int, tile_n: Optional[int],
                      precision: str,
                      survivors: Optional[int] = None,
                      block_q: Optional[int] = None,
                      final_select: str = "exact",
                      include_distances: bool = True,
                      final_recall_target: Optional[float] = None,
                      grid_order: str = "query_major",
                      kernel: str = "tiled",
                      terms: str = "hh+hl+lh",
                      batch_rows: Optional[int] = None,
                      call_rows: Optional[int] = None,
                      trace_id: Optional[str] = None,
                      acct=obs.trace.NOOP_ACCOUNT,
                      masked: bool = False, vote=None,
                      own_rows: bool = False):
        """(program, m, analysis_window, interpret) for the one-pass
        certified path — the ONE home of the kernel-geometry margin cap
        and the packed-output window, shared by :meth:`_certify_pallas`
        and every other caller of the program so they can never run
        different programs or unpack different column layouts.

        ``interpret`` is the one knob nobody passes: resolved HERE
        (compiled on a TPU backend, Pallas interpret mode elsewhere —
        the CPU tests), handed to the program builders, which hand it to
        the kernel, and returned so ``stats["pallas_knobs"]`` reports
        the value the kernel was actually given.  ``terms`` is
        :meth:`_kernel_terms`'s reading of the data, no knob: left out,
        the program forms every product and is right for any rows.

        How the kernel cuts a row tile is resolved HERE too, once: its
        rows by ops.pallas_knn.row_blocking over the resolved tile, the
        query block a shard runs for batches of ``batch_rows`` queries
        (left out, a full ``block_q``), the parts ``terms`` streams and
        ``masked``, handed to the program as the kernel's static
        ``row_block``, so that what ``search_certified`` reports
        (``self._row_blocking``) is what the kernel was given, not a
        second reading of the shape; its columns by
        ops.pallas_knn.dim_chunking, a function of the width, the
        precision and the kernel alone, which the kernel reads for
        itself to the same answer (``self._dim_chunking``).

        The default precision's row operands are resolved HERE as well
        (:meth:`_row_operands` at the resolved tile and ``terms``; the
        first resolution builds them, under the caller's ``trace_id``
        and call account ``acct``): the program is built to take them
        as arguments where they are kept, :meth:`_pallas_operands` then
        hands them over, and ``self._operands_source`` says which.

        And the SUB-BATCH of a call of ``call_rows`` queries, which
        follows from them: ``batch_rows`` where the caller names one,
        else what ``analysis.subbatch.certified_sub_batch`` reads off
        the operands' source, the placed rows' width, the query block
        and the mesh's query shards.  ``self._sub_batch`` is ``(rows,
        why)``; the query block and the row blocks above are resolved at
        those rows.  Without ``call_rows`` (the probes that launch the
        program themselves) it is ``batch_rows`` as given.

        ``masked`` builds the program that takes a batch's validity
        words after that tail (a filtered call); the resolved row
        tile, whose layout the words are in, is ``self._kernel_tile``.

        ``vote`` (``predict_certified(vote="softmax")``: ``(1 / T,
        classes_out, delta)``) builds :func:`_pallas_vote_program` from
        the same resolution: the device labels follow the tail.

        ``own_rows`` (a bulk self-join's block, :class:`_SelfJoinCall`;
        "bf16x3" under the tiled kernel) builds
        :func:`_pallas_self_program` from the same resolution, for
        launches of the resolved sub-batch's rows: the program takes the
        first row of a launch where the search program takes the
        queries."""
        from knn_tpu.ops.pallas_knn import (
            BIN_W,
            BLOCK_Q,
            TILE_N,
            _geometry,
            default_backend_is_tpu,
            dim_chunking,
            effective_block_q,
            final_select_geometry,
            row_blocking,
            select_merge_geometry,
            survivor_depth,
        )

        from knn_tpu.analysis import hbm
        from knn_tpu.analysis.subbatch import certified_sub_batch
        from knn_tpu.utils.config import CERTIFIED_PRECISIONS

        if precision not in CERTIFIED_PRECISIONS:
            raise ValueError(
                f"precision {precision!r} has no certified tolerance "
                f"model; use one of {CERTIFIED_PRECISIONS}"
            )
        interpret = not default_backend_is_tpu()
        quant_offset = 0.0
        if precision == "int8":
            # builds (and caches) the quantized placement: the program
            # needs the translation-invariance shift as a static constant
            quant_offset = self._int8_placement()["offset"]

        shard_rows = self._shard_rows()
        # the survivors a kernel bin keeps: the caller's, or the least
        # depth whose modelled share of full-bin fallbacks is under the
        # limit, read off the shard's rows and m+2 (ONE home for the
        # arithmetic: ops.pallas_knn.survivor_depth), and at that depth
        # the same tile the kernel will pick (effective_tile, inside
        # it), so the m-cap below matches the kernel's real candidate
        # width.  Everything below, the program too, gets the RESOLVED
        # depth
        survivors, eff_tile, overflow_share = survivor_depth(
            shard_rows, tile_n or TILE_N, survivors,
            min(self.k + margin, shard_rows) + 2)
        self._kernel_tile = eff_tile
        out_w = _geometry(eff_tile, survivors)[2]
        # m is bounded by the db, the per-shard rows, and the kernel's
        # per-shard candidate width minus the two slots the exclusion
        # value needs (ops.pallas_knn.local_certified_candidates)
        select_width = -(-shard_rows // eff_tile) * out_w
        m = min(self.k + margin, self.n_train, shard_rows, select_width - 2)
        if m <= self.k:
            raise ValueError(
                f"pallas selector: margin headroom m={m} <= k={self.k} on "
                f"{shard_rows}-row shards; lower tile_n or use "
                f"selector='approx'"
            )
        # what the final top-(m+2) of each shard scans: the kernel's
        # width, or the bin-merge's where it engages (the same helper
        # local_select_rescore asks)
        merge = select_merge_geometry(select_width, m)
        self._select_widths = (
            select_width, select_width if merge is None else merge[2])
        self._select_merge_short = 0 if merge is None else (
            merge[0] * merge[1] - select_width // BIN_W)
        self._final_select_stage = (
            "pallas" if final_select == "exact" and final_select_geometry(
                self._select_widths[1], m) is not None else "xla")
        resident = precision == "bf16x3" and self._row_operands(
            eff_tile, "hl" in terms, trace_id=trace_id, acct=acct)
        self._operands_source = "resident" if resident else "per_call"
        bq = block_q or BLOCK_Q
        q_shards = self.mesh.shape[QUERY_AXIS]
        # what one query of a launch holds on its chip, and what the
        # chip has left beside the rows and their operands by the
        # reading the operands were decided on (0: no bound; a precision
        # that keeps none has no reading)
        w = _analysis_window(self.k, m)
        query_bytes = hbm.certified_query_bytes(
            m, self._tp.shape[1], select_width, w + -(-(w - 1) // 32) + 1
            + (self.k if include_distances else 0))
        room = hbm.certified_launch_room(
            self._operands_cache["room"] if precision == "bf16x3" else {})
        self._sub_batch = (
            (batch_rows, "explicit") if call_rows is None
            else certified_sub_batch(
                call_rows, batch_size=batch_rows,
                operands=self._operands_source, width=self._tp.shape[1],
                block_q=bq, query_shards=q_shards,
                query_bytes=query_bytes, room_bytes=room))
        batch_rows = self._sub_batch[0]
        if batch_rows is not None:
            bq = effective_block_q(bq, -(-batch_rows // q_shards))
        self._dim_chunking = dim_chunking(
            self._tp.shape[1], precision=precision, kernel=kernel)
        self._row_blocking = row_blocking(
            self._tp.shape[1], tile_n=eff_tile, block_q=bq,
            precision=precision, kernel=kernel, terms=terms,
            survivors=survivors, masked=masked)
        self._note_plan({
            "k": self.k, "m": m, "row_tile": eff_tile,
            "survivor_depth": survivors,
            "overflow_share": overflow_share,
            "select_width": select_width,
            "select_merged_width": self._select_widths[1],
            "select_merge_stage": "none" if merge is None else "pallas",
            "select_merge_short": self._select_merge_short,
            "final_select_stage": self._final_select_stage,
            "operands": self._operands_source,
            "dim_chunk": self._dim_chunking[0],
            "dim_chunks": self._dim_chunking[1],
            "row_block": self._row_blocking[0],
            "row_steps": self._row_blocking[1],
            "queries": call_rows, "sub_batch_rows": batch_rows,
            "sub_batch": self._sub_batch[1],
            "batches": (None if call_rows is None
                        else -(-call_rows // batch_rows)),
            "launch_bytes": (None if batch_rows is None else
                             -(-batch_rows // q_shards) * query_bytes),
            "room_bytes": room, "interpret": interpret})
        # the program gets setup's RESOLVED tile, not the raw request:
        # m was capped so that width(eff_tile) >= m+2, which makes the
        # kernel's own effective_tile(min_width=m+2) a fixpoint — the
        # tile the kernel runs is provably the tile this m-cap assumed
        # (ADVICE r4: the raw-tile plumbing let the two diverge on small
        # padded dbs where m is capped by n_train)
        if own_rows:
            prog = _pallas_self_program(
                self.mesh, m, self.k, self.merge, eff_tile, self.n_train,
                batch_rows, survivors=survivors, block_q=block_q,
                final_select=final_select,
                final_recall_target=final_recall_target,
                grid_order=grid_order, dcn_merge=self.dcn_merge,
                interpret=interpret, terms=terms,
                row_block=self._row_blocking[0],
                resident_parts=len(resident) - 1 if resident else 0)
            return prog, m, w, interpret
        if vote is not None:
            prog = _pallas_vote_program(
                self.mesh, m, self.k, self.merge, eff_tile, precision,
                self.n_train, vote, survivors=survivors, block_q=block_q,
                final_select=final_select,
                final_recall_target=final_recall_target,
                grid_order=grid_order, kernel=kernel,
                quant_offset=quant_offset, dcn_merge=self.dcn_merge,
                interpret=interpret, terms=terms,
                row_block=self._row_blocking[0],
                resident_parts=len(resident) - 1 if resident else 0)
            return prog, m, w, interpret
        prog = _pallas_certified_program(
            self.mesh, m, self.k, self.merge, eff_tile, precision,
            n_train=self.n_train, survivors=survivors,
            block_q=block_q, final_select=final_select,
            include_distances=include_distances,
            final_recall_target=final_recall_target,
            grid_order=grid_order, kernel=kernel,
            quant_offset=quant_offset, dcn_merge=self.dcn_merge,
            interpret=interpret, terms=terms,
            augmented=self._dot_aug or self._cosine_unit,
            row_block=self._row_blocking[0],
            resident_parts=len(resident) - 1 if resident else 0,
            **({"masked": True} if masked else {}),
            **({"slack_outcome": True} if self._cosine_unit else {}),
        )
        return prog, m, w, interpret

    def _certify_pallas(
        self, batches, bs, d, i, q_np, db_np, *, prog, w, ops_tail,
        precision, host_q, norms, trace_id=None, want_distances=True,
        rank_metric="l2", acct=obs.trace.NOOP_ACCOUNT, mask=None,
    ):
        """One-pass certificate, host side.  The device already ranked the
        candidates, flagged uncertified rows, and marked near-tie pairs
        (_pallas_certified_program); the host fetches ONLY the windowed
        indices, the bit-packed tight-pair mask, and the bad flags (plus
        the top-k distance block when ``want_distances``) — nothing wider
        crosses the slow device->host link — then repairs tie runs in
        float64 (ops.refine.rank_correct_runs, by ``rank_metric`` on
        ``host_q``, the queries the host ranks with (the placed ``q_np``
        but for cosine): an inner-product placement's runs are
        ordered by inner product, not by the augmented difference; a
        cosine placement's by the cosine of the rows and queries as
        given, ``norms`` their float64 norms, not by the distance of the
        unit rows, and the device's distances are halved into cosine
        distances before the host patches its own in).  A cosine
        placement's program also says which of its uncertified queries
        the pair slack alone failed (bit 1 of the flag word), and a
        query with a zero row among its candidates is flagged here: the
        device saw that row at half its distance.  Returns (flagged
        query indices, rank-corrected query count, queries failed by the
        slack alone).  ``prog`` and ``w`` are
        :meth:`_pallas_setup`'s, ``ops_tail`` :meth:`_pallas_operands`'s.
        Every sub-batch's program is launched before the first is
        fetched, so the host's share of sub-batch b (the copy down, the
        unpack, the tie repair) runs while the device is on b+1; a
        cosine call's ``batches`` (:class:`_QueryBatches`) map a
        sub-batch's rows of ``q_np`` and ``norms`` as the dispatch loop
        reaches it, so ``q_np`` is whole only after that loop.  Each
        sub-batch's stages (``certified.dispatch``, ``.device_wait``,
        ``.d2h``, ``.unpack``, ``.rank_correct``) are profiler
        annotations one an occurrence and ONE record a call, the sum
        over the sub-batches (``obs.trace.stage``): the call's account
        ``acct`` keeps them, is told of every launch and fetch, and is
        handed what ``unpack_certified`` and ``rank_correct_runs`` say of
        their own insides (the copies; the buffers, the re-score and the
        ordering), summed likewise.

        ``mask`` (a filtered call: :meth:`_filter_words`' maker of a
        batch's validity words from its slice of the tag ids or the
        ranges) launches the program ``filter_mask`` ahead of each
        batch's certified program and hands it the words as its last
        operand; the host waits for the words only once the certified
        program is queued behind them, to close ``filter_mask``'s
        flight."""
        from knn_tpu.ops.refine import rank_correct_runs

        k = self.k
        fetch = _staged_fetch(acct)
        bad_mask = np.zeros(q_np.shape[0], dtype=bool)
        n_corrected = n_by_slack = 0
        cosine = rank_metric == "cosine"

        def repair(lo, pad, packed, redo):
            """ONE fetch of the packed output, then float64 tie-run
            repair."""
            nonlocal n_corrected, n_by_slack
            take = bs - pad
            packed_np = _fetch_or_redispatch(packed, redo, "pallas fetch",
                                             fetch=fetch)
            with obs.trace.stage(acct, "certified.unpack") as sp:
                gi_np, tight_np, bad_np, dk_np = unpack_certified(
                    packed_np[:take], k, w, want_distances
                )
                # positions become the caller's ids here: everything
                # the host does from now on ranks and scores by id
                gi_np = self._row_ids(gi_np)
            acct.add(_UNPACK_COPIES, sp.attrs.get("copies_s", 0.0))
            with obs.trace.stage(acct, "certified.rank_correct") as sp:
                own = {}  # the caller's share of the buffers
                with obs.trace.phase(own, "buffers_s",
                                     _refine.PHASE_BUFFERS):
                    d32k = (None if dk_np is None
                            else dk_np.astype(np.float64))
                    if cosine and d32k is not None:
                        d32k *= 0.5  # unit rows: |q^ - t^|^2 = 2 (1 - cos)
                dc, ic, n_c = rank_correct_runs(
                    gi_np, tight_np, k, host_q[lo : lo + take], db_np,
                    d32k=d32k, metric=rank_metric,
                    norms=_refine.norms_rows(norms, slice(lo, lo + take)),
                )
                sp.set("queries_corrected", n_c)
            told = sp.attrs  # what rank_correct_runs said of its insides
            acct.add(_refine.PHASE_BUFFERS, told.get("buffers_s", 0.0)
                     + own.get("buffers_s", 0.0))
            acct.add(_refine.PHASE_SCORE, told.get("score_s", 0.0),
                     gather_s=told.get("gather_s", 0.0),
                     arith_s=told.get("arith_s", 0.0))
            acct.add(_refine.PHASE_ORDER, told.get("order_s", 0.0))
            n_corrected += n_c
            obs.counter(_mn.RANK_CORRECT_MEMBERS).inc(told.get("members", 0))
            if dc is not None:
                d[lo : lo + take] = dc
            i[lo : lo + take] = ic
            if cosine:
                n_by_slack += int(failed_by_slack(packed_np[:take], w).sum())
                if self._cos_zero_rows.size:
                    bad_np = bad_np | np.isin(
                        gi_np, self._cos_zero_rows).any(axis=1)
            bad_mask[lo : lo + take] = bad_np

        # stage 1: dispatch every sub-batch (async on device)
        outs = []
        for lo, chunk, pad in batches:
            tail = ops_tail
            if mask is not None:
                tail += (mask(lo, bs - pad, bs),)
            with obs.trace.stage(acct, "certified.dispatch",
                                 h2d_bytes=chunk.nbytes):
                qp, _ = self._place_queries(chunk)
                begun = _hooks.first_call_begin()
                outs.append((qp, tail, _retry_transient(
                    lambda q=qp, tail=tail: prog(q, self._tp, *tail),
                    "pallas dispatch")))
                acct.launched("certified")
                _hooks.first_call_end(begun, prog, "certified", trace_id,
                                      rows=qp.shape[0])

        if precision in ("int8", "pq") and obs.enabled():
            # the per-query certified quantization bound ε — the quality
            # signal the device certificate computes and discards
            # (quantize.score_error_bound_device / pq's twin):
            # recomputed host-side (O(Q·D), noise next to the O(Q·N·D)
            # sweep) and recorded as a distribution so a scraper sees
            # how tight the bound ran, not just the bench's one max; here,
            # where every sub-batch is dispatched: a cosine call's q_np
            # is whole only now (_QueryBatches)
            if precision == "pq":
                from knn_tpu.ops.pq import score_error_bound_pq

                eps = score_error_bound_pq(
                    q_np, self._pq_placement()["stats"])
            else:
                from knn_tpu.ops.quantize import score_error_bound

                pl = self._int8_placement()
                eps = score_error_bound(q_np, pl["stats"],
                                        offset=pl["offset"])
            obs.histogram(_mn.CERTIFIED_QUANT_BOUND).observe_many(eps)

        # stage 2: per sub-batch — fetch + repair, in dispatch order, the
        # later ones' programs on the device meanwhile
        for (lo, chunk, pad), (qp, tail, packed) in zip(batches, outs):
            if mask is not None:
                jax.block_until_ready(tail[-1])
                acct.ready("filter_mask")
            repair(lo, pad, packed,
                   lambda q=qp, tail=tail: prog(q, self._tp, *tail))
        return np.flatnonzero(bad_mask), n_corrected, n_by_slack

    def self_join_call(self, lo: int, hi: int, block_rows: int, *,
                       trace_id: Optional[str] = None,
                       acct=obs.trace.NOOP_ACCOUNT) -> "_SelfJoinCall":
        """The state of one bulk certified SELF-join over rows ``lo ..
        hi`` of this placement (every row a query of the placement it
        is part of, its own row out by id), in blocks of ``block_rows``
        for knn_tpu.join.engine's pipeline to order
        (:class:`_SelfJoinCall`).  A squared-L2 placement this class
        laid out itself, resident; everything else refuses, with what it
        lacks."""
        self._require_resident("the certified self-join")
        if self._row_order is not None:
            raise ValueError(
                "the certified self-join takes each block's queries out of "
                "the placed rows by position and takes a query's own row "
                "out by it; this placement was handed row_attr and its "
                "rows lie interleaved on the device: blocks of positions "
                "mapped back to row ids are not built (ROADMAP's table of "
                "what refuses), construct a second ShardedKNN without "
                "row_attr for the join")
        if self.metric not in ("l2", "sql2", "euclidean"):
            raise ValueError(
                f"the certified self-join answers squared-L2 placements: "
                f"this one is metric={self.metric!r}, whose host ranks by "
                f"another value than the device (the inner product, the "
                f"cosine of the rows as given) and whose queries are "
                f"mapped before they are placed; its self-join is not "
                f"built yet (ROADMAP R13)")
        if self._pre_placed:
            raise ValueError(
                "the certified self-join takes each block's queries out "
                "of the placed rows where they lie, in whole 128-column "
                "lane tiles, and ranks with the host's copy of them: a "
                "pre-placed array keeps the width it was handed in at and "
                "leaves no host copy; construct ShardedKNN from a host "
                "array")
        if not 0 <= lo < hi <= self.n_train:
            raise ValueError(
                f"rows=({lo}, {hi}) is no range of the {self.n_train} "
                f"placed rows")
        return _SelfJoinCall(self, int(lo), int(hi), int(block_rows),
                             trace_id, acct)

    def predict_certified(
        self, queries, *, vote: str = "majority",
        temperature: Optional[float] = None, classes_out: int = 1,
        margin: Optional[int] = None, selector: str = "approx",
        batch_size: Optional[int] = None, tile_n: Optional[int] = None,
        precision: Optional[str] = None, kernel: Optional[str] = None,
        tune_cache: Optional[str] = None,
    ):
        """Certified-exact classification.  Kernel knobs left at None
        resolve through ``knn_tpu.tuning`` exactly like
        :meth:`search_certified`.

        ``vote="majority"`` (the reference's): exact neighbour lists from
        :meth:`search_certified`, then the reference vote (ops.vote), whose
        first-to-reach tie-break reads the ORDER of the neighbours, so it
        keeps the ranked path.  Returns (labels [Q] int32, stats).

        ``vote="softmax"`` (a cosine placement; ``temperature`` T): the
        weighted vote of the k-NN evaluation protocol.  With c_i the
        float64 cosine distance of row i as given and N_k(q) the first k
        rows in lexicographic (c_i, i) order, class c's total is ``s_c =
        sum of exp((1 - c_i) / T) over i in N_k(q) with label c``, and the
        answer is the classes with s_c > 0 in lexicographic (-s_c, c)
        order, the first ``classes_out``, padded with -1, and their
        totals.  Returns (classes [Q, classes_out] int32, totals [Q,
        classes_out] float64, stats).  The CLASSES equal that float64
        answer for every query, whatever the selector.

        With ``selector="pallas"`` the answer is made and certified ON THE
        DEVICE, by the certified program's own tail (the placement, the
        kernel, the select, the exclusion and merge-drop certificate and
        the sub-batch rule are :meth:`search_certified`'s; the tail ends
        in ``_vote_pack``): labels of the first k merged candidates
        gathered from the replicated device labels, float32 weights
        ``exp(-c32 / T)`` from the device's direct-difference distances
        (the factor ``exp(1 / T)`` every weight shares is the host's, in
        float64), class totals, the first
        ``classes_out + 1`` of them, and one flag word a query from the
        vote certificate (``_certify_pack_spmd``'s docstring):
        ``boundary`` where the k-th and (k+1)-th candidates are too close
        to tell apart, ``margin`` where two adjacent class totals are
        within ``2 * vote_delta(T, k)`` of each other.  What comes to the
        host in every call is ``2 classes_out + 1`` words a query; the
        ranked candidates stay on the device until a sub-batch has a
        flagged query (one in some tens is): then its windows,
        ``min(k + 17, m + 1)`` words a query, follow in a second COPY,
        no program (a program would queue behind the later sub-batches'
        launches, and the host with it).  An
        unflagged query is answered by the device's classes, its totals
        the device's float32 values (within ``vote_delta`` of the
        float64 ones, relatively).  A flagged one is re-voted by the host
        in float64 from the rows as given and their float64 norms: over
        its whole window where the boundary was in doubt (the float64
        first k of the window ARE the neighbours: every row outside it is
        proven farther), over its first k where only a margin was; an
        uncertified one after :func:`ops.certified.repair_uncertified`
        gave its neighbours.  Those totals are float64.  The span
        ``certified.vote_repair``, one a call, is that host work
        (``queries``, ``members``).

        The other selectors vote on the host from
        :meth:`search_certified`'s float64 neighbours (totals float64).
        Any mesh: the labels are replicated and the merge is the
        search's.  Metrics other than cosine refuse ``vote="softmax"``: a
        weight is ``exp(cosine similarity / T)``.

        ``stats``: the search's, and ``vote``, ``temperature``,
        ``classes_out``, ``vote_boundary_queries``,
        ``vote_margin_queries`` (a query counts under the first of
        fallback, boundary, margin that holds), ``vote_repaired_queries``
        and ``vote_delta``."""
        if self._labels is None:
            raise RuntimeError("ShardedKNN built without labels; predict unavailable")
        if vote not in VOTES:
            raise ValueError(f"unknown vote {vote!r}; expected {VOTES}")
        knobs = dict(margin=margin, batch_size=batch_size, tile_n=tile_n,
                     precision=precision, kernel=kernel,
                     tune_cache=tune_cache)
        if vote == "majority":
            if temperature is not None or classes_out != 1:
                raise ValueError(
                    "vote='majority' is the reference's unweighted vote: it "
                    "takes no temperature and answers one label a query")
            _, idx, stats = self.search_certified(
                queries, selector=selector,
                return_distances=False,  # labels only: skip the d transfer
                **knobs)
            votes = majority_vote(jnp.asarray(self._labels_host[idx]),
                                  self.num_classes)
            return np.asarray(votes), stats
        if self.metric != "cosine":
            raise ValueError(
                f"vote='softmax' weighs a neighbour by exp(cosine similarity "
                f"/ T): a cosine placement only, this one is "
                f"{self.metric!r} (normalise the rows yourself and the "
                f"weights are those of the rounded unit rows)")
        if temperature is None or not temperature >= VOTE_MIN_TEMPERATURE:
            raise ValueError(
                f"vote='softmax' needs temperature >= "
                f"{VOTE_MIN_TEMPERATURE} (a device weight is exp(-c / T) in "
                f"float32, c up to 2), got {temperature!r}")
        if not 1 <= classes_out <= self.k:
            raise ValueError(
                f"classes_out must be in [1, k={self.k}], got {classes_out}")
        temperature, classes_out = float(temperature), int(classes_out)
        told = {"vote": vote, "temperature": temperature,
                "classes_out": classes_out}
        if selector == "pallas":
            return self._vote_certified(queries, told, **knobs)
        from knn_tpu.ops.refine import vote_exact

        d, idx, stats = self.search_certified(queries, selector=selector,
                                              **knobs)
        classes, totals = vote_exact(self._labels_host[idx], d, temperature,
                                     classes_out)
        obs.counter(_mn.VOTE_QUERIES, outcome="host").inc(idx.shape[0])
        return classes, totals, {
            **stats, **told, "vote_boundary_queries": 0,
            "vote_margin_queries": 0, "vote_repaired_queries": idx.shape[0]}

    def _vote_labels(self):
        """The labels the vote program gathers from: the replicated
        device labels, a zero row's as ``-1 - label`` (the device sees
        such a row at half its distance; ``_vote_pack`` flags a query
        that has one in its window).  Built by the first call."""
        if self._vote_labels_dev is None:
            marked = self._labels_host
            if self._cos_zero_rows.size:
                marked = marked.copy()
                marked[self._cos_zero_rows] = -1 - marked[self._cos_zero_rows]
                self._vote_labels_dev = replicate(self._as_placed(marked),
                                                  self.mesh)
            else:
                self._vote_labels_dev = self._labels
        return self._vote_labels_dev

    def _vote_certified(self, queries, told: dict, *, margin, batch_size,
                        tile_n, precision, kernel, tune_cache):
        """``predict_certified(vote="softmax", selector="pallas")``: the
        pallas branch of :meth:`search_certified` with the vote program in
        the certified program's place (one call span, the same prepare,
        setup, sub-batches, dispatch-all-then-fetch and fallback repair),
        and :meth:`_vote_pallas` for its host stage.  A body of its own so
        that ``search_certified``'s frame stays what the trace-stack
        tripwire recorded."""
        from knn_tpu import tuning
        from knn_tpu.ops.certified import repair_uncertified
        from knn_tpu.ops.refine import vote_exact

        self._require_resident("predict_certified")
        temperature, classes_out = told["temperature"], told["classes_out"]
        vote = (1.0 / temperature, classes_out,
                vote_delta(temperature, self.k))
        tid = obs.new_trace_id()
        slack = self._pair_slack()
        with obs.span(_CALL_SPAN, tid, selector="pallas", **told) as call:
            acct = _call_account("pallas", voted=True)
            host_q = np.asarray(queries, dtype=np.float32)
            map_s = _metric_map_seconds()
            # the unit queries matching the placed unit rows, each
            # sub-batch's filled immediately before its dispatch
            unit = _UnitQueries(host_q, map_s)
            q_np, q_norms = unit.rows, unit.norms
            norms = (q_norms, self._cos_norms)
            with obs.span("certified.prepare", tid, parent=_CALL_SPAN,
                          first_call=self._db_norm_max_cache is None):
                n_q = q_np.shape[0]
                shard_rows = self._shard_rows()
                max_widen = min(self.n_train, shard_rows)
                m = min(self.k + self._margin(margin), max_widen)
                db_np = self._host_train()
                if batch_size is not None and batch_size < 1:
                    raise ValueError(
                        f"batch_size must be >= 1, got {batch_size}")
                db_norm_max = self._db_norm_max()
                knobs, tune_info = tuning.resolve_full(
                    self.n_train, self._given_width, self.k, metric="l2",
                    dtype=self._dtype_key, cache_path=tune_cache,
                    overrides=dict(tile_n=tile_n, precision=precision,
                                   kernel=kernel))
                terms = self._kernel_terms(q_np, knobs["precision"], unit)
                prog, m_prog, w, interpret = self._pallas_setup(
                    m - self.k, include_distances=False, terms=terms,
                    batch_rows=batch_size, call_rows=n_q, trace_id=tid,
                    acct=acct, vote=vote, **knobs)
                bs, sub_why = self._sub_batch
                ops_tail = (self._pallas_operands(knobs["precision"])
                            + (self._vote_labels(),))
                batches = _QueryBatches(q_np, bs, unit)
            n_batches = len(batches)
            call.set("queries", n_q)
            call.set("batches", n_batches)
            call.set("metric", self.metric)
            call.set("pair_slack", slack)
            q_shards = self.mesh.shape[QUERY_AXIS]
            launch_rows = -(-bs // q_shards) * q_shards
            merge_bytes = self._record_merge_bytes(n_batches * launch_rows,
                                                   m_prog + 1)
            classes = np.empty((n_q, classes_out), np.int32)
            totals = np.empty((n_q, classes_out))
            # neighbours of the queries the host re-votes; the rest stay
            # on the device
            i = np.full((n_q, self.k), -1, np.int64)
            flags = self._vote_pallas(
                batches, bs, classes, totals, i, host_q, db_np, norms,
                prog=prog, w=w, ops_tail=ops_tail, told=told, trace_id=tid,
                acct=acct)
            bad = np.flatnonzero(flags & VOTE_BAD)
            d = np.empty((n_q, self.k))

            def _select(qb, widen):
                # search_certified's widened exact re-select, in f32
                exact = _knn_program(
                    self.mesh, widen, "l2", self.merge, self.n_train,
                    self.train_tile, None, "exact",
                    dcn_merge=self.dcn_merge)
                nonlocal merge_bytes
                # scan_rows_copied: the exact scan reads the placed rows
                # where they lie (ops.topk.knn_search_tiled)
                with obs.span("certified.repair.reselect", tid,
                              parent="certified.repair", widen=widen,
                              rows=qb.shape[0], scan_rows_copied=0):
                    bq, _ = self._place_queries(qb)
                    merge_bytes += self._record_merge_bytes(
                        bq.shape[0], widen)
                    begun = _hooks.first_call_begin()
                    fs, fi = exact(bq, self._tp)
                    acct.launched("reselect")
                    _hooks.first_call_end(begun, exact, "reselect", tid,
                                          rows=bq.shape[0])
                    fs = np.asarray(fs)
                    acct.ready("reselect")
                    return fs[: qb.shape[0]], self._row_ids(
                        np.asarray(fi)[: qb.shape[0]])

            with obs.span("certified.repair", tid, parent=_CALL_SPAN,
                          fallback_queries=int(bad.size)) as sp:
                repair = repair_uncertified(
                    d, i, self.k, m, bad, q_np, db_np, select_fn=_select,
                    max_widen=max_widen,
                    db_norm_max=db_norm_max, metric="cosine",
                    pair_slack=slack, rank_queries=host_q, norms=norms)
                sp.set("host_exact_queries",
                       repair.get("host_exact_queries", 0))
            if bad.size:
                # the float64 vote over the neighbours the repair proved
                with obs.trace.stage(acct, "certified.vote_repair",
                                     queries=int(bad.size),
                                     members=int(bad.size) * self.k):
                    classes[bad], totals[bad] = vote_exact(
                        self._labels_host[np.minimum(i[bad],
                                                     self.n_train - 1)],
                        d[bad], temperature, classes_out)
            boundary = (flags & (VOTE_BAD | VOTE_BOUNDARY)) == VOTE_BOUNDARY
            by_margin = (flags & (VOTE_BAD | VOTE_BOUNDARY | VOTE_MARGIN)
                         ) == VOTE_MARGIN
            n_by_slack = int(((flags & VOTE_BY_SLACK) != 0).sum())
            counts = {"device": n_q - int(bad.size) - int(boundary.sum())
                      - int(by_margin.sum()),
                      "boundary": int(boundary.sum()),
                      "margin": int(by_margin.sum()),
                      "fallback": int(bad.size)}
            merged = self._pallas_call_stats(terms, sub_why, n_batches,
                                             merge_bytes)
            stats = {
                "fallback_queries": int(bad.size),
                "certified": n_q - int(bad.size),
                "batches": n_batches,
                "metric": self.metric,
                "pair_slack": slack,
                **repair, **merged, **told,
                "vote_boundary_queries": counts["boundary"],
                "vote_margin_queries": counts["margin"],
                "vote_repaired_queries": n_q - counts["device"],
                "vote_delta": vote[2],
                # the search's key: a vote ranks nothing on the host
                "rank_corrected_queries": 0,
                "slack_fallback_queries": n_by_slack,
                "pallas_knobs": {
                    **knobs, "interpret": interpret, "terms": terms,
                    **{key: merged[key] for key in (
                        "mxu_passes", "dim_chunk", "dim_chunks", "row_block",
                        "row_steps", "final_select_stage",
                        "select_merge_short", "operands", "sub_batch")},
                    "batches": n_batches},
                "tuning": tune_info,
            }
            for key in (*merged, "vote_boundary_queries",
                        "vote_margin_queries", "slack_fallback_queries"):
                call.set(key, stats[key])
            for outcome, n_out in counts.items():
                obs.counter(_mn.VOTE_QUERIES, outcome=outcome).inc(n_out)
            obs.counter(_mn.VOTE_QUERIES, outcome="host").inc(0)
            obs.counter(_mn.CERTIFIED_QUERIES, selector="pallas").inc(n_q)
            obs.counter(_mn.CERTIFIED_METRIC_QUERIES,
                        metric=self.metric).inc(n_q)
            obs.counter(_mn.CERTIFIED_FALLBACKS, selector="pallas").inc(
                int(bad.size))
            obs.counter(_mn.CERTIFIED_GENUINE_MISSES, selector="pallas").inc(
                repair.get("fallback_genuine_misses", 0))
            obs.counter(_mn.CERTIFIED_FALSE_ALARMS, selector="pallas").inc(
                repair.get("fallback_false_alarms", 0))
            obs.counter(_mn.CERTIFIED_HOST_EXACT, selector="pallas").inc(
                repair.get("host_exact_queries", 0))
            for outcome, n_out in (
                    ("certified", n_q - int(bad.size)),
                    ("uncertified", int(bad.size) - n_by_slack),
                    ("uncertified_by_slack", n_by_slack)):
                obs.counter(_mn.CERTIFIED_SLACK_QUERIES,
                            outcome=outcome).inc(n_out)
            _record_metric_map(tid, self.metric, map_s)
            acct.close(tid, _CALL_SPAN)
            return classes, totals, stats

    def _pallas_call_stats(self, terms: str, sub_why: str, n_batches: int,
                           merge_bytes: int) -> dict:
        """What :meth:`_pallas_setup` resolved for the call that just
        ran, as ``search_certified`` reports it on the call's event and
        in ``stats`` (the same keys, the same counters), for the vote
        call: which merge, the select's widths, the kernel's products,
        how it cut a row tile, what ran the final select, where the row
        operands came from and how the call was cut."""
        width, merged_width = self._select_widths
        merged = {
            "db_shards": self.db_shards, "merge": self.merge,
            "merge_source": self.merge_source, "merge_bytes": merge_bytes,
            "select_width": width, "select_merged_width": merged_width,
            "select_merge_short": self._select_merge_short,
            "terms": terms, "mxu_passes": terms.count("+") + 1,
            "dim_chunk": self._dim_chunking[0],
            "dim_chunks": self._dim_chunking[1],
            "row_block": self._row_blocking[0],
            "row_steps": self._row_blocking[1],
            "final_select_stage": self._final_select_stage,
            "operands": self._operands_source, "sub_batch": sub_why}
        obs.counter(_mn.SELECT_MERGE_CALLS,
                    engaged="true" if merged_width < width else "false"
                    ).inc(n_batches)
        obs.counter(_mn.KERNEL_TERMS, terms=terms).inc(n_batches)
        obs.counter(_mn.KERNEL_DIM_CHUNKS, chunks=str(merged["dim_chunks"]),
                    row_steps=str(merged["row_steps"])).inc(n_batches)
        obs.counter(_mn.FINAL_SELECT_CALLS,
                    stage=self._final_select_stage).inc(n_batches)
        obs.counter(_mn.KERNEL_OPERANDS,
                    source=self._operands_source).inc(n_batches)
        obs.counter(_mn.CERTIFIED_SUB_BATCH_CALLS, why=sub_why).inc()
        self._count_launches(n_batches)
        return merged

    def _vote_pallas(self, batches, bs, classes, totals, i, host_q, db_np,
                     norms, *, prog, w, ops_tail, told, trace_id, acct):
        """The host side of a voted call, :meth:`_certify_pallas`'s
        shape: every sub-batch's program is launched before the first is
        fetched (``batches`` maps a sub-batch's queries as that loop
        reaches it, :class:`_QueryBatches`); per sub-batch ONE fetch of the answer (``2 classes_out +
        1`` words a query), the unpack, and where a query is flagged a
        second copy, of the sub-batch's candidate windows, and the
        flagged queries' float64 re-vote (``predict_certified``'s
        docstring), the later sub-batches' programs on the device
        meanwhile.  Writes
        ``classes`` and ``totals`` of every query but the uncertified
        (the caller's repair answers those) and ``i``, the neighbours,
        of the re-voted ones; returns every query's flag word."""
        from knn_tpu.ops.refine import revote_exact

        k, classes_out = self.k, told["classes_out"]
        flags = np.zeros(host_q.shape[0], np.int32)

        def fetch(out):
            with obs.trace.stage(acct, "certified.device_wait"):
                jax.block_until_ready(out)
                acct.ready("certified")
            with obs.trace.stage(acct, "certified.d2h") as sp:
                arr = np.asarray(out[0])
                sp.set("d2h_bytes", arr.nbytes)
            return arr, out[1]

        def windows(window, rows):
            """``window[rows]`` on the host.  The sub-batch's whole
            window comes down in one copy (150 KB at 1,024 queries) and
            the rows are taken here: a device program that gathered
            them (PR 48's ``vote_rows``) queued behind every later
            sub-batch's launch, so the first sub-batch's re-vote waited
            for the whole call's device work and every later one ran
            with the device idle (PERF.md section 6, PR 49)."""
            with obs.trace.stage(acct, "certified.d2h") as sp:
                arr = np.asarray(window)
                sp.set("d2h_bytes", arr.nbytes)
            return self._row_ids(arr[rows].astype(np.int64))

        def repair(lo, pad, out, redo):
            take = bs - pad
            answer, window = _fetch_or_redispatch(out, redo, "vote fetch",
                                                  fetch=fetch)
            with obs.trace.stage(acct, "certified.unpack"):
                cls, tot, flag = unpack_voted(answer[:take], classes_out,
                                              told["temperature"])
                classes[lo : lo + take] = cls
                totals[lo : lo + take] = tot
                flags[lo : lo + take] = flag
            need = np.flatnonzero(
                flag & (VOTE_BAD | VOTE_BOUNDARY | VOTE_MARGIN))
            if not need.size:
                return
            cand = windows(window, need)
            kind = flag[need] & (VOTE_BAD | VOTE_BOUNDARY)
            # an uncertified query's first k, for the repair's account of
            # what it changed
            i[lo + need] = cand[:, :k]
            members = 0
            with obs.trace.stage(acct, "certified.vote_repair") as sp:
                # a margin alone: the first k ARE the neighbours; a
                # boundary: the float64 first k of the whole window
                for sel, width in ((kind == 0, k), (kind == VOTE_BOUNDARY, w)):
                    at = lo + need[sel]
                    if not at.size:
                        continue
                    classes[at], totals[at], i[at] = revote_exact(
                        db_np, host_q[at], cand[sel, :width],
                        self._labels_host, k, told["temperature"],
                        classes_out, _refine.norms_rows(norms, at))
                    members += at.size * width
                sp.set("queries", int(((kind & VOTE_BAD) == 0).sum()))
                sp.set("members", members)

        outs = []
        for lo, chunk, pad in batches:
            with obs.trace.stage(acct, "certified.dispatch",
                                 h2d_bytes=chunk.nbytes):
                qp, _ = self._place_queries(chunk)
                begun = _hooks.first_call_begin()
                outs.append((qp, _retry_transient(
                    lambda q=qp: prog(q, self._tp, *ops_tail),
                    "vote dispatch")))
                acct.launched("certified")
                _hooks.first_call_end(begun, prog, "certified", trace_id,
                                      rows=qp.shape[0])
        for (lo, chunk, pad), (qp, out) in zip(batches, outs):
            repair(lo, pad, out,
                   lambda q=qp: prog(q, self._tp, *ops_tail))
        return flags

    def predict(self, queries: jax.Array) -> jax.Array:
        """Predicted labels [Q] — requires ``labels`` at construction.
        The k nearest by (float32 distance, position on the device)
        vote: on an interleaved placement (``row_attr``) WHICH copies of
        a row vote, where more of them tie at the k-th distance than
        fit, is the placement's choice (:meth:`_answers_by_id`)."""
        if self._labels is None:
            raise RuntimeError("ShardedKNN built without labels; predict unavailable")
        self._require_resident("predict")
        qp, n_q = self._place_queries(queries)
        fn = _predict_program(
            self.mesh, self.k, self.num_classes, self.metric, self.merge,
            self.n_train, self.train_tile, self._dtype_key,
            dcn_merge=self.dcn_merge,
        )
        out = _retry_transient(lambda: fn(qp, self._tp, self._labels),
                               "predict dispatch")
        return out[:n_q]


class _Block:
    """One block of a self-join call in flight."""

    __slots__ = ("lo", "hi", "t0", "acct", "launches", "flagged",
                 "reselects")

    def __init__(self, lo: int, hi: int, acct):
        self.lo, self.hi, self.acct = lo, hi, acct
        self.t0 = time.perf_counter()
        self.launches, self.reselects = [], []
        self.flagged = np.empty(0, np.int64)


class _SelfJoinCall:
    """One bulk certified self-join over rows ``lo .. hi`` of a
    placement: what its blocks share (the program
    :func:`_pallas_self_program`, resolved as a search call of one
    block's rows resolves its own; the operand tail; the host's rows;
    the answer arrays) and a block's three steps, which
    knn_tpu.join.engine orders into its bounded pipeline:

    - :meth:`launch` queues the block's programs, one a sub-batch
      (``analysis.subbatch``: 4 of 1,024 rows for a block of 4,096).  A
      launch's operand is its first row id, one int32: the queries are
      the placed rows themselves, taken on the device.  The last launch
      of a ragged block starts early enough to end at the call's last
      row (one compiled shape; the rows it answers twice are read once);
    - :meth:`collect` fetches each launch's packed answer, unpacks it
      and repairs tie runs in float64 (``rank_correct_runs``) with the
      host's copy of the same rows as the queries, then launches the
      exact re-select of the block's flagged queries, padded to
      ``_SELF_RESELECT_ROWS`` a launch;
    - :meth:`settle` fetches that re-select and repairs the flagged
      (``repair_uncertified(exclude=...)``: the query's own row goes by
      id from the widened selection too), closes the block's account and
      records ``join.block``.

    The answer of row i is the first k rows j != i in lexicographic
    (float64 squared L2 of the float32 rows as given, j) order; exact
    copies of row i stay, at distance 0, in id order.  ``self.d``,
    ``self.i`` hold it for the call's rows once every block is
    settled."""

    def __init__(self, knn: ShardedKNN, lo: int, hi: int, block_rows: int,
                 trace_id: Optional[str], acct):
        from knn_tpu import tuning
        from knn_tpu.ops.certified import repair_widen

        self.knn, self.lo, self.hi = knn, lo, hi
        self.tid, self.acct = trace_id, acct
        k, shard_rows = knn.k, knn._shard_rows()
        knobs, self.tune_info = tuning.resolve_full(
            knn.n_train, knn._given_width, k, metric="l2",
            dtype=knn._dtype_key)
        if knobs["precision"] != "bf16x3" or knobs["kernel"] != "tiled":
            raise ValueError(
                f"the certified self-join runs the default precision "
                f"under the tiled kernel; this shape's tuned winner is "
                f"precision={knobs['precision']!r}, "
                f"kernel={knobs['kernel']!r}")
        self.knobs = knobs
        self.db = knn._host_train()
        self.db_norm_max = knn._db_norm_max()
        # a row is a query: what the walk saw of the rows holds of both
        self.terms = knn._kernel_terms(self.db[lo : lo + 1], "bf16x3")
        q_shards = knn.mesh.shape[QUERY_AXIS]
        call_rows = min(block_rows, hi - lo) // q_shards * q_shards
        if not call_rows:
            raise ValueError(
                f"rows=({lo}, {hi}) holds fewer rows than the mesh has "
                f"query shards ({q_shards})")
        self.prog, self.m, self.w, self.interpret = knn._pallas_setup(
            min(k + 28, knn.n_train, shard_rows) - k, terms=self.terms,
            call_rows=call_rows, trace_id=trace_id, acct=acct,
            own_rows=True, **knobs)
        self.bs, self.sub_why = knn._sub_batch
        self.tail = knn._pallas_operands("bf16x3")
        self.max_widen = min(knn.n_train, shard_rows)
        self.widen = repair_widen(self.m, self.max_widen)
        self.exact = _knn_program(
            knn.mesh, self.widen, "l2", knn.merge, knn.n_train,
            knn.train_tile, None, "exact", dcn_merge=knn.dcn_merge)
        self.d = np.empty((hi - lo, k))
        self.i = np.empty((hi - lo, k), dtype=np.int64)
        self.told = {"launches": 0, "fallback_queries": 0,
                     "rank_corrected_queries": 0, "merge_bytes": 0,
                     "fallback_genuine_misses": 0,
                     "fallback_false_alarms": 0, "host_exact_queries": 0}
        if self.widen not in knn._self_reselect_warm:
            # the re-select's one shape, once a placement: its compile
            # falls here, before the first block, whatever gets flagged
            jax.block_until_ready(self._reselect(np.zeros(1, np.int64)))
            acct.ready("reselect")
            knn._self_reselect_warm.add(self.widen)

    # -- device launches ---------------------------------------------------
    def _reselect(self, at: np.ndarray):
        """Launch the exact top-``widen`` of the call's rows ``at``
        (positions in the call; at most ``_SELF_RESELECT_ROWS``), every
        row a candidate, the query's own among them."""
        qb = np.zeros((_SELF_RESELECT_ROWS, self.db.shape[1]), np.float32)
        qb[: at.size] = self.db[self.lo + at]
        with obs.span("certified.repair.reselect", self.tid,
                      parent="certified.repair", widen=self.widen,
                      rows=int(at.size), scan_rows_copied=0):
            bq, _ = self.knn._place_queries(qb)
            self.told["merge_bytes"] += self.knn._record_merge_bytes(
                bq.shape[0], self.widen)
            begun = _hooks.first_call_begin()
            out = self.exact(bq, self.knn._tp)
            self.acct.launched("reselect")
            _hooks.first_call_end(begun, self.exact, "reselect", self.tid,
                                  rows=bq.shape[0])
        return out

    def launch(self, lo: int, hi: int) -> _Block:
        knn, bs = self.knn, self.bs
        blk = _Block(lo, hi, obs.trace.block_account(self.acct, _BLOCK_SUMS))
        for start in range(lo, hi, bs):
            # one compiled shape: a launch that would run past the
            # call's rows starts early enough to end at the last
            first = np.asarray([min(start, self.hi - bs)], np.int32)

            def run(first=first):
                return self.prog(first, knn._tp, *self.tail)

            with obs.trace.stage(blk.acct, "certified.dispatch",
                                 h2d_bytes=0):
                begun = _hooks.first_call_begin()
                packed = _retry_transient(run, "self-join dispatch")
                self.acct.launched("certified")
                _hooks.first_call_end(begun, self.prog, "certified",
                                      self.tid, rows=bs)
            blk.launches.append((start, int(first[0]), packed, run))
            self.told["merge_bytes"] += knn._record_merge_bytes(
                bs, self.m + 1)
        self.told["launches"] += len(blk.launches)
        return blk

    # -- the host's share --------------------------------------------------
    def collect(self, blk: _Block) -> None:
        from knn_tpu.ops.refine import rank_correct_runs

        k, acct = self.knn.k, blk.acct
        fetch = _staged_fetch(acct)
        bad = np.zeros(blk.hi - blk.lo, dtype=bool)
        for start, first, packed, run in blk.launches:
            arr = _fetch_or_redispatch(packed, run, "self-join fetch",
                                       fetch=fetch)
            # the launch's rows this block has not had from an earlier one
            rows = min(start + self.bs, blk.hi) - start
            own = slice(start - first, start - first + rows)
            with obs.trace.stage(acct, "certified.unpack") as sp:
                gi, tight, bad_np, dk = unpack_certified(
                    arr[own], k, self.w, True)
            acct.add(_UNPACK_COPIES, sp.attrs.get("copies_s", 0.0))
            with obs.trace.stage(acct, "certified.rank_correct") as sp:
                mine = {}  # the caller's share of the buffers
                with obs.trace.phase(mine, "buffers_s",
                                     _refine.PHASE_BUFFERS):
                    d32k = dk.astype(np.float64)
                dc, ic, n_c = rank_correct_runs(
                    gi, tight, k, self.db[start : start + rows], self.db,
                    d32k=d32k, metric="l2")
                sp.set("queries_corrected", n_c)
            told = sp.attrs
            acct.add(_refine.PHASE_BUFFERS, told.get("buffers_s", 0.0)
                     + mine.get("buffers_s", 0.0))
            acct.add(_refine.PHASE_SCORE, told.get("score_s", 0.0),
                     gather_s=told.get("gather_s", 0.0),
                     arith_s=told.get("arith_s", 0.0))
            acct.add(_refine.PHASE_ORDER, told.get("order_s", 0.0))
            obs.counter(_mn.RANK_CORRECT_MEMBERS).inc(told.get("members", 0))
            self.told["rank_corrected_queries"] += n_c
            out = slice(start - self.lo, start - self.lo + rows)
            self.d[out], self.i[out] = dc, ic
            bad[start - blk.lo : start - blk.lo + rows] = bad_np
        blk.launches = []  # the packed answers go
        blk.flagged = np.flatnonzero(bad) + (blk.lo - self.lo)
        if blk.flagged.size:
            with obs.trace.stage(acct, "certified.repair",
                                 fallback_queries=int(blk.flagged.size)):
                blk.reselects = [
                    self._reselect(blk.flagged[j : j + _SELF_RESELECT_ROWS])
                    for j in range(0, blk.flagged.size,
                                   _SELF_RESELECT_ROWS)]

    def settle(self, blk: _Block) -> None:
        from knn_tpu.ops.certified import repair_uncertified

        n_bad = int(blk.flagged.size)
        # every block, flagged rows or none: the repair records its
        # phases once a block, at 0.0 where it had nothing to do
        with obs.trace.stage(blk.acct, "certified.repair", self.tid) as sp:
            fs, fi = [], []
            for j, (ps, pi) in enumerate(blk.reselects):
                rows = min(_SELF_RESELECT_ROWS,
                           n_bad - j * _SELF_RESELECT_ROWS)
                fs.append(np.asarray(ps)[:rows])
                self.acct.ready("reselect")
                fi.append(np.asarray(pi)[:rows])
            repair = repair_uncertified(
                self.d, self.i, self.knn.k, self.m, blk.flagged,
                self.db[self.lo : self.hi], self.db,
                select_fn=lambda qb, widen: (
                    np.concatenate(fs), np.concatenate(fi)),
                max_widen=self.max_widen, db_norm_max=self.db_norm_max,
                exclude=self.lo + blk.flagged)
            sp.set("host_exact_queries",
                   repair.get("host_exact_queries", 0))
        blk.reselects = []
        self.told["fallback_queries"] += n_bad
        for key, value in repair.items():
            self.told[key] += value
        blk.acct.close(self.tid, _BLOCK_SPAN)
        obs.record_span(_BLOCK_SPAN, self.tid,
                        time.perf_counter() - blk.t0, lo=blk.lo,
                        rows=blk.hi - blk.lo, flagged=n_bad)

    def finish(self) -> dict:
        """Once every block is settled: the call's counters, and what
        ``search_certified``'s stats say of a call, summed over this
        one's blocks."""
        knn, told, n = self.knn, self.told, self.hi - self.lo
        selector = "pallas"
        obs.counter(_mn.CERTIFIED_QUERIES, selector=selector).inc(n)
        obs.counter(_mn.CERTIFIED_METRIC_QUERIES, metric=knn.metric).inc(n)
        obs.counter(_mn.CERTIFIED_FALLBACKS, selector=selector).inc(
            told["fallback_queries"])
        obs.counter(_mn.CERTIFIED_GENUINE_MISSES, selector=selector).inc(
            told["fallback_genuine_misses"])
        obs.counter(_mn.CERTIFIED_FALSE_ALARMS, selector=selector).inc(
            told["fallback_false_alarms"])
        obs.counter(_mn.CERTIFIED_HOST_EXACT, selector=selector).inc(
            told["host_exact_queries"])
        obs.counter(_mn.CERTIFIED_RANK_CORRECTED).inc(
            told["rank_corrected_queries"])
        obs.counter(_mn.KERNEL_TERMS, terms=self.terms).inc(told["launches"])
        obs.counter(_mn.KERNEL_OPERANDS, source=knn._operands_source).inc(
            told["launches"])
        geometry = {
            "terms": self.terms, "mxu_passes": self.terms.count("+") + 1,
            "dim_chunk": knn._dim_chunking[0],
            "dim_chunks": knn._dim_chunking[1],
            "row_block": knn._row_blocking[0],
            "row_steps": knn._row_blocking[1],
            "final_select_stage": knn._final_select_stage,
            "select_merge_short": knn._select_merge_short,
            "operands": knn._operands_source, "sub_batch": self.sub_why}
        return {
            **told, "certified": n - told["fallback_queries"],
            "batches": told["launches"], "sub_batch_rows": self.bs,
            "metric": knn.metric, "self_excluded": n, **geometry,
            "pallas_knobs": {**self.knobs, "interpret": self.interpret,
                             **geometry, "batches": told["launches"]},
            "tuning": self.tune_info}


def sharded_knn(
    queries: jax.Array,
    train: jax.Array,
    k: int,
    *,
    mesh: Mesh,
    metric: str = "l2",
    merge: Optional[str] = None,
    train_tile: Optional[int] = None,
    compute_dtype=None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact KNN sharded over ``mesh``: (distances, global indices), [Q, k].

    Queries are sharded along the query axis, train along the db axis; both
    are padded to the mesh (the reference aborts instead,
    knn_mpi.cpp:127-129).  Results are bitwise-equal to single-device
    ``knn_search`` for any mesh shape and either merge strategy.  One-shot
    wrapper over :class:`ShardedKNN`.
    """
    prog = ShardedKNN(
        train, mesh=mesh, k=k, metric=metric, merge=merge,
        train_tile=train_tile, compute_dtype=compute_dtype,
    )
    return prog.search(queries)


@functools.lru_cache(maxsize=64)
def _predict_program(
    mesh: Mesh,
    k: int,
    num_classes: int,
    metric: str,
    merge: str,
    n_train: int,
    train_tile: Optional[int],
    compute_dtype,
    dcn_merge: Optional[str] = None,
):
    hosts, chips = db_topology(mesh)

    def spmd(q, t):
        return _merged_topk(
            q, t, k, metric, merge, n_train, train_tile, compute_dtype,
            hosts, chips, dcn_merge=dcn_merge,
        )

    knn = jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=(P(QUERY_AXIS), P(db_axes(mesh))),
        out_specs=(P(QUERY_AXIS), P(QUERY_AXIS)),
        check_vma=False,
    )

    def run(q, t, labels):
        # the vote runs OUTSIDE the shard_map body (still inside the one
        # jitted program, still on device): with check_vma/check_rep off,
        # GSPMD is free to assume a query-spec'd output is replicated
        # along the db axis, and on 2-D meshes it miscompiled the
        # in-body vote of the TILED search (every query shard got shard
        # 0's votes).  On the global [Q, k] index array the partitioner
        # handles the replicated-label gather + vote natively.
        _, gi = knn(q, t)
        safe = jnp.minimum(gi, n_train - 1)  # sentinel survives only if n_train < k (raised)
        return majority_vote(labels[safe], num_classes)

    return jax.jit(run)


def sharded_knn_predict(
    train: jax.Array,
    train_labels: jax.Array,
    queries: jax.Array,
    *,
    k: int,
    num_classes: int,
    mesh: Mesh,
    metric: str = "l2",
    merge: Optional[str] = None,
    train_tile: Optional[int] = None,
    compute_dtype=None,
) -> jax.Array:
    """Distributed classify: the whole reference KNN phase (distance fill →
    select → vote, knn_mpi.cpp:308-393) as one SPMD program.  Labels ride
    replicated (they are tiny next to features); votes happen on-device so
    only final labels leave the mesh.  One-shot wrapper over
    :class:`ShardedKNN`."""
    prog = ShardedKNN(
        train, mesh=mesh, k=k, metric=metric, merge=merge,
        train_tile=train_tile, compute_dtype=compute_dtype,
        labels=train_labels, num_classes=num_classes,
    )
    return prog.predict(queries)


@functools.lru_cache(maxsize=32)
def _pallas_certified_program(
    mesh: Mesh, m: int, k: int, merge: str, tile_n: Optional[int],
    precision: str, n_train: Optional[int] = None,
    survivors: Optional[int] = None,
    block_q: Optional[int] = None, final_select: str = "exact",
    include_distances: bool = True,
    final_recall_target: Optional[float] = None,
    grid_order: str = "query_major",
    kernel: str = "tiled",
    quant_offset: float = 0.0,
    dcn_merge: Optional[str] = None,
    interpret: Optional[bool] = None,
    terms: str = "hh+hl+lh",
    augmented: bool = False,
    row_block: Optional[int] = None,
    resident_parts: int = 0,
    masked: bool = False,
    slack_outcome: bool = False,
):
    """ONE-pass sharded self-certifying coarse select + device rank +
    device certificate (ops.pallas_knn.local_certified_candidates per
    shard): candidates arrive as direct-difference f32 distances already
    in lexicographic order, merged across the db axis (ring/allgather as
    usual) while the kernel-space exclusion bounds pmin.

    The certificate and the near-tie analysis run ON DEVICE, and every
    host-facing output is packed into ONE int32 array
    [Q, W + nw + 1 (+ k)], so the host makes one fetch per batch
    instead of four.  Packed columns (see ``unpack_certified`` for the
    host-side inverse):

      [0, W)            i32   ranked global db row indices over the
                              analysis window W = min(k+17, m+1),
      [W, W+nw)         u32-bits  near-tie mask, bit-packed: bit j is 1
                              when positions j, j+1 are closer than
                              RANK_SLACK and sit before the top-k set
                              boundary's first big gap,
      [W+nw]            i32   bad flag: uncertified OR boundary-
                              unresolvable rows (repair reruns exactly);
                              ``slack_outcome`` adds 2 where the pair
                              slack alone failed the certificate,
      [W+nw+1, +k)      f32-bitcast  ranked direct-difference top-k
                              distances (``include_distances`` only —
                              label/index consumers skip the columns).

    Soundness: a db row outside the candidates has kernel score >= lb,
    or was merge-dropped with direct distance >= d32[:, m]; ``bad`` is
    the union of both checks plus rows whose tie run crosses the
    analysis window (no provable top-k boundary).

    ``precision="int8"`` swaps the operand tail: instead of the scalar
    ``db_norm_max`` the program takes the quantized placement
    ``(values, scales, norms)`` (each db-sharded) plus the replicated
    bound-consts vector, and the certificate's tolerance becomes the
    per-query PROVABLE quantization bound ε (ops.quantize.
    score_error_bound_device) — the kernel scores and lb live in the
    ``quant_offset``-shifted space, so the comparison uses the shifted
    query norm (squared L2 is translation invariant; the f32 rescore
    distances d32 are space-independent up to RANK_SLACK, which the
    derivation already budgets).

    ``augmented`` (a placement with a pair slack, inner product or
    cosine: ``ShardedKNN._pair_slack``; never a caller's choice) appends
    one replicated scalar to the operand tail, whatever the precision:
    ``_certify_pack_spmd``'s ``aug_slack``.  Without it the program is
    the one it always was, operation for operation.  ``slack_outcome``
    (a cosine placement's program alone, beside ``augmented``) has the
    certificate evaluated once more without the slack and the
    difference packed as bit 1 of the flag word (``failed_by_slack``
    reads it); without it the program is the one it was.

    ``row_block`` is the kernel's static of that name, ``_pallas_setup``'s
    resolution (None: the kernel reads its own launch's shape).

    ``resident_parts`` ("bf16x3"; never a caller's choice either) is how
    many bf16 halves of the rows the operand tail carries after the
    scalar, the row norms after them (``ShardedKNN._row_operands``: 1 =
    ``th``, 2 = ``th`` and ``tl``), each db-sharded: the kernel streams
    them and the program forms nothing of the corpus's size.  0: the
    kernel's prologue forms them in every call, the program it always
    was.

    ``masked`` (a filtered call; never a caller's choice) appends
    the batch's validity words as the LAST operand, ``[queries, db
    shards x words]`` sharded over both axes, each shard's block in the
    kernel's layout at ``tile_n`` (ops.tagfilter.mask_words or
    range_words): the kernel
    scores a row whose bit is 0 at +inf before the bin-select, and
    ``_certify_pack_spmd`` reads a bound of +inf as "every valid row is
    a candidate".  Without it the program is the one it always was,
    operation for operation."""
    from knn_tpu.ops.pallas_knn import (
        BLOCK_Q,
        TILE_N,
        local_certified_candidates,
    )

    hosts, chips = db_topology(mesh)
    eff_tile = tile_n or TILE_N
    eff_bq = block_q or BLOCK_Q
    w = _analysis_window(k, m)

    def spmd(q, t, *tail):
        aug_slack = words = None
        if masked:
            *tail, words = tail
        if augmented:
            *tail, aug_slack = tail
        db_q, db_pq, consts, db_norm_max, db_rows = _split_operand_tail(
            precision, tail)
        q_cert = q
        if db_q is not None and db_q[0].shape[1] < q.shape[1]:
            # the quantized rows keep the width given, and their
            # certificate's query norm is taken in THEIR shifted space:
            # a lane-tile column of a placed batch is 0, not the offset
            q_cert = q[:, : db_q[0].shape[1]]
        d32, li, lb = local_certified_candidates(
            q, t, m, tile_n=eff_tile, survivors=survivors,
            block_q=eff_bq, final_select=final_select, precision=precision,
            final_recall_target=final_recall_target,
            grid_order=grid_order, kernel=kernel, interpret=interpret,
            db_int8=db_q, db_pq=db_pq, offset=quant_offset, terms=terms,
            row_block=row_block, db_prepared=db_rows,
            **({"valid_words": words} if masked else {}),
        )
        return _certify_pack_spmd(
            q_cert, t, d32, li, lb, consts=consts, db_norm_max=db_norm_max,
            precision=precision, quant_offset=quant_offset, m=m, k=k, w=w,
            merge=merge, n_train=n_train, hosts=hosts, chips=chips,
            dcn_merge=dcn_merge,
            include_distances=include_distances,
            pq_dsub=None if db_pq is None else int(db_pq[1].shape[2]),
            aug_slack=aug_slack, **({"masked": True} if masked else {}),
            **({"slack_outcome": True} if slack_outcome else {}),
        )

    prog = jax.jit(
        jax.shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(QUERY_AXIS), P(db_axes(mesh)),
                      *_tail_specs(precision, mesh, resident_parts),
                      *((P(),) if augmented else ()),
                      *((P(QUERY_AXIS, db_axes(mesh)),) if masked else ())),
            out_specs=P(QUERY_AXIS),
            check_vma=False,
        )
    )
    _hooks.mark_built(
        prog, f"m={m},k={k},tile={eff_tile},terms={terms},"
              f"row_block={row_block},precision={precision},"
              f"operands={'resident' if resident_parts else 'per_call'}"
              + (",masked" if masked else "")
              + (",slack_outcome" if slack_outcome else ""))
    return prog


@functools.lru_cache(maxsize=32)
def _pallas_vote_program(
    mesh: Mesh, m: int, k: int, merge: str, tile_n: Optional[int],
    precision: str, n_train: int, vote: tuple,
    survivors: Optional[int] = None, block_q: Optional[int] = None,
    final_select: str = "exact",
    final_recall_target: Optional[float] = None,
    grid_order: str = "query_major", kernel: str = "tiled",
    quant_offset: float = 0.0, dcn_merge: Optional[str] = None,
    interpret: Optional[bool] = None, terms: str = "hh+hl+lh",
    row_block: Optional[int] = None, resident_parts: int = 0,
):
    """:func:`_pallas_certified_program` for a cosine placement, with the
    tail ended in the weighted vote (``_certify_pack_spmd``'s ``vote``,
    ``(1 / T, classes_out, delta)``): the same kernel, final select,
    rescore, merge, rank analysis and certificate from the same operands,
    then one more replicated operand, the device labels, LAST.  Returns
    ``(answer, window)`` (:func:`_vote_pack`), both sharded over the
    query axis.  A function of its own so that the search program's
    ``spmd`` keeps its frame (tests/test_dim_chunking.py's tripwire).

    Where the placement keeps no resident row operands
    (``resident_parts`` 0: ``analysis.hbm.resident_operands_fit`` found
    no room) the default precision's are formed HERE, in every call, by
    the resident form's own arithmetic (ops.pallas_knn.row_operands: the
    low half taken against ``lax.reduce_precision``), not by the
    kernel wrapper's in-call split, whose low half the v5e's compiler
    turns to zeros (ROADMAP A17): a vote has no ranked list for a missed
    neighbour to show in, so its kernel must be inside
    ``kernel_tolerance`` on the chip too."""
    from knn_tpu.ops.pallas_knn import (
        BLOCK_Q,
        TILE_N,
        local_certified_candidates,
        row_operands,
    )

    hosts, chips = db_topology(mesh)
    w = _analysis_window(k, m)

    def spmd(q, t, *tail):
        *tail, aug_slack, labels = tail
        db_q, db_pq, consts, db_norm_max, db_rows = _split_operand_tail(
            precision, tail)
        if db_rows is None and precision == "bf16x3":
            db_rows = row_operands(t, tile_n=tile_n or TILE_N,
                                   with_lo="hl" in terms)
        d32, li, lb = local_certified_candidates(
            q, t, m, tile_n=tile_n or TILE_N, survivors=survivors,
            block_q=block_q or BLOCK_Q, final_select=final_select,
            precision=precision, final_recall_target=final_recall_target,
            grid_order=grid_order, kernel=kernel, interpret=interpret,
            db_int8=db_q, db_pq=db_pq, offset=quant_offset, terms=terms,
            row_block=row_block, db_prepared=db_rows)
        return _certify_pack_spmd(
            q, t, d32, li, lb, consts=consts, db_norm_max=db_norm_max,
            precision=precision, quant_offset=quant_offset, m=m, k=k, w=w,
            merge=merge, n_train=n_train, hosts=hosts, chips=chips,
            dcn_merge=dcn_merge, include_distances=False,
            pq_dsub=None if db_pq is None else int(db_pq[1].shape[2]),
            aug_slack=aug_slack, slack_outcome=True, vote=vote,
            labels=labels)

    prog = jax.jit(
        jax.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(QUERY_AXIS), P(db_axes(mesh)),
                      *_tail_specs(precision, mesh, resident_parts),
                      P(), P()),
            out_specs=(P(QUERY_AXIS), P(QUERY_AXIS)),
            check_vma=False,
        )
    )
    _hooks.mark_built(
        prog, f"m={m},k={k},tile={tile_n or TILE_N},terms={terms},"
              f"row_block={row_block},precision={precision},"
              f"operands={'resident' if resident_parts else 'per_call'},"
              f"vote=softmax,classes_out={vote[1]}")
    return prog


@functools.lru_cache(maxsize=32)
def _pallas_self_program(
    mesh: Mesh, m: int, k: int, merge: str, tile_n: int, n_train: int,
    rows: int, survivors: Optional[int] = None,
    block_q: Optional[int] = None, final_select: str = "exact",
    final_recall_target: Optional[float] = None,
    grid_order: str = "query_major", dcn_merge: Optional[str] = None,
    interpret: Optional[bool] = None, terms: str = "hh+hl+lh",
    row_block: Optional[int] = None, resident_parts: int = 0,
):
    """:func:`_pallas_certified_program` ("bf16x3", the tiled kernel) for
    queries that ARE rows of the placement: the launch answers rows
    ``lo .. lo + rows`` of the placed array, and row ``lo + r`` is no
    answer of query ``r``.  It takes no query operand: ``lo``, one
    replicated int32 scalar, stands where the queries stood, then the
    rows and the search program's operand tail.  Every shard takes the
    part of the block it holds out of its own rows where they lie
    (lane-tiled at the placed width, so no pad and no transfer), the
    parts are summed over the db axes (each row is held once: x + 0 is
    x), and every shard sees the block.  Row ``lo + r`` is scored +inf
    for query ``r`` BEFORE the bin-select, in the row tiles that hold
    the block and nowhere else (ops.pallas_knn.self_tile_candidates); the
    final select, the rescore, the merge and the certificate are the
    search program's, over the n - 1 rows that are left:
    ``_certify_pack_spmd``'s three inequalities bound the rows outside
    the candidates, and a row scored +inf is in no bound.  An exact copy
    of the query is a row like any other: it stays, at distance 0.

    A function of its own, as the vote program is, so that the search
    program's ``spmd`` keeps its frame (tests/test_dim_chunking.py's
    tripwire): called with no such scalar the search program is the one
    it always was, operation for operation."""
    from knn_tpu.ops.pallas_knn import (
        BLOCK_Q,
        effective_block_q,
        local_coarse_candidates,
        local_select_rescore,
        row_operands,
        self_tile_candidates,
    )

    hosts, chips = db_topology(mesh)
    w = _analysis_window(k, m)
    # the block's rows a query shard answers
    take = rows // mesh.shape[QUERY_AXIS]
    block_q = block_q or BLOCK_Q

    def spmd(lo, t, *tail):
        _, _, _, db_norm_max, db_rows = _split_operand_tail("bf16x3", tail)
        if db_rows is None:
            db_rows = row_operands(t, tile_n=tile_n, with_lo="hl" in terms)
        # this shard's part of the block: query r of this query shard is
        # (shard-local) row ``first + r``
        first = (lo[0] + lax.axis_index(QUERY_AXIS) * take
                 - _db_shard_index(hosts, chips) * t.shape[0])
        at = first + lax.iota(jnp.int32, take)
        q = jnp.where(((at >= 0) & (at < t.shape[0]))[:, None],
                      t[jnp.clip(at, 0, t.shape[0] - 1)], 0.0)
        if hosts * chips > 1:
            q = lax.psum(q, (HOST_AXIS, DB_AXIS) if hosts > 1 else DB_AXIS)
        coarse = dict(tile_n=tile_n, survivors=survivors, terms=terms,
                      row_block=row_block, interpret=interpret)
        cd, ci, bounds = local_coarse_candidates(
            q, t, m, block_q=block_q, precision="bf16x3",
            final_select=final_select, grid_order=grid_order,
            db_prepared=db_rows, **coarse)
        with jax.named_scope(SCOPE_SELF_TILES):
            cd, ci, bounds = self_tile_candidates(
                q, db_rows, cd, ci, bounds, first,
                block_q=effective_block_q(block_q, take), **coarse)
        d32, li, lb = local_select_rescore(
            q, t, cd, ci, bounds, m, final_select=final_select,
            final_recall_target=final_recall_target, interpret=interpret)
        return _certify_pack_spmd(
            q, t, d32, li, lb, consts=None, db_norm_max=db_norm_max,
            precision="bf16x3", quant_offset=0.0, m=m, k=k, w=w,
            merge=merge, n_train=n_train, hosts=hosts, chips=chips,
            dcn_merge=dcn_merge, include_distances=True)

    prog = jax.jit(
        jax.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(db_axes(mesh)),
                      *_tail_specs("bf16x3", mesh, resident_parts)),
            out_specs=P(QUERY_AXIS),
            check_vma=False,
        )
    )
    _hooks.mark_built(
        prog, f"m={m},k={k},tile={tile_n},terms={terms},"
              f"row_block={row_block},precision=bf16x3,"
              f"operands={'resident' if resident_parts else 'per_call'},"
              f"self_rows={rows}")
    return prog


def _tail_specs(precision: str, mesh: Mesh, resident_parts: int = 0):
    """shard_map in_specs of the precision-shaped operand tail
    (ShardedKNN._pallas_operands): int8 = the quantized placement
    (db-sharded values/scales/norms + replicated bound consts), pq =
    db-sharded codes + replicated codebooks + replicated per-subspace
    bound consts, f32 = the replicated scalar db-norm bound, and after
    it the resident row operands where the program takes them
    (``resident_parts`` db-sharded bf16 halves and the row norms)."""
    dbp = db_axes(mesh)
    if precision == "int8":
        return (P(dbp), P(dbp), P(dbp), P())
    if precision == "pq":
        return (P(dbp), P(), P())
    return (P(),) + (P(dbp),) * (resident_parts + 1 if resident_parts
                                 else 0)


def _split_operand_tail(precision: str, tail):
    """(db_quant, db_pq, consts, db_norm_max, db_rows) from the operand
    tail — the per-precision unpacking every pallas-certified program
    shares.  ``db_quant`` is the (values, scales, norms) triple of the
    int8 arm; ``db_pq`` is (codes, codebooks); ``db_rows`` is the
    resident ``(th[, tl], norms)`` of the default precision, None where
    the tail carries none."""
    if precision == "int8":
        tq, ts, tnr, consts = tail
        return (tq, ts, tnr), None, consts, None, None
    if precision == "pq":
        codes, books, consts = tail
        return None, (codes, books), consts, None, None
    db_norm_max, *db_rows = tail
    return None, None, None, db_norm_max, tuple(db_rows) or None


#: rows the lane-tile program writes at a time: what it keeps beside its
#: input and its output is one block in each layout (67 MB at 256
#: columns), not a second copy of the corpus
_LANE_TILE_BLOCK_ROWS = 65536


def _lane_tiled_rows(rows: jax.Array, width: int) -> jax.Array:
    """One shard's rows with zero columns after them up to ``width``,
    written a block of rows at a time into the output in place (the last
    block laid back over the one before it where the rows are no whole
    number of blocks)."""
    n, dim = rows.shape
    block = min(n, _LANE_TILE_BLOCK_ROWS)

    def write(i, out):
        lo = jnp.minimum(i * block, n - block)
        part = lax.dynamic_slice(rows, (lo, 0), (block, dim))
        return lax.dynamic_update_slice(
            out, jnp.pad(part, ((0, 0), (0, width - dim))), (lo, 0))

    return lax.fori_loop(0, -(-n // block), write,
                         jnp.zeros((n, width), rows.dtype))


@functools.lru_cache(maxsize=8)
def _lane_tile_program(mesh: Mesh, width: int):
    """The program that lays a placement's rows out in whole lane tiles,
    once a placement (``ShardedKNN.__init__``): every shard writes its
    rows with zero columns after them up to ``width``, db-sharded as the
    rows are.  A width that is no multiple of 128 lies column-major on a
    TPU, so this is the copy into the row-major form that every program
    reading the rows made in every call, made once."""
    dbp = db_axes(mesh)
    prog = jax.jit(
        jax.shard_map(
            functools.partial(_lane_tiled_rows, width=width),
            mesh=mesh,
            in_specs=P(dbp),
            out_specs=P(dbp),
            check_vma=False,
        )
    )
    _hooks.mark_built(prog, f"width={width}")
    return prog


@functools.lru_cache(maxsize=8)
def _row_operands_program(mesh: Mesh, tile: int, with_lo: bool):
    """The program that builds ``ShardedKNN._row_operands``' form, once
    a placement and geometry: every shard runs the kernel prologue's own
    operations on its rows (ops.pallas_knn.row_operands: pad to the
    tile with PAD_VAL rows and to whole dim chunks, the bf16 split, the
    norms), so each shard pads its OWN rows and the outputs are
    db-sharded as the rows are."""
    from knn_tpu.ops.pallas_knn import row_operands

    dbp = db_axes(mesh)
    prog = jax.jit(
        jax.shard_map(
            functools.partial(row_operands, tile_n=tile, with_lo=with_lo),
            mesh=mesh,
            in_specs=P(dbp),
            out_specs=(P(dbp),) * (2 + with_lo),
            check_vma=False,
        )
    )
    _hooks.mark_built(
        prog, f"tile={tile},parts={'th+tl' if with_lo else 'th'}")
    return prog


@functools.lru_cache(maxsize=8)
def _filter_mask_program(mesh: Mesh, tile: int, list_cap: int,
                         interpret: bool):
    """The program ``filter_mask``: a batch's ``[queries, 2]`` tag ids
    to its validity words, ``[queries, db shards x words]`` sharded over
    both axes, every shard making its own block from its own tag index
    (ops.tagfilter.mask_words over ``ShardedKNN._tag_index``'s arrays:
    the replicated slot table, then the shard's bitmaps, list offsets
    and list rows)."""
    from knn_tpu.ops.tagfilter import mask_words

    dbp = db_axes(mesh)
    prog = jax.jit(
        jax.shard_map(
            functools.partial(mask_words, tile_n=tile, list_cap=list_cap,
                              interpret=interpret),
            mesh=mesh,
            in_specs=(P(QUERY_AXIS), P(), P(dbp), P(dbp), P(dbp)),
            out_specs=P(QUERY_AXIS, dbp),
            check_vma=False,
        )
    )
    _hooks.mark_built(prog, f"tile={tile},list_cap={list_cap}")
    return prog


@functools.lru_cache(maxsize=8)
def _range_mask_program(mesh: Mesh, interpret: bool):
    """The program ``range_mask``: a batch's ``[queries, 2]`` inclusive
    attribute bounds to its validity words, ``[queries, db shards x
    words]`` sharded over both axes, every shard comparing its own
    rows' attribute (ops.tagfilter.range_words over
    ``ShardedKNN._attr_rows``' placement, whose shape carries the row
    tile's layout)."""
    from knn_tpu.ops.tagfilter import range_words

    dbp = db_axes(mesh)
    prog = jax.jit(
        jax.shard_map(
            functools.partial(range_words, interpret=interpret),
            mesh=mesh,
            in_specs=(P(QUERY_AXIS), P(dbp)),
            out_specs=P(QUERY_AXIS, dbp),
            check_vma=False,
        )
    )
    _hooks.mark_built(prog, f"maker=range,interpret={interpret}")
    return prog


@functools.lru_cache(maxsize=32)
def _masked_reselect_program(mesh: Mesh, k: int, merge: str, n_train: int,
                             train_tile: Optional[int], tile_n: int,
                             dcn_merge: Optional[str] = None):
    """The repair's widened exact re-select (``_knn_program(...,
    "exact")``) held to a batch's validity words: every shard lays its
    block of the words over its exact float32 distances
    (ops.tagfilter.masked_topk), then the usual merge.  A query with
    fewer than ``k`` valid rows gets them all, then +inf and the
    sentinel."""
    from knn_tpu.ops.tagfilter import masked_topk, words_to_valid

    hosts, chips = db_topology(mesh)

    def spmd(q, t, words):
        db_idx = _db_shard_index(hosts, chips)
        d, i = masked_topk(
            q, t, k,
            words_to_valid(words, tile_n=tile_n, n_rows=t.shape[0]),
            train_tile=train_tile,
            n_valid=jnp.clip(n_train - db_idx * t.shape[0], 0, t.shape[0]))
        gi = jnp.where(i == _INT_SENTINEL, _INT_SENTINEL,
                       i + db_idx * t.shape[0])
        return _merge_shards(d, gi, k, hosts, chips, merge, dcn_merge)

    dbp = db_axes(mesh)
    prog = jax.jit(
        jax.shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(QUERY_AXIS), P(dbp), P(QUERY_AXIS, dbp)),
            out_specs=(P(QUERY_AXIS), P(QUERY_AXIS)),
            check_vma=False,
        )
    )
    _hooks.mark_built(prog, f"k={k},selector=exact,tile={train_tile},masked")
    return prog


#: device scope of the certify/pack tail; its four siblings (operand
#: prep, kernel, final select, rescore) are ops.pallas_knn's SCOPE_*
SCOPE_CERTIFY_PACK = "knn.certify_pack"
#: device scope of a self-join launch's second select of the row tiles
#: that hold its own rows (ops.pallas_knn.self_tile_candidates), beside
#: ops.pallas_knn's SCOPE_KERNEL
SCOPE_SELF_TILES = "knn.self_tiles"
#: device scope of the cross-shard merge (:func:`_merge_shards` wherever
#: it runs, and the certified program's ``pmin`` of the exclusion bound):
#: the collectives and the re-selects between them
SCOPE_MERGE = "knn.merge"


@jax.named_scope(SCOPE_CERTIFY_PACK)
def _certify_pack_spmd(q, t, d32, li, lb, *, consts, db_norm_max,
                       precision, quant_offset, m, k, w, merge, n_train,
                       hosts, chips, include_distances,
                       dcn_merge=None, pq_dsub=None, aug_slack=None,
                       masked: bool = False, slack_outcome: bool = False,
                       vote=None, labels=None):
    """The certify/pack tail of the pallas certified program, from one
    shard's ranked candidates ``(d32, li, lb)`` to the packed host-facing
    int32 array: merge, rank analysis, certificate, packing.

    ``aug_slack`` (a traced scalar; placements with a pair slack only,
    ``ShardedKNN._pair_slack``; None and no operation otherwise) is the
    most by which the exact placed distances D' of two placed rows can
    disagree with the order of the metric the host ranks by:
    ``DOT_AUG_SLACK * M`` for inner products (``_augment_dot`` derives
    it), ``COS_UNIT_SLACK`` for cosines (derived beside it).  With e =
    aug_slack, r = RANK_SLACK and |d32 - D'| <= r D' / 3
    (ops.pallas_knn.RANK_SLACK), the three inequalities below carry it:

    - near-tie: a pair with d32 gap > r d_hi + e has D' gap > r d_hi / 3
      + e > e, so its inner products (cosines) are ordered as the device
      ranked them; every other pair is marked tight and re-scored by the
      host in float64 INNER PRODUCT (COSINE of the rows as given)
      (ops.refine.rank_correct_runs);
    - exclusion: a row outside the candidates has kernel score >= lb,
      so D' - |q|^2 >= lb - tol; ``s_k + r d_k + tol + e < lb`` puts it
      more than e above D'(c) <= d_k (1 + r) of every row c the host may
      keep, hence below each in inner product (cosine);
    - merge-drop: a dropped candidate has D' >= d32[:, m] (1 - r), and
      ``d_k + r d_k + e < d32[:, m] (1 - r)`` says the same of it.

    ``slack_outcome`` (a cosine placement's program): the exclusion and
    merge-drop tests are evaluated once more with e = 0, and a query
    that fails only with the slack gets 2 added to its flag word, so the
    host can count what the slack costs (``failed_by_slack``).

    ``masked`` (the candidates came from a kernel that held each query
    to its validity words): the certificate is the same in form, read
    over the query's VALID rows.  A masked row scored +inf before the
    bin-select, so it is in neither the candidates nor any bound: ``lb``
    bounds the valid rows outside the candidates, and every inequality
    above is the one it was, over fewer rows.  What changes is that the
    valid rows can RUN OUT, and +inf then means "nothing there", on both
    sides:

    - ``lb`` = +inf says no kernel bin, no merge bin and not the final
      select left a valid row out (each gives +inf only when it kept all
      it saw), so every valid row of every shard is among the
      candidates and nothing is excluded: the query certifies whatever
      its k-th distance, +inf included (fewer than k valid rows: the
      answer is all of them, padded).  So ``reach >= lb`` flags only
      where ``lb`` is finite;
    - the merge dropped a real candidate only if the (m+1)-th kept one
      is real: ``d32[:, m]`` = +inf says all that was dropped was
      padding, and the merge-drop test likewise holds only where it is
      finite;
    - a window that runs into padding needs no repair: a (row, +inf)
      pair is never tight and always a provable boundary, so the test
      that every one of the first k+1 is finite (which without a mask
      says "this shard gave junk") is left out.

    ``vote`` (``(1 / T, classes_out, delta)``, static; a cosine
    placement's ``predict_certified(vote="softmax")``; ``labels`` the
    replicated device labels) ends the tail in :func:`_vote_pack` instead
    of the packed candidates: the same merge, rank analysis and
    certificate, then the weighted vote over the first k candidates and
    the VOTE CERTIFICATE, which asks less of the ranking than the order
    of all k does.  A sum over the k nearest rows is the same whatever
    their order among themselves, so beside the exclusion and merge-drop
    tests above (a failure is ``bad``, today's fallback) only two things
    can change the answer:

    - *membership*: which row is k-th and which (k+1)-th.  The near-tie
      inequality is read at that ONE pair: ``tight[:, k - 1]`` flags the
      query ``boundary``; tight pairs inside the first k are not flagged.
      With the pair not tight every candidate from the (k+1)-th on is
      farther, in the cosine of the rows as given, than each of the
      first k (the gap to any later one is wider still), so the first k
      ARE the float64 neighbours, in some order;
    - *margin*: how close two class totals are (:func:`vote_delta`).  Two
      adjacent totals a >= b among the first ``classes_out + 1`` with
      ``a - b <= 2 delta a`` flag the query ``margin``; equal totals do.
      Totals past those are under the last compared one, so cannot enter.

    A zero row among the window's candidates (a label below 0 in
    ``labels``: ``ShardedKNN._vote_labels`` marks them) is ``bad``, as
    ``_certify_pallas`` flags it on the host for a search."""
    from knn_tpu.ops.pallas_knn import RANK_SLACK

    db_shards = hosts * chips
    db_idx = _db_shard_index(hosts, chips)
    gi = jnp.where(li == _INT_SENTINEL, _INT_SENTINEL,
                   li + db_idx * t.shape[0])
    if n_train is not None:
        # pre-placed databases may be zero-padded by the caller (the
        # multihost contract); rows past n_train are padding, and a
        # zero pad row sits at the origin — mask by GLOBAL index so
        # it can never be returned as a neighbor
        pad = gi >= n_train
        gi = jnp.where(pad, _INT_SENTINEL, gi)
        d32 = jnp.where(pad, jnp.inf, d32)
    if db_shards > 1:
        # hierarchical merge tree: per-chip -> per-host over ICI, then
        # per-host -> global over DCN; the exclusion bound pmins over
        # every db-sharding axis in one reduction
        d32, gi = _merge_shards(d32, gi, m + 1, hosts, chips, merge,
                                dcn_merge)
        with jax.named_scope(SCOPE_MERGE):
            lb = lax.pmin(
                lb,
                axis_name=(HOST_AXIS, DB_AXIS) if hosts > 1 else DB_AXIS)

    # --- device rank analysis over the window [0, w) ---------------
    dw = d32[:, :w]
    gaps = dw[:, 1:] - dw[:, :-1]  # [Q, w-1]
    # isfinite guard: an (x, inf-sentinel) pair yields inf <= inf,
    # which must not count as a near-tie
    near = RANK_SLACK * dw[:, 1:]
    if aug_slack is not None:
        near = near + aug_slack
    tight = (gaps <= near) & jnp.isfinite(dw[:, 1:])
    pair = lax.broadcasted_iota(jnp.int32, tight.shape, 1)
    big_after = (~tight) & (pair >= k - 1)
    has_stop = big_after.any(axis=-1)
    stop = jnp.where(has_stop, jnp.argmax(big_after, axis=-1), w - 1)
    # rows without a provable boundary (or junk near it) rerun exactly
    unresolved = ~has_stop
    if not masked:
        unresolved = unresolved | ~jnp.isfinite(dw[:, : k + 1]).all(-1)
    tight_use = tight & (pair < stop[:, None]) & ~unresolved[:, None]

    # --- device certificate ----------------------------------------
    # tolerances mirror ops.pallas_knn.kernel_tolerance and include
    # the extra f32 reduction this on-device path adds (q_norm +
    # s_k arithmetic, <= ~12 eps of the norm scale): "highest" budgets
    # 32 eps total; bf16x3's 2^-14 dwarfs the f32 terms either way.
    # int8's tolerance is the per-query PROVABLE quantization
    # bound ε from the ACTUAL residual norms — byte-exact data (bvecs)
    # gets an ε of pure f32 slack, tighter than bf16x3's; pq's is the
    # per-subspace Cauchy-Schwarz bound (ops.pq, same actual-residual
    # discipline hoisted per subspace at encode time).
    q32 = q.astype(jnp.float32)
    if precision == "int8":
        from knn_tpu.ops.quantize import score_error_bound_device

        q_norm, tol = score_error_bound_device(
            q32 - quant_offset, consts)
    elif precision == "pq":
        from knn_tpu.ops.pq import score_error_bound_pq_device

        q_norm, tol = score_error_bound_pq_device(
            q32, consts, dsub=pq_dsub)
    elif precision in ("bf16x3", "bf16x3f"):
        q_norm = jnp.sum(q32 * q32, axis=-1)
        tol = 2.0 ** -14 * (q_norm + db_norm_max)
    else:
        q_norm = jnp.sum(q32 * q32, axis=-1)
        tol = 32.0 * float(np.finfo(np.float32).eps) * (
            q_norm + db_norm_max)
    d_k = dw[:, k - 1]
    s_k = d_k - q_norm
    reach = s_k + RANK_SLACK * d_k + tol
    if aug_slack is not None:
        reach = reach + aug_slack
    bad = reach >= lb
    if masked:
        bad = bad & jnp.isfinite(lb)
    if slack_outcome:
        bare = reach - aug_slack >= lb  # the certificate without the slack
    if db_shards > 1:
        # merge-dropped candidates have direct-diff f32 distance
        # >= the (m+1)-th kept; require true-distance clearance
        kept = d_k + RANK_SLACK * d_k
        if slack_outcome:
            bare = bare | (kept >= d32[:, m] * (1.0 - RANK_SLACK))
        if aug_slack is not None:
            kept = kept + aug_slack
        dropped = kept >= d32[:, m] * (1.0 - RANK_SLACK)
        if masked:
            dropped = dropped & jnp.isfinite(d32[:, m])
        bad = bad | dropped
    if slack_outcome:
        by_slack = bad & ~bare & ~unresolved
    bad = bad | unresolved
    if vote is not None:  # a cosine program: slack_outcome is on
        return _vote_pack(d32, gi, tight, bad, by_slack, labels,
                          k=k, w=w, vote=vote)
    cols = [
        gi[:, :w],
        lax.bitcast_convert_type(_pack_bits_u32(tight_use), jnp.int32),
        bad.astype(jnp.int32)[:, None],
    ]
    if slack_outcome:
        cols[2] = cols[2] + 2 * by_slack.astype(jnp.int32)[:, None]
    if include_distances:
        cols.append(lax.bitcast_convert_type(d32[:, :k], jnp.int32))
    return jnp.concatenate(cols, axis=1)


#: device scope of the weighted vote and its certificate, inside the
#: certify/pack tail's
SCOPE_VOTE = "knn.vote"
#: bits of a voted query's flag word (``_vote_pack``): uncertified (the
#: search's own fallback), the k-th and (k+1)-th candidates too close to
#: tell apart, two class totals too close, and (as bit 1 of a cosine
#: search's word) failed by the pair slack alone
VOTE_BAD, VOTE_BOUNDARY, VOTE_MARGIN, VOTE_BY_SLACK = 1, 2, 4, 8


@jax.named_scope(SCOPE_VOTE)
def _vote_pack(d32, gi, tight, bad, by_slack, labels, *, k, w, vote):
    """The weighted vote over the first k merged candidates and its
    certificate (``_certify_pack_spmd``'s docstring), packed for the
    host: ``(answer [Q, 2 classes_out + 1] int32, window [Q, w] int32)``.
    ``answer`` holds the classes (-1 past the last present), their
    float32 totals WITHOUT the factor ``exp(1 / T)`` that every weight
    shares (bitcast; :func:`unpack_voted` applies it in float64) and one
    flag word; ``window`` the ranked
    candidate indices, which stay on the device unless the host asks for
    a flagged query's (``ShardedKNN._vote_pallas``).  ``d32`` are squared
    distances of unit rows: a cosine distance is half of one."""
    from knn_tpu.ops.vote import exp_weight, softmax_vote

    inv_t, classes_out, delta = vote
    in_db = gi[:, :w] != _INT_SENTINEL
    lab_w = labels[jnp.minimum(gi[:, :w], labels.shape[0] - 1)]
    bad = bad | ((lab_w < 0) & in_db).any(axis=-1)
    lab = jnp.where(lab_w[:, :k] < 0, -1 - lab_w[:, :k], lab_w[:, :k])
    # exp(-c / T): the factor exp(1 / T) every weight shares is the
    # host's (float64), so the argument stays small and its rounding too
    weights = jnp.where(
        in_db[:, :k], exp_weight(-(0.5 * d32[:, :k]) * inv_t), 0.0)
    cls, tot = softmax_vote(lab, weights, classes_out + 1)
    hi, lo = tot[:, :-1], tot[:, 1:]
    margin = ((lo > 0) & (hi - lo <= (2.0 * delta) * hi)).any(axis=-1)
    flag = (VOTE_BAD * bad.astype(jnp.int32)
            + VOTE_BOUNDARY * tight[:, k - 1].astype(jnp.int32)
            + VOTE_MARGIN * margin.astype(jnp.int32)
            + VOTE_BY_SLACK * by_slack.astype(jnp.int32))
    answer = jnp.concatenate(
        [cls[:, :classes_out],
         lax.bitcast_convert_type(tot[:, :classes_out], jnp.int32),
         flag[:, None]], axis=1)
    return answer, gi[:, :w]


def unpack_voted(answer: np.ndarray, classes_out: int, temperature: float
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host inverse of :func:`_vote_pack`'s ``answer``: (classes [Q,
    classes_out] int32, totals [Q, classes_out] float64: the device's
    float32 sums of ``exp(-c32 / T)`` times ``exp(1 / T)``, flag words
    [Q])."""
    arr = np.ascontiguousarray(np.asarray(answer))
    totals = np.ascontiguousarray(
        arr[:, classes_out : 2 * classes_out]).view(np.float32)
    return (arr[:, :classes_out].copy(),
            totals.astype(np.float64) * np.exp(1.0 / temperature),
            arr[:, 2 * classes_out])


def unpack_certified(
    packed: np.ndarray, k: int, w: int, with_distances: bool
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Host inverse of ``_pallas_certified_program``'s packed output:
    (gi [Q, w] i32, tight [Q, w-1] bool, bad [Q] bool, dk [Q, k] f32 or
    None).  The innermost span open on the calling thread (the caller's
    ``certified.unpack``) is told ``copies_s``, the seconds of its two
    contiguous copies."""
    copies = {}
    with obs.trace.phase(copies, "s"):
        arr = np.ascontiguousarray(np.asarray(packed))
    nw = -(-(w - 1) // 32)
    gi = arr[:, :w]
    tight = unpack_bits_u32(arr[:, w : w + nw].view(np.uint32), w - 1)
    bad = arr[:, w + nw] != 0
    dk = None
    if with_distances:
        with obs.trace.phase(copies, "s"):
            dk = np.ascontiguousarray(
                arr[:, w + nw + 1 : w + nw + 1 + k]
            ).view(np.float32)
    obs.current_span().set("copies_s", copies.get("s", 0.0))
    return gi, tight, bad, dk


def failed_by_slack(packed: np.ndarray, w: int) -> np.ndarray:
    """[Q] bool from a COSINE program's packed output: the queries whose
    certificate holds without the pair slack and fails with it (bit 1 of
    the flag word, ``_certify_pack_spmd``'s ``slack_outcome``)."""
    return (np.asarray(packed)[:, w + -(-(w - 1) // 32)] & 2) != 0


@functools.lru_cache(maxsize=32)
def _count_program(mesh: Mesh, n_train: int, train_tile: Optional[int]):
    """Per-query count of db rows with squared-L2 distance strictly below
    the query's threshold — the distributed certificate pass of
    ops.certified (matmul-bound, no selection).  Counts psum over the db
    axis; output replicated there."""
    from knn_tpu.ops.certified import count_below

    hosts, chips = db_topology(mesh)
    dbp = db_axes(mesh)
    tile = train_tile or 131072

    def spmd(q, t, thr):
        db_idx = _db_shard_index(hosts, chips)
        n_local_valid = jnp.clip(n_train - db_idx * t.shape[0], 0, t.shape[0])
        # count within the local shard, masking padding rows via a
        # +inf-threshold trick: rows >= n_local_valid can't be < thr
        local = count_below.__wrapped__(
            t, q, thr, tile=min(tile, t.shape[0]), n_valid=n_local_valid
        )
        if hosts * chips > 1:
            local = lax.psum(local, dbp if hosts > 1 else DB_AXIS)
        return local

    prog = jax.jit(
        jax.shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(QUERY_AXIS), P(dbp), P(QUERY_AXIS)),
            out_specs=P(QUERY_AXIS),
            check_vma=False,
        )
    )
    _hooks.mark_built(prog, f"tile={tile}")
    return prog


@functools.lru_cache(maxsize=32)
def _range_program(mesh: Mesh, n_train: int, tile: int, width: int):
    """The range completion's device program: on every db shard one
    pass over the placed rows, ``tile`` rows a step
    (ops.radius.within_words), and the marked words compacted to
    ``width`` a query (ops.radius.compact_words).  Per query the count
    of rows at or under its threshold, summed over the db axis, and the
    shards' compacted words side by side along the last axis."""
    from knn_tpu.ops.radius import compact_words, within_words

    hosts, chips = db_topology(mesh)
    dbp = db_axes(mesh)

    def spmd(q, t, thr):
        db_idx = _db_shard_index(hosts, chips)
        n_local_valid = jnp.clip(n_train - db_idx * t.shape[0], 0, t.shape[0])
        counts, words = within_words(t, q, thr, tile=tile,
                                     n_valid=n_local_valid)
        if hosts * chips > 1:
            counts = lax.psum(counts, dbp if hosts > 1 else DB_AXIS)
        return counts, compact_words(words, width)

    prog = jax.jit(
        jax.shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(QUERY_AXIS), P(dbp), P(QUERY_AXIS)),
            out_specs=(P(QUERY_AXIS), P(QUERY_AXIS, None, dbp)),
            check_vma=False,
        )
    )
    _hooks.mark_built(prog, f"tile={tile},width={width}")
    return prog


@functools.lru_cache(maxsize=16)
def _minmax_program(mesh: Mesh, n_arrays: int):
    axes = (QUERY_AXIS, HOST_AXIS, DB_AXIS) if HOST_AXIS in mesh.shape \
        else (QUERY_AXIS, DB_AXIS)

    def spmd(*arrays):
        lo, hi = None, None
        for a in arrays:
            alo, ahi = local_minmax(a)
            lo = alo if lo is None else jnp.minimum(lo, alo)
            hi = ahi if hi is None else jnp.maximum(hi, ahi)
        # The reference's two Allreduces, knn_mpi.cpp:276-277:
        lo = allreduce_min(lo, axes)
        hi = allreduce_max(hi, axes)
        return lo, hi

    return jax.jit(
        jax.shard_map(
            spmd,
            mesh=mesh,
            in_specs=tuple(P(axes) for _ in range(n_arrays)),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )


def sharded_minmax(
    arrays: Sequence[jax.Array], *, mesh: Mesh
) -> Tuple[jax.Array, jax.Array]:
    """Distributed per-dim (min, max) over the union of several [N_i, D]
    arrays — the reference's transductive extrema phase (knn_mpi.cpp:245-277)
    with pmin/pmax standing in for its Allreduce pair.  Row padding uses
    edge replication, which leaves extrema unchanged.  Empty arrays are the
    reduce identity (+inf, -inf), matching ops.normalize.local_minmax."""
    arrays = list(arrays)
    if not arrays:
        raise ValueError("sharded_minmax needs at least one array")
    dim = arrays[0].shape[-1]
    nonempty = [a for a in arrays if a.shape[0] > 0]
    if not nonempty:
        return (
            jnp.full((dim,), jnp.inf, dtype=jnp.float32),
            jnp.full((dim,), -jnp.inf, dtype=jnp.float32),
        )
    n_dev = mesh.size
    padded = []
    for a in nonempty:
        n = a.shape[0]
        target = max(-(-n // n_dev) * n_dev, n_dev)
        if target != n:
            pad_fn = np.pad if isinstance(a, np.ndarray) else jnp.pad
            a = pad_fn(a, ((0, target - n), (0, 0)), mode="edge")
        padded.append(shard(
            a, mesh,
            (QUERY_AXIS, HOST_AXIS, DB_AXIS) if HOST_AXIS in mesh.shape
            else (QUERY_AXIS, DB_AXIS)))
    fn = _minmax_program(mesh, len(padded))
    return fn(*padded)


def sharded_normalize_transductive(
    train: jax.Array,
    test: Optional[jax.Array] = None,
    val: Optional[jax.Array] = None,
    *,
    mesh: Mesh,
):
    """Reference L2 phase (knn_mpi.cpp:229-306) on the mesh: joint extrema
    over train ∪ test ∪ val, then in-place rescale with constant dims passed
    through.  Returns (train', test', val') with None passed through."""
    present = [a for a in (train, test, val) if a is not None]
    lo, hi = sharded_minmax(present, mesh=mesh)
    return tuple(
        None if a is None else _minmax_apply_jit(a, lo, hi) for a in (train, test, val)
    )
