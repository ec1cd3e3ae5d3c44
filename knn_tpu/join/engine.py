"""The bulk kNN-join engine — query-side double buffering over the
EXISTING kernels and sharded programs (no new kernels).

Two modes (:data:`JOIN_MODES`):

- ``"stream"``: the throughput path.  A splits into fixed-width query
  superblocks (explicit rows > ``KNN_TPU_JOIN_SUPERBLOCK`` env > a
  query-byte budget through :func:`knn_tpu.analysis.hbm.
  plan_superblocks` > the library default); each superblock places
  h2d and dispatches through
  :func:`knn_tpu.parallel.sharded.query_stream_program` (the exact
  search program) under the
  bounded-depth drain-oldest discipline — block i+1's transfer +
  dispatch overlaps block i's fetch, measured as the
  dispatch-timeline ``overlap_ratio``.
  When B itself exceeds HBM (a host-RAM-tier placement), the sweep
  nesting order comes from :func:`knn_tpu.analysis.hbm.plan_join`:
  ``db_major`` outer streams each db segment h2d ONCE and serves every
  superblock while it is resident (per-superblock top-k carries merge
  host-side in the device merge's lexicographic order), ``query_major``
  outer streams each superblock once — whichever moves fewer h2d
  bytes.  Results are the exact f32 lexicographic top-k, bitwise equal
  to looping :meth:`ShardedKNN.search` over the same rows.

- ``"certified"``: the exactness anchor.  Each superblock runs the
  UNMODIFIED ``search_certified`` (any selector x precision x kernel,
  kwargs forwarded; an :class:`knn_tpu.ivf.index.IVFIndex` works the
  same way), so the join result is bitwise-equal to the looped
  certified path by construction — the oracle tests pin.

Every run returns ``(d, i, stats)`` with ``stats`` carrying the
executed superblock/segment/dispatch counts (pinned against the
analysis.hbm byte model), ``rows_per_s``, and ``overlap_ratio``.
"""

from __future__ import annotations

import os
import time
from collections import deque
from typing import List, Optional, Tuple

import numpy as np

from knn_tpu.analysis import hbm

#: fallback query-superblock width when neither explicit rows, the env
#: switch, nor a query-byte budget decides — large enough that the db
#: stream amortizes (db bytes/query ~ B_bytes / 4096), small enough to
#: place twice (double buffering) beside any realistic corpus
DEFAULT_SUPERBLOCK_ROWS = 4096

#: bounded in-flight superblock depth of the drain-oldest stream
DEFAULT_DEPTH = 2

JOIN_MODES = ("stream", "certified")

#: blocks of a bulk self-join (:func:`knn_self_join`) launched and not
#: yet fetched, at most: block b+1's programs are queued before block
#: b is fetched, so the host's share of b runs under b+1 on the device
#: and the device never waits for the host.  A constant of the engine,
#: as ``DEFAULT_DEPTH`` is the stream's: 2 is what "the device always
#: has the next block" takes; a third block in flight adds queued
#: answers and nothing else.  (To time the serial loop against it, set
#: it to 1 from outside, as ``analysis.subbatch.SUB_BATCHES`` is set.)
SELF_JOIN_DEPTH = 2

_ENV_SUPERBLOCK = "KNN_TPU_JOIN_SUPERBLOCK"
_ENV_DEPTH = "KNN_TPU_JOIN_DEPTH"
_ENV_QUERY_BUDGET = "KNN_TPU_JOIN_QUERY_BUDGET_BYTES"


def _env_int(name: str) -> Optional[int]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError as e:
        # strict-env discipline (hosttier/admission switches): a typo'd
        # knob raises instead of silently running at the default
        raise ValueError(f"{name}={raw!r} is not an int") from e


def _is_sharded(program) -> bool:
    return hasattr(program, "_place_queries")


def _resolve_superblock(program, n_a: int, superblock_rows: Optional[int],
                        query_budget_bytes: Optional[int]) -> int:
    """Superblock width: explicit rows > env rows > (explicit/env)
    query-byte budget through the hbm model > the library default —
    always clamped to ``n_a`` and at least 1."""
    rows = superblock_rows if superblock_rows is not None \
        else _env_int(_ENV_SUPERBLOCK)
    if rows is None:
        budget = query_budget_bytes if query_budget_bytes is not None \
            else _env_int(_ENV_QUERY_BUDGET)
        if budget is not None:
            dim = _query_dim(program)
            qm = _query_multiple(program)
            segs = hbm.plan_superblocks(n_a, dim, budget,
                                        query_multiple=qm)
            rows = segs[0][1] - segs[0][0]
        else:
            rows = DEFAULT_SUPERBLOCK_ROWS
    rows = int(rows)
    if rows < 1:
        raise ValueError(f"superblock_rows must be >= 1, got {rows}")
    return min(rows, int(n_a))


def _resolve_depth(depth: Optional[int]) -> int:
    d = depth if depth is not None else _env_int(_ENV_DEPTH)
    return max(1, int(d)) if d is not None else DEFAULT_DEPTH


def _query_dim(program) -> int:
    if _is_sharded(program):
        return int(getattr(program, "dim_in", None)
                   or program._tp.shape[1])
    return int(program.dim)  # IVFIndex


def _query_multiple(program) -> int:
    from knn_tpu.parallel.mesh import QUERY_AXIS

    try:
        return int(program.mesh.shape[QUERY_AXIS])
    except Exception:
        return 1


def default_plan(program, n_a: int, *,
                 superblock_rows: Optional[int] = None,
                 query_budget_bytes: Optional[int] = None) -> dict:
    """The jax-free plan :func:`knn_join` would execute for ``n_a``
    query rows against ``program``'s corpus: superblock width, sweep
    nesting order, and h2d byte totals (analysis.hbm.plan_join)."""
    sb = _resolve_superblock(program, n_a, superblock_rows,
                             query_budget_bytes)
    dim = _query_dim(program)
    if _is_sharded(program) and program._host_tier is not None:
        seg_rows = int(program._host_tier["segment_rows"])
        n_b = int(program.n_train)
    else:
        seg_rows = 0
        n_b = int(program.n_train if _is_sharded(program)
                  else program.stats()["live_rows"])
    plan = hbm.plan_join(n_a, n_b, dim, superblock_rows=sb,
                         db_segment_rows=seg_rows)
    plan["superblock_rows"] = sb
    plan["db_segment_rows"] = seg_rows
    return plan


def _pad_block(q: np.ndarray, lo: int, hi: int, rows: int) -> np.ndarray:
    """One fixed-width query block (ragged tail zero-pads up, so every
    superblock dispatch shares ONE compiled program shape; pad rows are
    ordinary queries whose outputs are sliced away)."""
    blk = q[lo:hi]
    if blk.shape[0] < rows:
        blk = np.pad(blk, ((0, rows - blk.shape[0]), (0, 0)))
    return blk


def _stream_resident(program, q: np.ndarray, k: int, sb_rows: int,
                     depth: int, d_out, i_out) -> dict:
    """Resident-B stream: double-buffer query superblocks through the
    search program, drain-oldest at ``depth``."""
    from knn_tpu.parallel.sharded import (
        _fetch_or_redispatch, _overlap_ratio, _retry_transient,
        query_stream_program)

    prog = query_stream_program(
        program.mesh, k, program.n_train, program.metric, program.merge,
        train_tile=program.train_tile, compute_dtype=program._dtype_key,
        dcn_merge=program.dcn_merge)
    n_a = q.shape[0]
    blocks = [(lo, min(lo + sb_rows, n_a))
              for lo in range(0, n_a, sb_rows)]

    def launch(lo: int, hi: int):
        # h2d placement + async dispatch: each placement is freed when
        # its block is collected, so at most ``depth`` coexist
        qp, _ = program._place_queries(_pad_block(q, lo, hi, sb_rows))
        return prog(qp, program._tp)

    pending: list = []
    intervals: list = []

    def collect() -> None:
        lo, hi, t0, out = pending.pop(0)
        cur = {"out": out}

        def redo():
            # d and i MUST come from the same execution (the host-tier
            # paired-output discipline): relaunch rebinds BOTH outputs
            cur["out"] = launch(lo, hi)
            return cur["out"][0]

        d = _fetch_or_redispatch(out[0], redo, "join fetch")
        i = np.asarray(cur["out"][1])
        intervals.append((t0, time.perf_counter()))
        # (positions among the placed rows become row ids here)
        d_out[lo:hi], i_out[lo:hi] = program._answers_by_id(
            d[: hi - lo], i[: hi - lo])

    for lo, hi in blocks:
        while len(pending) >= depth:
            collect()
        t0 = time.perf_counter()
        out = _retry_transient(lambda lo=lo, hi=hi: launch(lo, hi),
                               "join dispatch")
        pending.append((lo, hi, t0, out))
    while pending:
        collect()
    return {
        "superblocks": len(blocks),
        "db_segments": 1,
        "dispatches": len(blocks),
        "overlap_ratio": round(_overlap_ratio(intervals), 4),
    }


def _stream_tiered(program, q: np.ndarray, k: int, sb_rows: int,
                   depth: int, order: str, d_out, i_out) -> dict:
    """Super-HBM-B stream: both A and B sweep through the host-tier
    SEGMENT program in the byte-model-chosen nesting order, with
    per-superblock top-k carries merged host-side in the device merge's
    lexicographic order.  ``db_major`` places each db segment h2d ONCE
    (it stays resident for every superblock's dispatch); ``query_major``
    places each superblock once."""
    from knn_tpu.ops.pallas_knn import PAD_VAL
    from knn_tpu.parallel.collectives import replicate, shard
    from knn_tpu.parallel.mesh import db_axes
    from knn_tpu.parallel.multihost import merge_topk_host
    from knn_tpu.parallel.sharded import (
        _INT_SENTINEL, _fetch_or_redispatch, _overlap_ratio,
        _retry_transient, segment_search_program)

    import jax.numpy as jnp

    ht = program._host_tier
    host = program._train_host
    seg_rows = ht["segment_rows"]
    dtype = (None if program._dtype_key is None
             else jnp.dtype(program._dtype_key))
    prog = segment_search_program(
        program.mesh, k, program.metric, program.merge,
        train_tile=program.train_tile, compute_dtype=dtype,
        dcn_merge=program.dcn_merge)
    n_a = q.shape[0]
    blocks = [(lo, min(lo + sb_rows, n_a))
              for lo in range(0, n_a, sb_rows)]
    segments = ht["segments"]
    carry_d: List[Optional[np.ndarray]] = [None] * len(blocks)
    carry_i: List[Optional[np.ndarray]] = [None] * len(blocks)

    def place_seg(slo: int, shi: int):
        seg = host[slo:shi]
        if seg.shape[0] < seg_rows:
            seg = np.pad(seg, ((0, seg_rows - seg.shape[0]), (0, 0)),
                         constant_values=PAD_VAL)
        tp = shard(seg, program.mesh, db_axes(program.mesh))
        nv = replicate(np.asarray([shi - slo], np.int32), program.mesh)
        return tp, nv

    def place_q(lo: int, hi: int):
        qp, _ = program._place_queries(_pad_block(q, lo, hi, sb_rows))
        return qp

    pending: list = []
    intervals: list = []

    def collect() -> None:
        bi, (lo, hi), slo, t0, out, relaunch = pending.pop(0)
        cur = {"out": out}

        def redo():
            cur["out"] = relaunch()
            return cur["out"][0]

        d = _fetch_or_redispatch(out[0], redo, "join fetch")
        i = np.asarray(cur["out"][1])
        intervals.append((t0, time.perf_counter()))
        pad = i == _INT_SENTINEL
        gi = np.where(pad, _INT_SENTINEL, i.astype(np.int64) + slo)
        d = np.asarray(d)
        if carry_d[bi] is None:
            carry_d[bi], carry_i[bi] = d, gi
        else:
            carry_d[bi], carry_i[bi] = merge_topk_host(
                [carry_d[bi], d], [carry_i[bi], gi], k)

    dispatches = 0
    if order == "db_major":
        outer = [((slo, shi), None) for slo, shi in segments]
        for (slo, shi), _ in outer:
            tp, nv = place_seg(slo, shi)
            for bi, (lo, hi) in enumerate(blocks):
                while len(pending) >= depth:
                    collect()
                t0 = time.perf_counter()

                def relaunch(lo=lo, hi=hi, tp=tp, nv=nv):
                    return prog(place_q(lo, hi), tp, nv)

                out = _retry_transient(relaunch, "join dispatch")
                pending.append((bi, (lo, hi), slo, t0, out, relaunch))
                dispatches += 1
            # drain before the NEXT segment placement replaces tp: at
            # most one db segment is device-resident at a time (the
            # byte budget the tier exists to honor)
            while pending:
                collect()
    else:  # query_major
        for bi, (lo, hi) in enumerate(blocks):
            qp = place_q(lo, hi)
            for slo, shi in segments:
                while len(pending) >= depth:
                    collect()
                t0 = time.perf_counter()

                def relaunch(qp=qp, slo=slo, shi=shi):
                    tp, nv = place_seg(slo, shi)
                    return prog(qp, tp, nv)

                out = _retry_transient(relaunch, "join dispatch")
                pending.append((bi, (lo, hi), slo, t0, out, relaunch))
                dispatches += 1
        while pending:
            collect()
    for bi, (lo, hi) in enumerate(blocks):
        d_out[lo:hi] = carry_d[bi][: hi - lo]
        i_out[lo:hi] = carry_i[bi][: hi - lo]
    return {
        "superblocks": len(blocks),
        "db_segments": len(segments),
        "dispatches": dispatches,
        "overlap_ratio": round(_overlap_ratio(intervals), 4),
    }


def _certified_loop(program, q: np.ndarray, k: int, sb_rows: int,
                    d_out, i_out, kw: dict) -> dict:
    """The exactness anchor: the UNMODIFIED certified path per
    superblock (ragged tail included as-is — search_certified batches
    internally), so the join equals the looped certified path bitwise
    by construction."""
    n_a = q.shape[0]
    blocks = [(lo, min(lo + sb_rows, n_a))
              for lo in range(0, n_a, sb_rows)]
    fallbacks = 0
    for lo, hi in blocks:
        if _is_sharded(program):
            d, i, st = program.search_certified(q[lo:hi], **kw)
        else:  # IVFIndex — same surface, k rides as a kwarg
            d, i, st = program.search_certified(q[lo:hi], k=k, **kw)
        d_out[lo:hi] = d
        i_out[lo:hi] = i
        fallbacks += int(st.get("fallback_queries", 0))
    return {
        "superblocks": len(blocks),
        "db_segments": 1,
        "dispatches": len(blocks),
        "fallback_queries": fallbacks,
        "overlap_ratio": None,  # the certified loop has no pipeline
    }


def knn_join(
    program,
    queries,
    *,
    k: Optional[int] = None,
    mode: str = "stream",
    superblock_rows: Optional[int] = None,
    depth: Optional[int] = None,
    query_budget_bytes: Optional[int] = None,
    return_sqrt: bool = False,
    **certified_kw,
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Top-k of every row of ``queries`` (A) against ``program``'s
    corpus (B): ``(d [N_A, k], i [N_A, k], stats)`` host arrays.

    ``program`` is a placed :class:`knn_tpu.parallel.ShardedKNN`
    (resident or host-RAM tier) or an :class:`knn_tpu.ivf.index.
    IVFIndex` (certified mode only).  ``mode="stream"`` is the
    double-buffered throughput path (module docstring);
    ``mode="certified"`` loops the unmodified certified path per
    superblock and forwards ``certified_kw`` (selector, precision,
    kernel, margin, ...) to it.  ``superblock_rows`` / ``depth`` /
    ``query_budget_bytes`` default through the ``KNN_TPU_JOIN_*`` env
    switches.  ``stats`` reports executed superblock / db-segment /
    dispatch counts (pinned against analysis.hbm), ``rows_per_s``,
    ``overlap_ratio`` (stream mode), and the byte-model ``plan``."""
    from knn_tpu import obs

    if mode not in JOIN_MODES:
        raise ValueError(f"unknown join mode {mode!r}; expected one of "
                         f"{JOIN_MODES}")
    sharded = _is_sharded(program)
    if not sharded and mode != "certified":
        raise ValueError(
            "IVF joins run mode='certified' only (the probed tier has "
            "no resident placement to stream queries against)")
    q = np.ascontiguousarray(np.asarray(queries, np.float32))
    dim = _query_dim(program)
    if q.ndim != 2 or q.shape[1] != dim:
        raise ValueError(
            f"queries shape {q.shape} incompatible with corpus dim {dim}")
    k = int(k) if k is not None else int(program.k)
    if sharded:
        if mode == "certified" and k != int(program.k):
            raise ValueError(
                f"certified joins run the program's own certified path: "
                f"k={k} != program.k={program.k}; construct the "
                f"placement with the join k")
        if mode == "stream":
            from knn_tpu.parallel.mesh import db_topology

            hosts, chips = db_topology(program.mesh)
            db_shards = hosts * chips
            placed = (program._host_tier["segment_rows"]
                      if program._host_tier is not None
                      else int(program._tp.shape[0]))
            if k > placed // db_shards:
                raise ValueError(
                    f"k={k} exceeds db shard size "
                    f"{placed // db_shards}; use fewer db shards")
    n_a = q.shape[0]
    if n_a < 1:
        raise ValueError("knn_join needs at least one query row")
    sb_rows = _resolve_superblock(program, n_a, superblock_rows,
                                  query_budget_bytes)
    dep = _resolve_depth(depth)
    plan = default_plan(program, n_a, superblock_rows=sb_rows)
    i_out = np.empty((n_a, k), np.int64)
    d_out = np.empty((n_a, k),
                     np.float64 if mode == "certified" else np.float32)
    t0 = time.perf_counter()
    if mode == "certified":
        # the certified path owns its own metric->value mapping; let it
        # apply return_sqrt so joined values equal the looped call's
        if return_sqrt:
            certified_kw = {**certified_kw, "return_sqrt": True}
        executed = _certified_loop(program, q, k, sb_rows, d_out, i_out,
                                   certified_kw)
    elif program._host_tier is not None:
        executed = _stream_tiered(program, q, k, sb_rows, dep,
                                  plan["order"], d_out, i_out)
    else:
        executed = _stream_resident(program, q, k, sb_rows, dep,
                                    d_out, i_out)
    wall = time.perf_counter() - t0
    # the executed sweep counts must MATCH the plan — a drift here means
    # the engine and the byte model disagree about what ran
    for key in ("superblocks", "db_segments", "dispatches"):
        if mode == "stream" and executed[key] != plan[key]:
            raise RuntimeError(
                f"join executed {key}={executed[key]} but the byte model "
                f"planned {plan[key]} — engine/model drift")
    stats = {
        "mode": mode,
        "k": k,
        "rows": n_a,
        "superblock_rows": sb_rows,
        "depth": dep,
        "order": plan["order"] if mode == "stream" else "query_major",
        "wall_s": round(wall, 6),
        "rows_per_s": round(n_a / wall, 3) if wall > 0 else float("inf"),
        "plan": plan,
        **executed,
    }
    obs.record_span("join.bulk", f"join-{id(program):x}", wall,
                    rows=n_a, mode=mode)
    if return_sqrt and mode == "stream":
        # the same post-map ShardedKNN.search applies for return_sqrt
        import jax.numpy as jnp

        from knn_tpu.ops.distance import metric_values

        d_out = np.asarray(metric_values(jnp.asarray(d_out),
                                         program.metric))
    return d_out, i_out, stats


def knn_self_join(
    program, rows: Optional[Tuple[int, int]] = None, *, filter_tags=None,
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """The exact k-NN GRAPH of the placed rows, as one bulk call: for
    every row i of ``rows = (lo, hi)`` of ``program``'s placement (all
    rows where left out) the first ``program.k`` rows j != i in
    lexicographic (float64 squared L2 over the float32 rows as given,
    j) order: ``(d [hi - lo, k] float64, i [hi - lo, k] int64,
    stats)``.  The row itself goes BY ID: exact copies of row i stay,
    at distance 0, in id order.

    Every row is a query of the corpus it is part of, so the call is
    the certified path's own (``selector="pallas"``: one database pass,
    the kernel's exclusion bound the certificate) under the join's
    schedule:

    - the call is cut into blocks of ``DEFAULT_SUPERBLOCK_ROWS`` rows,
      each the sub-batch rule's launches (4 of 1,024), and at most
      ``SELF_JOIN_DEPTH`` blocks are in flight: block b+1's launches
      are queued before block b is fetched, unpacked, rank-corrected
      and, where a query was flagged, re-selected; the re-select of
      block b is launched behind block b+1 and fetched after it.  No
      step waits for the whole call, and what is queued on the device
      is bounded whatever ``rows`` is;
    - a block's queries are rows of the placed array, taken on the
      device where they lie: nothing of them crosses the link
      (``certified.dispatch`` says ``h2d_bytes`` 0), and the host ranks
      and repairs with its own copy of the same rows;
    - row ``lo + r`` is scored +inf for query ``r`` before the
      bin-select, in the row tiles that hold the launch's rows
      (``parallel.sharded._pallas_self_program``), so it takes no
      candidate's place and the certificate is read over the n - 1
      rows that are left.

    Squared-L2 placements that ``ShardedKNN`` laid out itself, resident
    on the device.  An inner-product or cosine placement, the host-RAM
    tier, a pre-placed array and ``filter_tags`` (a graph under a
    predicate) refuse, each with what it lacks.  The answers do not
    depend on the depth, bit for bit.

    ``stats`` carries the stream's counts (``superblocks``,
    ``dispatches``, ``overlap_ratio``, ``rows_per_s``) and the
    certified call's (``fallback_queries``, ``rank_corrected_queries``,
    ``operands``, ``sub_batch``, ``pallas_knobs``, ``tuning``), and
    ``self_excluded``: the rows whose own row was taken out, which is
    every row answered.  Spans: ``join.block`` a block (launch to
    answer) with the ``certified.*`` stages summed over its launches
    as its children; a call's ``certified.exposed`` and
    ``certified.inflight.*`` as a search call's, and ``join.exposed``,
    the same exposed seconds by where they fell (``fill_s`` before the
    first launch, ``drain_s`` after the last answer, ``between_s``);
    ``join.bulk`` the call."""
    from knn_tpu import obs
    from knn_tpu.obs import names as mn
    from knn_tpu.parallel.sharded import _ACCOUNT_ROOT, _overlap_ratio

    if not _is_sharded(program):
        raise ValueError(
            "the certified self-join needs a placed ShardedKNN: an IVF "
            "index probes a few lists a query and holds no certificate "
            "over all rows")
    if filter_tags is not None:
        raise ValueError(
            "the certified self-join holds every query to ONE exclusion, "
            "its own row; filter_tags asks for a graph under a predicate "
            "(validity words a query beside the own row), which is not "
            "built yet (ROADMAP R13)")
    lo, hi = (0, int(program.n_train)) if rows is None else map(int, rows)
    tid = obs.new_trace_id()
    # a certified call's account (``certified.exposed``,
    # ``certified.inflight.*``: once a call, as a search's), without its
    # stage sums: those are a block's (``obs.trace.BlockAccount``)
    acct = obs.trace.call_account(_ACCOUNT_ROOT, ("certified", "reselect"))
    t0 = time.perf_counter()
    call = program.self_join_call(lo, hi, DEFAULT_SUPERBLOCK_ROWS,
                                  trace_id=tid, acct=acct)
    blocks = [(b, min(b + DEFAULT_SUPERBLOCK_ROWS, hi))
              for b in range(lo, hi, DEFAULT_SUPERBLOCK_ROWS)]
    inflight = obs.gauge(mn.JOIN_BLOCKS_INFLIGHT)
    pending, unsettled, intervals = deque(), deque(), []

    def settle() -> None:
        blk = unsettled.popleft()
        call.settle(blk)
        intervals.append((blk.t0, time.perf_counter()))

    def collect() -> None:
        blk = pending.popleft()
        call.collect(blk)
        unsettled.append(blk)
        # block b's re-select was queued behind block b+1's programs:
        # it is fetched once b+1 is, and at depth 1 at once
        while len(unsettled) >= SELF_JOIN_DEPTH:
            settle()

    for b_lo, b_hi in blocks:
        pending.append(call.launch(b_lo, b_hi))
        inflight.set(len(pending))
        if len(pending) >= SELF_JOIN_DEPTH:
            collect()
    while pending:
        collect()
    while unsettled:
        settle()
    inflight.set(0)
    wall = time.perf_counter() - t0
    n = hi - lo
    told = call.finish()
    stats = {
        "mode": "self", "k": int(program.k), "rows": n,
        "row_range": (lo, hi),
        "superblock_rows": DEFAULT_SUPERBLOCK_ROWS,
        "depth": SELF_JOIN_DEPTH, "superblocks": len(blocks),
        "db_segments": 1, "dispatches": told["launches"],
        "overlap_ratio": round(_overlap_ratio(intervals), 4),
        "wall_s": round(wall, 6),
        "rows_per_s": round(n / wall, 3) if wall > 0 else float("inf"),
        **told,
    }
    obs.counter(mn.JOIN_ROWS, mode="self").inc(n)
    acct.close(tid, "join.bulk")
    if obs.enabled():
        # what the pipeline did not hide: the call's exposed seconds by
        # where they fell (the fill before the first launch, the drain
        # after the last answer, and between, where the device had run
        # out of queued blocks)
        fill = acct.before_first or 0.0
        obs.record_span(
            "join.exposed", tid, acct.exposed, parent="join.bulk",
            blocks=len(blocks), fill_s=fill, between_s=acct.between,
            drain_s=acct.exposed - fill - acct.between)
    obs.record_span("join.bulk", tid, wall, rows=n, mode="self",
                    blocks=len(blocks), self_excluded=n,
                    fallback_queries=told["fallback_queries"])
    return call.d, call.i, stats
