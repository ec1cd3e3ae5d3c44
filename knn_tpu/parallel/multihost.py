"""Multi-host execution — the DCN half of the communication backend.

The reference scales with ``mpiexec -n N`` across nodes: every rank is an
OS process and MPI wires them together (knn_mpi.cpp:123-125; report PDF
p.5-7 §2.2).  The TPU-native equivalent is one JAX process per host joined
through :func:`jax.distributed.initialize`; after that, ``jax.devices()``
is the *global* device list, the 2-D mesh (parallel.mesh) spans every
host, and the SAME SPMD programs (parallel.sharded) run unchanged — XLA
routes collectives over ICI within a slice and DCN across slices.  There
is no second code path: multi-host is a bigger mesh.

What this module adds is the data-movement story MPI gets from its
collectives: each host holds only its own slice of the database/queries
(the reference instead makes rank 0 read everything and Bcast it —
knn_mpi.cpp:154-175,224), and :func:`shard_across_hosts` assembles those
host-local rows into one globally-sharded ``jax.Array`` without any host
ever materializing the full matrix.

Two DCN transports for the hierarchical merge's global level:

- **in-mesh** — a process-spanning ``make_host_mesh`` placement; XLA
  runs the host-axis collectives over DCN (parallel.sharded's merge
  tree).  Needs a backend that can execute cross-process computations.
- **host-mediated** — :class:`MultiHostKNN`: per-host candidates
  computed on each process's own devices, exchanged through the
  ``jax.distributed`` coordinator's key-value store
  (:func:`dcn_allgather_arrays`) and merged on host
  (:func:`merge_topk_host`, the same lexicographic order).  Works on
  every supported jaxlib — it is the 2-process CPU CI lane — and is
  bitwise-identical to the single-host reference.
"""

from __future__ import annotations

import base64
import io
import itertools
import threading
import time
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from knn_tpu import obs
from knn_tpu.obs import ident as _ident
from knn_tpu.obs import names as _mn
from knn_tpu.parallel import crossover
from knn_tpu.parallel.mesh import DB_AXIS, QUERY_AXIS, make_mesh


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join this process to the multi-host runtime (the reference's
    ``MPI_Init``, knn_mpi.cpp:123).  No-op when single-process or already
    initialized, so driver code can call it unconditionally."""
    if num_processes is None or num_processes <= 1:
        return
    # already-joined guard WITHOUT jax.process_count(): that call would
    # initialize the local backend first, after which distributed init
    # can no longer succeed
    from jax._src import distributed as _distributed

    if _distributed.global_state.client is not None:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    # stamp the process identity every snapshot / JSONL line carries
    # (knn_tpu.obs.ident) — the fleet aggregator attributes members by
    # it.  Only the init args: touching jax.process_index() here could
    # initialize the local backend earlier than callers expect.
    stamp = {"process_count": int(num_processes),
             "coordinator_address": coordinator_address}
    if process_id is not None:
        stamp["process_index"] = int(process_id)
    _ident.set_identity(**stamp)


def global_mesh(
    query_shards: Optional[int] = None, db_shards: int = 1
) -> Mesh:
    """The (query, db) mesh over every device of every host — the
    reference's ``MPI_COMM_WORLD`` (knn_mpi.cpp:124-125)."""
    return make_mesh(query_shards, db_shards, devices=jax.devices())


def shard_across_hosts(
    local_rows: np.ndarray,
    mesh: Mesh,
    axis_name: str = DB_AXIS,
) -> jax.Array:
    """Assemble per-host row blocks into one global ``jax.Array`` sharded
    along ``axis_name`` — the multi-host ``MPI_Scatter`` (knn_mpi.cpp:
    226-227) with no root: every host contributes the rows it already has,
    concatenated in process order.  Row counts must be equal across hosts
    (pad with :func:`knn_tpu.parallel.mesh.pad_to_multiple` first — prefer
    ``fill=ops.pallas_knn.PAD_VAL`` so the pallas certificate's exclusion
    bound stays sharp; zero fill is correct but costs fallbacks — and pass
    the true pre-pad row count to ``ShardedKNN(..., n_train=...)`` so pad
    rows stay masked); the global row count is
    ``local_rows.shape[0] * process_count``.

    Single-process, this is exactly a sharded ``device_put``.
    """
    local_rows = np.asarray(local_rows)
    pc = jax.process_count()
    axis_size = int(np.prod([mesh.shape[a] for a in (
        (axis_name,) if isinstance(axis_name, str) else axis_name
    )]))
    if axis_size % pc:
        raise ValueError(
            f"mesh axis {axis_name!r} (size {axis_size}) must be a multiple "
            f"of process_count={pc} to scatter rows across hosts; with fewer "
            "shards than processes the array would be replicated and every "
            "host would need the full matrix"
        )
    spec = [None] * local_rows.ndim
    spec[0] = axis_name
    sharding = NamedSharding(mesh, P(*spec))
    global_shape = (
        local_rows.shape[0] * pc,
        *local_rows.shape[1:],
    )
    return jax.make_array_from_process_local_data(
        sharding, local_rows, global_shape
    )


def process_row_slice(n_global_rows: int) -> slice:
    """Which contiguous rows of a [N, D] global matrix this process should
    load from disk — the per-rank read assignment the reference hard-codes
    by rank id (knn_mpi.cpp:154-222).  Rows must already be padded to a
    multiple of process_count."""
    pc = jax.process_count()
    if n_global_rows % pc:
        raise ValueError(
            f"{n_global_rows} rows not divisible by {pc} processes; pad first"
        )
    per = n_global_rows // pc
    pid = jax.process_index()
    return slice(pid * per, (pid + 1) * per)


# --- host-mediated DCN merge (the transport that works on ANY jaxlib) --

#: bounded last-merge report for /statusz + doctor (obs.health reads it)
_REPORT_LOCK = threading.Lock()
_LAST_REPORT: dict = {}

#: per-process replica counter: KV keys embed the replica's construction
#: ordinal, so two replicas (or two searches of one replica) can never
#: collide on a coordinator key — construction and call order must match
#: across processes anyway (the SPMD collective discipline)
_INSTANCE_SEQ = itertools.count()


def last_report() -> Optional[dict]:
    """The last cross-host merge's observability snapshot (hosts,
    strategy, straggler gap, merge bytes) — the /statusz "multihost"
    section; None until a merge ran in this process."""
    with _REPORT_LOCK:
        return dict(_LAST_REPORT) if _LAST_REPORT else None


def _update_report(**kw) -> None:
    with _REPORT_LOCK:
        _LAST_REPORT.clear()
        _LAST_REPORT.update(kw)


def _kv_client():
    """The jax.distributed coordinator's key-value client — the DCN
    side channel every jaxlib build carries once ``initialize`` ran,
    even the ones whose CPU backend cannot EXECUTE cross-process
    computations ("Multiprocess computations aren't implemented": the
    collective would run inside XLA; this store runs beside it)."""
    from jax._src import distributed

    client = getattr(distributed.global_state, "client", None)
    if client is None:
        raise RuntimeError(
            "jax.distributed is not initialized; call "
            "multihost.initialize(...) first")
    return client


def _encode_arrays(*arrays) -> str:
    buf = io.BytesIO()
    np.savez(buf, *[np.ascontiguousarray(np.asarray(a)) for a in arrays])
    return base64.b64encode(buf.getvalue()).decode("ascii")


def _decode_arrays(raw: str, n: int) -> List[np.ndarray]:
    with np.load(io.BytesIO(base64.b64decode(raw))) as z:
        return [z[f"arr_{i}"] for i in range(n)]


def dcn_allgather_arrays(arrays: Sequence[np.ndarray], *, tag: str,
                         timeout_s: float = 180.0) -> List[List[np.ndarray]]:
    """Allgather a tuple of host arrays across every jax.distributed
    process through the coordinator KV store — the host-mediated DCN
    collective.  Returns one array list per process, in process order.
    ``tag`` must be unique per logical call and identical across
    processes (every process must make the same sequence of calls —
    the usual collective discipline, enforced here by the blocking
    get's timeout rather than a hang)."""
    pc = jax.process_count()
    if pc == 1:
        return [[np.asarray(a) for a in arrays]]
    client = _kv_client()
    n = len(arrays)
    own_key = f"knn_tpu/dcn/{tag}/{jax.process_index()}"
    client.key_value_set(own_key, _encode_arrays(*arrays))
    out: List[List[np.ndarray]] = []
    for p in range(pc):
        if p == jax.process_index():
            out.append([np.asarray(a) for a in arrays])
            continue
        raw = client.blocking_key_value_get(
            f"knn_tpu/dcn/{tag}/{p}", int(timeout_s * 1000))
        out.append(_decode_arrays(raw, n))
    # reclaim coordinator memory: once EVERY process has read every
    # list (the barrier), each deletes its own key — without this a
    # long-lived replica grows the coordinator by one payload per
    # search forever.
    client.wait_at_barrier(f"knn_tpu/dcn/{tag}/read",
                           int(timeout_s * 1000))
    client.key_value_delete(own_key)
    return out


def merge_topk_host(d_lists: Sequence[np.ndarray],
                    i_lists: Sequence[np.ndarray],
                    k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side lexicographic (distance, index) top-k merge of
    per-host candidate lists — the same associative merge order
    ops.topk runs on device, so the merged result is bitwise-identical
    to a single placement ranking all rows (pinned in
    tests/test_multihost.py)."""
    cd = np.concatenate(list(d_lists), axis=1)
    ci = np.concatenate(list(i_lists), axis=1)
    order = np.lexsort((ci, cd), axis=-1)[:, :k]
    return (np.take_along_axis(cd, order, axis=-1),
            np.take_along_axis(ci, order, axis=-1))


class MultiHostKNN:
    """One logical serving replica spanning ``jax.distributed``
    processes, each holding ONLY its own contiguous row block — the
    reference's ``mpiexec -n N`` scale-out (knn_mpi.cpp:123-175) without
    its replicate-everything memory wall.

    The merge tree is hierarchical: per-chip candidate lists reduce
    per-host inside the local :class:`~knn_tpu.parallel.sharded.
    ShardedKNN` program (ICI — the local mesh's db axis, ring/allgather
    by the measured crossover), then the per-host [Q, k] lists merge
    globally over DCN.  The DCN transport here is HOST-MEDIATED: lists
    travel through the coordinator KV store and merge on host
    (:func:`merge_topk_host`) — ~Q·k·8 bytes per host per query batch,
    the volume :func:`knn_tpu.parallel.crossover.merge_bytes` prices —
    which works on every jaxlib build, including the ones whose CPU
    backend cannot execute cross-process XLA computations (the 2-process
    CI lane).  On pods whose backend CAN span processes, the in-mesh
    alternative is a hierarchical ``make_host_mesh`` placement over
    ``jax.devices()`` — same tree, collectives instead of the KV hop.

    Every process must hold the SAME row count (pad the tail host) and
    call each search method in the same order with the same queries —
    the usual SPMD collective discipline.  Results are bitwise-identical
    to a single-host ShardedKNN over the concatenated rows: per-pair
    distances are placement-invariant and both merge levels are the
    associative lexicographic order.
    """

    def __init__(
        self,
        local_rows,
        *,
        k: int,
        metric: str = "l2",
        merge: Optional[str] = None,
        dcn_merge: Optional[str] = None,
        db_shards: int = 1,
        train_tile: Optional[int] = None,
        compute_dtype=None,
        n_local: Optional[int] = None,
        mesh: Optional[Mesh] = None,
    ):
        from knn_tpu.parallel.sharded import ShardedKNN

        local_rows = np.asarray(local_rows)
        self.process_index = jax.process_index()
        self.process_count = jax.process_count()
        if mesh is None:
            mesh = make_mesh(None, db_shards, devices=jax.local_devices())
        self._local = ShardedKNN(
            local_rows, mesh=mesh, k=k, metric=metric, merge=merge,
            train_tile=train_tile, compute_dtype=compute_dtype,
        )
        if n_local is not None and n_local != local_rows.shape[0]:
            raise ValueError(
                f"n_local={n_local} != local rows {local_rows.shape[0]}; "
                f"pad every host to the same row count first")
        self.n_local = local_rows.shape[0]
        self.row_offset = self.process_index * self.n_local
        self.n_global = self.n_local * self.process_count
        self.k = k
        self.metric = self._local.metric
        if self.process_count > 1:
            # the KV transport IS an allgather (every host's list ships
            # to every host); advertising the crossover table's pick
            # here would claim an override that changes nothing.  The
            # ring/allgather choice belongs to the in-mesh path
            # (make_host_mesh + ShardedKNN.dcn_merge); an explicit
            # non-allgather request is refused rather than ignored.
            if dcn_merge is not None and dcn_merge != "allgather":
                raise ValueError(
                    f"MultiHostKNN's host-mediated DCN transport is "
                    f"inherently an allgather; dcn_merge={dcn_merge!r} "
                    f"cannot take effect — use the in-mesh "
                    f"make_host_mesh path for ring merges")
            self.dcn_merge, self.dcn_merge_source = "allgather", "transport"
            obs.counter(_mn.MERGE_SELECTED, level="dcn",
                        strategy=self.dcn_merge,
                        source=self.dcn_merge_source).inc()
        else:
            self.dcn_merge, self.dcn_merge_source = None, None
        self._instance = next(_INSTANCE_SEQ)
        self._seq = itertools.count()
        try:
            kind = jax.local_devices()[0].device_kind
        except Exception:  # backendless builds: identity stays honest
            kind = None
        _ident.set_identity(process_index=self.process_index,
                            process_count=self.process_count,
                            device_kind=kind)

    def _local_report(self, wall: float) -> None:
        """Single-process degenerate: no DCN level, but /statusz still
        gets a fresh snapshot (both search paths call this)."""
        _update_report(hosts=1, process_index=0, transport="local",
                       dcn_merge=None, dcn_merge_bytes=0,
                       straggler_gap_s=0.0, straggler_host=0,
                       host_walls_s=[round(wall, 6)])

    def _dcn_merge(self, d: np.ndarray, gi: np.ndarray, k: int,
                   local_wall_s: float, tag: str, extra=(),
                   trace_id: Optional[str] = None,
                   t_start: Optional[float] = None):
        """Exchange this host's globalized candidate list (+ optional
        per-host ``extra`` payload arrays), its local wall time, and
        its trace id, merge, record the straggler gap (max-min
        per-host wall — what /statusz attributes, with the argmax host
        named) and the DCN volume.  Returns ``(merged_d, merged_gi,
        info)`` where ``info`` carries the per-process walls, gap,
        straggler host, canonical trace id, bytes, and each process's
        extra arrays — ONE exchange/metrics/report home for both
        search paths.

        Trace stitching: each process's trace id rides the same
        coordinator-KV exchange as the candidate lists, the FIRST
        non-empty id in process order becomes the request's canonical
        cross-host id, and every process emits one ``multihost.merge``
        span under it carrying all per-host walls — so one host's
        event stream (or N merged streams) reconstructs the cross-host
        waterfall (knn_tpu.obs.waterfall.stitch_multihost) with the
        straggler gap as explicit per-host wait segments."""
        tid_arr = np.frombuffer((trace_id or "").encode("ascii"),
                                dtype=np.uint8)
        lists = dcn_allgather_arrays(
            (d, gi, *extra, tid_arr, np.float64(local_wall_s)), tag=tag)
        walls = [float(rec[-1]) for rec in lists]
        gap = max(walls) - min(walls)
        straggler = int(np.argmax(walls))
        ctid = next(
            (t for t in (bytes(rec[-2].tobytes()).decode("ascii")
                         for rec in lists) if t), None)
        md, mi = merge_topk_host([r[0] for r in lists],
                                 [r[1] for r in lists], k)
        bytes_moved = crossover.merge_bytes(
            d.shape[0], k, self.process_count, "allgather")
        obs.gauge(_mn.MERGE_STRAGGLER_GAP).set(gap)
        obs.counter(_mn.MERGE_BYTES, level="dcn",
                    strategy="allgather").inc(bytes_moved)
        _update_report(
            hosts=self.process_count,
            process_index=self.process_index,
            transport="kv",
            dcn_merge=self.dcn_merge,
            dcn_merge_source=self.dcn_merge_source,
            dcn_merge_bytes=bytes_moved,
            straggler_gap_s=round(gap, 6),
            straggler_host=straggler,
            host_walls_s=[round(w, 6) for w in walls],
        )
        if t_start is not None:
            obs.record_span(
                "multihost.merge", ctid,
                time.perf_counter() - t_start,
                host=self.process_index,
                hosts=self.process_count,
                local_wall_s=round(local_wall_s, 6),
                walls_s=[round(w, 6) for w in walls],
                straggler_host=straggler,
                straggler_gap_s=round(gap, 6),
                tag=tag,
            )
        info = {
            "walls_s": walls,
            "straggler_gap_s": gap,
            "straggler_host": straggler,
            "trace_id": ctid,
            "bytes": bytes_moved,
            "extra": [rec[2:-2] for rec in lists],
        }
        return md, mi, info

    def search(self, queries, *, k: Optional[int] = None,
               return_sqrt: bool = False,
               trace_id: Optional[str] = None):
        """Global (distances, indices) [Q, k] over every host's rows —
        bitwise-identical to a single-host ``ShardedKNN.search`` of the
        concatenated database.  ``trace_id`` (minted here when absent
        and telemetry is on) is propagated through the DCN exchange so
        the cross-host waterfall stitches under one id."""
        k = self.k if k is None else k
        if trace_id is None:
            trace_id = obs.new_trace_id()
        t0 = time.perf_counter()
        d, i = self._local.search(queries, k=k)
        d = np.asarray(d)
        gi = np.asarray(i).astype(np.int64) + self.row_offset
        wall = time.perf_counter() - t0
        if self.process_count > 1:
            d, gi, _ = self._dcn_merge(
                d, gi, k, wall,
                f"r{self._instance}/search/{next(self._seq)}",
                trace_id=trace_id, t_start=t0)
        else:
            self._local_report(wall)
        if return_sqrt:
            from knn_tpu.ops.distance import metric_values

            d = np.asarray(metric_values(d, self.metric))
        return d, gi

    def search_certified(self, queries, trace_id: Optional[str] = None,
                         **kwargs):
        """Certified-exact global top-k: each host certifies the exact
        top-k of ITS row block (the full search_certified machinery —
        selector/precision/kernel knobs pass through), then the exact
        per-host lists merge over DCN.  The merge of exact disjoint-
        block top-k lists IS the exact global top-k, so the
        certification guarantee survives the tree; ``stats`` sums the
        per-host certification counters and carries the straggler
        gap (with the argmax host named)."""
        k = self.k
        if trace_id is None:
            trace_id = obs.new_trace_id()
        t0 = time.perf_counter()
        d, i, stats = self._local.search_certified(queries, **kwargs)
        wall = time.perf_counter() - t0
        gi = np.asarray(i).astype(np.int64) + self.row_offset
        if kwargs.get("return_distances") is False:
            raise ValueError(
                "MultiHostKNN.search_certified merges on distances; "
                "return_distances=False is not supported")
        d = np.asarray(d)
        if self.process_count > 1:
            # per-host certification counters ride the same exchange as
            # the candidate lists
            counts = np.asarray(
                [stats.get("fallback_queries", 0),
                 stats.get("certified", 0)], np.int64)
            d, gi, info = self._dcn_merge(
                d, gi, k, wall,
                f"r{self._instance}/certified/{next(self._seq)}",
                extra=(counts,), trace_id=trace_id, t_start=t0)
            stats = dict(stats)
            stats["per_host"] = {
                "fallback_queries": [int(e[0][0]) for e in info["extra"]],
                "certified": [int(e[0][1]) for e in info["extra"]],
                "walls_s": [round(w, 6) for w in info["walls_s"]],
            }
            stats["straggler_gap_s"] = round(info["straggler_gap_s"], 6)
            stats["straggler_host"] = info["straggler_host"]
        else:
            self._local_report(wall)
        return d, gi, stats

    # -- mutation refusals (knn_tpu.index, docs/INDEX.md) ---------------
    def _refuse_mutation(self, what: str):
        from knn_tpu.index.artifact import MutationUnsupportedError

        raise MutationUnsupportedError(
            f"{what}: MultiHostKNN spans {self.process_count} "
            f"process(es) with no write replication protocol — a "
            f"single-host write would silently serve stale results "
            f"from the other hosts; rebuild the replica from the "
            f"updated corpus, or serve a mutable corpus from a "
            f"single-host MutableIndex (docs/INDEX.md)")

    def insert(self, vectors=None, ids=None):
        """LOUD refusal — see :mod:`knn_tpu.index` for the single-host
        mutable path."""
        self._refuse_mutation("insert")

    def delete(self, ids=None):
        """LOUD refusal — see :mod:`knn_tpu.index` for the single-host
        mutable path."""
        self._refuse_mutation("delete")


__all__ = [
    "initialize",
    "global_mesh",
    "shard_across_hosts",
    "process_row_slice",
    "MultiHostKNN",
    "dcn_allgather_arrays",
    "merge_topk_host",
    "last_report",
]
