"""The default sub-batch of a certified call: the rule of what the call
can see (the hbm.py and vmem.py discipline, for the host's share of a
batch).

``ShardedKNN.search_certified(selector="pallas")`` launches every
sub-batch's device program before it fetches the first
(``_certify_pallas``), so the host's work on sub-batch b (the copy down,
the unpack, the float64 repair of tie runs) runs while the device is on
b+1.  A call that is ONE batch has nothing to run it under: the device
finishes, then idles while the host repairs.  Cutting the call costs the
device whatever its program does once a LAUNCH and not once a query, so
the rule cuts only where that is next to nothing:

1. the kernel's row operands are resident (``ShardedKNN._row_operands``).
   ``per_call`` means every launch re-casts and re-norms the whole
   corpus before its kernel may start;
2. the placed rows are a whole number of 128-column lane tiles wide.
   Where they are not, XLA copies all rows into the kernel's layout in
   every launch (7 ms of 2.5M x 201, 12 ms of 1M x 960, PERF.md §6
   PR 39).  Since PR 44 ``ShardedKNN`` places the rows it lays out
   itself in whole lane tiles whatever their width, so who can still
   reach this is a PRE-PLACED ``jax.Array`` of another width, which is
   used as it is handed in;
3. the call has ``SUB_BATCHES`` sub-batches in it, each whole query
   blocks on every query shard of the mesh, of at least
   ``SUB_BATCH_MIN_ROWS`` queries and ``SUB_BATCH_MIN_BLOCKS`` such
   blocks (the sub-batches are one compiled shape, the last one padded
   to it: under one more block a shard for each sub-batch after the
   first than the uncut call would have run).

Everything else stays one batch, the program and the launch it always
was.

And a launch has to FIT: its own arrays grow with its queries (the
rescore gathers m+1 rows a query at the placed width, twice over:
``analysis.hbm.certified_query_bytes``), and at k = 1,024 over 1,024
columns 1,024 queries hold 10.2 GB beside 8.4 GB of rows and operands.
Whatever 1 to 3 gave, a launch whose queries do not fit what the chip
has left (``analysis.hbm.certified_launch_room``) is cut to the most
whole query blocks that do, the call into equal launches of one
compiled shape (``memory``).  At m = 130 no shape of the benchmark
comes within a third of its room, and every call is cut as it was.

Nothing sets any of this: no argument, no environment switch,
nothing in ``knn_tpu.tuning``.  An explicit ``batch_size`` wins.
"""

from __future__ import annotations

from typing import Optional, Tuple

from knn_tpu.analysis.widths import DIM_CHUNK

#: sub-batches a call is cut into where the rule cuts.  Timed on a v5e
#: at 4,096 queries against the uncut call (PERF.md section 6, PR 42;
#: ``sweep_qps``, one seed a column pair, the runs of a cut side by
#: side; one chip: 20 s windows; four chips: 50 s, a seed a run):
#:
#:     cut in              1          2 (2,048 rows)     4 (1,024 rows)
#:     5M x 128          49,614     53,094 / 53,521    57,502 / 57,596
#:     4 chips x 5M   53,040 / 53,166  57,228 / 56,962  61,306 / 61,330
#:     2.5M x 256        32,213     34,104 / 33,869    35,398 / 35,376
#:     (range search: the first pass alone is cut)
#:
#: and traced at 5M x 128 (four chips in brackets): the device idle
#: 27.0 / 20.5 / 15.6 (25.4 / 19.2 / 15.1) % of the window,
#: ``certified.exposed`` 15.5 / 9.4 / 6.2 ms a call, the kernel 41.22 /
#: 41.23 / 41.26 ms a call, the merge's collectives 0.29 / 0.32 / 0.36.  What a cut leaves exposed is the
#: LAST sub-batch's host work, a 1/n share, so 4 beats 2 by as much
#: again as 2 beats 1; what it costs is the host's own time, which
#: grows under a busy device (``rank_correct`` 12.3 / 16.6 / 15.4 ms a
#: call, ``dispatch`` + ``d2h`` 2.3 / 3.9 / 7.0), and a launch's fixed
#: part on the device (0.3 ms).  8 was not timed: a sub-batch of 512
#: rows is two query blocks, and each further launch costs 1.2 ms of
#: host dispatch and copy for 1/8 less of 18.
SUB_BATCHES = 4

#: the fewest queries a sub-batch may hold: a call under ``SUB_BATCHES``
#: of them (serving's buckets, every CPU test's call) is one batch.
#: 1,024 is the smallest sub-batch the chip has timed (above)
SUB_BATCH_MIN_ROWS = 1024

#: and the fewest query blocks it may hold on every query shard: 1,024
#: rows at the default block of 256 on one query shard, more where there
#: are more shards.  A cut call runs at most SUB_BATCHES - 1 blocks a
#: query shard more than the uncut one (the padding of its equal
#: shapes): 3 on 16 at the worst (4,097 queries), nothing at a multiple
#: of SUB_BATCHES blocks, and less the larger the call
SUB_BATCH_MIN_BLOCKS = 4

#: why a call's sub-batch is what it is, as its event, its ``stats`` and
#: ``knn_tpu_certified_sub_batch_calls_total{why}`` say it: cut by the
#: rule, or one batch because of 1, 2 or 3 above, or the caller's own,
#: or cut to what the chip has room for
REASONS = ("resident", "per_call_operands", "layout_copy", "small",
           "explicit", "memory")


def certified_sub_batch(
    queries: int, *, batch_size: Optional[int], operands: str, width: int,
    block_q: int, query_shards: int, query_bytes: int = 0,
    room_bytes: int = 0,
) -> Tuple[int, str]:
    """``(rows, why)``: the queries a sub-batch of one certified call of
    ``queries`` queries holds, and the entry of ``REASONS`` that says
    why.  ``operands`` is where the kernel's row operands come from
    (``resident`` / ``per_call``), ``width`` the placed rows' columns,
    ``block_q`` the kernel's query block and ``query_shards`` the mesh's
    query axis.  Where the rule cuts, ``rows`` is the call's
    ``SUB_BATCHES``-th part rounded up to whole query blocks on every
    query shard (the last sub-batch is padded to it: one compiled shape
    a call); everywhere else it is ``queries``.

    ``query_bytes`` (what one query of a launch holds on its chip,
    ``analysis.hbm.certified_query_bytes``) and ``room_bytes`` (what a
    chip has left, ``analysis.hbm.certified_launch_room``; 0: no bound)
    hold that answer to the chip's memory: ``rows`` queries are ``rows /
    query_shards`` a chip, and where they do not fit the call is cut
    into the fewest equal launches of whole query blocks that do
    (``memory``; never under one block a query shard)."""
    if batch_size is not None:
        return int(batch_size), "explicit"
    grain = block_q * query_shards
    rows, why = _by_launch_cost(queries, operands, width, grain)
    fits = room_bytes // max(1, query_bytes) * query_shards
    if room_bytes and rows > max(grain, fits):
        launches = -(-queries // max(grain, fits // grain * grain))
        return -(-queries // (launches * grain)) * grain, "memory"
    return rows, why


def _by_launch_cost(queries: int, operands: str, width: int, grain: int
                    ) -> Tuple[int, str]:
    """Rules 1 to 3 of the module docstring."""
    if operands != "resident":
        return queries, "per_call_operands"
    if width % DIM_CHUNK:  # the feature axis's padding grain, 128
        return queries, "layout_copy"
    least = max(SUB_BATCH_MIN_ROWS, SUB_BATCH_MIN_BLOCKS * grain)
    if queries < SUB_BATCHES * least:
        return queries, "small"
    return -(-queries // (SUB_BATCHES * grain)) * grain, "resident"


__all__ = ["REASONS", "SUB_BATCHES", "SUB_BATCH_MIN_BLOCKS",
           "SUB_BATCH_MIN_ROWS", "certified_sub_batch"]
