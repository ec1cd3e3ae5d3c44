"""Analytic HBM byte accounting for database placements — the budget
side of the host-RAM shard tier (the vmem.py discipline, one level up
the memory hierarchy).

``analysis.vmem`` prices a kernel launch's VMEM footprint; nothing
priced the PLACEMENT's HBM footprint, yet that is what decides whether
a corpus fits one serving replica at all: ``ShardedKNN`` places the
full padded f32 database (plus, lazily, the int8 quantized copy), so
the reachable corpus was capped at the mesh's HBM.  This module is the
jax-free arithmetic the host-RAM tier plans against:

- :func:`placement_bytes` — bytes one placed database occupies across
  the mesh (values + the per-row norm/scale aux the search programs
  keep warm), mirroring what ``ShardedKNN.__init__`` actually places;
- :func:`plan_segments` — partition ``n_rows`` into equal row segments
  whose per-host share fits a byte budget, each a multiple of the db
  shard count so every sweep reuses ONE compiled program shape (the
  flat-per-sweep-latency contract tests pin).

Tests pin ``plan_segments``'s sweep count against the byte model and
the boundary cases (corpus exactly at, one row over, many-x over the
budget) in tests/test_hosttier.py.
"""

from __future__ import annotations

from typing import List, Tuple

from knn_tpu.analysis import widths as _widths

#: f32 aux bytes the placement keeps beside each row (the squared row
#: norm the distance programs hoist); the int8 tier would add scales,
#: but the host-RAM tier streams the f32 placement.  A view of the ONE
#: shared width table (analysis.widths).
AUX_BYTES_PER_ROW = _widths.AUX_BYTES_PER_ROW


def placement_bytes(n_rows: int, dim: int, itemsize: int = 4) -> int:
    """Total HBM bytes a ``[n_rows, dim]`` placement occupies across
    the mesh: the value matrix at ``itemsize`` bytes/element plus the
    per-row aux column."""
    n_rows, dim = int(n_rows), int(dim)
    if n_rows < 0 or dim <= 0:
        raise ValueError(f"bad placement shape ({n_rows}, {dim})")
    return n_rows * (dim * int(itemsize) + AUX_BYTES_PER_ROW)


def rows_for_budget(budget_bytes: int, dim: int, *, itemsize: int = 4,
                    hosts: int = 1, shard_multiple: int = 1) -> int:
    """The largest row count whose PER-HOST placement share fits
    ``budget_bytes``, rounded down to ``shard_multiple`` (the db shard
    count — a segment must divide evenly across the db axis)."""
    if budget_bytes <= 0:
        raise ValueError(f"budget_bytes must be > 0, got {budget_bytes}")
    per_row = dim * int(itemsize) + AUX_BYTES_PER_ROW
    rows = (int(budget_bytes) * max(1, int(hosts))) // per_row
    return (rows // shard_multiple) * shard_multiple


def plan_segments(
    n_rows: int, dim: int, budget_bytes: int, *, itemsize: int = 4,
    hosts: int = 1, shard_multiple: int = 1,
) -> List[Tuple[int, int]]:
    """``[(lo, hi), ...]`` row segments covering ``[0, n_rows)``, every
    segment's per-host placed bytes within ``budget_bytes`` and every
    segment the SAME padded width (``segment_rows``; the tail is ragged
    in valid rows but pads to the same shape so all sweeps share one
    compiled program).  Raises when the budget cannot hold even one
    ``shard_multiple`` of rows — a budget that small cannot stream."""
    n_rows = int(n_rows)
    if n_rows <= 0:
        raise ValueError(f"n_rows must be > 0, got {n_rows}")
    seg = rows_for_budget(budget_bytes, dim, itemsize=itemsize,
                          hosts=hosts, shard_multiple=shard_multiple)
    if seg < shard_multiple or seg < 1:
        raise ValueError(
            f"hbm budget {budget_bytes} B/host cannot hold even "
            f"{shard_multiple} rows of dim {dim} at {itemsize} B/elem; "
            f"raise the budget or use fewer db shards")
    seg = min(seg, -(-n_rows // shard_multiple) * shard_multiple)
    return [(lo, min(lo + seg, n_rows)) for lo in range(0, n_rows, seg)]


def n_sweeps(n_rows: int, dim: int, budget_bytes: int, *,
             itemsize: int = 4, hosts: int = 1,
             shard_multiple: int = 1) -> int:
    """Sweep count the plan implies — what tests pin the executed sweep
    counter against."""
    return len(plan_segments(n_rows, dim, budget_bytes, itemsize=itemsize,
                             hosts=hosts, shard_multiple=shard_multiple))


def query_block_bytes(n_rows: int, dim: int, itemsize: int = 4) -> int:
    """Host->device bytes one ``[n_rows, dim]`` QUERY block transfers —
    no aux column (queries carry no placed row norms), otherwise the
    :func:`placement_bytes` arithmetic."""
    n_rows, dim = int(n_rows), int(dim)
    if n_rows < 0 or dim <= 0:
        raise ValueError(f"bad query block shape ({n_rows}, {dim})")
    return n_rows * dim * int(itemsize)


def superblock_rows_for_budget(budget_bytes: int, dim: int, *,
                               itemsize: int = 4,
                               query_multiple: int = 1) -> int:
    """The largest query-superblock row count whose h2d block fits
    ``budget_bytes``, rounded down to ``query_multiple`` (the query
    shard count — a placed block must divide evenly across the query
    axis).  The query-side mirror of :func:`rows_for_budget`."""
    if budget_bytes <= 0:
        raise ValueError(f"budget_bytes must be > 0, got {budget_bytes}")
    rows = int(budget_bytes) // (int(dim) * int(itemsize))
    return (rows // query_multiple) * query_multiple


def plan_superblocks(
    n_a: int, dim: int, budget_bytes: int, *, itemsize: int = 4,
    query_multiple: int = 1,
) -> List[Tuple[int, int]]:
    """``[(lo, hi), ...]`` query-superblock extents covering
    ``[0, n_a)`` — the join engine's query-side :func:`plan_segments`:
    every superblock the SAME padded width (the ragged tail pads up, so
    all blocks share one compiled program shape).  Raises when the
    budget cannot hold even ``query_multiple`` query rows."""
    n_a = int(n_a)
    if n_a <= 0:
        raise ValueError(f"n_a must be > 0, got {n_a}")
    sb = superblock_rows_for_budget(budget_bytes, dim, itemsize=itemsize,
                                    query_multiple=query_multiple)
    if sb < query_multiple or sb < 1:
        raise ValueError(
            f"query budget {budget_bytes} B cannot hold even "
            f"{query_multiple} query rows of dim {dim} at {itemsize} "
            f"B/elem; raise the budget or use fewer query shards")
    sb = min(sb, -(-n_a // query_multiple) * query_multiple)
    return [(lo, min(lo + sb, n_a)) for lo in range(0, n_a, sb)]


def n_superblocks(n_a: int, dim: int, budget_bytes: int, *,
                  itemsize: int = 4, query_multiple: int = 1) -> int:
    """Superblock count the plan implies — what tests pin the executed
    join superblock counter against."""
    return len(plan_superblocks(n_a, dim, budget_bytes, itemsize=itemsize,
                                query_multiple=query_multiple))


def plan_join(
    n_a: int, n_b: int, dim: int, *, superblock_rows: int,
    db_segment_rows: int = 0, itemsize: int = 4,
) -> dict:
    """The bulk kNN-join sweep-nesting plan: which loop goes OUTER when
    both the query set A and the corpus B stream from host RAM.

    With ``s = ceil(n_a / superblock_rows)`` superblocks and
    ``g = ceil(n_b / db_segment_rows)`` db segments
    (``db_segment_rows = 0`` means B is device-resident, ``g = 1`` and
    its stream bytes are 0 — placed once at construction):

    - **query_major** (superblocks outer): each superblock transfers
      h2d once, each db segment re-streams once PER superblock —
      ``h2d = A_bytes + s * B_bytes``.
    - **db_major** (db segments outer): each db segment transfers h2d
      once and serves every superblock while resident, each superblock
      re-streams once per segment — ``h2d = B_bytes + g * A_bytes``.

    The returned ``order`` minimizes total h2d bytes (ties prefer
    query_major — it needs no per-superblock top-k carry).  A resident
    B is always query_major.  Dispatch count is ``s * g`` either way;
    only the transfer schedule differs."""
    n_a, n_b = int(n_a), int(n_b)
    sb = int(superblock_rows)
    if n_a <= 0 or n_b <= 0 or sb <= 0:
        raise ValueError(
            f"bad join shape n_a={n_a} n_b={n_b} "
            f"superblock_rows={superblock_rows}")
    s = -(-n_a // sb)
    a_bytes = query_block_bytes(n_a, dim, itemsize)
    seg = int(db_segment_rows)
    if seg <= 0:  # resident corpus: placed once, no per-sweep stream
        g = 1
        b_bytes = 0
    else:
        g = -(-n_b // seg)
        b_bytes = placement_bytes(n_b, dim, itemsize)
    qm_bytes = a_bytes + s * b_bytes
    dm_bytes = b_bytes + g * a_bytes
    order = "db_major" if (seg > 0 and dm_bytes < qm_bytes) \
        else "query_major"
    return {
        "order": order,
        "superblocks": s,
        "db_segments": g,
        "dispatches": s * g,
        "h2d_bytes": {"query_major": qm_bytes, "db_major": dm_bytes},
        "a_bytes": a_bytes,
        "b_stream_bytes": b_bytes,
    }


#: what the largest device program loaded beside RESIDENT row operands
#: may set aside for its temporaries, as a multiple of one chip's placed
#: rows (their own bytes, ``nbytes``), by how the rows lie.  Both are
#: readings of XLA's ``memory_analysis`` of the programs a benchmark
#: cell loads, compiled for a v5e (the chip's ``bytes_reserved`` read
#: the same numbers to the megabyte where both were taken: PERF.md
#: section 4 "Memory").
#:
#: Rows in whole 128-column lane tiles (every placement ``ShardedKNN``
#: lays out itself since PR 44, and a pre-placed array of such a width):
#: no program copies them.  ``python scripts/aot_compile_check.py
#: --temporaries [--shape S]`` prints the readings (PR 49, jax 0.9.0 /
#: libtpu 0.0.34).  What a default call loads beside resident operands:
#: the sub-batch's certified (or vote) program at 1,024 queries, 0.09
#: to 0.54 over the benchmark's shapes, and the repair's exact
#: re-select, under 0.001 since its scan reads the rows where they lie
#: (PR 50; its padded copy of the rows read 1.02 to 1.05).  At the two
#: shapes this factor decides for, ``gist`` (1M x 1,024 placed) /
#: ``imagenet768`` (1,281,167 x 768; every narrower cell fits at 2.7
#: too), it covers every other form of the program as well: handed its
#: operands at an uncut 4,096 queries 1.089 / 0.339, forming them in
#: the call 1.175 / 1.221 (1.056 / 1.063 at 1,024 queries).  A quarter
#: over the rows is the least twentieth above all of those.  It bounds
#: no launch: a program's temporaries grow with its QUERIES (the
#: candidates are queries x rows / 64), so an uncut 4,096-query launch
#: reads 1.38 over 5M x 128 and 2.18 over 500K x 1,536; those fit
#: beside the operands as they did at 2.7 (the spare eighth, and
#: ``bytes_reserved`` once loaded).  The exact route is ROADMAP D4.
LANE_TILED_TEMP_FACTOR = 1.25

#: Rows of any other width (a PRE-PLACED array, used as handed in;
#: ``sub_batch: layout_copy``) lie column-major on the v5e and every
#: program reading them copies and pads all of them: 2.61 at the most,
#: the repair's exact re-select over 2.5M x 201 (a row-major copy, 201
#: columns in 256 lanes, and a copy padded to the exact path's tile);
#: 2.19 the re-select at 1M x 960; 1.64 the certified program there
#: once it is handed its operands (PR 39's readings, from before the
#: placement widened such rows itself).
ROWS_PROGRAM_TEMP_FACTOR = 2.7

#: the share of the device's memory the resident row operands may fill
#: with everything else counted: an eighth stays spare, the margin
#: ``analysis.vmem`` keeps of VMEM
RESIDENT_FILL = 7 / 8


def row_operand_bytes(rows_p: int, dim_p: int, with_lo: bool) -> int:
    """Bytes ONE chip keeps for the "bf16x3" kernel's resident row
    operands (``ShardedKNN._row_operands``): the bf16 high half of its
    ``rows_p x dim_p`` padded rows, the low half ``with_lo``, and the
    float32 row norms."""
    return int(rows_p) * (int(dim_p) * 2 * (2 if with_lo else 1) + 4)


def program_temp_factor(width: int) -> float:
    """The multiple of one chip's placed rows that the largest program
    beside them sets aside, read off the rows' placed ``width``:
    ``LANE_TILED_TEMP_FACTOR`` where it is whole 128-column lane tiles,
    ``ROWS_PROGRAM_TEMP_FACTOR`` where the programs still copy the rows
    into such tiles themselves."""
    if _widths.lane_tiled(width) == int(width):
        return LANE_TILED_TEMP_FACTOR
    return ROWS_PROGRAM_TEMP_FACTOR


def resident_operands_room(form_bytes: int, placed_bytes: int,
                           memory_stats: dict, *, width: int) -> dict:
    """The terms of the rule that decides whether one chip KEEPS
    ``form_bytes`` of row operands beside its ``placed_bytes`` of rows
    ``width`` columns wide, by the device's own ``memory_stats()``:
    ``held``, what the client holds there now (``bytes_in_use``: the
    rows and whatever else the process placed), ``form_bytes``, and
    ``temporaries``, those of the largest device program, must fit
    ``limit`` = ``RESIDENT_FILL`` of ``bytes_limit``; ``kept`` says
    whether they do.  The runtime sets aside ONE region for the loaded
    programs' temporaries, as large as the largest needs
    (``bytes_reserved``; it is 0 until a program has run), so the
    temporaries are that reading or, where it is larger,
    :func:`program_temp_factor` of the width times the placed rows.  A
    backend that reports no ``bytes_limit`` (the CPU) has no such
    bound: ``limit`` 0 and ``kept``."""
    bytes_limit = int(memory_stats.get("bytes_limit") or 0)
    limit = int(RESIDENT_FILL * bytes_limit)
    held = max(int(memory_stats.get("bytes_in_use") or 0), int(placed_bytes))
    temporaries = max(int(memory_stats.get("bytes_reserved") or 0),
                      int(program_temp_factor(width) * placed_bytes))
    return {"held": held, "form_bytes": int(form_bytes),
            "temporaries": temporaries, "limit": limit,
            "kept": (not bytes_limit
                     or held + form_bytes + temporaries <= limit)}


def resident_operands_fit(form_bytes: int, placed_bytes: int,
                          memory_stats: dict, *, width: int) -> bool:
    """Whether the rule of :func:`resident_operands_room` keeps the
    operands."""
    return resident_operands_room(
        form_bytes, placed_bytes, memory_stats, width=width)["kept"]


def certified_query_bytes(m: int, width: int, select_width: int,
                          packed_columns: int) -> int:
    """Bytes ONE query of a certified launch holds on its chip, whatever
    the corpus: what the sub-batch rule multiplies by a launch's queries
    (``analysis.subbatch.certified_sub_batch``).  Three terms, each an
    upper reading of what XLA's ``memory_analysis`` gives for the
    compiled program (they are not all live at once):

    - the rescore's rows: the m+1 selected rows gathered at the placed
      ``width`` in float32, and a second array of that size for the
      differences (``ops.pallas_knn.local_select_rescore``; the traces
      show ``f32[queries x (m+1), width]`` and a copy of it).  At m =
      130 and 1,024 columns 1.07 MB, at m = 1,152 9.45 MB, which is the
      whole of what a launch of k = 1,024 holds: compiled for a v5e at
      1M x 1,024, 512 queries set aside 4.86 GB, 9.50 MB a query
      (``scripts/aot_compile_check.py --temporaries --shape knnlm1m``;
      PERF.md section 6, PR 55);
    - the candidates: the kernel's scores and indices at
      ``select_width`` columns, and as much again for the select's own
      operands;
    - the packed answer, ``packed_columns`` int32 words
      (``_pallas_certified_program``), which stays until it is
      fetched."""
    rescore = 2 * (int(m) + 1) * _widths.lane_tiled(int(width)) * 4
    candidates = 4 * int(select_width) * 4
    return rescore + candidates + int(packed_columns) * 4


def certified_launch_room(room: dict) -> int:
    """What one chip has left for a certified launch's own arrays beside
    its rows and their row operands, from the terms
    :func:`resident_operands_room` read: ``limit`` less ``held`` less
    ``form_bytes``.  The operands count whether they are kept (they are
    held) or formed in every call (they are the launch's first
    temporaries).  0, no bound, where the backend reports no limit (the
    CPU) and where nothing is left (a device that refused the operands
    for want of room: how its calls are cut is the sub-batch rule's
    first three lines, as it was)."""
    if not room.get("limit"):
        return 0
    return max(0, room["limit"] - room["held"] - room["form_bytes"])


__all__ = [
    "AUX_BYTES_PER_ROW",
    "LANE_TILED_TEMP_FACTOR",
    "ROWS_PROGRAM_TEMP_FACTOR",
    "RESIDENT_FILL",
    "row_operand_bytes",
    "program_temp_factor",
    "resident_operands_room",
    "resident_operands_fit",
    "certified_query_bytes",
    "certified_launch_room",
    "placement_bytes",
    "rows_for_budget",
    "plan_segments",
    "n_sweeps",
    "query_block_bytes",
    "superblock_rows_for_budget",
    "plan_superblocks",
    "n_superblocks",
    "plan_join",
]
