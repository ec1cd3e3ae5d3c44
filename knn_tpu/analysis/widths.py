"""ONE shared per-precision operand byte-width table.

The db-operand stream widths that ``analysis.vmem.DB_PARTS`` (the
launch budget), ``analysis.hbm``'s itemsize arithmetic (the placement
budget) and the IVF index's probed-bytes count read live HERE and the
consumers import them; tests/test_analysis.py pins the identity
(``is``, not ``==``) so a re-forked table can't reappear, and the
widths themselves against the arrays the kernel builds.

Jax-free on purpose: every consumer is a jax-free analysis module.

Layout provenance (what the kernels actually stream,
``ops.pallas_knn._bin_candidates``):

- ``bf16x3``  : precomputed bf16 hi+lo db parts, 2+2 B/elem.
- ``bf16x3f`` : one 3x-wide bf16 contraction, 6 B/elem.
- ``int8``    : per-row symmetric int8 rows, 1 B/elem.
- ``pq``      : one byte code per subspace, ``ceil(d / dsub)`` B/row
  (``ops.pq``); per-element width is shape-dependent, so consumers call
  :func:`db_row_bytes` instead of indexing ``DB_ELEM_BYTES``.
- ``highest``: the raw f32 rows, 4 B/elem.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

#: the padding grain of the feature axis, and the width of a dim chunk
#: wherever a row tile's whole padded width does not fit VMEM
#: (analysis.vmem.dim_chunking; mirror of ops.pallas_knn.DIM_CHUNK,
#: pinned by test)
DIM_CHUNK = 128


def lane_tiled(width: int) -> int:
    """The columns a placement lays rows of ``width`` columns out in:
    the next whole number of 128-column lane tiles (192 -> 256,
    960 -> 1,024; 128, 256 and 1,536 as they are).  A narrower f32
    array lies column-major on a TPU, and every program that reads it
    row-major copies the whole of it first
    (parallel.sharded.ShardedKNN)."""
    return int(width) + -int(width) % DIM_CHUNK


#: db stream width per element by kernel matmul precision.  "pq" is
#: deliberately ABSENT — its row width is ``ceil(d / dsub)`` bytes,
#: shape-dependent, served by :func:`db_row_bytes`.
DB_ELEM_BYTES: Dict[str, float] = {
    "bf16x3": 4, "bf16x3f": 6, "int8": 1, "highest": 4,
}

#: f32 sublane rows of the per-tile aux block: 8 rows of broadcast row
#: norms, and int8 stacks 8 broadcast scale rows under them (16).
#: PQ needs no db-side
#: norms (the per-query LUT carries the reconstruction's norm term),
#: so its aux block is the 8-row pad-fill carrier only.
AUX_ROWS: Dict[str, int] = {"int8": 16}
AUX_ROWS_DEFAULT = 8

#: query operand width per element: the int8 arm streams int8
#: queries.  PQ is absent here too: its query-side
#: operand is the per-query LUT (analysis.vmem prices its block).
QUERY_ELEM_BYTES: Dict[str, int] = {"int8": 1}
QUERY_ELEM_BYTES_DEFAULT = 4

#: db operand parts per precision for the VMEM launch model:
#: (n_parts, chunk_w, bytes/elem) — one db block of ONE part occupies
#: (tile_n, chunk_w) at the part dtype, per DIM_CHUNK columns of the
#: launch's dim chunk (analysis.vmem scales it by the resolved width).  "pq" is
#: absent: its chunk width is the shape-dependent code width
#: ``ceil(d / dsub)`` (analysis.vmem special-cases it via
#: :func:`db_row_bytes`).
DB_PARTS: Dict[str, Tuple[int, int, int]] = {
    "bf16x3": (2, DIM_CHUNK, 2),
    "bf16x3f": (1, 3 * DIM_CHUNK, 2),
    "int8": (1, DIM_CHUNK, 1),
    "highest": (1, DIM_CHUNK, 4),
}

#: f32 aux bytes beside each placed row (the hoisted squared norm) —
#: analysis.hbm's placement arithmetic
AUX_BYTES_PER_ROW = 4

#: PQ defaults: 4 dims per subspace and 256 codes (one byte) per
#: codebook — the classic 8-bit PQ point; at SIFT's d=128 a row is 32
#: code bytes = 1/16 the f32 row
PQ_DSUB_DEFAULT = 4
PQ_NCODES_DEFAULT = 256


def _ceil_div(a: int, b: int) -> int:
    return -(-int(a) // int(b))


def pq_nsub(d: int, dsub: Optional[int] = None) -> int:
    """Subspace count ``m = ceil(d / dsub)`` — also the PQ row's code
    bytes (one uint8 code per subspace)."""
    return _ceil_div(int(d), int(dsub or PQ_DSUB_DEFAULT))


def db_row_bytes(d: int, precision: str, *,
                 dsub: Optional[int] = None) -> int:
    """EXACT bytes one db row streams at this precision — the one
    entry point that covers the shape-dependent arm: PQ streams
    ``ceil(d / dsub)`` code bytes."""
    d = int(d)
    if precision == "pq":
        return pq_nsub(d, dsub)
    if precision not in DB_ELEM_BYTES:
        raise ValueError(
            f"precision {precision!r} not in "
            f"{sorted(DB_ELEM_BYTES) + ['pq']}")
    return int(d * DB_ELEM_BYTES[precision])


def aux_rows_for(precision: str) -> int:
    return AUX_ROWS.get(precision, AUX_ROWS_DEFAULT)


def query_elem_bytes(precision: str) -> int:
    return QUERY_ELEM_BYTES.get(precision, QUERY_ELEM_BYTES_DEFAULT)


def db_operand_nbytes(n: int, d: int, precision: str, *,
                      dsub: Optional[int] = None) -> Dict[str, int]:
    """Bytes of the db-side operands ONE full-db stream moves — the
    values array(s) plus the lane-major aux block — matching the arrays
    ``ops.pallas_knn._bin_candidates`` actually builds (the property
    test compares against their ``nbytes``).  The shape-dependent arm
    routes through :func:`db_row_bytes`: "pq" streams
    ``ceil(d / dsub)`` code bytes per row."""
    return {
        "db_values": int(n) * db_row_bytes(d, precision, dsub=dsub),
        "db_aux": int(n) * aux_rows_for(precision) * 4,
    }


__all__ = [
    "DIM_CHUNK", "DB_ELEM_BYTES", "AUX_ROWS", "AUX_ROWS_DEFAULT",
    "QUERY_ELEM_BYTES", "QUERY_ELEM_BYTES_DEFAULT", "DB_PARTS",
    "AUX_BYTES_PER_ROW", "PQ_DSUB_DEFAULT", "PQ_NCODES_DEFAULT",
    "pq_nsub", "db_row_bytes", "aux_rows_for", "query_elem_bytes",
    "db_operand_nbytes", "lane_tiled",
]
