"""Per-launch VMEM model of the Pallas kernels.

VMEM is the resource that decides whether a config RUNS AT ALL: an
over-VMEM knob combination is refused by Mosaic at compile time.  This
jax-free module is the ONE home of that arithmetic:

- ``ops.pallas_knn`` sizes its ``vmem_limit_bytes`` request from
  :func:`kernel_bytes` / :func:`limit_bytes`;
- the ``vmem-budget`` checker (knn_tpu.analysis.check_vmem) holds the
  default knob set to the target device's budget.

ONE rule decides who has the last word (:func:`calibrated`): where the
model was fitted to the compiler it REFUSES what cannot fit — the
kernel raises with the knobs to change before Mosaic is asked;
everywhere else the model only describes, the kernel requests the
device's whole VMEM, and Mosaic decides.

Calibration: the buffers the kernel declares (pipelined operand and
output blocks, scratch) are exact; what Mosaic keeps live ON TOP of
them is not declared anywhere, so the score-tile multipliers below were
fitted to the scoped-VMEM need Mosaic itself reported (libtpu 0.0.34,
v5e, deviceless AOT — ``scripts/aot_compile_check.py --probe``) for the
flagship bf16x3 arm at the three benchmark shapes,
both block_q and both tile sizes, all three kernels.  There the model
tracked the compiler within +7%/-1% at every probed geometry (tiled
GIST bq256: 82.5 MiB modeled, 81.94 reported; streaming SIFT bq256:
126.75 / 126.55), and :func:`limit_bytes` adds an eighth for that
error.  It is NOT calibrated anywhere else.  Probed the same way at
bq256 (SIFT tiled / GIST tiled / SIFT streaming, MiB): "highest" needs
18.46 / 66.29 / 111.19 where the model says 50.5 / 82.5 / 126.75,
"bf16x3f" 26.02 / 73.88 / 119.19 against 58.5 / 90.5 / 134.75, "int8"
<=8 / 55.66 / 100.06 against 39.56 / 71.56 / 115.81 — an upper bound,
by up to 2.7x; and the "pq" one-hot expansion is not modeled at all.

HOW A ROW TILE IS CUT is part of the launch geometry and has its one
rule here (:func:`row_blocking`, PR 46; :func:`dim_chunking` since PR
32).  Under the tiled kernel a tile is never cut by columns: every grid
step multiplies the WHOLE padded width in one product, so no partial
product outlives a step.  Where the tile's row blocks at that width fit
the device beside everything else the launch keeps, with
:func:`limit_bytes`' eighth to spare, the tile is ONE step (no scratch,
the select in the matmul's own step: 128 to 512 columns at the default
tile and query block); where they do not (``gist1m``'s 1,024 columns,
``openai500k``'s 1,536) the grid's third axis walks ROW BLOCKS of the
tile, the largest that divides the tile into whole 128-row groups and
fits, and only the bin-select's running arrays (``select_state``, 640
KiB at a query block of 256) are carried between steps.  The kernel
and :func:`launch_estimate` both ask the rule.  Until
PR 46 such a tile was cut into ``DIM_CHUNK``-column chunks whose
``[block_q, tile_n]`` partial product (16 MiB) was read, added to and
stored back at every step: 25.5 us a 128-column pass-set at ``gist1m``
against 16.7 in the one-step form (root PERF.md section 6, PR 45).
The one-step geometries were probed like the rest (bf16x3, bq256, tile
16384; the least limit that compiles, by bisection, in MiB against the
model): 256 columns 59 / 66.75, 384 79 / 83.0, 512 99 / 99.25, beside
48 / 50.5 at 128 — the model over by 0.3% to 13%, 7.75 MiB at the
most, inside the eighth (8.3) its limit adds.  It was NOT re-fitted:
what Mosaic keeps beside the declared buffers is 1.8, 1.5, 1.7 and 2.0
score tiles at 128, 256, 384 and 512 columns, no function of the width
a multiplier could follow, and ``live = 2`` bounds all four.  (Under
its need Mosaic may schedule otherwise and name another size: 98.32
MiB at a limit of 58 for the 256-column chunk, which compiles from 59
up — the probe bisects on the limit, it does not trust the size.)
With the low row half dropped (``terms`` "hh": one part) the model is
an upper bound by far: 18 reported against 50.75 at 256 columns, 70
against 100.25 at 1,024.  The row-cut geometries' readings are in
:func:`row_blocking`.  The rule is the TILED kernel's alone: the
streaming and fused kernels hold a whole query block, both row buffers
and every tile's output at once, so one wide chunk that the tiled
kernel has room for overruns them (1M rows of 512 columns at bq128:
120.2 and 124.4 MiB modeled, and Mosaic refuses both; of 640, 136 and
140) where their 128-column chunks compile at about 80.  They keep
``DIM_CHUNK`` columns at every width, the parent's programs.

Geometry constants mirror ``ops.pallas_knn`` (TILE_N/BLOCK_Q/BIN_W/
DIM_CHUNK/MAX_CARRY_DEPTH), pinned by tests/test_analysis.py.  The
per-precision operand widths live in the ONE shared table
:mod:`knn_tpu.analysis.widths` (this module's ``DB_PARTS``/``AUX_ROWS``
are ``is``-identity views of it, shared with ``analysis.hbm``).

Capacity provenance: TPU v2/v3 cores carry ~16 MiB of VMEM; v4 and
every later announced generation carry 128 MiB (Mosaic's own refusal on
a v5e reads "Used 133.75M of 128.00M vmem").  A TPU kind that is not
in the table is an error, not a default; CPU backends have no VMEM and
are never budget-checked.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from knn_tpu.analysis import widths as _widths

#: mirrors of ops.pallas_knn geometry constants (pinned by test)
TILE_N_DEFAULT = 16384
BLOCK_Q_DEFAULT = 128
BIN_W = 128
DIM_CHUNK = _widths.DIM_CHUNK
MAX_CARRY_DEPTH = 8
SURVIVORS_GROUPED_DEFAULT = 2

#: db operand parts per precision: (n_parts, chunk_w, bytes/elem) —
#: what one db block of ONE part occupies ((tile_n, chunk_w) at the
#: part dtype); a VIEW of the shared width table
#: (knn_tpu.analysis.widths.DB_PARTS).  "pq" is absent: its chunk
#: width is the shape-dependent code width ``ceil(d / dsub)``
#: (launch_estimate special-cases it).
DB_PARTS = _widths.DB_PARTS

#: f32 sublane rows of the aux (norms / norms+scales) block
AUX_ROWS = _widths.AUX_ROWS
AUX_ROWS_DEFAULT = _widths.AUX_ROWS_DEFAULT

#: per-device-kind VMEM capacity in bytes (see module docstring)
MIB = 1024 * 1024
VMEM_BYTES_BY_KIND: Dict[str, int] = {
    "TPU v2": 16 * MIB,
    "TPU v3": 16 * MIB,
    "TPU v4": 128 * MIB,
    "TPU v4i": 128 * MIB,
    "TPU v5 lite": 128 * MIB,
    "TPU v5e": 128 * MIB,
    "TPU v5": 128 * MIB,
    "TPU v5p": 128 * MIB,
    "TPU v6 lite": 128 * MIB,
    "TPU v6e": 128 * MIB,
    "TPU v6": 128 * MIB,
    "TPU v6p": 128 * MIB,
    "TPU v7": 128 * MIB,
    "TPU v7x": 128 * MIB,
}

#: the repo's target hardware (every headline number is v5e) and the
#: headline problem shape (SIFT1M) the static checker prices at
TARGET_DEVICE_KIND = "TPU v5e"
HEADLINE_SHAPE = {"n": 1_000_000, "d": 128, "k": 100, "margin": 28}


def budget_for(device_kind: Optional[str],
               backend: Optional[str] = None) -> Optional[int]:
    """VMEM bytes of a device kind; None when there is no VMEM to
    budget (cpu / interpret mode) — nothing is refused there on a
    number that doesn't exist.  An explicit TPU ``device_kind`` wins
    over ``backend``: a caller modeling a specific chip gets that
    chip's budget even when it runs in CPU interpret mode.  A TPU whose kind
    is not in the table raises: a device the table does not know is an
    error, not a default."""
    if device_kind in VMEM_BYTES_BY_KIND:
        return VMEM_BYTES_BY_KIND[device_kind]
    if str(device_kind or "").startswith("TPU") or (
            device_kind is None and str(backend or "").lower() == "tpu"):
        raise ValueError(
            f"device kind {device_kind!r} is not in "
            f"analysis.vmem.VMEM_BYTES_BY_KIND; add its VMEM size (with "
            f"its source) before budgeting kernels for it")
    return None


def calibrated(precision: Optional[str]) -> bool:
    """Whether the model was fitted to the compiler for this arm (module
    docstring) — the ONE switch between "the model refuses" and "Mosaic
    decides", shared by the kernel and the ``vmem-budget`` checker."""
    return (precision or "bf16x3") == "bf16x3"


def limit_bytes(estimate_bytes: int, budget_bytes: int) -> int:
    """The scoped-VMEM limit a :func:`calibrated` launch whose estimate
    is within the budget requests: the estimate plus an eighth for the
    model's error (the compiler's need ran up to 1% over it), capped at
    the device (no reserve is held back: Mosaic on a v5e compiled the
    streaming kernel at a reported 126.55 MiB under a 128 MiB limit),
    floored at 64 MiB because the fit covers the benchmark geometries
    (31 MiB and up), not the small ones."""
    want = int(estimate_bytes) + int(estimate_bytes) // 8
    return min(int(budget_bytes), max(64 * MIB, want))


def _ceil_div(a: int, b: int) -> int:
    return -(-int(a) // int(b))


def _geometry(n: int, d: int, precision: str, kernel: str,
              tile_n: Optional[int], block_q: Optional[int],
              survivors: Optional[int]):
    if precision != "pq" and precision not in DB_PARTS:
        raise ValueError(
            f"precision {precision!r} not in {sorted(DB_PARTS) + ['pq']}")
    tile = int(tile_n or TILE_N_DEFAULT)
    # the kernel pads the db to a tile multiple; an oversize tile caps
    # at the padded row count
    tile = min(tile, max(BIN_W, _ceil_div(n, BIN_W) * BIN_W))
    bq = int(block_q or BLOCK_Q_DEFAULT)
    n_tiles = _ceil_div(n, tile)
    dim_p = _ceil_div(d, DIM_CHUNK) * DIM_CHUNK
    out_w = int(survivors or SURVIVORS_GROUPED_DEFAULT) * BIN_W
    bound_w = BIN_W
    return tile, bq, n_tiles, dim_p, out_w, bound_w


def kernel_bytes(
    *, kernel: str, block_q: int, tile_n: int, n_tiles: int, nd: int,
    out_w: int, bound_w: int, db_block: int, aux_rows: int,
    q_block: int, q_extra: int = 0, carry_depth: int = 0,
    row_block: Optional[int] = None, dim_padded: int = 0,
) -> Dict[str, int]:
    """Per-buffer VMEM bytes of ONE launch from the kernel's RESOLVED
    geometry — what ``ops.pallas_knn`` sizes its scoped-VMEM request
    from, and what :func:`launch_estimate` prices a knob set with.

    ``db_block`` is what one grid step holds of the rows across all
    their parts (a whole tile, or one ``row_block`` of it), ``q_block``
    one query operand block (tiled: the whole padded width, or one dim
    chunk where a caller handed the launch a narrower one;
    streaming/fused: the full-dim block), ``q_extra`` the quantized
    arms' query-scale block, ``carry_depth`` the fused arm's armed
    carry depth (0 = disarmed), ``row_block`` the rows of a tiled
    launch's step (None: the whole tile) and ``dim_padded`` the columns
    such a step multiplies (read where the tile is cut by rows alone).

    - **tiled**: every grid-mapped operand and output block is
      double-buffered by the Pallas pipeline; a tile cut by rows keeps
      the select's running arrays (``select_state``: the survivors + 1
      value and survivors index arrays of ``[block_q, 128]``) in a
      scratch that lives once, as the multi-chunk accumulator of a
      launch handed a dim chunk does.
    - **streaming/fused**: the kernel OWNS its db double buffering (two
      scratch slots per part + aux); the full-width candidate output
      block is a pipelined (double-buffered) output all the same.
    - ``score_tiles``: the [block_q, rows of a step] f32 tiles Mosaic
      keeps live around the select (partial dots, the score, one
      temporary) — 2 at one dim chunk, 3 when chunks accumulate; fitted
      to the compiler's reported need (module docstring), not declared
      by the kernel.
    - ``row_part_live``: a step of a tile cut by rows multiplies 1,024
      columns and more in one product, and beside the pipeline's
      buffers Mosaic keeps what one more bf16 copy of a row part's
      block would take (``[row_block, dim_padded]``: fitted like the
      score tiles, :func:`row_blocking`)."""
    rows = int(row_block or tile_n)
    score = block_q * rows * 4
    aux_block = aux_rows * rows * 4
    live = 2 if nd == 1 else 3
    if kernel == "tiled":
        return {
            "db_blocks_x2": 2 * db_block,
            "aux_x2": 2 * aux_block,
            "query_x2": 2 * (q_block + q_extra),
            "outputs_x2": 2 * block_q * (2 * out_w + bound_w) * 4,
            "score_tiles": live * score,
            "accum_scratch": score if nd > 1 else 0,
            "select_state": (block_q * (2 * out_w + bound_w) * 4
                             if rows < tile_n else 0),
            "row_part_live": (rows * int(dim_padded) * 2
                              if rows < tile_n else 0),
        }
    if kernel not in ("streaming", "fused"):
        raise ValueError(
            f"kernel {kernel!r} not in ('tiled', 'streaming', 'fused')")
    return {
        "outputs_fullwidth_x2":
            2 * block_q * n_tiles * (2 * out_w + bound_w) * 4,
        "stream_scratch_x2": 2 * (db_block + aux_block),
        "query_x2": 2 * (q_block + q_extra),
        "score_tiles": live * score,
        # the early-out's lane minima / skip-branch temporaries
        # (fitted: half a score tile) plus the per-lane f32 carry
        "fused_select": (score // 2 + block_q * carry_depth * BIN_W * 4
                         if kernel == "fused" else 0),
    }


def dim_chunking(
    dim_padded: int, *, kernel: str = "tiled", precision: str = "bf16x3",
) -> Tuple[int, int]:
    """``(chunk_w, nd)``: the columns of one dim chunk and the chunks a
    row tile's WIDTH is cut into.  The tiled kernel never cuts it (one
    product over the whole padded width a step; a tile too large for
    VMEM at that width is cut by rows, :func:`row_blocking`); the
    streaming and fused kernels keep ``DIM_CHUNK`` columns at every
    width (module docstring: what the tiled kernel has room for, they
    do not).  ``pq`` has no chunk loop and a width of 128 or less is
    one chunk already.  No knob."""
    dim_padded = int(dim_padded)
    if dim_padded % DIM_CHUNK:
        raise ValueError(
            f"dim_padded={dim_padded} is no multiple of {DIM_CHUNK}")
    if precision == "pq" or dim_padded <= DIM_CHUNK or kernel == "tiled":
        return dim_padded, 1
    return DIM_CHUNK, dim_padded // DIM_CHUNK


#: the rows a validity word spans: bit ``g % 32`` of a word is a lane of
#: 128-row group ``g`` (ops.pallas_knn.valid_word_position), so a masked
#: step of this many rows (or a whole multiple) reads whole 128-word
#: blocks at fixed bits
MASK_WORD_ROWS = 32 * BIN_W


#: the most rows of one step of a tile cut by rows.  Timed on the v5e
#: at ``gist1m``'s shape (1,024 columns, two row parts, tile 16,384,
#: query block 256; root PERF.md section 6, PR 45): 8,192 rows a step,
#: the largest that fits, cost 22.2 us a 128-column pass-set of a tile
#: where 4,096 cost 19.4, 2,048 19.5 and 1,024 19.9, and the parent's
#: own one-step kernel over tiles of 8,192 / 4,096 / 2,048 rows read
#: 19.0 / 16.8 / 16.9 alike
ROW_BLOCK_MAX = 4096


def row_blocking(
    dim_padded: int, *, tile_n: int, block_q: int,
    precision: str = "bf16x3", kernel: str = "tiled",
    db_parts: Optional[int] = None,
    out_w: int = SURVIVORS_GROUPED_DEFAULT * BIN_W,
    masked: bool = False, budget_bytes: Optional[int] = None,
) -> Tuple[int, int]:
    """``(row_block, row_steps)``: the rows of a tile that one grid step
    of the tiled kernel multiplies at the whole padded width, and the
    steps a tile takes, from what a launch can see of itself — the
    padded width, the tile, the query block, the precision's db parts
    (``db_parts``: the parts actually streamed, where the bf16x3 split
    drops its low half; None = the precision's own), whether it carries
    per-query validity words.  No knob.

    ONE step (``row_block = tile_n``) wherever the whole tile's
    geometry, by the model, plus :func:`limit_bytes`' eighth fits
    ``budget_bytes`` (None = the target device's): the launch it always
    was.  Otherwise the LARGEST block of at most ``ROW_BLOCK_MAX`` rows
    that divides the tile, is a whole number of 128-row groups and fits
    the same way — 4,096 rows a step at GIST's 1,024 columns in two
    parts (32 MiB of row buffers) and at ``openai500k``'s 1,536 (48
    MiB); 2,048 from 2,560 columns up.  A masked launch's block is besides a whole
    number of ``MASK_WORD_ROWS`` or divides it, so that a step's
    validity words are one block at fixed or at once-shifted bits
    (ops.pallas_knn._kernel).  Where not even one group fits, 128 rows:
    the kernel's own budget check then names the knobs to change.

    The cut geometries were probed like the rest (bf16x3, two row
    parts, libtpu 0.0.34, deviceless for a v5e; the least limit that
    compiles, by bisection, in MiB against the model): 1,024 columns at
    bq256 47 / 52.1 in steps of 4,096 rows (tile 16,384 or 32,768
    alike), 25 / 28.1 of 2,048 and 93 / 100.4 of 8,192; at bq128 44 /
    46.2; 1,536 columns at bq256 71 / 73.1 of 4,096 and 39 / 39.1 of
    2,048; 640 columns 32 / 36.4 — the model over by 0% to 14%.  What
    Mosaic keeps beside the declared buffers there follows the step's
    rows times its WIDTH (10.9, 9.8, 17.9 MiB at 4,096 x 1,024 at bq256
    and bq128 and at 4,096 x 1,536), not the score tile: two score
    tiles and one bf16 copy of a row part's block bound all eight
    (``kernel_bytes``' ``row_part_live``).

    The other two kernels loop over their tiles themselves and ``pq``
    streams codes, not rows: always one step.  The model is calibrated
    for bf16x3 and an upper bound for the other arms (module
    docstring), so what it lets through fits there too."""
    dim_padded, tile_n = int(dim_padded), int(tile_n)
    if dim_padded % DIM_CHUNK or tile_n % BIN_W:
        raise ValueError(
            f"dim_padded={dim_padded} is no multiple of {DIM_CHUNK}, or "
            f"tile_n={tile_n} none of {BIN_W}")
    if kernel != "tiled" or precision == "pq":
        return tile_n, 1
    if budget_bytes is None:
        budget_bytes = budget_for(TARGET_DEVICE_KIND)
    n_parts, chunk_w, part_b = DB_PARTS[precision]
    row_bytes = ((n_parts if db_parts is None else int(db_parts))
                 * dim_padded * (chunk_w // DIM_CHUNK) * part_b)
    groups = tile_n // BIN_W
    for steps in range(1, groups + 1):
        if groups % steps:
            continue
        rows = tile_n // steps
        if steps > 1 and rows > ROW_BLOCK_MAX:
            continue
        if masked and steps > 1 and (rows % MASK_WORD_ROWS
                                     and MASK_WORD_ROWS % rows):
            continue
        need = sum(kernel_bytes(
            kernel="tiled", block_q=block_q, tile_n=tile_n, n_tiles=1,
            nd=1, out_w=out_w, bound_w=BIN_W, db_block=rows * row_bytes,
            aux_rows=AUX_ROWS.get(precision, AUX_ROWS_DEFAULT),
            q_block=block_q * dim_padded * _widths.query_elem_bytes(
                precision),
            q_extra=block_q * BIN_W * 4 if precision == "int8" else 0,
            row_block=rows, dim_padded=dim_padded,
        ).values())
        if need + need // 8 <= budget_bytes:
            return rows, steps
    return BIN_W, groups


#: the final select's Pallas stage (ops.pallas_knn._select_final): the
#: query rows of a block it prefers — eight vregs an array: its pop
#: rounds each wait on a cross-lane min, so they run as fast as the
#: block has independent vregs to keep in flight (timed on the v5e at
#: 8,704 columns, m+2 = 130, its lane-row loops still unrolled: 4.73 ms
#: a 4,096-query batch in blocks of 32, 3.49 at 64, 3.25 at 128, which
#: read 13.4 on heavily tied scores; root PERF.md, PR 35) — and the least it runs at (one vreg's sublanes)
FINAL_SELECT_BLOCK_Q = 64
FINAL_SELECT_MIN_BLOCK_Q = 8


def final_select_bytes(block_q: int, width: int, keep: int) -> Dict[str, int]:
    """Per-buffer VMEM bytes of one grid step of the final select's
    Pallas stage over ``width`` candidate columns, ``keep`` = m+2: the
    score and index blocks ``[block_q, width]`` the pipeline double
    buffers, the int32 key scratch of one block, the index and bound
    output blocks, and what Mosaic keeps beside them (the vregs it
    spills around the loops over the lane-rows: 20 KiB a query row
    bounds the least limit it compiled at, deviceless for a v5e, at 256
    to 15,872 columns in blocks of 32, 64 and 128 — 12 MiB against
    10.81 declared at 8,704 columns in blocks of 64, 42 against 39.12
    at 15,872 in blocks of 128; ``scripts/aot_compile_check.py`` prints
    the reading beside this model at every cell's shape)."""
    block = int(block_q) * int(width) * 4
    out_w = _ceil_div(keep - 1, BIN_W) * BIN_W + BIN_W
    return {
        "inputs_x2": 4 * block,
        "key_scratch": block,
        "outputs_x2": 2 * int(block_q) * out_w * 4,
        "live": int(block_q) * 20 * 1024,
    }


def final_select_block_q(width: int, keep: int,
                         budget_bytes: Optional[int] = None) -> Optional[int]:
    """The query rows of a block of the final select's Pallas stage at
    this shape: ``FINAL_SELECT_BLOCK_Q``, halved while the modelled need
    plus :func:`limit_bytes`' eighth overruns ``budget_bytes`` (None =
    the target device's); None where not even
    ``FINAL_SELECT_MIN_BLOCK_Q`` rows fit (the stage is not run)."""
    if budget_bytes is None:
        budget_bytes = budget_for(TARGET_DEVICE_KIND)
    block_q = FINAL_SELECT_BLOCK_Q
    while block_q >= FINAL_SELECT_MIN_BLOCK_Q:
        need = sum(final_select_bytes(block_q, width, keep).values())
        if need + need // 8 <= budget_bytes:
            return block_q
        block_q //= 2
    return None


def launch_estimate(
    *, n: int, d: int, k: int, margin: int = 28,
    precision: Optional[str] = None, kernel: Optional[str] = None,
    tile_n: Optional[int] = None, block_q: Optional[int] = None,
    survivors: Optional[int] = None,
    pq_dsub: Optional[int] = None, pq_ncodes: Optional[int] = None,
    budget_bytes: Optional[int] = None,
) -> dict:
    """Estimated VMEM high-water bytes of ONE kernel launch for this
    knob set at this problem shape, with the per-buffer breakdown
    (:func:`kernel_bytes` over the geometry the kernel would
    resolve, its tiles cut by :func:`dim_chunking` and by
    :func:`row_blocking` against ``budget_bytes``: None = the target
    device's)."""
    precision = precision or "bf16x3"
    kernel = kernel or "tiled"
    tile, bq, n_tiles, dim_p, out_w, bound_w = _geometry(
        n, d, precision, kernel, tile_n, block_q, survivors)
    dim_chunk, nd = dim_chunking(dim_p, kernel=kernel, precision=precision)
    row_block, row_steps = row_blocking(
        dim_p, tile_n=tile, block_q=bq, precision=precision, kernel=kernel,
        out_w=out_w, budget_bytes=budget_bytes)
    lut_w = 0
    if precision == "pq":
        # one db block is the [tile_n, m] byte code tensor; the
        # query-side block is the whole [block_q, m·ncodes] f32 LUT
        # (lane-padded), consumed in ONE dot — there is no dim-chunk
        # loop (ops.pallas_knn._bin_candidates pq arm)
        m_sub = _widths.pq_nsub(d, pq_dsub)
        n_parts, chunk_w, part_b = 1, m_sub, 1
        lut_w = _ceil_div(
            m_sub * int(pq_ncodes or _widths.PQ_NCODES_DEFAULT),
            BIN_W) * BIN_W
    else:
        n_parts, chunk_w, part_b = DB_PARTS[precision]
        chunk_w = chunk_w // DIM_CHUNK * dim_chunk
    quantized = precision == "int8"
    if precision == "pq":
        q_block = bq * lut_w * 4
    else:
        q_block = bq * (dim_chunk if kernel == "tiled" else dim_p) * (
            1 if quantized else 4)
    carry_depth = 0
    if kernel == "fused":
        keep = min(int(k) + int(margin), max(1, int(n) - 1)) + 2
        depth = _ceil_div(keep, BIN_W)
        carry_depth = depth if depth <= MAX_CARRY_DEPTH else 0
    breakdown = kernel_bytes(
        kernel=kernel, block_q=bq, tile_n=tile, n_tiles=n_tiles, nd=nd,
        out_w=out_w, bound_w=bound_w,
        db_block=n_parts * row_block * chunk_w * part_b,
        aux_rows=AUX_ROWS.get(precision, AUX_ROWS_DEFAULT),
        q_block=q_block, q_extra=bq * BIN_W * 4 if quantized else 0,
        carry_depth=carry_depth, row_block=row_block, dim_padded=dim_p)
    return {
        "total_bytes": int(sum(breakdown.values())),
        "breakdown": {kk: int(v) for kk, v in breakdown.items()},
        "geometry": {
            "tile_n": tile, "block_q": bq, "n_tiles": n_tiles,
            "dim_padded": dim_p, "dim_chunk": dim_chunk, "dim_chunks": nd,
            "row_block": row_block, "row_steps": row_steps,
            "out_w": out_w, "bound_w": bound_w,
            "kernel": kernel, "precision": precision,
        },
    }


def _estimate_for(knobs: dict, *, n: int, d: int, k: int,
                  margin: int, budget_bytes: Optional[int] = None) -> int:
    return launch_estimate(
        n=n, d=d, k=k, margin=margin, budget_bytes=budget_bytes,
        precision=knobs.get("precision"), kernel=knobs.get("kernel"),
        tile_n=knobs.get("tile_n"), block_q=knobs.get("block_q"),
        survivors=knobs.get("survivors"),
        pq_dsub=knobs.get("pq_dsub"),
        pq_ncodes=knobs.get("pq_ncodes"))["total_bytes"]


def check_candidate(
    knobs: dict, *, n: int, d: int, k: int, margin: int = 28,
    device_kind: Optional[str] = None, backend: Optional[str] = None,
) -> dict:
    """Price one knob set against one device kind's VMEM:
    ``{"checked", "fits", "estimate_bytes", "budget_bytes", ...}``.
    ``checked=False`` (cpu / no-VMEM backend, or an arm the model is
    not :func:`calibrated` for) means the verdict is N/A, never a
    refusal."""
    budget = budget_for(device_kind, backend)
    est = _estimate_for(knobs, n=n, d=d, k=k, margin=margin,
                        budget_bytes=budget)
    checked = budget is not None and calibrated(knobs.get("precision"))
    return {
        "checked": checked,
        "estimate_bytes": est,
        "budget_bytes": budget,
        "device_kind": device_kind,
        "fits": est <= budget if checked else None,
    }
