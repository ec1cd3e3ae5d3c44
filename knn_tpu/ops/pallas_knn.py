"""Pallas TPU kernel: fused distance + top-s-per-bin candidates + exclusion
bound — a *self-certifying* coarse pass.

The hot loop of the whole framework is ``query x database`` distance +
neighbor selection (the reference burns it in a scalar loop + full sort,
knn_mpi.cpp:317-323).  The XLA exact path is selection-bound: ``lax.top_k``
over a 1M-wide distance row costs ~30x the distance matmul.  This kernel
fuses distance + a hierarchical reduction so the [Q, N] distance matrix
never reaches HBM, and emits everything the certified pipeline needs in
ONE database pass:

  per grid cell (query block i, db tile j):
    1. MXU:  qt = Q_i @ T_j^T             (f32; the whole padded width in
                                           one product)
    2. XLA:  tn = ||T_j||^2               (db row norms, once a call)
    3. VPU:  s = tn - 2 qt                (squared L2 minus ||q||^2: the
                                           per-query constant is rank- and
                                           certificate-irrelevant)
    4. VPU:  per 128-wide bin, the s smallest values + their indices
             (candidates) AND the (s+1)-th smallest value (the *exclusion
             bound*: no non-candidate in this bin can score below it)
  (a tile too wide for VMEM takes steps 1-3 a ROW BLOCK at a time and
  carries step 4's running arrays between them: ``row_blocking``)

The bin LAYOUT is grouped: bin b = lane b of every 128-wide column group
of the score tile (128 bins/tile, members strided 128 apart).  The
per-bin reduction runs across column groups as elementwise vreg
min/compare/select chains — ZERO cross-lane shuffles; a single fused
pass maintains the running (s+1)-smallest per lane plus survivor group
indices (``_emit_select_grouped``).  A layout whose bins are contiguous
128-lane spans reduces over lanes (~7 shuffle rounds for each min and
argmin): its select dominated the kernel and it measured 1.8-3.1x
slower at the SIFT shape on a v5e.  The compiled grouped kernel passed
the 200k-row float64-oracle soundness gate and a tie-stressed
gate on a v5e chip.

Outputs per (i, j) cell are lane-aligned blocks (``s * 128`` lanes: a
(256, 16) output block fails to lower for exactly this rule).
Each (query block, db tile) cell writes its per-bin exclusion bounds to
its own disjoint output block; the min over tiles happens in XLA after
the kernel.  (Min-accumulating the bounds in-place across tiles via
output revisiting recorded an inflated bound on hardware in a
compiled-soundness gate, and per-tile emission costs ~0.3 ms of HBM
writes while depending on no revisiting semantics at all.)

Why top-2 per bin (the default): with 1M rows in ~7900 128-member bins
(the default geometry), two true top-100 neighbors
share a bin for ~47% of queries — a 1-survivor kernel falls back
constantly.  Three sharing one bin happens
~0.3% of the time: top-2 makes the certified fast path the common
case, and the bound makes every miss *detectable*:

  a point t outside the candidate set either (a) lost its bin's top-s —
  then s32(t) >= bound_b >= B, or (b) survived its kernel bin but lost
  its MERGE bin's smallest few — then s32(t) >= that merge bin's bound
  >= B, or (c) its entry lost the final top-(m+1) — then s32(t) >=
  v_excl >= B, where B = min(all bin bounds, all merge-bin bounds,
  v_excl).  With |s32 - s_true| <= tol, ``s_k_true < B - tol`` proves no
  true neighbor is missing — certified exact, NO separate count pass
  (ops.certified's count-below matmul becomes redundant on this path).

Case (b) is the final select's own bin-merge (``select_merge_geometry``,
``_select_merge``).  The kernel's candidate width grows with the corpus
(``n_tiles * survivors`` lane-rows of 128: 78,336 columns a query at 5M
rows) and a top-(m+2) over it was longer than the kernel.  From twice
the merged width up, the lane-rows are cut into ``ceil((m+2)/8)``
contiguous groups and (group, lane) is a merge bin that keeps its
``SELECT_MERGE_SURVIVORS`` smallest and gives the next as its bound —
the kernel's own elementwise per-lane reduction once more, so the
top-(m+2) scans 8,704 columns at m+2 = 130 whatever the corpus.  A
query whose true neighbours put five in one merge bin reads a lower B,
fails its certificate and is repaired like any other miss: a counted
fallback, never a different answer.  Narrower candidate arrays (1M rows
and every small shape) run the select over the kernel's candidates as
they are.

Case (c)'s top-(m+2) is itself a Pallas call wherever the shape is one
it was timed at (``final_select_geometry``, ``_select_final``: whole
lane-rows, m+2 at most 256, (m+2) x width at most 2^21 — every cell of
the benchmark).  XLA's ``TopK`` costs 28 ms + 1.42 us a column inside
the certified program and the gather of the selected indices 7 more;
the stage finds the (m+2)-th smallest score by 32 halvings on the
scores' monotone int32 keys (compare-and-count passes over the block's
lane-rows), cuts ties at it by column, and compacts the m+1 selected
row indices lane-wise and then by m+1 cross-lane pops: 4 ms at 8,704
columns.  It returns the set and the value ``lax.top_k`` would, bit for
bit, so nothing after it can tell; other shapes and
``final_select="approx"`` run XLA's ops as they did.

The kernel computes in float32 (precision configurable) because the
certificate's tolerance must be float32-tight; a bf16 coarse pass would
blur v_excl by ~1000x the k-th/(k+1)-th distance gap and never certify.
The default reaches it by splitting both operands into bf16 halves
(three MXU passes); on rows whose float32 values ARE their bf16 cast
(byte corpora) the low half is all zeros, and the passes that would
multiply by it are not made (``BF16X3_TERMS``): the same bits, one pass.

This is the ApproxTopK/PartialReduce shape (TPU-KNN paper, PAPERS.md) made
exact: fused with the distance matmul, two survivors instead of one, and a
sound exclusion bound instead of a recall target.

Two DB-STREAMING STRATEGIES share the select/emit machinery (``kernel``,
see ``KERNELS``):

- ``"tiled"`` (default): grid = (q_blocks, db_tiles, row_steps); the
  Pallas pipeline re-launches the kernel body once per train tile and
  each (query block, db tile) cell round-trips its survivor block
  through HBM before the XLA final select.  Every step multiplies the
  WHOLE padded width in one product.  ``row_steps`` is 1 wherever a
  row tile at that width fits VMEM (``row_blocking``: 128, 256, 384
  and 512 columns at the default tile and query block): no scratch
  exists and the bin-select sits in the matmul's own step.  A wider
  tile (GIST's 1,024 columns, 1,536 of an embedding) is cut by ROWS:
  the grid's third axis walks row blocks of 4,096 rows, each step runs
  its groups through the bin-select's insertion network, and what the
  steps of a tile share is the network's running arrays (five of
  ``[block_q, 128]``) in a VMEM scratch (``_row_step``).  The bins,
  the candidates and the bounds are the uncut tile's.  (Until PR 46
  such a tile was cut by COLUMNS and its 16 MB partial product read,
  added to and stored back at every step: 26.2 us a 128-column
  pass-set of a tile against 16.9.)
- ``"streaming"``: grid = (q_blocks,) — ONE kernel launch per
  (batch, shard).  The db tiles stay in HBM and stream through a
  double-buffered pair of VMEM scratch buffers via explicit async
  copies: while the MXU computes distances + the per-bin select on
  tile i, the DMA engine prefetches tile i+1 into the other slot.
  The per-tile survivor blocks accumulate in the VMEM-resident output
  block across the whole in-kernel tile loop (the running
  (distance, index) candidate list) and flush to HBM once per query
  block, instead of once per (query block, db tile) cell.  Its rows
  stay cut into 128-column chunks at every width (it holds a whole
  query block, both row buffers and every tile's output at once: the
  one wide chunk the tiled kernel has room for overruns it).  Outputs
  are BITWISE-IDENTICAL to the tiled kernel's wherever the two cut the
  rows alike (up to 128 columns, wider rows the tiled kernel does not
  collapse, and any width handed to both) — both run the same
  emitters on the same per-tile scores — so the downstream certified
  pipeline is unchanged and interpret-mode equality is testable
  (tests/test_pallas_streaming.py).  Opt-in (``kernel="streaming"`` in
  the call): no cell of the benchmark runs it, and it has no time on
  the chip.

Runs in interpret mode off-TPU so the CPU test suite covers it;
``chip_smoke.py`` gates the *compiled* kernel against a float64 oracle
on the chip (round 3 showed interpret-pass is not hardware-sound).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from knn_tpu.ops.topk import topk_pairs
from knn_tpu.utils.config import CERTIFIED_PRECISIONS

#: bin width — the lane count; `survivors` candidates + one bound per bin
BIN_W = 128
#: query rows per grid cell (VMEM: the [BLOCK_Q, TILE_N] f32 score tile;
#: 128 fills the MXU's M dimension — measured best on v5e)
BLOCK_Q = 128
#: database rows per grid cell.  16384 is the sweet spot at 1M rows:
#: 128 lane-bins of 128 members per tile keep the three-share rate at
#: ~0.3% (survivors=2) while halving the final-select width vs tile 8192
#: (62 tiles x 256 = 15.9k candidates vs 123 x 256 = 31.5k); every
#: production shape compile-checks for v5e at this tile
#: (scripts/aot_compile_check.py).
TILE_N = 16384
#: the final select's second bin-merge (``select_merge_geometry``): the
#: candidates a merge bin keeps; its next smallest is the bin's bound.
#: Five of a query's true top-k in one of 2,176 bins happen 3e-6 of the
#: time at k=100 (three kept would be 3e-4: a fallback in most batches)
SELECT_MERGE_SURVIVORS = 4
#: merge bins per slot of the top-(m+2) that follows: groups x 128 lanes
#: >= 16 x (m + 2), so the merged width follows m and not the corpus
SELECT_MERGE_BINS_PER_SLOT = 16
#: the final select as a Pallas stage (``final_select_geometry``,
#: ``_select_final``): the sorted slots a lane keeps in one compaction
#: pass.  A lane holds more than eight of a query's m+1 selected in
#: about 1% of the blocks at 8,704 columns (Poisson(1) a lane); those
#: run a second pass
FINAL_SELECT_SLOTS = 8
#: ... the lane-rows a step of its loops over them takes (the loops are
#: in the program, not unrolled in its trace)
FINAL_SELECT_UNROLL = 4
#: ... and the shapes it runs at: (m+2) x width at most this, m+2 at
#: most two vregs of output lanes.  Timed on the v5e against the
#: ``lax.top_k`` + gather it replaces at the three widths the
#: benchmark's cells have (root PERF.md, PR 35): 8,704 x 130, 15,872 x
#: 130 (the largest product timed, which sets the bound) and 2,560 x
#: 40 all win several times over; its pop rounds are serial in m, XLA's
#: cost is not, and nothing wider or deeper was timed (the k = 2,048
#: selection keeps XLA's path)
FINAL_SELECT_MAX_WORK = 1 << 21
FINAL_SELECT_MAX_KEEP = 2 * BIN_W
#: the padding grain of the feature axis (columns are zero-padded to a
#: multiple of it) and the width of a dim chunk under the streaming and
#: fused kernels, qt adding up across them.  The tiled kernel's chunk
#: is the whole padded width (``dim_chunking``); never a caller's choice
DIM_CHUNK = 128
#: survivors per bin where a caller names none and no rule resolved a
#: depth (``_geometry``), and the least ``survivor_depth`` considers:
#: every shape the chip had timed until PR 55
DEFAULT_SURVIVORS = 2
#: cap on survivors per bin (tiny tile_n in tests would otherwise unroll
#: a 128-step trace); capped cells just pad their output block
MAX_SURVIVORS = 8
#: row-padding fill: huge positive so padded rows score astronomically far
#: and can never become candidates or deflate a bin bound.  Soundness never
#: depends on this (a deflated bound only causes a fallback); candidate
#: sanity does, and 1.5e17 keeps ||pad||^2 finite in f32.
PAD_VAL = 1.5e17

_I32MAX = jnp.iinfo(jnp.int32).max
_I32MIN = jnp.iinfo(jnp.int32).min

#: kernel matmul modes.  "bf16x3" is the default: q and t split into
#: bf16 high/low parts, three MXU passes reconstruct the f32 product to
#: ~2^-17 relative accuracy at half the cost of a native f32 HIGHEST
#: matmul (Mosaic rejects Precision.HIGH, so the split is done by hand).
#: "bf16x3f" computes the SAME three-term sum as one dot over a 3x-wide
#: contraction ([qh|qh|ql] @ [th|tl|th]^T) — one MXU op and one f32
#: accumulator instead of three partials round-tripping VMEM; identical
#: error model, 1.5x the db streaming bytes.  "int8" is the hardware's
#: fastest scoring mode: per-row symmetrically quantized q and t
#: (ops.quantize), ONE int8 MXU dot per chunk (int32-exact, ~2x bf16
#: throughput, 1/4 the db streaming bytes) rescaled to f32 by the
#: per-query x per-row scale product — its certified tolerance is the
#: PROVABLE per-query quantization bound ε (quantize.score_error_bound),
#: so misses fall back, never leak.  "pq"
#: drops below bits-per-dim entirely: product-quantization codes (one
#: byte per ``dsub``-dim subspace, ops.pq) stream as the db operand and
#: the query side arrives as a per-query LOOKUP TABLE
#: (LUT[q, s*C + c] = q_s·cb[s,c] - ||cb[s,c]||²/2) so the kernel's
#: score is one dense MXU dot of the LUT against a one-hot code
#: expansion — s = tn - 2·qt then equals ||t̂||² - 2 q·t̂, the exact
#: kernel score against the RECONSTRUCTION t̂, and the per-subspace
#: Cauchy–Schwarz bound (ops.pq.score_error_bound_pq) certifies the
#: distance to the true rows.  "highest" is the native f32 path.
#: Every mode has a certified tolerance model (``kernel_tolerance``);
#: a single-pass DEFAULT-precision f32 dot has none (~2^-10 relative
#: error, measured: certificate-hostile) and is no mode.
PRECISIONS = CERTIFIED_PRECISIONS

#: the products of the "bf16x3" split a launch forms, named by their
#: (query, row) halves: h = the bf16 cast, l = the bf16 of what the cast
#: left.  A float32 whose low 16 bits are zero IS its bf16 cast, so its l
#: half is exactly zero: where every row is such a value (byte corpora:
#: whole numbers 0...255) ``qh.tl`` is a matrix of exact zeros, and where
#: the batch is too so is ``ql.th``.  Adding one changes no bit of the
#: sum, so the launch leaves it out, with the ``tl`` stream and its
#: prologue: one MXU pass and one row stream for three and two.  Read
#: off the data by the caller (``bf16x3_terms``), never set by one; rows
#: with any inexact value run the full sum whatever the batch.
BF16X3_TERMS = ("hh+hl+lh", "hh+lh", "hh")


def lo_halves_zero(x: np.ndarray) -> bool:
    """Whether every value of a host array is bf16-exact as float32: the
    low 16 bits of each are zero.  One OR-reduction over the values taken
    as uint32, no temporary of the array's size (float32 input: none)."""
    bits = np.ascontiguousarray(x, dtype=np.float32).reshape(-1).view(
        np.uint32)
    return not int(np.bitwise_or.reduce(bits)) & 0xFFFF


def bf16x3_terms(rows_lo_zero: bool, batch_lo_zero: bool) -> str:
    """The entry of ``BF16X3_TERMS`` for a placement whose rows are all
    bf16-exact (or not) and a batch that is (or not)."""
    if not rows_lo_zero:
        return BF16X3_TERMS[0]
    return BF16X3_TERMS[2] if batch_lo_zero else BF16X3_TERMS[1]


def _split_qt(q, th, tl, terms: str):
    """``q.t`` of one dim chunk by the "bf16x3" split, for the tiled and
    the streaming kernel alike — ONE arithmetic, which their bitwise
    contract rests on.  ``q`` [BQ, chunk] f32 splits here; ``th`` is the
    rows' high half, ``tl`` their low half — the tiled body's block ref,
    read where ``terms`` keeps ``hl`` and where it always was, or the
    streaming loop's loaded buffer — and unused where it does not.
    The full sum is qh.th + qh.tl + ql.th (ql.tl dropped: <= 2^-18
    |q||t|, covered by kernel_tolerance's 2^-14 factor); a term left out
    by ``terms`` is one whose low operand the caller saw to be all zero
    (``BF16X3_TERMS``)."""
    dn = (((1,), (1,)), ((), ()))
    qh = q.astype(jnp.bfloat16)
    if "lh" in terms:
        ql = (q - qh.astype(jnp.float32)).astype(jnp.bfloat16)
    qt = lax.dot_general(qh, th, dn, preferred_element_type=jnp.float32)
    if "hl" in terms:
        qt = qt + lax.dot_general(qh, tl[:], dn,
                                  preferred_element_type=jnp.float32)
    if "lh" in terms:
        qt = qt + lax.dot_general(ql, th, dn,
                                  preferred_element_type=jnp.float32)
    return qt


#: relative slack of the device rank stage's direct-difference f32
#: distances: per-term (q-t)^2 rounding plus the depth-7 tree reduce give
#: |d32 - d| <= ~1.2e-6 * d; 2^-18 = 3.8e-6 is ~3x headroom.  Candidate
#: pairs whose gap falls inside this band get a targeted float64
#: correction on host (exactness never rests on the f32 rank).  At SIFT1M
#: scale near-ties are COMMON — most queries have a few — so the
#: correction is per-pair, never per-query.
RANK_SLACK = 2.0 ** -18


#: ``jax.named_scope`` names of the certified program's device stages.
#: A scope is metadata on the ops traced inside it (their HLO
#: ``op_name``; the profiler shows it beside each ``XLA Ops`` event):
#: the compiled program and its instruction names do not change, and a
#: trace reduction can split the device time outside the kernel by
#: stage without leaning on names XLA numbers (``%fusion.2``).  The
#: fifth, ``knn.certify_pack``, and the cross-shard ``knn.merge`` inside
#: it are parallel.sharded's.
SCOPE_OPERAND_PREP = "knn.operand_prep"  # row pad + bf16 split + norms
SCOPE_KERNEL = "knn.kernel"              # the _bin_candidates call
SCOPE_FINAL_SELECT = "knn.final_select"  # top-(m+2) over the candidates
SCOPE_SELECT_MERGE = "knn.select_merge"  # its bin-merge (inside the above)
SCOPE_RESCORE = "knn.rescore"            # survivor gather + f32 rescore


def _round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


#: grid iteration orders.  "query_major" (default): grid =
#: (q_blocks, db_tiles, row_steps) — every query block streams the
#: FULL db through VMEM, so db HBM traffic scales with the query-block
#: count (16 GB per 4096-query sweep at the SIFT shape, the largest
#: term of the measured cost model in docs/PERF.md).  "db_major": grid =
#: (db_tiles, q_blocks, row_steps) — consecutive steps revisit the
#: same db tile (Pallas re-fetches an input block only when its mapped
#: index changes), so AT ONE STEP A TILE (SIFT's 128 columns, and every
#: wider tile ``row_blocking`` keeps whole)
#: each db tile streams ONCE per sweep and only the small query blocks
#: re-stream (~2 MB x n_tiles).  Where a tile is cut by rows the
#: innermost axis cycles between query blocks, so every row block
#: re-fetches per query block — db traffic identical to query_major;
#: the variant buys nothing there (gist).  Candidate/bound
#: outputs stay disjoint per (query block, db tile) cell in both orders
#: — no output revisiting (module docstring) either way.
#: db_major is opt-in until the on-hardware gate + A/B pass on it.
GRID_ORDERS = ("query_major", "db_major")

#: db-streaming strategies (module docstring).  "tiled" = the Pallas
#: grid pipeline re-launches the body per train tile; "streaming" = one
#: launch per (batch, shard) with explicit double-buffered HBM->VMEM
#: async copies and the candidate list carried in VMEM across tiles.
#: "fused" = the streaming launch with the select fused DEEPER into the
#: tile loop: each tile's per-lane minima are reduced against a
#: VMEM-resident carry of running order statistics, and a SOUND
#: exclusion-bound early-out skips a tile's whole select chain when its
#: best possible score provably cannot enter the final top-(m+2) nor
#: lower the exclusion bound — the select cost rides the HBM stream's
#: shadow instead of following it.  Final certified results are
#: bitwise-identical to the tiled reference: a skipped tile's candidate
#: block pads with +inf/sentinel, and the skip predicate (strict
#: tile-min > carry threshold, threshold an upper bound on the final
#: (m+2)-th smallest EMITTED candidate) guarantees neither the final
#: select, its tie-breaks, nor the exclusion bound can see the
#: difference (tests/test_fused_overlap.py).  Where the final select's
#: bin-merge engages (``select_merge_geometry``) the skip stays sound:
#: a skipped tile's scores exceed the (m+2)-th smallest EMITTED
#: candidate e; if the merge dropped any of the emitted top-(m+2), that
#: merge bin's bound is at most e, else the exclusion value is e —
#: either way lb <= e, below every skipped score.  Query-major only,
#: like streaming.
KERNELS = ("tiled", "streaming", "fused")

#: early-out carry depth cap: the threshold needs ceil(min_keep / 128)
#: running order statistics per lane; deeper carries unroll more
#: insertion steps per tile, so past this depth the early-out disarms
#: (thr stays +inf) rather than bloating the kernel trace
MAX_CARRY_DEPTH = 8


def kernel_launches_per_batch(kernel: str, rows: int, tile_n: int) -> int:
    """Db-streaming kernel dispatches per (batch, shard) — the number
    the bench publishes so launch accounting has ONE home: the tiled
    grid re-launches its pipelined body once per train tile; the
    streaming/fused kernels are ONE launch whose in-kernel loop covers
    every tile."""
    if kernel not in KERNELS:
        raise ValueError(f"kernel {kernel!r} not in {KERNELS}")
    n_tiles = -(-rows // tile_n)
    return 1 if kernel in ("streaming", "fused") else n_tiles


def _geometry(
    tile_n: int, survivors: Optional[int] = None,
) -> Tuple[int, int, int, int]:
    """(n_bins, survivors, out_w, bound_w) for a db tile: the 128 lanes
    are the bins (each has ``tile_n // 128`` members, strided 128
    apart), so the tile must be a multiple of 128.  Output blocks are
    lane-aligned: ``out_w = survivors * 128`` lanes of candidates per
    cell, ``bound_w = 128`` lanes of per-bin exclusion bounds.
    ``survivors=None`` picks ``DEFAULT_SURVIVORS`` (the collision-rate
    sweet spot at k = 100, module docstring; a certified call hands in
    the depth :func:`survivor_depth` read off its shape); the
    MAX_SURVIVORS cap applies to explicit requests too (each survivor is
    an unrolled insertion step in the kernel trace)."""
    if tile_n % BIN_W:
        raise ValueError(
            f"tile_n={tile_n} must be a multiple of {BIN_W} lanes")
    if survivors is None:
        survivors = DEFAULT_SURVIVORS
    survivors = min(survivors, MAX_SURVIVORS)
    return BIN_W, survivors, survivors * BIN_W, BIN_W


def valid_words_per_tile(tile_n: int) -> int:
    """Words a query of one row tile's validity: a word holds one lane
    of 32 consecutive 128-row groups, so whole vregs of 128 words."""
    return -(-(tile_n // BIN_W) // 32) * BIN_W


def valid_word_position(rows, tile_n: int):
    """``(column, bit)`` of (shard-local) row indices in a query's
    validity words at row tile ``tile_n`` (numpy or jax integers): tile
    ``t`` owns columns ``[t * w, (t + 1) * w)``, ``w =
    valid_words_per_tile(tile_n)``, and within it bit ``g % 32`` of word
    ``(g // 32) * 128 + lane`` is row ``g * 128 + lane`` of the tile.
    Wherever ``tile_n % 4096 == 0`` that is bit ``G % 32`` of word
    ``(G // 32) * 128 + lane`` for the row's GLOBAL group ``G``, the
    same whatever the tile."""
    w = valid_words_per_tile(tile_n)
    t, r = rows // tile_n, rows % tile_n
    g = r // BIN_W
    return t * w + (g // 32) * BIN_W + r % BIN_W, g % 32


def pack_valid_words(valid: np.ndarray, tile_n: int) -> np.ndarray:
    """Host packing of a bool ``[queries, rows]`` validity matrix into
    the kernel's words (uint32 ``[queries, n_tiles * w]``), rows past
    the matrix invalid: the plain statement of the layout, for tests
    and small callers."""
    n_q, n = valid.shape
    n_tiles = -(-n // tile_n)
    out = np.zeros((n_q, n_tiles * valid_words_per_tile(tile_n)), np.uint32)
    col, bit = valid_word_position(np.arange(n), tile_n)
    for q in range(n_q):
        on = np.flatnonzero(valid[q])
        np.bitwise_or.at(out[q], col[on],
                         np.uint32(1) << bit[on].astype(np.uint32))
    return out


def effective_tile(
    rows: int, tile_n: int, survivors: Optional[int], min_width: int,
) -> int:
    """The db tile the kernel will actually run: capped to the (padded)
    db, then HALVED until the total candidate width ``n_tiles * out_w``
    covers ``min_width`` (= m+2 for certified callers) or the tile
    bottoms out at ``BIN_W``.  Mid-size databases would otherwise lose
    candidate width to a large default tile (one 16384-tile over a 10k
    db emits 256 lanes where two 8192-tiles emitted 512) and raise the
    m+2-exceeds-width ValueError on margins that a smaller tile serves
    fine.  ONE home for this arithmetic: parallel.sharded._pallas_setup
    resolves the tile here and plumbs the RESOLVED tile into the sharded
    program, so local_certified_candidates' own call (min_width = m+2,
    guaranteed covered by setup's m-cap) is a fixpoint — the two can
    never run different tiles."""
    if tile_n % BIN_W:
        # the caller's REQUESTED tile must be well-formed (the halving
        # below rounds its own internal steps, but never repairs an
        # invalid request silently)
        raise ValueError(
            f"tile_n={tile_n} must be a multiple of {BIN_W} lanes")
    eff = min(tile_n, max(BIN_W, -(-rows // BIN_W) * BIN_W))

    def width(t: int) -> int:
        _, _, out_w, _ = _geometry(t, survivors)
        return -(-rows // t) * out_w

    while eff > BIN_W and width(eff) < min_width:
        eff = max(BIN_W, -(-(eff // 2) // BIN_W) * BIN_W)
    return eff


#: the modelled share of queries (``bin_overflow_share``) under which
#: :func:`survivor_depth` stops deepening.  The nine cells the benchmark
#: had when the rule came (PR 55) read 0.0001 to 2.3 % at depth 2 (the
#: highest ``openai500k``: 500K rows, 3,968 bins, m+2 = 130) and keep
#: it; k = 1,024 over 1M rows (m+2 = 1,154 of 7,936 bins) reads 97 %
#: there, 12 % at 3, 0.38 % at 4
SURVIVOR_OVERFLOW_LIMIT = 0.05


#: the candidates a certified call keeps beyond k where the caller names
#: no ``margin``: what every k = 100 shape the chip has timed ran with
DEFAULT_MARGIN = 28
#: ... and the share of k it does not go under
MARGIN_K_SHARE = 8


def default_margin(k: int) -> int:
    """The ``margin`` of a certified call whose caller names none: m =
    k + margin candidates are kept a query, and the certificate holds
    only where the (m+2)-th nearest row lies further beyond the k-th
    than the kernel's tolerance (``kernel_tolerance``: 2^-14 of |q|^2 +
    the largest |t|^2, whatever k).  The gap between neighbours a fixed
    number of ranks apart shrinks as 1 / rank (the k-th distance moves
    with log k in a cluster), so a fixed margin that clears the
    tolerance at k = 100 does not at k = 1,000: on ``knnlm1m``'s rows
    28 ranks past the 1,024-th are 2.2e-4 in the largest cluster
    against a tolerance of 2.8e-4, and 17.9 % of the queries, all of
    the four largest clusters', failed for that and for no full bin
    (PERF.md section 6, PR 55).  So the margin follows k: an eighth of
    it (128 ranks, 9.1e-4 there), and no less than ``DEFAULT_MARGIN``,
    which k up to 231 keep."""
    return max(DEFAULT_MARGIN, int(k) // MARGIN_K_SHARE)


def bin_overflow_share(keep: int, bins: int, depth: int) -> float:
    """The modelled share of queries whose certificate fails on a FULL
    BIN: some kernel bin holds more than ``depth`` of the query's
    ``keep`` (= m+2) nearest rows, so one of them is no candidate and
    the bin's bound falls among them.  Rows fall into the ``bins``
    (128 lanes a row tile) independently of their distance to a query,
    so a bin's count is Poisson(keep / bins) and the share is ``1 -
    exp(-bins * P(count > depth))``: an upper reading (a full bin past
    the k-th neighbour still certifies, and db shards split the
    nearest between them).  Counted on uniform rows in
    ``tests/test_knnlm_topk.py``; on the chip ``fallback_pct`` read
    0.014 / 0.11 to 0.22 / 1.06 % where this gives 0.02 / 0.1 / 2.3
    (``bigann5m``, ``ssnpp2m5``, ``openai500k``; ledger, PR 54).

    WHEN THE INDEPENDENCE HOLDS.  Over all of a placement's rows, for
    any placement: a row's lane and tile say nothing of its distance to
    a query.  Under a PREDICATE it holds only where the rows the
    predicate keeps lie over the bins as all rows do, and an attribute
    that follows the rows' order (an id, a time stamp) breaks exactly
    that: ``id >= 495,000`` of 500,000 kept its 5,000 rows in ONE row
    tile of 31, 128 of the 3,968 bins this is asked about, and
    ``fallback_pct`` read 50.5 at
    ``openai500k-intfilter.sweep_cos_filter`` (ledger, PR 57:
    ``bin_overflow_share(130, 128, 2)`` is 1.0).  So a placement that
    is handed an attribute lays its rows out INTERLEAVED
    (parallel.sharded ``_interleave_order``: one pseudo-random order a
    row count), which makes a range's rows fall over every bin
    whatever the attribute's order, and the same cell reads 0.77 to
    1.81 over twelve seeds, nearly every flagged query on a full bin
    (three of its 100 nearest in one of 3,968 bins: C(100, 3) /
    3,968^2 = 1.03 %; my chip runs, PR 58).  A pre-placed array handed
    an attribute
    keeps its order, and this model is then the caller's to make true
    (shuffle before placing)."""
    lam = keep / bins
    # the upper tail summed upward from its first term: 1 - cdf cancels
    # at the small rates this is asked about
    term = math.exp(-lam) * lam ** (depth + 1) / math.factorial(depth + 1)
    tail = 0.0
    for j in range(depth + 2, depth + 66):
        tail += term
        term *= lam / j
    return -math.expm1(-bins * tail)


def survivor_depth(
    rows: int, tile_n: int, survivors: Optional[int], keep: int,
) -> Tuple[int, int, float]:
    """``(depth, tile, share)``: the survivors a kernel bin keeps for a
    shard of ``rows`` rows selected ``keep`` (= m+2) deep, the row tile
    :func:`effective_tile` resolves at that depth and the share
    :func:`bin_overflow_share` models there.  A caller's ``survivors``
    is taken as given (capped like ``_geometry`` caps it).  Left out, it
    follows from what the call can see and from no knob: the least depth
    from ``DEFAULT_SURVIVORS`` to ``MAX_SURVIVORS`` whose modelled share
    is under ``SURVIVOR_OVERFLOW_LIMIT`` (each further survivor is one
    more insertion step an element on the VPU, and 128 more candidate
    columns a tile for the final select), and where none is (a corpus
    of a few bins) the depth of the least share, the shallowest on
    equal ones.  The bins counted are the SHARD's: right wherever a
    query's nearest rows may lie in any of them, which a filtered call
    owes to the interleaved placement of :func:`bin_overflow_share`'s
    docstring, not to this rule (reading the depth off the tiles a
    range meets was tried in PR 57 and taken out)."""
    def at(depth: int) -> Tuple[int, int, float]:
        tile = effective_tile(rows, tile_n, depth, keep)
        return depth, tile, bin_overflow_share(
            keep, -(-rows // tile) * BIN_W, depth)

    if survivors is not None:
        return at(min(int(survivors), MAX_SURVIVORS))
    best = None
    for depth in range(DEFAULT_SURVIVORS, MAX_SURVIVORS + 1):
        got = at(depth)
        if got[2] < SURVIVOR_OVERFLOW_LIMIT:
            return got
        if best is None or got[2] < best[2]:
            best = got
    return best


def select_merge_geometry(
    width: int, m: int,
) -> Optional[Tuple[int, int, int]]:
    """``(groups, rows, merged_width)`` of the bin-merge that runs
    between the kernel and the final top-(m+2)
    (:func:`local_select_rescore`), or ``None`` where the final select
    runs over the kernel's candidates as they are.  The candidate array
    is ``width // 128`` lane-rows; they are cut into ``groups``
    contiguous runs of ``rows`` (the last short of it where they do not
    divide: ``_select_merge`` reads +inf past the array) and
    ``(group, lane)`` is a merge bin that keeps its
    ``SELECT_MERGE_SURVIVORS`` smallest: ``groups * 128`` bins, at least
    ``SELECT_MERGE_BINS_PER_SLOT`` for each of the m+2 slots, so
    ``merged_width = groups * SELECT_MERGE_SURVIVORS * 128`` follows m
    and not the corpus (8,704 at m+2 = 130).  Engages only where that
    at least halves the width, from what the call can see and by no
    knob: 78,336 columns (5M rows at tile 16,384) do, 15,872 (1M rows)
    and every narrower shape run today's program."""
    groups = -(-(m + 2) * SELECT_MERGE_BINS_PER_SLOT // BIN_W)
    merged = groups * SELECT_MERGE_SURVIVORS * BIN_W
    if width % BIN_W or width < 2 * merged:
        return None
    return groups, -(-(width // BIN_W) // groups), merged


def final_select_geometry(width: int, m: int) -> Optional[int]:
    """The query rows of a block of the Pallas stage that is the exact
    final top-(m+2) (:func:`_select_final`), or ``None`` where
    :func:`local_select_rescore` keeps XLA's ``lax.top_k`` and gather.
    ``width`` is what the select scans: the bin-merge's width where
    ``select_merge_geometry`` engages, the kernel's where it does not.
    By shape, from what the call can see and by no knob: whole
    lane-rows, m+2 and (m+2) x width inside what was timed
    (``FINAL_SELECT_MAX_KEEP``, ``FINAL_SELECT_MAX_WORK``), and a block
    of at least eight queries inside the device's VMEM by
    knn_tpu.analysis.vmem's model, which also sizes the block."""
    from knn_tpu.analysis import vmem

    keep = m + 2
    if (width % BIN_W or keep > min(width, FINAL_SELECT_MAX_KEEP)
            or keep * width > FINAL_SELECT_MAX_WORK):
        return None
    return vmem.final_select_block_q(
        width, keep, vmem.budget_for(_vmem_device_kind()))


def _pq_onehot_qt(lut, codes_u8, *, tile_n: int, pq_shape):
    """The PQ scoring dot shared by the tiled and streaming kernels —
    ONE arithmetic, which the bitwise contract across db-streaming
    strategies rests on.  ``lut`` [BQ, >= m*C] per-query tables
    (LUT[q, s*C + c] = q_s·cb[s,c] - ||cb[s,c]||²/2, built once in the
    XLA prologue), ``codes_u8`` [T, m] the streamed byte codes.  The
    gather of m table entries per row becomes a dense MXU matmul of the
    LUT against the codes' one-hot expansion: qt[q, t] =
    sum_s LUT[q, s*C + codes[t, s]] = q·t̂ - ||t̂||²/2, so the shared
    emitters' ``s = tn - 2·qt`` (tn = 0 on valid rows, PAD_VAL on
    padding) equals ||t̂||² - 2 q·t̂ — the standard kernel score against
    the reconstruction t̂."""
    m_sub, ncodes = pq_shape
    codes = codes_u8.astype(jnp.int32)
    cidx = lax.broadcasted_iota(jnp.int32, (tile_n, m_sub, ncodes), 2)
    onehot = (codes[:, :, None] == cidx).astype(jnp.float32).reshape(
        tile_n, m_sub * ncodes)
    dn = (((1,), (1,)), ((), ()))
    return lax.dot_general(lut[:, : m_sub * ncodes], onehot, dn,
                           preferred_element_type=jnp.float32)


def _kernel(q_ref, *refs, tile_n: int, survivors: int, nd: int,
            precision: str, ti_axis: int = 1, pq_shape=None,
            terms: str = BF16X3_TERMS[0], masked: bool = False):
    ti = pl.program_id(ti_axis)  # 1 = query_major grid, 0 = db_major
    di = pl.program_id(2)
    q = q_ref[:]
    vw_ref = None
    if masked:
        # the batch's validity words of this (query block, row tile)
        # cell come first after the queries (``valid_words``)
        vw_ref, *refs = refs
    dn = (((1,), (1,)), ((), ()))
    if precision == "bf16x3":
        # db high/low bf16 parts arrive PRECOMPUTED (one XLA pass per
        # call instead of a per-cell VPU split redone for every query
        # block); only the small q block splits in-kernel.  The low part
        # is no operand where ``terms`` drops its product
        if "hl" in terms:
            th_ref, tl_ref, tn_ref, d_ref, i_ref, b_ref, *scratch = refs
        else:
            th_ref, tn_ref, d_ref, i_ref, b_ref, *scratch = refs
            tl_ref = None
        qt = _split_qt(q, th_ref[:], tl_ref, terms)
    elif precision == "bf16x3f":
        # fused form of the same sum: ONE dot over a 3x contraction
        t3_ref, tn_ref, d_ref, i_ref, b_ref, *scratch = refs
        qh = q.astype(jnp.bfloat16)
        ql = (q - qh.astype(jnp.float32)).astype(jnp.bfloat16)
        q3 = jnp.concatenate([qh, qh, ql], axis=1)  # [BQ, 3 * chunk]
        qt = lax.dot_general(q3, t3_ref[:], dn,
                             preferred_element_type=jnp.float32)
    elif precision == "int8":
        # q arrives PRE-QUANTIZED int8 (the XLA prologue in
        # _bin_candidates quantized it once per call, like the bf16
        # split); the db tile streams as int8 and the dot accumulates in
        # int32 — EXACTLY, across every dim chunk (|qi.ti| <= 2^14 * d
        # can't overflow below d ~ 2^17), so the chunk loop is pure
        # integer arithmetic and the ONE f32 rescale (per-query x
        # per-row scale product, applied at select time) is the only
        # rounding site — which is also what makes the tiled and
        # streaming kernels bitwise-identical here: integer adds admit
        # no fusion/reassociation rounding differences.  The aux block
        # stacks row norms (sublanes 0-7) over row scales (8-15) so the
        # db side streams ONE extra lane-major array, not two.
        ti_ref, qsc_ref, aux_ref, d_ref, i_ref, b_ref, *scratch = refs
        tn_ref = aux_ref
        qt = lax.dot_general(q, ti_ref[:], dn,
                             preferred_element_type=jnp.int32)
    elif precision == "pq":
        # product-quantization scoring: q_ref carries the per-query LUT
        # block (one block, nd == 1 always), the db operand is the byte
        # code tile — _pq_onehot_qt turns the per-row table gather into
        # one dense MXU dot.  The aux block is the pad-fill carrier only
        # (0 on valid rows: the LUT already embeds the reconstruction's
        # norm term)
        codes_ref, tn_ref, d_ref, i_ref, b_ref, *scratch = refs
        qt = _pq_onehot_qt(q, codes_ref[:], tile_n=tile_n,
                           pq_shape=pq_shape)
    else:  # "highest"
        t_ref, tn_ref, d_ref, i_ref, b_ref, *scratch = refs
        qt = lax.dot_general(q, t_ref[:], dn,
                             preferred_element_type=jnp.float32,
                             precision=lax.Precision.HIGHEST)  # [BQ, T]
    # db row norms arrive precomputed ([8, T] broadcast, row 0 used): an
    # XLA f32 reduction once per call instead of a per-cell ones-matmul
    # (which cost ~12% of the qt matmul as a 6-pass f32 HIGHEST dot)

    def write(qt_acc):
        if precision == "int8":
            # the one rescale: full int32 dot -> f32 (rounded for
            # d > 1040, covered by the bound's f32 slack), times the
            # per-query [BQ, 1] and per-row [1, T] scales.  The aux
            # stacks 8 norm rows over 8 scale rows (scales at row 8)
            qt_acc = ((qt_acc.astype(jnp.float32) * qsc_ref[:, 0:1])
                      * aux_ref[8:9, :])
        cd, ci, bound = _emit_select_grouped(
            ti, qt_acc, tn_ref[:], tile_n=tile_n, survivors=survivors,
            valid_words=None if vw_ref is None else vw_ref[:])
        d_ref[:] = cd
        i_ref[:] = ci
        b_ref[:] = bound

    if tn_ref.shape[1] < tile_n:
        # the blocks this step was handed are one ROW BLOCK of the tile
        # (``_row_call``: a tile too wide for VMEM, cut by rows): the
        # product above is whole, and what the tile's steps share is
        # the bin-select's running arrays alone (``_row_step``)
        if precision == "int8":
            # ``write``'s rescale, on the step's rows
            qt = ((qt.astype(jnp.float32) * qsc_ref[:, 0:1])
                  * aux_ref[8:9, :])
        _row_step(scratch[0], ti, qt, tn_ref, vw_ref, d_ref, i_ref, b_ref,
                  tile_n=tile_n)
        return
    if nd == 1:
        # single dim chunk: no scratch allocated, skip the VMEM
        # accumulation round-trip entirely (measured ~16% of kernel time
        # at SIFT shape)
        write(qt)
        return
    # a dim chunk handed to the launch (tests alone: the rule never
    # cuts the tiled kernel's columns): the partial products add up in
    # a [BQ, T] scratch, the select behind the last
    qt_ref, = scratch

    @pl.when(di == 0)
    def _init():
        qt_ref[:] = qt

    @pl.when(di > 0)
    def _acc():
        qt_ref[:] += qt

    @pl.when(di == nd - 1)
    def _select():
        write(qt_ref[:])


def _row_step(state_ref, ti, qt, tn_ref, vw_ref, d_ref, i_ref, b_ref, *,
              tile_n: int):
    """One of the steps a row tile is cut into (``row_blocking``): ``qt``
    [BQ, rows] is the product of the step's rows, whole, and ``tn_ref``
    their norms.  The step's 128-row groups go through the insertion
    network of :func:`_emit_select_grouped_scores`, numbered as they
    are in the tile (``step * groups + g``), into the running arrays
    the steps of a tile share: ``state_ref`` f32 ``[2 * survivors + 1,
    BQ, 128]`` holds the ``survivors + 1`` smallest values a lane has
    seen and, as bits, the groups of the ``survivors`` kept.  Read as
    +inf at the tile's first step, written out as the tile's ``(cand_d,
    cand_i, bounds)`` at the last: the bins, the order of insertion and
    so every emitted value are the uncut tile's.

    ``vw_ref`` (None: no row is masked) is the step's block of validity
    words: whole word blocks at the words' own bits where the step is a
    whole number of ``32 * 128`` rows; else the ONE block its groups
    share, shifted here so that the step's first group is bit 0.

    The network and the emission are a COPY of the one-step emitter's,
    not a call into a body both share: every cell's kernel traces that
    emitter, some 1,500 binds a tile, and a frame more under it, or a
    wider one, moves where CPython's 16 KiB frame-stack chunks end
    under the binds (root PERF.md section 6, PRs 29 and 46: PR 45 was
    refused for 0.9 s of ``setup_s`` in a cell whose program it did not
    change).  tests/test_dim_chunking.py holds the two to one answer,
    bit for bit."""
    si = pl.program_id(2)
    bq, rows = qt.shape
    groups = rows // BIN_W
    survivors = state_ref.shape[0] // 2
    s = tn_ref[0:1, :] - 2.0 * qt  # [BQ, rows], ||q||^2 dropped
    first = lax.mul(si, np.int32(groups))
    lane = lax.broadcasted_iota(jnp.int32, (bq, BIN_W), 1)
    inf = lax.full((bq, BIN_W), jnp.inf, jnp.float32)
    if vw_ref is not None:
        zero = lax.full((bq, BIN_W), 0, jnp.int32)
        words = vw_ref[:]
        if groups % 32:
            words = lax.shift_right_logical(words, lax.broadcast(
                lax.rem(first, np.int32(32)), words.shape))
    # a tile's first step starts from +inf (never displaces; a group
    # index under it is never read) by a select on the step's number,
    # not by a branch: a ``pl.when`` here would stand between the
    # product and the select and keep Mosaic from running one under the
    # other (5 us of 39 a step at ``gist1m``'s shape: root PERF.md
    # section 6, PR 45)
    fresh = lax.eq(si, np.int32(0))
    vals = [lax.select(fresh, inf, state_ref[j])
            for j in range(survivors + 1)]
    gidx = [lax.bitcast_convert_type(state_ref[survivors + 1 + j], jnp.int32)
            for j in range(survivors)]
    for g in range(groups):
        cur_v = lax.slice_in_dim(s, g * BIN_W, (g + 1) * BIN_W, axis=1)
        if vw_ref is not None:
            word = lax.slice_in_dim(words, g // 32 * BIN_W,
                                    (g // 32 + 1) * BIN_W, axis=1)
            bit = lax.full((bq, BIN_W), np.int32(
                np.uint32(1 << g % 32).view(np.int32)), jnp.int32)
            cur_v = lax.select(lax.ne(lax.bitwise_and(word, bit), zero),
                               cur_v, inf)
        cur_g = lax.broadcast(lax.add(first, np.int32(g)), (bq, BIN_W))
        for j in range(survivors):
            less = lax.lt(cur_v, vals[j])
            disp_v = lax.max(cur_v, vals[j])
            disp_g = lax.select(less, gidx[j], cur_g)
            vals[j] = lax.min(cur_v, vals[j])
            gidx[j] = lax.select(less, cur_g, gidx[j])
            cur_v, cur_g = disp_v, disp_g
        vals[survivors] = lax.min(vals[survivors], cur_v)
    for j in range(survivors + 1):
        state_ref[j] = vals[j]
    for j in range(survivors):
        state_ref[survivors + 1 + j] = lax.bitcast_convert_type(
            gidx[j], jnp.float32)

    @pl.when(si == tile_n // rows - 1)
    def _emit():
        d_ref[:] = jnp.concatenate(vals[:survivors], axis=-1)
        i_ref[:] = jnp.concatenate([
            jnp.where(jnp.isfinite(vals[j]),
                      ti * tile_n + gidx[j] * BIN_W + lane, _I32MAX)
            for j in range(survivors)], axis=-1)
        b_ref[:] = vals[survivors]


def _emit_select_grouped(ti, qt, tn, *, tile_n: int, survivors: int,
                         valid_words=None):
    """Survivor/bound emission from an accumulated score tile: returns
    ``(cand_d, cand_i, bounds)`` for the caller to write (the tiled
    kernel stores them to its per-cell output blocks; the streaming
    kernel stores them at the tile's dynamic column offset) — ONE
    emitter serves every db-streaming strategy, which is what makes
    them bitwise-identical.  ``ti`` is the db-tile index, hoisted by the
    caller because ``pl.program_id`` is unavailable inside a ``pl.when``
    branch in interpret mode.

    Bin b = lane b of every 128-wide column group, so the per-bin
    reduction runs over the GROUP axis — a chain of elementwise vector
    min/compare/select over [BQ, 128] vregs, zero cross-lane shuffles.
    One fused pass maintains the running (survivors+1) smallest values
    per lane (a sorted insertion network) plus the group index of each
    survivor; the (survivors+1)-th value is the bin's exclusion bound.

    Soundness contract: every tile row not emitted as a candidate scores
    >= its bin's bound (rows other than a bin's ``survivors`` smallest
    score >= the (survivors+1)-th smallest).  Every (qi, ti) cell owns
    its own disjoint bounds block; the min over tiles happens in XLA
    after the kernel (module docstring)."""
    s = tn[0:1, :] - 2.0 * qt  # [BQ, T], ||q||^2 dropped
    return _emit_select_grouped_scores(
        ti, s, tile_n=tile_n, survivors=survivors, valid_words=valid_words)


def _emit_select_grouped_scores(ti, s, *, tile_n: int, survivors: int,
                                payload=None, valid_words=None):
    """The grouped emitter on a PRECOMPUTED score tile ``s`` — split out
    so the fused kernel (which needs ``s`` for its early-out predicate
    before deciding whether to run the select at all) shares the EXACT
    ops with the tiled/streaming paths: ``_emit_select_grouped`` computes
    ``s = tn[0:1, :] - 2.0 * qt`` and delegates here, the fused tile
    body computes the identical expression and calls this directly —
    one arithmetic, bitwise-identical emissions.

    A survivor's index is its db row, rebuilt from the group it came
    from (``ti * tile_n + group * 128 + lane``; sentinel where +inf) —
    or, where ``s`` is itself an array of candidates with their indices
    in ``payload`` (same shape, int32, sentinel where +inf: the final
    select's bin-merge, ``_select_merge``), the payload riding with
    it.  Returns ``(cd, ci [BQ, survivors * 128], bound [BQ, 128])``.

    ``valid_words`` (int32 ``[BQ, valid_words_per_tile(tile_n)]``, this
    tile's block of a batch's per-query validity words; None: every row
    is a candidate and not one operation is added) turns the score of a
    row whose bit is 0 into +inf BEFORE the insertion network: bit
    ``g % 32`` of word ``[query, (g // 32) * 128 + lane]`` is the
    validity of the tile's row ``g * 128 + lane``, so the test is one
    ``and`` with a constant and one compare on the group's own
    ``[BQ, 128]`` vreg, with no cross-lane move.  A masked row is then
    never a candidate (+inf never displaces: strict ``<``) and never
    lowers a bin's bound, so the soundness contract holds over the
    VALID rows: every valid row not emitted scores >= its bin's bound,
    and a bin with at most ``survivors`` valid rows has bound +inf."""
    bq = s.shape[0]
    n_groups = tile_n // BIN_W
    lane = lax.broadcasted_iota(jnp.int32, (bq, BIN_W), 1)
    inf = jnp.full((bq, BIN_W), jnp.inf, jnp.float32)
    none = jnp.full((bq, BIN_W), 0 if payload is None else _I32MAX,
                    jnp.int32)
    if valid_words is not None:
        zero = lax.full((bq, BIN_W), 0, jnp.int32)
    vals = [inf] * (survivors + 1)  # running sorted smallest per lane
    gidx = [none] * survivors       # group index of each survivor
    # lax primitives, not their jnp twins, inside the unrolled loop: it
    # binds ~1,500 ops a tile, and every jnp call re-enters jit's Python
    # machinery (a nested trace some 15 frames deep) to emit the same op
    # — seconds of each process's first call (root PERF.md, PR 29)
    for g in range(n_groups):
        cur_v = lax.slice_in_dim(s, g * BIN_W, (g + 1) * BIN_W, axis=1)
        if valid_words is not None:
            word = lax.slice_in_dim(valid_words, g // 32 * BIN_W,
                                    (g // 32 + 1) * BIN_W, axis=1)
            bit = lax.full((bq, BIN_W), np.int32(
                np.uint32(1 << g % 32).view(np.int32)), jnp.int32)
            cur_v = lax.select(lax.ne(lax.bitwise_and(word, bit), zero),
                               cur_v, inf)
        cur_g = (lax.full((bq, BIN_W), g, jnp.int32) if payload is None
                 else lax.slice_in_dim(payload, g * BIN_W, (g + 1) * BIN_W,
                                       axis=1))
        for j in range(survivors):
            less = lax.lt(cur_v, vals[j])
            disp_v = lax.max(cur_v, vals[j])
            disp_g = lax.select(less, gidx[j], cur_g)
            vals[j] = lax.min(cur_v, vals[j])
            gidx[j] = lax.select(less, cur_g, gidx[j])
            cur_v, cur_g = disp_v, disp_g
        vals[survivors] = lax.min(vals[survivors], cur_v)
    ds, is_ = [], []
    for j in range(survivors):
        ds.append(vals[j])
        # +inf never displaces (strict <), so an unfilled payload slot
        # still reads the sentinel it started with
        is_.append(gidx[j] if payload is not None else jnp.where(
            jnp.isfinite(vals[j]),
            ti * tile_n + gidx[j] * BIN_W + lane, _I32MAX))
    cd = jnp.concatenate(ds, axis=-1)   # [BQ, survivors * 128]
    ci = jnp.concatenate(is_, axis=-1)
    return cd, ci, vals[survivors]      # bound: [BQ, 128]


def _stream_kernel(q_ref, *refs, tile_n: int, survivors: int, out_w: int,
                   bound_w: int, n_tiles: int, nd: int, precision: str,
                   n_parts: int, chunk_w: int, aux_rows: int = 8,
                   fused: bool = False, keep: Optional[int] = None,
                   pq_shape=None, terms: str = BF16X3_TERMS[0]):
    """One launch per (batch, shard): the db-side arrays stay in HBM and
    stream tile-by-tile through TWO VMEM scratch slots via explicit
    async copies — tile i+1's HBM->VMEM copy overlaps tile i's MXU
    distance pass and VPU select (the double buffer).  The running
    (distance, index) candidate list lives in the VMEM-resident output
    block across the whole tile loop and flushes to HBM once per query
    block; each tile's survivors land at the tile's column offset, so
    the output layout (and every value in it — the shared emitters do
    the selection) is bitwise-identical to the tiled kernel's.

    Ref layout (inputs, then outputs, then scratch):
      [qsc VMEM ref]                int8 only: [BQ, 128] query scales
      [db part HBM refs x n_parts]  bf16x3: th, tl (th alone where
                                    ``terms`` has no hl) | bf16x3f: t3
                                    | int8: quantized db | else: db
      tn HBM ref                    [aux_rows, n_tiles * tile_n] row
                                    norms (int8: norms over scales)
      d_ref, i_ref, b_ref           full-width VMEM output blocks
      [part VMEM buffers x n_parts] (2, tile_n, chunk_w) double buffers
      tn VMEM buffer                (2, aux_rows, tile_n)
      sem                           DMA semaphores (2, n_parts + 1)
    """
    qsc_ref = None
    if precision == "int8":
        qsc_ref, refs = refs[0], refs[1:]
    parts_hbm = refs[:n_parts]
    tn_hbm = refs[n_parts]
    d_ref, i_ref, b_ref = refs[n_parts + 1 : n_parts + 4]
    part_bufs = refs[n_parts + 4 : 2 * n_parts + 4]
    tn_buf = refs[2 * n_parts + 4]
    sem = refs[2 * n_parts + 5]
    q = q_ref[:]
    dn = (((1,), (1,)), ((), ()))

    def part_dma(j, ti, c, slot):
        return pltpu.make_async_copy(
            parts_hbm[j].at[pl.ds(ti * tile_n, tile_n),
                            pl.ds(c * chunk_w, chunk_w)],
            part_bufs[j].at[slot],
            sem.at[slot, j],
        )

    def tn_dma(ti, slot):
        return pltpu.make_async_copy(
            tn_hbm.at[:, pl.ds(ti * tile_n, tile_n)],
            tn_buf.at[slot],
            sem.at[slot, n_parts],
        )

    def start_parts(ti, c, slot):
        for j in range(n_parts):
            part_dma(j, ti, c, slot).start()

    def chunk_qt(c, bufs):
        """[BQ, tile_n] score contribution of dim chunk ``c`` — the
        same per-chunk arithmetic as the tiled kernel body (the query
        chunk is a static slice of the full-dim block here where the
        tiled kernel's BlockSpec sliced it; the cast/dot sequence is
        identical, which the bitwise contract rests on).  int8 returns
        the raw int32 partial dot (exact integer accumulation; the one
        f32 rescale happens at emit time, like the tiled kernel)."""
        if precision == "pq":
            # nd == 1 always: the whole per-query LUT block scores the
            # streamed byte-code tile in one shared dot
            codes_buf, = bufs
            return _pq_onehot_qt(q, codes_buf, tile_n=tile_n,
                                 pq_shape=pq_shape)
        # the launch's dim chunk: DIM_CHUNK by the rule (dim_chunking),
        # another width only where a test hands one to every strategy
        qw = q.shape[1] // nd
        qc = q[:, c * qw : (c + 1) * qw]
        if precision == "int8":
            t, = bufs
            return lax.dot_general(qc, t, dn,
                                   preferred_element_type=jnp.int32)
        if precision == "bf16x3":
            # [th, tl], or [th] where ``terms`` drops the low part
            return _split_qt(qc, bufs[0], bufs[-1], terms)
        if precision == "bf16x3f":
            t3, = bufs
            qh = qc.astype(jnp.bfloat16)
            ql = (qc - qh.astype(jnp.float32)).astype(jnp.bfloat16)
            q3 = jnp.concatenate([qh, qh, ql], axis=1)
            return lax.dot_general(q3, t3, dn,
                                   preferred_element_type=jnp.float32)
        t, = bufs  # "highest"
        return lax.dot_general(qc, t, dn,
                               preferred_element_type=jnp.float32,
                               precision=lax.Precision.HIGHEST)

    # warm-up: tile 0's first chunk + row norms start before the loop
    start_parts(0, 0, 0)
    tn_dma(0, 0).start()

    # fused arm: the early-out carry is ceil(keep / 128) running order
    # statistics per lane of the emitted per-tile lane minima; armed
    # only when the depth stays inside MAX_CARRY_DEPTH (a deeper carry
    # unrolls more insertion steps per tile than the select it skips)
    depth = 0
    if fused and keep is not None:
        depth = -(-int(keep) // BIN_W)
    armed = fused and 0 < depth <= MAX_CARRY_DEPTH
    bq = q.shape[0]

    def tile_body(ti, carry):
        qt = None
        for c in range(nd):  # nd is static: the chunk loop unrolls
            slot = (ti * nd + c) % 2
            for j in range(n_parts):
                part_dma(j, ti, c, slot).wait()
            # prefetch the NEXT step while this chunk computes: the
            # other slot's previous occupant was consumed last step
            nxt = (ti * nd + c + 1) % 2
            if c + 1 < nd:
                start_parts(ti, c + 1, nxt)
            else:
                @pl.when(ti + 1 < n_tiles)
                def _():
                    start_parts(ti + 1, 0, nxt)
                    tn_dma(ti + 1, (ti + 1) % 2).start()
            qt_c = chunk_qt(c, [part_bufs[j][slot] for j in range(n_parts)])
            # same accumulation order as the tiled kernel's qt scratch
            # (int8: exact int32 adds — order-independent by construction)
            qt = qt_c if qt is None else qt + qt_c
        tn_dma(ti, ti % 2).wait()
        if precision == "int8":
            # the one f32 rescale, same op sequence as the tiled
            # write() (scales at row 8 of the 16-row stacked aux)
            qt = ((qt.astype(jnp.float32) * qsc_ref[:, 0:1])
                  * tn_buf[ti % 2][8:9, :])
        off = pl.multiple_of(ti * out_w, out_w)
        boff = pl.multiple_of(ti * bound_w, bound_w)
        if not armed:
            cd, ci, bound = _emit_select_grouped(
                ti, qt, tn_buf[ti % 2], tile_n=tile_n, survivors=survivors)
            d_ref[:, pl.ds(off, out_w)] = cd
            i_ref[:, pl.ds(off, out_w)] = ci
            b_ref[:, pl.ds(boff, bound_w)] = bound
            return carry

        # ---- fused early-out path --------------------------------------
        # the SAME score expression the emitter computes — the
        # bitwise contract of the non-skipped tiles rests on this
        s = tn_buf[ti % 2][0:1, :] - 2.0 * qt  # [BQ, T]
        n_groups = tile_n // BIN_W
        lane_min = s[:, 0:BIN_W]
        for g in range(1, n_groups):
            lane_min = jnp.minimum(lane_min,
                                   s[:, g * BIN_W : (g + 1) * BIN_W])
        # threshold: with every lane holding `depth` carry stats <= thr,
        # at least 128*depth >= keep emitted candidates score <= thr, so
        # the final keep-th smallest emitted value is <= thr — a tile
        # whose WHOLE score block is strictly above thr (for every query
        # row of the block) can neither place a candidate in the final
        # top-keep nor lower the exclusion bound below the keep-th value
        thr = jnp.max(carry[depth - 1], axis=-1)  # [BQ]
        tile_min = jnp.min(lane_min, axis=-1)     # [BQ]
        skip = jnp.all(tile_min > thr)

        @pl.when(jnp.logical_not(skip))
        def _select():
            cd, ci, bound = _emit_select_grouped_scores(
                ti, s, tile_n=tile_n, survivors=survivors)
            d_ref[:, pl.ds(off, out_w)] = cd
            i_ref[:, pl.ds(off, out_w)] = ci
            b_ref[:, pl.ds(boff, bound_w)] = bound

        @pl.when(skip)
        def _pad():
            # a skipped tile's blocks pad exactly like kernel padding:
            # +inf candidates / sentinel indices lose every final
            # select, +inf bounds never bind — and by the predicate no
            # real value here could have either (strictly above thr)
            d_ref[:, pl.ds(off, out_w)] = jnp.full(
                (bq, out_w), jnp.inf, jnp.float32)
            i_ref[:, pl.ds(off, out_w)] = jnp.full(
                (bq, out_w), _I32MAX, jnp.int32)
            b_ref[:, pl.ds(boff, bound_w)] = jnp.full(
                (bq, bound_w), jnp.inf, jnp.float32)

        # carry update: insert this tile's per-lane minima (each IS an
        # emitted candidate — the lane's first survivor) into the sorted
        # per-lane stats.  Unconditional on purpose: a SKIPPED tile's
        # lane minima all exceed thr >= every carry stat, so insertion
        # is a provable no-op there — cheaper than a conditional carry
        cur = lane_min
        new = []
        for j in range(depth):
            new.append(jnp.minimum(carry[j], cur))
            cur = jnp.maximum(carry[j], cur)
        return tuple(new)

    init = (tuple(jnp.full((bq, BIN_W), jnp.inf, jnp.float32)
                  for _ in range(depth)) if armed else 0)
    lax.fori_loop(0, n_tiles, tile_body, init)


def default_backend_is_tpu() -> bool:
    """Whether kernels compile (True) or run in Pallas interpret mode
    (False, the CPU test suite) when the caller leaves ``interpret`` at
    None — decided at trace time from ``jax.default_backend()``.
    ShardedKNN resolves it once (``_pallas_setup``), passes the value
    down to the kernel and reports that same value in
    ``stats["pallas_knobs"]["interpret"]``; it takes no ``interpret``
    argument from its callers."""
    return jax.default_backend() == "tpu"


def _vmem_device_kind() -> str:
    """The device kind whose VMEM a launch is budgeted for: the chip's
    own on a TPU backend; off it (interpret mode, deviceless AOT
    compiles) the target kind's."""
    from knn_tpu.analysis import vmem

    return (jax.devices()[0].device_kind if default_backend_is_tpu()
            else vmem.TARGET_DEVICE_KIND)


def effective_block_q(block_q: int, query_rows: int) -> int:
    """The query block a launch over ``query_rows`` queries runs: the
    requested one, capped to the batch (8 sublanes at the least)."""
    return min(block_q, max(8, query_rows))


def _vmem_limit_bytes(kernel: str, precision: str, **geometry) -> int:
    """The scoped-VMEM limit a compiled launch requests
    (knn_tpu.analysis.vmem — the ONE home of the arithmetic and of the
    rule).  Where the model is calibrated against what Mosaic reports
    (bf16x3) the request is the modeled footprint of
    this geometry plus its error, and a geometry that cannot fit the
    device is refused HERE, naming the knobs to change, instead of by
    Mosaic's allocator dump.  Every other arm asks for the device's
    whole VMEM and Mosaic decides.  Deviceless AOT compiles trace
    off-TPU and budget for the target device kind."""
    from knn_tpu.analysis import vmem

    kind = _vmem_device_kind()
    budget = vmem.budget_for(kind)
    if not vmem.calibrated(precision):
        return budget
    need = sum(vmem.kernel_bytes(kernel=kernel, **geometry).values())
    if need > budget:
        raise ValueError(
            f"kernel={kernel!r} at block_q={geometry['block_q']}, "
            f"tile_n={geometry['tile_n']} over {geometry['n_tiles']} db "
            f"tiles needs ~{need // vmem.MIB} MiB of VMEM; {kind} has "
            f"{budget // vmem.MIB} MiB.  Lower block_q or tile_n"
            + ("" if kernel == "tiled" else ", or use kernel='tiled'"))
    return vmem.limit_bytes(need, budget)


def dim_chunking(dim: int, *, precision: str,
                 kernel: str = "tiled") -> Tuple[int, int]:
    """``(chunk_w, nd)`` of a launch over ``dim`` columns (padded here
    to the ``DIM_CHUNK`` grain): the width of one dim chunk and how
    many a row tile's width has.  knn_tpu.analysis.vmem.dim_chunking is
    the ONE home of the rule: the whole padded width under the tiled
    kernel (a tile too large for VMEM at that width is cut by rows,
    :func:`row_blocking`), 128 columns under the other two."""
    from knn_tpu.analysis import vmem

    return vmem.dim_chunking(_round_up(dim, DIM_CHUNK), kernel=kernel,
                             precision=precision)


def row_blocking(dim: int, *, tile_n: int, block_q: int, precision: str,
                 kernel: str = "tiled", terms: str = BF16X3_TERMS[0],
                 survivors: Optional[int] = None,
                 masked: bool = False) -> Tuple[int, int]:
    """``(row_block, row_steps)`` of a launch over ``dim`` columns
    (padded here to the ``DIM_CHUNK`` grain): the rows of a tile that
    one grid step multiplies at the whole width, and the steps a tile
    takes.  knn_tpu.analysis.vmem.row_blocking is the ONE home of the
    rule (under the tiled kernel the whole tile wherever it fits the
    device's VMEM beside the rest of the launch, else the largest block
    of whole 128-row groups that divides the tile and fits; under the
    other two kernels always the whole tile); this hands it what the
    launch sees — the RESOLVED tile and query block, the row parts
    ``terms`` leaves to stream, whether it carries validity words — and
    the budget of the device it compiles for (off-TPU, the target
    kind's: interpret mode cuts the rows as the chip would).  Nothing
    sets it: a bare launch asks here, and ShardedKNN asks here ONCE for
    its program (``_pallas_setup``), hands the kernel the answer and
    reports that answer (``row_block``, ``row_steps`` on the
    ``certified.call`` event)."""
    from knn_tpu.analysis import vmem

    return vmem.row_blocking(
        _round_up(dim, DIM_CHUNK), tile_n=tile_n, block_q=block_q,
        precision=precision, kernel=kernel,
        db_parts=(1 if precision == "bf16x3" and "hl" not in terms
                  else None),
        out_w=_geometry(tile_n, survivors)[2], masked=masked,
        budget_bytes=vmem.budget_for(_vmem_device_kind()))


def _pad_axis(x, multiple: int, axis: int, fill: float = 0.0):
    """parallel.mesh.pad_to_multiple without the size return (imported
    lazily: ops must not import the parallel package at module scope)."""
    from knn_tpu.parallel.mesh import pad_to_multiple

    return pad_to_multiple(x, multiple, axis, fill=fill)[0]


def _pad_rows(db: jax.Array, tile_n: int) -> jax.Array:
    """The rows as the kernel tiles them: float32, PAD_VAL rows up to a
    whole tile, zero columns up to whole dim chunks."""
    with jax.named_scope(SCOPE_OPERAND_PREP):
        db = _pad_axis(db.astype(jnp.float32), tile_n, 0, fill=PAD_VAL)
        return _pad_axis(db, DIM_CHUNK, 1)


def _split_rows(db: jax.Array, with_lo: bool) -> Tuple[jax.Array, ...]:
    """The bf16 halves of the padded rows the kernel streams: the high
    half, and with ``with_lo`` the bf16 of what the cast left.

    "What the cast left" is taken against ``lax.reduce_precision(db, 8,
    7)``, the same value as the cast's (round to nearest even) by an
    operation XLA may not skip.  Taken against the cast's own round
    trip, ``db - f32(bf16(db))`` in ONE fusion, the TPU compiler keeps
    the cast's result in float32 (excess precision: on the v5e a jitted
    ``f32(bf16(x))`` IS ``x``), so the difference and with it the low
    half come out ZERO on the chip: the kernel's ``hl`` term adds
    nothing and its score is off by 2^-9 / sqrt(3) of sqrt(sum q_i^2
    t_i^2).  Measured on 1,536-column unit rows (PERF.md section 6,
    PR 43): up to 4.7e-4, 3.9 times ``kernel_tolerance``, and one query
    in about 1,400 checked came back with a row missing; against the
    rounding 1.1e-6, numpy's own split to the bit.  The resident
    placement (:func:`row_operands`) has taken it so since PR 43,
    :func:`_bin_candidates`' in-call split since PR 49 (until then a
    placement whose operands stayed ``per_call`` multiplied a zero low
    half on the chip)."""
    with jax.named_scope(SCOPE_OPERAND_PREP):
        th = db.astype(jnp.bfloat16)
        if not with_lo:
            return (th,)
        back = lax.reduce_precision(db, exponent_bits=8, mantissa_bits=7)
        return th, (db - back).astype(jnp.bfloat16)


def _row_norms(db: jax.Array) -> jax.Array:
    """Full-dim float32 squared norms of the padded rows."""
    with jax.named_scope(SCOPE_OPERAND_PREP):
        return jnp.sum(db * db, axis=-1)


def row_operands(db: jax.Array, *, tile_n: int,
                 with_lo: bool) -> Tuple[jax.Array, ...]:
    """``(th[, tl], norms)`` of one db (shard): the row operands the
    "bf16x3" kernel streams at tile ``tile_n``, by the very pieces
    :func:`_bin_candidates` forms them from in every call that hands it
    none (ONE arithmetic, so a caller that keeps them beside the rows
    and passes them as ``db_prepared`` gets that call's outputs bit for
    bit; :func:`_split_rows`).  ``th`` and ``tl`` are bf16 ``[rows_p,
    dim_p]``, ``norms`` f32 ``[rows_p]``; ``with_lo`` is whether ``"hl"`` is among the launch's
    ``terms`` (the rows' low half is streamed at all)."""
    db = _pad_rows(db, tile_n)
    return (*_split_rows(db, with_lo), _row_norms(db))


@functools.partial(
    jax.jit, static_argnames=("block_q", "tile_n", "survivors",
                              "precision", "interpret", "grid_order",
                              "kernel", "offset", "keep", "terms",
                              "dim_chunk", "row_block")
)
def _bin_candidates(
    queries: jax.Array,
    db: jax.Array,
    *,
    block_q: int,
    tile_n: int,
    survivors: Optional[int],
    precision: str,
    interpret: bool,
    grid_order: str = "query_major",
    kernel: str = "tiled",
    db_int8: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    offset: float = 0.0,
    keep: Optional[int] = None,
    db_pq: Optional[Tuple[jax.Array, jax.Array]] = None,
    terms: str = BF16X3_TERMS[0],
    dim_chunk: Optional[int] = None,
    db_prepared: Optional[Tuple[jax.Array, ...]] = None,
    valid_words: Optional[jax.Array] = None,
    row_block: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Kernel launch on padded shapes.  Returns

      cand_d [Qp, W]  f32  per-bin survivor scores (squared L2 - ||q||^2),
      cand_i [Qp, W]  i32  their global db row indices (sentinel = i32 max),
      bounds [Qp, T*B] f32 per-tile per-bin exclusion bounds (each db
                           tile's block is disjoint; callers lane-min
                           the whole row for the scalar bound).

    W = n_tiles * out_w (survivors per bin, lane-padded per tile).  Zero
    dim-padding preserves scores exactly; PAD_VAL row-padding scores
    ~1e36 so pads never surface (module docstring).  ``kernel`` picks
    the db-streaming strategy (KERNELS); outputs are bitwise-identical
    across strategies.

    ``precision="int8"`` adds a quantized coarse arm (ops.quantize):
    queries quantize per call in an XLA prologue (like the bf16 split);
    the db either quantizes the same way (``db_int8=None`` — the
    convenience/test path) or arrives PRE-QUANTIZED as
    ``db_int8=(values int8 [N,D], scales f32 [N], row_norms f32 [N])``
    — the ShardedKNN placement path, where the f32 db never re-streams
    for the coarse pass.  ``offset`` is the translation-invariance shift
    both sides subtract before quantizing (128.0 for bvecs payloads).

    ``precision="pq"`` REQUIRES ``db_pq=(codes uint8 [N, m], codebooks
    f32 [m, C, dsub])`` (codebooks train on data — ops.pq.train_pq;
    there is no quantize-on-the-fly arm).  The query operand becomes
    the per-query LUT built in the XLA prologue; scores are against the
    RECONSTRUCTION t̂ (see ``_pq_onehot_qt``), certified by the
    per-subspace bound in ops.pq.

    ``terms`` (``BF16X3_TERMS``; "bf16x3" only) names the products of
    the split the launch forms.  A caller that has SEEN every row (and
    the batch) to be bf16-exact drops the products of their zero low
    halves: without ``hl`` the rows' low half is neither computed here
    nor streamed, without ``lh`` the kernel never forms the batch's.
    Outputs are the full sum's bit for bit on such data, and wrong on
    any other: nothing here checks.

    ``row_block`` is the rows of a tile that one grid step of the
    tiled kernel multiplies, at the whole padded width: a whole number
    of 128-row groups that divides ``tile_n``.  :func:`row_blocking`'s
    reading of this launch's shape, made here where it is None and by
    the caller where a program reports it (ShardedKNN, through
    :func:`local_certified_candidates`); tests and chip probes pass one
    to hold a cut at shapes the rule leaves whole.  Never a knob.  The
    whole tile is the launch below; a smaller block is ``_row_call``'s,
    whose bins, candidates and bounds are the uncut tile's, bit for
    bit: a step's product is whole, and the steps share the select's
    running arrays alone (``_row_step``).

    ``dim_chunk`` is the width of one dim chunk, a multiple of
    ``DIM_CHUNK`` that divides the padded width: :func:`dim_chunking`'s
    reading where it is None (the whole width under ``kernel="tiled"``,
    128 columns under the other two: a function of the width, the
    precision and the kernel alone, so no caller need hand it down),
    and what tests pass to hold the three strategies to one cut of the
    columns.  Outputs are bitwise-identical across ``kernel`` where the
    width is the same (up to 128 columns, and wherever one width is
    handed to all three); across widths they agree to the f32
    accumulation order (``kernel_tolerance`` covers it).  A tile is cut
    one way or the other: a launch of several dim chunks runs whole
    tiles.

    ``db_prepared`` ("bf16x3" only) is :func:`row_operands` of ``db`` at
    this ``tile_n`` and these ``terms``, made once by a caller whose
    rows stay (ShardedKNN's resident placement): the launch streams them
    and forms nothing of the corpus's size, no padded copy, no cast, no
    norms.  ``None`` forms them here, as every call always did.

    ``valid_words`` (uint32 or int32 ``[queries, n_tiles *
    valid_words_per_tile(tile_n)]``, or wider: columns past those are
    never read; ``kernel="tiled"`` only) is a
    per-query predicate over the rows, in the layout of
    :func:`valid_word_position` at this ``tile_n``: a row whose bit is 0
    scores +inf for that query before the bin-select
    (``_emit_select_grouped_scores``), so it is no candidate and lowers
    no bound.  Row padding must be marked invalid by the caller; query
    padding is (zero words).  ``None`` is the launch it always was,
    operation for operation."""
    if valid_words is not None and kernel != "tiled":
        raise ValueError(
            f"kernel={kernel!r} takes no per-query validity words: only "
            f"the tiled kernel's body applies them; use kernel='tiled'")
    queries = _pad_axis(queries.astype(jnp.float32), block_q, 0)
    queries = _pad_axis(queries, DIM_CHUNK, 1)
    n_rows = db.shape[0]
    qp, dim = queries.shape
    if db_prepared is None:
        db = _pad_rows(db, tile_n)
        rows_p = db.shape[0]
    else:
        *row_parts, row_norms = db_prepared
        rows_p = _round_up(n_rows, tile_n)
        want = [(rows_p, dim)] * (1 + ("hl" in terms)) + [(rows_p,)]
        if precision != "bf16x3" or [
                x.shape for x in db_prepared] != want:
            raise ValueError(
                f"db_prepared of shapes {[x.shape for x in db_prepared]} "
                f"is not row_operands of {n_rows} rows at tile_n={tile_n}, "
                f"terms={terms!r} ({want}), or precision={precision!r} "
                f"streams no such operands")
    n_tiles = rows_p // tile_n
    _, survivors, out_w, bound_w = _geometry(tile_n, survivors)

    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    if grid_order not in GRID_ORDERS:
        raise ValueError(f"grid_order {grid_order!r} not in {GRID_ORDERS}")
    if kernel not in KERNELS:
        raise ValueError(f"kernel {kernel!r} not in {KERNELS}")
    if terms not in BF16X3_TERMS or (
            terms != BF16X3_TERMS[0] and precision != "bf16x3"):
        raise ValueError(
            f"terms {terms!r} not in {BF16X3_TERMS}, or dropped from a "
            f"product that precision={precision!r} does not form")
    if kernel in ("streaming", "fused") and grid_order != "query_major":
        # the streaming/fused launches have no db grid axis to reorder:
        # their tile loop is inherently query-major.  Refuse rather than
        # silently ignore the knob.
        raise ValueError(
            f"kernel={kernel!r} streams the db inside one launch; "
            f"grid_order='db_major' does not apply")
    if kernel == "fused" and precision == "pq":
        # the fused early-out's bitwise argument (a skipped tile's
        # scores all strictly exceed an upper bound on the final
        # (m+2)-th smallest EMITTED candidate) was established for the
        # tn - 2·qt score pipeline whose emitted values the carry
        # tracks.  PQ's scores are against the RECONSTRUCTION t̂, and
        # its certificate separately bounds the true-row distance — the
        # carry-soundness argument has NOT been extended to compose
        # with that second bound, so the fused arm refuses rather than
        # ship an unproven skip predicate.  Use kernel="streaming".
        raise ValueError(
            "kernel='fused' is not certified for precision='pq': the "
            "early-out carry-soundness argument has not been extended "
            "to reconstruction-space scores; use 'streaming' or 'tiled'")
    if dim_chunk is None:
        dim_chunk, nd = dim_chunking(dim, precision=precision, kernel=kernel)
    elif dim_chunk % DIM_CHUNK or dim % dim_chunk:
        raise ValueError(
            f"dim_chunk={dim_chunk} must be a multiple of {DIM_CHUNK} "
            f"that divides the padded width {dim}")
    else:
        nd = dim // dim_chunk
    if row_block is None:
        row_block = tile_n if nd > 1 else row_blocking(
            dim, tile_n=tile_n, block_q=block_q, precision=precision,
            kernel=kernel, terms=terms, survivors=survivors,
            masked=valid_words is not None)[0]
    elif row_block % BIN_W or tile_n % row_block or (
            row_block < tile_n and (
                nd > 1 or kernel != "tiled" or precision == "pq")):
        raise ValueError(
            f"row_block={row_block} must be a whole number of {BIN_W}-row "
            f"groups that divides tile_n={tile_n}, and the tile itself "
            f"under kernel={kernel!r}, precision={precision!r} and "
            f"{nd} dim chunks")
    pq_shape = None
    queries_in = queries
    q_extra = []  # int8: the per-query-row scale block rides as an input
    aux_rows = 8
    if precision in ("bf16x3", "bf16x3f"):
        # the high/low split of the db happens ONCE in XLA; the kernel
        # streams bf16 tiles and never re-derives them per query block
        if db_prepared is None:
            row_parts = _split_rows(db, "hl" in terms)
        if precision == "bf16x3":
            db_inputs = list(row_parts)
            chunk_w = dim_chunk
        else:
            th, tl = row_parts
            # per dim chunk c the fused contraction reads [th_c|tl_c|th_c]
            th3 = th.reshape(db.shape[0], nd, dim_chunk)
            tl3 = tl.reshape(db.shape[0], nd, dim_chunk)
            t3 = jnp.concatenate([th3, tl3, th3], axis=2).reshape(
                db.shape[0], nd * 3 * dim_chunk)
            db_inputs = [t3]
            chunk_w = 3 * dim_chunk
    elif precision == "int8":
        from knn_tpu.ops.quantize import quantize_rows

        # queries quantize per call (one XLA prologue pass, like the
        # bf16 split); the db either quantizes here too (convenience /
        # test path) or arrives pre-quantized from the placement
        qi, qsc = quantize_rows(queries - offset)
        queries_in = qi
        q_extra = [jnp.broadcast_to(qsc[:, None], (qp, BIN_W))]
        if db_int8 is None:
            db_sh = db - offset
            ti, ts = quantize_rows(db_sh)
            tn_rows = jnp.sum(db_sh * db_sh, axis=-1)
        else:
            ti, ts, tn_rows = db_int8
            # tile-padding of the pre-quantized arrays: zero int8 rows at
            # zero scale dequantize to the origin, and a huge norm fill
            # makes their kernel score ~PAD_VAL — never a candidate,
            # never deflating a bin bound (same contract as PAD_VAL rows)
            ti = _pad_axis(ti, tile_n, 0)
            ti = _pad_axis(ti, DIM_CHUNK, 1)
            ts = _pad_axis(ts[:, None], tile_n, 0)[:, 0]
            tn_rows = _pad_axis(tn_rows[:, None], tile_n, 0,
                                fill=PAD_VAL)[:, 0]
        db_inputs = [ti]
        chunk_w = dim_chunk
        # the db-side aux block stacks norms over scales ([16, N]: rows
        # 0-7 tn broadcast, 8-15 scales broadcast) so BOTH stream through
        # the one lane-major aux slot the f32 path already has
        aux_rows = 16
    elif precision == "pq":
        if db_pq is None:
            raise ValueError(
                "precision='pq' requires db_pq=(codes, codebooks): PQ "
                "codebooks train on data (ops.pq.train_pq) — there is "
                "no quantize-on-the-fly arm")
        codes, books = db_pq
        m_sub, ncodes, dsub = books.shape
        pq_shape = (m_sub, ncodes)
        # per-query LUT prologue (the PQ analogue of the bf16 split /
        # int8 quantization prologues): queries zero-pad to the trained
        # m*dsub width — zero-padding is exactly how the codebooks were
        # trained, so the subspace split matches
        qv = queries
        if qv.shape[1] < m_sub * dsub:
            qv = jnp.pad(qv, ((0, 0), (0, m_sub * dsub - qv.shape[1])))
        qv = qv[:, : m_sub * dsub].reshape(qp, m_sub, dsub)
        lut = (jnp.einsum("qmd,mcd->qmc", qv, books)
               - 0.5 * jnp.sum(books * books, axis=-1)[None])
        queries_in = _pad_axis(
            lut.reshape(qp, m_sub * ncodes).astype(jnp.float32), BIN_W, 1)
        if codes.shape[0] != n_rows:
            raise ValueError(
                f"db_pq codes rows ({codes.shape[0]}) do not match the "
                f"db rows ({n_rows}) the rescore gathers from")
        tn_rows = jnp.zeros((codes.shape[0],), jnp.float32)
        codes = _pad_axis(codes, tile_n, 0)
        tn_rows = _pad_axis(tn_rows[:, None], tile_n, 0,
                            fill=PAD_VAL)[:, 0]
        db_inputs = [codes]
        # NOTE: the streamed code block is [tile_n, m] uint8 — at small
        # m this is narrower than the 128-lane tile; fine in interpret
        # mode, and the compiled-mode geometry goes through the same
        # on-hardware gate every new arm goes through before promotion
        chunk_w = m_sub
        nd = 1  # the LUT scores in ONE dot; there is no dim-chunk loop
    else:
        db_inputs = [db]
        chunk_w = dim_chunk
    if precision == "int8":
        tnorm = jnp.concatenate([
            jnp.broadcast_to(tn_rows[None, :], (8, db.shape[0])),
            jnp.broadcast_to(ts[None, :].astype(jnp.float32),
                             (8, db.shape[0])),
        ], axis=0)
    elif precision == "pq":
        # pad-fill carrier only: 0 on valid rows (the LUT carries the
        # reconstruction norm term), PAD_VAL on tile padding
        tnorm = jnp.broadcast_to(tn_rows[None, :], (8, db.shape[0]))
    else:
        # full-dim db row norms, f32, broadcast to 8 sublanes so the
        # kernel reads them as a lane-major [8, tile_n] block
        if db_prepared is None:
            row_norms = _row_norms(db)
        with jax.named_scope(SCOPE_OPERAND_PREP):
            tnorm = jnp.broadcast_to(row_norms[None, :], (8, rows_p))
    out_shape = [
        jax.ShapeDtypeStruct((qp, n_tiles * out_w), jnp.float32),
        jax.ShapeDtypeStruct((qp, n_tiles * out_w), jnp.int32),
        jax.ShapeDtypeStruct((qp, n_tiles * bound_w), jnp.float32),
    ]

    if kernel in ("streaming", "fused"):
        return _stream_call(
            queries_in, db_inputs, tnorm, out_shape, qp=qp,
            dim=queries_in.shape[1],
            block_q=block_q, tile_n=tile_n, survivors=survivors,
            out_w=out_w, bound_w=bound_w, n_tiles=n_tiles, nd=nd,
            precision=precision, chunk_w=chunk_w, interpret=interpret,
            q_extra=q_extra, aux_rows=aux_rows,
            fused=kernel == "fused", keep=keep, pq_shape=pq_shape,
            terms=terms,
        )

    db_major = grid_order == "db_major"
    words, wpt = [], valid_words_per_tile(tile_n)
    if valid_words is not None:
        if valid_words.shape[1] < n_tiles * wpt:
            raise ValueError(
                f"valid_words of shape {valid_words.shape} is not "
                f"{n_tiles} tiles of {wpt} words a query "
                f"(valid_word_position at tile_n={tile_n})")
        words = [_pad_axis(lax.bitcast_convert_type(
            valid_words, jnp.int32), block_q, 0)]
    if row_block < tile_n:
        return _row_call(
            queries_in, words, db_inputs, q_extra, tnorm, out_shape,
            block_q=block_q, tile_n=tile_n, row_block=row_block,
            survivors=survivors, precision=precision, terms=terms,
            db_major=db_major, interpret=interpret, chunk_w=chunk_w,
            aux_rows=aux_rows)
    body = functools.partial(
        _kernel, tile_n=tile_n, survivors=survivors, nd=nd,
        precision=precision, ti_axis=0 if db_major else 1,
        pq_shape=pq_shape, terms=terms, masked=bool(words),
    )
    # the query operand block: one dim-chunk slice per grid step for the
    # feature-chunked arms; PQ's LUT has no chunk loop (nd == 1) and
    # rides as ONE lane-padded block
    q_block_w = queries_in.shape[1] if precision == "pq" else dim_chunk
    if db_major:
        grid = (n_tiles, qp // block_q, nd)
        q_idx = lambda t, q, d: (q, d)      # noqa: E731
        t_idx = lambda t, q, d: (t, d)      # noqa: E731
        n_idx = lambda t, q, d: (0, t)      # noqa: E731
        o_idx = lambda t, q, d: (q, t)      # noqa: E731
    else:
        grid = (qp // block_q, n_tiles, nd)
        q_idx = lambda q, t, d: (q, d)      # noqa: E731
        t_idx = lambda q, t, d: (t, d)      # noqa: E731
        n_idx = lambda q, t, d: (0, t)      # noqa: E731
        o_idx = lambda q, t, d: (q, t)      # noqa: E731
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            # db_major: the outer axis is the db tile, whose input block
            # is revisited across inner steps — it must stay sequential
            dimension_semantics=(
                ("arbitrary", "arbitrary", "arbitrary") if db_major
                else ("parallel", "arbitrary", "arbitrary")),
            vmem_limit_bytes=_vmem_limit_bytes(
                "tiled", precision,
                block_q=block_q, tile_n=tile_n, n_tiles=n_tiles,
                nd=nd, out_w=out_w, bound_w=bound_w,
                db_block=sum(tile_n * chunk_w * x.dtype.itemsize
                             for x in db_inputs),
                aux_rows=aux_rows,
                q_block=block_q * q_block_w * queries_in.dtype.itemsize,
                q_extra=(len(q_extra) * BIN_W + len(words) * wpt)
                * block_q * 4),
        )
    db_specs = [pl.BlockSpec((tile_n, chunk_w), t_idx) for _ in db_inputs]
    if db_major:
        s_idx = lambda t, q, d: (q, 0)      # noqa: E731
    else:
        s_idx = lambda q, t, d: (q, 0)      # noqa: E731
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, q_block_w), q_idx),
            *[pl.BlockSpec((block_q, wpt), o_idx) for _ in words],
            *db_specs,
            *[pl.BlockSpec((block_q, BIN_W), s_idx) for _ in q_extra],
            pl.BlockSpec((aux_rows, tile_n), n_idx),
        ],
        out_specs=[
            pl.BlockSpec((block_q, out_w), o_idx),
            pl.BlockSpec((block_q, out_w), o_idx),
            pl.BlockSpec((block_q, bound_w), o_idx),
        ],
        out_shape=out_shape,
        # the qt accumulation scratch is only touched when dim spans
        # multiple chunks (a launch handed a dim chunk); at one chunk
        # skipping it returns VMEM to the pipeline
        # int8 accumulates the raw int32 dot across chunks (exact);
        # the f32 paths accumulate the scaled f32 score
        scratch_shapes=[] if nd == 1 else [
            pltpu.VMEM((block_q, tile_n),
                       jnp.int32 if precision == "int8" else jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(queries_in, *words, *db_inputs, *q_extra, tnorm)


def _row_call(queries, words, db_inputs, q_extra, tnorm, out_shape, *,
              block_q, tile_n, row_block, survivors, precision, terms,
              db_major, interpret, chunk_w, aux_rows):
    """The tiled ``pallas_call`` of a row tile cut by ROWS
    (``row_blocking``: a tile too wide for VMEM at its whole padded
    width): the grid's third axis walks the tile's ``tile_n //
    row_block`` row blocks, every step multiplies the whole width
    (``_kernel`` at one dim chunk; its blocks' shapes tell it that it
    holds a part of the tile) and the bin-select's running arrays pass
    from step to step in ONE VMEM scratch (``_row_step``).  The query
    block's mapped index moves with neither the tile nor its row block,
    so it is fetched once a query block under ``query_major``.  A
    launch of one step a tile never enters here
    (:func:`_bin_candidates`' own ``pallas_call``).

    ``words`` (a list of none or one: the padded int32 validity words)
    are cut with the rows: a step's block is the whole word blocks of
    its groups (a whole number of ``32 * 128`` rows a step), or the one
    block that holds them all, which ``_row_step`` shifts."""
    qp, q_w = queries.shape
    n_tiles = tnorm.shape[1] // tile_n
    row_steps = tile_n // row_block
    step_groups = row_block // BIN_W
    out_w = out_shape[0].shape[1] // n_tiles
    bound_w = out_shape[2].shape[1] // n_tiles
    # the words of one step: row_blocking keeps a masked step to whole
    # word blocks, or to a part of one
    words_w = max(1, step_groups // 32) * BIN_W
    if words and step_groups % 32 and 32 % step_groups:
        raise ValueError(
            f"row_block={row_block} of a launch with validity words is "
            f"neither a whole number of {32 * BIN_W} rows nor divides "
            f"them")
    word_blocks = valid_words_per_tile(tile_n) // words_w

    def axes(index_map):
        """An index map over (query block, tile, row step) in the
        grid's own order of its axes."""
        if db_major:
            return lambda t, q, x: index_map(q, t, x)
        return index_map

    q_idx = axes(lambda q, t, x: (q, 0))
    t_idx = axes(lambda q, t, x: (t * row_steps + x, 0))
    n_idx = axes(lambda q, t, x: (0, t * row_steps + x))
    o_idx = axes(lambda q, t, x: (q, t))
    w_idx = axes(lambda q, t, x: (
        q, t * word_blocks + (x * step_groups // 32 if step_groups < 32
                              else x)))
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            # as the one-step launch: under db_major the outer axis is
            # the db tile and must stay sequential
            dimension_semantics=(
                ("arbitrary", "arbitrary", "arbitrary") if db_major
                else ("parallel", "arbitrary", "arbitrary")),
            vmem_limit_bytes=_vmem_limit_bytes(
                "tiled", precision,
                block_q=block_q, tile_n=tile_n, n_tiles=n_tiles, nd=1,
                out_w=out_w, bound_w=bound_w,
                db_block=sum(row_block * chunk_w * x.dtype.itemsize
                             for x in db_inputs),
                aux_rows=aux_rows,
                q_block=block_q * q_w * queries.dtype.itemsize,
                q_extra=(len(q_extra) * BIN_W + len(words) * words_w)
                * block_q * 4, row_block=row_block,
                dim_padded=q_w),
        )
    return pl.pallas_call(
        functools.partial(
            _kernel, tile_n=tile_n, survivors=survivors, nd=1,
            precision=precision, ti_axis=0 if db_major else 1,
            terms=terms, masked=bool(words)),
        grid=((n_tiles, qp // block_q) if db_major
              else (qp // block_q, n_tiles)) + (row_steps,),
        in_specs=[
            pl.BlockSpec((block_q, q_w), q_idx),
            *[pl.BlockSpec((block_q, words_w), w_idx) for _ in words],
            *[pl.BlockSpec((row_block, chunk_w), t_idx) for _ in db_inputs],
            *[pl.BlockSpec((block_q, BIN_W), q_idx) for _ in q_extra],
            pl.BlockSpec((aux_rows, row_block), n_idx),
        ],
        out_specs=[
            pl.BlockSpec((block_q, out_w), o_idx),
            pl.BlockSpec((block_q, out_w), o_idx),
            pl.BlockSpec((block_q, bound_w), o_idx),
        ],
        out_shape=out_shape,
        # the select's running arrays, shared by the steps of a tile
        scratch_shapes=[pltpu.VMEM(
            (2 * survivors + 1, block_q, BIN_W), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(queries, *words, *db_inputs, *q_extra, tnorm)


def _self_kernel(at_ref, q_ref, *refs, tile_n: int, survivors: int,
                 terms: str, n_held: int):
    """The tiled "bf16x3" body over the row tiles that hold a launch's
    OWN rows (:func:`self_tile_candidates`): the same product
    (``_split_qt``), then the score of row ``own + r`` in query row ``r``
    turned to +inf BEFORE the insertion network, then the network and
    the emission every other launch runs (``_emit_select_grouped``, or
    ``_row_step`` where the blocks handed in are one row block of the
    tile).  The mask goes on the product, ``qt = -inf`` there, so that
    the emitters' own ``tn - 2 qt`` reads +inf: such a row is never a
    candidate (+inf never displaces: strict ``<``) and lowers no bin's
    bound, so the soundness contract holds over the rows OTHER than the
    query's own, as it does over the valid rows under validity words.
    A body of its own, so that no frame under the search's trace stack
    changes size (tests/test_dim_chunking.py's tripwire).

    ``at_ref`` (int32 ``[2]``, scalar prefetch): the first tile of the
    launch, by which ``ti`` numbers the emitted rows, and the
    (shard-local) row that the launch's first query is.  The
    ``n_held`` refs after the row norms are the first launch's whole
    outputs, aliased to this launch's and never read here."""
    qi, ti = pl.program_id(0), at_ref[0] + pl.program_id(1)
    if "hl" in terms:
        th_ref, tl_ref, tn_ref, *refs = refs
    else:
        (th_ref, tn_ref, *refs), tl_ref = refs, None
    d_ref, i_ref, b_ref, *scratch = refs[n_held:]
    qt = _split_qt(q_ref[:], th_ref[:], tl_ref, terms)
    bq, rows = qt.shape
    # query row r of this block is row ``at_ref[1] + qi * bq + r``, and
    # column c of this step row ``ti * tile_n + step * rows + c``
    own = (at_ref[1] + qi * bq - ti * tile_n - pl.program_id(2) * rows)
    qt = jnp.where(
        lax.broadcasted_iota(jnp.int32, qt.shape, 1)
        - lax.broadcasted_iota(jnp.int32, qt.shape, 0) == own,
        -jnp.inf, qt)
    if rows < tile_n:
        _row_step(scratch[0], ti, qt, tn_ref, None, d_ref, i_ref, b_ref,
                  tile_n=tile_n)
        return
    cd, ci, bound = _emit_select_grouped(
        ti, qt, tn_ref[:], tile_n=tile_n, survivors=survivors)
    d_ref[:] = cd
    i_ref[:] = ci
    b_ref[:] = bound


def self_tile_candidates(
    queries: jax.Array, db_prepared: Tuple[jax.Array, ...], cd: jax.Array,
    ci: jax.Array, bounds: jax.Array, first_row, *, block_q: int,
    tile_n: int, survivors: Optional[int], terms: str,
    row_block: Optional[int], interpret: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(cd, ci, bounds)`` of :func:`local_coarse_candidates` for
    queries that ARE rows of the db (shard) they search, with each
    query's own row out of its bins: query ``r`` is row ``first_row +
    r`` (a traced int32; outside ``[0, rows)`` where another shard
    holds it), and no other row goes, whatever its distance: an exact
    copy of the query stays a candidate.

    The kernel's launch over ALL tiles is the one it always was, body
    and operands; the tiles that hold the launch's own rows (``queries
    // tile_n + 1`` of them at most, from ``first_row // tile_n``,
    clamped to the tiles there are) are then selected ONCE MORE by
    :func:`_self_kernel`, the own row at +inf before the insertion
    network, and those tiles' candidates and bounds laid over the first
    launch's, in place.  So self leaves before the select, in the tiles
    where it lies, and every other tile's cell is the unmasked launch's
    own.  A second launch and no branch in the first: a ``pl.when``
    between the product and the select keeps Mosaic from running one
    under the other (``_row_step``).

    "bf16x3" under the tiled kernel only (what a default certified call
    runs); ``db_prepared`` is :func:`row_operands` of the shard at
    ``tile_n`` and ``terms``, ``row_block`` the resolved cut of a tile
    (:func:`row_blocking`'s reading where it is None, as the first
    launch reads it)."""
    n_q = queries.shape[0]
    queries = _pad_axis(queries.astype(jnp.float32), block_q, 0)
    queries = _pad_axis(queries, DIM_CHUNK, 1)
    qp, dim = queries.shape
    if row_block is None:
        row_block = row_blocking(
            dim, tile_n=tile_n, block_q=block_q, precision="bf16x3",
            terms=terms, survivors=survivors)[0]
    *row_parts, row_norms = db_prepared
    n_tiles = row_norms.shape[0] // tile_n
    _, survivors, out_w, bound_w = _geometry(tile_n, survivors)
    n_own = min(n_tiles, -(-n_q // tile_n) + 1)
    first_row = jnp.asarray(first_row, jnp.int32)
    t0 = jnp.clip(first_row // tile_n, 0, n_tiles - n_own)
    # the own tiles' operands, cut out of the resident ones: a copy of
    # ``n_own`` tiles, so the launch's index maps are static
    parts = [lax.dynamic_slice_in_dim(x, t0 * tile_n, n_own * tile_n, 0)
             for x in row_parts]
    tnorm = jnp.broadcast_to(lax.dynamic_slice_in_dim(
        row_norms, t0 * tile_n, n_own * tile_n, 0)[None, :],
        (8, n_own * tile_n))
    row_steps = tile_n // row_block
    t_idx = lambda q, t, x, at: (t * row_steps + x, 0)  # noqa: E731
    n_idx = lambda q, t, x, at: (0, t * row_steps + x)  # noqa: E731
    # the own tiles' cells of the first launch's outputs, which this
    # launch takes as operands it never reads and writes in place
    o_idx = lambda q, t, x, at: (q, at[0] + t)          # noqa: E731
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes(
                "tiled", "bf16x3", block_q=block_q, tile_n=tile_n,
                n_tiles=n_own, nd=1, out_w=out_w, bound_w=bound_w,
                db_block=sum(row_block * dim * x.dtype.itemsize
                             for x in parts),
                aux_rows=8, q_block=block_q * dim * 4, q_extra=0,
                **({} if row_steps == 1
                   else {"row_block": row_block, "dim_padded": dim})))
    whole = [_pad_axis(x, block_q, 0) for x in (cd, ci, bounds)]
    first_out = 2 + len(parts) + 1  # after the scalars, q, parts, norms
    out = pl.pallas_call(
        functools.partial(_self_kernel, tile_n=tile_n, survivors=survivors,
                          terms=terms, n_held=len(whole)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(qp // block_q, n_own, row_steps),
            in_specs=[
                pl.BlockSpec((block_q, dim), lambda q, t, x, at: (q, 0)),
                *[pl.BlockSpec((row_block, dim), t_idx) for _ in parts],
                pl.BlockSpec((8, row_block), n_idx),
                *[pl.BlockSpec(memory_space=pl.ANY) for _ in whole],
            ],
            out_specs=[
                pl.BlockSpec((block_q, out_w), o_idx),
                pl.BlockSpec((block_q, out_w), o_idx),
                pl.BlockSpec((block_q, bound_w), o_idx),
            ],
            # the select's running arrays, shared by the steps of a tile
            scratch_shapes=[] if row_steps == 1 else [pltpu.VMEM(
                (2 * survivors + 1, block_q, BIN_W), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in whole],
        input_output_aliases={first_out + j: j for j in range(len(whole))},
        interpret=interpret,
        name="self_tiles",
        **kwargs,
    )(jnp.stack([t0, first_row]), queries, *parts, tnorm, *whole)
    return tuple(x[:n_q] for x in out)


def _stream_call(queries, db_inputs, tnorm, out_shape, *, qp, dim, block_q,
                 tile_n, survivors, out_w, bound_w, n_tiles, nd, precision,
                 chunk_w, interpret, q_extra=(), aux_rows=8, fused=False,
                 keep=None, pq_shape=None, terms=BF16X3_TERMS[0]):
    """The streaming ``pallas_call``: grid over query blocks only, db
    parts + row norms left in compiler-chosen (HBM) memory and streamed
    by the kernel's own double-buffered DMA loop (``_stream_kernel``).
    ``q_extra`` carries the int8 query-scale block (a small VMEM input
    alongside the query block); ``aux_rows`` is 16 when the aux array
    stacks scales under norms (int8), else 8.  ``fused`` arms the
    in-loop carry + exclusion-bound early-out (kernel="fused"); ``keep``
    sizes its carry (the final select's m+2)."""
    n_parts = len(db_inputs)
    body = functools.partial(
        _stream_kernel, tile_n=tile_n, survivors=survivors, out_w=out_w,
        bound_w=bound_w, n_tiles=n_tiles, nd=nd, precision=precision,
        n_parts=n_parts, chunk_w=chunk_w, aux_rows=aux_rows,
        fused=fused, keep=keep, pq_shape=pq_shape, terms=terms,
    )
    part_dtype = db_inputs[0].dtype
    kwargs = {}
    if not interpret:
        depth = -(-int(keep) // BIN_W) if fused and keep is not None else 0
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit_bytes(
                "fused" if fused else "streaming", precision,
                block_q=block_q, tile_n=tile_n, n_tiles=n_tiles, nd=nd,
                out_w=out_w, bound_w=bound_w,
                db_block=n_parts * tile_n * chunk_w * part_dtype.itemsize,
                aux_rows=aux_rows,
                q_block=block_q * dim * queries.dtype.itemsize,
                q_extra=len(q_extra) * block_q * BIN_W * 4,
                carry_depth=depth if depth <= MAX_CARRY_DEPTH else 0),
        )
    return pl.pallas_call(
        body,
        grid=(qp // block_q,),
        in_specs=[
            pl.BlockSpec((block_q, dim), lambda q: (q, 0)),
            *[pl.BlockSpec((block_q, BIN_W), lambda q: (q, 0))
              for _ in q_extra],
            *[pl.BlockSpec(memory_space=pl.ANY) for _ in db_inputs],
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((block_q, n_tiles * out_w), lambda q: (q, 0)),
            pl.BlockSpec((block_q, n_tiles * out_w), lambda q: (q, 0)),
            pl.BlockSpec((block_q, n_tiles * bound_w), lambda q: (q, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            *[pltpu.VMEM((2, tile_n, chunk_w), part_dtype)
              for _ in db_inputs],
            pltpu.VMEM((2, aux_rows, tile_n), jnp.float32),
            pltpu.SemaphoreType.DMA((2, n_parts + 1)),
        ],
        interpret=interpret,
        **kwargs,
    )(queries, *q_extra, *db_inputs, tnorm)


@functools.partial(
    jax.jit,
    static_argnames=("m", "tile_n", "block_q", "survivors",
                     "precision", "final_select", "interpret",
                     "final_recall_target", "grid_order", "kernel",
                     "offset", "terms", "row_block"),
)
def local_certified_candidates(
    q: jax.Array,
    t: jax.Array,
    m: int,
    *,
    tile_n: int = TILE_N,
    block_q: int = BLOCK_Q,
    survivors: Optional[int] = None,
    precision: str = "bf16x3",
    final_select: str = "exact",
    interpret: Optional[bool] = None,
    final_recall_target: Optional[float] = None,
    grid_order: str = "query_major",
    kernel: str = "tiled",
    db_int8: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    offset: float = 0.0,
    db_pq: Optional[Tuple[jax.Array, jax.Array]] = None,
    terms: str = BF16X3_TERMS[0],
    row_block: Optional[int] = None,
    db_prepared: Optional[Tuple[jax.Array, ...]] = None,
    valid_words: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The whole device-side certified coarse pass against one db (shard):

      d32   [Q, m+1]  f32 direct-difference squared L2 of the selected
                      candidates, lexicographically ordered with their
      idx   [Q, m+1]  local db row indices (sentinel i32-max on padding),
      lb    [Q]       kernel-space exclusion bound: every db row NOT among
                      the selected candidates has kernel score >= lb.

    Three stages, all on device:

    1. fused kernel -> per-bin survivors + bin bounds;
    2. an exact top-(m+2) (``final_select="exact"``) or an
       ``approx_max_k`` + exact masked-min (``"approx"``) picks ~(m+1)
       survivors; either way the exclusion value over the de-selected
       survivors is EXACT, so the final selection cannot silently weaken
       the bound — an approx miss only strengthens lb downward, causing
       a fallback, never an unsound certificate;
    3. the selected rows are gathered and re-scored with direct-difference
       f32 (no catastrophic cancellation — relative error ~1e-6, vs the
       expanded-square kernel score's absolute error at ||q||^2 scale),
       then ordered lexicographically by (distance, index).

    Callable inside shard_map; parallel.sharded merges (d32, idx) across
    db shards and pmin's lb.

    ``precision="int8"`` runs the quantized coarse arm: the kernel score
    lives in SHIFTED space (``offset`` subtracted from both sides before
    quantization — squared L2 is translation invariant), ``lb`` with it,
    and the certificate widens its threshold by the provable per-query
    quantization bound ε (ops.quantize).  ``db_int8`` plugs the
    placement-time quantized db in (values, scales, row norms — see
    ``_bin_candidates``); the stage-3 rescore ALWAYS gathers the f32
    ``t`` rows, so the returned d32 values and the near-tie analysis are
    precision-independent — the quantization only steers which
    candidates surface, never what their distances read.  The "pq"
    arm follows the same contract (``db_pq`` plugs its placement in);
    the rescore's precision-independence is what makes ALL quantized arms bitwise-equal to the exact reference
    whenever their candidates cover the true top-k — and certified
    fallback material otherwise.  ``db_prepared`` likewise plugs in the
    "bf16x3" kernel's row operands kept beside ``t``
    (:func:`row_operands`): stage 1 then reads nothing of ``t``.

    ``valid_words`` (``_bin_candidates``) restricts each query to the
    rows its words mark: ``idx`` then names valid rows only (sentinel
    and +inf once they run out) and ``lb`` bounds the valid rows not
    selected; ``lb`` is +inf exactly when every valid row of the shard
    is among the candidates (no kernel bin held more than ``survivors``
    of them, no merge bin more than ``SELECT_MERGE_SURVIVORS``, and
    fewer than m+2 survived in all), so a short or empty valid set
    certifies by that alone.  Stages 2 and 3 are untouched."""
    if interpret is None:
        interpret = not default_backend_is_tpu()
    cd, ci, bounds = local_coarse_candidates(
        q, t, m, tile_n=tile_n, block_q=block_q, survivors=survivors,
        precision=precision, interpret=interpret,
        final_select=final_select, grid_order=grid_order, kernel=kernel,
        db_int8=db_int8, offset=offset, db_pq=db_pq, terms=terms,
        row_block=row_block, db_prepared=db_prepared,
        valid_words=valid_words,
    )
    return local_select_rescore(
        q, t, cd, ci, bounds, m, final_select=final_select,
        final_recall_target=final_recall_target, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("m", "tile_n", "block_q", "survivors",
                     "precision", "interpret", "final_select",
                     "grid_order", "kernel", "offset", "terms",
                     "row_block"),
)
def local_coarse_candidates(
    q: jax.Array,
    t: jax.Array,
    m: int,
    *,
    tile_n: int = TILE_N,
    block_q: int = BLOCK_Q,
    survivors: Optional[int] = None,
    precision: str = "bf16x3",
    interpret: Optional[bool] = None,
    grid_order: str = "query_major",
    kernel: str = "tiled",
    db_int8: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    offset: float = 0.0,
    final_select: str = "exact",
    db_pq: Optional[Tuple[jax.Array, jax.Array]] = None,
    terms: str = BF16X3_TERMS[0],
    row_block: Optional[int] = None,
    db_prepared: Optional[Tuple[jax.Array, ...]] = None,
    valid_words: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Stage 1 of :func:`local_certified_candidates` — the db-streaming
    coarse pass alone: resolve the effective tile, launch the kernel,
    trim the query padding.  Returns the packed candidates
    ``(cd [Q, W], ci [Q, W], bounds [Q, T*B])``; stage 2
    (:func:`local_select_rescore`) is everything after the kernel.
    ``row_block``, ``db_prepared`` and ``valid_words`` go to the kernel
    as given (``_bin_candidates``)."""
    if interpret is None:
        interpret = not default_backend_is_tpu()
    if final_select not in ("exact", "approx"):
        raise ValueError(
            f"final_select {final_select!r} not in ('exact', 'approx')")
    if kernel == "fused" and final_select == "approx":
        # the early-out's bitwise argument rests on the EXACT top-(m+2)
        # boundary: every skipped value is provably above the final
        # (m+2)-th smallest, which the hardware ApproxTopK's internal
        # binning does not respect (a recall miss could select a
        # skipped-vs-kept position differently).  Refuse rather than
        # weaken the contract.
        raise ValueError(
            "kernel='fused' requires final_select='exact' (the "
            "early-out's bitwise contract is an exact-boundary argument)")
    eff_tile = effective_tile(t.shape[0], tile_n, survivors, m + 2)
    with jax.named_scope(SCOPE_KERNEL):
        cd, ci, bounds = _bin_candidates(
            q, t, block_q=effective_block_q(block_q, q.shape[0]),
            tile_n=eff_tile, survivors=survivors,
            precision=precision, interpret=interpret,
            grid_order=grid_order, kernel=kernel, db_int8=db_int8,
            offset=offset, keep=m + 2 if kernel == "fused" else None,
            db_pq=db_pq, terms=terms, row_block=row_block,
            db_prepared=db_prepared, valid_words=valid_words,
        )
    n_q = q.shape[0]
    return cd[:n_q], ci[:n_q], bounds[:n_q]


def _select_merge_kernel(cd_ref, ci_ref, v_ref, i_ref, b_ref, *, rows: int):
    """One (query block, group) cell of :func:`_select_merge`: the
    grouped emitter on the group's ``rows`` lane-rows of ``cd`` as one
    precomputed score tile, each candidate's row index riding with it."""
    v, i, b = _emit_select_grouped_scores(
        None, cd_ref[...], tile_n=rows * BIN_W,
        survivors=SELECT_MERGE_SURVIVORS, payload=ci_ref[...])
    v_ref[...] = v
    i_ref[...] = i
    b_ref[...] = b


def _scores_past_the_array_inf(cd_ref, rows: int, groups: int, short: int):
    """A (query block, group) cell's block of ``cd`` where the
    ``groups`` runs of ``rows`` lane-rows reach ``short`` past the
    array's ``groups * rows - short``: the last group's block hangs
    over the array's end, and what it holds out there is unspecified
    (NaN under the interpreter).  Those scores are made +inf here, by
    index, before the insertion network sees them: one select on the
    group's number for each lane-row of a block that lies past the
    array in some group (the last ``short`` of the last group's, where
    ``short < rows``).  Their payload needs nothing: +inf never
    displaces, so whatever index lies there is never kept."""
    s = cd_ref[...]
    first = lax.mul(pl.program_id(1), np.int32(rows))
    inf = lax.full((s.shape[0], BIN_W), jnp.inf, jnp.float32)
    whole = max(0, rows - short)
    cols = [lax.slice_in_dim(s, 0, whole * BIN_W, axis=1)] if whole else []
    for r in range(whole, rows):
        # lane-row ``first + r`` of the array: past its last?
        past = lax.ge(first, np.int32(groups * rows - short - r))
        cols.append(lax.select(past, inf, lax.slice_in_dim(
            s, r * BIN_W, (r + 1) * BIN_W, axis=1)))
    return lax.concatenate(cols, 1)


def _select_merge_short_kernel(cd_ref, ci_ref, v_ref, i_ref, b_ref, *, grid):
    """:func:`_select_merge_kernel` on a group grid ``(rows, groups,
    short)`` that overshoots the array by ``short`` lane-rows: the same
    emitter on the block's scores with the overhang masked
    (:func:`_scores_past_the_array_inf`).  The mask is no argument of
    the emitter, which is the distance kernel's too and one of the
    frames its trace stack is measured by (``_row_step``), and it is
    formed in a call that has returned before the emitter's loop binds:
    this frame is :func:`_select_merge_kernel`'s to the slot (one static
    argument, no local of its own), so a cell whose merge is off its
    grid traces the loop where it always stood on CPython's frame stack
    (root PERF.md section 6, PRs 46 and 52)."""
    v = _scores_past_the_array_inf(cd_ref, *grid)
    v, i, b = _emit_select_grouped_scores(
        None, v, tile_n=grid[0] * BIN_W,
        survivors=SELECT_MERGE_SURVIVORS, payload=ci_ref[...])
    v_ref[...] = v
    i_ref[...] = i
    b_ref[...] = b


def _select_merge_cell(groups: int, rows: int, short: int):
    """``(kernel, index map of cd and ci)`` of :func:`_select_merge`'s
    grid cell ``(query block, group)``: the plain kernel on the group's
    own block where the groups tile the array (``short`` 0: the call is
    what it always was, operation for operation), else the kernel that
    masks the overhang, on the last block that holds any of the array:
    the group's own but for a group that lies past the array altogether
    (``short >= rows``: fewer lane-rows than groups squared), which
    reads that block and masks all of it."""
    if not short:
        return (functools.partial(_select_merge_kernel, rows=rows),
                lambda i, g: (i, g))
    held = (groups * rows - short - 1) // rows
    return (functools.partial(_select_merge_short_kernel,
                              grid=(rows, groups, short)),
            lambda i, g: (i, jnp.minimum(g, held)))


def _select_merge(cd: jax.Array, ci: jax.Array, groups: int, rows: int,
                  *, interpret: bool):
    """The second bin-merge (``select_merge_geometry``): per merge bin
    (group of ``rows`` lane-rows, lane) the ``SELECT_MERGE_SURVIVORS``
    smallest scores of ``cd`` with their indices from ``ci``, and the
    next smallest score as the bin's exclusion bound.  Returns
    ``(scores, indices [Q, groups * survivors * 128], bounds
    [Q, groups * 128])``.  Strict ``<`` keeps the earlier column on ties
    and +inf (kernel padding, the last group's own) never enters: its
    slot keeps the sentinel index.  Every (query block, group) cell
    writes its own disjoint output blocks, like the kernel's.

    ``cd`` and ``ci`` are read where the kernel wrote them, at their own
    width: where ``groups * rows`` lane-rows overshoot it the last
    group's block hangs over the arrays' end and its cell masks the
    overhang by index (``_select_merge_short_kernel``).  No copy of
    either is made to pad them to the group grid: that was two passes
    over both arrays in every launch, more than the merge itself at
    2.5M rows and k = 10 (root PERF.md section 6, PR 52)."""
    n_q = cd.shape[0]
    kernel, read = _select_merge_cell(
        groups, rows, groups * rows - cd.shape[1] // BIN_W)
    # two [block_q, rows * 128] input blocks of at most 3 MiB each,
    # double buffered by the pipeline: Mosaic's 16 MiB of scoped VMEM
    # hold them up to 3.1 MiB a block and not at 3.4 (16.14 MiB asked
    # for, compiled for a v5e with no chip; 3.9 MiB was refused on the
    # chip at the 62 lane-rows of a 2.5M-row shard at m+2 = 40).  128
    # query rows at the 36 lane-rows of a 5M-row shard at m+2 = 130,
    # fewer as ``rows`` grows: a power of two, so that a batch that is
    # one splits evenly
    fit = (3 << 20) // (rows * BIN_W * 4)
    block_q = min(n_q, max(8, min(BLOCK_Q, 1 << (fit.bit_length() - 1))))
    out_w = SELECT_MERGE_SURVIVORS * BIN_W
    cell = lambda i, g: (i, g)  # noqa: E731
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    return pl.pallas_call(
        kernel,
        grid=(-(-n_q // block_q), groups),
        in_specs=[pl.BlockSpec((block_q, rows * BIN_W), read)] * 2,
        out_specs=[pl.BlockSpec((block_q, out_w), cell),
                   pl.BlockSpec((block_q, out_w), cell),
                   pl.BlockSpec((block_q, BIN_W), cell)],
        out_shape=[
            jax.ShapeDtypeStruct((n_q, groups * out_w), jnp.float32),
            jax.ShapeDtypeStruct((n_q, groups * out_w), jnp.int32),
            jax.ShapeDtypeStruct((n_q, groups * BIN_W), jnp.float32),
        ],
        interpret=interpret,
        name="select_merge",
        **kwargs,
    )(cd, ci)


def _select_final_kernel(cd_ref, ci_ref, idx_ref, excl_ref, key_ref, *,
                         keep: int, rows: int):
    """One query block of :func:`_select_final`.  Every array is
    ``[block_q, 128]``: a query a sublane, one lane-row of its
    candidates at a time.

    1. each score becomes its monotone int32 key (the float's bits, the
       low 31 flipped where the sign is set: the total order of
       ``lax.top_k``, -0 before +0) in ``key_ref``;
    2. 32 halvings of the int32 range find ``thr``, the least key with
       at least ``keep`` keys at or under it: the ``keep``-th smallest
       score, which is the exclusion value;
    3. ``keep - 1`` candidates are selected: every key under ``thr`` and
       the first ``need`` columns AT it, ``cut`` their last column, by
       halvings of the column range where any query of the block has
       ties to cut (-1 where none is taken);
    4. the selected indices are compacted: down the lane-rows a lane
       keeps its ``FINAL_SELECT_SLOTS`` smallest in a sorted stack (a
       min/max insertion network, no cross-lane op), then ``keep - 1``
       rounds pop the block's least index a query (one cross-lane min)
       into the next output column.  A lane that held more selected
       than slots runs the pass again above the last index it kept.

    An index is written once because a query's finite candidates are
    distinct rows below the sentinel, which +inf padding carries: a
    selected pad leaves the output's sentinel fill as it is."""
    shape = (cd_ref.shape[0], BIN_W)
    out_v = idx_ref.shape[1] // BIN_W
    i32 = np.int32

    # lax primitives throughout, not their jnp twins: every jnp call
    # re-enters jit's Python machinery to emit the same op, which each
    # process's first call pays for (``_emit_select_grouped_scores``)
    def full(v):
        return lax.full(shape, v, jnp.int32)

    def lanes(x):  # a [block_q] reduction over the lanes, on every lane
        return lax.broadcast_in_dim(x, shape, (0,))

    zero, one, top = full(0), full(1), full(_I32MAX)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)

    def over_rows(body, init):
        """``body(columns of lane-row r, r's first column on every lane,
        carry)`` down the lane-rows, ``FINAL_SELECT_UNROLL`` of them a
        step of a loop that is in the program and not in its trace:
        the trace and the lowering are paid in every process's first
        call (root PERF.md, PRs 29 and 35)."""
        unroll = min(FINAL_SELECT_UNROLL, rows)

        def at(start, c):
            return body(pl.ds(pl.multiple_of(start, BIN_W), BIN_W),
                        lax.broadcast_in_dim(start, shape, ()), c)

        def step(i, c):
            for j in range(unroll):
                c = at(lax.add(lax.mul(i, i32(unroll * BIN_W)),
                               i32(j * BIN_W)), c)
            return c

        c = lax.fori_loop(0, rows // unroll, step, init)
        for r in range(rows - rows % unroll, rows):
            c = at(i32(r * BIN_W), c)
        return c

    def to_keys(cols, _, c):
        bits = lax.bitcast_convert_type(cd_ref[:, cols], jnp.int32)
        key_ref[:, cols] = lax.select(
            lax.lt(bits, zero), lax.bitwise_xor(bits, top), bits)
        return c

    over_rows(to_keys, 0)

    def count(pred):
        """Of each query's keys, how many ``pred(keys, their columns)``
        holds for, on every lane."""
        return lanes(lax.reduce_sum(over_rows(
            lambda cols, first, acc: lax.add(acc, lax.select(
                pred(key_ref[:, cols], lax.add(lane, first)), one, zero)),
            zero), (1,)))

    def least(enough, lo, hi, steps):
        """The least value in [lo, hi] that ``enough`` accepts (it
        accepts ``hi`` and is monotone), a query a sublane."""
        def step(_, c):
            lo, hi = c
            # the floor of the mean, with no overflow
            mid = lax.add(lax.bitwise_and(lo, hi), lax.shift_right_arithmetic(
                lax.bitwise_xor(lo, hi), one))
            ok = enough(mid)
            return (lax.select(ok, lo, lax.add(mid, one)),
                    lax.select(ok, mid, hi))

        return lax.fori_loop(0, steps, step, (full(lo), full(hi)))[1]

    thr = least(lambda mid: lax.ge(
        count(lambda k, _: lax.le(k, mid)), full(keep)), _I32MIN, _I32MAX, 32)
    need = lax.sub(full(keep - 1), count(lambda k, _: lax.lt(k, thr)))

    def tie_cut():
        last = rows * BIN_W - 1
        cut = least(
            lambda mid: lax.ge(count(lambda k, col: lax.bitwise_and(
                lax.eq(k, thr), lax.le(col, mid))), need),
            0, last, last.bit_length())
        return lax.select(lax.gt(need, zero), cut, full(-1))

    cut = lax.cond(lax.gt(lax.reduce_max(need, (0, 1)), i32(0)),
                   tie_cut, lambda: full(-1))

    def compact(floor):
        def push(cols, first, c):
            stack, over = c
            k = key_ref[:, cols]
            idx = ci_ref[:, cols]
            sel = lax.bitwise_or(lax.lt(k, thr), lax.bitwise_and(
                lax.eq(k, thr), lax.le(lax.add(lane, first), cut)))
            cur = lax.select(
                lax.bitwise_and(sel, lax.gt(idx, floor)), idx, top)
            kept = []
            for held in stack:
                kept.append(lax.min(held, cur))
                cur = lax.max(held, cur)
            return kept, lax.min(over, cur)

        return over_rows(push, ([top] * FINAL_SELECT_SLOTS, top))

    def pop(_, c):
        stack, out, n = c
        first = lanes(lax.reduce_min(stack[0], (1,)))
        hit = lax.eq(stack[0], first)
        stack = [lax.select(hit, below, held)
                 for held, below in zip(stack, stack[1:] + [top])]
        out = [lax.select(lax.eq(lax.add(lane, full(o * BIN_W)), n),
                          first, held) for o, held in enumerate(out)]
        return stack, out, lax.add(n, lax.select(lax.ne(first, top),
                                                 one, zero))

    def one_pass(c):
        floor, out, n, _, passes = c
        stack, over = compact(floor)
        _, out, n = lax.fori_loop(
            0, lax.sub(i32(keep - 1), lax.reduce_min(n, (0, 1))), pop,
            (stack, out, n))
        return (stack[-1], out, n, lax.convert_element_type(lax.ne(
            lax.reduce_min(over, (0, 1)), i32(_I32MAX)), jnp.int32),
            lax.add(passes, i32(1)))

    # a pass takes at least FINAL_SELECT_SLOTS indices off every lane it
    # leaves unfinished, so the pass count is bounded whatever a partial
    # block's padding rows hold
    _, out, _, _, _ = lax.while_loop(
        lambda c: lax.bitwise_and(lax.ne(c[3], i32(0)), lax.le(
            c[4], i32(rows // FINAL_SELECT_SLOTS + 1))),
        one_pass, (full(-1), [top] * out_v, zero, i32(1), i32(0)))
    for o, held in enumerate(out):
        idx_ref[:, o * BIN_W:(o + 1) * BIN_W] = held
    excl_ref[...] = lax.bitcast_convert_type(
        lax.select(lax.lt(thr, zero), lax.bitwise_xor(thr, top), thr),
        jnp.float32)


def _final_select_vmem_limit(block_q: int, width: int, keep: int) -> int:
    """The scoped-VMEM limit :func:`_select_final` requests: the model's
    need of the block (knn_tpu.analysis.vmem.final_select_bytes) plus an
    eighth, no less than Mosaic's own default and no more than the
    device has."""
    from knn_tpu.analysis import vmem

    need = sum(vmem.final_select_bytes(block_q, width, keep).values())
    return min(vmem.budget_for(_vmem_device_kind()),
               max(16 * vmem.MIB, need + need // 8))


def _select_final(cd: jax.Array, ci: jax.Array, m: int, block_q: int,
                  *, interpret: bool):
    """The exact final select as ONE Pallas call (``final_select_geometry``
    gives ``block_q``): ``(lidx [Q, m+1], excl [Q])``, the row indices
    ``ci`` of the m+1 smallest scores of ``cd`` (ascending within a
    compaction pass, sentinels last) and the (m+2)-th smallest score — the SET and the
    value of ``lax.top_k(-cd, m + 2)`` and the gather after it, bit for
    bit: smallest score first, the earlier column on equal scores, -0
    before +0, a +inf pad taken only where fewer than m+2 scores are
    finite.  What follows orders by (distance, index) whatever the
    order here (``topk_pairs``).  Outputs are lane-padded blocks, cut
    to size outside; int32 throughout (no ``uint32`` array reaches HLO:
    the range completion's trace pattern reads those)."""
    n_q, w = cd.shape
    keep = m + 2
    block_q = min(block_q, _round_up(n_q, 8))
    out_w = _round_up(keep - 1, BIN_W)
    row = lambda i: (i, 0)  # noqa: E731
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_final_select_vmem_limit(block_q, w, keep))
    idx, excl = pl.pallas_call(
        functools.partial(_select_final_kernel, keep=keep,
                          rows=w // BIN_W),
        grid=(-(-n_q // block_q),),
        in_specs=[pl.BlockSpec((block_q, w), row)] * 2,
        out_specs=[pl.BlockSpec((block_q, out_w), row),
                   pl.BlockSpec((block_q, BIN_W), row)],
        out_shape=[jax.ShapeDtypeStruct((n_q, out_w), jnp.int32),
                   jax.ShapeDtypeStruct((n_q, BIN_W), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, w), jnp.int32)],
        interpret=interpret,
        name="select_final",
        **kwargs,
    )(cd, ci)
    return idx[:, :m + 1], excl[:, 0]


@functools.partial(
    jax.jit, static_argnames=("m", "final_select", "final_recall_target",
                              "interpret"),
)
def local_select_rescore(
    q: jax.Array,
    t: jax.Array,
    cd: jax.Array,
    ci: jax.Array,
    bounds: jax.Array,
    m: int,
    *,
    final_select: str = "exact",
    final_recall_target: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Stage 2 of :func:`local_certified_candidates`: final top-(m+2)
    select over the packed candidates (over their bin-merge's survivors
    where ``select_merge_geometry`` engages, every merge bin's bound
    joining ``lb``; as one Pallas call where ``final_select_geometry``
    engages), exclusion-value restoration, the direct-difference f32
    rescore gather, and lexicographic ordering.  ``interpret`` is the
    kernel's (None: by the backend) and goes to both Pallas stages."""
    if interpret is None:
        interpret = not default_backend_is_tpu()
    n_q = q.shape[0]
    w = cd.shape[1]
    if m + 2 > w:
        raise ValueError(
            f"pallas selector: m+2={m + 2} exceeds {w} bin survivors on a "
            f"{t.shape[0]}-row shard; lower margin or tile_n, or use the "
            f"approx selector"
        )
    if final_select not in ("exact", "approx"):
        raise ValueError(
            f"final_select {final_select!r} not in ('exact', 'approx')")
    with jax.named_scope(SCOPE_FINAL_SELECT):
        merge = select_merge_geometry(w, m)
        if merge is not None:
            # a wide candidate array: keep the smallest few of each merge
            # bin, select among those, and let every merge bin's bound
            # join lb (module docstring, case (b))
            with jax.named_scope(SCOPE_SELECT_MERGE):
                cd, ci, merge_bounds = _select_merge(
                    cd, ci, *merge[:2], interpret=interpret)
                # kernel bins' and merge bins' bounds: one column now
                bounds = jnp.minimum(
                    jnp.min(bounds, axis=-1),
                    jnp.min(merge_bounds, axis=-1))[:, None]
        if final_select == "approx":
            # hardware ApproxTopK over the candidate array, with the
            # exclusion value restored EXACTLY: every de-selected
            # candidate joins the bound via a masked min, so a recall
            # miss here can only cause a fallback, never a wrong
            # certificate.  (~40% cheaper than the full top_k at SIFT
            # candidate widths.)  ``final_recall_target`` tunes the
            # fallback rate of this one-pass path the same way
            # ``recall_target`` tunes the counted selector (ADVICE r3).
            _, sel = lax.approx_max_k(
                -cd, m + 1, recall_target=final_recall_target or 0.999)
            lidx = jnp.take_along_axis(ci, sel, axis=-1)
            masked = cd.at[jnp.arange(n_q)[:, None], sel].set(jnp.inf)
            excl = jnp.min(masked, axis=-1)
            lb = jnp.minimum(jnp.min(bounds, axis=-1), excl)
        else:
            # exact top-(m+2) by kernel score: the last value is the
            # exclusion value over every de-selected survivor.  One
            # Pallas call that carries the indices with the scores
            # where the shape is one it was timed at, XLA's top_k and
            # the gather after it elsewhere: the same set and value
            block_q = final_select_geometry(cd.shape[1], m)
            if block_q is not None:
                lidx, excl = _select_final(
                    cd, ci, m, block_q, interpret=interpret)
                lb = jnp.minimum(jnp.min(bounds, axis=-1), excl)
            else:
                # op for op what stood here before the stage: a shape
                # the rule refuses lowers to the text it always did
                neg, sel = lax.top_k(-cd, m + 2)
                vals = -neg
                lidx = jnp.take_along_axis(ci, sel, axis=-1)[:, : m + 1]
                lb = jnp.minimum(jnp.min(bounds, axis=-1), vals[:, m + 1])

    with jax.named_scope(SCOPE_RESCORE):
        # kernel-padding rows carry real-looking indices in [rows,
        # padded); clip-gathering them would hand a PAD candidate the
        # LAST REAL row's finite distance — mask them to sentinel BEFORE
        # the rescore
        valid = lidx < t.shape[0]
        lidx = jnp.where(valid, lidx, _I32MAX)

        # device rank stage: direct-difference f32 rescore of the
        # selected rows
        safe = jnp.clip(lidx, 0, t.shape[0] - 1)
        rows = t[safe]  # [Q, m+1, D] gather
        diff = q[:, None, :].astype(jnp.float32) - rows.astype(jnp.float32)
        d32 = jnp.sum(diff * diff, axis=-1)
        d32 = jnp.where(valid, d32, jnp.inf)
        d32, lidx = topk_pairs(d32, lidx, m + 1)
    return d32, lidx, lb


def pallas_knn_candidates(
    queries: jax.Array,
    db: jax.Array,
    m: int,
    *,
    block_q: int = BLOCK_Q,
    tile_n: int = TILE_N,
    precision: str = "bf16x3",
    interpret: Optional[bool] = None,
    compute_dtype=None,  # accepted for API compat; the kernel is f32-only
) -> jax.Array:
    """[Q, m] coarse candidate indices from the fused kernel — the
    ``candidate_fn`` plug for ops.certified.knn_search_certified and the
    kernel-mechanics test surface.  Sentinel (i32 max) marks unfilled
    slots; ops.refine tolerates them."""
    del compute_dtype
    n_q = queries.shape[0]
    # the kernel needs one exclusion slot, so a whole-db request (m >= n,
    # e.g. knn_search_certified on a tiny db computing m = min(k+margin,
    # n)) selects n-1 rows and sentinel-pads the rest — the count
    # certificate catches the one unexaminable row, keeping composition
    # exact while honoring the [Q, m] shape contract
    m_eff = min(m, max(db.shape[0] - 1, 1))
    d32, idx, _ = local_certified_candidates(
        queries, db, m=m_eff, tile_n=tile_n, block_q=block_q,
        precision=precision, interpret=interpret,
    )
    idx = idx[:n_q, :m_eff]
    if m_eff < m:
        idx = jnp.concatenate(
            [idx, jnp.full((n_q, m - m_eff), _I32MAX, jnp.int32)], axis=-1
        )
    return idx


def kernel_tolerance(
    queries_np: np.ndarray, db_np: np.ndarray,
    *, db_norm_max: Optional[float] = None, precision: str = "bf16x3",
    q_norm: Optional[np.ndarray] = None,
    quant=None,
) -> np.ndarray:
    """Per-query bound on |kernel score - exact score| — the certificate
    comparison's slack, by kernel matmul mode.  Mirrors the on-device
    formula in parallel.sharded._pallas_certified_program.

    - "highest": 4x ops.certified.certification_tolerance (= 32 eps_f32 *
      (||q||^2 + max||t||^2)) — the kernel's tn - 2*qt pipeline has two
      f32 reduction trees where the count pass has one fused expansion,
      and the on-device certificate adds an f32 q_norm reduction of its
      own.
    - "bf16x3": the dropped ql.tl term and the low-part rounding are each
      <= 2^-17 (||q||^2 + max||t||^2)/2; 2^-14 gives ~8x headroom (and
      subsumes every f32 accumulation term).
    - "int8": the PROVABLE per-query quantization bound ε derived from
      the actual residual norms (ops.quantize.score_error_bound; the
      property test in tests/test_quantize.py pins its soundness).
      ``quant`` supplies the placement's QuantizedRows; None quantizes
      ``db_np`` here (host pass — fine for the gate scripts this
      function serves).
    """
    from knn_tpu.ops.certified import certification_tolerance

    if q_norm is None:
        q_norm = (queries_np.astype(np.float64) ** 2).sum(-1)
    if db_norm_max is None:
        db_norm_max = float((db_np.astype(np.float64) ** 2).sum(-1).max())
    base = 4.0 * certification_tolerance(
        queries_np, db_np, db_norm_max=db_norm_max, q_norm=q_norm
    )
    if precision == "int8":
        from knn_tpu.ops import quantize as qz

        if quant is None:
            quant = qz.quantize_rows_np(db_np)
        stats = qz.db_bound_stats(quant, db_np)
        return np.maximum(
            base,
            qz.score_error_bound(queries_np, stats, offset=quant.offset),
        )
    if precision == "pq":
        from knn_tpu.ops import pq as pqm

        if quant is None:
            raise ValueError(
                "precision='pq' needs quant=<ops.pq.PQResult> (codebooks "
                "train on data; there is no quantize-on-the-fly arm)")
        return np.maximum(
            base, pqm.score_error_bound_pq(queries_np, quant.stats))
    if precision in ("bf16x3", "bf16x3f"):
        return np.maximum(base, 2.0 ** -14 * (q_norm + db_norm_max))
    if precision == "highest":
        return base
    raise ValueError(
        f"precision {precision!r} has no certified tolerance model; "
        f"use one of {PRECISIONS}"
    )


def knn_search_pallas(
    queries,
    db,
    k: int,
    *,
    margin: int = 28,
    tile_n: int = TILE_N,
    precision: str = "bf16x3",
    survivors: Optional[int] = None,
    block_q: Optional[int] = None,
    final_select: str = "exact",
    final_recall_target: Optional[float] = None,
    grid_order: str = "query_major",
    kernel: str = "tiled",
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Certified-exact KNN in ONE database pass on a single-device mesh:
    fused kernel coarse select -> device rank -> exclusion-bound
    certificate -> float64 escalation only for ambiguous/uncertified
    queries.  Returns (dists [Q, k] float64 array, idx [Q, k], stats):
    indices are the exact lexicographic top-k; distance VALUES are device
    f32 direct-difference (relative error < RANK_SLACK) except near-tied
    or repaired entries, which are float64-exact.  Thin wrapper over
    ShardedKNN.search_certified(selector="pallas") so single-device
    and sharded paths share ONE certificate implementation.

    Convenience/test surface: every call places the database on the mesh
    afresh.  Repeated searches against the same database should construct
    ``ShardedKNN`` once and call ``search_certified`` on it.

    Geometry note for SMALL databases: bin collision rates scale with
    (bin_members / n)^2, so the default tile (128-member bins, tuned
    for ~1M rows) falls back often below ~300k rows — still exact,
    just slower.  Pass a smaller ``tile_n`` (e.g. ``n // 25`` rounded
    to a multiple of 128) to restore a sub-1% fallback rate."""
    from knn_tpu.parallel.mesh import make_mesh
    from knn_tpu.parallel.sharded import ShardedKNN

    db_np = np.asarray(db, dtype=np.float32)
    prog = ShardedKNN(
        db_np, mesh=make_mesh(1, 1, devices=jax.devices()[:1]), k=k
    )
    return prog.search_certified(
        np.asarray(queries, dtype=np.float32), margin=margin,
        selector="pallas", tile_n=tile_n, precision=precision,
        survivors=survivors, block_q=block_q, final_select=final_select,
        final_recall_target=final_recall_target,
        grid_order=grid_order, kernel=kernel,
    )


