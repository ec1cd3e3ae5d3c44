"""Per-query tag filters for the certified path: which rows a query may
return, as the kernel's validity words.

A row carries a bag of tags (CSR, row -> sorted tag ids); a query names
up to two tags and may return only rows whose bag holds every one of
them (AND; -1 = no tag in that slot).  The certified kernel takes the
predicate as one uint32 word array a batch
(``ops.pallas_knn.valid_word_position``: bit ``g % 32`` of word
``(g // 32) * 128 + lane`` of a row tile is its row ``g * 128 +
lane``), and this module makes those words ON THE DEVICE from a tag
index placed once:

- a tag held by MANY rows keeps a **bitmap**, its rows already in the
  words' layout: a query's lookup is one row of ``rows / 8`` bytes read;
- every other tag keeps its **list** of row ids (CSR by tag): a lookup
  sets one bit an id.

Which form a tag takes is ONE rule on its list's length
(:func:`bitmap_min_rows`), read off the placement and set by nothing
else.  :func:`mask_words` is the program's body on one shard: a Pallas
kernel (``filter_mask``) that, a query a grid step, starts from each
tag's bitmap row (the all-zero row for a listed tag, the all-valid row
for an absent one), sets the listed ids' bits and ANDs the two tags.

The host keeps the same index (:func:`invert_bags`) for the repair's
exact scan (:func:`valid_rows`).

A second maker needs no index at all: a row carries ONE whole number
(its id, a time stamp: ``ShardedKNN(row_attr=)``) and a query a
half-open range ``[lo, hi)`` on it.  :func:`range_words` is that
program's body on one shard, a Pallas kernel (``range_mask``) that
compares every (query, row) pair and packs 32 outcomes a word, in the
same layout; :func:`place_attr` lays a shard's attribute out so that the
kernel reads a word row's 32 row groups as 32 consecutive sublanes
whatever the row tile; :func:`range_valid_rows` is the host's statement
of the predicate for the repair's scan.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from knn_tpu.ops.pallas_knn import (
    BIN_W,
    valid_word_position,
    valid_words_per_tile,
)

#: a tag gets a bitmap where at least one of a shard's padded rows in
#: this many holds it (so from 612 rows at ``yfcc2m5``'s 2,506,752-row
#: shard), a list below that.  What each form costs, timed on the v5e at
#: the cell ``yfcc2m5.sweep_filter``'s own mix (4,096 queries a batch,
#: 5,635 tag lookups; root PERF.md section 6, PR 40, chip calls 1-2,
#: the kernel ``filter_mask``'s time a batch in the trace):
#:
#:     rows / share   bitmaps   list ids a query   kernel    sweep_qps
#:     256   (9,792)      304        1,441        103.4 ms     20,866
#:     1,024 (2,448)      978          364         29.1 ms     37,327
#:     4,096   (612)    4,683           86         10.3 ms     45,762
#:
#: so a listed id costs 16.6 ns (one scalar read-modify-write of a
#: 128-lane word row) and a bitmap row 0.38 us of HBM time (313 KB at
#: 819 GB/s): by TIME a list loses from 23 ids up.  By BYTES a bitmap
#: (rows / 8) loses to its list (4 an id) below rows / 32 ids, and every
#: step down the table buys its milliseconds with HBM: 0.10, 0.31,
#: 1.47 GB of bitmaps; the next (rows / 16,384) would keep some 20,000
#: tags' (6 GB) to save the 5 ms that are left over the kernel's own
#: floor (the words written and two rows read a query: 4.7 ms).  4,096
#: is where the mask stops being a third of the batch and the index is
#: still a fifth of what the chip holds for the cell (7.5 of 16.9 GB).
BITMAP_ROW_SHARE = 4096

#: bitmap slots every shard has before its tags': no row, every valid row
SLOT_NONE, SLOT_ALL, SLOT_TAGS = 0, 1, 2

#: device scope of the mask program
SCOPE_FILTER_MASK = "knn.filter_mask"
#: device scope of the range maker's program
SCOPE_RANGE_MASK = "knn.range_mask"

#: the largest magnitude a row's attribute may have: int32 but for its
#: least value, which the placement keeps for "no row here" (padding
#: past the placed rows, bits past a row tile's groups): no range a
#: query can name holds it (:func:`range_bounds`)
ATTR_MAX = np.iinfo(np.int32).max
_NO_ROW = np.iinfo(np.int32).min
#: queries a grid step of ``range_mask`` takes, and how many of them the
#: kernel keeps in registers at a time (their bounds and the word being
#: made: three ``[32, 128]`` arrays, twelve vregs)
RANGE_BLOCK_Q = 1024
_RANGE_SUB_Q = 32


def bitmap_min_rows(rows_padded: int) -> int:
    """The rule: a tag whose list, on the shard where it is longest,
    holds at least this many rows gets a bitmap."""
    return max(1, rows_padded // BITMAP_ROW_SHARE)


def list_capacity(rows_padded: int) -> int:
    """Ids a listed lookup hands the kernel: the longest list the rule
    leaves (one under :func:`bitmap_min_rows`), in whole 128s."""
    return -(-bitmap_min_rows(rows_padded) // BIN_W) * BIN_W


def check_bags(indptr, tags, n_rows: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(indptr int64 [n_rows + 1], tags int32 [pairs])`` of a CSR
    ``row -> tag ids``, or ValueError."""
    indptr = np.asarray(indptr, dtype=np.int64)
    tags = np.asarray(tags, dtype=np.int32)
    if indptr.shape != (n_rows + 1,) or indptr[0] != 0 \
            or indptr[-1] != tags.size or (np.diff(indptr) < 0).any():
        raise ValueError(
            f"row_tags: indptr must be [rows + 1] = [{n_rows + 1}] "
            f"non-decreasing from 0 to len(tags) = {tags.size}; got shape "
            f"{indptr.shape}")
    if tags.size and tags.min() < 0:
        raise ValueError("row_tags: tag ids must be >= 0")
    return indptr, tags


def invert_bags(indptr: np.ndarray, tags: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
    """The bags by tag: ``(inv_indptr int64 [vocabulary + 1], inv_rows
    int32 [pairs])``, tag ``t``'s rows ascending at
    ``inv_rows[inv_indptr[t]:inv_indptr[t + 1]]``; the vocabulary is one
    past the largest tag id present.  A sort of 64-bit (tag, row)
    keys."""
    vocabulary = int(tags.max()) + 1 if tags.size else 0
    n = indptr.size - 1
    key = np.empty(tags.size, np.int64)
    # a run of rows a thread: forming the keys (the page faults of a
    # fresh array of the pairs' size) and sorting them both spread, and
    # the one stable sort after only has to merge the runs
    edges = np.linspace(0, n, min(8, max(1, n >> 16)) + 1).astype(np.int64)

    def run(i: int) -> None:
        lo, hi = indptr[edges[i]], indptr[edges[i + 1]]
        k = key[lo:hi]
        k[:] = tags[lo:hi]
        k <<= 32
        k |= np.repeat(np.arange(edges[i], edges[i + 1], dtype=np.int64),
                       np.diff(indptr[edges[i]:edges[i + 1] + 1]))
        k.sort()

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(run, range(edges.size - 1)))  # list(): re-raise
    key.sort(kind="stable")
    key &= 0xFFFFFFFF
    inv_rows = key.astype(np.int32)
    inv_indptr = np.zeros(vocabulary + 1, np.int64)
    np.cumsum(np.bincount(tags, minlength=vocabulary), out=inv_indptr[1:])
    return inv_indptr, inv_rows


def bags_at(inv_indptr: np.ndarray, places: np.ndarray
            ) -> Tuple[np.ndarray, np.ndarray]:
    """:func:`invert_bags`' bags with their rows renamed: ``places``
    [pairs] is ``inv_rows`` through a one-to-one map (where an
    interleaved placement laid each row), and comes back with each
    tag's rows ascending again: the bags by POSITION, which
    :func:`place_arrays` lays out for the device, beside the bags by id
    that :func:`valid_rows` reads."""
    tag = np.repeat(np.arange(inv_indptr.size - 1, dtype=np.int64),
                    np.diff(inv_indptr))
    key = (tag << 32) | places.astype(np.int64)
    key.sort()
    return inv_indptr, (key & 0xFFFFFFFF).astype(np.int32)


def check_filter_tags(filter_tags, n_q: int) -> np.ndarray:
    """int32 ``[n_q, 2]`` of a caller's ``filter_tags``, or ValueError."""
    ft = np.asarray(filter_tags)
    if ft.shape != (n_q, 2) or not np.issubdtype(ft.dtype, np.integer):
        raise ValueError(
            f"filter_tags must be whole numbers of shape [{n_q}, 2] (-1 "
            f"for an absent tag); got {ft.dtype} {ft.shape}")
    return np.ascontiguousarray(np.clip(ft, -1, np.iinfo(np.int32).max),
                                dtype=np.int32)


def tag_rows(inv_indptr: np.ndarray, inv_rows: np.ndarray, tag: int
             ) -> np.ndarray:
    """Rows (ascending) that hold ``tag``; none for an id past the
    vocabulary."""
    if not 0 <= tag < inv_indptr.size - 1:
        return inv_rows[:0]
    return inv_rows[inv_indptr[tag]:inv_indptr[tag + 1]]


def valid_rows(inv_indptr: np.ndarray, inv_rows: np.ndarray, n_rows: int,
               a: int, b: int) -> np.ndarray:
    """Rows (ascending, int64) whose bag holds every tag of the query
    ``(a, b)``, -1 = no tag in that slot: the host's statement of the
    predicate, for the repair's exact scan."""
    held = [tag_rows(inv_indptr, inv_rows, t) for t in (a, b) if t >= 0]
    if not held:
        return np.arange(n_rows, dtype=np.int64)
    if len(held) == 1:
        return held[0].astype(np.int64)
    return np.intersect1d(held[0], held[1]).astype(np.int64)


def place_arrays(inv_indptr: np.ndarray, inv_rows: np.ndarray, *,
                 n_train: int, shards: int, shard_rows: int, tile_n: int
                 ) -> dict:
    """The tag index as the arrays each shard keeps on the device, for
    ``shards`` shards of ``shard_rows`` rows by index (global row =
    shard * shard_rows + local) at row tile ``tile_n``.  Every shard
    keeps the same tags as bitmaps (the rule reads a tag's LONGEST
    shard list), so one slot table serves them all:

    - ``slots`` int32 [vocabulary]: a tag's bitmap slot, -1 where listed;
    - ``bitmaps`` int32 [shards, SLOT_TAGS + bitmap tags, word rows,
      128] (``words`` / 128 rows, padded with zeros to whole 8s): slot
      0 no row, slot 1 every row under ``n_train``, then the tags';
    - ``list_ptr`` int32 [shards, vocabulary + 1] and ``list_rows``
      int32 [shards, ids + list capacity]: the listed tags' shard-local
      rows, CSR by tag (a bitmap tag's list is empty), padded so that a
      whole-capacity read at any start stays inside;

    and what was counted: ``tags`` with a row, ``pairs``,
    ``bitmap_tags``, ``list_ids`` kept, ``bytes`` over all shards."""
    vocabulary = inv_indptr.size - 1
    n_tiles = -(-shard_rows // tile_n)
    rows_padded = n_tiles * tile_n
    n_words = n_tiles * valid_words_per_tile(tile_n)
    # whole (8, 128) tiles of word rows: a bitmap array whose second
    # dimension is off them is kept in another layout on the device and
    # copied into this one by every call (4.4 ms a batch at yfcc2m5's
    # 612 rows; root PERF.md section 6, PR 40).  The words a batch gets
    # keep the padding (cutting it off is one more copy of them, 3.8 ms)
    word_rows = -(-n_words // BIN_W // 8) * 8
    words = word_rows * BIN_W
    counts = np.diff(inv_indptr)
    if shards == 1:
        shard_of = None
        per_shard = counts[:, None]
    else:
        shard_of = inv_rows // shard_rows
        pair_tag = np.repeat(np.arange(vocabulary, dtype=np.int64), counts)
        per_shard = np.bincount(
            pair_tag * shards + shard_of, minlength=vocabulary * shards
        ).reshape(vocabulary, shards)
    mapped = per_shard.max(axis=1, initial=0) >= bitmap_min_rows(rows_padded)
    map_tags = np.flatnonzero(mapped)
    n_maps = map_tags.size
    slots = np.full(vocabulary, -1, np.int32)
    slots[map_tags] = SLOT_TAGS + np.arange(n_maps, dtype=np.int32)

    cap = list_capacity(rows_padded)
    listed = np.where(mapped[:, None], 0, per_shard)  # [vocabulary, shards]
    width = int(listed.sum(axis=0).max(initial=0)) + cap
    list_ptr = np.zeros((shards, vocabulary + 1), np.int32)
    np.cumsum(listed.T, axis=1, out=list_ptr[:, 1:])
    list_rows = np.zeros((shards, width), np.int32)
    bitmaps = np.zeros((shards, SLOT_TAGS + n_maps, words), np.uint32)
    # the pairs are grouped by tag, so the few mapped tags cut them into
    # runs: theirs, and the listed tags' between them
    cuts = np.concatenate([[0], np.stack(
        [map_tags, map_tags + 1], axis=1).reshape(-1), [vocabulary]])
    for s in range(shards):
        if shard_of is None:
            local, ptr = inv_rows, inv_indptr
        else:
            local = inv_rows[shard_of == s] - s * shard_rows
            ptr = np.concatenate([[0], np.cumsum(per_shard[:, s])])
        at = ptr[cuts]  # listed run, mapped tag, listed run, ...
        short = np.concatenate(
            [local[lo:hi] for lo, hi in zip(at[0::2], at[1::2])])
        list_rows[s, :short.size] = short
        # every valid row of the shard, then the mapped tags' rows, as
        # (slot, row) pairs packed a block of slots at a time: the bits
        # of one word are distinct, so their sum is their OR
        n_valid = int(np.clip(n_train - s * shard_rows, 0, shard_rows))
        rows = np.concatenate(
            [np.arange(n_valid, dtype=np.int32)]
            + [local[lo:hi] for lo, hi in zip(at[1:-1:2], at[2::2])])
        sizes = np.concatenate([[n_valid], at[2::2] - at[1:-1:2]])
        col, bit = valid_word_position(rows, tile_n)
        weight = np.left_shift(np.uint64(1), bit.astype(np.uint64)
                               ).astype(np.float64)
        ends = np.cumsum(sizes)  # pairs up to and with slot SLOT_ALL + j
        block = max(1, (1 << 23) // words)
        for first in range(SLOT_ALL, SLOT_TAGS + n_maps, block):
            last = min(first + block, SLOT_TAGS + n_maps)
            lo = 0 if first == SLOT_ALL else ends[first - SLOT_ALL - 1]
            hi = ends[last - SLOT_ALL - 1]
            slot = np.repeat(np.arange(last - first, dtype=np.int64),
                             sizes[first - SLOT_ALL:last - SLOT_ALL])
            bitmaps[s, first:last] = np.bincount(
                slot * words + col[lo:hi], weights=weight[lo:hi],
                minlength=(last - first) * words,
            ).astype(np.uint32).reshape(last - first, words)
    out = {
        "slots": slots,
        "bitmaps": bitmaps.view(np.int32).reshape(
            shards, SLOT_TAGS + n_maps, word_rows, BIN_W),
        "list_ptr": list_ptr, "list_rows": list_rows,
        "tags": int((counts > 0).sum()), "pairs": int(inv_rows.size),
        "bitmap_tags": n_maps, "list_ids": int(listed.sum()),
        "list_cap": cap,
    }
    out["bytes"] = sum(out[key].nbytes for key in (
        "bitmaps", "list_ptr", "list_rows")) + slots.nbytes * shards
    return out


def lookup_forms(slots: np.ndarray, counts: np.ndarray,
                 filter_tags: np.ndarray) -> dict:
    """What a batch's lookups are, from the host's copy of the slot
    table and the tags' global list lengths: ``bitmap_lookups``,
    ``list_lookups`` and ``list_ids`` (the ids the listed lookups
    name)."""
    t = filter_tags.reshape(-1)
    t = t[(t >= 0) & (t < slots.size)]  # a tag past the vocabulary: neither
    listed = slots[t] < 0
    return {"bitmap_lookups": int((~listed).sum()),
            "list_lookups": int(listed.sum()),
            "list_ids": int(counts[t][listed].sum())}


# --- the mask program's body -------------------------------------------------
def _mask_kernel(slot_a, slot_b, len_a, len_b, ids_a_ref, ids_b_ref,
                 bm_a_ref, bm_b_ref, out_ref, acc_ref, *, tile_n: int,
                 word_rows_per_tile: int):
    """One query a grid step: each tag's words start as its bitmap row
    (``slot_*`` picked the block: ``SLOT_NONE`` for a listed or unknown
    tag, ``SLOT_ALL`` for an absent one), take one bit for each of its
    ``len_*`` listed ids, and the two are ANDed."""
    del slot_a, slot_b  # read by the index maps
    q = pl.program_id(0)
    lane = lax.broadcasted_iota(jnp.int32, (1, BIN_W), 1)

    def set_bits(ids_ref, n, ref):
        def body(i, carry):
            r = ids_ref[0, 0, i]
            t = lax.div(r, jnp.int32(tile_n))
            rr = r - t * tile_n
            g = lax.shift_right_logical(rr, jnp.int32(7))
            row = t * word_rows_per_tile + lax.shift_right_logical(
                g, jnp.int32(5))
            bit = lax.shift_left(jnp.int32(1), lax.bitwise_and(
                g, jnp.int32(31)))
            cur = ref[pl.ds(row, 1), :]
            ref[pl.ds(row, 1), :] = lax.bitwise_or(cur, jnp.where(
                lane == lax.bitwise_and(rr, jnp.int32(BIN_W - 1)), bit, 0))
            return carry

        lax.fori_loop(0, n, body, 0)

    out_ref[0] = bm_a_ref[0]
    set_bits(ids_a_ref, len_a[q], out_ref.at[0])
    acc_ref[...] = bm_b_ref[0]
    set_bits(ids_b_ref, len_b[q], acc_ref)
    out_ref[0] = lax.bitwise_and(out_ref[0], acc_ref[...])


def mask_words(filter_tags, slots, bitmaps, list_ptr, list_rows, *,
               tile_n: int, list_cap: int, interpret: bool) -> jax.Array:
    """One shard's validity words of a batch, int32 ``[queries, word
    rows x 128]`` (``ops.pallas_knn.valid_word_position`` at ``tile_n``;
    the bits, not the sign, are what is read; the columns past the
    layout's own, where the bitmaps' word rows were padded to whole 8s,
    are zeros that the kernel never indexes), from ``filter_tags`` int32
    ``[queries, 2]`` and the shard's arrays of :func:`place_arrays`
    (``bitmaps`` ``[slots, word rows, 128]``, ``list_ptr``
    ``[vocabulary + 1]``, ``list_rows`` ``[ids + list_cap]``).  A tag
    id past the vocabulary matches no row; -1 in a slot constrains
    nothing; rows past the shard's valid ones are never set."""
    n_q = filter_tags.shape[0]
    vocabulary = slots.shape[0]
    n_slots, word_rows, _ = bitmaps.shape
    with jax.named_scope(SCOPE_FILTER_MASK):
        def lookup(t):
            known = (t >= 0) & (t < vocabulary)
            tc = jnp.clip(t, 0, max(vocabulary - 1, 0))
            if vocabulary:
                slot, start = slots[tc], list_ptr[tc]
                n = list_ptr[tc + 1] - start
            else:
                slot = start = n = jnp.zeros_like(t)
            listed = known & (slot < 0)
            slot = jnp.where(
                known, jnp.where(listed, SLOT_NONE, slot),
                jnp.where(t < 0, SLOT_ALL, SLOT_NONE))
            start = jnp.where(listed, start, 0)
            ids = jax.vmap(lambda s: lax.dynamic_slice(
                list_rows, (s,), (list_cap,)))(start)
            return (slot.astype(jnp.int32),
                    jnp.where(listed, n, 0).astype(jnp.int32), ids)

        slot_a, len_a, ids_a = lookup(filter_tags[:, 0])
        slot_b, len_b, ids_b = lookup(filter_tags[:, 1])
        # [queries, 1, ids]: an SMEM block's last two dimensions must be
        # the array's own
        ids_spec = pl.BlockSpec((1, 1, list_cap), lambda q, *_: (q, 0, 0),
                                memory_space=pltpu.SMEM)
        block = (1, word_rows, BIN_W)
        out = pl.pallas_call(
            functools.partial(
                _mask_kernel, tile_n=tile_n,
                word_rows_per_tile=valid_words_per_tile(tile_n) // BIN_W),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(n_q,),
                in_specs=[
                    ids_spec, ids_spec,
                    pl.BlockSpec(block, lambda q, sa, sb, la, lb:
                                 (sa[q], 0, 0)),
                    pl.BlockSpec(block, lambda q, sa, sb, la, lb:
                                 (sb[q], 0, 0)),
                ],
                out_specs=pl.BlockSpec(block, lambda q, *_: (q, 0, 0)),
                scratch_shapes=[pltpu.VMEM((word_rows, BIN_W), jnp.int32)],
            ),
            out_shape=jax.ShapeDtypeStruct((n_q, word_rows, BIN_W),
                                           jnp.int32),
            interpret=interpret,
            name="filter_mask",
        )(slot_a, slot_b, len_a, len_b, ids_a[:, None, :], ids_b[:, None, :],
          bitmaps, bitmaps)
        return out.reshape(n_q, word_rows * BIN_W)


# --- the range maker ---------------------------------------------------------
def check_row_attr(row_attr, n_rows: int) -> np.ndarray:
    """int32 ``[n_rows]`` of a caller's ``row_attr`` (one whole number a
    row), or ValueError: any integer array whose values lie within
    ``+-ATTR_MAX``."""
    attr = np.asarray(row_attr)
    if attr.shape != (n_rows,) or not np.issubdtype(attr.dtype, np.integer):
        raise ValueError(
            f"row_attr must be whole numbers of shape [{n_rows}], one a "
            f"row; got {attr.dtype} {attr.shape}")
    if attr.size and (int(attr.min()) < -ATTR_MAX
                      or int(attr.max()) > ATTR_MAX):
        raise ValueError(
            f"row_attr is compared on the device as int32: values must "
            f"lie in [-{ATTR_MAX}, {ATTR_MAX}] (the least int32 is kept "
            f"for 'no row'); got [{int(attr.min())}, {int(attr.max())}]")
    return np.ascontiguousarray(attr, dtype=np.int32)


def range_bounds(filter_range, n_q: int) -> np.ndarray:
    """int32 ``[n_q, 2]`` INCLUSIVE bounds ``(lo, last)`` of a caller's
    half-open ``filter_range`` (whole numbers ``[n_q, 2]``, ``[lo,
    hi)`` a query, any magnitude), or ValueError.  A row's attribute
    lies within ``+-ATTR_MAX``, so the bounds are clipped to that; a
    range no row answers (``lo >= hi``, or wholly outside) becomes
    ``(ATTR_MAX, -ATTR_MAX)``, which holds no value at all."""
    fr = np.asarray(filter_range)
    if fr.shape != (n_q, 2) or not np.issubdtype(fr.dtype, np.integer):
        raise ValueError(
            f"filter_range must be whole numbers of shape [{n_q}, 2], a "
            f"half-open [lo, hi) on the rows' attribute a query; got "
            f"{fr.dtype} {fr.shape}")
    fr = fr.astype(np.int64) if fr.dtype != np.uint64 else np.minimum(
        fr, np.uint64(ATTR_MAX) + np.uint64(1)).astype(np.int64)
    lo = np.clip(fr[:, 0], -ATTR_MAX, ATTR_MAX + 1)
    last = np.clip(fr[:, 1], -ATTR_MAX, ATTR_MAX + 1) - 1
    none = lo > last
    return np.ascontiguousarray(np.stack(
        [np.where(none, ATTR_MAX, lo), np.where(none, -ATTR_MAX, last)],
        axis=1), dtype=np.int32)


def range_valid_rows(attr: np.ndarray, lo: int, last: int) -> np.ndarray:
    """Rows (ascending, int64) whose attribute lies in the inclusive
    ``[lo, last]`` of :func:`range_bounds`: the host's statement of the
    predicate, for the repair's exact scan."""
    return np.flatnonzero((attr >= lo) & (attr <= last)).astype(np.int64)


def place_attr(attr: np.ndarray, *, shards: int, shard_rows: int,
               tile_n: int) -> np.ndarray:
    """The rows' attribute as each shard keeps it on the device, int32
    ``[shards, word rows x 32, 128]`` at row tile ``tile_n`` (global row
    = shard * shard_rows + local): sublane ``b`` of word row ``R`` holds
    the 128 rows whose validity is bit ``b`` of that word row's 128
    words (``valid_word_position``), so the maker reads 32 consecutive
    sublanes a word row whatever the tile.  Wherever ``tile_n % 4096 ==
    0`` that is the padded attribute itself, 128 rows a sublane.  Rows
    past the placed ones and bits past a tile's groups hold the value no
    range holds."""
    groups = tile_n // BIN_W
    n_tiles = -(-shard_rows // tile_n)
    word_rows = valid_words_per_tile(tile_n) // BIN_W  # a tile's
    out = np.full((shards, n_tiles, word_rows * 32, BIN_W), _NO_ROW,
                  np.int32)
    for s in range(shards):
        own = attr[s * shard_rows:(s + 1) * shard_rows]
        flat = np.full(n_tiles * tile_n, _NO_ROW, np.int32)
        flat[:own.size] = own
        out[s, :, :groups] = flat.reshape(n_tiles, groups, BIN_W)
    return out.reshape(shards, n_tiles * word_rows * 32, BIN_W)


def _range_kernel(lo_ref, last_ref, attr_ref, out_ref):
    """One word row (32 row groups of 128) of one block of queries a
    grid step: bit ``b`` of a query's word ``lane`` is ``lo <=
    attr[b, lane] <= last``.  The queries are walked ``_RANGE_SUB_Q`` at
    a time so that their bounds and the word being made stay in
    registers over the 32 compares."""

    def some(s, carry):
        at = pl.multiple_of(s * _RANGE_SUB_Q, _RANGE_SUB_Q)
        lo = lo_ref[pl.ds(at, _RANGE_SUB_Q), :]
        last = last_ref[pl.ds(at, _RANGE_SUB_Q), :]

        def bit(b, acc):
            a = lax.broadcast_in_dim(attr_ref[pl.ds(b, 1), :], lo.shape,
                                     (0, 1))
            ok = lax.bitwise_and(lax.ge(a, lo), lax.le(a, last))
            return lax.bitwise_or(acc, lax.select(
                ok, lax.broadcast(lax.shift_left(jnp.int32(1), b),
                                  ok.shape), lax.full_like(acc, 0)))

        out_ref[pl.ds(at, _RANGE_SUB_Q), :] = lax.fori_loop(
            0, 32, bit, lax.full_like(lo, 0), unroll=True)
        return carry

    lax.fori_loop(0, lo_ref.shape[0] // _RANGE_SUB_Q, some, 0)


def range_words(bounds, attr_rows, *, interpret: bool) -> jax.Array:
    """One shard's validity words of a batch, int32 ``[queries, word
    rows x 128]`` (``ops.pallas_knn.valid_word_position`` at the tile
    ``attr_rows`` was placed for; the bits, not the sign, are what is
    read), from ``bounds`` int32 ``[queries, 2]`` (:func:`range_bounds`)
    and the shard's attribute as :func:`place_attr` laid it out
    (``[word rows x 32, 128]``): one compare a (query, row) pair, one
    bit written.  Rows past the shard's placed ones are never set."""
    n_q = bounds.shape[0]
    word_rows = attr_rows.shape[0] // 32
    block_q = min(RANGE_BLOCK_Q, -(-n_q // _RANGE_SUB_Q) * _RANGE_SUB_Q)
    padded = -(-n_q // block_q) * block_q
    with jax.named_scope(SCOPE_RANGE_MASK):
        # the bounds go in as whole vregs, a query a sublane (what the
        # queries added here get is cut off below)
        b = jnp.pad(bounds, ((0, padded - n_q), (0, 0)))
        lo, last = (lax.broadcast_in_dim(b[:, c], (padded, BIN_W), (0,))
                    for c in (0, 1))
        q_spec = pl.BlockSpec((block_q, BIN_W), lambda q, r: (q, 0))
        out = pl.pallas_call(
            _range_kernel,
            grid=(padded // block_q, word_rows),
            in_specs=[q_spec, q_spec,
                      pl.BlockSpec((32, BIN_W), lambda q, r: (r, 0))],
            out_specs=pl.BlockSpec((block_q, BIN_W), lambda q, r: (q, r)),
            out_shape=jax.ShapeDtypeStruct((padded, word_rows * BIN_W),
                                           jnp.int32),
            interpret=interpret,
            name="range_mask",
        )(lo, last, attr_rows)
        return out[:n_q]


def words_to_valid(words, *, tile_n: int, n_rows: int) -> jax.Array:
    """bool ``[queries, n_rows]`` of a shard's validity words: the
    layout's inverse, by reshapes and shifts alone."""
    n_q = words.shape[0]
    wpt = valid_words_per_tile(tile_n)
    n_tiles = words.shape[1] // wpt
    w = words.astype(jnp.uint32).reshape(n_q, n_tiles, wpt // BIN_W, 1, BIN_W)
    shifts = lax.broadcasted_iota(jnp.uint32, (1, 1, 1, 32, 1), 3)
    bits = (w >> shifts) & jnp.uint32(1)
    valid = bits.reshape(n_q, n_tiles, wpt * 32)[:, :, :tile_n]
    return valid.reshape(n_q, n_tiles * tile_n)[:, :n_rows] != 0


def masked_topk(q, t, k: int, valid, *, train_tile: Optional[int],
                n_valid) -> Tuple[jax.Array, jax.Array]:
    """Exact float32 squared-L2 top-k of each query over the rows its
    ``valid`` (bool ``[queries, rows]``) marks, ``(d [Q, k] ascending,
    shard-local i)``, +inf and the int32 sentinel once they run out:
    ``ops.topk.knn_search_tiled``'s scan with the predicate laid over
    each tile's distances (rows at or past ``n_valid`` are padding)."""
    from knn_tpu.ops.distance import pairwise_distance
    from knn_tpu.ops.topk import merge_topk, topk_smallest

    n = t.shape[0]
    tile = n if train_tile is None else min(train_tile, n)
    n_tiles = -(-n // tile)
    pad = n_tiles * tile - n
    if pad:
        t = jnp.pad(t, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    n_q = q.shape[0]
    kk = min(k, tile)
    sentinel = jnp.iinfo(jnp.int32).max

    def step(carry, args):
        best_d, best_i = carry
        at, rows, ok = args
        d = pairwise_distance(q, rows, "l2")
        col = at * tile + lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        d = jnp.where(ok & (col < n_valid), d, jnp.inf)
        td, ti = topk_smallest(d, kk)
        return merge_topk(best_d, best_i, td, at * tile + ti, k), None

    (d, i), _ = lax.scan(
        step,
        (jnp.full((n_q, k), jnp.inf, jnp.float32),
         jnp.full((n_q, k), sentinel, jnp.int32)),
        (jnp.arange(n_tiles, dtype=jnp.int32),
         t.reshape(n_tiles, tile, t.shape[1]),
         valid.reshape(n_q, n_tiles, tile).transpose(1, 0, 2)))
    return d, jnp.where(jnp.isfinite(d), i, sentinel)


def filtered_topk_reference(db, queries, filter_tags, indptr, tags, k: int):
    """The plain statement of the contract in ``jax.numpy`` float32 at
    ``highest`` precision, for the CPU tests: validity straight from the
    bags, direct-difference squared L2 over every row, +inf where the
    bag lacks a tag, the first k by (distance, index), padded with
    index -1 and +inf.  ``[queries, rows]`` temporaries: small inputs
    only."""
    db = jnp.asarray(db, jnp.float32)
    q = jnp.asarray(queries, jnp.float32)
    n = db.shape[0]
    row_of = np.repeat(np.arange(n), np.diff(np.asarray(indptr)))
    tags = np.asarray(tags)
    valid = np.ones((q.shape[0], n), bool)
    for qi, pair in enumerate(np.asarray(filter_tags).reshape(len(q), -1)):
        for tag in pair:
            if tag >= 0:
                has = np.zeros(n, bool)
                has[row_of[tags == tag]] = True
                valid[qi] &= has
    diff = q[:, None, :] - db[None, :, :]
    d = jnp.einsum("qnd,qnd->qn", diff, diff,
                   precision=lax.Precision.HIGHEST)
    d = jnp.where(jnp.asarray(valid), d, jnp.inf)
    kk = min(k, n)
    neg, idx = lax.top_k(-d, kk)  # ties: the lower index first
    dk = -neg
    idx = jnp.where(jnp.isfinite(dk), idx, -1)
    if kk < k:
        dk = jnp.pad(dk, ((0, 0), (0, k - kk)), constant_values=jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, k - kk)), constant_values=-1)
    return np.asarray(dk), np.asarray(idx)


def range_topk_reference(db, queries, filter_range, row_attr, k: int,
                         metric: str = "l2"):
    """The plain statement of the range filter's contract in
    ``jax.numpy`` float32 at ``highest`` precision, for the CPU tests:
    validity a boolean matrix straight from the attribute (``lo <=
    attr < hi``), the metric over every row (direct-difference squared
    L2, or the cosine distance ``1 - q.t / (|q| |t|)``, a zero norm at
    cosine 0), +inf where the attribute is out of range, the first k by
    (distance, index), padded with index -1 and +inf.  ``[queries,
    rows]`` temporaries: small inputs only."""
    db = jnp.asarray(db, jnp.float32)
    q = jnp.asarray(queries, jnp.float32)
    n = db.shape[0]
    attr = np.asarray(row_attr).astype(np.int64)[None, :]
    fr = np.asarray(filter_range).astype(np.int64)
    valid = (attr >= fr[:, :1]) & (attr < fr[:, 1:])
    with jax.default_matmul_precision("highest"):
        if metric == "cosine":
            den = (jnp.sqrt(jnp.sum(q * q, axis=-1))[:, None]
                   * jnp.sqrt(jnp.sum(db * db, axis=-1))[None, :])
            d = 1.0 - jnp.where(den > 0, (q @ db.T) / jnp.where(
                den > 0, den, 1.0), 0.0)
        else:
            diff = q[:, None, :] - db[None, :, :]
            d = jnp.einsum("qnd,qnd->qn", diff, diff,
                           precision=lax.Precision.HIGHEST)
    d = jnp.where(jnp.asarray(valid), d, jnp.inf)
    kk = min(k, n)
    neg, idx = lax.top_k(-d, kk)  # ties: the lower index first
    dk = -neg
    idx = jnp.where(jnp.isfinite(dk), idx, -1)
    if kk < k:
        dk = jnp.pad(dk, ((0, 0), (0, k - kk)), constant_values=jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, k - kk)), constant_values=-1)
    return np.asarray(dk), np.asarray(idx)
