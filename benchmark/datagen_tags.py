"""Clustered byte rows that each carry a bag of tags, and tagged
queries, from ``--seed``: what a configuration's ``rows`` entry names as
``tagged_bytes`` and its ``tags`` entry describes (big-ann-benchmarks'
filter track: a descriptor and a bag of words a row; a descriptor and
one or two words a query; the answer among the rows whose bag holds
every word).  One generator a chunk of 65,536 rows, seeded ``[seed,
stream, chunk]`` as ``datagen.py`` does, so the same seed gives the same
values whatever the number of threads.

Rows (``{"dist": "tagged_bytes", "clusters", "zipf_s", "centre",
"centre_spread", "noise"}``): ``datagen_dup``'s background and nothing
else: Gaussian clusters of Zipf sizes in byte space, whole numbers
0...255 held as float32, each row's cluster kept for its bag.

Bags (``{"vocabulary": V, "zipf_s": s, "top_share": f, "cluster_tags":
T, "cluster_take": p}``): tag ``r`` (0-based) has global weight
``(r + 1)^-s``; a row draws Poisson(``f`` / tag 0's probability) tags
from that law, so tag 0 lies in about a share ``f`` of the rows; each
cluster owns ``T`` tags drawn once, uniformly over the vocabulary (a
place's or an event's own words: rare but for their cluster), and a row
also takes each of its cluster's with probability ``p`` (tags go with
content).  A bag is the sorted set of both.  CSR: ``(indptr int64
[rows + 1], tags int32)``.

Queries (:func:`draw_queries`): a candidate is a fresh row of the same
law (its cluster drawn by size, so popular clusters and their tags are
asked for more often, as in a log), its descriptor moved off the
cluster's centre along the cluster's own query direction, and one or
two tags (equal shares) copied from ITS bag: a row of the corpus the
placed rows are a share of, so nothing says the placed share holds a
row with both.  Every candidate's whole filter is counted against the
placed rows (exactly, by the bags inverted), and every batch takes the
fixed number of candidates the traffic file's ``strata`` give for each
band of that count, in drawn order; a band the draw cannot fill hands
its remainder to the next band down (the last to any left), and
:func:`draw_queries` says what each batch really holds.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Sequence, Tuple

import numpy as np

import datagen_dup
import datagen_mix
from datagen import CHUNK_ROWS, STREAM_QUERIES, rng_for

DIST = "tagged_bytes"
#: the bags' own streams (datagen's are 0...3, datagen_mix's 4,
#: datagen_dup's 5 and 6)
STREAM_TAGS, STREAM_CLUSTER_TAGS = 7, 8
#: candidates drawn a round, and rounds before a band goes short
ROUND, MAX_ROUNDS = 32_768, 12
#: a tag held by this many placed rows is counted by a packed bitmap
DENSE_ROWS = 8_192


def _tag_cdf(tags: dict) -> np.ndarray:
    w = np.arange(1, int(tags["vocabulary"]) + 1) ** -float(tags["zipf_s"])
    return np.cumsum(w / w.sum())


def global_mean(tags: dict) -> float:
    """Global tags a row draws, so that the most frequent lies in about
    ``top_share`` of the rows."""
    cdf = _tag_cdf(tags)
    return float(tags["top_share"]) / float(cdf[0])


def cluster_tags(tags: dict, clusters: int, seed: int) -> np.ndarray:
    """[clusters, cluster_tags] int32: each cluster's own tags, drawn
    once, uniformly over the vocabulary."""
    return rng_for(seed, STREAM_CLUSTER_TAGS).integers(
        0, int(tags["vocabulary"]),
        size=(clusters, int(tags["cluster_tags"])), dtype=np.int32)


def draw(spec: dict, n: int, dim: int, seed: int, stream: int
         ) -> Tuple[np.ndarray, np.ndarray]:
    """``(rows [n, dim] float32, cluster [n] int32)``."""
    if spec["dist"] != DIST:
        raise ValueError(f"rows.dist {spec['dist']!r} is not {DIST!r}")
    cen = datagen_dup._centres(spec, seed, dim)
    cdf = datagen_dup._cluster_cdf(spec)
    noise = np.float32(spec["noise"])
    out = np.empty((n, dim), np.float32)
    cluster = np.empty(n, np.int32)

    def fill(c: int) -> None:
        lo, hi = c * CHUNK_ROWS, min((c + 1) * CHUNK_ROWS, n)
        rng, block = rng_for(seed, stream, c), out[lo:hi]
        j = np.minimum(np.searchsorted(cdf, rng.random(hi - lo)),
                       len(cen) - 1)
        cluster[lo:hi] = j
        rng.standard_normal(out=block, dtype=np.float32)
        block *= noise
        block += cen[j]
        datagen_dup._to_bytes(block)

    datagen_mix._in_chunks(n, fill)
    return out, cluster


def _bag_keys(tags: dict, cdf: np.ndarray, own: np.ndarray, mean: float,
              cluster: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Sorted unique ``row << 32 | tag`` keys of the bags of rows whose
    clusters are ``cluster`` (rows numbered from 0)."""
    n = cluster.size
    n_global = rng.poisson(mean, n)
    g_row = np.repeat(np.arange(n, dtype=np.int64), n_global)
    g_tag = np.minimum(np.searchsorted(cdf, rng.random(g_row.size)),
                       cdf.size - 1)
    take = rng.random((n, own.shape[1])) < float(tags["cluster_take"])
    c_row, c_col = np.nonzero(take)
    c_tag = own[cluster[c_row], c_col]
    return np.unique(np.concatenate([
        (g_row << 32) | g_tag, (c_row.astype(np.int64) << 32) | c_tag]))


def draw_bags(tags: dict, clusters: int, cluster: np.ndarray, seed: int,
              stream: int = STREAM_TAGS) -> Tuple[np.ndarray, np.ndarray]:
    """The rows' bags as CSR ``(indptr, tags)``, tags ascending in a
    row."""
    n = cluster.size
    cdf, mean = _tag_cdf(tags), global_mean(tags)
    own = cluster_tags(tags, clusters, seed)
    n_chunks = -(-n // CHUNK_ROWS)
    parts: List = [None] * n_chunks

    def fill(c: int) -> None:
        lo, hi = c * CHUNK_ROWS, min((c + 1) * CHUNK_ROWS, n)
        parts[c] = _bag_keys(tags, cdf, own, mean, cluster[lo:hi],
                             rng_for(seed, stream, c))

    datagen_mix._in_chunks(n, fill)
    indptr = np.zeros(n + 1, np.int64)
    for c, keys in enumerate(parts):
        lo = c * CHUNK_ROWS
        rows = np.bincount(keys >> 32, minlength=min(CHUNK_ROWS, n - lo))
        indptr[lo + 1:lo + 1 + rows.size] = rows
    np.cumsum(indptr, out=indptr)
    flat = np.concatenate([(k & 0xFFFFFFFF).astype(np.int32) for k in parts]) \
        if parts else np.empty(0, np.int32)
    return indptr, flat


class Inverted:
    """The placed rows' bags by tag, for counting a filter's matches:
    each tag's rows ascending, and a packed bitmap of every tag that
    ``DENSE_ROWS`` rows or more hold."""

    def __init__(self, indptr: np.ndarray, tags: np.ndarray,
                 vocabulary: int):
        n = indptr.size - 1
        self.n = n
        # (tag << 32 | row) keys, made and sorted a run of rows at a
        # time on threads (the page faults of a fresh 200 MB array and
        # the sort both spread), then one stable sort that only has to
        # merge the runs
        key = np.empty(tags.size, np.int64)
        edges = np.linspace(0, n, min(8, max(1, n // CHUNK_ROWS)) + 1
                            ).astype(np.int64)

        def run(i: int) -> None:
            lo, hi = indptr[edges[i]], indptr[edges[i + 1]]
            k = key[lo:hi]
            k[:] = tags[lo:hi]
            k <<= 32
            k |= np.repeat(np.arange(edges[i], edges[i + 1], dtype=np.int64),
                           np.diff(indptr[edges[i]:edges[i + 1] + 1]))
            k.sort()

        with ThreadPoolExecutor(8) as pool:
            list(pool.map(run, range(edges.size - 1)))
        key.sort(kind="stable")
        key &= 0xFFFFFFFF
        self.rows = key.astype(np.int32)
        self.ptr = np.zeros(vocabulary + 1, np.int64)
        np.cumsum(np.bincount(tags, minlength=vocabulary), out=self.ptr[1:])
        self.counts = np.diff(self.ptr)
        self._dense = {}
        for t in np.flatnonzero(self.counts >= DENSE_ROWS):
            bits = np.zeros(-(-n // 64) * 64, np.uint8)
            bits[self.of(t)] = 1
            self._dense[int(t)] = np.packbits(bits).view(np.uint64)

    def of(self, tag: int) -> np.ndarray:
        return self.rows[self.ptr[tag]:self.ptr[tag + 1]]

    def matches(self, a: int, b: int) -> int:
        """Placed rows whose bag holds ``a`` and (where ``b`` >= 0)
        ``b``."""
        if b < 0 or b == a:
            return int(self.counts[a])
        if self.counts[a] > self.counts[b]:
            a, b = b, a
        if a in self._dense:  # then b is dense too
            return int(np.bitwise_count(
                self._dense[a] & self._dense[b]).sum())
        short, long_ = self.of(a), self.of(b)
        if not short.size or not long_.size:
            return 0
        at = np.minimum(np.searchsorted(long_, short), long_.size - 1)
        return int(np.count_nonzero(long_[at] == short))


def _pick_tags(keys: np.ndarray, n: int, two: np.ndarray,
               rng: np.random.Generator) -> np.ndarray:
    """[n, 2] int32: one tag of each row's bag (``keys``:
    :func:`_bag_keys`), and a second, different one where ``two`` and
    the bag has it; -1 otherwise."""
    row = (keys >> 32).astype(np.int64)
    tag = (keys & 0xFFFFFFFF).astype(np.int32)
    size = np.bincount(row, minlength=n)
    start = np.concatenate([[0], np.cumsum(size)[:-1]])
    out = np.full((n, 2), -1, np.int32)
    has = size > 0
    first = (rng.random(n) * size).astype(np.int64)
    out[has, 0] = tag[(start + first)[has]]
    # a second position among the others: skip over the first
    more = two & (size > 1)
    second = (rng.random(n) * (size - 1)).astype(np.int64)
    second += second >= first
    out[more, 1] = tag[(start + second)[more]]
    return out


def stratum_of(matches: np.ndarray, strata: Sequence[Sequence[int]]
               ) -> np.ndarray:
    """The band (index into ``strata``, each ``[least matches, queries a
    batch]``, ascending) of each match count."""
    edges = np.asarray([s[0] for s in strata], np.int64)
    return np.searchsorted(edges, matches, side="right") - 1


def draw_queries(rows_spec: dict, query_spec: dict, tags: dict,
                 inverted: Inverted, dim: int, seed: int, batch_rows: int,
                 n_batches: int, strata: Sequence[Sequence[int]]):
    """``(queries [n_batches * batch_rows, dim] float32, filter_tags
    [..., 2] int32, matches [...] int64, held [n_batches, bands])``:
    every batch holds ``strata``'s number of queries of each band of
    match counts (``held`` says what it really holds where a band ran
    short), at shuffled positions."""
    quota = np.asarray([s[1] for s in strata], np.int64)
    if quota.sum() != batch_rows:
        raise ValueError(f"strata {list(strata)} do not add up to a batch "
                         f"of {batch_rows} rows")
    clusters = int(rows_spec["clusters"])
    cen = datagen_dup._centres(rows_spec, seed, dim)
    cdf_c = datagen_dup._cluster_cdf(rows_spec)
    cdf_t, mean = _tag_cdf(tags), global_mean(tags)
    own = cluster_tags(tags, clusters, seed)
    off = datagen_mix.directions(seed, STREAM_QUERIES,
                                 datagen_mix.DIRECTIONS_CHUNK, clusters, dim)
    off *= np.float32(query_spec["offset"] * dim ** 0.5)
    need = quota * n_batches
    pools: List[List[np.ndarray]] = [[] for _ in strata]
    have = np.zeros(len(strata), np.int64)
    rest: List[np.ndarray] = []  # candidates past their band's need
    cand_q, cand_t, cand_m = [], [], []
    base = 0
    for rnd in range(MAX_ROUNDS):
        if (have >= need).all():
            break
        rng = rng_for(seed, STREAM_QUERIES, rnd)
        j = np.minimum(np.searchsorted(cdf_c, rng.random(ROUND)),
                       clusters - 1)
        keys = _bag_keys(tags, cdf_t, own, mean, j, rng)
        ft = _pick_tags(keys, ROUND, rng.random(ROUND) < 0.5, rng)
        q = rng.standard_normal((ROUND, dim), dtype=np.float32)
        q *= np.float32(query_spec["noise"])
        q += cen[j] + off[j]
        datagen_dup._to_bytes(q)
        ok = ft[:, 0] >= 0
        m = np.full(ROUND, -1, np.int64)
        # a band already full needs no count: single tags first (free),
        # pairs only while some band is short
        single = ok & (ft[:, 1] < 0)
        m[single] = inverted.counts[ft[single, 0]]
        for at in np.flatnonzero(ok & ~single):
            m[at] = inverted.matches(int(ft[at, 0]), int(ft[at, 1]))
        band = stratum_of(m, strata)
        for s in range(len(strata)):
            at = np.flatnonzero(ok & (band == s))
            take = at[:max(0, int(need[s] - have[s]))]
            pools[s].append(base + take)
            have[s] += take.size
            rest.append(base + at[take.size:])
        cand_q.append(q), cand_t.append(ft), cand_m.append(m)
        base += ROUND
    cand_q, cand_t = np.concatenate(cand_q), np.concatenate(cand_t)
    cand_m = np.concatenate(cand_m)
    pools = [np.concatenate(p) for p in pools]
    spare = np.concatenate(rest)
    out_q = np.empty((n_batches * batch_rows, dim), np.float32)
    out_t = np.empty((n_batches * batch_rows, 2), np.int32)
    out_m = np.empty(n_batches * batch_rows, np.int64)
    held = np.zeros((n_batches, len(strata)), np.int64)
    used = np.zeros(len(strata), np.int64)
    n_spare = 0
    for b in range(n_batches):
        picks, carry = [], 0
        for s in reversed(range(len(strata))):
            want = int(quota[s]) + carry
            got = pools[s][used[s]:used[s] + want]
            used[s] += got.size
            carry = want - got.size
            picks.append(got)
        if carry:  # every band ran short: any candidate left
            got = spare[n_spare:n_spare + carry]
            if got.size < carry:
                raise ValueError(
                    f"{MAX_ROUNDS} rounds of {ROUND} candidates cannot fill "
                    f"a batch of {batch_rows} queries in bands {list(strata)}")
            n_spare += carry
            picks.append(got)
        pick = np.concatenate(picks)
        rng_for(seed, STREAM_QUERIES, 2 ** 30 + b).shuffle(pick)
        at = slice(b * batch_rows, (b + 1) * batch_rows)
        out_q[at], out_t[at], out_m[at] = cand_q[pick], cand_t[pick], \
            cand_m[pick]
        held[b] = np.bincount(stratum_of(cand_m[pick], strata),
                              minlength=len(strata))
    return out_q, out_t, out_m, held
