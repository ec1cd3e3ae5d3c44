"""Product quantization with a *certified* per-subspace error bound —
the arithmetic behind the kernel's ``precision="pq"`` arm.

Below int8 the per-dim ladder runs out: a few bits a dim are a handful
of levels of the WHOLE dynamic range and the certified ε stops
excluding anything.  Product quantization changes the axis instead —
split the dim into ``m = ceil(d / dsub)`` subspaces, train a
``C``-codeword codebook per subspace, and a row becomes ``m`` bytes: at
SIFT's d=128
with the classic (dsub=4, C=256) point that is 32 B/row, 1/16 the f32
stream and 1/4 int8's (ISSUE 17 / ROADMAP item 4).

Training is the SEEDED DETERMINISTIC k-means the IVF tier already
ships (``knn_tpu.ivf.kmeans.train_kmeans``): same sharded Lloyd assign
(ShardedKNN k=1, lexicographic ties), same farthest-point init, same
host-f64 segment-mean update — one subspace-offset seed each, so a
(rows, dsub, ncodes, seed) tuple always yields bit-identical codebooks
regardless of mesh shape.

Scoring is ASYMMETRIC (query exact, db reconstructed): the kernel
streams the byte codes and the query side rides as a per-query lookup
table

    LUT[q, s*C + c] = q_s · cb[s, c] - ||cb[s, c]||² / 2

so one dense MXU dot of the LUT against the codes' one-hot expansion
yields ``qt = q·t̂ - ||t̂||²/2`` and the shared emitters' ``tn - 2·qt``
(tn = 0 on valid rows) is ``||t̂||² - 2 q·t̂`` — the standard kernel
score against the reconstruction t̂ (ops.pallas_knn._pq_onehot_qt).

Error bound derivation (the certificate's ε).  With t = t̂ + e, the
kernel-space score error is

    s(t) - ŝ(t) = (||t||² - ||t̂||²) - 2 q·(t - t̂)

The second term splits PER SUBSPACE, and Cauchy–Schwarz applies in
each: |q·e| = |Σ_s q_s·e_s| <= Σ_s ||q_s|| · r_s with
``r_s = max_rows ||t_s - t̂_s||`` hoisted at encode time (f64, actual
residuals — a tight codebook certifies tightly, exactly like the int8
bound's actual-residual discipline).  The norm term is bounded by its
own hoisted maximum, so

    ε = ( norm_err_max + 2 Σ_s ||q_s|| r_s ) * (1 + 2^-10)
        + 64·eps_f32 · (||q||² + max||t||²)

with the same headroom/f32-slack budget as ops.quantize.  Per-query,
per-subspace: a query aligned with a well-quantized subspace certifies
tighter than the worst-case row.  ``tests/test_pq.py`` property-checks
ε >= observed |exact - coarse| across dims/dsub/codebook sizes (f64
and f32-arithmetic reconstruction) and pins the forced-miss path:
detection -> fallback repair -> bitwise-exact final results.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from knn_tpu.ops.quantize import _BOUND_HEADROOM, _F32_SLACK, _f32_up


class PQResult(NamedTuple):
    """A trained product quantizer + the encoded corpus.

    ``codebooks`` f32 [m, C, dsub] (subspace-major); ``codes`` uint8
    [N, m] (row-major — the list-major byte tensor the kernel streams);
    ``dim`` is the ORIGINAL feature width (rows zero-pad to
    ``m * dsub`` for training, and queries zero-pad the same way in
    the LUT prologue, so the split always matches); ``stats`` the
    hoisted bound maxima (:func:`pq_bound_stats`)."""

    codebooks: np.ndarray
    codes: np.ndarray
    dsub: int
    dim: int
    stats: dict

    @property
    def nsub(self) -> int:
        return int(self.codebooks.shape[0])

    @property
    def ncodes(self) -> int:
        return int(self.codebooks.shape[1])


def _pad_dim(x: np.ndarray, width: int) -> np.ndarray:
    if x.shape[1] == width:
        return x
    out = np.zeros((x.shape[0], width), dtype=x.dtype)
    out[:, : x.shape[1]] = x
    return out


def train_pq(rows: np.ndarray, *, mesh, dsub: int = 4, ncodes: int = 256,
             iters: int = 5, seed: int = 0,
             train_tile: Optional[int] = None) -> PQResult:
    """Train per-subspace codebooks with the IVF tier's seeded
    deterministic k-means and encode ``rows``.  ``seed + s`` seeds
    subspace ``s`` — deterministic, and distinct subspaces never share
    an init row pick by construction of their distinct data."""
    from knn_tpu.ivf.kmeans import train_kmeans

    rows = np.ascontiguousarray(np.asarray(rows, np.float32))
    n, d = rows.shape
    dsub = int(dsub)
    if dsub < 1:
        raise ValueError(f"dsub must be >= 1, got {dsub}")
    if not 2 <= int(ncodes) <= 256:
        raise ValueError(
            f"ncodes must be in [2, 256] (one uint8 code per subspace), "
            f"got {ncodes}")
    m = -(-d // dsub)
    padded = _pad_dim(rows, m * dsub)
    books, codes = [], []
    c_eff = min(int(ncodes), n)
    for s in range(m):
        sub = padded[:, s * dsub : (s + 1) * dsub]
        km = train_kmeans(sub, c_eff, mesh=mesh, iters=iters,
                          seed=seed + s, train_tile=train_tile)
        books.append(km.centroids)
        codes.append(km.assign)
    codebooks = np.stack(books).astype(np.float32)  # [m, C, dsub]
    codes = np.stack(codes, axis=1).astype(np.uint8)  # [N, m]
    stats = pq_bound_stats(codebooks, codes, rows, dsub=dsub)
    return PQResult(codebooks, codes, dsub, d, stats)


def encode_pq(rows: np.ndarray, codebooks: np.ndarray, *, mesh,
              dsub: int, train_tile: Optional[int] = None) -> np.ndarray:
    """Encode NEW rows against trained codebooks (delta-shard inserts):
    the same sharded k=1 assign as training, per subspace.  Returns
    uint8 [N, m].  NOTE: freshly encoded rows can exceed the hoisted
    ``r_s`` maxima — callers must refresh stats via
    :func:`pq_bound_stats` before certifying against them."""
    from knn_tpu.ivf.kmeans import assign_lists

    rows = np.asarray(rows, np.float32)
    m = codebooks.shape[0]
    padded = _pad_dim(rows, m * int(dsub))
    cols = []
    for s in range(m):
        sub = padded[:, s * dsub : (s + 1) * dsub]
        cols.append(assign_lists(sub, codebooks[s], mesh=mesh,
                                 train_tile=train_tile))
    return np.stack(cols, axis=1).astype(np.uint8)


def reconstruct(codebooks: np.ndarray, codes: np.ndarray, dim: int,
                dsub: int) -> np.ndarray:
    """f32 decode [N, dim] — the t̂ the kernel scores against (tests /
    bound computation)."""
    m = codebooks.shape[0]
    parts = [codebooks[s][codes[:, s]] for s in range(m)]
    return np.concatenate(parts, axis=1)[:, :dim].astype(np.float32)


def build_luts(q: np.ndarray, codebooks: np.ndarray,
               dsub: int) -> np.ndarray:
    """Host twin of the kernel's XLA LUT prologue (tests):
    [Q, m * C] f32 with LUT[q, s*C + c] = q_s·cb[s,c] - ||cb[s,c]||²/2."""
    q = np.asarray(q, np.float32)
    m, c, _ = codebooks.shape
    qp = _pad_dim(q, m * int(dsub)).reshape(q.shape[0], m, dsub)
    lut = (np.einsum("qmd,mcd->qmc", qp, codebooks)
           - 0.5 * (codebooks ** 2).sum(-1)[None])
    return lut.reshape(q.shape[0], m * c).astype(np.float32)


def pq_bound_stats(codebooks: np.ndarray, codes: np.ndarray,
                   original: np.ndarray, *, dsub: int,
                   chunk: int = 65536) -> dict:
    """The db-side maxima of the PQ error bound, float64 from the
    ACTUAL residuals at encode time:

      ``r_sub``        [m] max_rows ||t_s - t̂_s||  per subspace,
      ``norm_err_max`` max_rows |  ||t||² - ||t̂||²  |,
      ``db_norm_max``  max_rows ||t||²  (the f32-slack scale).

    Chunked so a 1M-row corpus never materializes a full f64 copy."""
    original = np.asarray(original)
    m = codebooks.shape[0]
    dim = original.shape[1]
    books64 = codebooks.astype(np.float64)
    r_sub = np.zeros(m, np.float64)
    norm_err = 0.0
    nrm = 0.0
    n = original.shape[0]
    for lo in range(0, n, chunk):
        hi = min(n, lo + chunk)
        t = _pad_dim(original[lo:hi].astype(np.float64), m * dsub)
        t_norm = (t ** 2).sum(-1)
        that_norm = np.zeros(hi - lo, np.float64)
        for s in range(m):
            t_s = t[:, s * dsub : (s + 1) * dsub]
            that_s = books64[s][codes[lo:hi, s]]
            diff = t_s - that_s
            r_sub[s] = max(r_sub[s],
                           float(np.sqrt((diff ** 2).sum(-1)).max()))
            that_norm += (that_s ** 2).sum(-1)
        norm_err = max(norm_err, float(np.abs(t_norm - that_norm).max()))
        nrm = max(nrm, float(t_norm.max()))
    return {
        "r_sub": r_sub,
        "norm_err_max": float(norm_err),
        "db_norm_max": float(nrm),
        "dsub": int(dsub),
        "dim": int(dim),
    }


def bound_consts_pq(stats: dict) -> np.ndarray:
    """[r_0 .. r_{m-1}, norm_err_max, db_norm_max] as an f32 vector
    (each rounded UP) — the replicated operand the sharded pq program
    consumes, ONE packing home shared with
    :func:`score_error_bound_pq_device`'s unpacking."""
    vals = [ _f32_up(float(r)) for r in stats["r_sub"] ]
    vals += [_f32_up(stats["norm_err_max"]), _f32_up(stats["db_norm_max"])]
    return np.array(vals, dtype=np.float32)


def score_error_bound_pq(q: np.ndarray, stats: dict) -> np.ndarray:
    """Host-side per-query ε [Q] (float64): sound upper bound on
    |kernel-space exact score - PQ reconstruction score| for EVERY db
    row (module docstring).  Mirrors
    :func:`score_error_bound_pq_device`; tests/test_pq.py pins
    ε >= observed."""
    q64 = np.asarray(q, np.float64)
    m = len(stats["r_sub"])
    dsub = stats["dsub"]
    qp = _pad_dim(q64, m * dsub).reshape(q64.shape[0], m, dsub)
    qs_norm = np.sqrt((qp ** 2).sum(-1))  # [Q, m]
    q_norm = (q64 ** 2).sum(-1)
    quant = stats["norm_err_max"] + 2.0 * (qs_norm
                                           * stats["r_sub"][None, :]).sum(-1)
    return (quant * _BOUND_HEADROOM
            + _F32_SLACK * (q_norm + stats["db_norm_max"]))


def score_error_bound_pq_device(q, consts, *, dsub: int):
    """Traceable twin of :func:`score_error_bound_pq` for the sharded
    certificate program: ``q`` [Q, D] f32, ``consts`` the
    :func:`bound_consts_pq` vector ([m + 2] f32), ``dsub`` static.
    Returns ``(q_norm [Q], eps [Q])``."""
    import jax.numpy as jnp

    m = consts.shape[0] - 2
    d = q.shape[1]
    if d < m * dsub:
        q_pad = jnp.pad(q, ((0, 0), (0, m * dsub - d)))
    else:
        q_pad = q[:, : m * dsub]
    qs = q_pad.reshape(q.shape[0], m, dsub)
    qs_norm = jnp.sqrt(jnp.sum(qs * qs, axis=-1))  # [Q, m]
    q_norm = jnp.sum(q * q, axis=-1)
    quant = consts[m] + 2.0 * jnp.sum(qs_norm * consts[None, :m], axis=-1)
    eps = quant * _BOUND_HEADROOM + _F32_SLACK * (q_norm + consts[m + 1])
    return q_norm, eps
