"""NumPy fp64 oracles mirroring the reference program's semantics.

These re-state the behavior of knn_mpi.cpp in NumPy (not copies — the
reference is scalar C++); tests check the JAX ops against them.
"""

import numpy as np


def sq_l2(q, t):
    """||q-t||^2 oracle for Euclidean_D (knn_mpi.cpp:33-50) minus the
    monotone sqrt."""
    diff = q[:, None, :].astype(np.float64) - t[None, :, :].astype(np.float64)
    return np.sum(diff * diff, axis=-1)


def l1(q, t):
    """Manhattan_D oracle (knn_mpi.cpp:51-67)."""
    diff = q[:, None, :].astype(np.float64) - t[None, :, :].astype(np.float64)
    return np.sum(np.abs(diff), axis=-1)


def cosine(q, t):
    qn = q / np.linalg.norm(q, axis=-1, keepdims=True)
    tn = t / np.linalg.norm(t, axis=-1, keepdims=True)
    return 1.0 - qn @ tn.T


def topk_lowindex(d, k):
    """k smallest per row, ties to lower index (the framework's documented
    tie-break; the reference's std::sort leaves it unspecified)."""
    idx = np.argsort(d, axis=-1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, axis=-1), idx


def running_argmax_vote(neighbor_labels, num_classes):
    """The reference's vote loop verbatim in semantics (knn_mpi.cpp:324-336):
    histogram over neighbors in distance order, running argmax with strict >,
    first label to reach the final max wins."""
    out = np.empty(neighbor_labels.shape[0], dtype=np.int32)
    for i, row in enumerate(neighbor_labels):
        counts = np.zeros(num_classes, dtype=np.int64)
        best, best_label = 0, 0
        for lab in row:
            counts[lab] += 1
            if counts[lab] > best:
                best = counts[lab]
                best_label = lab
        out[i] = best_label
    return out


def minmax_normalize_transductive(train, test=None, val=None):
    """Joint extrema over all sets, constant dims untouched
    (knn_mpi.cpp:229-306 with the ±inf init fix)."""
    parts = [a for a in (train, test, val) if a is not None]
    stacked = np.concatenate([p.astype(np.float64) for p in parts], axis=0)
    mins, maxs = stacked.min(0), stacked.max(0)
    rng = maxs - mins

    def apply(x):
        if x is None:
            return None
        x = x.astype(np.float64)
        return np.where(rng != 0, (x - mins) / np.where(rng != 0, rng, 1.0), x)

    return apply(train), apply(test), apply(val)


def knn_classify(train, labels, queries, k, num_classes, metric="l2"):
    """End-to-end oracle: distances -> lowest-k (low-index ties) -> reference
    vote."""
    d = sq_l2(queries, train) if metric in ("l2", "sql2", "euclidean") else l1(queries, train)
    _, idx = topk_lowindex(d, k)
    return running_argmax_vote(labels[idx], num_classes)


def assert_same_neighbors(d, i, ref_d, ref_i, q, db):
    """What the search contract promises between two DIFFERENTLY SHAPED
    programs (a flat and a hierarchical mesh, a resident and a streamed
    placement, a superblock and a looped batch): equal indices, and
    squared-L2 f32 distances within the rounding of the expanded-square
    form — 8 eps_f32 (||q||^2 + max||t||^2) per query, the slack
    ops.certified budgets for the same arithmetic.  XLA may order a
    gemm's K-reduction differently for every operand shape, so bitwise
    f32 distances are promised only between runs of the same program
    (docs/serving.md); tests of that keep assert_array_equal."""
    from knn_tpu.ops.certified import certification_tolerance

    np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))
    tol = certification_tolerance(np.asarray(q), np.asarray(db))
    err = np.abs(np.asarray(d, np.float64) - np.asarray(ref_d, np.float64))
    assert (err <= tol[:, None]).all(), (
        f"distances differ by up to {err.max():.3e}, over the f32 "
        f"rounding bound {tol.min():.3e}")
