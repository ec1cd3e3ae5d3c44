"""knn_search_approx (the recall/speed knob) and dtype-generality tests —
BASELINE.json configs 4/5: cosine metric and bf16 compute with fp32
accumulation at GIST-like high dimension."""

import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu.ops.topk import knn_search, knn_search_approx
from knn_tpu.utils.timing import PhaseTimer


def _recall(pred, true):
    return sum(
        len(set(p.tolist()) & set(t.tolist())) for p, t in zip(pred, true)
    ) / true.size


def test_approx_recall_and_distances(rng):
    db = rng.normal(size=(2000, 32)).astype(np.float32)
    q = rng.normal(size=(50, 32)).astype(np.float32)
    ref_d, ref_i = knn_search(jnp.asarray(q), jnp.asarray(db), 10)
    d, i = knn_search_approx(jnp.asarray(q), jnp.asarray(db), 10, recall_target=0.95)
    assert _recall(np.asarray(i), np.asarray(ref_i)) >= 0.9
    # returned distances are squared L2 of the returned indices
    gather = np.asarray(db)[np.asarray(i)]
    want = ((gather.astype(np.float64) - np.asarray(q)[:, None].astype(np.float64)) ** 2).sum(-1)
    np.testing.assert_allclose(np.asarray(d), want, rtol=1e-3, atol=1e-3)


def test_bf16_high_dim_recall(rng):
    # GIST-like: 960-dim, bf16 matmul inputs with fp32 accumulation must
    # keep near-perfect recall on well-separated data
    db = rng.normal(size=(1500, 960)).astype(np.float32)
    q = db[:20] + 0.01 * rng.normal(size=(20, 960)).astype(np.float32)
    ref_d, ref_i = knn_search(jnp.asarray(q), jnp.asarray(db), 5)
    d, i = knn_search(jnp.asarray(q), jnp.asarray(db), 5, compute_dtype=jnp.bfloat16)
    assert _recall(np.asarray(i), np.asarray(ref_i)) >= 0.95
    # the true nearest (the perturbed source row) survives bf16
    np.testing.assert_array_equal(np.asarray(i)[:, 0], np.arange(20))


def test_cosine_high_dim(rng):
    db = rng.normal(size=(800, 300)).astype(np.float32)  # GloVe-like
    q = db[100:110] * 3.0  # same direction, different magnitude
    d, i = knn_search(jnp.asarray(q), jnp.asarray(db), 1, metric="cosine")
    np.testing.assert_array_equal(np.asarray(i)[:, 0], np.arange(100, 110))
    assert float(np.asarray(d).max()) < 1e-5


def test_phase_timer():
    timer = PhaseTimer()
    with timer.phase("a"):
        x = jnp.arange(8) * 2
        timer.block(x)
    with timer.phase("b"):
        pass
    s = timer.summary()
    assert set(s) == {"a", "b", "total"} and s["total"] >= s["a"] >= 0
