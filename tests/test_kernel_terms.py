"""The "bf16x3" product drops the terms whose low operand is all zero
(``ops.pallas_knn.BF16X3_TERMS``): on rows (and a batch) whose float32
values are bf16-exact the kernel forms ``qh.th`` alone, on any other
data the sum it always formed.

- the kernel's raw outputs with a term dropped against the full sum,
  bit for bit, for the three db-streaming kernels, at one and at two
  dim chunks, with row padding in the last tile;
- what the host reads off the data: ``lo_halves_zero``, the placement's
  walk and where it stops asking, the batch;
- ``search_certified`` end to end against the float64 oracle on one CPU
  device and on a (1, 4) mesh, with what it reports (``terms``,
  ``mxu_passes``, the counter);
- the traced program: how many bf16 row operands the kernel takes and
  how many dots its body holds.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.obs import names as mn
from knn_tpu.ops import pallas_knn as pk
from knn_tpu.parallel import ShardedKNN, make_mesh
from tests.oracles import sq_l2, topk_lowindex

FULL, ROWS_EXACT, BOTH_EXACT = pk.BF16X3_TERMS
K = 10
TILE = 2 * pk.BIN_W
WALK_CHUNK = 8192             # rows a step of ShardedKNN._db_norm_max


def byte_values(rng, shape):
    return rng.integers(0, 256, shape).astype(np.float32)


# --- the kernel ---------------------------------------------------------
@pytest.mark.parametrize("batch", ["byte", "float"])
@pytest.mark.parametrize("dim", [128, 200])
@pytest.mark.parametrize("kernel", pk.KERNELS)
def test_a_dropped_term_changes_no_bit(kernel, dim, batch):
    """``cd``, ``ci`` and the bin bounds of ``_bin_candidates`` on byte
    rows: ``hh+lh`` equals the full sum for any batch, ``hh`` for a byte
    batch.  3 x 128 + 41 rows in tiles of 256: the last tile is mostly
    PAD_VAL rows, whose low half is NOT zero and whose scores must come
    out the same all the same (their norm swamps the product)."""
    rng = np.random.default_rng([30, dim])
    db = byte_values(rng, (3 * pk.BIN_W + 41, dim))
    q = byte_values(rng, (11, dim))
    if batch == "float":
        q = q + rng.random(q.shape, dtype=np.float32)
    run = functools.partial(
        pk._bin_candidates, jnp.asarray(q), jnp.asarray(db), block_q=8,
        tile_n=TILE, survivors=2, precision="bf16x3", interpret=True,
        kernel=kernel, keep=K + 2 if kernel == "fused" else None)
    full = [np.asarray(x) for x in run(terms=FULL)]
    assert np.isfinite(full[0]).all()
    for terms in (ROWS_EXACT, BOTH_EXACT)[:2 if batch == "byte" else 1]:
        for want, got in zip(full, run(terms=terms)):
            np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("precision,terms", [
    ("bf16x3", "hh+hl"), ("bf16x3", "lh"), ("bf16x3f", BOTH_EXACT),
    ("highest", ROWS_EXACT), ("int8", BOTH_EXACT)])
def test_terms_are_refused_where_no_such_product_is_formed(precision,
                                                           terms):
    db = jnp.zeros((TILE, 8), jnp.float32)
    with pytest.raises(ValueError, match="terms"):
        pk._bin_candidates(db[:8], db, block_q=8, tile_n=TILE,
                           survivors=2, precision=precision,
                           interpret=True, terms=terms)


# --- what the host reads off the data -------------------------------------
@pytest.mark.parametrize("values,want", [
    (np.arange(256, dtype=np.float32), True),
    (np.arange(256, dtype=np.uint8), True),            # cast, then asked
    (np.array([0.5, -3.0, 2.0 ** 100, 1.0 + 2.0 ** -7]), True),
    (np.array([1.0 + 2.0 ** -8], np.float32), False),  # a ninth bit
    (np.array([[1.0, 2.0, 0.1]], np.float32), False),
    (np.array([257.0], np.float32), False),            # 9 significant bits
    (np.array([0.1], np.float64), False),
    (np.zeros((0, 4), np.float32), True),
    (np.arange(12, dtype=np.float32).reshape(3, 4)[:, ::2], True),
])
def test_lo_halves_zero(values, want):
    assert pk.lo_halves_zero(values) is want
    # it is the statement about the bf16 cast that the kernel relies on
    f32 = jnp.asarray(values, jnp.float32)
    assert bool((f32.astype(jnp.bfloat16).astype(jnp.float32) == f32
                 ).all()) is want


@pytest.mark.parametrize("rows,batch,want", [
    (False, False, FULL), (False, True, FULL),
    (True, False, ROWS_EXACT), (True, True, BOTH_EXACT)])
def test_bf16x3_terms(rows, batch, want):
    assert pk.bf16x3_terms(rows, batch) == want
    assert want.count("+") + 1 == {FULL: 3, ROWS_EXACT: 2, BOTH_EXACT: 1}[
        want]


def exact_topk(db, q, k=K):
    return topk_lowindex(sq_l2(q, db), k)[1]


@pytest.fixture
def fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


def terms_batches():
    """``knn_tpu_kernel_terms_total`` by its ``terms`` label."""
    series = obs.snapshot().get(mn.KERNEL_TERMS, {"series": []})["series"]
    return {t: sum(s["value"] for s in series
                   if s["labels"] == {"terms": t})
            for t in pk.BF16X3_TERMS}


@pytest.mark.parametrize("corpus,want_terms,want_asked", [
    ("byte", BOTH_EXACT, 3),        # every chunk asked, all say yes
    ("one_inexact", FULL, 3),       # the LAST chunk holds the one value
    ("uniform", FULL, 1),           # the first chunk says no
])
def test_the_walk_asks_until_a_chunk_says_no(corpus, want_terms,
                                             want_asked):
    rng = np.random.default_rng([30, 1])
    n = 2 * WALK_CHUNK + 100
    db = (rng.random((n, 8), dtype=np.float32) if corpus == "uniform"
          else byte_values(rng, (n, 8)))
    if corpus == "one_inexact":
        db[-1, 3] = 0.1
    q = byte_values(rng, (4, 8))
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=K)
    asked = []
    real = pk.lo_halves_zero

    def spy(x):
        asked.append(x.shape)
        return real(x)

    pk.lo_halves_zero = spy
    try:
        assert prog._kernel_terms(q, "bf16x3") == want_terms
        # a second call walks nothing: the rows' answer is the
        # placement's, only the batch is asked again
        assert prog._kernel_terms(q + 0.5, "bf16x3") == (
            FULL if want_terms == FULL else ROWS_EXACT)
    finally:
        pk.lo_halves_zero = real
    rows_asked = [s for s in asked if s[0] > 4]
    assert len(rows_asked) == want_asked
    # the batch is asked only where the rows qualified
    assert len(asked) - len(rows_asked) == (2 if want_terms != FULL else 0)
    assert prog._kernel_terms(q, "highest") == FULL
    assert prog._db_norm_max() == float(
        (db.astype(np.float64) ** 2).sum(-1).max())


# --- end to end ---------------------------------------------------------------
@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("batch,want_terms", [
    ("byte", BOTH_EXACT), ("float", ROWS_EXACT)])
def test_a_byte_corpus_is_searched_exactly_in_fewer_passes(
        fresh_registry, shards, batch, want_terms):
    rng = np.random.default_rng([30, 2, shards])
    db = byte_values(rng, (shards * 700, 24))
    q = byte_values(rng, (9, 24))
    if batch == "float":
        q = q + rng.random(q.shape, dtype=np.float32)
    prog = ShardedKNN(
        db, mesh=make_mesh(1, shards, devices=jax.devices()[:shards]), k=K)
    before = terms_batches()
    d, i, stats = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                        batch_size=3)
    np.testing.assert_array_equal(i, exact_topk(db, q))
    np.testing.assert_allclose(
        d, np.take_along_axis(sq_l2(q, db), i, axis=1), rtol=2.0 ** -18)
    passes = want_terms.count("+") + 1
    assert (stats["terms"], stats["mxu_passes"]) == (want_terms, passes)
    assert stats["pallas_knobs"]["terms"] == want_terms
    assert stats["pallas_knobs"]["mxu_passes"] == passes
    assert stats["pallas_knobs"]["precision"] == "bf16x3"
    assert stats["tuning"]["source"] == "default"
    after = terms_batches()
    assert {t: after[t] - before[t] for t in after} == {
        **dict.fromkeys(pk.BF16X3_TERMS, 0), want_terms: 3}
    call, = [e for e in obs.get_event_log().recent()
             if e.get("span") == "certified.call"]
    assert (call["terms"], call["mxu_passes"]) == (want_terms, passes)
    # the same corpus with the full sum forced answers the same arrays
    real = ShardedKNN._kernel_terms
    ShardedKNN._kernel_terms = lambda self, q_np, precision, unit=None: FULL
    try:
        d3, i3, s3 = prog.search_certified(q, selector="pallas",
                                           tile_n=TILE, batch_size=3)
    finally:
        ShardedKNN._kernel_terms = real
    assert s3["mxu_passes"] == 3
    np.testing.assert_array_equal(i3, i)
    np.testing.assert_array_equal(d3, d)
    assert s3["fallback_queries"] == stats["fallback_queries"]
    assert s3["rank_corrected_queries"] == stats["rank_corrected_queries"]


@pytest.mark.parametrize("metric", ["cosine", "dot"])
def test_a_normalized_or_augmented_placement_never_engages(metric):
    """Cosine rows are normalized and dot rows gain a norm column at
    placement: what is placed is not bf16-exact though what came was."""
    rng = np.random.default_rng([30, 3])
    db = byte_values(rng, (600, 16)) + 1.0
    q = byte_values(rng, (5, 16)) + 1.0
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=K, metric=metric)
    _, _, stats = prog.search_certified(q, selector="pallas", tile_n=TILE)
    assert (stats["terms"], stats["mxu_passes"]) == (FULL, 3)
    assert not prog._rows_lo_zero


def test_other_precisions_form_what_they_always_did():
    rng = np.random.default_rng([30, 4])
    db, q = byte_values(rng, (600, 16)), byte_values(rng, (5, 16))
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=K)
    _, i, stats = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                        precision="highest")
    np.testing.assert_array_equal(i, exact_topk(db, q))
    assert (stats["terms"], stats["mxu_passes"]) == (FULL, 3)
    assert prog._rows_lo_zero          # seen, and not used


# --- the traced program ---------------------------------------------------
def kernel_calls(jaxpr):
    """Every ``pallas_call`` equation under a jaxpr, outermost first."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from kernel_calls(sub)


@pytest.mark.parametrize("corpus,batch,row_operands,dots", [
    ("float", "byte", 2, 3), ("float", "float", 2, 3),
    ("byte", "float", 1, 2), ("byte", "byte", 1, 1)])
def test_the_certified_program_streams_and_multiplies_what_is_left(
        corpus, batch, row_operands, dots):
    rng = np.random.default_rng([30, 5])
    db, q = byte_values(rng, (600, 16)), byte_values(rng, (8, 16))
    if corpus == "float":
        db = db + rng.random(db.shape, dtype=np.float32)
    if batch == "float":
        q = q + rng.random(q.shape, dtype=np.float32)
    placed = ShardedKNN(db, mesh=make_mesh(1, 1), k=K)
    prog, _, _, _ = placed._pallas_setup(
        28, TILE, "bf16x3", terms=placed._kernel_terms(q, "bf16x3"))
    qp, _ = placed._place_queries(q)
    traced = jax.make_jaxpr(prog)(qp, placed._tp,
                                  *placed._pallas_operands("bf16x3"))
    call = next(kernel_calls(traced.jaxpr))
    assert sum(v.aval.dtype == jnp.bfloat16
               for v in call.invars) == row_operands
    body = call.params["jaxpr"]
    assert sum(e.primitive.name == "dot_general"
               for e in body.eqns) == dots
