"""The oracle against a brute force small enough to write in one line,
the comparison, the seeded data, and the CONTROL: the reference in a
lower precision, put in the program's place, has to come out as not
correct by each configuration's own limits."""

import json
import os

import numpy as np
import pytest

import datagen
import reference

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


def brute(db, q, k):
    d = ((q[:, None, :].astype(np.float64)
          - db[None, :, :].astype(np.float64)) ** 2).sum(-1)
    order = np.lexsort((np.broadcast_to(np.arange(db.shape[0]), d.shape), d),
                       axis=-1)[:, :k]
    return order, np.take_along_axis(d, order, axis=1)


@pytest.mark.parametrize("dist", ["uint8", "uniform"])
def test_oracle_equals_a_200_row_brute_force(dist):
    db = datagen.draw({"dist": dist}, 200, 16, 11, datagen.STREAM_ROWS)
    q = datagen.draw({"dist": dist}, 9, 16, 11, datagen.STREAM_QUERIES)
    i, d = reference.oracle_topk(db, q, 10)
    bi, bd = brute(db, q, 10)
    assert np.array_equal(i, bi)
    np.testing.assert_allclose(d, bd, rtol=1e-12)


def test_oracle_breaks_ties_by_index_across_chunks(monkeypatch):
    monkeypatch.setattr(reference, "CHUNK", 64)
    db = np.zeros((300, 4), np.float32)  # every row the same distance
    i, _ = reference.oracle_topk(db, np.ones((2, 4), np.float32), 100)
    assert np.array_equal(i, np.tile(np.arange(100), (2, 1)))


def test_same_seed_same_rows_and_large_seeds_work():
    a = datagen.draw({"dist": "uint8"}, 70_000, 8, 2**31 + 99, 0)
    b = datagen.draw({"dist": "uint8"}, 70_000, 8, 2**31 + 99, 0)
    c = datagen.draw({"dist": "uint8"}, 70_000, 8, 2**31 + 100, 0)
    assert a.dtype == np.float32 and np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.min() == 0 and a.max() == 255 and (a == np.round(a)).all()
    u = datagen.draw({"dist": "uniform", "high": 1.0}, 1000, 8, 5, 0)
    assert 0 <= u.min() and u.max() < 1


def test_compare_counts_what_differs():
    want_i = np.arange(12).reshape(3, 4)
    want_d = np.arange(12, dtype=np.float64).reshape(3, 4) + 1
    same = reference.compare(want_i, want_d, want_i, want_d)
    assert same == {"rows": 3, "mismatched_rows": 0, "recall": 1.0,
                    "dist_rel_err_max": 0.0}
    got_i = want_i.copy()
    got_i[1, 3] = 99
    got_d = want_d.copy()
    got_d[2, 0] *= 1.01
    off = reference.compare(got_i, got_d, want_i, want_d)
    assert off["mismatched_rows"] == 1
    assert off["recall"] == pytest.approx(11 / 12)
    assert off["dist_rel_err_max"] == pytest.approx(0.01)
    got_d[0, 0] = np.nan
    assert reference.compare(got_i, got_d, want_i, want_d)[
        "dist_rel_err_max"] == np.inf


def test_checks_hold_each_number_to_its_limit():
    c = reference.Checks()
    assert not c.correct  # nothing compared is not correct
    c.add("a", 0, 0)
    c.add("r", 0.9995, 0.999, at_least=True)
    assert c.correct
    c.add("b", float("nan"), 1.0)
    assert not c.correct


def sweep_checks(cfg, got, want):
    cmp = reference.compare(*got, *want)
    c = reference.Checks()
    c.add("mismatched_rows", cmp["mismatched_rows"],
          cfg["limits"]["mismatched_rows"])
    c.add("dist_rel_err_max", cmp["dist_rel_err_max"],
          cfg["limits"]["dist_rel_err_max"])
    return c, cmp


# the control at a size a test can hold; control.py reads it at the
# cells' own sizes (PERF.md has the readings the limits were set from)
@pytest.mark.parametrize("name,precision,rows", [
    ("gist1m", "f32", 20_000),  # float rows: float32 ranking swaps near-ties
    ("bigann5m", "int4", 20_000),  # byte rows: float32 is exact; int4 is not
])
def test_the_lower_precision_control_comes_out_not_correct(
        name, precision, rows):
    cfg = config(name)
    db = datagen.draw(cfg["rows"], rows, cfg["dim"], 21, datagen.STREAM_ROWS)
    q = datagen.draw(cfg["rows"], 32, cfg["dim"], 21, datagen.STREAM_QUERIES)
    want = reference.oracle_topk(db, q, cfg["k"])
    sound, _ = sweep_checks(cfg, want, want)
    assert sound.correct
    control, cmp = sweep_checks(
        cfg, reference.lowprec_topk(db, q, cfg["k"], precision), want)
    assert not control.correct, cmp


def test_float32_is_exact_on_byte_rows_so_it_is_no_control_there():
    cfg = config("bigann5m")
    db = datagen.draw(cfg["rows"], 20_000, cfg["dim"], 22, datagen.STREAM_ROWS)
    q = datagen.draw(cfg["rows"], 16, cfg["dim"], 22, datagen.STREAM_QUERIES)
    want = reference.oracle_topk(db, q, cfg["k"])
    control, cmp = sweep_checks(
        cfg, reference.lowprec_topk(db, q, cfg["k"], "f32"), want)
    assert control.correct and cmp["dist_rel_err_max"] == 0.0


def test_the_serving_control_in_bfloat16_comes_out_not_correct():
    cfg = config("bigann5m")
    db = datagen.draw(cfg["rows"], 50_000, cfg["dim"], 23, datagen.STREAM_ROWS)
    q = datagen.draw(cfg["rows"], 32, cfg["dim"], 23, datagen.STREAM_QUERIES)
    want = reference.oracle_topk(db, q, cfg["k"])
    cmp = reference.compare(
        *reference.lowprec_topk(db, q, cfg["k"], "bf16"), *want)
    c = reference.Checks()
    c.add("recall", cmp["recall"], cfg["limits"]["recall_min"], at_least=True)
    c.add("serve_dist_rel_err_max", cmp["dist_rel_err_max"],
          cfg["limits"]["serve_dist_rel_err_max"])
    assert not c.correct
    assert not c.rows[1]["ok"], "the distance gap alone must catch bf16"
