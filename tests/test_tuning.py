"""The one home of the kernel's knobs (knn_tpu.tuning): a knob comes
from the call or from ``DEFAULT_KNOBS`` and from nowhere else.  An
explicit knob beats the default, an unknown one is refused, the
full-width kernels get their own block_q, a winner cache left over from
before PR 59 (the file the parent's ``TuneCache.put`` wrote, named by
the environment variable the parent read) chooses nothing at any
benchmark cell's shape, and a ``tune_cache`` / ``cache_path`` that is
not None raises instead of being ignored."""

import json
import os

import numpy as np
import pytest
from cells import CONFIGS, REPO, config as cell_config

from knn_tpu import tuning

#: a winner cache as the PARENT of PR 59 wrote it (``TuneCache.put``,
#: once, device kind ``cpu``, keys ``...|rl8|kv11``): a non-default
#: winner (streaming, tile_n 8192, block_q 64) for every configuration's
#: ``(rows_n, dim [+1 under dot], k, l2, float32)``
OLD_CACHE = os.path.join(REPO, "tests", "fixtures", "pr58_tune_cache.json")

#: one value other than the default for every knob
OTHER = {
    "kernel": "streaming", "tile_n": 384, "block_q": 16, "survivors": 3,
    "precision": "highest", "final_select": "approx",
    "grid_order": "db_major", "final_recall_target": 0.99,
}


@pytest.fixture
def data(rng):
    db = rng.normal(size=(700, 16)).astype(np.float32) * 10
    q = rng.normal(size=(9, 16)).astype(np.float32) * 10
    return db, q


def test_the_cells_are_the_eleven_and_every_knob_has_another_value():
    assert len(CONFIGS) == 11
    assert set(OTHER) == set(tuning.DEFAULT_KNOBS)
    assert all(OTHER[kk] != tuning.DEFAULT_KNOBS[kk] for kk in OTHER)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_a_winner_cache_from_before_pr59_chooses_nothing(config,
                                                         monkeypatch):
    """The parent read ``KNN_TPU_TUNE_CACHE`` in every call and ran the
    winner it found for the placement's shape: with this file it ran the
    streaming kernel at every cell.  Nothing reads the variable now."""
    cfg = cell_config(config)
    width = cfg["dim"] + (cfg["metric"] == "dot")  # dot's norm column
    with open(OLD_CACHE) as f:
        (entry,) = [e for key, e in json.load(f)["entries"].items()
                    if key.startswith(
                        f"cpu|n{cfg['rows_n']}|d{width}|k{cfg['k']}|l2|")]
    assert entry["knobs"]["kernel"] == "streaming"
    monkeypatch.setenv("KNN_TPU_TUNE_CACHE", OLD_CACHE)
    knobs, info = tuning.resolve_full(
        cfg["rows_n"], width, cfg["k"], metric="l2", dtype=None)
    assert knobs == tuning.DEFAULT_KNOBS
    assert info == {"source": "default", "overridden": []}


@pytest.mark.parametrize("knob", sorted(tuning.DEFAULT_KNOBS))
def test_a_knob_named_in_the_call_wins(knob):
    knobs, info = tuning.resolve_full(
        700, 16, 5, overrides={**dict.fromkeys(tuning.DEFAULT_KNOBS),
                               knob: OTHER[knob]})
    want = {**tuning.DEFAULT_KNOBS, knob: OTHER[knob]}
    if knob == "kernel":  # a full-width kernel brings its own block_q
        want["block_q"] = tuning.FULL_WIDTH_BLOCK_Q
    assert knobs == want
    assert info == {"source": "default", "overridden": [knob]}


def test_full_width_kernels_default_to_their_own_block_q():
    """streaming/fused hold every db tile's candidates in VMEM at once:
    left alone they resolve block_q=128 (the tiled default's 256 does
    not fit them beyond SIFT); a caller's block_q still wins."""
    assert tuning.resolve(700, 16, 5)["block_q"] == 256
    for kern in ("streaming", "fused"):
        knobs, info = tuning.resolve_full(
            700, 16, 5, overrides={"kernel": kern})
        assert info["source"] == "default"
        assert knobs["block_q"] == tuning.FULL_WIDTH_BLOCK_Q == 128
        pinned = tuning.resolve(700, 16, 5,
                                overrides={"kernel": kern, "block_q": 256})
        assert pinned["block_q"] == 256


def test_explicit_knobs_beat_the_defaults(data):
    db, q = data
    knobs, info = tuning.resolve_full(
        700, 16, 5, overrides={"kernel": "tiled", "block_q": 16,
                               "tile_n": None})
    assert knobs == {**tuning.DEFAULT_KNOBS, "block_q": 16}
    assert info["overridden"] == ["block_q", "kernel"]

    # end to end through ShardedKNN.search_certified: a call that names
    # no knob runs the defaults, explicit args win, the stats record both
    from knn_tpu.parallel import ShardedKNN, make_mesh

    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=5)
    _, i_default, st = prog.search_certified(q, selector="pallas", margin=8)
    assert st["tuning"] == {"source": "default", "overridden": []}
    # beside the knobs: what the program resolved for itself, from the
    # backend (interpret), from the data (terms, mxu_passes) and from
    # the launch's shape (dim_chunk(s), row_block / row_steps,
    # final_select_stage, select_merge_short) and
    # from the device's memory (operands), and how the call was cut
    # (sub_batch, batches: analysis.subbatch)
    assert {kk: v for kk, v in st["pallas_knobs"].items()
            if kk not in ("interpret", "terms", "mxu_passes", "dim_chunk",
                          "dim_chunks", "row_block", "row_steps",
                          "final_select_stage", "select_merge_short",
                          "operands", "sub_batch", "batches",
                          "survivor_depth")
            } == tuning.DEFAULT_KNOBS
    assert (st["pallas_knobs"]["dim_chunk"],
            st["pallas_knobs"]["dim_chunks"]) == (128, 1)
    assert st["pallas_knobs"]["row_steps"] == 1
    _, i_over, st2 = prog.search_certified(
        q, selector="pallas", margin=8, kernel="streaming", tile_n=384)
    assert st2["pallas_knobs"]["kernel"] == "streaming"
    assert st2["pallas_knobs"]["tile_n"] == 384
    assert st2["pallas_knobs"]["block_q"] == tuning.FULL_WIDTH_BLOCK_Q
    assert st2["tuning"] == {"source": "default",
                             "overridden": ["kernel", "tile_n"]}
    # exactness is knob-independent (the certified contract)
    np.testing.assert_array_equal(i_default, i_over)


@pytest.fixture(scope="module")
def placed():
    from knn_tpu.parallel import ShardedKNN, make_mesh

    rng = np.random.default_rng(59)
    db = rng.normal(size=(700, 16)).astype(np.float32) * 10
    q = rng.normal(size=(9, 16)).astype(np.float32) * 10
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=5)
    _, idx, _ = prog.search_certified(q, selector="pallas", margin=8)
    return prog, q, idx


@pytest.mark.parametrize("knob", sorted(tuning.DEFAULT_KNOBS))
def test_a_knob_named_in_the_call_reaches_the_kernel(knob, placed):
    """Every arm is reachable by its argument alone (ROADMAP D3: nothing
    enumerates them any more): the call's stats say the kernel ran it,
    no other knob moved but the full-width kernels' block_q, and the
    answer is the defaults' (exactness is knob-independent)."""
    prog, q, idx_default = placed
    named = {knob: OTHER[knob]}
    if knob == "final_recall_target":  # the approx final select's own
        named["final_select"] = "approx"
    _, idx, st = prog.search_certified(q, selector="pallas", margin=8,
                                       **named)
    want = {**tuning.DEFAULT_KNOBS, **named}
    if knob == "kernel":
        want["block_q"] = tuning.FULL_WIDTH_BLOCK_Q
    if knob == "survivors":  # the rule's output elsewhere: see the depth
        assert st["pallas_knobs"]["survivor_depth"] == OTHER[knob]
    assert {kk: st["pallas_knobs"][kk] for kk in want} == want
    assert st["tuning"] == {"source": "default",
                            "overridden": sorted(named)}
    np.testing.assert_array_equal(idx, idx_default)


def test_resolve_rejects_unknown_knob():
    with pytest.raises(ValueError, match="unknown pallas knob"):
        tuning.resolve(100, 8, 3, overrides={"warp_speed": 9})


@pytest.mark.parametrize("through", [
    "resolve_full", "search_certified", "predict_certified",
    "certified_plan"])
def test_a_tune_cache_is_refused_never_ignored(through, data, tmp_path):
    """``tune_cache=`` is still in four signatures (ROADMAP D20: their
    frames are on the trace stack) and has one legal value; a path
    raises from the resolver, whoever hands it on."""
    from knn_tpu.parallel import ShardedKNN, make_mesh

    db, q = data
    path = str(tmp_path / "autotune.json")
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=5,
                      labels=np.arange(len(db)) % 3, num_classes=3)
    call = {
        "resolve_full": lambda: tuning.resolve_full(
            700, 16, 5, cache_path=path),
        "search_certified": lambda: prog.search_certified(
            q, selector="pallas", tune_cache=path),
        "predict_certified": lambda: prog.predict_certified(
            q, selector="pallas", tune_cache=path),
        "certified_plan": lambda: prog.certified_plan(
            len(q), tune_cache=path),
    }[through]
    with pytest.raises(ValueError, match="winner cache was removed"):
        call()
    assert not os.path.exists(path)


def test_default_knobs_are_the_kernel_shaping_arguments():
    """The knob list has ONE home: ``DEFAULT_KNOBS`` names exactly the
    kernel-shaping keyword arguments of ``search_certified`` and of
    ``_pallas_setup`` (what is left of their signatures once the
    arguments that shape the call, not the kernel, are taken out), so a
    knob added to one of the three and not the others fails here."""
    import inspect

    from knn_tpu.parallel import ShardedKNN

    def kwargs_of(fn, *not_knobs):
        return set(inspect.signature(fn).parameters) - {"self", *not_knobs}

    assert kwargs_of(
        ShardedKNN.search_certified, "queries", "margin", "selector",
        "batch_size", "return_distances", "recall_target", "tune_cache",
        "return_sqrt", "filter_tags", "filter_range",
        "_under") == set(tuning.DEFAULT_KNOBS)
    assert kwargs_of(
        ShardedKNN._pallas_setup, "margin", "include_distances", "terms",
        "batch_rows", "call_rows", "trace_id", "acct", "masked",
        "vote", "own_rows") == set(
            tuning.DEFAULT_KNOBS)
