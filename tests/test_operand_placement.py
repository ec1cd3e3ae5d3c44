"""The default precision's row operands as a resident placement
(``ShardedKNN._row_operands``): the kernel's bf16 halves of the padded
rows and the row norms are built once on the device, beside the rows,
and the certified program takes them as arguments.

- the packed output of the certified program with the resident operands
  against the in-program form's, bit for bit, on byte rows (``th``
  alone), float rows (``th`` and ``tl``), byte rows under a float batch,
  an inner-product placement of odd width, and ``range_search_certified``,
  each on one CPU device and on a (1, 4) mesh whose shards pad their
  own rows;
- built once: a second call launches nothing, another tile rebuilds and
  drops the old form;
- the traced resident program holds no operation over an array of the
  padded corpus's size outside the kernel;
- the rule that keeps them or does not (``analysis.hbm``), at every
  cell's bytes as the chip read them and end to end when told the
  device is full;
- what a call reports: ``stats``, the ``certified.call`` event, the
  counter, the ``placement.operands`` event.
"""

import functools
import math
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.analysis import hbm
from knn_tpu.obs import names as mn
from knn_tpu.ops import pallas_knn as pk
from knn_tpu.parallel import ShardedKNN, make_mesh

K = 10
TILE = 2 * pk.BIN_W
#: rows no multiple of shards x tile: 4 shards of 751 rows (four zero
#: rows appended by the placement) pad to 768 each, one shard to 3,072
ROWS = 3001
FULL = {"bytes_limit": 1}     # a device with no room for anything


@pytest.fixture(autouse=True)
def _fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


def byte_values(rng, shape):
    return rng.integers(0, 256, shape).astype(np.float32)


def corpus(kind: str, dim: int = 24):
    """(rows, batch, metric, terms the kernel should form)."""
    rng = np.random.default_rng([39, len(kind), dim])
    db, q = byte_values(rng, (ROWS, dim)), byte_values(rng, (21, dim))
    if kind == "byte":
        return db, q, "l2", "hh"
    if kind == "byte_rows_float_batch":
        return db, q + rng.random(q.shape, dtype=np.float32), "l2", "hh+lh"
    db = db / 256 + rng.random(db.shape, dtype=np.float32)
    q = q / 256 + rng.random(q.shape, dtype=np.float32)
    return db, q, ("dot" if kind == "dot" else "l2"), "hh+hl+lh"


def placed_pair(db, metric, shards):
    """Two placements of the same rows: one to keep the row operands
    (any CPU device has the room), one for the caller to tell its device
    is full."""
    res = ShardedKNN(db, mesh=make_mesh(1, shards), k=K, metric=metric)
    per = ShardedKNN(db, mesh=make_mesh(1, shards), k=K, metric=metric)
    return res, per


def packed(placed, q, terms, memory_stats=None):
    """The certified program's packed int32 output for ``q``, called as
    ``search_certified`` calls it; ``memory_stats`` is handed to the
    rule before the setup resolves the geometry."""
    if placed.metric == "dot":
        q = np.concatenate([q, np.zeros((q.shape[0], 1), np.float32)], 1)
    assert placed._kernel_terms(q, "bf16x3") == terms
    if memory_stats is not None:
        tile = pk.effective_tile(placed._shard_rows(), TILE, None, K + 30)
        assert placed._row_operands(
            tile, "hl" in terms, memory_stats=memory_stats) is None
    prog, _, _, _ = placed._pallas_setup(
        28, TILE, "bf16x3", terms=terms,
        include_distances=placed.metric != "dot", batch_rows=q.shape[0])
    qp, _ = placed._place_queries(q)
    return np.asarray(prog(qp, placed._tp,
                           *placed._pallas_operands("bf16x3")))


def _launches(program="operands"):
    series = obs.snapshot().get(mn.PROGRAM_LAUNCHES, {"series": []})["series"]
    return sum(s["value"] for s in series
               if s["labels"]["program"] == program)


def _decided():
    """Every ``placement.operands`` event: one a decision of the rule,
    kept or not."""
    return [e for e in obs.get_event_log().recent()
            if e.get("name") == "placement.operands"]


def _built():
    return [e for e in _decided() if e["kept"]]


def _inflight_builds():
    return [e for e in obs.get_event_log().recent()
            if e.get("span") == "certified.inflight.operands"]


# --- bit for bit ----------------------------------------------------------
@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("kind,dim", [
    ("byte", 24), ("float", 24), ("byte_rows_float_batch", 24),
    ("dot", 37), ("float", 200)])
def test_the_resident_operands_change_no_bit_of_the_packed_output(
        kind, dim, shards):
    db, q, metric, terms = corpus(kind, dim)
    res, per = placed_pair(db, metric, shards)
    got = packed(res, q, terms)
    want = packed(per, q, terms, memory_stats=FULL)
    assert (res._operands_source, per._operands_source) == (
        "resident", "per_call")
    assert got.dtype == np.int32 and np.array_equal(got, want)
    # each shard padded its own rows, with PAD_VAL rows up to the tile
    rows_p = -(-res._shard_rows() // TILE) * TILE
    parts = res._operands_cache["parts"]
    assert len(parts) == 2 + ("hl" in terms)
    width = -(-res._tp.shape[1] // pk.DIM_CHUNK) * pk.DIM_CHUNK
    assert [x.shape for x in parts] == (
        [(rows_p * shards, width)] * (len(parts) - 1) + [(rows_p * shards,)])
    assert all(x.dtype == jnp.bfloat16 for x in parts[:-1])
    norms = np.asarray(parts[-1]).reshape(shards, rows_p)
    assert (norms[:, res._shard_rows():] > 1e30).all()
    assert (norms[:, :res._shard_rows() - 4] < 1e30).all()


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("kind", ["byte", "float"])
def test_a_range_search_answers_the_same_from_either(kind, shards):
    db, q, metric, _ = corpus(kind)
    res, per = placed_pair(db, metric, shards)
    tile = pk.effective_tile(per._shard_rows(), pk.TILE_N, None, K + 30)
    assert per._row_operands(tile, kind == "float",
                             memory_stats=FULL) is None
    d2 = ((q[:, None, :].astype(np.float64) - db[None]) ** 2).sum(-1)
    radius = float(np.sort(d2, axis=1)[:, 2 * K].mean())  # some truncated
    got = res.range_search_certified(q, radius_sq=radius)
    want = per.range_search_certified(q, radius_sq=radius)
    assert got[3]["operands"] == "resident"
    assert want[3]["operands"] == "per_call"
    assert got[3]["range"]["truncated"] > 0
    for a, b in zip(got[:3], want[:3]):
        assert np.array_equal(a, b)
    assert got[3]["range"] == want[3]["range"]


@pytest.mark.parametrize("kind", ["byte", "float", "dot"])
def test_a_full_device_keeps_the_in_program_form_and_answers_the_same(kind):
    """The rule is handed the device's reading (no knob to patch): told
    the device is full it keeps nothing, the call says ``per_call``
    wherever it says ``resident`` otherwise, and answers the same."""
    db, q, metric, terms = corpus(kind, 37 if kind == "dot" else 24)
    res, per = placed_pair(db, metric, 1)
    tile = pk.effective_tile(per._shard_rows(), pk.TILE_N, None, K + 30)
    assert per._row_operands(tile, "hl" in terms,
                             memory_stats=FULL) is None
    out = {}
    for name, prog in (("resident", res), ("per_call", per)):
        obs.reset(enabled=True)
        obs.reset_event_log(None)
        d, i, stats = prog.search_certified(q, selector="pallas")
        out[name] = (d, i)
        (call,) = [e for e in obs.get_event_log().recent()
                   if e.get("span") == "certified.call"]
        by = {s["labels"]["source"]: s["value"] for s in
              obs.snapshot()[mn.KERNEL_OPERANDS]["series"]}
        assert (stats["operands"], stats["pallas_knobs"]["operands"],
                call["operands"], by) == (name, name, name, {name: 1})
        assert len(_built()) == (name == "resident")
    assert np.array_equal(out["resident"][1], out["per_call"][1])
    assert np.array_equal(out["resident"][0], out["per_call"][0])


# --- built once -----------------------------------------------------------
@pytest.mark.parametrize("shards", [1, 4])
def test_a_second_call_builds_nothing(shards):
    db, q, metric, _ = corpus("byte")
    prog = ShardedKNN(db, mesh=make_mesh(1, shards), k=K)
    prog.search_certified(q, selector="pallas", tile_n=TILE)
    (event,) = _built()
    rows_p = -(-prog._shard_rows() // TILE) * TILE * shards
    assert (event["rows"], event["tile"], event["parts"], event["bytes"]
            ) == (rows_p, TILE, "th",
                  shards * hbm.row_operand_bytes(rows_p // shards, 128,
                                                 False))
    assert event["seconds"] >= 0 and _launches() == 1
    assert [e["launches"] for e in _inflight_builds()] == [1]
    parts = prog._operands_cache["parts"]
    for _ in range(2):
        prog.search_certified(q[:7], selector="pallas", tile_n=TILE)
    assert len(_built()) == 1 and _launches() == 1
    assert prog._operands_cache["parts"] is parts
    assert _launches("certified") == 3
    # the account names the build in the call that made it, and only there
    assert len(_inflight_builds()) == 1


def test_another_tile_rebuilds_and_drops_the_old_form():
    db, q, metric, _ = corpus("float")
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=K)
    _, want, _ = prog.search_certified(q, selector="pallas", tile_n=TILE)
    old = [weakref.ref(x) for x in prog._operands_cache["parts"]]
    assert prog._operands_cache["key"] == (TILE, True)
    _, got, _ = prog.search_certified(q, selector="pallas",
                                      tile_n=2 * TILE)
    assert prog._operands_cache["key"] == (2 * TILE, True)
    assert [e["tile"] for e in _built()] == [TILE, 2 * TILE]
    assert _launches() == 2 and np.array_equal(got, want)
    assert all(ref() is None for ref in old)   # one form at a time
    # a float batch on byte rows wants no low half: another form again
    byte_db, byte_q, _, _ = corpus("byte")
    prog = ShardedKNN(byte_db, mesh=make_mesh(1, 1), k=K)
    prog.search_certified(byte_q, selector="pallas", tile_n=TILE)
    first = prog._operands_cache["parts"]
    prog.search_certified(byte_q + np.float32(0.25), selector="pallas",
                          tile_n=TILE)
    assert prog._operands_cache["parts"] is first  # hh+lh streams th alone


def test_the_other_precisions_keep_their_in_program_prep():
    db, q, metric, _ = corpus("float")
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=K)
    for precision in ("highest", "bf16x3f"):
        _, _, stats = prog.search_certified(
            q, selector="pallas", tile_n=TILE, precision=precision)
        assert stats["operands"] == "per_call"
    assert prog._operands_cache is None and _decided() == []


# --- the traced program ---------------------------------------------------
def operations_outside_kernels(jaxpr):
    """Every equation under a jaxpr that does something itself (holds no
    jaxpr of its own: not a ``jit`` or ``shard_map`` that hands its
    arguments down) and is not a ``pallas_call`` or inside one: the walk
    of ``tests/test_kernel_terms.py``'s ``kernel_calls``, turned inside
    out."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        inner = list(jax.core.jaxprs_in_params(eqn.params))
        if not inner:
            yield eqn
        for sub in inner:
            yield from operations_outside_kernels(sub)


def corpus_sized(traced, shape):
    """(primitive, output dtype) of every operation outside the kernels
    that reads or makes an array of ``shape``."""
    return [(e.primitive.name, e.outvars[0].aval.dtype)
            for e in operations_outside_kernels(traced.jaxpr)
            if any(getattr(v.aval, "shape", None) == shape
                   for v in (*e.invars, *e.outvars))]


@pytest.mark.parametrize("kind", ["byte", "float"])
def test_the_resident_program_forms_nothing_of_the_corpus_size(kind):
    db, q, metric, terms = corpus(kind)
    res, per = placed_pair(db, metric, 1)
    shape = (-(-ROWS // TILE) * TILE, pk.DIM_CHUNK)
    seen = {}
    for name, placed, stats in (("resident", res, None),
                                ("per_call", per, FULL)):
        if stats is not None:
            placed._row_operands(TILE, "hl" in terms, memory_stats=stats)
        prog, _, _, _ = placed._pallas_setup(28, TILE, "bf16x3",
                                             terms=terms)
        qp, _ = placed._place_queries(q)
        traced = jax.make_jaxpr(prog)(
            qp, placed._tp, *placed._pallas_operands("bf16x3"))
        seen[name] = corpus_sized(traced, shape)
    # the parent's text: a padded f32 copy, its cast (and on float rows
    # the low half's), the squares and their reduction
    prims = [p for p, _ in seen["per_call"]]
    assert "pad" in prims and "reduce_sum" in prims
    assert ("convert_element_type", jnp.bfloat16) in seen["per_call"]
    # resident: nothing outside the kernel touches an array that size
    assert seen["resident"] == []


def test_prepared_operands_of_another_geometry_are_refused():
    db, q, _, _ = corpus("float")
    run = dict(block_q=8, survivors=2, interpret=True)
    rows = pk.row_operands(jnp.asarray(db), tile_n=TILE, with_lo=True)
    with pytest.raises(ValueError, match="not row_operands of"):
        pk._bin_candidates(jnp.asarray(q), jnp.asarray(db), tile_n=640,
                           precision="bf16x3", db_prepared=rows, **run)
    with pytest.raises(ValueError, match="not row_operands of"):
        pk._bin_candidates(jnp.asarray(q), jnp.asarray(db), tile_n=TILE,
                           precision="bf16x3", terms="hh+lh",
                           db_prepared=rows, **run)
    with pytest.raises(ValueError, match="streams no such operands"):
        pk._bin_candidates(jnp.asarray(q), jnp.asarray(db), tile_n=TILE,
                           precision="highest", db_prepared=rows, **run)
    want = pk._bin_candidates(jnp.asarray(q), jnp.asarray(db), tile_n=TILE,
                              precision="bf16x3", **run)
    got = pk._bin_candidates(jnp.asarray(q), jnp.asarray(db), tile_n=TILE,
                             precision="bf16x3", db_prepared=rows, **run)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# --- the rule -------------------------------------------------------------
V5E = 16909336064     # bytes_limit of one v5e chip, as the chip reads it
#: bytes_in_use at ``_row_operands``' decision in the two cells PR 49
#: flipped (my chip run, PR 49, call 1, ``memory_stats()`` read in
#: ``_row_operands``: the lane-tiled rows ONCE; at gist1m the compact
#: 960-column source is gone by then, ``peak_bytes_in_use`` 7,936,549,376
#: is all that is left of it, and ``bytes_reserved`` reads the
#: ``lane_tile`` program's 536,903,680; at imagenet-knn768 the labels
#: are the 5 MB over the rows and nothing is reserved yet)
GIST1M_IN_USE = 4_096_303_616
IMAGENET_IN_USE = 3_940_903_424


@pytest.mark.parametrize("cell,rows,width,with_lo,in_use,keeps", [
    # ``width`` is the placed rows' columns (whole lane tiles wherever
    # ShardedKNN laid them out), ``in_use`` the chip's bytes_in_use with
    # the rows placed and no program of the call run yet (PERF.md
    # section 4 "Memory"; gist1m's and imagenet-knn768's are what the
    # chip read AT the decision, PR 49)
    ("bigann5m", 5_000_000, 128, False, 2_560_027_136, True),
    ("ssnpp2m5", 2_500_000, 256, False, 2_560_027_136, True),
    # 201 given columns placed in 256 since PR 44
    ("text2image2m5", 2_500_000, 256, True, 2_560_027_136, True),
    # 960 given columns placed in 1,024: 4.10 + 4.17 + 1.25 x 4.10 =
    # 13.4 of 14.80 GB (at 2.7: 19.3, and per_call until PR 49)
    ("gist1m", 1_000_000, 1024, True, GIST1M_IN_USE, True),
    # R9, 10M x 128 on one chip: 5.12 + 2.60 + 1.25 x 5.12 = 14.1 GB
    ("bigann10m", 10_000_000, 128, False, 5_120_027_136, True),
    # 500K x 1,536 unit rows with both halves (PR 43): kept at 2.7
    # already with 0.31 GB to spare
    ("openai500k", 500_000, 1536, True, 3_072_027_136, True),
    # 1,281,167 x 768 unit rows (PR 48): 3.94 + 3.98 + 1.25 x 3.94 =
    # 12.8 GB (at 2.7: 18.5, and per_call until PR 49)
    ("imagenet-knn768", 1_281_167, 768, True, IMAGENET_IN_USE, True),
    # 5M x 96 unit rows placed in 128 (PR 51), both halves: 2.56 + 2.59
    # + 1.25 x 2.56 = 8.3 GB
    ("deep5m-knng", 5_000_000, 128, True, 2_560_027_136, True),
    # a PRE-PLACED 1M x 960 array is used as handed in: its programs
    # still copy and pad all of it (2.7), 3.84 + 4.17 + 10.37 = 18.4 GB
    ("gist1m-preplaced-960", 1_000_000, 960, True, 3_840_027_136, False),
    # the same rows with the 960-column source still alive beside the
    # lane-tiled ones: no factor admits 7.94 + 4.17 + 5.12 = 17.2 GB,
    # so the decision must see the rows once
    ("gist1m-source-alive", 1_000_000, 1024, True, 7_936_027_136, False),
])
def test_the_rule_at_every_cells_bytes(cell, rows, width, with_lo, in_use,
                                       keeps):
    rows_p = -(-rows // pk.TILE_N) * pk.TILE_N
    form = hbm.row_operand_bytes(
        rows_p, -(-width // pk.DIM_CHUNK) * pk.DIM_CHUNK, with_lo)
    stats = {"bytes_limit": V5E, "bytes_in_use": in_use, "bytes_reserved": 0}
    room = hbm.resident_operands_room(form, rows * width * 4, stats,
                                      width=width)
    assert room["kept"] is keeps
    assert hbm.resident_operands_fit(form, rows * width * 4, stats,
                                     width=width) is keeps
    assert (room["held"], room["form_bytes"], room["limit"]) == (
        in_use, form, int(hbm.RESIDENT_FILL * V5E))
    assert room["temporaries"] == int(
        (1.25 if width % 128 == 0 else 2.7) * rows * width * 4)


@pytest.mark.parametrize("width,factor", [
    (128, hbm.LANE_TILED_TEMP_FACTOR), (1024, hbm.LANE_TILED_TEMP_FACTOR),
    (960, hbm.ROWS_PROGRAM_TEMP_FACTOR), (201, hbm.ROWS_PROGRAM_TEMP_FACTOR)])
def test_the_rule_reads_what_the_device_says(width, factor):
    assert hbm.program_temp_factor(width) == factor
    assert (hbm.LANE_TILED_TEMP_FACTOR, hbm.ROWS_PROGRAM_TEMP_FACTOR) == (
        1.25, 2.7)
    fit = functools.partial(hbm.resident_operands_fit, width=width)
    form, placed = 1_000, 4_000
    room = {"bytes_limit": 100_000, "bytes_in_use": placed}
    assert fit(form, placed, room)
    # no accounting (the CPU): nothing to run out of
    assert fit(form, placed, {})
    # placed + form + the width's factor x placed against 7/8 of the limit
    edge = placed + form + int(factor * placed)
    assert fit(form, placed,
               {"bytes_limit": math.ceil(edge / hbm.RESIDENT_FILL)})
    assert not fit(form, placed,
                   {"bytes_limit": math.floor((edge - 8) / hbm.RESIDENT_FILL)})
    # what else the process holds there counts, and so does a loaded
    # program that sets aside more than the model says
    assert not fit(form, placed, {**room, "bytes_in_use": 90_000})
    assert not fit(form, placed, {**room, "bytes_reserved": 85_000})
    assert hbm.row_operand_bytes(16, 128, False) == 16 * (256 + 4)
    assert hbm.row_operand_bytes(16, 128, True) == 16 * (512 + 4)


@pytest.mark.parametrize("kind", ["byte", "float"])
def test_a_refusal_says_what_the_rule_compared(kind):
    """The ``placement.operands`` event is the decision's, either way:
    on a refusal it carries the terms the rule compared, as it does
    where the operands are built."""
    db, q, metric, terms = corpus(kind)
    res, per = placed_pair(db, metric, 1)
    placed = per._tp.nbytes
    form = hbm.row_operand_bytes(-(-ROWS // TILE) * TILE, 128, "hl" in terms)
    # a device with room for the rows and the form but not for the
    # programs' temporaries beside them
    tight = {"bytes_limit": int((placed + form + placed) / hbm.RESIDENT_FILL),
             "bytes_in_use": placed + 64, "bytes_reserved": 0}
    assert per._row_operands(TILE, "hl" in terms, memory_stats=tight) is None
    (no,) = _decided()
    assert {k: no[k] for k in ("held", "form_bytes", "temporaries", "limit",
                               "kept")} == {
        "held": placed + 64, "form_bytes": form,
        "temporaries": int(hbm.LANE_TILED_TEMP_FACTOR * placed),
        "limit": int(hbm.RESIDENT_FILL * tight["bytes_limit"]),
        "kept": False}
    assert (no["tile"], no["parts"], no["bytes"], no["seconds"]) == (
        TILE, "th+tl" if "hl" in terms else "th", 0, 0.0)
    assert _built() == [] and _launches() == 0
    # decided once a geometry: the call that follows asks again, is
    # answered from the cache and says nothing more
    per.search_certified(q, selector="pallas", tile_n=TILE)
    assert len(_decided()) == 1 and per._operands_source == "per_call"
    # the same reading with the temporaries' room: kept, the same terms
    roomy = {**tight, "bytes_limit": 2 * tight["bytes_limit"]}
    assert res._row_operands(TILE, "hl" in terms,
                             memory_stats=roomy) is not None
    yes = _decided()[-1]
    assert (yes["kept"], yes["held"], yes["form_bytes"], yes["temporaries"],
            yes["bytes"]) == (True, placed + 64, form, no["temporaries"], form)
    assert yes["held"] + yes["form_bytes"] + yes["temporaries"] <= yes["limit"]
