#!/usr/bin/env python
"""Ask Mosaic/XLA about a kernel geometry WITHOUT a chip: deviceless AOT
compile against the installed libtpu's TPU v5e topology
(``jax.experimental.topologies.get_topology_desc``), so a default
promotion or a new geometry meets the compiler here before it spends
chip time.  A compile is not a run — the chip has the last word
(``chip_smoke.py``).

With no arguments it compiles the certified coarse pass (compiled,
4,096 queries) at the three benchmark shapes with the knobs the library
resolves when nobody picks any (``tuning.resolve_full``: the library
defaults), for each of the three kernels, and again at 512 columns, the
widest rows whose tile the tiled kernel runs as ONE grid step
(``analysis.vmem.row_blocking``; GloVe's 384 and ``text2image2m5``'s
256 padded columns are the other two such widths in the list), and at
640, the narrowest whose tile it cuts by rows, as it does ``gist``'s
1,024 (the streaming and fused kernels keep 128-column chunks at every
width: both rows are there so that a rule which collapsed them where
they have no room fails here); asks for
fused at block_q=256 at SIFT, which the library's own VMEM model must refuse
before Mosaic is asked; compiles the whole certified program with the
kernel's one-product form (``--terms hh``: what a byte corpus and a
byte batch run, ``ops.pallas_knn.BF16X3_TERMS``) at 5M x 128 on one
chip and at 20M x 128 on the 1x4 mesh, printing what each keeps on a
chip; compiles the inner-product cell's program (2.5M x 201 columns
placed in 256, as ``ShardedKNN`` lays them out since PR 44; k=10: one
more operand, no distance block) and the range
cell's first pass (2.5M x 256, one product, ``ssnpp2m5``); compiles the
final select's bin-merge kernel at a 5M-row chip's candidate width
(``bigann20m``), at ``text2image2m5``'s (39,168 columns at m+2 = 40:
62 lane-rows a merge bin) and at ``ssnpp2m5``'s (39,168 at m+2 = 130);
compiles the range completion's program at ``ssnpp2m5`` (the pass
over the rows and the compaction at ``ops.radius.range_width``); and
compiles the final select's Pallas stage (``select_final``, PR 35)
alone at every cell's (width, m) — 8,704 x 130, 15,872 x 130, 2,560 x
40 — reading the least scoped-VMEM limit Mosaic takes it at against
``analysis.vmem.final_select_bytes``; and reads the same for the
kernel at the two cells whose row tile is cut by rows (``gist`` and
``openai500k``: steps of 4,096 rows at 1,024 and 1,536 columns)
against ``analysis.vmem.launch_estimate``, at the default query block
and at 128 (a short sub-batch's).  The whole programs above carry
that stage and the bin-merge COMPILED (``interpret=False`` reaches
both), ``gist`` among them, and the range cell's first pass is checked
to hold still exactly one line with a ``uint32`` array of two
dimensions or more (the range completion's trace pattern).
Flags pick one geometry instead:

    python scripts/aot_compile_check.py --shape gist --block-q 128
    python scripts/aot_compile_check.py --shape sift --kernel streaming \\
        --block-q 256 --probe          # the compiler's scoped-VMEM need
    python scripts/aot_compile_check.py --shape bigann20m --mesh 1x4 \\
        --merge ring                   # the full SPMD certified program
    python scripts/aot_compile_check.py --shape bigann5m --mesh 1x1 \\
        --terms hh                     # ... of a byte corpus and batch
    python scripts/aot_compile_check.py --temporaries [--shape gist]
        # what every program a cell loads sets aside, over the placed
        # rows: the reading behind analysis.hbm.LANE_TILED_TEMP_FACTOR

Prints one line per case; exits non-zero if any case did not do what
was expected of it.
"""

from __future__ import annotations

import argparse
import functools
import math
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
# libtpu prints a start-up error per process when these are unset on a
# machine with no TPU metadata server; they do not affect the compile
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")

TOPOLOGY = "v5e:2x2"
NQ = 4096


def margin_of(k: int) -> int:
    """A default call's margin (ops.pallas_knn.default_margin: 28 at
    every k = 100 shape, an eighth of k from k = 232)."""
    from knn_tpu.ops.pallas_knn import default_margin

    return default_margin(k)

#: the shapes of chip_smoke.py and of the benchmark's cells: rows, dim,
#: k.  GloVe's cosine runs as l2 on unit vectors, so the kernel sees the same call.
SHAPES = {
    "sift": (1_000_000, 128, 100),
    "gist": (1_000_000, 960, 100),
    "glove": (1_183_514, 300, 50),
    # one chip of the corpus below (benchmark/configs/bigann5m.json)
    "bigann5m": (5_000_000, 128, 100),
    # the four-chip cell's corpus (benchmark/configs/bigann20m-x4.json);
    # asked for by name with --mesh 1x4, not part of a bare run
    "bigann20m": (20_000_000, 128, 100),
    # one chip of text2image-10M as PLACED: inner product, 200 columns
    # and the appended norm column (benchmark/configs/text2image2m5.json)
    "text2image2m5": (2_500_000, 201, 10),
    # no data set (at the end of this table): the widest rows whose
    # tile is still ONE grid step at the default tile and query block
    # (analysis.vmem.row_blocking), and the narrowest whose tile is cut
    # one chip of ssnpp-10M: 256 byte-valued columns, range search over
    # a top-100 first pass (benchmark/configs/ssnpp2m5.json)
    "ssnpp2m5": (2_500_000, 256, 100),
    # VectorDBBench's 500K x 1,536 cosine case whole on one chip: unit
    # rows, a row tile in four steps (benchmark/configs/openai500k.json)
    "openai500k": (500_000, 1536, 100),
    # the ImageNet-1k k-NN evaluation whole on one chip: cosine, k = 20,
    # answered by the VOTE program (benchmark/configs/imagenet-knn768.json)
    "imagenet768": (1_281_167, 768, 20),
    "wide512": (1_000_000, 512, 100),
    "wide640": (1_000_000, 640, 100),
    # one chip's block of a DEEP prefix, answered by the SELF program:
    # every row a query, its own row out (benchmark/configs/
    # deep5m-knng.json); 96 columns placed in 128
    "deep5m": (5_000_000, 96, 10),
    # one chip's slice of the kNN-LM WikiText-103 datastore at its own
    # k (benchmark/configs/knnlm1m.json): survivor depth 4, XLA's final
    # select, launches of 512 queries (``--shape knnlm1m --mesh 1x1``;
    # ``--temporaries --shape knnlm1m`` reads what a launch sets aside)
    "knnlm1m": (1_000_000, 1024, 1024),
}
#: bytes_limit of one v5e chip, as the chip reads it
V5E_BYTES_LIMIT = 16909336064


def _depth_and_tile(shape: str, db_shards: int, knobs: dict):
    """``(survivor depth, row tile)`` of one chip's shard of ``shape``,
    as ``ShardedKNN._pallas_setup`` resolves them."""
    from knn_tpu.ops.pallas_knn import TILE_N, survivor_depth

    n, _, k = SHAPES[shape]
    rows = -(-n // db_shards)
    depth, tile, _ = survivor_depth(
        rows, knobs.get("tile_n") or TILE_N, knobs.get("survivors"),
        min(k + margin_of(k), rows) + 2)
    return depth, tile


def launch_queries(shape: str, knobs: dict) -> int:
    """The queries of one launch of a 4,096-query call on one v5e chip
    at ``shape``: ``analysis.subbatch.certified_sub_batch`` over
    ``analysis.hbm``'s arithmetic with both row halves resident, no
    device asked (NQ // 4 wherever a launch of 1,024 fits)."""
    from knn_tpu.analysis import hbm, subbatch
    from knn_tpu.analysis.widths import lane_tiled
    from knn_tpu.ops.pallas_knn import BLOCK_Q
    from knn_tpu.parallel.sharded import _analysis_window

    n, d, k = SHAPES[shape]
    d, m = lane_tiled(d), k + margin_of(k)
    depth, tile = _depth_and_tile(shape, 1, knobs)
    w = _analysis_window(k, m)
    room = hbm.resident_operands_room(
        hbm.row_operand_bytes(-(-n // tile) * tile, d, True), n * d * 4,
        {"bytes_limit": V5E_BYTES_LIMIT}, width=d)
    return subbatch.certified_sub_batch(
        NQ, batch_size=None, operands="resident", width=d,
        block_q=knobs.get("block_q") or BLOCK_Q, query_shards=1,
        query_bytes=hbm.certified_query_bytes(
            m, d, -(-n // tile) * depth * 128, w + -(-(w - 1) // 32) + 1 + k),
        room_bytes=hbm.certified_launch_room(room))[0]
#: shapes whose rows are norm-augmented at placement (metric "dot"): the
#: certified program takes the augmentation's slack as one more scalar
#: and sends no distance block back
AUGMENTED = ("text2image2m5",)
#: shapes whose rows are unit rows (metric "cosine"): the program takes
#: the normalisation's slack as one more scalar too, keeps its distance
#: block and packs one more bit (``slack_outcome``)
COSINE = ("openai500k", "imagenet768")
#: shapes answered by ``predict_certified(vote="softmax")``: the vote
#: program (the certified program's tail ended in the weighted vote over
#: ``VOTE_CLASSES`` labels at ``VOTE_TEMPERATURE``, five classes out)
VOTED = ("imagenet768",)
VOTE_CLASSES, VOTE_TEMPERATURE, VOTE_CLASSES_OUT = 1000, 0.07, 5
#: shapes answered by ``knn_tpu.join.knn_self_join``: the self program
#: (no query operand: a launch's first row id stands where the queries
#: stood, ``parallel.sharded._pallas_self_program``), at a block's
#: launch of NQ // 4 rows, its row operands resident; after it
#: ``--shape deep5m`` prints what one chip holds beside the placed rows
SELF = ("deep5m",)
#: shapes answered by ``range_search_certified``: its completion's
#: program is compiled too
RANGE = ("ssnpp2m5",)
#: the exact path's row tile the benchmark's configurations place with
TRAIN_TILE = 131072


def _topology_devices():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        topology_name=TOPOLOGY, platform="tpu").devices


def _kernel_case(shape: str, knobs: dict, devices, terms=None,
                 row_block=None):
    """(fn, avals) of the compiled certified coarse pass on ONE chip
    (``row_block``: the kernel's static of that name, for a probe of a
    cut the rule does not make; None = the rule's)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from knn_tpu.ops.pallas_knn import local_certified_candidates

    n, d, k = SHAPES[shape]
    sh = SingleDeviceSharding(devices[0])
    q = jax.ShapeDtypeStruct((NQ, d), jnp.float32, sharding=sh)
    db = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=sh)
    kw = {kk: v for kk, v in knobs.items() if v is not None}
    kw["survivors"], kw["tile_n"] = _depth_and_tile(shape, 1, knobs)
    if terms:
        kw["terms"] = terms
    if row_block:
        kw["row_block"] = row_block
    fn = jax.jit(functools.partial(
        local_certified_candidates, m=k + margin_of(k), interpret=False, **kw))
    return fn, (q, db)


def _spmd_case(shape: str, knobs: dict, devices, mesh_shape, merge: str,
               terms=None, *, queries: int = NQ, resident: bool = False):
    """(fn, avals) of the full sharded certified program
    (parallel.sharded._pallas_certified_program) on a topology mesh, at
    ``queries`` queries; ``resident``: handed the placement's row
    operands (both bf16 halves, or the high one alone under ``terms``
    without ``hl``, and the norms) as ``ShardedKNN._row_operands`` keeps
    them, instead of forming them in the call."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from knn_tpu.analysis.widths import lane_tiled
    from knn_tpu.parallel.mesh import DB_AXIS, QUERY_AXIS
    from knn_tpu.parallel.sharded import (
        _pallas_certified_program,
        _pallas_self_program,
        _pallas_vote_program,
        vote_delta,
    )

    n, d, k = SHAPES[shape]
    d = lane_tiled(d)  # as ShardedKNN places the rows and every batch
    qs, ds = mesh_shape
    mesh = Mesh(np.asarray(devices[:qs * ds]).reshape(qs, ds),
                (QUERY_AXIS, DB_AXIS))
    rows = -(-n // ds) * ds
    kw = {kk: v for kk, v in knobs.items()
          if kk not in ("tile_n", "precision")}
    if terms:
        kw["terms"] = terms
    # the depth and the tile as _pallas_setup resolves them: 2 and the
    # default at every k = 100 shape
    kw["survivors"], tile = _depth_and_tile(shape, ds, knobs)
    parts = (1 + ("hl" in (terms or "hh+hl+lh"))) if resident else 0
    kw["resident_parts"] = parts
    if shape in AUGMENTED:
        kw.update(augmented=True, include_distances=False)
    if shape in COSINE:
        kw.update(augmented=True, slack_outcome=True)
    if shape in SELF:
        for key in ("kernel", "include_distances"):
            kw.pop(key, None)
        prog = _pallas_self_program(
            mesh, k + margin_of(k), k, merge, tile, n, queries, interpret=False,
            **kw)
    elif shape in VOTED:
        del kw["augmented"], kw["slack_outcome"]
        prog = _pallas_vote_program(
            mesh, k + margin_of(k), k, merge, tile,
            knobs["precision"], n,
            (1.0 / VOTE_TEMPERATURE, VOTE_CLASSES_OUT,
             vote_delta(VOTE_TEMPERATURE, k)), interpret=False, **kw)
    else:
        prog = _pallas_certified_program(
            mesh, k + margin_of(k), k, merge, tile,
            knobs["precision"], n_train=n, interpret=False, **kw)
    q = jax.ShapeDtypeStruct(
        (queries, d), jnp.float32,
        sharding=NamedSharding(mesh, P(QUERY_AXIS)))
    db = jax.ShapeDtypeStruct(
        (rows, d), jnp.float32, sharding=NamedSharding(mesh, P(DB_AXIS)))
    norm = jax.ShapeDtypeStruct(
        (), jnp.float32, sharding=NamedSharding(mesh, P()))
    labels = jax.ShapeDtypeStruct(
        (n,), jnp.int32, sharding=NamedSharding(mesh, P()))
    rows_p = -(-(rows // ds) // tile) * tile * ds  # each shard pads its own
    halves = jax.ShapeDtypeStruct(
        (rows_p, d), jnp.bfloat16, sharding=NamedSharding(mesh, P(DB_AXIS)))
    norms = jax.ShapeDtypeStruct(
        (rows_p,), jnp.float32, sharding=NamedSharding(mesh, P(DB_AXIS)))
    if shape in SELF:
        q = jax.ShapeDtypeStruct(
            (1,), jnp.int32, sharding=NamedSharding(mesh, P()))
    return prog, (q, db, norm) + (
        (halves,) * parts + (norms,) if parts else ()) + (
        (norm,) if shape in AUGMENTED + COSINE else ()) + (
        (labels,) if shape in VOTED else ())


#: the shapes ``--temporaries`` reads where none is named: the two
#: widest lane-tiled placements of the benchmark that keep both halves
TEMPORARIES_SHAPES = ("gist", "imagenet768")


def temporaries_table(shape: str, devices, terms=None, *,
                      self_rows: bool = False) -> tuple:
    """``(placed rows' bytes, [(program, temporaries' bytes)])`` on one
    chip at ``shape``, by XLA's ``memory_analysis()``: what
    ``analysis.hbm.LANE_TILED_TEMP_FACTOR`` is read from.  The programs
    a cell loads beside its rows: the certified program (the vote
    program where the shape is answered by a vote) with its row operands
    formed in the call and resident, at a call's 4,096 queries and at a
    sub-batch's 1,024; the repair's exact re-select at its widened k
    over 16 flagged queries; and the two the placement runs once,
    ``lane_tile`` (only where the given width is no whole tile) and
    ``operands``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from knn_tpu import tuning
    from knn_tpu.analysis.widths import lane_tiled
    from knn_tpu.ops.certified import repair_widen
    from knn_tpu.ops.pallas_knn import TILE_N
    from knn_tpu.parallel import sharded
    from knn_tpu.parallel.mesh import DB_AXIS, QUERY_AXIS

    n, given, k = SHAPES[shape]
    d = lane_tiled(given)
    knobs, _ = tuning.resolve_full(n, given, k)
    name = "vote" if shape in VOTED else "certified"
    cases = [(f"{name}, operands {'resident' if resident else 'in the call'}"
              f", {queries} queries",
              _spmd_case(shape, knobs, devices, (1, 1), "ring", terms,
                         queries=queries, resident=resident))
             for resident in (False, True)
             for queries in (NQ, launch_queries(shape, knobs))
             # a self-join's launch: a sub-batch's rows, operands resident
             if not self_rows or (resident and queries != NQ)]
    mesh = Mesh(np.asarray(devices[:1]).reshape(1, 1), (QUERY_AXIS, DB_AXIS))

    def aval(shp, dtype, spec):
        return jax.ShapeDtypeStruct(
            shp, dtype, sharding=NamedSharding(mesh, spec))

    m = k + margin_of(k)
    widen = repair_widen(m, n)
    flagged = sharded._SELF_RESELECT_ROWS if self_rows else 16
    cases.append((
        f"re-select, k={widen}, {flagged} queries",
        (sharded._knn_program(mesh, widen, "l2", "ring", n, TRAIN_TILE, None,
                              "exact", dcn_merge=None),
         (aval((flagged, d), jnp.float32, P(QUERY_AXIS)),
          aval((n, d), jnp.float32, P(DB_AXIS))))))
    if d != given:
        cases.append((
            f"lane_tile {given} -> {d} (once a placement)",
            (sharded._lane_tile_program(mesh, d),
             (aval((n, given), jnp.float32, P(DB_AXIS)),))))
    cases.append((
        "operands (once a placement)",
        (sharded._row_operands_program(
            mesh, knobs["tile_n"] or TILE_N, "hl" in (terms or "hh+hl+lh")),
         (aval((n, d), jnp.float32, P(DB_AXIS)),))))
    def temporaries(fn, avals):
        """What the compiled program sets aside, or None where the
        compiler finds no room for it on the chip at all (a launch of
        4,096 queries at k = 1,024 asks 17.7 GB for one array)."""
        try:
            return (fn.lower(*avals).compile().memory_analysis()
                    .temp_size_in_bytes)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return None

    return n * d * 4, [(label, temporaries(*case)) for label, case in cases]


def _shard_width(shape: str, db_shards: int) -> int:
    """The candidate columns a query the kernel hands the final select
    on one of ``db_shards`` chips, at the default tile."""
    from knn_tpu.ops import pallas_knn as pk

    rows = -(-SHAPES[shape][0] // db_shards)
    return -(-rows // pk.TILE_N) * 2 * pk.BIN_W


def _merge_case(shape: str, db_shards: int, devices):
    """(fn, avals) of the final select's bin-merge kernel alone, compiled,
    at the candidate width of one of ``db_shards`` chips, or None where
    that width does not engage it.  The certified program holds it too,
    but there it asks the backend and not its caller whether to compile,
    and the backend here is the CPU: the other cases carry it
    interpreted."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from knn_tpu.ops import pallas_knn as pk

    k = SHAPES[shape][2]
    width = _shard_width(shape, db_shards)
    geo = pk.select_merge_geometry(width, k + margin_of(k))
    if geo is None:
        return None
    sh = SingleDeviceSharding(devices[0])
    fn = jax.jit(lambda cd, ci: pk._select_merge(
        cd, ci, *geo[:2], interpret=False))
    return fn, (jax.ShapeDtypeStruct((NQ, width), jnp.float32, sharding=sh),
                jax.ShapeDtypeStruct((NQ, width), jnp.int32, sharding=sh))


def _final_case(shape: str, db_shards: int, devices):
    """(fn, avals, (block_q, width, keep)) of the final select's Pallas
    stage alone at the width one of ``db_shards`` chips hands it (the
    bin-merge's where that engages), or None where the shape rule keeps
    XLA's top_k."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from knn_tpu.ops import pallas_knn as pk

    m = SHAPES[shape][2] + margin_of(SHAPES[shape][2])
    width = _shard_width(shape, db_shards)
    merge = pk.select_merge_geometry(width, m)
    if merge is not None:
        width = merge[2]
    block_q = pk.final_select_geometry(width, m)
    if block_q is None:
        return None
    sh = SingleDeviceSharding(devices[0])
    fn = jax.jit(lambda cd, ci: pk._select_final(
        cd, ci, m, block_q, interpret=False))
    return (fn, (jax.ShapeDtypeStruct((NQ, width), jnp.float32, sharding=sh),
                 jax.ShapeDtypeStruct((NQ, width), jnp.int32, sharding=sh)),
            (block_q, width, m + 2))


#: an HLO line that holds a uint32 array of two or more dimensions: what
#: the benchmark's range-completion metrics read a device op by
#: (benchmark/layers/range_complete_device_ms.json)
U32_LINE = re.compile(r"\bu32\[[0-9]+,")


def _range_case(shape: str, devices, mesh_shape):
    """(name, fn, avals) of the range completion's program
    (parallel.sharded._range_program) over the shape's rows on a
    topology mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from knn_tpu.ops.radius import RANGE_SUB_BATCH, range_width
    from knn_tpu.parallel.mesh import DB_AXIS, QUERY_AXIS
    from knn_tpu.parallel.sharded import _range_program

    n, d, k = SHAPES[shape]
    qs, ds = mesh_shape
    mesh = Mesh(np.asarray(devices[:qs * ds]).reshape(qs, ds),
                (QUERY_AXIS, DB_AXIS))
    rows = -(-n // ds) * ds

    def aval(shp, dtype, spec):
        return jax.ShapeDtypeStruct(
            shp, dtype, sharding=NamedSharding(mesh, spec))

    return (f"{shape} range completion {RANGE_SUB_BATCH} x {rows} at width "
            f"{range_width(k)}",
            _range_program(mesh, n, TRAIN_TILE, range_width(k)),
            (aval((RANGE_SUB_BATCH, d), jnp.float32, P(QUERY_AXIS)),
             aval((rows, d), jnp.float32, P(DB_AXIS)),
             aval((RANGE_SUB_BATCH,), jnp.float32, P(QUERY_AXIS))))


def _executed_lines(hlo_text: str):
    """The instruction lines of a compiled module that run as device ops
    of their own, which is what a TPU trace names an op by: those of the
    entry computation and of loop and branch bodies, not the insides of
    a fusion or of a reducer (computations some ``calls=`` or
    ``to_apply=`` names)."""
    inner = set(re.findall(r"(?:calls|to_apply)=(%[\w.-]+)", hlo_text))
    lines, keep = [], False
    for ln in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.-]+) \(.*\{$", ln)
        if head:
            keep = head.group(1) not in inner
        elif ln.startswith("}"):
            keep = False
        elif keep and " = " in ln:
            lines.append(ln.strip())
    return lines


def _compile(fn, avals, u32_lines=None) -> str:
    """Compile, and say what the program keeps on a chip.  With
    ``u32_lines`` the compiled text must hold that many lines matching
    ``U32_LINE`` (ValueError otherwise)."""
    compiled = fn.lower(*avals).compile()
    mem = compiled.memory_analysis()
    detail = (f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB + "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB a chip")
    text = compiled.as_text()
    # the tail's Pallas calls, as the trace names them (compiled: an
    # interpreted one leaves no such op)
    stages = [name for name in ("select_merge", "select_final")
              if re.search(rf"%{name}(\.\d+)? = ", text)]
    if stages:
        detail += "; compiled " + " + ".join(stages)
    if u32_lines is not None:
        got = [ln[:60] for ln in _executed_lines(text)
               if U32_LINE.search(ln)]
        if len(got) != u32_lines:
            raise ValueError(
                f"{len(got)} lines hold a uint32 array of two dimensions "
                f"or more, expected {u32_lines}: {got[:4]}")
        detail += f"; {len(got)} uint32 line"
    return detail


def _probe_need(make_case, limit_fn: str = "_vmem_limit_bytes"):
    """The scoped-VMEM need Mosaic shows: climb the limit that
    ``ops.pallas_knn``'s ``limit_fn`` hands a launch (the kernel's, or
    ``_final_select_vmem_limit`` for the final select's stage) from
    8 MiB by the size each refusal names until it compiles, then bisect
    between the highest limit refused and the lowest that compiled.  A
    refusal's size alone misleads: under its need Mosaic may schedule
    otherwise and name what THAT would take (one 256-column chunk:
    98.32 MiB named at a limit of 58, compiles at 62), or spill past
    the whole VMEM and name nothing.  Returns (least MiB it compiled
    at, highest MiB refused, last size named)."""
    import jax

    import knn_tpu.ops.pallas_knn as pk

    limit = [8]
    real = getattr(pk, limit_fn)
    setattr(pk, limit_fn, lambda *a, **kw: limit[0] << 20)
    named = None

    def compiles():
        nonlocal named
        jax.clear_caches()
        try:
            _compile(*make_case())
            return True
        except Exception as e:  # noqa: BLE001 — parsed below
            m = re.search(r"Scoped allocation with size ([\d.]+)M", str(e))
            if m:
                named = float(m.group(1))
            elif "memory space vmem" not in str(e):
                raise
            return False

    try:
        refused = 0
        while not compiles():
            refused = limit[0]
            if refused >= 128:
                return None, refused, named
            step = refused + 8
            if named is not None and named > refused:
                step = max(math.ceil(named), refused + 4)
            limit[0] = min(128, step)
        ok = limit[0]
        while ok - refused > 1:
            limit[0] = (ok + refused) // 2
            if compiles():
                ok = limit[0]
            else:
                refused = limit[0]
        return ok, refused, named
    finally:
        setattr(pk, limit_fn, real)
        jax.clear_caches()


def default_cases():
    """The table a bare run prints: (name, shape, knob overrides,
    expectation[, mesh, terms]) — "compiles", or "refused" = the
    library's own VMEM model must refuse it with a ValueError before
    Mosaic is asked."""
    cases = [(f"{shape} {kernel} defaults", shape, {"kernel": kernel},
              "compiles")
             for kernel in ("tiled", "streaming", "fused")
             # the widths whose row tile is ONE grid step under the
             # tiled kernel at these knobs (analysis.vmem.row_blocking)
             # are GloVe's 384 padded columns, text2image2m5's 256
             # (below) and the widest, 512; at 640 and at gist's 1,024
             # it is four steps of 4,096 rows.  The other two kernels
             # must compile as they did at 128-column chunks
             for shape in ("sift", "gist", "glove", "wide512", "wide640")]
    cases.append(("sift fused block_q=256", "sift",
                  {"kernel": "fused", "block_q": 256}, "refused"))
    # the whole program of a byte corpus and a byte batch: one product,
    # one row stream (2.56 + 4.66 GB a chip where the full sum's program
    # keeps 2.56 + 5.94)
    cases += [(f"{shape} program mesh={mesh[0]}x{mesh[1]} terms=hh", shape,
               {}, "compiles", mesh, "hh")
              for shape, mesh in (("bigann5m", (1, 1)),
                                  ("bigann20m", (1, 4)))]
    # the range cell's first pass: 256 byte-valued columns in ONE dim
    # chunk, one product
    cases.append(("ssnpp2m5 program mesh=1x1 terms=hh", "ssnpp2m5", {},
                  "compiles", (1, 1), "hh"))
    # the inner-product cell's program: 201 columns placed in 256, k=10
    cases.append(("text2image2m5 program mesh=1x1", "text2image2m5", {},
                  "compiles", (1, 1), None))
    # gist1m's: the final select's Pallas stage over the kernel's own
    # 15,872 columns (no bin-merge), the widest it runs at
    cases.append(("gist program mesh=1x1", "gist", {}, "compiles", (1, 1),
                  None))
    return cases


def run_case(name, shape, overrides, expect, mesh, terms, devices, *,
             merge="ring", probe=False, row_block=None) -> bool:
    from knn_tpu import tuning

    # the knobs search_certified would resolve for these overrides
    knobs, _ = tuning.resolve_full(*SHAPES[shape], overrides=overrides)

    def make_case():
        if shape in SELF:
            return _spmd_case(shape, knobs, devices, mesh or (1, 1), merge,
                              terms, queries=NQ // 4, resident=True)
        if mesh is not None:
            # a call's 4,096 queries, or the launch the sub-batch rule
            # cuts it to where that many do not fit one chip (knnlm1m:
            # 512, its row operands resident)
            cut = mesh == (1, 1) and launch_queries(shape, knobs) < NQ // 4
            return _spmd_case(
                shape, knobs, devices, mesh, merge, terms,
                **({"queries": launch_queries(shape, knobs),
                    "resident": True} if cut else {}))
        return _kernel_case(shape, knobs, devices, terms, row_block)

    t0 = time.time()
    if probe:
        ok_at, refused, named = _probe_need(make_case)
        print(f"NEED {name}: compiles at limit {ok_at} MiB, refused at "
              f"{refused} (last size named {named} MiB)  "
              f"({time.time() - t0:.0f}s)", flush=True)
        return ok_at is not None
    try:
        # a range cell's first pass holds ONE line the range completion's
        # trace pattern matches (the certificate's packed bits)
        got, detail = "compiles", ": " + _compile(
            *make_case(),
            u32_lines=1 if mesh is not None and shape in RANGE else None)
    except ValueError as e:
        got, detail = "refused", f": {e}"
    except Exception as e:  # noqa: BLE001 — Mosaic/XLA refusal, reported
        got, detail = "mosaic-refused", f": {str(e)[-400:]}"
    ok = got == expect
    print(f"{'OK  ' if ok else 'FAIL'} {name}: {got}"
          f"{'' if ok else f' (expected {expect})'}{detail[:500]}  "
          f"({time.time() - t0:.0f}s)", flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--kernel")
    ap.add_argument("--block-q", type=int)
    ap.add_argument("--tile-n", type=int)
    ap.add_argument("--precision")
    ap.add_argument("--grid-order")
    ap.add_argument("--final-select")
    ap.add_argument("--mesh", help="QxD, e.g. 1x4: compile the full "
                    "SPMD certified program on that topology mesh")
    ap.add_argument("--merge", default="ring",
                    choices=("ring", "allgather"))
    ap.add_argument("--terms", choices=("hh+hl+lh", "hh+lh", "hh"),
                    help="the products of the bf16x3 split the kernel "
                    "forms; the library reads this off the data "
                    "(ops.pallas_knn.BF16X3_TERMS), here it is asked for")
    ap.add_argument("--row-block", type=int,
                    help="the rows of a tile one grid step of the tiled "
                    "kernel multiplies (ops.pallas_knn.row_blocking "
                    "reads this off the shape; here it is asked for, "
                    "for the kernel alone: no --mesh)")
    ap.add_argument("--temporaries", action="store_true",
                    help="print what every program a cell loads sets "
                    "aside, over the placed rows' bytes (--shape, else "
                    "gist and imagenet768): the reading behind "
                    "analysis.hbm.LANE_TILED_TEMP_FACTOR")
    ap.add_argument("--probe", action="store_true",
                    help="report the scoped-VMEM need Mosaic names "
                    "instead of compiling at the library's own limit")
    args = ap.parse_args(argv)

    devices = _topology_devices()
    print(f"target: {devices[0].device_kind} x{len(devices)} "
          f"({TOPOLOGY}, deviceless)", flush=True)
    if args.temporaries:
        from knn_tpu import tuning
        from knn_tpu.analysis import hbm

        # what a default call loads beside resident operands: the
        # sub-batch's program and the repair's re-select.  The others
        # are printed for the reading: the in-call forms are loaded
        # where the operands are NOT kept, and a launch of 4,096
        # queries is an explicit batch_size's or a 16,384-query call's
        # (its temporaries grow with the queries, not with the rows)
        worst = 0.0
        for shape in ([args.shape] if args.shape else TEMPORARIES_SHAPES):
            placed, table = temporaries_table(shape, devices, args.terms)
            n, given, k = SHAPES[shape]
            launch = launch_queries(
                shape, tuning.resolve_full(n, given, k)[0])
            beside = (f"operands resident, {launch} queries", "re-select")
            for label, temp in table:
                if temp is None:
                    print(f"TEMP {shape} {label}: over the chip's memory "
                          f"(the compiler refuses it)", flush=True)
                    continue
                counted = any(b in label for b in beside)
                if counted:
                    worst = max(worst, temp / placed)
                print(f"TEMP {shape} {label}: {temp / 1e9:.3f} GB of "
                      f"{placed / 1e9:.3f} GB placed = {temp / placed:.3f}"
                      f"{'  <- a default call' if counted else ''}",
                      flush=True)
        ok = worst <= hbm.LANE_TILED_TEMP_FACTOR
        print(f"{'OK  ' if ok else 'FAIL'} the largest a default call "
              f"loads beside resident operands: {worst:.3f} of the placed "
              f"rows; analysis.hbm.LANE_TILED_TEMP_FACTOR = "
              f"{hbm.LANE_TILED_TEMP_FACTOR}", flush=True)
        return 0 if ok else 1
    if args.shape is None:
        cases = default_cases()
    else:
        overrides = {
            "kernel": args.kernel, "block_q": args.block_q,
            "tile_n": args.tile_n, "precision": args.precision,
            "grid_order": args.grid_order,
            "final_select": args.final_select,
        }
        overrides = {k: v for k, v in overrides.items() if v is not None}
        label = " ".join(f"{k}={v}" for k, v in {
            **overrides, **({"terms": args.terms} if args.terms else {}),
            **({"row_block": args.row_block} if args.row_block else {}),
        }.items())
        cases = [(f"{args.shape} {label or 'defaults'}", args.shape,
                  overrides, "compiles")]
    mesh = None
    if args.mesh:
        mesh = tuple(int(x) for x in args.mesh.lower().split("x"))
        cases = [(f"{name} mesh={args.mesh} merge={args.merge}", *rest)
                 for name, *rest in cases]
    # a case names its own mesh and terms, or takes the command line's
    cases = [(*case, mesh, args.terms)[:6] for case in cases]
    ok = [run_case(*case, devices, merge=args.merge, probe=args.probe,
                   row_block=args.row_block)
          for case in cases]
    # the final select's bin-merge kernel, where the shape's width a chip
    # engages it: a bare run asks for bigann20m over its four chips (36
    # lane-rows a merge bin) and text2image2m5 on its one (62: the shape
    # whose blocks overran Mosaic's scoped VMEM on the chip, PR 31)
    merges = ([(args.shape, mesh[1] if mesh else 1)] if args.shape
              else [("bigann20m", 4), ("text2image2m5", 1), ("ssnpp2m5", 1)])
    for shape, db_shards in merges:
        case = _merge_case(shape, db_shards, devices)
        if case is None:
            continue
        t0 = time.time()
        name = f"{shape} select-merge kernel {case[1][0].shape}"
        try:
            _compile(*case)
            print(f"OK   {name}: compiles  ({time.time() - t0:.0f}s)",
                  flush=True)
        except Exception as e:  # noqa: BLE001 — Mosaic refusal, reported
            ok.append(False)
            print(f"FAIL {name}: {str(e)[-400:]}", flush=True)
    # the final select's Pallas stage alone, at each cell's (width, m):
    # it compiles at the limit the library asks for, and the least limit
    # Mosaic takes it at is inside the model's need plus an eighth
    from knn_tpu.analysis import vmem

    finals = ([(args.shape, mesh[1] if mesh else 1)] if args.shape
              else [("bigann20m", 4), ("gist", 1), ("text2image2m5", 1),
                    ("ssnpp2m5", 1)])
    for shape, db_shards in finals:
        case = _final_case(shape, db_shards, devices)
        if case is None:
            continue
        t0 = time.time()
        block_q, width, keep = case[2]
        name = (f"{shape} select-final stage {width} x {keep} in blocks of "
                f"{block_q}")
        try:
            _compile(*case[:2])
            model = sum(vmem.final_select_bytes(
                block_q, width, keep).values())
            need, _, _ = _probe_need(lambda: case[:2],
                                     "_final_select_vmem_limit")
            fits = need * vmem.MIB <= model + model // 8
            ok.append(fits)
            print(f"{'OK  ' if fits else 'FAIL'} {name}: compiles; least "
                  f"limit {need} MiB, model {model / vmem.MIB:.2f} MiB + an "
                  f"eighth  ({time.time() - t0:.0f}s)", flush=True)
        except Exception as e:  # noqa: BLE001 — Mosaic refusal, reported
            ok.append(False)
            print(f"FAIL {name}: {str(e)[-400:]}", flush=True)
    # the kernel where its row tile is cut by rows: the least limit
    # Mosaic takes it at is inside the model's need plus an eighth
    from knn_tpu import tuning

    # (at the default query block, and at 128: a short sub-batch's)
    for shape, block_q in ([] if args.shape else [
            (shape, block_q) for shape in ("gist", "openai500k")
            for block_q in (None, 128)]):
        knobs, _ = tuning.resolve_full(
            *SHAPES[shape],
            overrides={"block_q": block_q} if block_q else {})
        geo = vmem.launch_estimate(
            n=SHAPES[shape][0], d=SHAPES[shape][1], k=SHAPES[shape][2],
            **{kk: knobs[kk] for kk in ("precision", "kernel", "tile_n",
                                        "block_q", "survivors")})
        t0 = time.time()
        name = (f"{shape} kernel at block_q={geo['geometry']['block_q']} "
                f"in {geo['geometry']['row_steps']} steps of "
                f"{geo['geometry']['row_block']} rows a tile")
        need, _, _ = _probe_need(
            lambda: _kernel_case(shape, knobs, devices))
        model = geo["total_bytes"]
        fits = need is not None and need * vmem.MIB <= model + model // 8
        ok.append(fits)
        print(f"{'OK  ' if fits else 'FAIL'} {name}: least limit {need} "
              f"MiB, model {model / vmem.MIB:.2f} MiB + an eighth  "
              f"({time.time() - t0:.0f}s)", flush=True)
    # what one chip holds in a self-join cell: the placed rows, the
    # resident row operands, and the largest program loaded beside them
    if args.shape in SELF:
        from knn_tpu.analysis import hbm
        from knn_tpu.analysis.widths import lane_tiled
        from knn_tpu.ops.pallas_knn import TILE_N

        n, given, _ = SHAPES[args.shape]
        placed, table = temporaries_table(args.shape, devices, args.terms,
                                          self_rows=True)
        form = hbm.row_operand_bytes(
            -(-n // TILE_N) * TILE_N, lane_tiled(given),
            "hl" in (args.terms or "hh+hl+lh"))
        for label, temp in table:
            print(f"TEMP {args.shape} {label}: {temp / 1e9:.3f} GB",
                  flush=True)
        most = max(temp for _, temp in table)
        print(f"MEM  {args.shape}: rows {placed / 1e9:.3f} GB + row "
              f"operands {form / 1e9:.3f} GB + the largest program's "
              f"temporaries {most / 1e9:.3f} GB = "
              f"{(placed + form + most) / 1e9:.3f} GB on one chip "
              f"(deviceless: XLA's memory_analysis, no chip run)",
              flush=True)
    # the range completion's program
    for shape in ([args.shape] if args.shape else RANGE):
        if shape not in RANGE:
            continue
        name, fn, avals = _range_case(shape, devices, mesh or (1, 1))
        t0 = time.time()
        try:
            print(f"OK   {name}: compiles: {_compile(fn, avals)}  "
                  f"({time.time() - t0:.0f}s)", flush=True)
        except Exception as e:  # noqa: BLE001 — XLA refusal, reported
            ok.append(False)
            print(f"FAIL {name}: {str(e)[-400:]}", flush=True)
    return 0 if all(ok) else 1


if __name__ == "__main__":
    sys.exit(main())
