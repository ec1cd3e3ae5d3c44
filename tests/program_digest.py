"""Digests of the UNFILTERED certified program at the benchmark's cells'
shapes: the sha256 of its jaxpr (the kernel's body included), traced
from abstract arguments on the CPU, so nothing of a corpus's size is
made.  ``tests/fixtures/unfiltered_program_digests.json`` holds what the
tree BEFORE per-query validity (PR 40's parent) gives;
``tests/test_yfcc_filter.py`` holds this tree to it, so that a call
without ``filter_tags`` is the program it always was, operation for
operation.  To record anew after a change that is MEANT to alter the
unfiltered program (say so in the PR):

    JAX_PLATFORMS=cpu PYTHONPATH=<tree> python tests/program_digest.py \\
        > tests/fixtures/unfiltered_program_digests.json

``tests/fixtures/sub_batch_program_digests.json`` holds the same
program's digests at the SUB-BATCH rows a default call may be cut into
(``SUB_BATCH_ROWS`` of a cell's 4,096 queries), from the tree before the
default sub-batch was a rule (PR 42's parent, where only an explicit
``batch_size`` gave them); ``tests/test_sub_batch.py`` holds the rule's
cut to them.  Recorded by the same command with ``--sub-batches``.

PR 46 recorded ``gist1m.sweep``'s three digests anew and no other: its
kernel cuts the row tile by rows where it cut the columns
(``analysis.vmem.row_blocking``); the four cells whose tile is one step
give the digests they gave.

PR 48 added the eighth row, ``imagenet-knn768.sweep_vote``, whose program
is the vote program (``VOTED``); the seven digests of the five older
rows are what they were.

PR 49 recorded ``gist1m.sweep``'s and ``imagenet-knn768.sweep_vote``'s
three digests each anew and no other: both cells keep their row operands
resident on the chip since (``analysis.hbm.program_temp_factor``), so
their rows are digested as the other cells' are, handed both halves and
the norms (``resident_parts`` 2 where it was 0).  PR 49's PARENT gives
the same six digests at ``resident_parts`` 2: the program is the
parent's, it is the cell that runs another of its forms.

PR 51 added the ninth row, ``deep5m-knng.build``, whose program is the
SELF program (``SELF``: ``_pallas_self_program``, the block's first row
id where the queries stood); the eight older rows' digests are what
they were.

PR 52 recorded ``text2image2m5.sweep_ip``'s, ``imagenet-knn768.sweep_vote``'s
and ``deep5m-knng.build``'s three digests each anew and no other: the
final select's bin-merge reads the kernel's candidate arrays where they
lie, its last group short of its grid (306 lane-rows in 5 groups of 62,
158 in 7 x 23, 612 in 5 x 123) masked by index in the merge's own cell,
where two ``pad`` copied both arrays to the group grid.  The four rows
whose groups tile the width (``bigann5m.sweep``, ``bigann20m-x4.sweep``,
``ssnpp2m5.sweep_range``: 17 x 36 and 17 x 18) or that run no merge
(``gist1m.sweep``) give the digests they gave: the program is the
parent's where nothing is short.
"""

import hashlib
import json
import re

#: cell -> (db shards, rows a shard, placed columns, k, terms, halves of
#: the rows kept resident, inner-product placement).  Placed columns:
#: whole 128-column lane tiles since PR 44 (``gist1m``'s 960 given
#: columns in 1,024, ``text2image2m5``'s 201 in 256; those two cells'
#: digests were recorded anew there, on a builder that gave the old
#: ones at the old widths)
CELLS = {
    "bigann5m.sweep": (1, 5_000_000, 128, 100, "hh", 1, False),
    "gist1m.sweep": (1, 1_000_000, 1024, 100, "hh+hl+lh", 2, False),
    "bigann20m-x4.sweep": (4, 5_000_000, 128, 100, "hh", 1, False),
    "text2image2m5.sweep_ip": (1, 2_500_000, 256, 10, "hh+hl+lh", 2, True),
    "ssnpp2m5.sweep_range": (1, 2_500_000, 256, 100, "hh", 1, False),
    # the eighth cell (PR 48): its program is the VOTE program (VOTED)
    "imagenet-knn768.sweep_vote": (1, 1_281_167, 768, 20, "hh+hl+lh", 2,
                                   False),
    # the ninth cell (PR 51): its program is the SELF program (SELF);
    # 96 given columns placed in 128
    "deep5m-knng.build": (1, 5_000_000, 128, 10, "hh+hl+lh", 2, False),
}
#: cell -> (temperature, classes out) of a cell answered by
#: ``predict_certified(vote="softmax")``: ``_pallas_vote_program`` is
#: digested in ``_pallas_certified_program``'s place, with the pair slack
#: and the replicated labels after the tail.  Recorded on PR 48's tree,
#: which brought the program: a later edit that moves the search
#: programs' shared tail moves this one too, and says so here
VOTED = {"imagenet-knn768.sweep_vote": (0.07, 5)}
#: cells answered by ``knn_tpu.join.knn_self_join``:
#: ``_pallas_self_program`` is digested in ``_pallas_certified_program``'s
#: place, a launch of ``queries`` rows, one int32 (the launch's first
#: row id) where the queries stood.  Recorded on PR 51's tree, which
#: brought the program; it shares the search programs' kernel launch,
#: final select, rescore and tail, so an edit that moves those moves it
SELF = ("deep5m-knng.build",)
QUERIES, MARGIN = 4096, 28
#: the rows of a cell's call cut in two and in four
SUB_BATCH_ROWS = (2048, 1024)


def traced(cell: str, queries: int = QUERIES, interpret: bool = True):
    """The cell's program traced from abstract arguments: its closed
    jaxpr.  ``interpret`` False traces the kernels as the chip runs
    them (the interpreter pads every array it cuts into blocks)."""
    import jax
    import jax.numpy as jnp

    from knn_tpu.ops import pallas_knn as pk
    from knn_tpu.parallel import make_mesh
    from knn_tpu.parallel import sharded as sh

    shards, rows, dim, k, terms, parts, dot = CELLS[cell]
    mesh = make_mesh(1, shards, devices=jax.devices()[:shards])
    m = k + MARGIN
    block, _ = pk.row_blocking(dim, tile_n=pk.TILE_N, block_q=pk.BLOCK_Q,
                               precision="bf16x3", kernel="tiled",
                               terms=terms, survivors=None)
    if cell in SELF:
        prog = sh._pallas_self_program(
            mesh, m, k, "ring", pk.TILE_N, rows * shards, queries,
            interpret=interpret, terms=terms, row_block=block,
            resident_parts=parts)
    elif cell in VOTED:
        temperature, classes_out = VOTED[cell]
        prog = sh._pallas_vote_program(
            mesh, m, k, "ring", pk.TILE_N, "bf16x3", rows * shards,
            (1.0 / temperature, classes_out,
             sh.vote_delta(temperature, k)),
            interpret=interpret, terms=terms, row_block=block,
            resident_parts=parts)
    else:
        prog = sh._pallas_certified_program(
            mesh, m, k, "ring", pk.TILE_N, "bf16x3", n_train=rows * shards,
            interpret=interpret, terms=terms, augmented=dot, row_block=block,
            resident_parts=parts)
    rows_p = -(-rows // pk.TILE_N) * pk.TILE_N
    dim_p = -(-dim // pk.DIM_CHUNK) * pk.DIM_CHUNK

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    tail = [aval((), jnp.float32)]
    if parts:
        tail += [aval((shards * rows_p, dim_p), jnp.bfloat16)] * parts
        tail += [aval((shards * rows_p,), jnp.float32)]
    if dot or cell in VOTED:
        tail += [aval((), jnp.float32)]
    if cell in VOTED:
        tail += [aval((shards * rows,), jnp.int32)]
    first = (aval((1,), jnp.int32) if cell in SELF
             else aval((queries, dim), jnp.float32))
    return jax.make_jaxpr(prog)(
        first, aval((shards * rows, dim), jnp.float32), *tail)


def jaxpr_text(cell: str, queries: int = QUERIES) -> str:
    text = str(traced(cell, queries))
    # addresses, and the order a frozenset happens to print in
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    return re.sub(r"frozenset\(\{([^}]*)\}\)", lambda m: "frozenset({%s})"
                  % ", ".join(sorted(m.group(1).split(", "))), text)


def digest(cell: str, queries: int = QUERIES) -> str:
    return hashlib.sha256(jaxpr_text(cell, queries).encode()).hexdigest()


def digests() -> dict:
    return {cell: digest(cell) for cell in CELLS}


def sub_batch_digests() -> dict:
    return {cell: {str(rows): digest(cell, rows) for rows in SUB_BATCH_ROWS}
            for cell in CELLS}


if __name__ == "__main__":
    import sys

    import jax

    jax.config.update("jax_num_cpu_devices", 8)
    print(json.dumps(sub_batch_digests() if "--sub-batches" in sys.argv
                     else digests(), indent=1))
