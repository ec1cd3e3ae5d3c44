"""The ``graph_build`` entries of the tables that ``benchmark/tests`` keys
by driver kind, given to them from outside, as ``tiny_filter.py`` and
``tiny_vote.py`` do for their kinds (the first says why:
``tinyroot.make`` shrinks every traffic file by a literal table of
driver kinds and raises ``KeyError`` on one it lacks, and neither
``tinyroot.py`` nor ``test_cells.py`` nor ``data/call_account_cell.json``
was this change's to edit).  Importing this module (``tests/conftest.py``
for the tier-1 files that call ``tinyroot.make``, ``test_graph_cell.py``
for ``benchmark/tests``) adds ``tinyroot.TINY_TRAFFIC["graph_build"]``;
where those modules are loaded, :func:`break_the_graph` gives
``test_cells.BREAKERS`` its entry and :func:`join_the_call_account` the
cell's name to ``test_call_account``'s five entries.  The repair is one
line in each of the three files; ROADMAP R0 item 0 asks the next
``benchmark`` issue for it, which then deletes this file with
``tiny_filter.py`` and ``tiny_vote.py``.
"""

import numpy as np

import tinyroot

CELL = "deep5m-knng.build"

#: calls of 500 rows at the tiny corpus (3,000 rows, TINY_CONFIG's): a
#: call is one block and one launch there (the engine's blocks are
#: 4,096 rows), and a whole number of calls make a pass over the rows, so
#: that no call before the wrap is a shorter one, which at this size is
#: another program (at the cell's own size a short call is still whole
#: launches of 1,024)
TINY_GRAPH = {"call_rows": 500, "block_rows": 500, "batch_rows": 500,
              "check_rows": 8, "trace_seconds": 1}

tinyroot.TINY_TRAFFIC.setdefault("graph_build", TINY_GRAPH)


def _break_graph_build(monkeypatch):
    """An answer altered where it is produced: every row's own id is put
    back at the head of its list, at distance 0 (what the search gives
    when nothing takes the query's own row out)."""
    import knn_tpu.join

    real = knn_tpu.join.knn_self_join

    def broken(program, rows=None, **kw):
        d, i, stats = real(program, rows=rows, **kw)
        lo, hi = stats["row_range"]
        d, i = np.array(d), np.array(i)
        d[:, 1:], i[:, 1:] = d[:, :-1].copy(), i[:, :-1].copy()
        d[:, 0], i[:, 0] = 0.0, np.arange(lo, hi)
        return d, i, stats

    monkeypatch.setattr(knn_tpu.join, "knn_self_join", broken)


def break_the_graph(test_cells) -> None:
    test_cells.BREAKERS.setdefault("graph_build", _break_graph_build)


def join_the_call_account(test_call_account) -> None:
    """After the three cells before it, whichever file is imported
    first: the lists are compared in BENCHMARK.json's order."""
    import tiny_vote

    tiny_vote.join_the_call_account(test_call_account)
    for entry in test_call_account.ENTRIES:
        if CELL not in entry["workloads"]:
            entry["workloads"].append(CELL)
