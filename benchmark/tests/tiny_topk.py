"""The ``sweep_topk`` entries of the tables that ``benchmark/tests`` keys
by driver kind, given to them from outside, as ``tiny_filter.py``,
``tiny_vote.py`` and ``tiny_graph.py`` do for their kinds (the first
says why: ``tinyroot.make`` shrinks every traffic file by a literal
table of driver kinds and raises ``KeyError`` on one it lacks, and
neither ``tinyroot.py`` nor ``test_cells.py`` nor
``data/call_account_cell.json`` was this change's to edit).  Importing
this module (``tests/conftest.py`` for the tier-1 files that call
``tinyroot.make``, ``test_topk_cell.py`` for ``benchmark/tests``) adds
``tinyroot.TINY_TRAFFIC["sweep_topk"]``; where those modules are loaded,
:func:`break_the_topk` gives ``test_cells.BREAKERS`` its entry (the
sweep's own breaker: the call is ``search_certified``) and
:func:`join_the_call_account` the cell's name to ``test_call_account``'s
five entries.  The repair is one line in each of the three files;
ROADMAP R0 item 0 asks the next ``benchmark`` issue for it, which then
deletes this file with the other three.

The cell keeps its own k = 1,024 at the tiny corpus (3,000 rows,
``TINY_CONFIG``'s): m+2 = 1,054 of them are selected, over a third, so
the tiny run crosses the large-keep line too (XLA's final select, a
survivor depth over 2).
"""

import tinyroot

CELL = "knnlm1m.sweep_k1024"

tinyroot.TINY_TRAFFIC.setdefault("sweep_topk", tinyroot.TINY_SWEEP)


def break_the_topk(test_cells) -> None:
    test_cells.BREAKERS.setdefault("sweep_topk", test_cells._break_sweep)


def join_the_call_account(test_call_account) -> None:
    """After the four cells before it, whichever file is imported first:
    the lists are compared in BENCHMARK.json's order."""
    import tiny_graph

    tiny_graph.join_the_call_account(test_call_account)
    for entry in test_call_account.ENTRIES:
        if CELL not in entry["workloads"]:
            entry["workloads"].append(CELL)
