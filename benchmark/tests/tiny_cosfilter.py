"""The ``sweep_cos_filter`` entries of the tables that ``benchmark/tests``
keys by driver kind, given to them from outside, as ``tiny_filter.py``,
``tiny_vote.py``, ``tiny_graph.py`` and ``tiny_topk.py`` do for their
kinds (the first says why: ``tinyroot.make`` shrinks every traffic file
by a literal table of driver kinds and raises ``KeyError`` on one it
lacks, and neither ``tinyroot.py`` nor ``test_cells.py`` nor
``data/call_account_cell.json`` was this change's to edit).  Importing
this module (``tests/conftest.py`` for the tier-1 files that call
``tinyroot.make``, ``test_cosfilter_cell.py`` for ``benchmark/tests``)
adds ``tinyroot.TINY_TRAFFIC["sweep_cos_filter"]``; where those modules
are loaded, :func:`break_like_sweep` gives ``test_cells.BREAKERS`` its
entry (the sweep's own breaker: the call is ``search_certified``) and
:func:`join_the_call_account` the cell's name to ``test_call_account``'s
five entries.  The repair is one line in each of the three files;
ROADMAP R0 item 0 asks the next ``benchmark`` issue for it, which then
deletes this file with the other four.
"""

import tinyroot

CELL = "openai500k-intfilter.sweep_cos_filter"
CONFIG = "openai500k-intfilter"

#: the two rates cut to the tiny corpus (3,000 rows, ``TINY_CONFIG``'s):
#: 1 % filtered out, and 99 % out, which leaves 30 rows for k = 100, so
#: every other answer of a tiny batch comes back short and padded
TINY_SWEEP_COS_FILTER = {**tinyroot.TINY_SWEEP, "filter_from": [30, 2970],
                         "check_flagged_rows": 4}

tinyroot.TINY_TRAFFIC.setdefault("sweep_cos_filter", TINY_SWEEP_COS_FILTER)


def break_like_sweep(test_cells) -> None:
    test_cells.BREAKERS.setdefault("sweep_cos_filter",
                                   test_cells._break_sweep)


def join_the_call_account(test_call_account) -> None:
    """After the five cells before it, whichever file is imported
    first: the lists are compared in BENCHMARK.json's order."""
    import tiny_topk

    tiny_topk.join_the_call_account(test_call_account)
    for entry in test_call_account.ENTRIES:
        if CELL not in entry["workloads"]:
            entry["workloads"].append(CELL)
