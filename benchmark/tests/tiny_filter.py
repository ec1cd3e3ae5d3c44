"""The ``sweep_filter`` entry of ``tinyroot.TINY_TRAFFIC``, given to it
from outside: ``tinyroot.make`` shrinks every traffic file by a literal
table of driver kinds, so a traffic file of a kind the table lacks makes
it raise ``KeyError`` for every caller, and ``tinyroot.py`` was not this
change's to edit.  Importing this module (``tests/conftest.py`` for the
tier-1 files that call ``tinyroot.make``, ``test_filter_cell.py`` for
``benchmark/tests``; collection imports both before any fixture runs)
adds the entry, and where those modules are loaded
``test_cells.BREAKERS``' (keyed by driver kind too) and this cell's
name in ``test_call_account``'s five entries (which hold the list of
"all five cells" and are compared with BENCHMARK.json's).  The repair is
one line in ``tinyroot.py`` (an entry, or ``TINY_TRAFFIC.get(kind,
TINY_SWEEP)``), one in ``test_cells.py`` and the cell's name in
``data/call_account_cell.json``; root PERF.md section 7 asks the next
``benchmark`` issue for them, which then deletes this file.
"""

import tinyroot

#: the seven bands cut to a 64-query batch over 3,000 rows
TINY_SWEEP_FILTER = {
    **tinyroot.TINY_SWEEP, "check_rows_a_band": 1,
    "strata": [[0, 8], [1, 24], [10, 24], [100, 8]]}

tinyroot.TINY_TRAFFIC.setdefault("sweep_filter", TINY_SWEEP_FILTER)


def break_like_sweep(test_cells) -> None:
    """``test_cells.BREAKERS`` is keyed by driver kind too: a filtered
    answer is broken as a plain one is (one neighbour swapped)."""
    test_cells.BREAKERS.setdefault("sweep_filter", test_cells._break_sweep)


def join_the_call_account(test_call_account) -> None:
    """``data/call_account_cell.json``'s five entries list "every
    sweep cell" by name and ``test_call_account`` holds that list to
    BENCHMARK.json's: a sixth cell joins it (its calls keep the same
    account, so the five readers find their spans there)."""
    for entry in test_call_account.ENTRIES:
        if "yfcc2m5.sweep_filter" not in entry["workloads"]:
            entry["workloads"].append("yfcc2m5.sweep_filter")
