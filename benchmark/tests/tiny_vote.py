"""The ``sweep_vote`` entries of the tables that ``benchmark/tests`` keys
by driver kind, given to them from outside, as ``tiny_filter.py`` does for
``sweep_filter`` (its docstring says why: ``tinyroot.make`` shrinks every
traffic file by a literal table of driver kinds and raises ``KeyError``
on one it lacks, and neither ``tinyroot.py`` nor ``test_cells.py`` nor
``data/call_account_cell.json`` was this change's to edit).  Importing
this module (``tests/conftest.py`` for the tier-1 files that call
``tinyroot.make``, ``test_vote_cell.py`` for ``benchmark/tests``) adds
``tinyroot.TINY_TRAFFIC["sweep_vote"]``; where those modules are loaded,
:func:`break_the_vote` gives ``test_cells.BREAKERS`` its entry and
:func:`join_the_call_account` the cell's name to ``test_call_account``'s
five entries.  The repair is one line in each of the three files; root
PERF.md section 7 asks the next ``benchmark`` issue for it, which then
deletes this file with ``tiny_filter.py``.
"""

import functools

import numpy as np

import tinyroot

CELL = "imagenet-knn768.sweep_vote"

tinyroot.TINY_TRAFFIC.setdefault("sweep_vote", tinyroot.TINY_SWEEP)


def _break_sweep_vote(monkeypatch):
    """An answer altered where it is produced: the first two classes of
    every query that has two change places."""
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN.predict_certified

    @functools.wraps(real)  # the driver asks the signature for the path
    def broken(self, queries, **kw):
        classes, totals, stats = real(self, queries, **kw)
        classes = np.array(classes)
        two = classes[:, 1] >= 0
        classes[two, :2] = classes[two, 1::-1]
        return classes, totals, stats

    monkeypatch.setattr(ShardedKNN, "predict_certified", broken)


def break_the_vote(test_cells) -> None:
    test_cells.BREAKERS.setdefault("sweep_vote", _break_sweep_vote)


def join_the_call_account(test_call_account) -> None:
    """After the filter cell's and the cosine cell's, whichever file is
    imported first: the lists are compared in BENCHMARK.json's order."""
    import tiny_cos
    import tiny_filter

    tiny_filter.join_the_call_account(test_call_account)
    for cell in (tiny_cos.CELL, CELL):
        for entry in test_call_account.ENTRIES:
            if cell not in entry["workloads"]:
                entry["workloads"].append(cell)
