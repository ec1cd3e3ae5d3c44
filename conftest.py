"""Three cases of ``benchmark/tests`` say what PRs 31 and 34 made untrue, in files
that only a ``benchmark`` PR may edit (nothing under ``benchmark/`` that
exists is edited by any other kind).  They are expected failures until
that PR makes the edits named here and deletes this file; nothing
else is touched, and ``tests/`` has no case by these names."""

import pytest

OUTDATED = {
    "benchmark/tests/test_cells.py::"
    "test_a_broken_timed_path_comes_out_not_correct[text2image2m5.sweep_ip]":
        "its table of breakers is keyed by traffic kind and has no entry "
        "for 'sweep_ip' (KeyError): add \"sweep_ip\": _break_sweep. The "
        "cell's broken paths are tests/test_text2image.py's until then",
    "benchmark/tests/test_cells.py::"
    "test_a_broken_timed_path_comes_out_not_correct[ssnpp2m5.sweep_range]":
        "its table of breakers has no entry for 'sweep_range' (KeyError), "
        "and _break_sweep would not do: it alters the first pass's k-th "
        "index, which a range answer holds only where completion replaces "
        "it. Add \"sweep_range\": a breaker of its own that patches "
        "ShardedKNN.range_search_certified to drop the last index of every "
        "non-empty list. The cell's broken paths (no completion, an "
        "exclusive boundary, an index dropped in the pack) are "
        "tests/test_ssnpp_range.py's until then",
    "benchmark/tests/test_sweep_stages.py::"
    "test_the_stage_entries_use_layer_names_the_benchmark_has_or_one_new":
        "it asserts that the six stage entries of data/sweep_stages_cell.json "
        "are in no per_layer list; since PR 31 they are, for "
        "text2image2m5.sweep_ip, the first cell whose driver hands the "
        "harness a registry: drop that assertion and the 'one new layer' one",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        why = OUTDATED.get(item.nodeid)
        if why is not None:
            item.add_marker(pytest.mark.xfail(reason=why, strict=True))
