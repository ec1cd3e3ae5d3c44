"""What a four-chip cell needs of the benchmark's own tests, as a new
file beside them.

Four CPU devices, asked for before JAX starts its backend:
``bigann20m-x4.sweep`` places its rows on a (1, 4) mesh, and
``tests/test_cells.py`` runs every cell of BENCHMARK.json.

And ``tests/test_lastline.py`` builds its good line with
``device.count`` 1, which the validator rightly refuses for a cell that
asks for four chips: here the line gets the count of the cell's chips.
"""

import os

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()


@pytest.fixture(autouse=True)
def good_lines_hold_the_cells_chips(request, monkeypatch):
    mod = request.module
    if mod.__name__ != "test_lastline":
        return
    one_chip = mod.good

    def good(workload, traced):
        line = one_chip(workload, traced)
        line["device"]["count"] = mod.lastline.cell_of(
            mod.BENCH, workload)["chips"]
        return line

    monkeypatch.setattr(mod, "good", good)
