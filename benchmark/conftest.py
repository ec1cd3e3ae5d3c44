"""What a four-chip cell needs of the benchmark's own tests: four CPU
devices, asked for before JAX starts its backend.  ``bigann20m-x4.sweep``
places its rows on a (1, 4) mesh, and ``tests/test_cells.py`` runs every
cell of BENCHMARK.json.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()
