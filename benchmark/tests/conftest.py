"""The benchmark's own tests run on the CPU, in seconds:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
