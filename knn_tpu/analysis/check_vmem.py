"""``vmem-budget`` — the default knob set fits on-chip memory before
anyone burns chip time discovering it doesn't.

An over-VMEM knob combination fails at Mosaic compile time, on
hardware.  This checker prices ``tuning.DEFAULT_KNOBS`` — the
configuration every ``search_certified`` call that names no knob runs —
with the analytic bytes-per-launch model (knn_tpu.analysis.vmem —
operand blocks + scratch + carry, mirroring the budgets
``ops.pallas_knn`` computes for its own compiler hints) at the headline
shape (SIFT1M) on the target device kind (TPU v5e): it must never be
the one that overflows.  (tests/test_analysis.py prices the same knobs
at every benchmark cell's shape.)

Scope note: the checker prices the IMPORTED package's ``DEFAULT_KNOBS``
(model and knobs live in the same package, so importing is the only
non-circular source of truth) — it speaks for the session package;
under ``--root`` pointing at a checkout without ``knn_tpu/tuning`` it
checks nothing.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

from knn_tpu.analysis import vmem
from knn_tpu.analysis.core import Context, Finding, checker

_KNOBS_PATH = os.path.join("knn_tpu", "tuning", "__init__.py")


def default_findings(defaults: Dict[str, object],
                     shape: Optional[dict] = None) -> List[Finding]:
    """Price ``defaults`` at ``shape`` on the target device — the
    reusable core the checker and the known-bad fixture test share."""
    shape = dict(shape or vmem.HEADLINE_SHAPE)
    verdict = vmem.check_candidate(
        defaults, device_kind=vmem.TARGET_DEVICE_KIND, **shape)
    if verdict["fits"] is not False:
        return []
    return [Finding(
        checker="vmem-budget", path=_KNOBS_PATH, line=0,
        symbol="DEFAULT_KNOBS",
        message=f"the default knob set needs "
                f"{verdict['estimate_bytes']} bytes of VMEM at the "
                f"headline shape — over {vmem.TARGET_DEVICE_KIND}'s "
                f"{verdict['budget_bytes']}-byte budget",
        fix_hint="shrink tile_n/block_q; the path that names no knob "
                 "must always compile")]


@checker("vmem-budget",
         "the default knob set priced against the target device's VMEM",
         uses_ast=False)
def check_vmem(ctx: Context) -> List[Finding]:
    if not ctx.exists(_KNOBS_PATH):
        return []  # fixture tree without the tuning layer
    from knn_tpu.tuning import DEFAULT_KNOBS

    return default_findings(DEFAULT_KNOBS)
