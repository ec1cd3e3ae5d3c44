"""``vmem-budget`` — the knob grid fits on-chip memory before anyone
burns chip time discovering it doesn't.

The TPU-KNN thesis is peak-FLOP/s kernels; an over-VMEM knob
combination fails at Mosaic compile time, on hardware, mid-tune.  This
checker prices candidates with the analytic bytes-per-launch model
(knn_tpu.analysis.vmem — operand blocks + scratch + carry, mirroring
the budgets ``ops.pallas_knn`` computes for its own compiler hints)
and enforces three invariants at the headline shape (SIFT1M):

1. ``DEFAULT_KNOBS`` fit the target device kind (TPU v5e) — the
   untuned configuration every ``search_certified`` call runs must
   never be the one that overflows;
2. every autotuner grid candidate (``knob_grid("full")``) of the arm
   the model is calibrated for (``vmem.calibrated``) fits AT LEAST ONE
   known device kind — a candidate that fits nowhere is dead grid
   weight the runtime gate would refuse on every real device (other
   arms are Mosaic's to judge, never the model's);
3. the runtime gate is actually wired: ``tuning/autotune.py`` imports
   the vmem model (the lockstep check that keeps invariant 2
   meaningful — pricing before timing, provenance recorded like
   roofline pruning).

Scope note: invariants 1–2 price the IMPORTED tuning layer's
``DEFAULT_KNOBS``/``knob_grid`` (model and grid live in the same
package, so importing is the only non-circular source of truth) — this
checker speaks for the session package; under ``--root`` pointing at a
different checkout, only invariant 3 reads that tree.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence

from knn_tpu.analysis import vmem
from knn_tpu.analysis.core import Context, Finding, checker


def grid_findings(grid: Sequence[Dict[str, object]],
                  defaults: Dict[str, object],
                  shape: Optional[dict] = None,
                  label=None) -> List[Finding]:
    """Price ``grid`` (candidate deviations over ``defaults``) at
    ``shape`` — the reusable core the checker and the known-bad fixture
    tests share."""
    shape = dict(shape or vmem.HEADLINE_SHAPE)
    findings: List[Finding] = []
    grid_path = os.path.join("knn_tpu", "tuning", "autotune.py")

    verdict = vmem.check_candidate(
        defaults, device_kind=vmem.TARGET_DEVICE_KIND, **shape)
    if verdict["fits"] is False:
        findings.append(Finding(
            checker="vmem-budget", path=grid_path, line=0,
            symbol="DEFAULT_KNOBS",
            message=f"the default knob set needs "
                    f"{verdict['estimate_bytes']} bytes of VMEM at the "
                    f"headline shape — over "
                    f"{vmem.TARGET_DEVICE_KIND}'s "
                    f"{verdict['budget_bytes']}-byte budget",
            fix_hint="shrink tile_n/block_q; the untuned path must "
                     "always compile"))
    for cand in grid:
        knobs = dict(defaults)
        knobs.update(cand)
        if not isinstance(knobs.get("precision"), str) or \
                knobs["precision"] not in vmem.DB_PARTS:
            continue  # unpriceable: the model must never widen-refuse
        if vmem.fits_some_kind(knobs, **shape):
            continue
        est = vmem.launch_estimate(
            n=shape["n"], d=shape["d"], k=shape["k"],
            margin=shape.get("margin", 28),
            precision=knobs.get("precision"),
            kernel=knobs.get("kernel"), tile_n=knobs.get("tile_n"),
            block_q=knobs.get("block_q"),
            survivors=knobs.get("survivors"))
        name = label(knobs) if label else str(sorted(cand.items()))
        findings.append(Finding(
            checker="vmem-budget", path=grid_path, line=0, symbol=name,
            message=f"grid candidate needs {est['total_bytes']} bytes "
                    f"of VMEM per launch at the headline shape — over "
                    f"EVERY known device kind's budget (max "
                    f"{max(vmem.VMEM_BYTES_BY_KIND.values())}); the "
                    f"runtime gate would refuse it on all hardware",
            fix_hint="drop the combination from the grid (or shrink "
                     "its tile_n/block_q)"))
    return findings


@checker("vmem-budget",
         "knob-grid candidates priced against per-device-kind VMEM",
         uses_ast=False)
def check_vmem(ctx: Context) -> List[Finding]:
    autotune_rel = os.path.join("knn_tpu", "tuning", "autotune.py")
    if not ctx.exists(autotune_rel):
        return []  # fixture tree without the tuning layer
    from knn_tpu.tuning.autotune import DEFAULT_KNOBS, _label, knob_grid

    # invariant 2 sweeps BOTH tuning regimes: the throughput profile's
    # block_q 512/1024 ladder (the bulk-join grid, knn_tpu.join) is
    # exactly where a fits-nowhere arm is easiest to author by accident
    findings = grid_findings(
        knob_grid("full"), DEFAULT_KNOBS,
        label=lambda knobs: _label(knobs))
    findings += grid_findings(
        knob_grid("full", profile="throughput"), DEFAULT_KNOBS,
        label=lambda knobs: "throughput:" + _label(knobs))
    # invariant 3: the runtime gate is wired (autotune prices before
    # timing) — a model nobody consults protects nothing
    src = ctx.read(autotune_rel)
    if "analysis.vmem" not in src and "analysis import vmem" not in src:
        findings.append(Finding(
            checker="vmem-budget", path=autotune_rel, line=0,
            message="autotune() does not consult the VMEM budget model "
                    "(knn_tpu.analysis.vmem) before timing candidates",
            fix_hint="price every candidate with "
                     "vmem.check_candidate() and refuse over-budget "
                     "ones with provenance, like roofline pruning"))
    return findings
