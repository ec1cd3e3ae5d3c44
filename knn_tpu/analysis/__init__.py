"""knn_tpu.analysis — the repo-native static-analysis suite.

Machine-enforces the invariants every PR has been hand-checking, as
six registered checkers over a small framework (docs/ANALYSIS.md):

- ``switch-lockstep`` — every ``KNN_TPU_*`` env switch
  declared in the central catalog (:mod:`knn_tpu.analysis.switches`),
  documented, consumed, and test-isolated (conftest GENERATES its
  isolation from the catalog);
- ``metric-lockstep`` — the PR-4 metric-name lint rebuilt in the
  framework (``scripts/lint_metric_names.py`` is now a shim over it);
- ``locked-mutation`` — classes annotated thread-safe mutate shared
  attributes only under their declared lock (runtime complement:
  :mod:`knn_tpu.analysis.lockorder`, the instrumented-lock deadlock
  detector the hammer tests run);
- ``jax-hygiene`` — wall-clock reads, host syncs inside ``@hot_path``
  functions (:mod:`knn_tpu.analysis.annotations`), unhashable static
  args;
- ``vmem-budget`` — the default knob set priced against the target
  device kind's VMEM (:mod:`knn_tpu.analysis.vmem`);
- ``artifact-lockstep`` — the artifact pipeline in lockstep with its
  declarative schema catalog (:mod:`knn_tpu.analysis.artifacts`):
  every key an emitter writes into a cataloged bench block resolves in
  its schema, every schema field is emitted or justified-suppressed,
  the refresher performs every declared hoist, the sentinel derives
  its curated fields from the catalog, every version token is consumed
  by exactly one validator, and every block type keeps its docs
  anchor.

Entry points: ``python -m knn_tpu.cli lint`` (jax-free; exit 0 green,
1 findings), :func:`run` in-process.  Suppressions require a written
justification and fail the lint when stale
(``knn_tpu/analysis/suppressions.json``).
"""

from __future__ import annotations

from knn_tpu.analysis.core import (  # noqa: F401 — the public surface
    CHECKERS,
    Context,
    Finding,
    Report,
    SOURCE_ROOTS,
    SUPPRESSIONS_PATH,
    checker,
    load_suppressions,
)
from knn_tpu.analysis import (  # noqa: F401 — registration imports
    check_artifacts,
    check_concurrency,
    check_jax,
    check_metrics,
    check_switches,
    check_vmem,
)
from knn_tpu.analysis.core import run  # noqa: F401

__all__ = ["CHECKERS", "Context", "Finding", "Report", "SOURCE_ROOTS",
           "SUPPRESSIONS_PATH", "checker", "load_suppressions", "run"]
