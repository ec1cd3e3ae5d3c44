#!/usr/bin/env python
"""Measured-ceiling campaign driver — thin shim over
``python -m knn_tpu.cli campaign`` (same flags, same exit codes), kept
as a script so a hardware session can run the whole ROADMAP open-item-1
pass with one command from the repo root:

    python scripts/measured_ceiling_campaign.py --round 6
    python scripts/measured_ceiling_campaign.py --rehearse   # CPU proof

Per arm: flip the on-hardware gates, autotune with roofline+VMEM
pruning live, bench with device-trace capture, parse the trace
(knn_tpu.obs.traceread), reconcile measured device time against the
roofline model's terms, persist per-term calibration factors
(knn_tpu.obs.calibrate, ``KNN_TPU_CALIBRATION``), and write one
validated campaign JSONL artifact — which hardware runs also append to
``tpu_bench_lines.jsonl`` for ``refresh_bench_artifacts.py`` to curate
and the sentinel to baseline.  Runbook: docs/PERF.md "Calibration &
measured ceilings"."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from knn_tpu.cli import build_campaign_parser, run_campaign_cmd  # noqa: E402

if __name__ == "__main__":
    _args = build_campaign_parser().parse_args()
    if _args.cpu_devices:
        from knn_tpu.utils.compat import request_cpu_devices

        request_cpu_devices(_args.cpu_devices)
    sys.exit(run_campaign_cmd(_args))
