"""Command-line driver — the real flag system the reference never had
(reconfiguration there = edit constants + recompile, knn_mpi.cpp:108-119,
report PDF p.11 §3.3.1; SURVEY.md §5 calls the CLI the single biggest
usability delta).

Usage mirrors the reference job:

    python -m knn_tpu.cli --train mnist_train.csv --test mnist_test.csv \\
        --val mnist_validation.csv --k 50 --metric l2 --out Test_label.csv

Prints the reference's two lines (``accuracy = ...`` knn_mpi.cpp:348 and
``Running time is ... second`` :398) plus optional structured JSON metrics.

Subcommands ride alongside the job interface:

    python -m knn_tpu.cli join --n 1000000 --rows 65536 --k 10
    python -m knn_tpu.cli join --mode certified --superblock 8192

runs the offline bulk kNN-join (knn_tpu.join): every row of a
host-resident query set against the corpus through the double-buffered
superblock stream (query h2d overlapped under device compute), or the
certified per-superblock loop; prints plan + measured stats (rows/s,
overlap_ratio, superblock/segment/dispatch counts) as one JSON line
(docs/PERF.md "Bulk kNN-join").

    python -m knn_tpu.cli metrics --port 9100
    python -m knn_tpu.cli metrics --snapshot /path/run_metrics.json --format prom

reads the telemetry of a RUNNING process (its ``--metrics-port``
endpoint) or an atomic JSON snapshot file (knn_tpu.obs exporters) and
prints it as Prometheus text or JSON — the scrape/debug companion of
the job flags ``--metrics-port`` / ``--obs-log``
(docs/OBSERVABILITY.md).

    python -m knn_tpu.cli doctor --port 9100
    python -m knn_tpu.cli doctor --snapshot /path/run_metrics.json

renders the health/self-diagnosis report (readiness, device inventory,
engine warmup + queue worker state, SLO breaches, recent alerts) from a RUNNING process's ``/statusz`` endpoint or
offline from an atomic snapshot — the same report either way, jax-free
by construction.  Exit code: 0 healthy, 2 not ready, 1 unreadable
source.

    python -m knn_tpu.cli fleet --members host0:9100,host1:9100
    python -m knn_tpu.cli fleet --snapshot-dir /path/snapshots [--json]

collects every fleet member's telemetry (live ``/metrics.json`` +
``/statusz`` endpoints, or a directory of atomic snapshots plus event
logs) and renders ONE merged cross-host report (knn_tpu.obs.fleet):
counters summed bitwise-deterministically, gauges kept per-host with
min/max/argmax, fleet quantiles taken ONLY from element-wise-summed
histogram buckets (never averaged percentiles), the named straggler
host, fleet SLO verdicts, and the stitched cross-host waterfalls.
Unreachable / torn / stale / catalog-skewed members render loudly as a
partial fleet.  Exit code: 0 healthy, 2 partial or breached, 1
unreadable source (docs/OBSERVABILITY.md "Fleet observability").

    python -m knn_tpu.cli audit --port 9100
    python -m knn_tpu.cli audit --bundle postmortem-....json

renders the quality-observability state (knn_tpu.obs.audit — shadow
audit sampler tallies, last audited recall@k, loud drop counts, drift
sketches) from a running process's ``/statusz``, an atomic snapshot,
or a flight-recorder postmortem bundle whose embedded audit evidence
includes the failing records themselves — jax-free by construction
(docs/OBSERVABILITY.md "Quality observability").  Exit code: 0 clean,
2 deficient or dropped audits on record, 1 unreadable source.

    python -m knn_tpu.cli waterfall --bundle postmortem-....json
    python -m knn_tpu.cli waterfall --log events.jsonl --top 5
    python -m knn_tpu.cli waterfall --port 9100 --trace-id 3fa9c1d2e4b56a78

renders per-request latency **waterfalls** (queue_wait / admission /
dispatch / compile / device / join / deliver segments tiling each
request's measured latency, gaps explicit as ``unattributed``) plus the
aggregated critical-path attribution (which segment dominates at p50 vs
p99, per tenant and per bucket) — from a flight-recorder postmortem
bundle (``KNN_TPU_POSTMORTEM_DIR``), a JSONL event log (the rotated
``.1`` generation is merged automatically), or a running process's
``/waterfallz`` endpoint.  Jax-free by construction
(docs/OBSERVABILITY.md "Waterfalls & exemplars").

    python -m knn_tpu.cli lint [--json] [--checker NAME]

runs the repo-native static-analysis suite (knn_tpu.analysis,
docs/ANALYSIS.md) over the source tree, jax-free: env-switch and
metric-name lockstep, locked-mutation (thread-safety contracts),
jax-hygiene (wall clocks, hot-path host syncs, unhashable static
args), and the default knobs' VMEM budget.  Exit 0 green — with every
suppression in knn_tpu/analysis/suppressions.json carrying a written
justification — 1 findings.  ``check_tier1.sh --fast`` runs it as a
hard gate.

    python -m knn_tpu.cli loadgen --synthetic 500 --slo-p99-ms 20
    python -m knn_tpu.cli loadgen --n 100000 --dim 64 --rates 50,100,200 \\
        --max-depth 64 --shed --deadline-ms 250 --tenants gold:3,free:1

runs the open-loop load harness (knn_tpu.loadgen): a seeded
Poisson/bursty multi-tenant workload stepped through increasing rates
against the synthetic single-server model (jax-free) or a freshly
built serving stack, printing the latency-vs-throughput knee artifact
(rate steps, admitted p50/p95/p99, shed fraction, detected knee q/s)
as one trailing JSON line.  Admission flags
(``--max-depth``/``--shed``/``--quota``) exercise the brownout
controls (docs/serving.md).

    python -m knn_tpu.cli index --port 9100
    python -m knn_tpu.cli index --snapshot run_metrics.json
    python -m knn_tpu.cli index --selftest

renders the mutable-index state (epoch, delta-tail fill, tombstones,
compaction history — knn_tpu.index, docs/INDEX.md) from a live
``/statusz`` or an offline snapshot, jax-free; ``--selftest`` builds a
tiny index live and verifies the insert/delete/compact mutation oracle
bitwise (exit 0 on a match).
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional, Sequence

from knn_tpu.ops.metrics import METRICS  # dependency-free; does not pull JAX
from knn_tpu.utils.config import BACKENDS, CERTIFIED_PRECISIONS, JobConfig


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="knn_tpu",
        description="TPU-native distributed brute-force KNN classifier",
    )
    p.add_argument("--train", required=True, help="labeled train CSV (label,f0,f1,...)")
    p.add_argument("--test", required=True, help="unlabeled test CSV (f0,f1,...)")
    p.add_argument("--val", default=None, help="labeled validation CSV; enables accuracy scoring")
    p.add_argument("--out", default="Test_label.csv", help="predicted-label output path")
    p.add_argument("--k", type=int, default=50, help="neighbor count (ref K, knn_mpi.cpp:109)")
    p.add_argument("--metric", default="l2", choices=sorted(METRICS))
    p.add_argument("--dim", type=int, default=None, help="expected feature dim (validated)")
    p.add_argument("--num-classes", type=int, default=None, help="label count (inferred if omitted)")
    p.add_argument("--no-normalize", action="store_true", help="skip min-max normalization (ref Normalize=false)")
    p.add_argument("--backend", default="jax", choices=BACKENDS)
    p.add_argument("--query-shards", type=int, default=None, help="mesh query-axis size (default: all devices)")
    p.add_argument("--db-shards", type=int, default=1, help="mesh db-axis size (shards the train rows)")
    p.add_argument("--merge", default="allgather", choices=("allgather", "ring"))
    p.add_argument("--train-tile", type=int, default=None, help="HBM tile rows for the streamed distance matrix")
    p.add_argument("--batch-size", type=int, default=None, help="queries per device step")
    p.add_argument("--compute-dtype", default=None, help="matmul dtype, e.g. bfloat16")
    p.add_argument(
        "--mode", default="exact", choices=("exact", "certified"),
        help="certified = fast approximate selection + float64 refinement + "
        "count-below certificate (exact results, l2 or cosine)",
    )
    p.add_argument(
        "--selector", default="approx", choices=("exact", "approx", "pallas"),
        help="local-shard selector for --mode certified",
    )
    p.add_argument(
        "--serve-buckets", default=None, metavar="SPEC",
        help="shape-bucketed serving: 'auto' or a comma list like "
        "'64,128,256' — query chunks pad up a geometric bucket ladder of "
        "precompiled executables (warmup at startup, at most one XLA "
        "compile per bucket for ANY traffic pattern); per-bucket compile "
        "counts and latency percentiles land in the JSON metrics",
    )
    p.add_argument(
        "--max-wait-ms", type=float, default=2.0,
        help="micro-batching deadline for CONCURRENT serving "
        "(knn_tpu.serving.QueryQueue): max time a request waits to be "
        "coalesced into a bigger bucket.  The sequential batch job this "
        "CLI runs has no concurrent callers, so here the value is only "
        "echoed into the serving metrics for downstream queue deployments",
    )
    p.add_argument("--num-threads", type=int, default=0, help="native backend threads (0 = all cores)")
    p.add_argument("--metrics-json", default=None, help="write structured run metrics to this path")
    p.add_argument(
        "--metrics-port", type=int, default=None, metavar="PORT",
        help="serve live telemetry over HTTP while the job runs: "
        "/metrics (Prometheus text) + /metrics.json (knn_tpu.obs; "
        "scrape with `python -m knn_tpu.cli metrics --port PORT`)",
    )
    p.add_argument(
        "--metrics-snapshot", default=None, metavar="PATH",
        help="write an atomic JSON telemetry snapshot (tmp+rename) at "
        "job end — the file-based exporter for runs nothing scrapes "
        "live",
    )
    p.add_argument(
        "--obs-log", default=None, metavar="PATH",
        help="append structured telemetry events (trace spans, compile "
        "events) to this JSONL file ($KNN_TPU_OBS_LOG equivalent)",
    )
    p.add_argument(
        "--cpu-devices",
        type=int,
        default=None,
        metavar="N",
        help="force an N-virtual-device CPU backend (testing without a TPU; "
        "must be set before any other JAX use in the process)",
    )
    p.add_argument(
        "--pallas-precision", default=None,
        choices=CERTIFIED_PRECISIONS,
        help="kernel matmul precision for --mode certified --selector "
        "pallas; 'int8' runs the quantized MXU coarse pass (db quantized "
        "once at placement, certify threshold widened by the provable "
        "per-query bound — results stay exact by construction).  Unset = "
        "the library default",
    )
    return p


def build_join_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="knn_tpu join",
        description="Bulk all-pairs kNN-join (knn_tpu.join): every row "
        "of a host-resident query set joined against the corpus "
        "through the double-buffered superblock stream (mode=stream) "
        "or the exactness-certified per-superblock loop "
        "(mode=certified).  Prints the plan + measured stats as one "
        "JSON line.",
    )
    p.add_argument("--n", type=int, default=100_000, help="corpus rows (B)")
    p.add_argument("--rows", type=int, default=16_384,
                   help="query rows (A) — the join's outer set")
    p.add_argument("--dim", type=int, default=128, help="feature dim")
    p.add_argument("--k", type=int, default=10, help="neighbor count")
    p.add_argument("--metric", default="l2",
                   choices=("l2", "sql2", "euclidean", "cosine", "dot"))
    p.add_argument("--mode", default="stream",
                   choices=("stream", "certified"),
                   help="stream = double-buffered raw top-k; certified "
                   "= search_certified per superblock (exact, slower)")
    p.add_argument("--superblock", type=int, default=None,
                   help="query superblock rows (default: "
                   "KNN_TPU_JOIN_SUPERBLOCK > h2d budget model > 4096)")
    p.add_argument("--depth", type=int, default=None,
                   help="dispatch-ahead depth (default: "
                   "KNN_TPU_JOIN_DEPTH > 2)")
    p.add_argument("--query-budget-bytes", type=int, default=None,
                   help="size superblocks from this h2d staging budget "
                   "(analysis.hbm.plan_superblocks)")
    p.add_argument("--hbm-budget-bytes", type=int, default=None,
                   help="force the host-RAM db tier with this device "
                   "budget (exercises the db-major/query-major sweep "
                   "nesting the byte model picks)")
    p.add_argument("--seed", type=int, default=0, help="synthetic data seed")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="also write the stats record to this path")
    p.add_argument("--cpu-devices", type=int, default=None, metavar="N",
                   help="force an N-virtual-device CPU backend")
    return p


def run_join(args: argparse.Namespace) -> int:
    """The `join` subcommand: synthetic data at the requested shape ->
    knn_tpu.join.knn_join -> one human-readable summary + one JSON
    line (the engine's stats dict: plan vs executed superblock/segment/
    dispatch counts, overlap_ratio, rows/s)."""
    import json

    import numpy as np

    from knn_tpu.join import knn_join
    from knn_tpu.parallel import ShardedKNN
    from knn_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(args.seed)
    db = rng.random(size=(args.n, args.dim)).astype(np.float32)
    qa = rng.random(size=(args.rows, args.dim)).astype(np.float32)
    kw = {}
    if args.hbm_budget_bytes is not None:
        kw["hbm_budget_bytes"] = args.hbm_budget_bytes
    prog = ShardedKNN(db, mesh=make_mesh(), k=args.k, metric=args.metric,
                      **kw)
    _, _, stats = knn_join(
        prog, qa, mode=args.mode, superblock_rows=args.superblock,
        depth=args.depth, query_budget_bytes=args.query_budget_bytes)
    print(f"joined {stats['rows']} x {args.n} rows (k={args.k}, "
          f"{args.metric}, {stats['mode']}): "
          f"{stats['rows_per_s']} rows/s over "
          f"{stats['superblocks']} superblocks x "
          f"{stats['db_segments']} db segments "
          f"({stats['order']}, overlap {stats['overlap_ratio']})")
    print(json.dumps(stats))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(stats, f, indent=2)
    return 0


def build_metrics_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="knn_tpu metrics",
        description="Read telemetry from a running process's "
        "--metrics-port endpoint or from an atomic JSON snapshot file "
        "(knn_tpu.obs) and print it as Prometheus text or JSON.",
    )
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--port", type=int, default=None,
                     help="fetch from http://HOST:PORT (a process "
                     "started with --metrics-port)")
    src.add_argument("--snapshot", default=None, metavar="PATH",
                     help="read an atomic JSON snapshot file "
                     "(--metrics-snapshot / obs.write_json_snapshot)")
    p.add_argument("--host", default="127.0.0.1",
                   help="endpoint host for --port (default localhost)")
    p.add_argument("--format", default="prom", choices=("prom", "json"),
                   help="output format (Prometheus text | snapshot JSON)")
    return p


def run_metrics(args: argparse.Namespace) -> int:
    """The `metrics` subcommand — jax-free by construction (knn_tpu.obs
    imports no JAX): scraping a box must not pay a backend init."""
    import json
    import urllib.request

    if args.port is not None:
        path = "/metrics" if args.format == "prom" else "/metrics.json"
        url = f"http://{args.host}:{args.port}{path}"
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                sys.stdout.write(r.read().decode())
        except OSError as e:
            print(f"metrics endpoint {url} unreachable: {e}",
                  file=sys.stderr)
            return 1
        return 0
    try:
        with open(args.snapshot) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"cannot read snapshot {args.snapshot}: {e}", file=sys.stderr)
        return 1
    if args.format == "json":
        print(json.dumps(payload, indent=1, sort_keys=True))
    else:
        from knn_tpu.obs import prometheus_text

        sys.stdout.write(prometheus_text(payload.get("metrics", {})))
    return 0


def build_doctor_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="knn_tpu doctor",
        description="Render the health/self-diagnosis report "
        "(knn_tpu.obs.health) of a running process (/statusz) or an "
        "atomic JSON snapshot, offline and jax-free.  Exit 0 healthy, "
        "2 not ready, 1 unreadable source.",
    )
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--port", type=int, default=None,
                     help="fetch /statusz from http://HOST:PORT (a "
                     "process started with --metrics-port)")
    src.add_argument("--snapshot", default=None, metavar="PATH",
                     help="read an atomic JSON snapshot file "
                     "(--metrics-snapshot / obs.write_json_snapshot)")
    p.add_argument("--host", default="127.0.0.1",
                   help="endpoint host for --port (default localhost)")
    p.add_argument("--json", action="store_true",
                   help="print the raw report JSON instead of the "
                   "human-readable rendering")
    return p


def run_doctor(args: argparse.Namespace) -> int:
    """The `doctor` subcommand — jax-free (knn_tpu.obs imports no JAX):
    diagnosing a box must not pay a backend init."""
    import json
    import urllib.request

    from knn_tpu.obs import health

    if args.port is not None:
        url = f"http://{args.host}:{args.port}/statusz"
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                report = json.loads(r.read().decode())
        except (OSError, json.JSONDecodeError) as e:
            print(f"statusz endpoint {url} unreachable: {e}",
                  file=sys.stderr)
            return 1
    else:
        try:
            with open(args.snapshot) as f:
                payload = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"cannot read snapshot {args.snapshot}: {e}",
                  file=sys.stderr)
            return 1
        report = health.report_from_snapshot(payload)
    if args.json:
        print(json.dumps(report, indent=1, sort_keys=True, default=str))
    else:
        sys.stdout.write(health.render_text(report))
    return 0 if report.get("readiness", {}).get("ready") else 2


def build_fleet_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="knn_tpu fleet",
        description="Collect every fleet member's telemetry and render "
        "ONE merged cross-host report (knn_tpu.obs.fleet): counters "
        "summed, gauges kept per-host with min/max/argmax, quantiles "
        "from element-wise-summed histogram buckets (never averaged "
        "percentiles), the named straggler host, and stitched "
        "cross-host waterfalls.  Exit 0 healthy, 2 partial fleet / "
        "nothing merged / fleet SLO breached, 1 unreadable source.",
    )
    p.add_argument("--members", default=None, metavar="HOST:PORT,...",
                   help="comma/space-separated live member endpoints "
                   "(default: KNN_TPU_FLEET_MEMBERS)")
    p.add_argument("--snapshot-dir", default=None, metavar="DIR",
                   help="merge offline from a directory of atomic JSON "
                   "snapshots (*.json) + optional event logs (*.jsonl, "
                   "stitched into cross-host waterfalls)")
    p.add_argument("--snapshot", action="append", default=None,
                   metavar="PATH",
                   help="merge offline from explicit snapshot files "
                   "(repeatable)")
    p.add_argument("--stale-s", type=float, default=None,
                   help="refuse members older than the newest by more "
                   "than this many seconds (default: "
                   "KNN_TPU_FLEET_STALE_S or %s)"
                   % "120")
    p.add_argument("--timeout", type=float, default=3.0,
                   help="per-member HTTP timeout for live collection")
    p.add_argument("--json", action="store_true",
                   help="print the raw merged report JSON instead of "
                   "the human-readable rendering")
    return p


def run_fleet(args: argparse.Namespace) -> int:
    """The `fleet` subcommand — jax-free (knn_tpu.obs imports no JAX):
    merging a fleet's telemetry must not pay a backend init."""
    import json
    import os

    from knn_tpu.obs import fleet

    members = None
    if args.members:
        import re as _re

        members = [m for m in _re.split(r"[,\s]+", args.members) if m]
    if args.snapshot_dir is not None and not os.path.isdir(
            args.snapshot_dir):
        print(f"cannot read snapshot dir {args.snapshot_dir}: "
              f"not a directory", file=sys.stderr)
        return 1
    if members is None and args.snapshot_dir is None \
            and args.snapshot is None and not fleet.fleet_members():
        print("no fleet source: pass --members/--snapshot-dir/--snapshot "
              f"or set {fleet.MEMBERS_ENV}", file=sys.stderr)
        return 1
    try:
        report = fleet.fleet_report(
            members, snapshot_dir=args.snapshot_dir,
            snapshot_files=args.snapshot, timeout_s=args.timeout,
            stale_s=args.stale_s)
    except OSError as e:
        print(f"fleet collection failed: {e}", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(report, indent=1, sort_keys=True, default=str))
    else:
        print(fleet.render_text(report))
    if not report.get("enabled", True):
        return 2
    unhealthy = (report["partial"] or report["member_count"] == 0
                 or bool((report.get("slo") or {}).get("breached")))
    return 2 if unhealthy else 0


def build_audit_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="knn_tpu audit",
        description="Render the quality-observability state "
        "(knn_tpu.obs.audit): the shadow audit sampler's sampled/"
        "replayed/deficient/dropped tallies and drift sketches from a "
        "running process's /statusz, an atomic JSON snapshot, or a "
        "flight-recorder postmortem bundle's embedded audit evidence "
        "— offline and jax-free.  Exit 0 clean, 2 deficient or "
        "dropped audits on record, 1 unreadable source.",
    )
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--port", type=int, default=None,
                     help="fetch /statusz from http://HOST:PORT (a "
                     "process started with --metrics-port)")
    src.add_argument("--snapshot", default=None, metavar="PATH",
                     help="read an atomic JSON snapshot file "
                     "(--metrics-snapshot / obs.write_json_snapshot)")
    src.add_argument("--bundle", default=None, metavar="PATH",
                     help="read a flight-recorder postmortem bundle "
                     "(KNN_TPU_POSTMORTEM_DIR) and render its embedded "
                     "audit evidence, failing records included")
    p.add_argument("--host", default="127.0.0.1",
                   help="endpoint host for --port (default localhost)")
    p.add_argument("--json", action="store_true",
                   help="print the raw quality JSON instead of the "
                   "human-readable rendering")
    return p


def run_audit(args: argparse.Namespace) -> int:
    """The `audit` subcommand — jax-free (knn_tpu.obs imports no JAX):
    judging a box's served quality must not pay a backend init."""
    import json
    import urllib.request

    failures: list = []
    if args.port is not None:
        url = f"http://{args.host}:{args.port}/statusz"
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                report = json.loads(r.read().decode())
        except (OSError, json.JSONDecodeError) as e:
            print(f"statusz endpoint {url} unreachable: {e}",
                  file=sys.stderr)
            return 1
        quality = report.get("quality") or {}
    elif args.snapshot is not None:
        from knn_tpu.obs import health

        try:
            with open(args.snapshot) as f:
                payload = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"cannot read snapshot {args.snapshot}: {e}",
                  file=sys.stderr)
            return 1
        quality = health.report_from_snapshot(payload).get("quality") or {}
    else:
        from knn_tpu.obs import blackbox

        try:
            payload = blackbox.read_bundle(args.bundle)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"cannot read bundle {args.bundle}: {e}",
                  file=sys.stderr)
            return 1
        audit_sec = payload.get("audit") or {}
        quality = audit_sec.get("summary") or {}
        failures = audit_sec.get("failures") or []
    if args.json:
        print(json.dumps({"quality": quality, "failures": failures},
                         indent=1, sort_keys=True, default=str))
    else:
        if not quality:
            print("audit: no quality section on record "
                  "(sampler never armed, or pre-quality source)")
        else:
            print(f"audit: rate={quality.get('rate')} "
                  f"budget_rows_s={quality.get('budget_rows_s')}")
            print(f"  sampled={quality.get('sampled_requests')} "
                  f"replayed={quality.get('replayed_queries')}q "
                  f"deficient={quality.get('deficient_queries')} "
                  f"rows_scored={quality.get('rows_scored')} "
                  f"last_recall@k={quality.get('last_recall_at_k')}")
            dropped = quality.get("dropped") or {}
            if dropped:
                drops = " ".join(f"{r}={c}"
                                 for r, c in sorted(dropped.items()))
                print(f"  dropped: {drops}")
            for i, dr in enumerate(quality.get("drift") or []):
                print(f"  drift[{i}]: "
                      f"queries={dr.get('queries_observed')} "
                      f"norm_psi={dr.get('norm_psi')} "
                      f"assign_psi={dr.get('centroid_assign_psi')}")
        if failures:
            print(f"failing audit record(s) ({len(failures)}):")
            for f_rec in failures:
                if "error" in f_rec:
                    print(f"  {f_rec.get('trace_id')} "
                          f"tenant={f_rec.get('tenant')} "
                          f"error={f_rec['error']}")
                    continue
                print(f"  {f_rec.get('trace_id')} "
                      f"tenant={f_rec.get('tenant')} "
                      f"epoch={f_rec.get('epoch')} "
                      f"deficient={f_rec.get('deficient_queries')} "
                      f"max_displacement="
                      f"{f_rec.get('max_rank_displacement')}")
                print(f"    recall@k={f_rec.get('recall_at_k')}")
                print(f"    worst q{f_rec.get('worst_query')}: "
                      f"served={f_rec.get('worst_served_ids')} "
                      f"oracle={f_rec.get('worst_oracle_ids')}")
    deficient = int(quality.get("deficient_queries") or 0)
    dropped_n = sum((quality.get("dropped") or {}).values())
    return 2 if (deficient or dropped_n or failures) else 0


def build_waterfall_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="knn_tpu waterfall",
        description="Render per-request latency waterfalls and the "
        "aggregated critical-path attribution (knn_tpu.obs.waterfall) "
        "from a flight-recorder postmortem bundle, a JSONL event log "
        "(KNN_TPU_OBS_LOG; the rotated .1 generation is merged), or a "
        "running process's /waterfallz endpoint — offline and "
        "jax-free.  Exit 0 rendered, 1 unreadable source.",
    )
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--bundle", default=None, metavar="PATH",
                     help="read a postmortem bundle written by the "
                     "flight recorder (KNN_TPU_POSTMORTEM_DIR)")
    src.add_argument("--log", default=None, metavar="PATH",
                     help="read a JSONL event log (KNN_TPU_OBS_LOG / "
                     "--obs-log); <PATH>.1 is merged when present")
    src.add_argument("--port", type=int, default=None,
                     help="fetch /waterfallz from http://HOST:PORT (a "
                     "process started with --metrics-port)")
    p.add_argument("--host", default="127.0.0.1",
                   help="endpoint host for --port (default localhost)")
    p.add_argument("--trace-id", action="append", default=[],
                   metavar="ID", help="render only these request ids "
                   "(repeatable; default: the --top slowest)")
    p.add_argument("--top", type=int, default=8,
                   help="how many waterfalls to render, slowest first")
    p.add_argument("--json", action="store_true",
                   help="print the raw forensics payload JSON instead "
                   "of the rendering")
    return p


def run_waterfall(args: argparse.Namespace) -> int:
    """The `waterfall` subcommand — jax-free (knn_tpu.obs imports no
    JAX): tail forensics must not pay a backend init."""
    import json
    import urllib.request

    from knn_tpu.obs import waterfall

    if args.port is not None:
        url = f"http://{args.host}:{args.port}/waterfallz"
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                payload = json.loads(r.read().decode())
        except (OSError, json.JSONDecodeError) as e:
            print(f"waterfallz endpoint {url} unreachable: {e}",
                  file=sys.stderr)
            return 1
        wfs = payload.get("waterfalls") or {}
        agg = payload.get("attribution") or waterfall.attribute(wfs)
    elif args.bundle is not None:
        from knn_tpu.obs import blackbox

        try:
            payload = blackbox.read_bundle(args.bundle)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"cannot read bundle {args.bundle}: {e}",
                  file=sys.stderr)
            return 1
        # the bundle embeds the raw event ring — reconstruct from it so
        # offline rendering uses the same code path as live
        wfs = waterfall.reconstruct(payload.get("events") or [])
        agg = payload.get("attribution") or waterfall.attribute(wfs)
        if not args.json:
            # header stays off the --json stdout: that output must
            # parse as one JSON document
            print(f"postmortem bundle: "
                  f"objective={payload.get('objective')} "
                  f"state={payload.get('state')} "
                  f"written_at={payload.get('written_at')} "
                  f"pid={payload.get('pid')}")
    else:
        try:
            events = waterfall.read_jsonl_events(args.log)
        except (OSError, ValueError) as e:
            print(f"cannot read event log {args.log}: {e}",
                  file=sys.stderr)
            return 1
        wfs = waterfall.reconstruct(events)
        agg = waterfall.attribute(wfs)
        payload = {"waterfalls": wfs, "attribution": agg}
    if args.json:
        print(json.dumps(payload, indent=1, sort_keys=True, default=str))
        return 0
    if args.trace_id:
        picked = [wfs[t] for t in args.trace_id if t in wfs]
        missing = [t for t in args.trace_id if t not in wfs]
        for t in missing:
            print(f"trace id {t}: no reconstructable request in this "
                  f"source", file=sys.stderr)
    else:
        picked = sorted(wfs.values(),
                        key=lambda w: -(w.get("total_s") or 0.0))
        picked = picked[: max(0, args.top)]
    print(waterfall.render_attribution(agg))
    for w in picked:
        print(waterfall.render_waterfall(w))
    if not picked:
        print("no reconstructable requests in this source",
              file=sys.stderr)
    return 0


def build_loadgen_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="knn_tpu loadgen",
        description="Open-loop load generation + knee sweep "
        "(knn_tpu.loadgen): drive a serving target with a seeded "
        "Poisson/bursty/replayed multi-tenant workload through a "
        "stepped-rate sweep, and print the latency-vs-throughput knee "
        "artifact (rate steps, admitted p50/p95/p99, shed fraction, "
        "detected knee q/s) as one trailing JSON line.  "
        "--synthetic CAPACITY runs against the built-in single-server "
        "model (jax-free — validates the harness and admission policy "
        "without hardware); otherwise a synthetic-data ShardedKNN + "
        "ServingEngine + QueryQueue is built at --n/--dim/--k.  "
        "Admission control: --max-depth/--shed/--quota/--deadline-ms "
        "(or the KNN_TPU_ADMISSION_* env knobs).")
    p.add_argument("--synthetic", type=float, default=None,
                   metavar="QPS", help="drive the jax-free synthetic "
                   "target with this service capacity instead of a "
                   "real engine")
    p.add_argument("--n", type=int, default=100_000, help="database rows")
    p.add_argument("--dim", type=int, default=64, help="feature dim")
    p.add_argument("--k", type=int, default=10, help="neighbor count")
    p.add_argument("--metric", default="l2",
                   choices=("l2", "sql2", "euclidean", "cosine"))
    p.add_argument("--rates", default=None, metavar="R1,R2,...",
                   help="offered request rates (q/s) to step through; "
                   "unset = a ladder bracketing a measured closed-loop "
                   "anchor (real target) or the synthetic capacity")
    p.add_argument("--duration", type=float, default=1.0, metavar="S",
                   help="seconds per rate step")
    p.add_argument("--slo-p99-ms", type=float, default=100.0,
                   help="admitted-request p99 bound defining the knee")
    p.add_argument("--tenants", default="default:1",
                   help="tenant mix: name[:weight[:priority]],...")
    p.add_argument("--batch-sizes", default="1,2,4,8",
                   help="request row counts, drawn uniformly per request")
    p.add_argument("--arrival", default="poisson",
                   choices=("poisson", "onoff"),
                   help="arrival process (bursty on/off via --on-s/"
                   "--off-s/--burst)")
    p.add_argument("--on-s", type=float, default=0.25)
    p.add_argument("--off-s", type=float, default=0.25)
    p.add_argument("--burst", type=float, default=4.0,
                   help="on-phase rate multiplier for --arrival onoff")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="per-request deadline applied to every tenant; "
                   "implies deadline-aware shedding (--shed), so the "
                   "deadlines are enforced, not just recorded")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="micro-batching deadline of the driven queue")
    p.add_argument("--max-depth", type=int, default=None,
                   help="admission: bounded queue depth (explicit "
                   "rejection past it)")
    p.add_argument("--shed", action="store_true",
                   help="admission: deadline-aware load shedding")
    p.add_argument("--quota", action="append", default=[],
                   metavar="TENANT:RATE[:BURST]",
                   help="admission: per-tenant token-bucket quota "
                   "(repeatable)")
    p.add_argument("--replay", default=None, metavar="PATH",
                   help="replay a recorded JSONL trace instead of "
                   "generating arrivals (single run, no sweep)")
    p.add_argument("--save-trace", default=None, metavar="PATH",
                   help="record the generated schedule (first rate "
                   "step) to this JSONL file for later --replay")
    p.add_argument("--json", action="store_true",
                   help="print the raw artifact JSON only")
    p.add_argument("--cpu-devices", type=int, default=None, metavar="N",
                   help="force an N-virtual-device CPU backend")
    return p


def run_loadgen(args: argparse.Namespace) -> int:
    """The `loadgen` subcommand: a knee sweep (or single replay run)
    against the synthetic model or a freshly built serving stack,
    printing a human summary plus ONE trailing JSON line (the knee
    artifact)."""
    import json

    import numpy as np

    from knn_tpu import loadgen
    from knn_tpu.serving.admission import AdmissionConfig

    tenants = tuple(
        loadgen.TenantSpec(
            t.name, weight=t.weight, priority=t.priority,
            batch_sizes=tuple(int(b) for b in
                              args.batch_sizes.split(",") if b.strip()),
            deadline_ms=args.deadline_ms)
        for t in loadgen.parse_tenants(args.tenants))
    from knn_tpu.serving.admission import parse_quotas

    try:
        quotas = parse_quotas(",".join(args.quota))
    except ValueError as e:
        print(f"--quota: {e}", file=sys.stderr)
        return 1
    # only NONZERO tenant levels become a priority table — an
    # all-zero dict would defeat the queue's FIFO fast path and
    # spuriously trip the synthetic-limitations warning below
    priorities = {t.name: t.priority for t in tenants if t.priority}
    if (args.max_depth is not None or args.shed or quotas or priorities
            or args.deadline_ms is not None):
        # any of these flags (nonzero tenant levels included —
        # priorities only reorder through an admission-enabled queue)
        # opts into admission.  --deadline-ms implies shedding:
        # attaching deadlines nobody enforces would silently report
        # shed=0 as "all deadlines met"
        admission = AdmissionConfig(
            max_depth=args.max_depth,
            shed=args.shed or args.deadline_ms is not None,
            quotas=quotas, priorities=priorities)
    else:
        admission = AdmissionConfig.from_env()

    # parse --rates up front so the anchor-probe gate and the ladder
    # fallback judge the SAME thing (the PARSED list: '--rates ,' is a
    # truthy string but an empty ladder)
    rates_given = ([float(r) for r in args.rates.split(",") if r.strip()]
                   if args.rates else None) or None

    dim = args.dim
    if args.synthetic is not None:
        if admission is not None and (admission.quotas
                                      or admission.priorities):
            # the single-server model can mimic depth/shed only; a
            # silent no-op would read as "quotas do nothing"
            print("warning: --synthetic models max-depth and deadline "
                  "shedding only — quotas and priorities are ignored "
                  "(use a real engine to exercise them)",
                  file=sys.stderr)

        def make_target():
            return loadgen.SyntheticTarget(
                args.synthetic,
                max_depth=None if admission is None
                else admission.max_depth,
                shed_deadlines=admission.shed if admission else False)
        anchor = args.synthetic
        pool = np.zeros((max(64, *(max(t.batch_sizes) for t in tenants)),
                         dim), np.float32)
    else:
        from knn_tpu.parallel.mesh import make_mesh
        from knn_tpu.parallel.sharded import ShardedKNN
        from knn_tpu.serving.engine import ServingEngine
        from knn_tpu.serving.queue import QueryQueue

        rng = np.random.default_rng(args.seed)
        db = (rng.random((args.n, dim)) * 128.0).astype(np.float32)
        pool = (rng.random((4096, dim)) * 128.0).astype(np.float32)
        prog = ShardedKNN(db, mesh=make_mesh(), k=args.k,
                          metric=args.metric)
        engine = ServingEngine(prog)
        print("warming serving engine ...", file=sys.stderr)
        engine.warmup()

        def make_target():
            return QueryQueue(engine, max_wait_ms=args.max_wait_ms,
                              admission=admission)

        anchor = None
        if rates_given is None and not args.replay:
            # closed-loop anchor probe through an ADMISSION-FREE
            # queue, only when the rate ladder actually needs it
            # (the burst would trip a tight --max-depth, and explicit
            # --rates/--replay would discard the result)
            with QueryQueue(engine, max_wait_ms=args.max_wait_ms) as q0:
                anchor = loadgen.closed_loop_anchor(q0, pool)

    base = loadgen.WorkloadSpec(
        rate_qps=1.0, duration_s=args.duration, seed=args.seed,
        arrival=args.arrival, tenants=tenants, on_s=args.on_s,
        off_s=args.off_s, burst=args.burst)
    if args.replay:
        reqs = loadgen.load_trace(args.replay)
        target = make_target()
        try:
            rep = loadgen.run_workload(target, reqs, queries=pool)
        finally:
            close = getattr(target, "close", None)
            if callable(close):
                close()
        if not args.json:
            lat = rep.get("latency_ms") or {}
            print(f"replayed {rep['offered']} requests: ok={rep['ok']} "
                  f"rejected={rep['rejected']} shed={rep['shed']} "
                  f"p99={lat.get('p99')} ms "
                  f"achieved={rep['achieved_qps']} q/s")
        print(json.dumps(rep))
        return 0
    rates = rates_given or loadgen.rates_around(anchor)
    if args.save_trace:
        loadgen.save_trace(loadgen.generate(base.at_rate(rates[0])),
                           args.save_trace)
        print(f"trace saved: {args.save_trace}", file=sys.stderr)
    block = loadgen.knee_sweep(make_target, base, rates, queries=pool,
                               slo_p99_ms=args.slo_p99_ms)
    if not args.json:
        for s in block["rate_steps"]:
            print(f"rate {s['rate_qps']:>9.2f} q/s: ok={s['ok']:>5} "
                  f"rejected={s['rejected']:>4} shed={s['shed']:>4} "
                  f"p99={s['admitted_p99_ms']} ms "
                  f"achieved={s['achieved_qps']} q/s "
                  f"{'WITHIN' if s['within_slo'] else 'OVER'} SLO")
        print(f"knee: {block['knee_qps']} q/s sustained "
              f"(offered {block['knee_rate_qps']} q/s) at p99 <= "
              f"{block['slo_p99_ms']} ms")
    print(json.dumps(block))
    return 0


def build_index_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="knn_tpu index",
        description="Mutable-index introspection and self-test "
        "(knn_tpu.index, docs/INDEX.md).  --port/--snapshot render "
        "the registered indexes' epoch/tail/tombstone/compaction "
        "state from a live /statusz or an offline snapshot, jax-free "
        "(exit 0 when every index reports, 2 when none is registered, "
        "1 unreachable source).  --selftest builds a tiny synthetic "
        "MutableIndex, runs an insert/delete/compact cycle, and "
        "verifies the mutation oracle (search_certified bitwise vs a "
        "fresh index of the surviving rows) live — exit 0 on a "
        "bitwise match.")
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--port", type=int, default=None,
                     help="fetch /statusz from http://HOST:PORT")
    src.add_argument("--snapshot", default=None, metavar="PATH",
                     help="read an atomic JSON snapshot file")
    src.add_argument("--selftest", action="store_true",
                     help="run the live insert/delete/compact oracle "
                     "check (imports JAX)")
    p.add_argument("--host", default="127.0.0.1",
                   help="endpoint host for --port (default localhost)")
    p.add_argument("--json", action="store_true",
                   help="print raw JSON instead of the rendering")
    return p


def run_index(args: argparse.Namespace) -> int:
    """The `index` subcommand: jax-free status render, or the live
    self-test (the one mode that imports JAX)."""
    import json

    if args.selftest:
        return _run_index_selftest(args)
    import urllib.request

    from knn_tpu.obs import health

    if args.port is not None:
        url = f"http://{args.host}:{args.port}/statusz"
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                report = json.loads(r.read().decode())
        except (OSError, json.JSONDecodeError) as e:
            print(f"statusz endpoint {url} unreachable: {e}",
                  file=sys.stderr)
            return 1
    else:
        try:
            with open(args.snapshot) as f:
                payload = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"cannot read snapshot {args.snapshot}: {e}",
                  file=sys.stderr)
            return 1
        report = health.report_from_snapshot(payload)
    section = report.get("index") or []
    if args.json:
        print(json.dumps(section, indent=1, sort_keys=True,
                         default=str))
    else:
        if not section:
            print("no mutable index registered in this process")
        for line in health.render_text(report).splitlines():
            if line.startswith("index["):
                print(line)
    return 0 if section else 2


def _run_index_selftest(args: argparse.Namespace) -> int:
    import json

    import numpy as np

    from knn_tpu.index.mutable import MutableIndex
    from knn_tpu.parallel.mesh import make_mesh
    from knn_tpu.utils.compat import request_cpu_devices

    request_cpu_devices(8)
    rng = np.random.default_rng(0)
    db = rng.normal(size=(600, 16)).astype(np.float32) * 10
    q = rng.normal(size=(8, 16)).astype(np.float32) * 10
    mesh = make_mesh()
    idx = MutableIndex(db, mesh=mesh, k=5, reserve=8)
    idx.insert(rng.normal(size=(6, 16)).astype(np.float32) * 10,
               np.arange(1000, 1006))
    idx.delete([3, 11, 40])
    d_m, i_m, _ = idx.search_certified(q)
    surv = np.ones(600, bool)
    surv[[3, 11, 40]] = False
    rows = np.concatenate([db[surv], idx._snapshot().tail])
    ids = np.concatenate([np.arange(600)[surv],
                          np.arange(1000, 1006)])
    fresh = MutableIndex(rows, ids, mesh=mesh, k=5, reserve=8)
    d_f, i_f, _ = fresh.search_certified(q)
    oracle_ok = bool(np.array_equal(d_m, d_f)
                     and np.array_equal(i_m, i_f))
    rep = idx.compact()
    d_c, i_c, _ = idx.search_certified(q)
    compact_ok = bool(np.array_equal(d_c, d_f)
                      and np.array_equal(i_c, i_f))
    out = {"ok": oracle_ok and compact_ok,
           "oracle_bitwise": oracle_ok,
           "post_compact_bitwise": compact_ok,
           "compaction": rep, "stats": idx.stats()}
    print(json.dumps(out, sort_keys=True, default=str))
    return 0 if out["ok"] else 1


def build_lint_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="knn_tpu lint",
        description="Run the repo-native static-analysis suite "
        "(knn_tpu.analysis — docs/ANALYSIS.md): switch/metric/artifact "
        "lockstep, locked-mutation, jax-hygiene, and VMEM-budget "
        "checkers over the source tree, jax-free.  Exit 0 green (every "
        "suppression justified), 1 findings (or a broken/stale "
        "suppression file).",
    )
    p.add_argument("--root", default=None, metavar="DIR",
                   help="tree to lint (default: the repo this package "
                   "is imported from); a root carrying its own "
                   "switch/metric catalogs is judged against those "
                   "(vmem-budget always prices the imported package's "
                   "default knobs)")
    p.add_argument("--checker", action="append", default=None,
                   metavar="NAME",
                   help="run only this checker (repeatable; default "
                   "all; see --list)")
    p.add_argument("--list", action="store_true",
                   help="list the registered checkers and exit")
    p.add_argument("--json", action="store_true",
                   help="print the full report as ONE JSON document "
                   "instead of the text rendering")
    return p


def run_lint(args: argparse.Namespace) -> int:
    """The `lint` subcommand — jax-free by construction (knn_tpu.analysis
    parses source with stdlib ``ast``; it never imports the code it
    inspects, only the jax-free declaration catalogs): the CI tripwire
    must not pay a backend init."""
    import json
    import os

    from knn_tpu import analysis

    if args.list:
        for name, (_fn, desc) in analysis.CHECKERS.items():
            print(f"{name:<16} {desc}")
        return 0
    root = args.root
    if root is None:
        # knn_tpu/cli.py -> knn_tpu/ -> the repo root
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        report = analysis.run(root, names=args.checker)
    except ValueError as e:  # unknown --checker name
        print(f"lint: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(report.as_dict(), indent=1, sort_keys=True))
    else:
        sys.stdout.write(report.render_text())
    return 0 if report.ok else 1


def args_to_config(args: argparse.Namespace) -> JobConfig:
    return JobConfig(
        train_file=args.train,
        test_file=args.test,
        val_file=args.val,
        output_file=args.out,
        dim=args.dim,
        k=args.k,
        num_classes=args.num_classes,
        metric=args.metric,
        normalize=not args.no_normalize,
        validation=args.val is not None,
        backend=args.backend,
        query_shards=args.query_shards,
        db_shards=args.db_shards,
        merge=args.merge,
        train_tile=args.train_tile,
        batch_size=args.batch_size,
        compute_dtype=args.compute_dtype,
        mode=args.mode,
        selector=args.selector,
        serve_buckets=args.serve_buckets,
        max_wait_ms=args.max_wait_ms,
        num_threads=args.num_threads,
        pallas_precision=args.pallas_precision,
    )


def _configure_backend(cpu_devices: Optional[int]) -> None:
    """What every device-using subcommand does after parsing and before
    its first compile (both must precede backend initialization): the
    optional virtual-CPU backend, and the persistent compile cache."""
    from knn_tpu.utils.compat import (
        enable_compile_cache,
        request_cpu_devices,
    )

    if cpu_devices:
        request_cpu_devices(cpu_devices)
    enable_compile_cache()


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # subcommand dispatch by leading token: the legacy flat job
    # interface (required --train/--test) stays byte-compatible for
    # every existing caller, and each subcommand gets its own parser
    if argv[:1] == ["join"]:
        jargs = build_join_parser().parse_args(argv[1:])
        _configure_backend(jargs.cpu_devices)
        return run_join(jargs)
    if argv[:1] == ["lint"]:
        return run_lint(build_lint_parser().parse_args(argv[1:]))
    if argv[:1] == ["metrics"]:
        return run_metrics(build_metrics_parser().parse_args(argv[1:]))
    if argv[:1] == ["doctor"]:
        return run_doctor(build_doctor_parser().parse_args(argv[1:]))
    if argv[:1] == ["fleet"]:
        return run_fleet(build_fleet_parser().parse_args(argv[1:]))
    if argv[:1] == ["audit"]:
        return run_audit(build_audit_parser().parse_args(argv[1:]))
    if argv[:1] == ["index"]:
        return run_index(build_index_parser().parse_args(argv[1:]))
    if argv[:1] == ["waterfall"]:
        return run_waterfall(build_waterfall_parser().parse_args(argv[1:]))
    if argv[:1] == ["loadgen"]:
        largs = build_loadgen_parser().parse_args(argv[1:])
        _configure_backend(largs.cpu_devices)
        return run_loadgen(largs)
    args = build_parser().parse_args(argv)
    _configure_backend(args.cpu_devices)
    server = None
    if args.obs_log or args.metrics_port is not None \
            or args.metrics_snapshot:
        from knn_tpu import obs

        if not obs.enabled():
            # the flags are an explicit telemetry request; a silent
            # empty log/endpoint would read as a collection bug
            print("warning: KNN_TPU_OBS=0 disables telemetry — "
                  "--obs-log/--metrics-port/--metrics-snapshot will "
                  "produce empty output", file=sys.stderr)
        if args.obs_log:
            obs.reset_event_log(args.obs_log)
        if args.metrics_port is not None:
            server = obs.start_metrics_server(args.metrics_port)
            port = server.server_address[1]  # resolved when PORT was 0
            print(f"metrics: http://127.0.0.1:{port}/metrics")
    from knn_tpu.pipeline import run_job  # deferred: JAX import is heavy

    try:
        result = run_job(args_to_config(args))
    finally:
        if server is not None:
            server.shutdown()
    if result.val_accuracy is not None:
        print(f"accuracy = {result.val_accuracy}")  # knn_mpi.cpp:348
    print(f"Running time is {result.total_time} second")  # knn_mpi.cpp:398
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            f.write(result.metrics_json())
    if args.metrics_snapshot:
        from knn_tpu import obs

        obs.write_json_snapshot(args.metrics_snapshot)
    return 0


if __name__ == "__main__":
    sys.exit(main())
