"""Exporters: Prometheus text format, atomic JSON snapshots, and a
stdlib-HTTP ``/metrics`` endpoint.

Rendering rules (one source: :func:`prometheus_text` over
``registry.snapshot()``):

- counters/gauges render as ``name{labels} value``;
- histograms render as Prometheus **summaries** — ``name{quantile="..."}``
  lines from the bounded-window percentiles plus lifetime ``_sum`` and
  ``_count`` (the window feeds quantiles, the lifetime pair feeds rate
  math, so a scraper gets both truths).

The HTTP server is intentionally boring: ``http.server`` threading
daemon, ``/metrics`` (text format) + ``/metrics.json`` (the snapshot),
no deps, no auth — bind it to localhost and let the scraper's side
handle the rest.  The JSON snapshot writer is atomic (tmp + rename)
so a scraper of the file never reads a torn write.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Optional

from knn_tpu.obs import ident, registry

#: summary quantiles exported from the histogram window
_QUANTILES = (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99"))


def _esc(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _labels_str(labels: dict, extra: Optional[tuple] = None) -> str:
    items = sorted(labels.items())
    if extra is not None:
        items = items + [extra]
    if not items:
        return ""
    return "{" + ",".join(f'{k}="{_esc(str(v))}"' for k, v in items) + "}"


def prometheus_text(snapshot: Optional[dict] = None) -> str:
    """The whole registry in Prometheus text exposition format."""
    snap = registry.snapshot() if snapshot is None else snapshot
    lines = []
    for name in sorted(snap):
        m = snap[name]
        kind = m["type"]
        prom_kind = "summary" if kind == "histogram" else kind
        lines.append(f"# HELP {name} {m['help']}")
        lines.append(f"# TYPE {name} {prom_kind}")
        for s in m["series"]:
            ls, v = s["labels"], s["value"]
            if kind == "histogram":
                for q, key in _QUANTILES:
                    if key in v:
                        lines.append(
                            f"{name}{_labels_str(ls, ('quantile', q))} "
                            f"{v[key]}")
                if v.get("exemplars"):
                    # the worst retained sample's trace id, value, and
                    # wall timestamp in OpenMetrics exemplar syntax —
                    # but on a COMMENT line: neither exposition format
                    # allows inline exemplars on summary quantiles, and
                    # a text-0.0.4 scraper must keep parsing (comments
                    # other than HELP/TYPE are ignored)
                    ex = v["exemplars"][0]
                    lines.append(
                        f"# EXEMPLAR "
                        f"{name}{_labels_str(ls, ('quantile', '0.99'))} "
                        f'{{trace_id="{_esc(str(ex["trace_id"]))}"}} '
                        f'{ex["value"]} {ex["ts"]}')
                if v.get("buckets"):
                    # the mergeable form: cumulative counts over the
                    # fixed registry.BUCKET_BOUNDS grid, classic
                    # ``_bucket{le=...}`` lines — identical bounds in
                    # every process is what lets the fleet aggregator
                    # add them and take quantiles of the SUM
                    cum = v["buckets"]
                    for b, c in zip(registry.BUCKET_BOUNDS, cum):
                        lines.append(
                            f"{name}_bucket"
                            f"{_labels_str(ls, ('le', format(b, '.6g')))} "
                            f"{c}")
                    lines.append(
                        f"{name}_bucket{_labels_str(ls, ('le', '+Inf'))} "
                        f"{cum[-1]}")
                lines.append(f"{name}_sum{_labels_str(ls)} {v['sum']}")
                lines.append(f"{name}_count{_labels_str(ls)} {v['count']}")
            else:
                lines.append(f"{name}{_labels_str(ls)} {v}")
    return "\n".join(lines) + "\n"


def compact_snapshot(snapshot: Optional[dict] = None) -> dict:
    """The snapshot flattened for embedding (JobResult.metrics()["obs"]):
    ``{name: value}`` for unlabeled series, ``{name:
    {"k=v,...": value}}`` for labeled ones; histograms keep their
    summary dict."""
    snap = registry.snapshot() if snapshot is None else snapshot
    out: dict = {}
    for name, m in snap.items():
        series = m["series"]
        if len(series) == 1 and not series[0]["labels"]:
            out[name] = series[0]["value"]
        else:
            out[name] = {
                ",".join(f"{k}={v}" for k, v in sorted(s["labels"].items())):
                    s["value"]
                for s in series
            }
    return out


def write_json_snapshot(path: str, snapshot: Optional[dict] = None) -> dict:
    """Atomic JSON snapshot (tmp + rename): a scraper of the file can
    never observe a torn write.  Returns the written payload.  Embeds
    the health/self-diagnosis report, so ``knn_tpu.cli doctor
    --snapshot`` renders offline exactly what ``/statusz`` served
    live."""
    from knn_tpu.obs import health

    payload = {
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "written_at_unix": round(time.time(), 3),
        "pid": os.getpid(),
        "identity": ident.identity(),
        "enabled": registry.enabled(),
        "metrics": registry.snapshot() if snapshot is None else snapshot,
        "health": health.report(),
    }
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return payload


def start_metrics_server(port: int, host: str = "127.0.0.1"):
    """Serve ``/metrics`` (Prometheus text), ``/metrics.json`` (the full
    snapshot), ``/healthz`` (liveness/readiness probe: 200 only once
    warmup completed and worker threads are live — knn_tpu.obs.health),
    ``/statusz`` (the full self-diagnosis report), ``/waterfallz``
    (per-request latency waterfalls + critical-path attribution —
    knn_tpu.obs.waterfall), and ``/fleetz`` (the merged cross-host
    fleet report over ``KNN_TPU_FLEET_MEMBERS`` — knn_tpu.obs.fleet)
    from a daemon
    thread; returns the server (``.shutdown()`` to stop;
    ``.server_address[1]`` for the bound port — pass port 0 to let the
    OS pick one)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 - stdlib handler contract
            from knn_tpu.obs import health

            path = self.path.split("?", 1)[0]
            status = 200
            if path in ("/metrics", "/"):
                body = prometheus_text().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif path == "/metrics.json":
                body = json.dumps(
                    {"enabled": registry.enabled(),
                     "identity": ident.identity(),
                     "written_at_unix": round(time.time(), 3),
                     "metrics": registry.snapshot()},
                    indent=1, sort_keys=True).encode()
                ctype = "application/json"
            elif path == "/healthz":
                probe = health.probe()
                status = 200 if probe["ready"] else 503
                body = json.dumps(probe, sort_keys=True).encode()
                ctype = "application/json"
            elif path == "/statusz":
                body = json.dumps(health.report(), indent=1,
                                  sort_keys=True, default=str).encode()
                ctype = "application/json"
            elif path == "/waterfallz":
                from knn_tpu.obs import waterfall

                # the full forensics payload: every reconstructable
                # waterfall from the live ring, attribution, and the
                # slowest-requests table (cli `waterfall --port`)
                body = json.dumps(waterfall.live_report(), indent=1,
                                  sort_keys=True, default=str).encode()
                ctype = "application/json"
            elif path == "/fleetz":
                from knn_tpu.obs import fleet

                # the merged fleet report over KNN_TPU_FLEET_MEMBERS
                # (knn_tpu.obs.fleet) — partial collections render
                # loudly with their unreachable/skewed members listed
                body = json.dumps(fleet.live_fleet_report(), indent=1,
                                  sort_keys=True, default=str).encode()
                ctype = "application/json"
            else:
                self.send_response(404)
                self.end_headers()
                return
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, fmt, *args):  # silence per-scrape stderr
            pass

    server = ThreadingHTTPServer((host, int(port)), Handler)
    server.daemon_threads = True
    t = threading.Thread(
        target=server.serve_forever, name="knn-obs-metrics", daemon=True)
    t.start()
    return server
