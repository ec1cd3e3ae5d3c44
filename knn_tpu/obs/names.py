"""The metric catalog — ONE jax-free home for every metric name the
registry may hand out.

Every metric the subsystem can register is declared here, with its type,
label names, and help string.  The registry REFUSES names outside this
catalog (knn_tpu.obs.registry), and ``scripts/lint_metric_names.py``
checks two invariants over it: every name matches ``knn_tpu_[a-z0-9_]+``
and every name appears in the ``docs/OBSERVABILITY.md`` catalog table —
so an instrumented code path can neither invent an undocumented metric
nor document a phantom one.

Names follow the Prometheus conventions the exporters assume: a
``knn_tpu_`` namespace prefix, ``_total`` suffix on counters, ``_seconds``
on time-valued metrics, base units throughout.

:func:`catalog_version` digests the whole catalog into a short token.
Identity-stamped snapshots carry it (knn_tpu.obs.export), and the fleet
aggregator refuses to merge members whose token differs — summing a
counter whose meaning changed between versions would silently produce
nonsense (knn_tpu.obs.fleet lists such members under ``skewed``).
"""

from __future__ import annotations

import hashlib
from functools import lru_cache


@lru_cache(maxsize=1)
def catalog_version() -> str:
    """A 12-hex digest of every (name, kind, labels) triple in the
    catalog — help-string edits don't move it, but adding/removing a
    metric or changing its kind/labels does."""
    h = hashlib.sha256()
    for name in sorted(CATALOG):
        kind, labels, _help = CATALOG[name]
        h.update(f"{name}|{kind}|{','.join(sorted(labels))}\n".encode())
    return h.hexdigest()[:12]

# --- serving engine (knn_tpu.serving.engine) ---------------------------
SERVING_REQUESTS = "knn_tpu_serving_requests_total"
SERVING_QUERIES = "knn_tpu_serving_queries_total"
SERVING_ERRORS = "knn_tpu_serving_errors_total"
SERVING_DISPATCHES = "knn_tpu_serving_dispatches_total"
SERVING_COMPILES = "knn_tpu_serving_compiles_total"
SERVING_REQUEST_LATENCY = "knn_tpu_serving_request_latency_seconds"

# --- micro-batching queue (knn_tpu.serving.queue) ----------------------
QUEUE_DEPTH_REQUESTS = "knn_tpu_queue_depth_requests"
QUEUE_DEPTH_ROWS = "knn_tpu_queue_depth_rows"
QUEUE_REQUESTS = "knn_tpu_queue_requests_total"
QUEUE_DISPATCHES = "knn_tpu_queue_dispatches_total"
QUEUE_COALESCED_ROWS = "knn_tpu_queue_coalesced_rows_total"
QUEUE_ERRORS = "knn_tpu_queue_errors_total"
QUEUE_WAIT = "knn_tpu_queue_wait_seconds"
QUEUE_REQUEST_LATENCY = "knn_tpu_queue_request_latency_seconds"

# --- admission control (knn_tpu.serving.admission / queue) -------------
ADMISSION_ADMITTED = "knn_tpu_admission_admitted_total"
ADMISSION_REJECTED = "knn_tpu_admission_rejected_total"
ADMISSION_SHED = "knn_tpu_admission_shed_total"
ADMISSION_WAIT_ESTIMATE = "knn_tpu_admission_queue_wait_estimate_seconds"

# --- per-tenant serving attribution (knn_tpu.serving) ------------------
TENANT_REQUESTS = "knn_tpu_tenant_requests_total"
TENANT_ERRORS = "knn_tpu_tenant_errors_total"
TENANT_REQUEST_LATENCY = "knn_tpu_tenant_request_latency_seconds"

# --- certified search (knn_tpu.parallel.sharded) -----------------------
CERTIFIED_QUERIES = "knn_tpu_certified_queries_total"
CERTIFIED_FALLBACKS = "knn_tpu_certified_fallback_queries_total"
CERTIFIED_GENUINE_MISSES = "knn_tpu_certified_fallback_genuine_misses_total"
CERTIFIED_FALSE_ALARMS = "knn_tpu_certified_fallback_false_alarms_total"
CERTIFIED_HOST_EXACT = "knn_tpu_certified_host_exact_queries_total"
CERTIFIED_RANK_CORRECTED = "knn_tpu_certified_rank_corrected_queries_total"
CERTIFIED_METRIC_QUERIES = "knn_tpu_certified_metric_queries_total"
CERTIFIED_SLACK_QUERIES = "knn_tpu_certified_slack_queries_total"
RANK_CORRECT_MEMBERS = "knn_tpu_rank_correct_members_total"
REPAIR_QUERIES = "knn_tpu_repair_queries_total"
REPAIR_REFINE_ROWS = "knn_tpu_repair_refine_rows_total"
VOTE_QUERIES = "knn_tpu_vote_queries_total"
CERTIFIED_QUANT_BOUND = "knn_tpu_certified_quant_bound"
RANGE_QUERIES = "knn_tpu_range_queries_total"
RANGE_RESULTS = "knn_tpu_range_results_total"
PROGRAM_LAUNCHES = "knn_tpu_program_launches_total"

# --- JAX compile events (knn_tpu.obs.jax_hooks) ------------------------
JAX_COMPILES = "knn_tpu_jax_compiles_total"
JAX_COMPILE_SECONDS = "knn_tpu_jax_compile_seconds_total"

# --- pipeline / spans (knn_tpu.utils.timing, knn_tpu.obs.trace) --------
PHASE_SECONDS = "knn_tpu_phase_seconds"
SPAN_SECONDS = "knn_tpu_span_seconds"
EVENTS_DROPPED = "knn_tpu_events_dropped_total"

# --- SLO engine (knn_tpu.obs.slo) --------------------------------------
SLO_BURN_RATE = "knn_tpu_slo_burn_rate"
SLO_BREACHED = "knn_tpu_slo_breached"
SLO_BREACH_TRANSITIONS = "knn_tpu_slo_breach_transitions_total"
SLO_EVALUATIONS = "knn_tpu_slo_evaluations_total"

# --- health introspection (knn_tpu.obs.health) -------------------------
HEALTH_READY = "knn_tpu_health_ready"

# --- flight recorder (knn_tpu.obs.blackbox) ----------------------------
POSTMORTEMS_WRITTEN = "knn_tpu_postmortems_written_total"

# --- multi-host merge tree (knn_tpu.parallel.sharded / .multihost) -----
MERGE_SELECTED = "knn_tpu_merge_strategy_selected_total"
MERGE_BYTES = "knn_tpu_merge_bytes_total"
MERGE_STRAGGLER_GAP = "knn_tpu_merge_straggler_gap_seconds"
SELECT_MERGE_CALLS = "knn_tpu_select_merge_calls_total"
KERNEL_TERMS = "knn_tpu_kernel_terms_total"
KERNEL_DIM_CHUNKS = "knn_tpu_kernel_dim_chunks_total"
FINAL_SELECT_CALLS = "knn_tpu_final_select_calls_total"
KERNEL_OPERANDS = "knn_tpu_kernel_operands_total"
CERTIFIED_SUB_BATCH_CALLS = "knn_tpu_certified_sub_batch_calls_total"
CERTIFIED_LAUNCHES = "knn_tpu_certified_launches_total"
CERTIFIED_BIN_OVERFLOW = "knn_tpu_certified_bin_overflow_queries_total"
FILTER_QUERIES = "knn_tpu_filter_queries_total"
JOIN_ROWS = "knn_tpu_join_rows_total"
JOIN_BLOCKS_INFLIGHT = "knn_tpu_join_blocks_inflight"
FILTER_LIST_IDS = "knn_tpu_filter_list_ids_total"
FILTER_RANGE_QUERIES = "knn_tpu_filter_range_queries_total"
FILTER_RANGE_VALID_ROWS = "knn_tpu_filter_range_valid_rows_total"

# --- host-RAM shard tier (knn_tpu.parallel.sharded) --------------------
HOSTTIER_SWEEPS = "knn_tpu_hosttier_sweeps_total"
HOSTTIER_SEGMENT_ROWS = "knn_tpu_hosttier_segment_rows"
HOSTTIER_SWEEP_SECONDS = "knn_tpu_hosttier_sweep_seconds"

# --- mutable index (knn_tpu.index.mutable) -----------------------------
INDEX_EPOCH = "knn_tpu_index_epoch"
INDEX_TAIL_ROWS = "knn_tpu_index_tail_rows"
INDEX_TOMBSTONES = "knn_tpu_index_tombstones"
INDEX_COMPACTIONS = "knn_tpu_index_compactions_total"
INDEX_SWAP_SECONDS = "knn_tpu_index_swap_seconds"

# --- shadow audit sampler (knn_tpu.obs.audit) --------------------------
AUDIT_SAMPLED = "knn_tpu_audit_sampled_requests_total"
AUDIT_REPLAYED = "knn_tpu_audit_replayed_queries_total"
AUDIT_DEFICIENT = "knn_tpu_audit_deficient_queries_total"
AUDIT_DROPPED = "knn_tpu_audit_dropped_total"
AUDIT_ROWS_SCORED = "knn_tpu_audit_rows_scored_total"
AUDIT_RECALL = "knn_tpu_audit_recall_at_k"
AUDIT_RANK_DISPLACEMENT = "knn_tpu_audit_rank_displacement"
AUDIT_DISTANCE_ERROR = "knn_tpu_audit_distance_rel_error"

# --- certificate-margin telemetry (sharded / ivf certified paths) ------
CERTIFIED_MARGIN = "knn_tpu_certified_margin_ratio"

# --- IVF per-search quality (knn_tpu.ivf.index) ------------------------
IVF_FALLBACK_RATE = "knn_tpu_ivf_fallback_rate"
IVF_RECALL_AT_K = "knn_tpu_ivf_recall_at_k"
IVF_PROBE_FRACTION = "knn_tpu_ivf_probe_fraction"
IVF_BYTES_STREAMED_RATIO = "knn_tpu_ivf_bytes_streamed_ratio"

# --- query-distribution drift (knn_tpu.obs.drift) ----------------------
DRIFT_NORM_PSI = "knn_tpu_drift_query_norm_psi"
DRIFT_ASSIGN_PSI = "knn_tpu_drift_centroid_assign_psi"
DRIFT_QUERIES = "knn_tpu_drift_queries_observed_total"

# --- index-health gauges (knn_tpu.obs.drift) ---------------------------
INDEX_LIST_IMBALANCE = "knn_tpu_index_list_imbalance"
INDEX_TAIL_FRACTION = "knn_tpu_index_delta_tail_fraction"
INDEX_TOMBSTONE_DENSITY = "knn_tpu_index_tombstone_density"

# --- fleet observability plane (knn_tpu.obs.fleet) ---------------------
FLEET_MEMBERS = "knn_tpu_fleet_members"
FLEET_UNREACHABLE = "knn_tpu_fleet_unreachable"
FLEET_MERGE_STALENESS = "knn_tpu_fleet_merge_staleness_seconds"
FLEET_STRAGGLER_HOST = "knn_tpu_fleet_straggler_host"

#: name -> (type, label names, help).  Types: "counter" (monotone,
#: float-valued so second-counters work), "gauge", "histogram" (bounded
#: sample window + lifetime count/sum; exported as a Prometheus summary).
CATALOG = {
    SERVING_REQUESTS: (
        "counter", ("op",),
        "Lifetime requests accepted by ServingEngine.submit()."),
    SERVING_QUERIES: (
        "counter", ("op",),
        "Lifetime query rows accepted by ServingEngine.submit()."),
    SERVING_ERRORS: (
        "counter", ("op",),
        "Requests that raised through dispatch or result join."),
    SERVING_DISPATCHES: (
        "counter", ("op", "bucket"),
        "Bucketed chunk dispatches, by op and bucket rung."),
    SERVING_COMPILES: (
        "counter", ("op", "bucket"),
        "Executable builds per (op, bucket) — the bucket ladder's "
        "compile-bound proof."),
    SERVING_REQUEST_LATENCY: (
        "histogram", ("op",),
        "Arrival-to-result request latency through the engine (seconds)."),
    QUEUE_DEPTH_REQUESTS: (
        "gauge", (),
        "Requests currently waiting in the micro-batching queue."),
    QUEUE_DEPTH_ROWS: (
        "gauge", (),
        "Query rows currently waiting in the micro-batching queue."),
    QUEUE_REQUESTS: (
        "counter", (),
        "Lifetime requests accepted by QueryQueue.submit()."),
    QUEUE_DISPATCHES: (
        "counter", (),
        "Coalesced batches the queue dispatched to the engine."),
    QUEUE_COALESCED_ROWS: (
        "counter", (),
        "Query rows dispatched through coalesced batches."),
    QUEUE_ERRORS: (
        "counter", (),
        "Queued requests resolved with an exception."),
    QUEUE_WAIT: (
        "histogram", (),
        "Per-request wait from arrival to batch dispatch (seconds)."),
    QUEUE_REQUEST_LATENCY: (
        "histogram", (),
        "Per-request arrival-to-result latency through the queue "
        "(seconds) — includes the micro-batching wait."),
    ADMISSION_ADMITTED: (
        "counter", ("tenant",),
        "Requests admitted past the admission controller, by tenant "
        "('-' for untagged traffic)."),
    ADMISSION_REJECTED: (
        "counter", ("tenant", "reason"),
        "Requests rejected AT SUBMIT with an explicit outcome "
        "(queue_full / quota / deadline) instead of unbounded queue "
        "growth."),
    ADMISSION_SHED: (
        "counter", ("tenant", "reason"),
        "Admitted requests shed before device dispatch (expired: the "
        "deadline passed while queued) — load the controller dropped "
        "instead of wasting device time on."),
    ADMISSION_WAIT_ESTIMATE: (
        "gauge", (),
        "Current wait estimate (seconds) the deadline-aware shedding "
        "decision uses: outstanding rows (queued + in flight) x EWMA "
        "per-row service time + the micro-batching deadline."),
    TENANT_REQUESTS: (
        "counter", ("tenant",),
        "Lifetime requests per tenant through the serving layer (only "
        "tenant-tagged submissions produce series)."),
    TENANT_ERRORS: (
        "counter", ("tenant",),
        "Per-tenant requests resolved with an exception (admission "
        "rejections/sheds count separately, not here)."),
    TENANT_REQUEST_LATENCY: (
        "histogram", ("tenant",),
        "Per-tenant arrival-to-result latency (seconds) of ADMITTED "
        "requests — the per-tenant SLO objectives read this."),
    CERTIFIED_QUERIES: (
        "counter", ("selector",),
        "Queries processed by ShardedKNN.search_certified."),
    CERTIFIED_FALLBACKS: (
        "counter", ("selector",),
        "Queries that failed certification and took the widened "
        "re-select fallback."),
    CERTIFIED_GENUINE_MISSES: (
        "counter", ("selector",),
        "Fallbacks where the repair CHANGED the answer (the coarse pass "
        "really missed a neighbor)."),
    CERTIFIED_FALSE_ALARMS: (
        "counter", ("selector",),
        "Fallbacks that reproduced the original answer (the tolerance "
        "cried wolf)."),
    CERTIFIED_HOST_EXACT: (
        "counter", ("selector",),
        "Fallbacks escalated to the unconditional float64 host scan."),
    CERTIFIED_RANK_CORRECTED: (
        "counter", (),
        "Pallas-selector queries whose near-tie runs were re-ranked in "
        "float64."),
    CERTIFIED_METRIC_QUERIES: (
        "counter", ("metric",),
        "Queries processed by ShardedKNN.search_certified, by the "
        "placement's metric (l2 / cosine / dot): which contract the "
        "answers were held to."),
    CERTIFIED_SLACK_QUERIES: (
        "counter", ("outcome",),
        "Queries of search_certified(selector='pallas') on a COSINE "
        "placement, by what its certificate said: 'certified'; "
        "'uncertified_by_slack' (it holds without the placement's pair "
        "slack, COS_UNIT_SLACK for the rounding of the unit rows, and "
        "fails with it: what the exactness of the rows as given costs "
        "in repairs); 'uncertified' (every other flagged query: the "
        "certificate fails without the slack too, the tie window has no "
        "provable boundary, or a zero row is among the candidates).  "
        "Every outcome exists from the first such call, at 0 where "
        "nothing took it."),
    VOTE_QUERIES: (
        "counter", ("outcome",),
        "Queries of ShardedKNN.predict_certified(vote='softmax'), by who "
        "answered: 'device' (the device's vote stood: its certificate "
        "flagged nothing); 'boundary' (the k-th and (k+1)-th candidates "
        "too close to tell apart: the host took the float64 first k of "
        "the query's window and re-voted); 'margin' (two adjacent class "
        "totals within 2 vote_delta: the host re-voted the first k in "
        "float64); 'fallback' (uncertified: re-voted from the repair's "
        "neighbours); 'host' (a counted selector's call: every query "
        "voted on the host from float64 neighbours).  A query counts "
        "under the first of fallback, boundary, margin that holds; every "
        "outcome exists from the first selector='pallas' call, at 0 where "
        "nothing took it."),
    RANK_CORRECT_MEMBERS: (
        "counter", (),
        "Candidate rows the host's float64 rank correction gathered and "
        "re-scored (ops.refine.rank_correct_runs' members, summed over "
        "the sub-batches of search_certified(selector='pallas') calls): "
        "what a pair slack widens and a wide row makes dear."),
    REPAIR_QUERIES: (
        "counter", ("outcome",),
        "Queries ops.certified.repair_uncertified answered, by what "
        "settled them: 'proven' (the widened re-select's own exclusion "
        "value proved the float64 refine exact) or 'host_scan' (the "
        "unconditional float64 host scan, host_exact_knn).  Their sum is "
        "the fallback queries; both outcomes exist from the first "
        "certified call, at 0 where nothing took them."),
    REPAIR_REFINE_ROWS: (
        "counter", ("outcome",),
        "Candidates the widened re-select handed "
        "ops.certified.repair_uncertified (flagged queries x the width "
        "left after a self-join's own row went), by what the float64 "
        "refine did with them: 'refined' (gathered from the host rows "
        "and scored: the 'rows' of the span certified.repair.refine) or "
        "'thinned' (past the longest prefix whose float32 score can "
        "still reach the top-k, twice the certificate's tolerance over "
        "the k-th: never gathered).  Their sum is the span's "
        "'selected'; both outcomes exist from the first certified call, "
        "at 0 where nothing took them."),
    CERTIFIED_QUANT_BOUND: (
        "histogram", (),
        "Per-query int8 certified quantization error bound epsilon "
        "(score units) — the quality signal the int8 coarse pass "
        "computes."),
    RANGE_QUERIES: (
        "counter", ("outcome",),
        "Queries answered by ShardedKNN.range_search_certified, by how "
        "their result list was finished: 'complete' by the first pass "
        "alone (k-th distance over the radius), 'truncated' by the "
        "device completion, 'host_scan' by the exact host scan (count "
        "over the collect width).  Every outcome exists from the first "
        "call."),
    RANGE_RESULTS: (
        "counter", (),
        "Rows returned by ShardedKNN.range_search_certified, over all "
        "its queries."),
    PROGRAM_LAUNCHES: (
        "counter", ("program",),
        "Device program launches by the certified and range calls, by "
        "the program's name: 'certified' (the one-pass program), "
        "'reselect' (the repair's widened exact select), 'range' (the "
        "range completion), 'counted' and 'count' (the counted "
        "selectors' two passes), 'operands' (the build of the "
        "resident row operands: 1 a placement and geometry, in the "
        "call that first resolved it).  Moved once a call, by the "
        "call's account (obs.trace.CallAccount)."),
    JAX_COMPILES: (
        "counter", ("event",),
        "JAX/XLA compile and compilation-cache events observed via "
        "jax.monitoring (every /jax/core/compile/ and "
        "/jax/compilation_cache/ key: traces, lowerings, backend "
        "compiles, cache hits, misses and retrievals)."),
    JAX_COMPILE_SECONDS: (
        "counter", ("event",),
        "Cumulative seconds spent in the observed JAX/XLA compile and "
        "compilation-cache events that carry a duration."),
    PHASE_SECONDS: (
        "histogram", ("phase",),
        "PhaseTimer phase durations (seconds), by phase name."),
    SPAN_SECONDS: (
        "histogram", ("span",),
        "Trace span durations (seconds), by span name."),
    EVENTS_DROPPED: (
        "counter", (),
        "Structured events dropped because the JSONL sink raised."),
    SLO_BURN_RATE: (
        "gauge", ("objective", "window"),
        "Error-budget burn rate per SLO objective and evaluation window "
        "(ratio objectives: window error ratio / budget; quantile "
        "objectives: window quantile / threshold, window label 'hist')."),
    SLO_BREACHED: (
        "gauge", ("objective",),
        "1 while the objective's multi-window burn-rate policy is "
        "breached, 0 otherwise (edge transitions emit slo.alert events)."),
    SLO_BREACH_TRANSITIONS: (
        "counter", ("objective",),
        "Healthy-to-breached transitions per objective (each one also "
        "emits exactly one firing slo.alert event)."),
    SLO_EVALUATIONS: (
        "counter", (),
        "SLO engine evaluation passes (each appends one counter sample "
        "to the burn-rate window ring)."),
    HEALTH_READY: (
        "gauge", (),
        "1 when the readiness probe passes (warmup complete, worker "
        "threads live), 0 otherwise; set on every /healthz or report()."),
    POSTMORTEMS_WRITTEN: (
        "counter", ("objective",),
        "Flight-recorder postmortem bundles written to "
        "KNN_TPU_POSTMORTEM_DIR, one per edge-triggered SLO breach "
        "transition, by the objective that fired."),
    MERGE_SELECTED: (
        "counter", ("level", "strategy", "source"),
        "Merge-strategy resolutions at placement time, by merge level "
        "(intra = per-host ICI db axis, dcn = cross-host) x chosen "
        "strategy (ring / allgather) x provenance (explicit caller / "
        "env switch / measured crossover table)."),
    MERGE_BYTES: (
        "counter", ("level", "strategy"),
        "Modeled candidate bytes moved by top-k merges "
        "(parallel.crossover.merge_bytes), by level and strategy."),
    SELECT_MERGE_CALLS: (
        "counter", ("engaged",),
        "Batches of search_certified(selector='pallas'), by whether "
        "the final select's bin-merge engaged (ops.pallas_knn."
        "select_merge_geometry: the candidate width at least twice the "
        "merged width) or the top-(m+2) ran over the kernel's "
        "candidates as they are."),
    KERNEL_TERMS: (
        "counter", ("terms",),
        "Batches of search_certified(selector='pallas'), by the "
        "products of the bf16x3 split their kernel formed "
        "(ops.pallas_knn.BF16X3_TERMS): 'hh+hl+lh' the full sum, "
        "'hh+lh' where every row is bf16-exact, 'hh' where the batch "
        "is too — 3, 2 or 1 MXU passes."),
    KERNEL_DIM_CHUNKS: (
        "counter", ("chunks", "row_steps"),
        "Batches of search_certified(selector='pallas'), by how their "
        "kernel cut a row tile: 'chunks' the dim chunks of its width "
        "(ops.pallas_knn.dim_chunking: '1' under the tiled kernel, the "
        "padded width over 128 under the other two), 'row_steps' the "
        "grid steps of its rows (ops.pallas_knn.row_blocking: '1' "
        "wherever the whole tile fits VMEM at that width, no scratch; "
        "else the row blocks it is cut into, the bin-select's running "
        "arrays carried between them)."),
    FINAL_SELECT_CALLS: (
        "counter", ("stage",),
        "Batches of search_certified(selector='pallas'), by what ran "
        "each shard's final top-(m+2): 'pallas' the one Pallas call "
        "that carries the indices with the scores (ops.pallas_knn."
        "final_select_geometry: an exact final select at a shape it "
        "was timed at), 'xla' lax.top_k and the gather after it (every "
        "other shape, and final_select='approx')."),
    FILTER_QUERIES: (
        "counter", ("outcome",),
        "Queries of search_certified(filter_tags=...), by how many of "
        "the rows their tags allow came back: 'full' (k rows), 'short' "
        "(1 to k-1: fewer than k rows hold every tag), 'empty' (none "
        "does).  Every outcome exists from the first filtered call, at "
        "0 where nothing took it."),
    FILTER_LIST_IDS: (
        "counter", (),
        "Row ids named by the LISTED tag lookups of "
        "search_certified(filter_tags=...) (a tag too rare for a "
        "bitmap, ops.tagfilter.bitmap_min_rows): what the program "
        "filter_mask sets one bit apiece for."),
    FILTER_RANGE_QUERIES: (
        "counter", ("outcome",),
        "Queries of search_certified(filter_range=...), by how many of "
        "the rows whose attribute lies in their range came back: 'full' "
        "(k rows), 'short' (1 to k-1: fewer than k rows lie in it), "
        "'empty' (none does).  Every outcome exists from the first such "
        "call, at 0 where nothing took it."),
    FILTER_RANGE_VALID_ROWS: (
        "counter", (),
        "Placed rows whose attribute lies in the range of a query of "
        "search_certified(filter_range=...), summed over the queries: "
        "over knn_tpu_filter_range_queries_total times the placed rows "
        "it is the selectivity a run saw."),
    KERNEL_OPERANDS: (
        "counter", ("source",),
        "Batches of search_certified(selector='pallas'), by where their "
        "kernel's row operands (the bf16 halves of the padded rows and "
        "the row norms) came from: 'resident' the placement's own, "
        "built once on the device and handed to the program "
        "(ShardedKNN._row_operands: the default precision, where "
        "analysis.hbm.resident_operands_room finds the device has "
        "room: the rows, the form and the largest program's "
        "temporaries, 1.25 x rows placed in whole lane tiles and 2.7 "
        "x any other, within 7/8 of bytes_limit; its terms are the "
        "placement.operands event's), 'per_call' the program's "
        "prologue, over the whole corpus in every call."),
    CERTIFIED_SUB_BATCH_CALLS: (
        "counter", ("why",),
        "Calls of search_certified(selector='pallas'), by why their "
        "sub-batch is what it is (analysis.subbatch.certified_sub_batch): "
        "'resident' cut by the rule into SUB_BATCHES launches, so that "
        "the host repairs one while the device runs the next; one batch "
        "because every launch would re-form the row operands "
        "('per_call_operands'), because the placed rows' width is no "
        "whole number of 128-column tiles and every launch would copy "
        "them ('layout_copy'), or because the call is too few queries "
        "('small'); 'explicit' the caller's batch_size; 'memory' cut "
        "further, to the most whole query blocks a chip has room for "
        "beside its rows (analysis.hbm.certified_query_bytes: the "
        "rescore gathers m+1 rows a query)."),
    CERTIFIED_LAUNCHES: (
        "counter", ("survivor_depth", "final_select_stage"),
        "Device programs launched by calls of search_certified("
        "selector='pallas') (the sub-batches of every call: over "
        "knn_tpu_certified_sub_batch_calls_total it is the launches a "
        "call), by the survivors a kernel bin kept (ops.pallas_knn."
        "survivor_depth: 2 at every k = 100 shape, 4 at k = 1,024 over "
        "1M rows) and by what ran the final top-(m+2) ('pallas' / "
        "'xla')."),
    CERTIFIED_BIN_OVERFLOW: (
        "counter", (),
        "Queries of search_certified(selector='pallas') whose "
        "certificate failed on a FULL BIN: flagged by the device, and "
        "after the repair one kernel bin (a lane of a row tile of its "
        "shard) is seen to hold more of the query's exact top-k than "
        "the survivor depth, so the kernel cannot have emitted them "
        "all.  Over the call's queries it is what "
        "ops.pallas_knn.bin_overflow_share models from above."),
    JOIN_ROWS: (
        "counter", ("mode",),
        "Rows answered by the bulk join (knn_tpu.join.engine), by its "
        "mode: 'self' the pipelined certified self-join "
        "(knn_self_join: every row a query of the placement it is part "
        "of, its own row out by id)."),
    JOIN_BLOCKS_INFLIGHT: (
        "gauge", (),
        "Blocks of a bulk self-join launched and not yet fetched, "
        "sampled at each block's launch: at most the engine's depth "
        "(SELF_JOIN_DEPTH = 2), each block SUB_BATCHES certified "
        "programs."),
    MERGE_STRAGGLER_GAP: (
        "gauge", (),
        "Max-minus-min per-host local search wall time of the last "
        "cross-host merge (parallel.multihost) — the straggler signal "
        "/statusz and doctor attribute."),
    HOSTTIER_SWEEPS: (
        "counter", (),
        "Host-RAM tier segment sweeps executed: one per super-HBM "
        "db segment streamed through the device placement."),
    HOSTTIER_SEGMENT_ROWS: (
        "gauge", (),
        "Padded rows per host-RAM tier segment of the last planned "
        "sweep (every sweep reuses this one compiled shape)."),
    HOSTTIER_SWEEP_SECONDS: (
        "histogram", (),
        "Wall seconds per host-RAM tier sweep (dispatch to fetch of "
        "one segment) — flat across sweeps when the stream overlaps."),
    INDEX_EPOCH: (
        "gauge", (),
        "Current snapshot epoch of the mutable index — bumps once per "
        "compaction swap (knn_tpu.index.mutable)."),
    INDEX_TAIL_ROWS: (
        "gauge", (),
        "Rows currently in the mutable index's delta tail (searched "
        "alongside the main placement; compaction folds them in)."),
    INDEX_TOMBSTONES: (
        "gauge", (),
        "Ids currently tombstoned in the mutable index — masked out of "
        "every merged select under the certify reserve; compaction "
        "drops the rows and resets this."),
    INDEX_COMPACTIONS: (
        "counter", (),
        "Completed compaction cycles (tail merged + tombstones "
        "dropped into a fresh placement, snapshot-swapped in)."),
    INDEX_SWAP_SECONDS: (
        "histogram", (),
        "Seconds the compaction's atomic pointer swap held the index "
        "lock — the only slice of a compaction that can contend with "
        "the serving path (the build/warm runs off it)."),
    AUDIT_SAMPLED: (
        "counter", ("tenant",),
        "Live requests selected by the shadow audit sampler's "
        "trace-id hash (KNN_TPU_AUDIT_RATE), by tenant ('-' for "
        "untagged traffic) — includes records later dropped by the "
        "budget or backlog."),
    AUDIT_REPLAYED: (
        "counter", ("tenant",),
        "Query rows replayed against the f64 exact oracle by the "
        "audit worker, by tenant — the denominator of the "
        "audit_recall SLO objective."),
    AUDIT_DEFICIENT: (
        "counter", ("tenant",),
        "Audited query rows whose served neighbors missed the exact "
        "top-k (recall@k < 1), by tenant — the numerator of the "
        "audit_recall SLO objective."),
    AUDIT_DROPPED: (
        "counter", ("reason",),
        "Sampled audit records dropped WITHOUT replay, by reason "
        "(budget: over the KNN_TPU_AUDIT_BUDGET_ROWS_S token bucket; "
        "queue_full: the bounded replay backlog; error: the oracle "
        "replay raised) — a silent drop would read as a healthy "
        "audit."),
    AUDIT_ROWS_SCORED: (
        "counter", (),
        "Oracle rows scanned by completed audit replays — the spend "
        "the row budget meters."),
    AUDIT_RECALL: (
        "histogram", ("tenant",),
        "Per-audited-query recall@k of the served answer against the "
        "f64 exact oracle (1.0 = the exact set, tie-tolerant), by "
        "tenant."),
    AUDIT_RANK_DISPLACEMENT: (
        "histogram", ("tenant",),
        "Per-served-neighbor displacement from its exact oracle rank "
        "(0 = served in its true position), by tenant."),
    AUDIT_DISTANCE_ERROR: (
        "histogram", ("tenant",),
        "Relative error of each served distance against its own f64 "
        "recompute — arithmetic drift, independent of ranking."),
    CERTIFIED_MARGIN: (
        "histogram", ("path",),
        "Per-certified-query relative margin between the k-th result "
        "distance and the exclusion bound that certified it, by "
        "certification path (sharded / ivf).  Margins crowding 0 are "
        "the leading indicator that fallback rate is about to grow."),
    IVF_FALLBACK_RATE: (
        "gauge", ("selector",),
        "Fraction of the last IVF search's queries that failed the "
        "probe-pruning certificate and fell back to wider scans."),
    IVF_RECALL_AT_K: (
        "gauge", ("selector",),
        "Measured recall@k of the last IVF search against its own "
        "exact rescore (1.0 when every certificate held)."),
    IVF_PROBE_FRACTION: (
        "gauge", ("selector",),
        "Fraction of trained IVF lists probed by the last search — "
        "the pruning the tier exists to deliver."),
    IVF_BYTES_STREAMED_RATIO: (
        "gauge", ("selector",),
        "Bytes streamed by the last IVF search as a fraction of the "
        "brute-force full-corpus stream."),
    DRIFT_NORM_PSI: (
        "gauge", (),
        "Population-stability index of the live query-norm histogram "
        "against the train-time baseline (0 = identical; > 0.2 "
        "investigate, > 0.5 act)."),
    DRIFT_ASSIGN_PSI: (
        "gauge", (),
        "Population-stability index of the live IVF "
        "centroid-assignment histogram against the k-means training "
        "assignment counts."),
    DRIFT_QUERIES: (
        "counter", (),
        "Query rows folded into the drift sketches."),
    INDEX_LIST_IMBALANCE: (
        "gauge", (),
        "Max/mean trained IVF list size of the current snapshot "
        "(1.0 = perfectly balanced; growth concentrates probe cost)."),
    INDEX_TAIL_FRACTION: (
        "gauge", (),
        "Fraction of all index rows sitting in the unindexed delta "
        "tail — the slice every search brute-forces until "
        "compaction."),
    INDEX_TOMBSTONE_DENSITY: (
        "gauge", (),
        "Fraction of all index rows tombstoned — dead bytes diluting "
        "every stream until compaction drops them."),
    FLEET_MEMBERS: (
        "gauge", (),
        "Members the last fleet collection merged (knn_tpu.obs.fleet) "
        "— live endpoints reached or snapshot files read."),
    FLEET_UNREACHABLE: (
        "gauge", (),
        "Members the last fleet collection could NOT merge "
        "(unreachable endpoint, torn/unreadable snapshot, or "
        "catalog-version skew) — nonzero marks the report partial."),
    FLEET_MERGE_STALENESS: (
        "gauge", (),
        "Spread (seconds) between the oldest and newest member "
        "snapshot the last fleet collection merged — how far apart in "
        "time the merged numbers are."),
    FLEET_STRAGGLER_HOST: (
        "gauge", ("host",),
        "1 on the member whose per-host DCN-merge wall time was the "
        "fleet maximum in the last collection (the named straggler), "
        "0 on the others."),
}
