"""Measured-term calibration: reconcile device time against the
roofline model's terms and persist per-term scale factors the model
consults — MODEL_VERSION 3's measured half (ROADMAP open item 1).

The analytic model (:mod:`knn_tpu.obs.roofline`) predicts per-sweep
term times ``t_hbm``/``t_mxu``/``t_vpu`` from spec-sheet peaks.  The
thesis of TPU-KNN (arXiv:2206.14286) is only falsifiable when those
terms can be DECOMPOSED against measured kernel time — the PANDA-style
discipline (arXiv:1607.08220) of fitting cost-model constants to
measurement instead of assuming them.  This module is that loop:

- :func:`reconcile` takes one modeled block plus one measured sample
  (:mod:`knn_tpu.obs.traceread`: a device-trace busy time or a
  host-phase ``device_s``) and solves for per-term scale factors.
  The BINDING term absorbs the residual (the other terms are hidden
  under it in the combined-time formula, so the measurement carries no
  information about them — attributing their share would be
  fabrication); when no bound-term factor inside the sane clamp can
  reproduce the measurement, every term scales uniformly and the entry
  says so (``method: "uniform"``).  Either way the calibrated combined
  time REPRODUCES the measured device time by construction, so the
  calibrated ceiling equals the measured q/s up to arithmetic —
  ``model_residual_pct`` records how far the ANALYTIC model was off.
- Factors persist to a calibration store — ``KNN_TPU_CALIBRATION``
  JSON, atomic tmp+rename writes, mtime-memoized reads: the tune-cache
  discipline — keyed by
  ``device_kind|n|d|k|selector:precision:kernel|cal<MODEL_VERSION>``.  The
  trailing version token means a calibration fit under an older model's
  terms SELF-INVALIDATES (misses on lookup) instead of scaling terms it
  was never fit against, exactly like ``|rl``/``|kv`` in the tune
  cache key.
- :mod:`knn_tpu.obs.roofline` consults the overlay on every block
  (lazily, through :func:`lookup_for_block`): blocks gain
  ``calibration: {applied, factors, source, age_s, …}`` and a
  calibrated ``ceiling_qps`` beside ``ceiling_qps_analytic``.

Full provenance rides every entry (device_kind, shape key, config
label, commit, round, source ``device_trace``/``host_phase``) so a
curated artifact can say not just *that* the ceiling was calibrated
but *from which measurement*.  Everything here is jax-free.
Derivation: docs/PERF.md "Calibration & measured ceilings".
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Dict, Iterable, List, Optional

from knn_tpu.obs import names, registry, trace

#: env switch: path of the calibration store JSON; unset = no overlay
#: (every roofline block renders ``calibration: {applied: false}``)
CAL_ENV = "KNN_TPU_CALIBRATION"

#: store file schema version (guards future migrations, like the tune
#: cache's ``version`` field)
STORE_VERSION = 1

#: the model terms a factor can scale, in roofline term order
TERMS = ("hbm", "mxu", "vpu_select")

_TERM_OF_BOUND = {"hbm_bound": "hbm", "mxu_bound": "mxu",
                  "vpu_select_bound": "vpu_select"}

#: sane clamp on a single term's scale factor: outside it the
#: measurement is telling us something no per-term rate error explains
#: (wrong shape key, torn trace) and reconcile refuses loudly.  The
#: ceiling is deliberately generous — the CPU rehearsal reconciles an
#: INTERPRET-mode kernel against compiled-CPU generic peaks, which
#: legitimately sits 10-100x under the analytic terms
FACTOR_MIN, FACTOR_MAX = 1e-3, 1e4

#: stated tolerance (percent) between a calibrated ceiling and the
#: measured qps it was fit from; the
#: reconstruction is exact up to rounding, so this bound is generous
RESIDUAL_TOLERANCE_PCT = 2.0

#: measured-sample sources (traceread vocabulary)
SOURCES = ("device_trace", "host_phase")

_lock = threading.Lock()
#: path -> ((mtime_ns, size), entries) read memo (tune-cache pattern)
_read_memo: dict = {}


def store_path() -> Optional[str]:
    """The calibration store file, or None when ``KNN_TPU_CALIBRATION``
    is unset (no overlay — the analytic model stands alone)."""
    return os.environ.get(CAL_ENV) or None


def model_token() -> str:
    """``cal<MODEL_VERSION>`` — the version token baked into every
    store key: factors are a fit AGAINST one model version's terms, so
    when the model changes the persisted entry's key no longer matches
    and lookups fall back to analytic cleanly (the ``|rl``/``|kv``
    self-invalidation mechanism of the tune cache)."""
    from knn_tpu.obs.roofline import MODEL_VERSION

    return f"cal{MODEL_VERSION}"


def calibration_key(device_kind: Optional[str], n: int, d: int, k: int,
                    selector: str, precision: Optional[str],
                    kernel: Optional[str] = None) -> str:
    """The shape key one calibration is valid for — the tune-cache key
    discipline: any field mismatch MUST miss (a factor fit on one
    (kind, shape, precision, kernel) point says nothing about another —
    in particular, the tiled/streaming/fused kernels at the SAME
    shape measure different machines and must never share an entry)."""
    kind = device_kind or "generic-cpu"
    kern = f":{kernel}" if kernel else ""
    return (f"{kind}|n{int(n)}|d{int(d)}|k{int(k)}|"
            f"{selector}:{precision or 'default'}{kern}|{model_token()}")


def key_for_block(block: dict) -> Optional[str]:
    """The store key a roofline block looks itself up under (from its
    own ``config``/``selector`` fields), or None when the block doesn't
    carry enough shape to key on."""
    cfg = block.get("config")
    sel = block.get("selector")
    if not isinstance(cfg, dict) or not sel:
        return None
    try:
        precision = (cfg.get("precision") if sel == "pallas"
                     else cfg.get("dtype"))
        return calibration_key(block.get("device_kind"), cfg["n"],
                               cfg["d"], cfg["k"], sel, precision,
                               kernel=(cfg.get("kernel")
                                       if sel == "pallas" else None))
    except (KeyError, TypeError, ValueError):
        return None


def load(path: Optional[str] = None) -> dict:
    """All store entries (empty when the file is absent/corrupt — a
    broken overlay degrades to the analytic model, never to an
    error)."""
    path = path or store_path()
    if not path:
        return {}
    try:
        st = os.stat(path)
    except OSError:
        return {}
    sig = (st.st_mtime_ns, st.st_size)
    with _lock:
        memo = _read_memo.get(path)
        if memo and memo[0] == sig:
            return memo[1]
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict) or \
                data.get("version") != STORE_VERSION:
            return {}
        entries = data.get("entries", {})
        if not isinstance(entries, dict):
            return {}
    except (OSError, json.JSONDecodeError):
        return {}
    with _lock:
        _read_memo[path] = (sig, entries)
    return entries


def get(key: str, path: Optional[str] = None) -> Optional[dict]:
    entry = load(path).get(key)
    return entry if isinstance(entry, dict) else None


def put(key: str, entry: dict, path: Optional[str] = None) -> str:
    """Insert/replace one entry; atomic write (tmp + rename).  Returns
    the path written.  Raises ValueError when no store path is
    configured — persisting a calibration nowhere is a caller bug, not
    a degradable condition."""
    path = path or store_path()
    if not path:
        raise ValueError(
            f"no calibration store configured (set {CAL_ENV} or pass "
            f"an explicit path)")
    with _lock:
        entries = {}
        try:
            with open(path) as f:
                data = json.load(f)
            if (isinstance(data, dict)
                    and data.get("version") == STORE_VERSION
                    and isinstance(data.get("entries"), dict)):
                entries = data["entries"]
        except (OSError, json.JSONDecodeError):
            pass
        prev = entries.get(key)
        if isinstance(prev, dict):
            entry = dict(entry,
                         samples=int(prev.get("samples", 1)) + 1)
        entries[key] = entry
        payload = {"version": STORE_VERSION, "entries": entries}
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
        _read_memo.pop(path, None)
    return path


def _combined_time(times: Dict[str, float],
                   select_overlapped: bool) -> float:
    """The roofline's combined-time formula over per-term times
    (``{hbm, mxu, vpu_select}`` keys).  Delegates to the ONE formula
    the ceiling itself uses (:func:`roofline._combined`) — the
    reconciler's factors are only sound when it solves against exactly
    that combination, so a second copy here would drift the moment a
    model version changes it."""
    from knn_tpu.obs.roofline import _combined

    return _combined({"hbm_bound": times["hbm"],
                      "mxu_bound": times["mxu"],
                      "vpu_select_bound": times["vpu_select"]},
                     select_overlapped)


def reconcile(block: dict, measured: dict, *,
              provenance: Optional[dict] = None) -> dict:
    """Decompose one measured device time against one modeled block's
    terms (module docstring for the solving discipline).  Returns the
    store entry: per-term ``factors`` + ``term_residual_pct``, the
    signed ``model_residual_pct`` the analytic model was off by, the
    measured sample's provenance, and the fit ``method``."""
    src = measured.get("source")
    if src not in SOURCES:
        raise ValueError(f"measured source {src!r} not in {SOURCES}")
    dev_s = measured.get("device_s")
    m_nq = measured.get("nq")
    if not isinstance(dev_s, (int, float)) or dev_s <= 0:
        raise ValueError(f"measured device_s {dev_s!r} must be > 0")
    if not isinstance(m_nq, int) or m_nq <= 0:
        raise ValueError(f"measured nq {m_nq!r} must be a positive int")
    terms = block.get("terms")
    cfg = block.get("config") or {}
    if not isinstance(terms, dict) or \
            block.get("bound_class") not in _TERM_OF_BOUND:
        raise ValueError("block is not a roofline model "
                         "(missing terms/bound_class)")
    times = {t: float(terms[t]["time_s"]) for t in TERMS}
    if any(v <= 0 for v in times.values()):
        raise ValueError(f"non-positive modeled term time: {times}")
    # attribute against the ANALYTIC binding term, re-derived from the
    # raw term times (a block that already consulted an earlier overlay
    # carries the CALIBRATED bound_class — fitting against that would
    # compound factors across rounds instead of re-fitting the model)
    bound = max(_TERM_OF_BOUND,
                key=lambda c: (times[_TERM_OF_BOUND[c]],
                               -list(_TERM_OF_BOUND).index(c)))
    overlapped = bool(block.get("select_overlapped"))
    nq_model = int(cfg.get("nq") or m_nq)
    # normalize the measurement to the model's sweep size
    measured_t = float(dev_s) * (nq_model / m_nq)
    modeled_t = _combined_time(times, overlapped)
    scale = measured_t / modeled_t
    if not (FACTOR_MIN <= scale <= FACTOR_MAX):
        raise ValueError(
            f"measured/modeled ratio {scale:.4g} outside the sane "
            f"clamp [{FACTOR_MIN}, {FACTOR_MAX}] — wrong shape key or "
            f"torn measurement, refusing to calibrate")
    bterm = _TERM_OF_BOUND[bound]
    factors = {t: 1.0 for t in TERMS}
    # solve the combined-time formula for the bound term's factor with
    # the hidden terms held at 1.0
    if overlapped:
        f_b = measured_t / times[bterm]
        solvable = f_b * times[bterm] >= max(
            v for t, v in times.items() if t != bterm)
    else:
        if bterm == "vpu_select":
            f_b = (measured_t - max(times["hbm"], times["mxu"])) \
                / times["vpu_select"]
            solvable = f_b > 0
        else:
            f_b = (measured_t - times["vpu_select"]) / times[bterm]
            other = "mxu" if bterm == "hbm" else "hbm"
            solvable = f_b > 0 and f_b * times[bterm] >= times[other]
    if solvable and FACTOR_MIN <= f_b <= FACTOR_MAX:
        factors[bterm] = f_b
        method = "bound_term"
    else:
        # the measurement sits where no single-term factor can put it
        # (e.g. measured under a hidden term): scale everything
        factors = {t: scale for t in TERMS}
        method = "uniform"
    cal_times = {t: times[t] * factors[t] for t in TERMS}
    cal_t = _combined_time(cal_times, overlapped)
    entry = {
        # 9 decimals: a uniform CPU-rehearsal factor can sit at 1e-3,
        # where 6-decimal rounding would visibly move the calibrated
        # ceiling away from the measurement it must reproduce
        "factors": {t: round(f, 9) for t, f in factors.items()},
        "method": method,
        "bound_class": bound,
        "select_overlapped": overlapped,
        "model_residual_pct": round((scale - 1.0) * 100.0, 2),
        "term_residual_pct": {
            t: round((factors[t] - 1.0) * 100.0, 2) for t in TERMS},
        "measured_qps": round(nq_model / measured_t, 2),
        "analytic_ceiling_qps": block.get("ceiling_qps_analytic")
        or block.get("ceiling_qps"),
        "calibrated_ceiling_qps": round(nq_model / cal_t, 1),
        "source": src,
        "model_version": block.get("model_version"),
        "samples": 1,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime()),
        "measured_at_unix": round(time.time(), 3),
        "provenance": {
            "device_kind": block.get("device_kind"),
            "shape_key": key_for_block(block),
            "nq_model": nq_model, "nq_measured": m_nq,
            "device_s": round(float(dev_s), 6),
            **(provenance or {}),
        },
    }
    return entry


def apply_to_times(times: Dict[str, float],
                   factors: Dict[str, float]) -> Dict[str, float]:
    """Calibrated per-term times (missing factors default to 1.0)."""
    return {t: float(times[t]) * float(factors.get(t, 1.0))
            for t in times}


def entry_age_s(entry: dict) -> Optional[float]:
    ts = entry.get("measured_at_unix")
    if not isinstance(ts, (int, float)):
        return None
    return max(0.0, round(time.time() - float(ts), 1))


def lookup_for_block(block: dict,
                     path: Optional[str] = None) -> Optional[dict]:
    """The store entry covering this block's shape key, or None (no
    store configured, no entry, stale model token)."""
    key = key_for_block(block)
    if key is None:
        return None
    return get(key, path)


def publish(label: str, cal: dict) -> None:
    """Export one block's calibration verdict to the metrics registry
    (obs-gated, like every exporter): applied flag, entry age, and the
    analytic model's residual (the drift signal)."""
    if not registry.enabled():
        return
    applied = bool(cal.get("applied"))
    registry.gauge(names.CALIBRATION_APPLIED, config=label).set(
        1.0 if applied else 0.0)
    if not applied:
        return
    age = cal.get("age_s")
    if isinstance(age, (int, float)):
        registry.gauge(names.CALIBRATION_AGE, config=label).set(
            float(age))
    res = cal.get("model_residual_pct")
    if isinstance(res, (int, float)):
        registry.gauge(names.CALIBRATION_RESIDUAL, config=label).set(
            float(res))
    trace.emit_event("calibration.publish", config=label,
                     source=cal.get("source"),
                     model_residual_pct=res)


def status() -> dict:
    """The /statusz ``calibration`` section: store location, entry
    count, and the worst per-term residual on file — the one-line
    answer to "is this process's roofline calibrated, and how wrong
    was the analytic model?"."""
    path = store_path()
    out: dict = {"store": path, "exists": False, "entries": 0,
                 "model_token": model_token(),
                 "worst_residual_pct": None}
    if not path:
        return out
    out["exists"] = os.path.exists(path)
    entries = load(path)
    # only entries fit against the CURRENT model version count — a
    # stale-token entry will never be applied, so reporting its
    # residual as live calibration state would overstate coverage
    live = {k: v for k, v in entries.items()
            if k.endswith(f"|{model_token()}") and isinstance(v, dict)}
    out["entries"] = len(live)
    worst = None
    worst_key = None
    for key, e in live.items():
        for t, pct in (e.get("term_residual_pct") or {}).items():
            if isinstance(pct, (int, float)) and (
                    worst is None or abs(pct) > abs(worst)):
                worst, worst_key = pct, f"{key}:{t}"
    out["worst_residual_pct"] = worst
    out["worst_residual_key"] = worst_key
    return out


def validate_calibration(cal) -> List[str]:
    """Structural validation of a block's ``calibration`` field.
    Returns error strings, empty when well-formed.  An absent overlay must still be EXPLICIT: the field
    is a dict with ``applied: false``, never missing-and-implied.
    A compat shim over the artifact-schema catalog
    (:mod:`knn_tpu.analysis.artifacts`, the ``calibration`` entry):
    the engine's canonical phrasing is normalized, this entry point
    keeps the historical strings so postmortem/doctor renderings stay
    stable."""
    from knn_tpu.analysis.artifacts import validate

    return validate("calibration", cal, style="legacy")


def reset() -> None:
    """Drop the read memo (test isolation)."""
    with _lock:
        _read_memo.clear()
