"""On-disk winner cache for the kernel autotuner (knn_tpu.tuning).

One JSON file maps ``cache_key(device_kind, n, d, k, metric, dtype)``
to the measured winning knob set plus its provenance (timings, gate
verdict, jax version, timestamp).  The point is operational: every
hand-tuned TPU-session knob search so far died with the session — a
persisted winner keyed by the exact problem shape survives the session, so the next
``ShardedKNN.search_certified`` / bench run on the same chip resolves
its knobs from disk with ZERO re-timing.

File format (``version`` guards future migrations)::

    {
      "version": 1,
      "entries": {
        "TPU v5e|n1000000|d128|k100|l2|bfloat16": {
          "knobs": {"kernel": "streaming", "tile_n": 32768,
                    "block_q": 256, "grid_order": "query_major",
                    "precision": "bf16x3", ...},
          "winner_ms": 55.9,
          "timings_ms": {"<candidate label>": ms | null (ineligible)},
          "gate": "bitwise-vs-reference",
          "measured_at": "2026-08-03T...Z", "jax_version": "...",
          "n_queries": 64, "runs": 2
        }
      }
    }

Reads are memoized on (mtime, size) so hot paths (every
``search_certified`` call resolves) cost a ``stat``, not a parse;
writes are atomic (tmp + rename) so a crashed tune run can never leave
a torn cache behind.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Optional

CACHE_VERSION = 1

#: env override for the cache location — the tests and the CLI use it;
#: the default keeps per-user winners out of the repo tree
CACHE_ENV = "KNN_TPU_TUNE_CACHE"

_lock = threading.Lock()
#: path -> ((mtime_ns, size), entries) read memo
_read_memo: dict = {}


def default_cache_path() -> str:
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "knn_tpu", "autotune.json")


def kernel_version_token() -> str:
    """The kernel/emitter code version baked into every cache key
    (ops.pallas_knn.KERNEL_VERSION): a winner is a MEASUREMENT of one
    kernel build, so when the kernel code changes the persisted entry's
    key no longer matches and resolve falls back to defaults — stale
    winners self-invalidate instead of silently steering a kernel they
    never timed.  Lazy import: the cache module itself stays jax-free
    until a key is actually built."""
    try:
        from knn_tpu.ops.pallas_knn import KERNEL_VERSION

        return str(KERNEL_VERSION)
    except Exception:  # pragma: no cover - import failure -> never match
        return "unknown"


def roofline_token() -> str:
    """The roofline model version baked into every cache key: since
    entries carry the winner's ``roofline_pct``/``bound_class``
    attribution, an entry written under an older (or no) model would
    republish a verdict the current model never rendered — so the key
    version-bumps (the same self-invalidation mechanism as
    ``kv<KERNEL_VERSION>``) and pre-roofline entries fall back to
    defaults cleanly instead of carrying stale attributions."""
    try:
        from knn_tpu.obs.roofline import MODEL_VERSION

        return str(MODEL_VERSION)
    except Exception:  # pragma: no cover - import failure -> never match
        return "unknown"


#: Tuning regimes a winner can be keyed under.  ``latency`` is the
#: serving regime (small batches, time-to-first-result) and is the
#: default everywhere; ``throughput`` is the bulk kNN-join regime
#: (huge query superblocks, rows/s) whose grid reaches block_q values
#: a latency tune would never time.  Separate key suffix = separate
#: cache rows: a join winner can never clobber a serving winner.
PROFILES = ("latency", "throughput")


def cache_key(device_kind: str, n: int, d: int, k: int, metric: str,
              dtype: Optional[str], profile: str = "latency") -> str:
    """The shape key a winner is valid for.  ``dtype`` is the placement
    compute dtype (None = float32, the library default); any field
    mismatch MUST miss — a winner tuned for one shape says nothing
    about another.  The trailing ``rl<version>|kv<version>`` tokens tie
    the entry to the roofline-model schema its attribution was rendered
    under (:func:`roofline_token`) and the kernel code that was
    measured (:func:`kernel_version_token`); pre-token entries (no
    ``|rl``/``|kv`` suffix) miss the same way.  ``profile`` picks the
    tuning regime (:data:`PROFILES`): the default ``latency`` key is
    byte-identical to the pre-profile format (old caches keep
    hitting), while ``throughput`` appends a ``|throughput`` suffix so
    the two regimes' winners live in disjoint rows."""
    if profile not in PROFILES:
        raise ValueError(
            f"unknown tuning profile {profile!r}; expected one of "
            f"{PROFILES}")
    suffix = "" if profile == "latency" else f"|{profile}"
    return (f"{device_kind}|n{int(n)}|d{int(d)}|k{int(k)}|"
            f"{metric.lower()}|{dtype or 'float32'}"
            f"|rl{roofline_token()}"
            f"|kv{kernel_version_token()}" + suffix)


class TuneCache:
    """Handle on one cache file; ``get``/``put`` are the whole API."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()

    def load(self) -> dict:
        """All entries (empty dict when the file is absent/corrupt —
        a broken cache degrades to defaults, never to an error)."""
        try:
            st = os.stat(self.path)
        except OSError:
            return {}
        sig = (st.st_mtime_ns, st.st_size)
        with _lock:
            memo = _read_memo.get(self.path)
            if memo and memo[0] == sig:
                return memo[1]
        try:
            with open(self.path) as f:
                data = json.load(f)
            if not isinstance(data, dict) or data.get("version") != CACHE_VERSION:
                return {}
            entries = data.get("entries", {})
            if not isinstance(entries, dict):
                return {}
        except (OSError, json.JSONDecodeError):
            return {}
        with _lock:
            _read_memo[self.path] = (sig, entries)
        return entries

    def get(self, key: str) -> Optional[dict]:
        entry = self.load().get(key)
        return entry if isinstance(entry, dict) else None

    def put(self, key: str, entry: dict) -> None:
        """Insert/replace one entry; atomic write (tmp + rename)."""
        with _lock:
            entries = {}
            try:
                with open(self.path) as f:
                    data = json.load(f)
                if (isinstance(data, dict)
                        and data.get("version") == CACHE_VERSION
                        and isinstance(data.get("entries"), dict)):
                    entries = data["entries"]
            except (OSError, json.JSONDecodeError):
                pass
            entries[key] = entry
            payload = {"version": CACHE_VERSION, "entries": entries}
            d = os.path.dirname(os.path.abspath(self.path))
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(payload, f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            _read_memo.pop(self.path, None)
