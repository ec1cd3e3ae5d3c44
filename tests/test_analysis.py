"""The static-analysis suite itself (knn_tpu.analysis, docs/ANALYSIS.md):
framework semantics (registry, suppression grammar, crash-to-finding),
one known-bad and one known-good fixture per checker, the geometry/width
mirror pins of the VMEM model (the row widths against the arrays the
kernel builds), the default knobs priced at every benchmark cell's
shape, the runtime lock-order (deadlock) harness over the real serving
stack, and the ``cli lint`` subprocess exit-code contract.

The fixture trees seed deliberate violations (uncataloged switches,
phantom metrics, unlocked mutations) — tests/ is exempt from the lint's
source roots precisely so these seeds never trip the real gate.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading

import jax.numpy as jnp
import numpy as np
import pytest
from cells import CONFIGS as CELL_CONFIGS, config as cell_config

from knn_tpu import analysis
from knn_tpu.analysis import switches as sw
from knn_tpu.analysis import vmem
from knn_tpu.analysis.check_vmem import default_findings
from knn_tpu.analysis.core import CHECKERS, load_suppressions
from knn_tpu.analysis.lockorder import (
    InstrumentedLock,
    LockOrderRecorder,
    instrument,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_tree(root, files):
    for rel, text in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(text))


def run_on(root, checker):
    return analysis.run(str(root), names=[checker])


# --- framework ----------------------------------------------------------
def test_registry_has_the_six_checkers():
    assert set(CHECKERS) == {"switch-lockstep", "metric-lockstep",
                             "locked-mutation", "jax-hygiene",
                             "vmem-budget", "artifact-lockstep"}


def test_unknown_checker_raises():
    with pytest.raises(ValueError, match="unknown checker"):
        analysis.run(REPO, names=["no-such-checker"])


def test_syntax_error_becomes_finding(tmp_path):
    write_tree(tmp_path, {"knn_tpu/broken.py": "def f(:\n"})
    rep = run_on(tmp_path, "locked-mutation")
    assert not rep.ok
    assert any(f.checker == "framework" and "does not parse" in f.message
               for f in rep.findings)


def test_text_only_pass_skips_the_parse(tmp_path):
    """A pass selecting only non-AST checkers (the lint_metric_names
    shim's metric-lockstep run) keeps the original text lint's
    tolerance of unparseable files — no whole-tree parse, no
    syntax-error findings that would be wrong for a pass in which no
    AST checker ran."""
    write_tree(tmp_path, {"knn_tpu/broken.py": "def f(:\n"})
    rep = run_on(tmp_path, "metric-lockstep")
    assert rep.ok, [f.message for f in rep.findings]
    rep2 = run_on(tmp_path, "vmem-budget")
    assert not any(f.checker == "framework" for f in rep2.findings)


def test_checker_crash_becomes_finding(tmp_path):
    write_tree(tmp_path, {"knn_tpu/ok.py": "x = 1\n"})

    def boom(ctx):
        raise RuntimeError("kaboom")

    CHECKERS["test-boom"] = (boom, "always crashes")
    try:
        rep = analysis.run(str(tmp_path), names=["test-boom"])
    finally:
        del CHECKERS["test-boom"]
    assert not rep.ok
    assert any("checker crashed" in f.message and "kaboom" in f.message
               for f in rep.findings)


def test_report_json_shape(tmp_path):
    write_tree(tmp_path, {"knn_tpu/ok.py": "x = 1\n"})
    rep = run_on(tmp_path, "locked-mutation")
    d = rep.as_dict()
    assert d["ok"] is True
    assert d["checkers"] == ["locked-mutation"]
    assert d["findings"] == [] and d["suppressed"] == 0
    assert "OK" in rep.render_text()


# --- suppression grammar ------------------------------------------------
def _sup_file(tmp_path, payload):
    p = tmp_path / "sup.json"
    p.write_text(json.dumps(payload))
    return str(p)


def test_suppression_requires_written_justification(tmp_path):
    path = _sup_file(tmp_path, {"suppressions": [
        {"checker": "jax-hygiene", "path": "a.py", "contains": "x",
         "justification": "because"}]})  # < 10 chars
    sups, errors = load_suppressions(path)
    assert sups == []
    assert any("justification" in e.message for e in errors)


def test_suppression_rejects_unknown_keys_and_shapes(tmp_path):
    path = _sup_file(tmp_path, {"suppressions": [
        {"checker": "jax-hygiene", "line": 3,
         "justification": "long enough justification"}]})
    _, errors = load_suppressions(path)
    assert any("unknown keys" in e.message for e in errors)
    path2 = _sup_file(tmp_path, {"not-suppressions": []})
    _, errors2 = load_suppressions(path2)
    assert any("top level" in e.message for e in errors2)


def test_stale_suppression_is_a_finding(tmp_path):
    write_tree(tmp_path, {"knn_tpu/ok.py": "x = 1\n"})
    path = _sup_file(tmp_path, {"suppressions": [
        {"checker": "locked-mutation", "path": "knn_tpu/gone.py",
         "contains": "self._x",
         "justification": "outlived the code it excused"}]})
    rep = analysis.run(str(tmp_path), names=["locked-mutation"],
                       suppressions_path=path)
    assert not rep.ok
    assert any("stale suppression" in f.message for f in rep.findings)


def test_subset_run_does_not_condemn_other_checkers_suppressions(
        tmp_path):
    """A metric-lockstep-only pass (the lint_metric_names shim) must not
    flag the jax-hygiene suppressions as stale."""
    write_tree(tmp_path, {"knn_tpu/ok.py": "x = 1\n"})
    path = _sup_file(tmp_path, {"suppressions": [
        {"checker": "jax-hygiene", "path": "knn_tpu/obs/trace.py",
         "contains": "time.time",
         "justification": "wall timestamp by contract, never differenced"
         }]})
    rep = analysis.run(str(tmp_path), names=["locked-mutation"],
                       suppressions_path=path)
    assert rep.ok, [f.message for f in rep.findings]
    # ...but an entry naming a checker that doesn't exist is stale in
    # EVERY pass
    path2 = _sup_file(tmp_path, {"suppressions": [
        {"checker": "no-such-checker", "path": "", "contains": "x",
         "justification": "points at nothing that could ever match"}]})
    rep2 = analysis.run(str(tmp_path), names=["locked-mutation"],
                        suppressions_path=path2)
    assert any("stale suppression" in f.message for f in rep2.findings)


def test_matching_suppression_silences_and_counts(tmp_path):
    write_tree(tmp_path, {"knn_tpu/mod.py": '''
        import time

        def f():
            return time.time()
        '''})
    rep = analysis.run(str(tmp_path), names=["jax-hygiene"])
    assert not rep.ok and rep.findings[0].symbol == "time.time"
    path = _sup_file(tmp_path, {"suppressions": [
        {"checker": "jax-hygiene", "path": "knn_tpu/mod.py",
         "contains": "time.time",
         "justification": "fixture wall timestamp, never differenced"}]})
    rep2 = analysis.run(str(tmp_path), names=["jax-hygiene"],
                        suppressions_path=path)
    assert rep2.ok and rep2.suppressed == 1


# --- switch-lockstep ----------------------------------------------------
ALL_SWITCH_NAMES = "\n".join(s.name for s in sw.SWITCHES)

GOOD_SWITCH_TREE = {
    # a CODE literal (not a docstring): consumption is judged on code
    "knn_tpu/mod.py": f'_READS = """\n{ALL_SWITCH_NAMES}\n"""\n',
    "docs/SWITCHES.md": ALL_SWITCH_NAMES + "\n",
    "tests/conftest.py": """
        import os

        from knn_tpu.analysis.switches import isolation_names

        for k in isolation_names(os.environ):
            os.environ.pop(k, None)
        """,
}


def test_switch_checker_passes_known_good_tree(tmp_path):
    write_tree(tmp_path, GOOD_SWITCH_TREE)
    rep = run_on(tmp_path, "switch-lockstep")
    assert rep.ok, [f.message for f in rep.findings]


def test_switch_checker_flags_uncataloged_switch(tmp_path):
    tree = dict(GOOD_SWITCH_TREE)
    tree["knn_tpu/rogue.py"] = '''
        import os

        FLAG = os.environ.get("KNN_TPU_TOTALLY_BOGUS")
        '''
    write_tree(tmp_path, tree)
    rep = run_on(tmp_path, "switch-lockstep")
    assert not rep.ok
    hits = [f for f in rep.findings if f.symbol == "KNN_TPU_TOTALLY_BOGUS"]
    assert hits and "not declared in the switch catalog" in hits[0].message
    assert hits[0].path == os.path.join("knn_tpu", "rogue.py")


def test_switch_checker_flags_phantom_doc_and_missing_doc(tmp_path):
    tree = dict(GOOD_SWITCH_TREE)
    tree["docs/SWITCHES.md"] = (
        ALL_SWITCH_NAMES.replace("KNN_TPU_OBS_LOG\n", "")
        + "\nKNN_TPU_PHANTOM_KNOB\n")
    write_tree(tmp_path, tree)
    rep = run_on(tmp_path, "switch-lockstep")
    msgs = [f.message for f in rep.findings]
    assert any("KNN_TPU_OBS_LOG is missing from the docs" in m
               for m in msgs)
    assert any("KNN_TPU_PHANTOM_KNOB" in m and "phantom" in m
               for m in msgs)


def test_switch_checker_flags_handlisted_conftest(tmp_path):
    tree = dict(GOOD_SWITCH_TREE)
    tree["tests/conftest.py"] = '''
        import os

        os.environ.pop("KNN_TPU_OBS", None)  # hand-listed, not derived
        '''
    write_tree(tmp_path, tree)
    rep = run_on(tmp_path, "switch-lockstep")
    assert any("isolation_names" in f.message for f in rep.findings)


def test_isolation_names_generated_from_catalog():
    names = sw.isolation_names()
    # every concrete isolate=True switch, no family prefixes
    assert "KNN_TPU_OBS" in names and "KNN_TPU_IVF_NPROBE" in names
    assert not any(n.endswith("_") for n in names)
    # ambient members of an isolated family prefix are swept in
    env = {"KNN_TPU_IVF_FUTURE_KNOB": "1", "UNRELATED": "x"}
    names_env = sw.isolation_names(env)
    assert "KNN_TPU_IVF_FUTURE_KNOB" in names_env
    assert "UNRELATED" not in names_env
    assert names_env == sorted(set(names_env))


def test_switch_checker_docstring_mention_is_not_consumption(tmp_path):
    """A switch named ONLY in a docstring reads as never-consumed: a
    deleted env read whose docstring survives must not keep a phantom
    catalog row alive."""
    tree = dict(GOOD_SWITCH_TREE)
    tree["knn_tpu/mod.py"] = (
        f'"""Docs mention KNN_TPU_OBS_LOG here."""\n_READS = """\n'
        + ALL_SWITCH_NAMES.replace("KNN_TPU_OBS_LOG\n", "")
        + '\n"""\n')
    write_tree(tmp_path, tree)
    rep = run_on(tmp_path, "switch-lockstep")
    assert any(f.symbol == "KNN_TPU_OBS_LOG"
               and "never read by source" in f.message
               for f in rep.findings)


def test_switch_checker_family_prefix_consumption(tmp_path):
    """A family's members count as consumed through the family prefix
    appearing as a code literal (admission.py reads its whole family
    wholesale) — but the RESERVED root namespaces never consume
    anything, or the invariant would be vacuous."""
    members = [s.name for s in sw.SWITCHES
               if s.name.startswith("KNN_TPU_ADMISSION_")
               and not s.family]
    assert members, "catalog lost its admission rows?"
    kept = "\n".join(n for n in ALL_SWITCH_NAMES.splitlines()
                     if not n.startswith("KNN_TPU_ADMISSION_"))
    tree = dict(GOOD_SWITCH_TREE)
    # members consumed only via the non-reserved family prefix: green
    tree["knn_tpu/mod.py"] = (
        f'_READS = """\n{kept}\n"""\n'
        f'ENV_PREFIX = "KNN_TPU_ADMISSION_"\n')
    write_tree(tmp_path, tree)
    rep = run_on(tmp_path, "switch-lockstep")
    assert rep.ok, [f.message for f in rep.findings]
    # the reserved KNN_TPU_ root prefix (always in code via the flight
    # recorder) must NOT stand in for the members
    tree["knn_tpu/mod.py"] = (
        f'_READS = """\n{kept}\n"""\n_ROOT = "KNN_TPU_"\n')
    write_tree(tmp_path, tree)
    rep2 = run_on(tmp_path, "switch-lockstep")
    flagged = {f.symbol for f in rep2.findings
               if "never read by source" in f.message}
    assert set(members) <= flagged


def test_lookup_family_semantics():
    assert sw.lookup("KNN_TPU_OBS") is not None
    assert sw.lookup("KNN_TPU_ADMISSION_") is not None  # declared prefix
    # a concrete member of a family still needs its own catalog row
    assert sw.lookup("KNN_TPU_ADMISSION_NOPE") is None
    assert sw.lookup("KNN_TPU_TOTALLY_BOGUS") is None


# --- metric-lockstep ----------------------------------------------------
def test_metric_checker_passes_known_good_tree(tmp_path):
    write_tree(tmp_path, {"knn_tpu/mod.py": '''
        NAME = "knn_tpu_serving_requests_total"
        SUFFIXED = "knn_tpu_serving_requests_total_count"  # prom summary
        '''})
    rep = run_on(tmp_path, "metric-lockstep")
    assert rep.ok, [f.message for f in rep.findings]


def test_metric_checker_flags_uncataloged_literal(tmp_path):
    write_tree(tmp_path, {"knn_tpu/mod.py": '''
        NAME = "knn_tpu_bogus_metric_total"
        '''})
    rep = run_on(tmp_path, "metric-lockstep")
    assert not rep.ok
    assert any(f.symbol == "knn_tpu_bogus_metric_total"
               for f in rep.findings)


def test_metric_shim_same_exit_codes():
    """scripts/lint_metric_names.py is a thin shim over the framework
    checker: exit 0 on the green tree (the historical contract the
    check_tier1 wiring relies on)."""
    proc = subprocess.run(
        [sys.executable, os.path.join("scripts", "lint_metric_names.py")],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


# --- locked-mutation ----------------------------------------------------
BAD_CLASS = '''
    import threading


    class Box:
        """A shared box.

        Thread-safety: guarded by ``self._lock``.
        """

        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0
            self._items = {}

        def bad(self):
            self._count = 1
            self._count += 1
            self._items["k"] = 2

        def good(self):
            with self._lock:
                self._count = 3
                self._items["k"] = 4

        def helper(self):
            """Bump the count.  Caller holds ``self._lock``."""
            self._count += 1
    '''


def test_concurrency_checker_flags_unlocked_writes(tmp_path):
    write_tree(tmp_path, {"knn_tpu/box.py": BAD_CLASS})
    rep = run_on(tmp_path, "locked-mutation")
    assert not rep.ok
    syms = [f.symbol for f in rep.findings]
    assert syms.count("Box.bad") == 3  # assign, augassign, subscript
    # locked writes and Caller-holds helpers are clean
    assert all(s == "Box.bad" for s in syms)


def test_concurrency_checker_passes_locked_class(tmp_path):
    good = BAD_CLASS.replace('''
        def bad(self):
            self._count = 1
            self._count += 1
            self._items["k"] = 2
''', "")
    write_tree(tmp_path, {"knn_tpu/box.py": good})
    rep = run_on(tmp_path, "locked-mutation")
    assert rep.ok, [f.message for f in rep.findings]


def test_concurrency_checker_flags_nested_callback_write(tmp_path):
    """A nested def's body runs when CALLED, not where it is defined:
    a callback built under the lock (fut.add_done_callback) executes
    later on another thread with no lock held, so the enclosing
    ``with self._lock:`` must not cover its writes."""
    write_tree(tmp_path, {"knn_tpu/cb.py": '''
        import threading


        class Box:
            """Thread-safety: guarded by ``self._lock``."""

            def __init__(self):
                self._lock = threading.Lock()
                self._done = 0

            def submit(self, fut):
                with self._lock:
                    def _cb(_fut):
                        self._done += 1
                    fut.add_done_callback(_cb)

            def locked_nested(self, fut):
                def _cb(_fut):
                    with self._lock:
                        self._done += 1  # takes the lock itself: clean
                fut.add_done_callback(_cb)
        '''})
    rep = run_on(tmp_path, "locked-mutation")
    assert not rep.ok
    syms = [f.symbol for f in rep.findings]
    assert syms == ["Box.submit"]


def test_concurrency_checker_flags_other_store_contexts(tmp_path):
    """`for self._x in ...:` and `with ... as self._x:` rebind shared
    attributes exactly like assignments and must be flagged outside
    the lock — and stay clean inside it."""
    write_tree(tmp_path, {"knn_tpu/stores.py": '''
        import threading


        class Box:
            """Thread-safety: guarded by ``self._lock``."""

            def __init__(self):
                self._lock = threading.Lock()
                self._cursor = 0
                self._fh = None

            def bad_loop(self, chunks):
                for self._cursor in chunks:
                    pass

            def bad_with(self, path):
                with open(path) as self._fh:
                    pass

            def good_loop(self, chunks):
                with self._lock:
                    for self._cursor in chunks:
                        pass
        '''})
    rep = run_on(tmp_path, "locked-mutation")
    assert not rep.ok
    syms = sorted(f.symbol for f in rep.findings)
    assert syms == ["Box.bad_loop", "Box.bad_with"]


def test_concurrency_checker_flags_marker_guarding_nothing(tmp_path):
    write_tree(tmp_path, {"knn_tpu/empty.py": '''
        import threading


        class Empty:
            """Thread-safety: guarded by ``self._lock``."""

            def method(self):
                return 1
        '''})
    rep = run_on(tmp_path, "locked-mutation")
    assert any("guards nothing" in f.message or "no shared attributes"
               in f.message for f in rep.findings)


def test_annotated_runtime_classes_lint_clean():
    """The five thread-safe classes the suite annotates (registry
    instruments, QueryQueue, ServingEngine, SLOEngine, PhaseTimer) pass
    the checker on the real tree — with only the justified single-writer
    suppression (queue completer's service-rate state)."""
    rep = analysis.run(REPO, names=["locked-mutation"])
    assert rep.ok, [f.message for f in rep.findings]
    assert rep.suppressed == 1
    text = open(os.path.join(REPO, "knn_tpu", "serving", "queue.py"),
                encoding="utf-8").read()
    assert "Thread-safety: guarded by ``self._cond``" in text


# --- jax-hygiene --------------------------------------------------------
def test_jax_checker_flags_wall_clock_in_library_only(tmp_path):
    write_tree(tmp_path, {
        "knn_tpu/mod.py": '''
            import time

            def f():
                return time.time()

            def g():
                return time.perf_counter()
            ''',
        "scripts/driver.py": '''
            import time

            STARTED = time.time()  # session drivers are out of scope
            ''',
    })
    rep = run_on(tmp_path, "jax-hygiene")
    assert len(rep.findings) == 1
    assert rep.findings[0].path == os.path.join("knn_tpu", "mod.py")


def test_jax_checker_hot_path_and_allow(tmp_path):
    write_tree(tmp_path, {"knn_tpu/hot.py": '''
        import numpy as np

        from knn_tpu.analysis.annotations import hot_path


        @hot_path
        def dispatch(x):
            y = np.asarray(x)          # finding: host sync on hot path
            x.block_until_ready()      # finding
            return y


        @hot_path(allow=("np.asarray",))
        def coerce(x):
            return np.asarray(x)       # whitelisted at the annotation


        def cold(x):
            return np.asarray(x)       # unannotated: out of scope
        '''})
    rep = run_on(tmp_path, "jax-hygiene")
    syms = sorted(f.symbol for f in rep.findings)
    assert syms == ["dispatch", "dispatch"]


def test_jax_checker_static_arg_hygiene(tmp_path):
    write_tree(tmp_path, {"knn_tpu/jit.py": '''
        from functools import partial

        import jax


        @partial(jax.jit, static_argnames=("shape",))
        def build(x, shape=[8, 8]):
            return x


        def caller(x):
            return build(x, shape=[16, 16])
        '''})
    rep = run_on(tmp_path, "jax-hygiene")
    msgs = [f.message for f in rep.findings]
    assert any("unhashable default" in m for m in msgs)
    assert any("unhashable list" in m for m in msgs)


# --- vmem model: mirror pins against the source modules -----------------
def test_vmem_geometry_mirrors_pallas_kernel():
    from knn_tpu.ops import pallas_knn as pk

    assert vmem.TILE_N_DEFAULT == pk.TILE_N
    assert vmem.BLOCK_Q_DEFAULT == pk.BLOCK_Q
    assert vmem.BIN_W == pk.BIN_W
    assert vmem.DIM_CHUNK == pk.DIM_CHUNK
    assert vmem.MAX_CARRY_DEPTH == pk.MAX_CARRY_DEPTH
    # DIM_CHUNK is the padding grain and the other two kernels' chunk;
    # how a launch cuts its tile is the rules', and the kernel asks the
    # model's rules
    budget = vmem.budget_for(vmem.TARGET_DEVICE_KIND)
    for dim in (8, 128, 201, 300, 512, 640, 960, 1536, 4096):
        padded = -(-dim // vmem.DIM_CHUNK) * vmem.DIM_CHUNK
        for kernel in ("tiled", "streaming"):
            assert pk.dim_chunking(
                dim, precision="bf16x3", kernel=kernel) == vmem.dim_chunking(
                padded, kernel=kernel)
        for bq, tile in ((256, 16384), (128, 32768), (8, 256)):
            for terms, parts in (("hh+hl+lh", None), ("hh", 1)):
                for masked in (False, True):
                    assert pk.row_blocking(
                        dim, tile_n=tile, block_q=bq, precision="bf16x3",
                        terms=terms, masked=masked) == vmem.row_blocking(
                        padded, tile_n=tile, block_q=bq, db_parts=parts,
                        masked=masked, budget_bytes=budget), (
                        dim, bq, tile, terms, masked)


def test_vmem_operand_widths_mirror_the_row_widths():
    from knn_tpu.analysis import widths

    assert set(vmem.DB_PARTS) == set(widths.DB_ELEM_BYTES)
    for prec, (n_parts, chunk_w, elem_b) in vmem.DB_PARTS.items():
        per_dim = n_parts * chunk_w * elem_b / vmem.DIM_CHUNK
        assert per_dim == widths.DB_ELEM_BYTES[prec], prec
        assert widths.aux_rows_for(prec) == vmem.AUX_ROWS.get(
            prec, vmem.AUX_ROWS_DEFAULT)


def test_operand_width_tables_are_the_shared_widths_objects():
    """Identity pin: every consumer re-exports the ONE width table in
    knn_tpu.analysis.widths — the SAME objects, not copies.  An `is`
    here (vs `==`) rules out the drift mode where a consumer forks its
    table, passes today's equality, and then diverges on the next new
    precision arm."""
    from knn_tpu.analysis import hbm, widths

    assert vmem.DB_PARTS is widths.DB_PARTS
    assert vmem.AUX_ROWS is widths.AUX_ROWS
    assert vmem.DIM_CHUNK == widths.DIM_CHUNK
    # ints are compared by value (an int re-export has no alias risk)
    assert hbm.AUX_BYTES_PER_ROW == widths.AUX_BYTES_PER_ROW


# --- the row widths against the arrays the kernel builds ------------------
def _actual_operand_nbytes(db, precision):
    """Build the db-side operand arrays exactly as
    ops.pallas_knn._bin_candidates does and return their real nbytes."""
    n = db.shape[0]
    if precision == "bf16x3":
        th = db.astype(jnp.bfloat16)
        tl = (db - th.astype(jnp.float32)).astype(jnp.bfloat16)
        values = th.nbytes + tl.nbytes
        aux = jnp.broadcast_to(
            jnp.sum(db * db, axis=-1)[None, :], (8, n)).nbytes
    elif precision == "bf16x3f":
        th = db.astype(jnp.bfloat16)
        tl = (db - th.astype(jnp.float32)).astype(jnp.bfloat16)
        t3 = jnp.concatenate([th, tl, th], axis=1)
        values = t3.nbytes
        aux = jnp.broadcast_to(
            jnp.sum(db * db, axis=-1)[None, :], (8, n)).nbytes
    elif precision == "int8":
        from knn_tpu.ops.quantize import quantize_rows

        ti, ts = quantize_rows(db)
        values = ti.nbytes
        tn = jnp.sum(db * db, axis=-1)
        aux = jnp.concatenate([
            jnp.broadcast_to(tn[None, :], (8, n)),
            jnp.broadcast_to(ts[None, :].astype(jnp.float32), (8, n)),
        ], axis=0).nbytes
    elif precision == "pq":
        # the streamed operand is the [N, ceil(d/dsub)] uint8 code
        # array (shape-determined — training moves no extra bytes)
        # plus the 8-row pad-fill carrier
        m_sub = -(-db.shape[1] // 4)
        values = jnp.zeros((n, m_sub), jnp.uint8).nbytes
        aux = jnp.broadcast_to(
            jnp.zeros((n,), jnp.float32)[None, :], (8, n)).nbytes
    else:  # highest streams the raw f32 rows
        values = db.astype(jnp.float32).nbytes
        aux = jnp.broadcast_to(
            jnp.sum(db * db, axis=-1)[None, :], (8, n)).nbytes
    return int(values), int(aux)


@pytest.mark.parametrize("precision",
                         ["bf16x3", "bf16x3f", "int8", "pq", "highest"])
def test_db_byte_terms_match_actual_operand_nbytes(rng, precision):
    """Property: the widths' per-pass db byte terms equal the nbytes of
    the arrays the kernel really streams, across the f32/bf16/int8/pq
    operand families."""
    from knn_tpu.analysis import widths

    n, d = 512, 128
    db = jnp.asarray(rng.random((n, d), dtype=np.float32) * 128)
    values_b, aux_b = _actual_operand_nbytes(db, precision)
    assert widths.db_operand_nbytes(n, d, precision) == {
        "db_values": values_b, "db_aux": aux_b}


def _pallas_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, found)
    return found


@pytest.mark.parametrize("kernel,grid_order,row_block,fetches", [
    ("tiled", "query_major", None, 1), ("tiled", "query_major", 128, 1),
    ("tiled", "db_major", None, 4), ("streaming", "query_major", None, 1)])
def test_the_tiled_kernels_query_block_streams_once(kernel, grid_order,
                                                    row_block, fetches):
    """The tiled kernel multiplies the whole padded width a step (a tile
    too wide for VMEM is cut by rows), so under ``query_major`` its
    query block's index moves with the query block alone: the queries
    stream once, whatever the tiles and their row blocks; under
    ``db_major`` once a row tile.  Read off the launch's own index maps:
    how often the query operand's block index changes over the grid, in
    the order the grid is walked."""
    import itertools

    import jax

    from knn_tpu.ops import pallas_knn as pk

    nq, n, d, block_q, tile_n = 64, 1024, 256, 16, 256  # 4 x 4 blocks
    traced = jax.make_jaxpr(lambda q, db: pk._bin_candidates(
        q, db, block_q=block_q, tile_n=tile_n, survivors=None,
        precision="bf16x3", interpret=True, grid_order=grid_order,
        kernel=kernel, row_block=row_block))(
            jnp.zeros((nq, d), jnp.float32), jnp.zeros((n, d), jnp.float32))
    (call,) = _pallas_calls(traced.jaxpr, [])
    mapping = call.params["grid_mapping"]
    assert len(mapping.grid) == (1 if kernel == "streaming" else 3)
    if kernel == "tiled":
        assert mapping.grid[2] == (1 if row_block is None
                                   else tile_n // row_block)
    index_map = mapping.block_mappings[0].index_map_jaxpr  # the queries
    moved, last = 0, None
    for step in itertools.product(*[range(g) for g in mapping.grid]):
        at = tuple(int(v) for v in jax.core.eval_jaxpr(
            index_map.jaxpr, index_map.consts,
            *[np.int32(x) for x in step]))
        moved, last = moved + (at != last), at
    assert moved == fetches * (nq // block_q)


def test_geometry_defaults_mirror_kernel_constants():
    """The jax-free module mirrors the kernel's geometry defaults (the
    constants: ``test_vmem_geometry_mirrors_pallas_kernel``); a drift
    here would mis-price every default-knob launch."""
    from knn_tpu.ops import pallas_knn as pk

    n_bins, surv, out_w, bound_w = pk._geometry(pk.TILE_N)
    assert surv == vmem.SURVIVORS_GROUPED_DEFAULT
    # grouped default survivors=2 -> the out/bound widths the launch
    # estimate's candidate output blocks assume
    assert out_w == surv * pk.BIN_W and bound_w == pk.BIN_W
    geo = vmem.launch_estimate(**vmem.HEADLINE_SHAPE)["geometry"]
    assert (geo["out_w"], geo["bound_w"]) == (out_w, bound_w)


def test_sub_int8_row_bytes_pinned():
    """Pinned byte ratios at SIFT dims (docs/PERF.md precision
    ladder): int8 streams a quarter of the f32 row, pq at
    the default dsub=4 streams m = ceil(d/4) code bytes — m/(4d) of
    the f32 row, 1/16 at d=128."""
    from knn_tpu.analysis import widths

    f32 = widths.db_row_bytes(128, "highest")
    i8 = widths.db_row_bytes(128, "int8")
    pq = widths.db_row_bytes(128, "pq", dsub=4)
    assert (f32, i8, pq) == (512, 128, 32)
    assert pq / f32 == widths.pq_nsub(128, 4) / (4 * 128) == 1 / 16
    # int8's aux stacks 8 scale rows under the 8 norm rows every other
    # arm streams
    a = widths.db_operand_nbytes(1000, 128, "bf16x3")
    b = widths.db_operand_nbytes(1000, 128, "int8")
    assert 2 * a["db_aux"] == b["db_aux"]


def test_launch_estimate_breakdown_and_monotonicity():
    shape = dict(vmem.HEADLINE_SHAPE)
    est = vmem.launch_estimate(**shape)
    assert est["total_bytes"] == sum(est["breakdown"].values())
    small = vmem.launch_estimate(**shape, tile_n=8192)["total_bytes"]
    big = vmem.launch_estimate(**shape, tile_n=32768)["total_bytes"]
    assert small < big
    bq = vmem.launch_estimate(**shape, block_q=512)["total_bytes"]
    assert est["total_bytes"] < bq
    with pytest.raises(ValueError):
        vmem.launch_estimate(**shape, precision="float8")
    with pytest.raises(ValueError):
        vmem.launch_estimate(**shape, kernel="warp")


def test_budget_for_provenance():
    assert vmem.budget_for("TPU v5e") == 128 * vmem.MIB
    assert vmem.budget_for("TPU v3") == 16 * vmem.MIB
    # a TPU the table does not know is an error, not a default
    with pytest.raises(ValueError, match="VMEM_BYTES_BY_KIND"):
        vmem.budget_for("TPU v9x")
    with pytest.raises(ValueError, match="VMEM_BYTES_BY_KIND"):
        vmem.budget_for(None, "tpu")
    # no VMEM to budget on host backends: N/A, never a refusal
    assert vmem.budget_for(None, "cpu") is None
    assert vmem.budget_for("cpu") is None


def test_vmem_model_tracks_the_compiler_at_the_benchmark_shapes():
    """The scoped-VMEM need Mosaic reported (libtpu 0.0.34, v5e,
    ``scripts/aot_compile_check.py --probe``; bf16x3, 4,096 queries)
    against the model, in MiB: the model may run up to 8% over and 1%
    under, and must refuse exactly what the compiler refused."""
    shapes = {"sift": (1_000_000, 128, 100), "gist": (1_000_000, 960, 100),
              "glove": (1_183_514, 300, 50)}
    reported = [
        ("sift", "tiled", 128, 16384, 31.51),
        ("sift", "tiled", 256, 16384, 47.18),
        ("sift", "tiled", 128, 32768, 62.47),
        ("sift", "tiled", 256, 32768, 93.09),
        ("sift", "streaming", 128, 16384, 71.65),
        ("sift", "streaming", 256, 16384, 126.55),
        ("gist", "streaming", 128, 16384, 80.07),
        ("glove", "streaming", 128, 16384, 86.63),
        ("sift", "fused", 128, 16384, 73.32),
        ("sift", "fused", 256, 16384, 133.75),  # > 128 MiB physical
        ("gist", "fused", 128, 16384, 82.64),
    ]
    budget = vmem.budget_for(vmem.TARGET_DEVICE_KIND)
    for shape, kernel, bq, tile, need in reported:
        n, d, k = shapes[shape]
        est = vmem.launch_estimate(
            n=n, d=d, k=k, kernel=kernel, block_q=bq,
            tile_n=tile)["total_bytes"]
        assert 0.99 * need <= est / vmem.MIB <= 1.08 * need, (
            shape, kernel, bq, tile, est / vmem.MIB, need)
        assert (est <= budget) == (need <= 128), (shape, kernel, bq)
        if est <= budget:
            assert vmem.limit_bytes(est, budget) >= need * vmem.MIB


def test_vmem_model_bounds_the_row_cut_geometries():
    """The geometries ``row_blocking`` cuts by rows (PR 46), probed by
    bisection on the limit like the one-step ones below: GIST's 1,024
    columns were eight 128-column chunks with a 16 MiB accumulator when
    the model was fitted (81.94 MiB at bq256, 49.10 at bq128, 99.60 and
    a refused 162.41 at tile 32,768); now a step is 4,096 rows at the
    whole width whatever the tile.  The model may run 14% over and not
    under, the limit it asks for must cover the need, and the rule must
    have chosen that block."""
    shapes = {"gist": (1_000_000, 960, 100),
              "openai500k": (500_000, 1536, 100),
              "wide640": (1_000_000, 640, 100)}
    compiled_at = [
        ("gist", 256, 16384, 4096, 47),   # refused at 46
        ("gist", 128, 16384, 4096, 44),
        ("gist", 128, 32768, 4096, 44),
        ("gist", 256, 32768, 4096, 47),
        ("openai500k", 256, 16384, 4096, 71),
        ("wide640", 256, 16384, 4096, 32),
    ]
    budget = vmem.budget_for(vmem.TARGET_DEVICE_KIND)
    for shape, bq, tile, block, need in compiled_at:
        n, d, k = shapes[shape]
        est = vmem.launch_estimate(n=n, d=d, k=k, block_q=bq, tile_n=tile)
        assert est["geometry"]["dim_chunks"] == 1, shape
        assert (est["geometry"]["row_block"],
                est["geometry"]["row_steps"]) == (block, tile // block)
        assert est["breakdown"]["accum_scratch"] == 0
        total = est["total_bytes"]
        assert need <= total / vmem.MIB <= 1.14 * need, (
            shape, bq, tile, total / vmem.MIB, need)
        assert vmem.limit_bytes(total, budget) >= need * vmem.MIB
    # blocks the rule does not choose at GIST's width, by the kernel's
    # own arithmetic: 2,048 rows (25) and 8,192 (93)
    for block, need in ((2048, 25), (8192, 93)):
        total = sum(vmem.kernel_bytes(
            kernel="tiled", block_q=256, tile_n=16384, n_tiles=62, nd=1,
            out_w=256, bound_w=128, db_block=2 * block * 1024 * 2,
            aux_rows=8, q_block=256 * 1024 * 4, row_block=block,
            dim_padded=1024).values())
        assert need <= total / vmem.MIB <= 1.14 * need, (block, total)


def test_vmem_model_bounds_the_one_chunk_geometries():
    """The geometries ``dim_chunking`` collapses to ONE chunk (PR 32),
    probed the same way but by bisection on the limit: the least MiB the
    kernel compiled at.  GloVe's rows (384 padded columns) were three
    128-column chunks under the tiled kernel when the model was fitted
    (82.94 at bq256); now they and ``text2image2m5``'s 256 are one.
    The model may run 14% over, the limit it asks for (an eighth more)
    must cover the need, and the rule must have chosen one chunk for
    every one of them — and 128 columns under the other two kernels,
    whose one-chunk geometry at 512 columns Mosaic refuses."""
    shapes = {"text2image2m5": (2_500_000, 201, 10),
              "glove": (1_183_514, 300, 50),
              "wide512": (1_000_000, 512, 100)}
    compiled_at = [
        ("text2image2m5", "tiled", 256, 59),   # refused at 58
        ("glove", "tiled", 256, 79),
        ("wide512", "tiled", 256, 99),
    ]
    budget = vmem.budget_for(vmem.TARGET_DEVICE_KIND)
    for shape, kernel, bq, need in compiled_at:
        n, d, k = shapes[shape]
        est = vmem.launch_estimate(n=n, d=d, k=k, kernel=kernel, block_q=bq)
        padded = -(-d // vmem.DIM_CHUNK) * vmem.DIM_CHUNK
        assert est["geometry"]["dim_chunk"] == padded, shape
        assert est["geometry"]["dim_chunks"] == 1, shape
        total = est["total_bytes"]
        assert need <= total / vmem.MIB <= 1.14 * need, (
            shape, kernel, total / vmem.MIB, need)
        assert total <= budget
        assert vmem.limit_bytes(total, budget) >= need * vmem.MIB
        for other in ("streaming", "fused"):
            geo = vmem.launch_estimate(n=n, d=d, k=k, kernel=other,
                                       block_q=128)["geometry"]
            assert geo["dim_chunk"] == vmem.DIM_CHUNK, (shape, other)
            assert geo["dim_chunks"] == padded // vmem.DIM_CHUNK


def test_vmem_model_refuses_only_where_it_is_calibrated():
    """ONE rule: the model has the last word on bf16x3, where it was
    fitted; on every other arm it only describes
    (an upper bound at best: Mosaic put "highest" / "bf16x3f" streaming
    at SIFT bq256 at 111.19 / 119.19 MiB where the model says 126.75 /
    134.75), the verdict is N/A and the compiler decides."""
    assert vmem.calibrated("bf16x3")
    assert vmem.calibrated(None)  # the default
    for precision in ("bf16x3f", "highest", "int8", "pq"):
        assert not vmem.calibrated(precision)
    shape = dict(vmem.HEADLINE_SHAPE)
    over = {"kernel": "streaming", "block_q": 256, "precision": "bf16x3f"}
    verdict = vmem.check_candidate(over, **shape, device_kind="TPU v5e")
    assert verdict["estimate_bytes"] > verdict["budget_bytes"]
    assert verdict["checked"] is False and verdict["fits"] is None
    fitted = {"kernel": "fused", "block_q": 256}
    verdict = vmem.check_candidate(fitted, **shape, device_kind="TPU v5e")
    assert verdict["checked"] and verdict["fits"] is False


def test_check_candidate_verdicts():
    shape = dict(vmem.HEADLINE_SHAPE)
    ok = vmem.check_candidate({}, **shape, device_kind="TPU v5e")
    assert ok["checked"] and ok["fits"] is True
    over = vmem.check_candidate({"kernel": "streaming", "block_q": 4096},
                                **shape, device_kind="TPU v3")
    assert over["fits"] is False
    assert over["estimate_bytes"] > over["budget_bytes"]
    na = vmem.check_candidate({}, **shape, backend="cpu")
    assert na["checked"] is False and na["fits"] is None


def test_default_knobs_fit_target_device():
    from knn_tpu.tuning import DEFAULT_KNOBS

    verdict = vmem.check_candidate(
        DEFAULT_KNOBS, **vmem.HEADLINE_SHAPE,
        device_kind=vmem.TARGET_DEVICE_KIND)
    assert verdict["fits"] is True


def test_the_cells_priced_are_the_eleven():
    assert len(CELL_CONFIGS) == 11


@pytest.mark.parametrize("config", sorted(CELL_CONFIGS))
def test_default_knobs_fit_the_target_device_at_every_cells_shape(config):
    """What every cell runs (``require.tuning_source: "default"``) is
    priced at ITS shape, a chip's share of the rows: until PR 59 only
    the SIFT headline shape was."""
    from knn_tpu.tuning import DEFAULT_KNOBS

    cfg = cell_config(config)
    assert cfg["require"]["tuning_source"] == "default"
    verdict = vmem.check_candidate(
        DEFAULT_KNOBS, n=cfg["rows_n"] // cfg.get("db_shards", 1),
        d=cfg["dim"], k=cfg["k"], device_kind=vmem.TARGET_DEVICE_KIND)
    assert verdict["checked"] and verdict["fits"] is True, verdict
    assert 32 * vmem.MIB < verdict["estimate_bytes"] < verdict[
        "budget_bytes"] == 128 * vmem.MIB


def test_vmem_checker_flags_seeded_over_budget_defaults():
    """The known-bad fixture: a default knob set that overruns the
    target device must produce a vmem-budget finding (and would flip
    cli lint red); the real one produces none."""
    from knn_tpu.tuning import DEFAULT_KNOBS

    bad = {**DEFAULT_KNOBS, "kernel": "streaming", "tile_n": 32768}
    findings = default_findings(bad)
    assert findings and findings[0].checker == "vmem-budget"
    assert findings[0].symbol == "DEFAULT_KNOBS"
    assert "over TPU v5e's" in findings[0].message
    assert default_findings(DEFAULT_KNOBS) == []


def test_vmem_checker_green_on_repo():
    rep = analysis.run(REPO, names=["vmem-budget"])
    assert rep.ok, [f.message for f in rep.findings]


# --- lock-order harness (runtime deadlock detection) --------------------
def test_lockorder_detects_inversion():
    rec = LockOrderRecorder()
    a = InstrumentedLock("A", rec)
    b = InstrumentedLock("B", rec)
    t1_done = threading.Event()

    def t1():
        # A -> B ...
        with a:
            with b:
                pass
        t1_done.set()

    def t2():
        # ... and B -> A in another thread: an order inversion.  Run
        # strictly after t1 so the locks themselves can never deadlock
        # — the ORDER graph still has the cycle, which is the point:
        # the harness convicts the interleaving that got lucky.
        t1_done.wait(5)
        with b:
            with a:
                pass

    ts = [threading.Thread(target=t1), threading.Thread(target=t2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    cyc = rec.find_cycle()
    assert cyc is not None and cyc[0] == cyc[-1]
    with pytest.raises(AssertionError, match="lock-order cycle"):
        rec.assert_acyclic()


def test_lockorder_consistent_order_is_acyclic():
    rec = LockOrderRecorder()
    a = InstrumentedLock("A", rec)
    b = InstrumentedLock("B", rec)

    def worker():
        for _ in range(50):
            with a:
                with b:
                    pass

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert rec.order_graph()["A"] == {"B"}
    assert rec.find_cycle() is None
    rec.assert_acyclic()


def test_instrument_swaps_lock_attrs():
    rec = LockOrderRecorder()

    class HasLock:
        def __init__(self):
            self._lock = threading.Lock()

    class HasNeither:
        pass

    obj = HasLock()
    instrument(rec, thing=obj)
    assert isinstance(obj._lock, InstrumentedLock)
    with pytest.raises(ValueError, match="neither _lock nor _cond"):
        instrument(rec, bad=HasNeither())


def test_serving_stack_lock_order_acyclic_under_hammer(rng):
    """The 8-thread hammer over the REAL thread-safe classes (engine,
    queue, SLO engine, registry, a registry histogram) with every lock
    instrumented: the recorded acquisition-order graph must be acyclic —
    a cycle is a deadlock waiting for its interleaving even when this
    run got lucky."""
    from knn_tpu import obs
    from knn_tpu.obs import names as mn
    from knn_tpu.obs.slo import SLOEngine
    from knn_tpu.parallel import ShardedKNN, make_mesh
    from knn_tpu.serving import QueryQueue, ServingEngine

    db = (rng.random((64, 8)) * 32).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(), k=3)
    engine = ServingEngine(prog, buckets=(8, 16))
    engine.warmup()
    slo_engine = SLOEngine()
    rec = LockOrderRecorder()
    hist = obs.histogram(mn.SERVING_REQUEST_LATENCY, op="search")
    with QueryQueue(engine, max_wait_ms=1.0) as queue:
        instrument(rec, engine=engine, queue=queue, slo=slo_engine,
                   registry=obs.get_registry(), latency_hist=hist)
        barrier = threading.Barrier(8)
        errors = []

        def hammer(i):
            try:
                barrier.wait(10)
                futs = [queue.submit(db[: 1 + (i + j) % 8])
                        for j in range(4)]
                queue.stats()
                slo_engine.evaluate()
                for f in futs:
                    f.result(timeout=30)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        ts = [threading.Thread(target=hammer, args=(i,))
              for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    assert not errors, errors
    assert rec.edges(), "hammer recorded no lock interleavings"
    rec.assert_acyclic()


# --- cli lint subprocess contract ---------------------------------------
@pytest.mark.slow
def test_cli_lint_green_on_repo_json():
    proc = subprocess.run(
        [sys.executable, "-m", "knn_tpu.cli", "lint", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True
    assert payload["findings"] == []
    assert set(payload["checkers"]) == set(CHECKERS)
    assert payload["suppressed"] >= 1  # justified baseline, never hidden


@pytest.mark.slow
def test_cli_lint_seeded_regression_exits_nonzero(tmp_path):
    """An uncataloged switch in a lint root flips cli lint to exit 1
    with the finding in the JSON report."""
    write_tree(tmp_path, {"knn_tpu/rogue.py": '''
        import os

        FLAG = os.environ.get("KNN_TPU_TOTALLY_BOGUS")
        '''})
    proc = subprocess.run(
        [sys.executable, "-m", "knn_tpu.cli", "lint", "--json",
         "--root", str(tmp_path), "--checker", "switch-lockstep"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert any(f["symbol"] == "KNN_TPU_TOTALLY_BOGUS"
               for f in payload["findings"])


def test_full_suite_green_in_process():
    """The in-process twin of the subprocess gate: every checker over
    the real tree, zero findings, every suppression used and
    justified."""
    rep = analysis.run(REPO)
    assert rep.ok, rep.render_text()
    assert rep.suppressed >= 1
