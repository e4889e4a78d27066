"""tools/slate_lint framework tests (ISSUE 13): per-analyzer clean +
violating synthetic fixtures, the exemption/baseline paths, the CLI,
and the pin that the six ported legacy rules report identically to
the check_instrumented.py shim."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import check_instrumented as shim                # noqa: E402
from tools.slate_lint import (REGISTRY, core, generate_reference,
                              legacy)                       # noqa: E402


def _write(tmp_path, files):
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return str(tmp_path)


def _codes(findings):
    return sorted(f.code for f in findings)


def _only(repo, name, **kw):
    return core.run(repo=repo, only=name, **kw)


# -- registry / live tree ------------------------------------------------

def test_registry_covers_all_analyzers():
    assert set(REGISTRY) == {
        "instrumented", "kernel-registry", "resil-contract",
        "shard-lookahead", "precision", "tune-keys",
        "lock-discipline", "obs-literals", "fault-sites",
        "flight-recorder", "sched-graph", "reqtrace-ctx",
        "elastic-mesh"}
    codes = {c for a in REGISTRY.values() for c in a.codes}
    assert {"SL101", "SL102", "SL103", "SL104", "SL105", "SL106",
            "SL201", "SL202", "SL203", "SL301", "SL401", "SL402",
            "SL501", "SL502", "SL503", "SL601", "SL602",
            "SL603", "SL701", "SL702", "SL801",
            "SL802", "SL803", "SL901", "SL902", "SL903"} == codes


def test_clean_on_live_tree():
    """The acceptance gate: zero live findings, zero baseline entries
    on the committed tree (exemptions are in-code and justified)."""
    res = core.run(repo=REPO)
    assert res.findings == []
    assert res.baselined == []
    for f, why in res.exempted:
        assert why.strip()     # a bare marker never exempts


def test_legacy_rules_match_shim_on_live_tree():
    """The six ported rules report identically to the
    check_instrumented.py shim (and both are clean)."""
    msgs = []
    for name in ("instrumented", "kernel-registry", "resil-contract",
                 "shard-lookahead", "precision"):
        msgs += [f.message for f in REGISTRY[name].fn(REPO)]
    assert msgs == shim.check(REPO) == legacy.check_all(REPO) == []


def test_legacy_identity_on_violating_fixture(tmp_path):
    """Shim and ported rules emit THE SAME problem strings on a tree
    seeded with violations of every legacy rule family."""
    repo = _write(tmp_path, {
        "slate_tpu/batch/drivers.py": """
            def gesv_batched(stack, rhs):     # missing hook
                return rhs
        """,
    })
    required = {"slate_tpu/batch/drivers.py": ["potrf_batched"]}
    direct = legacy.check_all(repo, required=required)
    import unittest.mock as mock
    with mock.patch.object(shim, "REQUIRED", required):
        via_shim = shim.check(repo)
    assert direct == via_shim
    assert any("potrf_batched" in p and "lost its" in p
               for p in direct)
    assert any("gesv_batched" in p and "unobservable" in p
               for p in direct)
    assert any("file missing" in p for p in direct)   # kernel/resil


# -- tune-keys (SL201/SL202/SL203) --------------------------------------

_METHODS_FIXTURE = """
    def str2method(family, s):
        fam = {
            "ooc": object, "precision": object,
        }[family]
        return fam
"""


def test_tune_keys_clean(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/tune/cache.py": """
            FROZEN = {
                ("ooc", "panel_cols"): 8192,
                ("*", "nb"): 256,
            }
        """,
        "slate_tpu/core/methods.py": _METHODS_FIXTURE,
        "slate_tpu/linalg/ooc.py": """
            from ..tune.select import resolve, tuned_int
            from ..core.methods import str2method

            def width(n, dtype):
                m = str2method("ooc", "stream")
                nb = tuned_int("getrf", "nb", 256)
                return int(resolve("ooc", "panel_cols", n=n,
                                   dtype=dtype))
        """,
    })
    res = _only(repo, "tune-keys")
    assert res.findings == []


def test_tune_keys_catches_typo_orphan_and_family(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/tune/cache.py": """
            FROZEN = {
                ("ooc", "panel_cols"): 8192,
                ("dead", "row"): 1,
            }
        """,
        "slate_tpu/core/methods.py": _METHODS_FIXTURE,
        "slate_tpu/linalg/ooc.py": """
            from ..tune.select import resolve
            from ..core.methods import str2method

            def width(n, dtype):
                m = str2method("oocc", "stream")          # bad family
                return int(resolve("ooc", "panel_colz"))  # typo'd key

            def width_ok(n, dtype):
                return int(resolve("ooc", "panel_cols", n=n))
        """,
    })
    res = _only(repo, "tune-keys")
    assert _codes(res.findings) == ["SL201", "SL202", "SL203"]
    by = {f.code: f for f in res.findings}
    assert "panel_colz" in by["SL201"].message
    assert by["SL201"].path == "slate_tpu/linalg/ooc.py"
    assert "('dead', 'row')" in by["SL202"].message
    assert by["SL202"].line > 0          # anchored at the row itself
    assert "'oocc'" in by["SL203"].message


def test_tune_keys_dynamic_op_matches_any_row(tmp_path):
    """resolve(op, "chain") with a runtime op must satisfy any row
    carrying that param (the svd.py chain-route idiom) — and an
    orphan row whose param IS dynamically read stays matched."""
    repo = _write(tmp_path, {
        "slate_tpu/tune/cache.py": """
            FROZEN = {
                ("steqr2", "chain"): "dense",
                ("bdsqr", "chain"): "dense",
            }
        """,
        "slate_tpu/core/methods.py": _METHODS_FIXTURE,
        "slate_tpu/linalg/svd.py": """
            from ..tune.select import resolve

            def route(op, n, dt):
                return resolve(op, "chain", n=n, dtype=dt,
                               fallback="dense")
        """,
    })
    res = _only(repo, "tune-keys")
    assert res.findings == []


# -- lock-discipline (SL301) --------------------------------------------

_LOCKED_CLASS = """
    import threading

    class Cache:
        def __init__(self):
            self._lock = threading.Lock()
            self.hits = 0

        def get(self, k):
            with self._lock:
                self.hits += 1

        def stats(self):
            %s
            self.hits += 10          # unlocked mutation
            return self.hits
"""


def test_lock_discipline_catches_mixed_mutation(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/x.py": _LOCKED_CLASS % "pass",
    })
    res = _only(repo, "lock-discipline")
    assert _codes(res.findings) == ["SL301"]
    f = res.findings[0]
    assert "self.hits" in f.message and "stats()" in f.message
    assert f.path == "slate_tpu/x.py" and f.line > 0


def test_lock_discipline_exemption_comment(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/x.py": _LOCKED_CLASS
        % "# slate-lint: exempt[SL301] single-threaded stats path",
    })
    res = _only(repo, "lock-discipline")
    assert res.findings == []
    assert len(res.exempted) == 1
    assert res.exempted[0][1] == "single-threaded stats path"


def test_lock_discipline_bare_marker_does_not_exempt(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/x.py": _LOCKED_CLASS
        % "# slate-lint: exempt[SL301]",     # no justification
    })
    res = _only(repo, "lock-discipline")
    assert _codes(res.findings) == ["SL301"]


def test_lock_discipline_clean_class_and_init(tmp_path):
    """Consistently-locked mutations and __init__ construction are
    never flagged; a lock-free class is out of scope entirely."""
    repo = _write(tmp_path, {
        "slate_tpu/x.py": """
            import threading

            class Clean:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0           # construction: fine

                def bump(self):
                    with self._lock:
                        self.n += 1

            class NoLock:
                def __init__(self):
                    self.n = 0

                def bump(self):
                    self.n += 1          # no lock owned: fine
        """,
    })
    res = _only(repo, "lock-discipline")
    assert res.findings == []


def test_lock_discipline_module_globals(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/m.py": """
            import threading

            _lock = threading.Lock()
            _counters = {}

            def inc(name):
                with _lock:
                    _counters[name] = _counters.get(name, 0) + 1

            def reset():
                _counters.clear()        # unlocked mutation
        """,
    })
    res = _only(repo, "lock-discipline")
    assert _codes(res.findings) == ["SL301"]
    assert "_counters" in res.findings[0].message


def test_lock_discipline_nested_def_resets_lock_context(tmp_path):
    """A worker closure defined inside a `with lock:` block runs
    later on another thread — its mutations are unlocked."""
    repo = _write(tmp_path, {
        "slate_tpu/x.py": """
            import threading

            class Eng:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.secs = 0.0

                def a(self):
                    with self._lock:
                        self.secs += 1.0

                def b(self):
                    with self._lock:
                        def task():
                            self.secs += 2.0     # runs lock-free
                        return task
        """,
    })
    res = _only(repo, "lock-discipline")
    assert _codes(res.findings) == ["SL301"]


# -- obs-literals (SL401/SL402) -----------------------------------------

def test_obs_literals_catches_near_miss(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/q.py": """
            from .obs import metrics as om

            def record(k):
                om.inc("batch.dispatches")
                om.inc("batch.dispatchs", k)     # one-off typo
        """,
    })
    res = _only(repo, "obs-literals")
    near = [f for f in res.findings if f.code == "SL401"]
    assert len(near) == 1
    assert "batch.dispatchs" in near[0].message
    assert "batch.dispatches" in near[0].message


def test_obs_literals_separator_variants_collide(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/q.py": """
            from .obs import metrics as om

            def record():
                om.inc("ooc.cast_bytes")
                om.inc("ooc.cast.bytes")     # separator drift
        """,
    })
    res = _only(repo, "obs-literals")
    assert [f.code for f in res.findings if f.code == "SL401"] \
        == ["SL401"]


def test_obs_literals_kinds_are_separate_namespaces(tmp_path):
    """A counter and an instant may share a stem (the live tree's
    resil.fallbacks counter vs resil::fallback instant)."""
    repo = _write(tmp_path, {
        "slate_tpu/q.py": """
            from .obs import metrics as om
            from .obs import events as ev

            def record():
                om.inc("resil.fallbacks")
                ev.instant("resil::fallback", cat="resil")
        """,
    })
    res = _only(repo, "obs-literals")
    assert [f for f in res.findings if f.code == "SL401"] == []


def test_obs_doc_stale_and_regenerated(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/q.py": """
            from .obs import metrics as om

            def record():
                om.inc("ooc.h2d_bytes")
        """,
    })
    res = _only(repo, "obs-literals")
    assert any(f.code == "SL402" and "missing" in f.message
               for f in res.findings)
    doc = tmp_path / "docs" / "OBS_REFERENCE.md"
    doc.parent.mkdir()
    doc.write_text(generate_reference(repo))
    res = _only(repo, "obs-literals")
    assert [f for f in res.findings if f.code == "SL402"] == []
    # any drift (an edit, a new series) re-fails
    doc.write_text(doc.read_text() + "stray\n")
    res = _only(repo, "obs-literals")
    assert any(f.code == "SL402" and "stale" in f.message
               for f in res.findings)


def test_obs_reference_doc_matches_live_tree():
    """The checked-in docs/OBS_REFERENCE.md is exactly the generator
    output (the SL402 contract, pinned directly)."""
    with open(os.path.join(REPO, "docs", "OBS_REFERENCE.md")) as f:
        assert f.read() == generate_reference(REPO)


# -- fault-sites (SL501/SL502/SL503) ------------------------------------

_FAULTS_FIXTURE = """
    SITES = {
        "h2d": "uploads",
        "ghost": "documented but never checked",
    }

    def check(site, **ctx):
        return None
"""


def test_fault_sites_catches_all_three_drifts(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/resil/faults.py": _FAULTS_FIXTURE,
        "slate_tpu/linalg/stream.py": """
            from ..resil import faults as _faults

            def upload():
                _faults.check("h2d", buf="A")
                _faults.check("rogue", buf="B")   # not in SITES
        """,
        "tests/test_x.py": """
            PLAN = [{"site": "typo", "times": 1}]
        """,
    })
    res = _only(repo, "fault-sites")
    assert _codes(res.findings) == ["SL501", "SL502", "SL503"]
    by = {f.code: f for f in res.findings}
    assert "'ghost'" in by["SL501"].message
    assert "'rogue'" in by["SL502"].message
    assert by["SL502"].path == "slate_tpu/linalg/stream.py"
    assert "'typo'" in by["SL503"].message
    assert by["SL503"].path == "tests/test_x.py"


def test_fault_sites_cover_serve_daemon_drift(tmp_path):
    """ISSUE 16 satellite: the serve_* fault sites ride the same
    SL501/502/503 contract — an unchecked serve SITES row, a live
    check at an unlisted serve site, and a fault plan naming a
    near-miss serve site all surface on a serve-shaped tree."""
    repo = _write(tmp_path, {
        "slate_tpu/resil/faults.py": """
            SITES = {
                "serve_admit": "serve/server.py admission decisions",
                "serve_drain": "documented but never checked",
            }

            def check(site, **ctx):
                return None
        """,
        "slate_tpu/serve/server.py": """
            from ..resil import faults as _faults

            def submit(tenant, op):
                _faults.check("serve_admit", tenant=tenant, op=op)
                _faults.check("serve_cache", op=op)   # not in SITES
        """,
        "tests/test_serve.py": """
            PLAN = [{"site": "serve_admits", "times": 1}]
        """,
    })
    res = _only(repo, "fault-sites")
    assert _codes(res.findings) == ["SL501", "SL502", "SL503"]
    by = {f.code: f for f in res.findings}
    assert "'serve_drain'" in by["SL501"].message
    assert "'serve_cache'" in by["SL502"].message
    assert by["SL502"].path == "slate_tpu/serve/server.py"
    assert "'serve_admits'" in by["SL503"].message
    assert by["SL503"].path == "tests/test_serve.py"


def test_fault_sites_clean(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/resil/faults.py": """
            SITES = {"h2d": "uploads"}

            def check(site, **ctx):
                return None
        """,
        "slate_tpu/linalg/stream.py": """
            from ..resil import faults as _faults

            def _guard_transfer(site, fn, **ctx):
                _faults.check(site, **ctx)       # dynamic: ignored
                return fn()

            def upload(loader):
                return _guard_transfer("h2d", loader, buf="A")
        """,
        "tests/test_x.py": """
            PLAN = [{"site": "h2d", "times": 1}]
        """,
    })
    res = _only(repo, "fault-sites")
    assert res.findings == []


def test_fault_sites_bare_imported_check_is_live(tmp_path):
    """`from ..resil.faults import check; check("h2d", ...)` keeps
    the site live — only unrelated `.check()` receivers are ignored."""
    repo = _write(tmp_path, {
        "slate_tpu/resil/faults.py": """
            SITES = {"h2d": "uploads"}

            def check(site, **ctx):
                return None
        """,
        "slate_tpu/linalg/stream.py": """
            from ..resil.faults import check

            def upload():
                check("h2d", buf="A")
        """,
        "slate_tpu/other.py": """
            class V:
                def check(self, x):
                    return x

            def run(v):
                v.check("ghost")     # unrelated .check(): ignored
        """,
    })
    res = _only(repo, "fault-sites")
    assert res.findings == []


def test_fault_sites_missing_schema(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/resil/faults.py": "def check(site):\n    pass\n",
    })
    res = _only(repo, "fault-sites")
    assert _codes(res.findings) == ["SL501"]
    assert "SITES" in res.findings[0].message


# -- flight-recorder (SL601/SL602/SL603) ---------------------------------

_FLIGHT_LEDGER = """
    PHASES = ("stage", "factor", "update", "bcast_wait", "cache",
              "other")
"""

_FLIGHT_HEALTH = """
    def _publish_stall(op):
        inc("health.stalls")
        instant("health::stall", op=op)
"""

_FLIGHT_TUNE = """
    FROZEN = {
        ("obs", "ledger"): "off",
        ("obs", "watchdog"): "off",
    }
"""


def test_flight_clean(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/obs/ledger.py": _FLIGHT_LEDGER,
        "slate_tpu/obs/health.py": _FLIGHT_HEALTH,
        "slate_tpu/tune/cache.py": _FLIGHT_TUNE,
        "slate_tpu/linalg/ooc.py": """
            from ..obs import health as _health
            from ..obs import ledger as _ledger

            def instrument_driver(op):
                return lambda f: f

            @instrument_driver("potrf_ooc")
            def potrf_ooc(a):
                for k in range(3):
                    _health.heartbeat("potrf_ooc", k, 3)
                    with _ledger.frame("stage"):
                        pass
                return a

            def potrs_ooc(l, b):      # no loop: exempt from SL601
                return b
        """,
        "slate_tpu/dist/shard_ooc.py": """
            from ..obs import health as _health
            from ..obs import ledger as _ledger

            def instrument_driver(op):
                return lambda f: f

            @instrument_driver("shard_potrf_ooc")
            def shard_potrf_ooc(a, grid):
                for k in range(3):
                    _health.heartbeat("shard_potrf_ooc", k, 3)
                    _ledger.credit("bcast_wait", 0.0)
                return a
        """,
        "slate_tpu/batch/queue.py": """
            from ..obs import ledger as _ledger

            def dispatch():
                _ledger.append("batch.dispatch", step=0,
                               phases={"stage": 0.0, "factor": 0.0})
        """,
    })
    res = _only(repo, "flight-recorder")
    assert res.findings == []


def test_flight_catches_all_three(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/obs/ledger.py": _FLIGHT_LEDGER,
        "slate_tpu/obs/health.py": """
            def _publish_stall(op):
                inc("health.stals")       # typo'd counter
                instant("health::stall", op=op)
        """,
        "slate_tpu/tune/cache.py": """
            FROZEN = {
                ("obs", "ledger"): "off",   # watchdog row missing
            }
        """,
        "slate_tpu/linalg/ooc.py": """
            from ..obs import ledger as _ledger

            def instrument_driver(op):
                return lambda f: f

            @instrument_driver("potrf_ooc")
            def potrf_ooc(a):
                for k in range(3):          # no heartbeat: SL601
                    with _ledger.frame("stag"):   # typo: SL602
                        pass
                return a
        """,
        "slate_tpu/dist/shard_ooc.py": "",
    })
    res = _only(repo, "flight-recorder")
    assert _codes(res.findings) == ["SL601", "SL602", "SL603",
                                    "SL603"]
    by_code = {}
    for f in res.findings:
        by_code.setdefault(f.code, []).append(f)
    assert "potrf_ooc" in by_code["SL601"][0].message
    assert "'stag'" in by_code["SL602"][0].message
    msgs = " ".join(f.message for f in by_code["SL603"])
    assert "watchdog" in msgs            # missing FROZEN row
    assert "health.stalls" in msgs       # missing counter literal


def test_flight_append_phase_keys_checked(tmp_path):
    """The one-shot append(phases={...}) dict keys ride the same
    closed set as frame()/credit() literals."""
    repo = _write(tmp_path, {
        "slate_tpu/obs/ledger.py": _FLIGHT_LEDGER,
        "slate_tpu/obs/health.py": _FLIGHT_HEALTH,
        "slate_tpu/tune/cache.py": _FLIGHT_TUNE,
        "slate_tpu/linalg/ooc.py": "",
        "slate_tpu/dist/shard_ooc.py": "",
        "slate_tpu/batch/queue.py": """
            from ..obs import ledger as _ledger

            def dispatch():
                _ledger.append("batch.dispatch", step=0,
                               phases={"staeg": 0.0})
        """,
    })
    res = _only(repo, "flight-recorder")
    assert _codes(res.findings) == ["SL602"]
    assert "'staeg'" in res.findings[0].message
    assert res.findings[0].path == "slate_tpu/batch/queue.py"


# -- sched-graph (SL701/SL702) --------------------------------------------

_SCHED_LEDGER = _FLIGHT_LEDGER

_SCHED_FAULTS = """
    SITES = {
        "h2d": "uploads",
        "d2h": "writebacks",
        "ppermute": "tree",
        "step": "panel loops",
    }
"""

_SCHED_GRAPH_CLEAN = """
    NODE_KINDS = ("stage", "factor", "update")
    PHASE_OF_KIND = {
        "stage": "stage",
        "factor": "factor",
        "update": "update",
    }
    FAULT_SITE_OF_KIND = {
        "stage": "h2d",
        "factor": None,
        "update": None,
    }
"""


def test_sched_graph_clean(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/obs/ledger.py": _SCHED_LEDGER,
        "slate_tpu/resil/faults.py": _SCHED_FAULTS,
        "slate_tpu/sched/graph.py": _SCHED_GRAPH_CLEAN,
    })
    res = _only(repo, "sched-graph")
    assert res.findings == []


def test_sched_graph_catches_both(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/obs/ledger.py": _SCHED_LEDGER,
        "slate_tpu/resil/faults.py": _SCHED_FAULTS,
        "slate_tpu/sched/graph.py": """
            NODE_KINDS = ("stage", "factor", "update")
            PHASE_OF_KIND = {
                "stage": "stag",          # off-vocabulary: SL701
                "factor": "factor",
                "update": "update",
            }                             # total, so only the typo
            FAULT_SITE_OF_KIND = {
                "stage": "h2dd",          # unknown site: SL702
                "factor": None,           # "update" unmapped: SL702
            }
        """,
    })
    res = _only(repo, "sched-graph")
    assert _codes(res.findings) == ["SL701", "SL702", "SL702"]
    msgs = " ".join(f.message for f in res.findings)
    assert "'stag'" in msgs               # the off-vocabulary phase
    assert "'h2dd'" in msgs               # the unknown fault site


def test_sched_graph_live_tables_match_runtime():
    """The analyzer's literal_eval view of the live tree equals the
    imported tables — the lint checks what the runtime runs."""
    from slate_tpu.sched import graph as live
    from tools.slate_lint import astutil
    path = os.path.join(REPO, "slate_tpu/sched/graph.py")
    assert astutil.assigned_literal(path, "NODE_KINDS") \
        == live.NODE_KINDS
    assert astutil.assigned_literal(path, "PHASE_OF_KIND") \
        == live.PHASE_OF_KIND
    assert astutil.assigned_literal(path, "FAULT_SITE_OF_KIND") \
        == live.FAULT_SITE_OF_KIND


# -- reqtrace-ctx (SL801/SL802/SL803) -------------------------------------

_TRACE_TUNE = """
    FROZEN = {
        ("obs", "reqtrace"): "off",
        ("serve", "metrics"): "off",
    }
"""

_TRACE_GATES = """
    def reqtrace_enabled():
        return resolve("obs", "reqtrace") == "on"

    def metrics_enabled():
        return resolve("serve", "metrics") == "on"

    def commit(sp):
        sample("serve.latency_s", sp.t1 - sp.t0)
"""


def test_reqtrace_ctx_clean(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/tune/cache.py": _TRACE_TUNE,
        "slate_tpu/obs/reqtrace.py": _TRACE_GATES,
        "slate_tpu/serve/admission.py": """
            def admit(t, op):
                tid = current_trace_id()
                record_escalation("serve_shed", tenant=t, op=op,
                                  trace=tid)
                inc("serve.shed")
        """,
        "slate_tpu/serve/server.py": """
            def route(st, op, key, sp):
                factors = cache_get(key, trace=sp)
                inc("serve.cache.hits")
                return factors
        """,
    })
    res = _only(repo, "reqtrace-ctx")
    assert res.findings == []


def test_reqtrace_ctx_catches_all_three(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/tune/cache.py": """
            FROZEN = {
                ("obs", "reqtrace"): "off",   # metrics row missing
            }
        """,
        "slate_tpu/obs/reqtrace.py": """
            def reqtrace_enabled():
                return resolve("obs", "reqtrace") == "on"
        """,                        # no metrics reader, no sample()
        "slate_tpu/serve/admission.py": """
            def admit(t, op):
                record_escalation("serve_shed", tenant=t,
                                  op=op)          # no trace: SL801
                inc("serve.shed")   # context-blind function: SL801
        """,
    })
    res = _only(repo, "reqtrace-ctx")
    assert _codes(res.findings) == ["SL801", "SL801", "SL802",
                                    "SL803", "SL803"]
    msgs = " ".join(f.message for f in res.findings)
    assert "'serve_shed'" in msgs        # the untraced escalation
    assert "'serve.shed'" in msgs        # the context-blind counter
    assert "admit()" in msgs
    assert "('serve', 'metrics')" in msgs
    by = {}
    for f in res.findings:
        by.setdefault(f.code, []).append(f)
    assert all(f.path == "slate_tpu/serve/admission.py"
               for f in by["SL801"])


def test_reqtrace_ctx_escalation_outside_serve_unchecked(tmp_path):
    """SL801 scopes to slate_tpu/serve/: the watchdog's and refine's
    escalations predate request tracing and stay un-linted."""
    repo = _write(tmp_path, {
        "slate_tpu/tune/cache.py": _TRACE_TUNE,
        "slate_tpu/obs/reqtrace.py": _TRACE_GATES,
        "slate_tpu/obs/health.py": """
            def _publish_stall(op):
                record_escalation("watchdog_stall", op=op)
        """,
        "slate_tpu/serve/server.py": """
            def route(st, op, key, sp):
                return cache_get(key, trace=sp)
        """,
    })
    res = _only(repo, "reqtrace-ctx")
    assert res.findings == []


# -- elastic-mesh (SL901/SL902/SL903) -------------------------------------

_ELASTIC_TUNE = """
    FROZEN = {
        ("mesh", "ownership"): "static",
        ("mesh", "remap_every"): 4,
        ("mesh", "remap_threshold"): 1.25,
        ("mesh", "throughput_alpha"): 0.4,
    }
"""

_ELASTIC_CLEAN = """
    class ElasticSchedule(CyclicSchedule):
        def __init__(self, nt, grid, owners=None):
            self.owners = list(owners or [])
            for k, o in enumerate(self.owners):
                if not 0 <= o < self.nranks:
                    raise ValueError("bad owner")

        def owner_flat(self, k):
            return self.owners[k]

        def owner_coords(self, k):
            f = self.owners[k]
            return f // self.q, f % self.q

        def remap(self, boundary, owners):
            owners = list(owners)
            if owners[:boundary] != self.owners[:boundary]:
                raise ValueError("relabel of a factored panel")
            return ElasticSchedule(self.nt, self.grid, owners)


    class Ctl:
        def __init__(self, n, dtype):
            self.every = _resolve("mesh", "remap_every", n=n,
                                  dtype=dtype)
            self.thr = _resolve("mesh", "remap_threshold", n=n,
                                dtype=dtype)
            self.alpha = _resolve("mesh", "throughput_alpha", n=n,
                                  dtype=dtype)


    def resolve_ownership(n, dtype):
        return _resolve("mesh", "ownership", n=n, dtype=dtype)
"""


def test_elastic_mesh_clean(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/dist/elastic.py": _ELASTIC_CLEAN,
        "slate_tpu/tune/cache.py": _ELASTIC_TUNE,
    })
    res = _only(repo, "elastic-mesh")
    assert res.findings == []


def test_elastic_mesh_catches_all_three(tmp_path):
    repo = _write(tmp_path, {
        "slate_tpu/dist/elastic.py": """
            class ElasticSchedule(CyclicSchedule):
                def __init__(self, nt, grid, owners=None):
                    self.owners = list(owners or [])
                    if len(self.owners) != nt:
                        raise ValueError("bad table")

                def owner_flat(self, k):
                    return self.owners[k]
                # owner_coords NOT overridden: SL901 (the base
                # class's arithmetic answers for it)

                def remap(self, boundary, owners):
                    return ElasticSchedule(self.nt, self.grid,
                                           owners)  # no guard: SL902
        """,
        "slate_tpu/tune/cache.py": """
            FROZEN = {
                ("mesh", "remap_every"): 4,
                ("mesh", "remap_threshold"): 1.25,
                ("mesh", "throughput_alpha"): 0.4,
            }                    # ownership row missing: SL903
        """,
    })
    res = _only(repo, "elastic-mesh")
    # SL901 (one primitive unoverridden), SL902 (unguarded remap),
    # SL903 twice (ownership row missing + no reader for it) and
    # three more SL903 (knob rows present but unread in the fixture)
    assert _codes(res.findings) == ["SL901", "SL902", "SL903",
                                    "SL903", "SL903", "SL903",
                                    "SL903"]
    msgs = " ".join(f.message for f in res.findings)
    assert "owner_coords" in msgs
    assert "owners[:boundary]" in msgs
    assert "('mesh', 'ownership')" in msgs


def test_elastic_mesh_catches_table_blind_override(tmp_path):
    """An override that answers from arithmetic instead of the owners
    table splits ownership truth — SL901 even with both overridden."""
    repo = _write(tmp_path, {
        "slate_tpu/dist/elastic.py": _ELASTIC_CLEAN.replace(
            "f = self.owners[k]\n", "f = k % self.nranks\n"),
        "slate_tpu/tune/cache.py": _ELASTIC_TUNE,
    })
    res = _only(repo, "elastic-mesh")
    assert _codes(res.findings) == ["SL901"]
    assert "owner_coords" in res.findings[0].message


# -- baseline + CLI ------------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    repo = _write(tmp_path, {"slate_tpu/x.py": _LOCKED_CLASS % "pass"})
    res = _only(repo, "lock-discipline")
    assert len(res.findings) == 1
    bl = tmp_path / "baseline.json"
    core.write_baseline(str(bl), res.findings)
    assert json.loads(bl.read_text())["entries"]
    res2 = _only(repo, "lock-discipline", baseline=str(bl))
    assert res2.findings == [] and len(res2.baselined) == 1
    # a message-less entry matches by (code, path) — the reword-proof
    # form the core doc documents
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"code": "SL301", "path": "slate_tpu/x.py"}]}))
    res3 = _only(repo, "lock-discipline", baseline=str(bl))
    assert res3.findings == [] and len(res3.baselined) == 1


def test_run_only_selector():
    res = core.run(repo=REPO, only="SL202")
    assert list(res.timings) == ["tune-keys"]
    res = core.run(repo=REPO, only="SL4")
    assert list(res.timings) == ["obs-literals"]
    with pytest.raises(ValueError):
        core.run(repo=REPO, only="nope")


def test_cli_clean_and_filters(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, "-m", "tools.slate_lint"], cwd=REPO,
        capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ok" in out.stdout
    # a violating tree via --repo exits 1 and names the code
    repo = _write(tmp_path, {"slate_tpu/x.py": _LOCKED_CLASS % "pass"})
    out = subprocess.run(
        [sys.executable, "-m", "tools.slate_lint", "--repo", repo,
         "--only", "lock-discipline"], cwd=REPO,
        capture_output=True, text=True, env=env)
    assert out.returncode == 1
    assert "SL301" in out.stdout
    out = subprocess.run(
        [sys.executable, "-m", "tools.slate_lint", "--list"],
        cwd=REPO, capture_output=True, text=True, env=env)
    assert out.returncode == 0 and "tune-keys" in out.stdout
