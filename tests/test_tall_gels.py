"""The least-squares deployment `lstsq-tall-65536x4096-cond1e4` (PR 31)
at sizes the CPU tier holds: `st.gels` with no option on a ladder of
stated conditionings against the benchmark's plain reference and
numpy's f64 `lstsq`, the explicit CholQR route's refusal, the route
and the phase spans the per-layer metrics read, the kind's `check()`
against a CholQR-grade and a lower-precision answer, the reader on
planes made by hand, and a rehearsal of the cell `tall-gels`."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.core.methods import GELS_CHOLQR_MAX_COND, MethodGels
from slate_tpu.core.options import Option
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import metrics as obs_metrics

from benchmarks import run as bench_run
from benchmarks.lib import (gen, lstsqcount, lstsqgen, lstsqtrace,
                            plainref, plainref_lstsq, reduce_trace)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL, CONFIG = "tall-gels", "lstsq-tall-65536x4096-cond1e4"
CFG = bench_run.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       CONFIG + ".json"))
KIND = bench_run.load_module("kinds", "lstsq")
EPS = float(np.finfo(np.float32).eps)
METRICS = ["lstsq.launches_per_solve", "lstsq.upload_s",
           "lstsq.solve_roofline", "idle_share.lstsq",
           "lstsq.idle_select_share", "lstsq.refactor_share"]

#: every rung is held to this multiple of cond * eps_f32. Householder
#: QR in f32 reads 0.07-0.5 of cond * eps on the rungs from 1e2 up
#: (CPU, seeds 1-2, both shapes) and 1.6-3.7 on the cond-2 rung, which
#: sits on the rounding floor of an m-term sum and not on cond * eps;
#: 8 is twice the floor rung. CholQR on the shape alone (the parent of
#: PR 31) reads 12-24 at cond 1e2, 84-180 at 1e3, 320-530 at 3e3 and
#: NaN from 1e4: it fails every rung above the first.
QR_GRADE = 8.0
LADDER = (2.0, 1e2, 1e3, 3e3, 1e4, 1e5)
SHAPES = ((2048, 256), (1024, 320))     # m = 8 n, and m = 3.2 n


@pytest.fixture
def bus():
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()
    yield
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()


def problem(seed, m, n, cond, nrhs=4, noise=1e-3):
    return lstsqgen.tall_lstsq(gen.rng(seed, "solve"), m, n, nrhs, cond,
                               noise)


def gels(a, b, opts=None, mb=64):
    return st.gels(st.Matrix(a, mb=mb), st.Matrix(b, mb=mb),
                   opts).to_numpy()


def driver_span(name):
    return [e for e in obs.bus_events(cat="driver")
            if e.name == name][-1].args


# -- the ladder ------------------------------------------------------------

@pytest.mark.parametrize("m,n", SHAPES)
@pytest.mark.parametrize("cond", LADDER)
def test_gels_with_no_option_is_qr_grade(bus, m, n, cond):
    a, b = problem(31, m, n, cond)
    x64 = np.linalg.lstsq(a.astype(np.float64), b.astype(np.float64),
                          rcond=None)[0]
    # the benchmark's own f64 reference is that solution
    assert KIND.solution_error(KIND.reference_solution(a, b), x64) \
        <= 1e-3 * cond * EPS
    obs.enable()
    x = gels(a, b)
    route = driver_span("gels")
    assert x.shape == (n, 4) and x.dtype == np.float32
    got = KIND.solution_error(x, x64)
    assert got <= QR_GRADE * cond * EPS, (got / (cond * EPS), route)
    # the plain reference is QR-grade too, and the two QR answers lie
    # within the same bound of each other
    xp = plainref_lstsq.lstsq_qr(a, b)
    assert KIND.solution_error(xp, x64) <= QR_GRADE * cond * EPS
    assert KIND.solution_error(x, xp.astype(np.float64)) \
        <= QR_GRADE * cond * EPS
    # CholQR only where what was observed allows it
    assert route["chosen"] == "observed"
    if route["method"] == "cholqr":
        assert route["gram_cond"] <= GELS_CHOLQR_MAX_COND
    assert (route["method"] == "cholqr") == (cond == 2.0)
    # the estimate is a lower bound of cond_2(A), which the generator
    # holds within its Gaussian factor's cond of the stated one
    if np.isfinite(route["gram_cond"]):
        g = (1 + np.sqrt(n / m)) / (1 - np.sqrt(n / m))
        assert cond / g / 2 <= route["gram_cond"] <= g * cond


@pytest.mark.parametrize("cond,must_raise", [(1e4, False), (1e6, True)])
def test_cholqr_forced_never_hands_back_nan(cond, must_raise):
    a, b = problem(32, 2048, 256, cond)
    A, B = st.Matrix(a, mb=64), st.Matrix(b, mb=64)
    forced = {Option.MethodGels: MethodGels.CholQR}
    for call in (lambda: st.gels(A, B, forced).to_numpy(),
                 lambda: st.cholqr(A)[0].to_numpy()):
        try:
            out = call()
        except st.SlateError as exc:
            assert "not numerically positive definite" in str(exc)
        else:
            assert not must_raise and np.isfinite(out).all()


def test_select_takes_cholqr_only_on_an_observation():
    assert MethodGels.select(96, 8) is MethodGels.QR
    assert MethodGels.select(96, 8, gram_cond=1.7) is MethodGels.CholQR
    assert MethodGels.select(96, 8, gram_cond=GELS_CHOLQR_MAX_COND * 1.01) \
        is MethodGels.QR
    assert MethodGels.select(96, 48, gram_cond=1.0) is MethodGels.QR
    assert MethodGels.select(96, 8, on_grid=True, gram_cond=1.0) \
        is MethodGels.TSQR


def test_gels_under_jit_observes_nothing_and_takes_qr():
    import jax
    a, b = problem(33, 512, 32, 1e5, noise=0.0)
    x = jax.jit(lambda p, q: st.gels(st.Matrix(p, mb=32),
                                     st.Matrix(q, mb=32)).data)(a, b)
    x64 = KIND.reference_solution(a, b)
    assert KIND.solution_error(np.asarray(x)[:32, :4], x64) \
        <= QR_GRADE * 1e5 * EPS


# -- route, spans, counters ------------------------------------------------

def test_gels_span_carries_its_route_and_every_stage_a_phase(bus):
    a, b = problem(34, 2048, 256, 1e4)
    gels(a, b)                              # compiled before the bus
    obs.enable()
    gels(a, b)
    route, qr = driver_span("gels"), driver_span("geqrf")
    assert route["method"] == "qr" and route["chosen"] == "observed"
    assert route["gram_cond"] > 1e3          # inf: the factor is NaN
    assert qr["factor"] in ("fused", "tiled") and qr["form"]
    phases = [e.name for e in obs.bus_events(cat="phase")]
    assert phases == ["gels::gram", "gels::potrf", "gels::select",
                      "gels::geqrf", "gels::unmqr", "gels::trsm"]
    counters = obs.snapshot()["metrics"]["counters"]
    assert counters["gels.solves"] == 1 and counters["gels.refactors"] == 1
    # an explicit route observes nothing and abandons nothing
    obs_events.clear()
    gels(a, b, {Option.MethodGels: MethodGels.QR})
    assert driver_span("gels")["chosen"] == "by option or shape"
    assert [e.name for e in obs.bus_events(cat="phase")] == \
        ["gels::geqrf", "gels::unmqr", "gels::trsm"]
    assert obs.snapshot()["metrics"]["counters"]["gels.refactors"] == 1


def test_well_conditioned_input_keeps_cholqr(bus):
    a, b = problem(35, 2048, 256, None)
    obs.enable()
    x = gels(a, b)
    route = driver_span("gels")
    assert route["method"] == "cholqr"
    assert 1.0 <= route["gram_cond"] <= 2.5
    assert [e.name for e in obs.bus_events(cat="phase")] == \
        ["gels::gram", "gels::potrf", "gels::select", "gels::apply",
         "gels::apply", "gels::trsm", "gels::refine", "gels::apply",
         "gels::trsm"]
    counters = obs.snapshot()["metrics"]["counters"]
    assert counters["gels.solves"] == 1 and "gels.refactors" not in counters
    assert KIND.solution_error(x, KIND.reference_solution(a, b)) \
        <= QR_GRADE * 2 * EPS


def test_gels_sites_are_one_branch_when_off(bus, monkeypatch):
    import jax
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(obs_events, "_annotation", Counting)
    a, b = problem(36, 1024, 64, 1e3)
    gels(a, b)
    assert made == [] and obs.bus_events() == []
    assert obs.snapshot()["metrics"]["counters"] == {}


def test_gels_spans_reach_the_host_plane(bus, host_plane):
    a, b = problem(37, 1024, 64, 1e4)
    gels(a, b)
    obs.enable()
    seen = host_plane(lambda: gels(a, b), lstsqtrace.SPANS)
    by_name = {}
    for ev in seen:
        by_name.setdefault(ev[2], []).append(ev)
    assert set(by_name) == set(lstsqtrace.SPANS)
    root = by_name["gels"][0]
    assert root[3]["method"] == "qr"
    for child in lstsqtrace.SPANS:
        if child not in ("gels", "matrix::h2d"):
            ev = by_name[child][0]
            assert root[0] <= ev[0] <= ev[1] <= root[1], child
    # the hand-over of A and B is before the driver opens
    assert max(e[1] for e in by_name["matrix::h2d"]) <= root[0]


def test_unmqr_span_holds_one_compiled_program(bus, dispatches_under):
    """On a second call (nothing left to trace) the span `gels::unmqr`
    of a rehearsal-sized solve holds ONE dispatch of the compiled
    apply, `_unmqr_apply`, and beside it only the slice that takes
    B's logical columns and the pad that stores the result: at most
    nt + 2 dispatches, where the panel loop run from Python held some
    dozens a panel (PR 44; after test_lu's
    test_getrf_carry_spans_hold_one_program_each)."""
    rh = CFG["rehearsal"]
    m, n, mb = rh["m"], rh["n"], rh["mb"]
    assert (m, n, mb) == (4096, 256, 64)
    nt = n // mb
    a, b = problem(39, m, n, CFG["matrix"]["cond"], nrhs=rh["nrhs"])
    gels(a, b, mb=mb)
    obs.enable()
    (held,) = dispatches_under(lambda: gels(a, b, mb=mb),
                               {"gels::unmqr"})["gels::unmqr"]
    route = driver_span("gels")
    assert route["method"] == "qr"
    assert route["unmqr"] == "compiled" and route["unmqr_nt"] == nt
    assert held.count("_unmqr_apply") == 1, held
    assert len(held) <= nt + 2, held
    assert set(held) <= {"_unmqr_apply", "dynamic_slice", "_pad"}, held


# -- the comparison that decides `correct` ---------------------------------

def rehearsal_cell(seed):
    cfg = {**CFG, **CFG["rehearsal"]}
    cell = KIND.Cell(cfg, {"warm_solves": 1}, seed)
    return cfg, cell


@pytest.mark.parametrize("answer", ["sound", "cholqr_grade", "high",
                                    "nan", "wrong_shape"])
def test_check_refuses_what_the_deployment_refuses(answer):
    cfg, cell = rehearsal_cell(3100000007)
    a, b = cell.sys.a, cell.sys.b
    if answer == "sound":
        x = plainref_lstsq.lstsq_qr(a, b)
    elif answer == "high":
        x = plainref_lstsq.lstsq_qr(a, b, plainref.matmul_bf16x3)
    elif answer == "cholqr_grade":
        # the normal equations in f32, as CholQR solves them
        x = np.linalg.solve(a.T @ a, a.T @ b).astype(np.float32)
    elif answer == "nan":
        x = np.full((cfg["n"], cfg["nrhs"]), np.nan, np.float32)
    else:
        x = plainref_lstsq.lstsq_qr(a, b)[:-1]
    cell.answers, cell.walls = [x, x], [0.1]
    v = cell.check()
    assert v["correct"] is (answer == "sound"), v
    assert v["failed"] == (0 if answer == "sound" else 1)
    assert v["attempted"] == 1 and v["compared"][0][0] == \
        "solution_error_max"


def test_generator_states_its_conditioning():
    a, _ = problem(38, 4096, 256, 1e4)
    s = np.linalg.svd(a.astype(np.float64), compute_uv=False)
    assert 1e4 / 1.67 <= s[0] / s[-1] <= 1.67e4
    # not a column scaling: the column norms are level
    norms = np.linalg.norm(a, axis=0)
    assert norms.max() / norms.min() < 4.0
    # the noise is at the stated scale
    ab = problem(38, 4096, 256, 1e4, noise=0.0)[1]
    noisy = problem(38, 4096, 256, 1e4)[1]
    ratio = np.sqrt(((noisy - ab) ** 2).mean(0) / (ab ** 2).mean(0))
    np.testing.assert_allclose(ratio, 1e-3, rtol=0.05)


def test_route_probe_passes_here_and_fails_a_shape_only_route(monkeypatch):
    KIND.route_probe()

    def by_shape(A, B, opts=None):
        from slate_tpu.linalg import qr
        span = obs_events.span
        return qr._cholqr_solve(A, qr._gram_factor(A, opts, span)[0], B,
                                opts, span)
    monkeypatch.setattr(st, "gels", by_shape)
    with pytest.raises(SystemExit) as exc:
        KIND.route_probe()
    assert exc.value.code == 4


# -- the reader ------------------------------------------------------------

def _run(trace, **kw):
    return {"workload": CELL, "trace": trace, "counters": {},
            "histograms": {}, "spans": {}, "device_kind": "TPU v5 lite",
            "config": CFG, "records": {"solves": 70, "slice_solves": 1},
            **kw}


def test_lstsq_slice_counts_the_upload_as_idle():
    # the hand-over of A at 0, the device's first operation at 1000
    # (the transfer), busy [1000,1400] and [1500,1900]; `gels` over
    # [100,1600], `gels::select` over [120,1450]
    sl = lstsqtrace.LstsqSlice(
        [[(1000, 1400), (1500, 1900)]],
        [(0, 50, "matrix::h2d"), (100, 1600, "gels"),
         (105, 110, "gels::gram"), (110, 120, "gels::potrf"),
         (120, 1450, "gels::select"), (1450, 1600, "gels::geqrf")])
    assert sl.idle == [[[0, 1000], [1400, 1500]]]
    assert sl.idle_ns == 1100
    assert sl.cover(["gels::select"]) == pytest.approx(100 * 930 / 1100)
    assert sl.cover(["gels::gram", "gels::potrf", "gels::select"]) == \
        pytest.approx(100 * 945 / 1100)
    assert sl.cover(["gels"]) == pytest.approx(100 * 1000 / 1100)


def test_lstsq_metrics_by_hand():
    flops, nbytes = lstsqcount.gels(65536, 4096, 16)
    assert flops == 2 * 65536 * 4096 ** 2 - 2 * 4096 ** 3 / 3 \
        + 4 * 65536 * 4096 * 16
    assert nbytes == 4 * (65536 * 4096 + 65536 * 16 + 4096 * 16)
    run = _run({"busy_s": 0.4, "window_s": 0.5, "module_launches": 90})
    assert lstsqtrace.solve_roofline(run) == \
        pytest.approx(100 * (flops / 197e12) / 0.4)
    assert 0 < lstsqtrace.solve_roofline(run) < 100
    run["counters"] = {"gels.solves": 71, "gels.refactors": 71}
    assert lstsqtrace.refactor_share(run) == 100.0
    run["counters"] = {"gels.solves": 71}
    assert lstsqtrace.refactor_share(run) == 0.0


@pytest.mark.parametrize("name", METRICS)
def test_lstsq_metric_is_found_and_silent_without_a_trace(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == entry["moves"])
    assert CELL in moved["workloads"]
    assert entry["moves"] == CFG["wall_metric"]
    compute = bench_run.load_module("layer_metrics", name).compute
    # a rehearsal on the CPU, or a program that published no such span
    # or counter (the parent commit): nothing, and no raise
    assert compute(_run(None)) is None
    got = compute(_run({"busy_s": 1.0, "window_s": 2.0,
                        "module_launches": 5}))
    assert got is None or isinstance(got, float)


def test_configuration_is_as_the_issue_states_it():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["reduced"] == CFG["reduced"] == ["m"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "repeat", 1) and len(cell["why"]) <= 200
    assert (CFG["m"], CFG["n"], CFG["nrhs"], CFG["mb"], CFG["dtype"]) == \
        (65536, 4096, 16, 512, "float32")
    assert CFG["matrix"]["cond"] == 1e4 and CFG["routine"] == "gels"
    for key in ("source", "reduced_why", "assumed", "deployment",
                "guarantee", "tolerance", "rehearsal"):
        assert CFG[key], key
    assert MethodGels.tall(CFG["rehearsal"]["m"], CFG["rehearsal"]["n"])


def test_benchmark_lines_are_within_the_contracts_length():
    # PR 31 was first refused on the configuration's `why`, 214
    # characters: every one-line string of the file, not only this
    # cell's, is 1 to 200 printable characters.
    lines = [(e["name"], key, e[key])
             for group, keys in (("configs", ("source", "why")),
                                 ("workloads", ("why",)),
                                 ("per_layer", ("layer",)))
             for e in BENCH[group] for key in keys]
    lines += [("command", i, word) for i, word in enumerate(BENCH["command"])]
    for name, key, text in lines:
        assert 1 <= len(text) <= 200 and text.isascii() \
            and text.isprintable(), (name, key, len(text))


# -- a rehearsal of the cell -----------------------------------------------

_RUN = """
import sys
sys.path.insert(0, %(root)r)
from benchmarks import run
from benchmarks.lib.tracer import Tracer
init = Tracer.__init__
Tracer.__init__ = lambda self, directory: init(self, %(trace)r)
sys.exit(run.main(sys.argv[1:]))
"""


def test_rehearsal_publishes_the_cells_spans(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         _RUN % {"root": ROOT, "trace": str(tmp_path / "trace")},
         "--workload", CELL, "--seed", "3100000019", "--seconds", "1.5",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert last["metrics"]["lstsq.refactor_share"]["value"] == 100.0
    xplane = next(ln["xplane"] for ln in lines if ln.get("phase") == "trace")
    seen = {e[2]: e for e in lstsqtrace.host_events(
        reduce_trace.load(xplane))}
    assert set(lstsqtrace.SPANS) <= set(seen), \
        sorted(set(lstsqtrace.SPANS) - set(seen))
    assert seen["gels"][3]["method"] == "qr" \
        and seen["gels"][3]["chosen"] == "observed"
