"""The mixed-precision deployment `hplmxp-gesv-n16384` (PR 42) at sizes
the CPU tier holds, on the pair the chip has (f32 -> bfloat16):
`st.gesv_mixed` with no option against the benchmark's plain reference
(lib/plainref_mixed.py); the lo factor's route (the carry form, panels
raised to f32, the factor stored in bfloat16) and the tall-panel
kernel it adds; a second call that compiles nothing; the fallback
decided ON THE HOST; the spans and counters the per-layer metrics
read; the kind's `check()` against sound and unsound answers; the
readers on a recorded trace; `gesv_mixed_gmres` and `posv_mixed`
against the reference's answer; and a rehearsal of the cell
`incore-gesv-mixed`."""

import inspect
import json
import os
import subprocess
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.core.methods import MethodFactor, MethodLUPanel
from slate_tpu.core.options import Option
from slate_tpu.linalg import lu as lu_mod
from slate_tpu.linalg import refine
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import metrics as obs_metrics
from slate_tpu.resil import guard

from benchmarks import run as bench_run
from benchmarks.lib import (gen, mixedcount, mixedtrace, plainref,
                            plainref_mixed, refcheck, svdgen)
from benchmarks.lib.compiles import Compiles

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL, CONFIG = "incore-gesv-mixed", "hplmxp-gesv-n16384"
CFG = bench_run.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       CONFIG + ".json"))
KIND = bench_run.load_module("kinds", "mixed")
RECORDED = os.path.join(ROOT, "benchmarks", "tests", "data",
                        "mixed.xplane.pb")
METRICS = ["idle_share.mixed", "mixed.launches_per_solve",
           "mixed.solve_roofline", "mixed.factor_roofline",
           "mixed.factor_busy_share", "mixed.refine_busy_share",
           "mixed.refine_sweeps_per_solve", "mixed.idle_verdict_share"]
BF16 = jnp.bfloat16


@pytest.fixture
def bus():
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()
    yield
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()


def system(seed, n):
    return KIND.hplmxp_system(gen.rng(seed, "solve"), n)


def ill_conditioned(seed, n=256):
    """Singular values geometric from 1 to 1e-6: past any bf16 factor."""
    return svdgen.geo_general(gen.rng(seed, "ill"), n, 1e6)[0]


def mixed(a, b, mb=64, opts=None, solver=None):
    return (solver or st.gesv_mixed)(st.Matrix(a, mb=mb),
                                     st.Matrix(b, mb=mb), opts)


def resid(a, x, b):
    return refcheck.hpl_resid_blocked(a, x, b, a.shape[0])


# -- the program against the plain reference -------------------------------

@pytest.mark.parametrize("n,mb,opts", [
    (256, 64, None), (512, 128, None),
    (512, 128, {Option.BlockSize: 128})])      # four block steps
def test_gesv_mixed_against_the_plain_reference(n, mb, opts):
    a, b = system(4200000100 + n, n)
    F, X, iters = mixed(a, b, mb, opts)
    x_ref, it_ref, ok = plainref_mixed.gesv_mixed(a, b)
    assert ok and iters >= 0 and abs(int(iters) - it_ref) <= 1
    assert F.LU.dtype == BF16 and X.dtype == np.float32
    x = X.to_numpy()
    # both are f32-grade answers of a system of condition 1.04: they
    # agree to a few eps of the largest entry, and each passes HPL's
    # test a hundred times over
    assert np.abs(x - x_ref).max() <= 8 * np.finfo(np.float32).eps \
        * np.abs(x_ref).max()
    assert resid(a, x, b) < 0.02 and resid(a, x_ref, b) < 0.02
    # the factor is the bf16 LU of A[perm], and perm is the pivots'
    perm = np.asarray(F.perm)
    assert np.array_equal(perm, np.asarray(
        jax.lax.linalg.lu_pivots_to_permutation(F.pivots, n)))
    lu = np.asarray(F.LU.data.astype(jnp.float32), np.float64)
    low, up = np.tril(lu, -1) + np.eye(n), np.triu(lu)
    assert np.abs(low @ up - a[perm]).max() <= 2.0 ** -7 * np.abs(a).max()


def test_the_bf16_answer_alone_is_not_f32_grade():
    a, b = system(4200000111, 256)
    _, X, iters = mixed(a, b, opts={Option.MaxIterations: 0,
                                    Option.UseFallbackSolver: False})
    assert iters == 0
    assert resid(a, X.to_numpy(), b) > 50 * resid(a, mixed(a, b)[1]
                                                  .to_numpy(), b)


def test_f32_factors_carry_no_permutation_and_run_the_old_programs():
    a, _ = system(4200000112, 256)
    F = st.getrf(st.Matrix(a, mb=64), {Option.BlockSize: 64})
    assert F.perm is None and F.LU.dtype == np.float32
    lo = st.getrf(st.Matrix(a.astype(BF16), mb=64), {Option.BlockSize: 64})
    assert lo.perm is not None and lo.LU.dtype == BF16
    # same pivots on this family: the diagonal
    assert np.array_equal(np.asarray(F.pivots), np.asarray(lo.pivots))


def _pivoting(m, w, dtype):
    r = np.random.default_rng(m + w)
    a = r.standard_normal((m, w))
    if np.dtype(dtype).kind == "c":
        a = a + 1j * r.standard_normal((m, w))
    return jnp.asarray(a, dtype)


@pytest.mark.parametrize("m,w,dtype,via", [
    (256, 64, "float32", "kernel"), (128, 128, "float32", "kernel"),
    (512, 96, "float32", "kernel"), (384, 40, "float32", "kernel"),
    # the f32 route (PR 48): tall, square-ish and ragged panels above
    # a native height forced to 96 rows, through `_lu_panel` and the
    # carry form's `_carry_panel`
    (1024, 64, "float32", "panel"), (192, 160, "float32", "panel"),
    (300, 72, "float32", "panel"), (1024, 128, "float32", "carry"),
    (200, 136, "float32", "carry"), (256, 64, "complex64", "carry")])
def test_blocked_panel_is_the_fori_panel(m, w, dtype, via, monkeypatch):
    """`lu_panel_blocked` (the tall panels of the lo factor and, since
    PR 48, of every f32 factor above the native height on the chip)
    against the masked fori kernel on a matrix that pivots."""
    a = _pivoting(m, w, dtype)
    lu0, piv0 = lu_mod.lu_panel_fori(a)
    if via == "kernel":
        lu1, piv1, perm = jax.jit(lu_mod.lu_panel_blocked,
                                  static_argnums=1)(a, lu_mod._blocked_ib(w))
    else:
        monkeypatch.setattr(MethodFactor, "native_lu_ok", staticmethod(
            lambda dtype, m: m <= 96))
        route = MethodLUPanel.resolve(m, w, a.dtype)
        assert route is MethodLUPanel.Blocked
        if via == "panel":
            lu1, piv1 = jax.jit(lu_mod._lu_panel)(a)
            perm = lu_mod._compose_swaps(piv1, m)
        else:
            # (a jit of its own: `_carry_panel` caches by shape, and
            # the route under the patch is not the CPU's)
            lu1, piv1, perm = jax.jit(
                lu_mod._carry_panel.__wrapped__, static_argnums=(1, 2))(
                    a, w, route)
    assert np.array_equal(np.asarray(piv0), np.asarray(piv1))
    assert (np.asarray(piv1) != np.arange(w)).sum() > w // 2
    assert np.abs(np.asarray(lu0) - np.asarray(lu1)).max() < 2e-4
    assert np.array_equal(np.asarray(perm), np.asarray(
        jax.lax.linalg.lu_pivots_to_permutation(piv1, m)))
    assert lu_mod._blocked_ib(100) == 0


@pytest.mark.parametrize("lower,adjoint,unit", [
    (True, False, True), (False, False, False), (True, True, False),
    (False, True, False)])
def test_tri_sweep_is_a_triangular_solve(lower, adjoint, unit):
    r = np.random.default_rng(7)
    n, nb = 256, 64
    a = (0.05 * r.standard_normal((n, n)) + np.eye(n)).astype(np.float32)
    y = r.standard_normal((n, 3)).astype(np.float32)
    t = np.tril(a) if lower else np.triu(a)
    if unit:
        np.fill_diagonal(t, 1)
    want = np.linalg.solve((t.T if adjoint else t).astype(np.float64), y)
    got = refine.tri_sweep(jnp.asarray(a), jnp.asarray(y), lower=lower,
                           nb=nb, unit_diagonal=unit, adjoint=adjoint)
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()


# -- compiled once, decided on the host ------------------------------------

def test_a_second_call_compiles_nothing():
    n = 384                     # a size no other test of the worker has
    a, b = system(4200000120, n)
    comp = Compiles()
    _, X, it0 = mixed(a, b)
    x0 = X.to_numpy()
    assert comp.since((0, 0.0, 0, 0))["programs"] > 0
    s0 = comp.snap()
    a2, b2 = system(4200000121, n)              # other data, same shapes
    _, X2, it2 = mixed(a2, b2)
    X2.to_numpy()
    _, X, it1 = mixed(a, b)
    assert comp.since(s0)["programs"] == 0
    assert it0 == it1 and np.array_equal(x0, X.to_numpy())


def test_refine_holds_no_cond_around_a_factorization():
    src = inspect.getsource(refine)
    assert "lax.cond(" not in src and "full_solve()" in src
    # the programs are module-level and take no closure
    for prog in (refine._ir_solve0, refine._ir_sweeps,
                 refine._fgmres_program):
        assert hasattr(prog, "lower")


def test_an_ill_conditioned_system_falls_back_on_the_host(bus):
    a = ill_conditioned(4200000130)
    b = system(4200000130, 256)[1]
    guard.reset_counts()
    obs.enable(beacon=False)
    F, X, iters = mixed(a, b)
    assert iters < 0 and -iters - 1 == 30       # MaxIterations sweeps
    assert guard.counts()["resil.fallback.mixed_to_full"] == 1
    seen = Counter(e.name for e in obs.bus_events(cat="phase"))
    assert seen[mixedtrace.FALLBACK] == 1 and seen[mixedtrace.VERDICT] == 1
    c = obs.snapshot()["metrics"]["counters"]
    assert c["refine.ir.calls"] == 1 and c["refine.ir.fallback"] == 1
    # an f32-grade answer: the f32 solve's own
    x = X.to_numpy()
    _, X32 = st.gesv(st.Matrix(a, mb=64), st.Matrix(b, mb=64))
    assert np.array_equal(x, X32.to_numpy())
    assert F.LU.dtype == BF16
    # with the fallback refused the caller gets the count, not a sign
    obs.disable()
    _, _, it = mixed(a, b, opts={Option.UseFallbackSolver: False})
    assert it == 30
    assert guard.counts()["resil.fallback.mixed_to_full"] == 1


def test_the_rung_is_recorded_with_the_bus_off(bus):
    guard.reset_counts()
    mixed(ill_conditioned(4200000131), system(4200000131, 256)[1])
    assert guard.counts()["resil.fallback.mixed_to_full"] == 1
    assert obs.snapshot()["metrics"]["counters"] == {}


def test_under_a_callers_jit_the_verdict_is_refused():
    a, b = system(4200000132, 256)

    @jax.jit
    def traced(a, b):
        return mixed(a, b)[1].data

    with pytest.raises(Exception, match="outside jax.jit"):
        traced(a, b)


# -- spans and counters ----------------------------------------------------

def test_spans_counters_and_the_route(bus):
    a, b = system(4200000140, 512)
    opts = {Option.BlockSize: 128}
    _, _, off = mixed(a, b, 128, opts)          # compiled, bus off
    assert obs.bus_events() == []
    obs.enable(beacon=False)
    _, _, on = mixed(a, b, 128, opts)
    assert on == off                            # no observer effect
    events = obs.bus_events()
    seen = Counter(e.name for e in events)
    assert set(mixedtrace.SPANS) <= set(seen), \
        sorted(set(mixedtrace.SPANS) - set(seen))
    assert mixedtrace.FALLBACK not in seen
    own = [s for s in mixedtrace.SPANS if s.startswith("gesv_mixed")]
    assert all(seen[s] == 1 for s in own)
    assert seen["getrf::panel"] == 4 and seen["getrf::update"] == 3
    ev = {e.name: e for e in events if e.name in own}
    assert sorted(own[1:], key=lambda k: ev[k].t0) == own[1:]
    root = ev["gesv_mixed"].args
    assert (root["lo"], root["refine"]) == ("bfloat16", "ir")
    for route in (root, next(e for e in events if e.name == "getrf").args):
        assert (route["factor"], route["form"], route["nb"],
                route["store"], route["panel"], route["panel_dtype"]) == \
            ("tiled", "carry", 128, "bfloat16", "native", "float32")
        assert route["update"].startswith("one pass bfloat16 x bfloat16")
    snap = obs.snapshot()["metrics"]
    assert snap["counters"]["refine.ir.calls"] == 1
    assert "refine.ir.fallback" not in snap["counters"]
    assert snap["histograms"]["refine.ir.iters"]["total"] == on
    run = {"counters": snap["counters"], "histograms": snap["histograms"]}
    assert mixedtrace.refine_sweeps_per_solve(run) == on


def test_spans_reach_the_host_plane(bus, host_plane):
    a, b = system(4200000141, 256)
    mixed(a, b)
    obs.enable()
    names = mixedtrace.SPANS + (mixedtrace.FALLBACK,)
    seen = host_plane(lambda: mixed(a, b), names)
    by_name = {}
    for ev in seen:
        by_name.setdefault(ev[2], []).append(ev)
    assert set(by_name) == set(mixedtrace.SPANS) - {"getrf::update"}
    root = by_name["gesv_mixed"][0]
    assert root[3]["lo"] == "bfloat16" and root[3]["refine"] == "ir"
    assert root[3]["store"] == "bfloat16" and root[3]["form"] == "carry"
    assert root[3]["update"].startswith("one pass")
    for name, evs in by_name.items():
        for ev in evs:
            assert root[0] <= ev[0] <= ev[1] <= root[1], name


def test_lo_panel_route_is_read_from_the_height(monkeypatch):
    def route(m, w):
        return lu_mod._lo_panel_route(m, w).value
    assert route(16384, 1024) == "native"                      # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert route(8192, 1024) == "native"
    assert route(16384, 1024) == "blocked"
    assert route(9216, 1024) == "blocked"
    assert route(16384, 100) == "fori"


# -- the comparison that decides `correct` ---------------------------------

def rehearsal_cell(seed):
    cfg = {**CFG, **CFG["rehearsal"]}
    return cfg, KIND.Cell(cfg, {"warm_solves": 1}, seed)


@pytest.mark.parametrize("answer", [
    "sound", "reference", "no_refinement", "fallback", "bf16_answer",
    "high_residual", "many_sweeps", "nan", "wrong_shape", "wrong_dtype"])
def test_check_refuses_what_the_deployment_refuses(answer):
    cfg, cell = rehearsal_cell(4200000007)
    a, b = cell.sys.a, cell.sys.b
    (_, iters), X = cell.sys.solve()
    x = X.to_numpy()
    if answer == "reference":
        x, iters, _ = plainref_mixed.gesv_mixed(a, b)
    elif answer == "no_refinement":
        cell.sys.opts = {Option.MaxIterations: 0,
                         Option.UseFallbackSolver: False}
        (_, iters), X = cell.sys.solve()
        x = X.to_numpy()
    elif answer == "fallback":
        iters = -iters - 1          # the f32 deployment's answer
    elif answer == "bf16_answer":
        x = x.astype(plainref.BF16).astype(np.float32)
    elif answer == "high_residual":
        x = plainref_mixed.gesv_mixed(a, b, plainref.matmul_bf16x3)[0]
    elif answer == "many_sweeps":
        iters = cfg["tolerance"]["refine_sweeps_max"] + 1
    elif answer == "nan":
        x = np.full_like(x, np.nan)
    elif answer == "wrong_shape":
        x = x[:-1]
    elif answer == "wrong_dtype":
        x = x.astype(np.float64)
    cell.answers, cell.walls = [(x, iters)] * 2, [0.1]
    got = cell.check()
    sound = answer in ("sound", "reference")
    assert got["correct"] is sound, got
    assert got["failed"] == (0 if sound else 1) and got["attempted"] == 1
    assert [c[0] for c in got["compared"]] == list(KIND.NUMBERS)
    over = {c[0] for c in got["compared"] if not c[1] <= c[2]}
    assert over == {"fallback": {"fallbacks"},
                    "many_sweeps": {"refine_sweeps_max"}}.get(
        answer, set() if sound else {"scaled_residual_max"})


def test_generator_is_hplmxps_family():
    a, b = system(4200000150, 512)
    assert a.dtype == b.dtype == np.float32 and b.shape == (512, 1)
    off = a - np.diag(np.diag(a))
    assert np.abs(off).max() <= 0.5 and abs(off.mean()) < 0.01
    assert np.allclose(np.diag(a), np.abs(off).sum(axis=1), rtol=1e-6)
    assert 0.2 < np.diag(a).mean() / 512 < 0.3
    assert np.linalg.cond(a.astype(np.float64)) < 1.3
    a2, _ = system(4200000150, 512)
    assert np.array_equal(a, a2)
    assert not np.array_equal(a, system(4200000151, 512)[0])
    assert np.linalg.cond(ill_conditioned(1).astype(np.float64)) > 1e5


def test_compile_probe_asks_the_program(monkeypatch):
    KIND.compile_probe(CFG)                     # this program: passes

    def before_pr_42(A, B, solve_lo, full_solve, opts=None):
        raise AssertionError

    monkeypatch.setattr(refine, "iterative_refinement", before_pr_42)
    with pytest.raises(SystemExit) as exc:
        KIND.compile_probe(CFG)
    assert exc.value.code == 4


# -- the readers -----------------------------------------------------------

def _run(trace, **kw):
    return {"workload": CELL, "trace": trace, "counters": {},
            "histograms": {}, "spans": {}, "device_kind": "TPU v5 lite",
            "config": CFG, "records": {"solves": 8, "slice_solves": 1},
            **kw}


def test_counts_by_hand():
    flops, nbytes = mixedcount.gesv_mixed(16384, 1)
    assert flops == pytest.approx(2.93e12, rel=2e-3)
    assert nbytes == 6 * 16384 ** 2 + 8 * 16384
    assert mixedcount.factor(16384) == pytest.approx(2 * 16384 ** 3 / 3)
    run = _run({"busy_s": 0.30, "window_s": 0.32, "module_launches": 60})
    # compute-bound: 14.9 ms at the ONE-PASS peak
    assert mixedtrace.solve_roofline(run) == \
        pytest.approx(100 * (flops / 197e12) / 0.30)
    assert 4 < mixedtrace.solve_roofline(run) < 6
    run["counters"] = {"refine.ir.calls": 8}
    run["histograms"] = {"refine.ir.iters": {"count": 8, "total": 16.0}}
    assert mixedtrace.refine_sweeps_per_solve(run) == 2.0


def test_busy_by_step_sorts_the_programs():
    ordered = [(0, "jit_convert_element_type", 0.002),
               (1, "jit__carry_panel_lo", 0.02), (2, "jit__carry_swap", 0.004),
               (3, "jit__carry_update", 0.01), (4, "jit__carry_panel_lo", 0.01),
               (5, "jit__carry_finish", 0.006), (6, "jit__ir_solve0", 0.004),
               (7, "jit__ir_sweeps", 0.012), (8, "jit__pad", 0.001)]
    got = mixedtrace.busy_by_step(ordered)
    assert got["factor"] == pytest.approx(0.05)
    assert got["refine"] == pytest.approx(0.016)
    assert got["all"] == pytest.approx(0.069)
    # a program before PR 42 runs none of them: nothing to read
    assert mixedtrace.busy_by_step(ordered[:1] + ordered[-1:]) is None


def test_readers_on_the_recorded_trace():
    """benchmarks/tests/data/mixed.xplane.pb: one small solve on the
    chip (tools/mixed_breakdown.py --record, stripped to the path's
    spans and the device's `XLA Modules` line)."""
    sl, steps = mixedtrace.read(RECORDED)
    assert len(sl.spans[mixedtrace.ROOT]) == 1
    for name in mixedtrace.SPANS:
        assert sl.spans.get(name), name
    assert steps["all"] >= steps["factor"] + steps["refine"] > 0
    assert steps["factor"] > 0 and steps["refine"] > 0
    assert 0.0 <= sl.cover((mixedtrace.VERDICT,)) <= 100.0


@pytest.mark.parametrize("name", METRICS)
def test_metric_reads_the_recorded_trace(name, monkeypatch):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == entry["moves"])
    assert CELL in moved["workloads"]
    assert entry["moves"] == CFG["wall_metric"]
    compute = bench_run.load_module("layer_metrics", name).compute
    # a rehearsal on the CPU, or a program that published no such span,
    # program or counter (the parent commit): nothing, and no raise
    assert compute(_run(None)) is None
    sl, steps = mixedtrace.read(RECORDED)
    monkeypatch.setattr(mixedtrace, "load", lambda run: (sl, steps))
    busy = steps["all"]
    got = compute(_run(
        {"busy_s": busy, "window_s": busy * 1.25, "module_launches": 40},
        config={**CFG, "n": 2048},              # the recording's size
        counters={"refine.ir.calls": 8},
        histograms={"refine.ir.iters": {"count": 8, "total": 16.0}}))
    assert isinstance(got, float) and np.isfinite(got) and got >= 0
    if entry["unit"] == "%":
        assert got <= 100.0


def test_configuration_is_as_the_issue_states_it():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["reduced"] == CFG["reduced"] == ["n"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    for word in ("HPL-MxP", "hpl-mxp.org", "hpl-ai", "1 rhs",
                 "SLATE 2023.11.05 gesv_mixed", "src/gesv_mixed.cc"):
        assert word in entry["source"], word
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "repeat", 1) and len(cell["why"]) <= 200
    # appended after what was there (a later PR appends after it)
    assert BENCH["workloads"].index(cell) == 7
    assert BENCH["configs"].index(entry) == 7
    assert (CFG["n"], CFG["nrhs"], CFG["mb"], CFG["dtype"], CFG["kind"],
            CFG["routine"]) == (16384, 1, 512, "float32", "mixed",
                                "gesv_mixed")
    assert set(CFG["tolerance"]) == set(KIND.NUMBERS) | {"reason"}
    assert CFG["tolerance"]["fallbacks"] == 0
    assert set(CFG["rehearsal"]["tolerance"]) == set(KIND.NUMBERS)
    for key in ("source", "reduced_why", "assumed", "deployment",
                "guarantee", "tolerance", "rehearsal", "wall_metric_why"):
        assert CFG[key], key
    for key in ("dtype", "mb", "refinement", "pivoting", "generator"):
        assert CFG["assumed"][key], key
    assert sorted(m["name"] for m in BENCH["per_layer"]
                  if m.get("workloads") == [CELL]) == sorted(METRICS)


# -- the rest of the family against the reference's answer -----------------

@pytest.mark.parametrize("n", [256, 512])
def test_gesv_mixed_gmres_against_the_references_answer(n):
    a, b = system(4200000160 + n, n)
    x_ref = plainref_mixed.gesv_mixed(a, b)[0]
    F, X, iters = mixed(a, b, solver=st.gesv_mixed_gmres)
    assert iters >= 0 and F.LU.dtype == BF16
    x = X.to_numpy()
    assert np.abs(x - x_ref).max() <= 8 * np.finfo(np.float32).eps \
        * np.abs(x_ref).max()
    assert resid(a, x, b) < 0.02


@pytest.mark.parametrize("n,uplo", [(256, st.Uplo.Lower),
                                    (512, st.Uplo.Upper)])
def test_posv_mixed_against_the_references_answer(n, uplo):
    """f64 -> f32 here: XLA's CPU Cholesky takes no bfloat16 operand
    (the chip's does), so the chip's pair cannot run on this tier."""
    r = gen.rng(4200000170 + n, "solve")
    a = gen.spd_gram(r, n).astype(np.float64)
    b = r.standard_normal((n, 2))
    want = np.linalg.solve(a, b)
    L, X, iters = st.posv_mixed(st.HermitianMatrix(uplo, a, mb=64),
                                st.Matrix(b, mb=64))
    assert iters >= 0 and L.dtype == np.float32
    assert np.abs(X.to_numpy() - want).max() <= 1e-12 * np.abs(want).max()
    L, X, iters = st.posv_mixed_gmres(st.HermitianMatrix(uplo, a, mb=64),
                                      st.Matrix(b[:, :1], mb=64))
    assert iters >= 0
    assert np.abs(X.to_numpy() - want[:, :1]).max() <= 1e-12 * np.abs(want).max()


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


def test_rehearsal_runs_end_to_end_and_publishes_its_spans(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         _RUN % {"root": ROOT, "trace": str(tmp_path / "trace")},
         "--workload", CELL, "--seed", "4200000019", "--seconds", "1.5",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert window["compiles_in_window"]["programs"] == 0
    assert 1 <= last["metrics"]["mixed.refine_sweeps_per_solve"]["value"] \
        <= CFG["rehearsal"]["tolerance"]["refine_sweeps_max"]
    from benchmarks.lib import reduce_trace
    xplane = next(ln["xplane"] for ln in lines if ln.get("phase") == "trace")
    seen = {e[2]: e for e in mixedtrace.host_events(
        reduce_trace.load(xplane))}
    assert set(mixedtrace.SPANS) <= set(seen), \
        sorted(set(mixedtrace.SPANS) - set(seen))
    assert seen["getrf"][3]["form"] == "carry" \
        and seen["getrf"][3]["store"] == "bfloat16"
