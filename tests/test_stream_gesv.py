"""The streamed general solve `ooc-gesv-n32768` (PR 46) at sizes the CPU
tier holds: `ooc.gesv_ooc` under both pivot disciplines against the
benchmark's plain reference (lib/plainref_streamlu.py); the spans,
counters and route the LU drivers publish, and that the bus changes
no byte of the answer; a second call that compiles nothing; the kind's
`check()` against sound answers, the three controls and broken ones;
the counts and the readers on traces recorded on the chip; and a
rehearsal of the cell `stream-gesv`."""

import json
import os
import subprocess
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from slate_tpu import obs
from slate_tpu.core.methods import MethodLUPivot
from slate_tpu.linalg import ooc
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import metrics as obs_metrics

from benchmarks import run as bench_run
from benchmarks.lib import (gen, plainref, plainref_streamlu, refcheck,
                            streamlucount, streamlugen, streamlutrace)
from benchmarks.lib.compiles import Compiles

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL, CONFIG = "stream-gesv", "ooc-gesv-n32768"
CFG = bench_run.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       CONFIG + ".json"))
KIND = bench_run.load_module("kinds", "streamlu")
RECORDED = {p: os.path.join(ROOT, "benchmarks", "tests", "data",
                            "streamlu-%s.xplane.pb" % p)
            for p in streamlutrace.BOTH}
METRICS = ["idle_share.streamlu", "streamlu.launches_per_solve",
           "streamlu.h2d_gb", "streamlu.invalidated_gb",
           "streamlu.cache_hit_share", "streamlu.fixup_s",
           "streamlu.idle_fixup_share", "streamlu.idle_pivots_share",
           "streamlu.idle_stage_wait_share",
           "streamlu.idle_unattributed_share", "streamlu.solve_roofline",
           "streamlu.panel_roofline", "streamlu.panel_busy_share"]
#: of those, what a run on the CPU can read: counters and bus spans
ON_CPU = ["streamlu.h2d_gb", "streamlu.invalidated_gb",
          "streamlu.cache_hit_share", "streamlu.fixup_s"]
N, W, NRHS = 512, 64, 8             # the rehearsal's shape
#: what `streamlutrace.SPANS` and `COUNTERS` still list under `partial`
#: and the partial stream no longer publishes since PR 47 (no host
#: gather of an input panel, no cache retired): the benchmark's file
#: is not a `perf_opt` PR's to edit, ROADMAP Queue 2 part C has the
#: line a `benchmark` issue owes
GONE = {"partial": {"ooc::lu_gather", "ooc.lu_invalidations",
                    "ooc.lu_invalidation_bytes"}, "tournament": set()}


@pytest.fixture
def bus():
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()
    yield
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()


def system(seed, n=N):
    return streamlugen.system(gen.rng(seed, "solve"), n, NRHS)


def solve(a, b, pivot=None, w=W):
    kw = {} if pivot is None else {"pivot": pivot}
    return ooc.gesv_ooc(a, b, panel_cols=w,
                        cache_budget_bytes=5 * a.shape[0] * w * 4, **kw)


# -- the program against the plain reference -------------------------------

@pytest.mark.parametrize("pivot", streamlutrace.BOTH)
def test_gesv_ooc_against_the_plain_reference(pivot):
    a, b = system(4600000100)
    (lu_ref, piv_ref), x_ref = plainref_streamlu.gesv(a, b)
    (lu, ipiv), x = solve(a, b, pivot)
    assert lu.dtype == x.dtype == np.float32 and x.shape == b.shape
    assert KIND.ipiv_valid(ipiv, N)
    # both are f32 solves of a system of cond ~ 1e3-1e4: they agree to
    # cond eps, far under the 2e-2 a wrong pivot or a lost update shows
    assert np.abs(x - x_ref).max() <= 2e-2 * np.abs(x_ref).max()
    assert refcheck.hpl_resid_blocked(a, x, b, N) < 0.01
    if pivot == "partial":
        # each column's search sees every row not yet eliminated, as
        # the reference's does: the same pivots, the factor to rounding
        np.testing.assert_array_equal(np.asarray(ipiv), piv_ref)
        assert np.abs(lu - lu_ref).max() <= 1e-3 * np.abs(lu_ref).max()


def test_the_default_is_the_partial_stream():
    a, b = system(4600000101)
    assert MethodLUPivot.resolve(32768, np.float32) is MethodLUPivot.Partial
    (lu0, piv0), x0 = solve(a, b)
    (lu1, piv1), x1 = solve(a, b, "partial")
    assert np.array_equal(lu0, lu1) and np.array_equal(piv0, piv1)
    assert np.array_equal(x0, x1)


def test_a_second_call_compiles_nothing():
    a, b = system(4600000102)
    solve(a, b)
    comp = Compiles()
    s0 = comp.snap()
    solve(*system(4600000103))
    assert comp.since(s0)["programs"] == 0


# -- spans, counters and the route -----------------------------------------

@pytest.mark.parametrize("pivot", streamlutrace.BOTH)
def test_spans_counters_and_the_route(bus, pivot):
    a, b = system(4600000110)
    (lu_off, piv_off), x_off = solve(a, b, pivot)       # bus off
    assert obs.bus_events() == []
    assert obs.snapshot()["metrics"]["counters"] == {}
    obs.enable(beacon=False)
    (lu_on, piv_on), x_on = solve(a, b, pivot)
    # no observer effect: not a byte of the factor, the pivots or X
    assert np.array_equal(lu_on, lu_off) and np.array_equal(x_on, x_off)
    assert np.array_equal(piv_on, piv_off)
    events = obs.bus_events()
    seen = Counter(e.name for e in events)
    for name, modes in streamlutrace.SPANS.items():
        assert (seen[name] > 0) == (pivot in modes
                                    and name not in GONE[pivot]), \
            (name, seen[name])
    assert all(seen[r] == 1 for r in streamlutrace.ROOTS)
    own = [e for e in events if e.name.startswith("ooc::lu_")
           or e.name == "ooc::rhs_permute"]
    assert all(e.cat == "staging" for e in own)     # run.py sums these
    nt = N // W
    assert seen["ooc::lu_pivots"] == nt and seen["ooc::rhs_permute"] == 1
    counters = obs.snapshot()["metrics"]["counters"]
    for name, modes in streamlutrace.COUNTERS.items():
        assert (name in counters) == (pivot in modes
                                      and name not in GONE[pivot]), name
    # HPL's matrix: every panel after the first swaps rows past itself
    assert counters["ooc.lu_panels_swapped"] == nt - 1
    route = next(e for e in events if e.name == "getrf_ooc").args
    assert route["lu_pivot"] == pivot
    if pivot == "partial":
        # no row moves on the host: the input panels are staged as
        # they lie, nothing written is rewritten before the one repair
        assert seen["ooc::lu_gather"] == 0
        assert seen["ooc::lu_fixup"] == 1
        assert (route["panel"], route["nb"]) == ("native", W)
        fixed = sum(e.args["bytes"] for e in events
                    if e.name == "ooc::lu_fixup")
        # rows j1: of every panel before the last, read and written
        assert fixed == counters["ooc.lu_fixup_bytes"] == sum(
            2 * (N - j1) * W * 4 for j1 in range(W, N, W))
        assert counters.get("ooc.cache.invalidations", 0) == 0
        # 28 visits and 7 repairs of a panel, 16 in the two sweeps:
        # what the cache does not serve is staged, and nothing else
        # but A and B; a panel the repair finds gone stages its rows
        # j1: alone, so whole panels bound it from above
        hits, misses = counters["ooc.cache.hits"], counters["ooc.cache.misses"]
        assert hits + misses == nt * (nt - 1) // 2 + (nt - 1) + 2 * nt
        assert hits > misses
        staged = counters["ooc.h2d_bytes"] - a.nbytes - b.nbytes
        assert (misses - (nt - 1)) * N * W * 4 < staged <= misses * N * W * 4
    else:
        assert seen["ooc::lu_finalize"] == 1
        assert (route["panel"], route["nb"]) == ("calu", W)


@pytest.fixture
def native_height(monkeypatch):
    """The chip's routing at sizes the CPU holds: the native LU takes
    `cap` rows and no more (the CPU's has no height limit), for
    programs traced inside the fixture."""
    from slate_tpu.core.methods import MethodFactor

    def force(cap):
        monkeypatch.setattr(MethodFactor, "native_lu_ok", staticmethod(
            lambda dtype, m: MethodFactor.native_lu_dtype_ok(dtype)
            and m <= cap))
    jax.clear_caches()
    yield force
    monkeypatch.undo()
    jax.clear_caches()


def test_route_names_the_tall_panels_kernel(bus, native_height):
    """At the cell's height the chip's native LU panel is refused
    (NATIVE_LU_MAX_M) and the tall panels run `lu_panel_blocked` at
    lu._carry_nb's blocking: the route says so without tracing
    anything. A width no base block divides keeps the fori kernel in
    blocks of 256."""
    from slate_tpu.linalg import lu
    native_height(8192)
    obs.enable(beacon=False)
    with obs_events.driver("getrf_ooc"):
        ooc._note_lu_route("partial", 32768, 4096, 1024, np.float32)
    route = next(e for e in obs.bus_events() if e.name == "getrf_ooc").args
    assert (route["panel"], route["nb"]) == ("blocked", lu.LU_TALL_NB)
    assert lu._carry_nb(32768, 4096, 1024, np.float32) == lu.LU_TALL_NB
    assert lu._carry_nb(8192, 4096, 1024, np.float32) == 1024
    assert lu._carry_nb(32768, 4096, 300, np.float32) == 256


@pytest.mark.parametrize("k0,height", [
    (0, 512), (192, 512), (256, 256), (320, 256), (384, 128), (448, 128)])
def test_a_panel_is_factored_at_the_height_of_its_live_rows(
        native_height, k0, height):
    """`_lu_panel_factor` at each rung of the ladder (the native LU
    forced to 128 rows: 512, 256, 128) against the full-height call,
    for k0 at a rung's edge and inside it: the same pivots, the same
    live rows, dead rows exactly zero."""
    native_height(128)
    m, w = 512, 64
    S = jnp.asarray(system(4800000100 + k0)[0][:, :w])
    assert ooc._lu_panel_height(m, m - k0, np.float32) == height
    full, piv_full = ooc._lu_panel_factor(S, k0, w)
    packed, piv = ooc._lu_panel_factor(S, k0, w, height)
    assert packed.shape == full.shape == (m, w)
    np.testing.assert_array_equal(np.asarray(piv), np.asarray(piv_full))
    assert (np.asarray(piv) != np.arange(w)).sum() > w // 2
    packed, full = np.asarray(packed), np.asarray(full)
    assert not packed[m - k0:].any() and not full[m - k0:].any()
    assert np.abs(packed - full).max() <= 1e-4 * np.abs(full).max()
    # and the ladder is one rung where the native LU takes m itself
    native_height(m)
    assert ooc._lu_panel_height(m, m - k0, np.float32) == m


def test_the_rehearsal_on_the_tall_route(bus, native_height):
    """The cell's rehearsal (n=512, eight panels of 64) through
    `getrf_ooc` with the chip's tall route forced: blocked panels at
    512 and 256 rows, the native LU at 128, the counters of the rows
    factored, and an answer inside the rehearsal's limits."""
    native_height(128)
    cfg, cell = rehearsal_cell(4800000101)
    obs.enable(beacon=False)
    F, x = cell.sys.solve()
    route = next(e for e in obs.bus_events() if e.name == "getrf_ooc").args
    assert (route["lu_pivot"], route["panel"], route["nb"]) == \
        ("partial", "blocked", W)
    counters = obs.snapshot()["metrics"]["counters"]
    assert counters["ooc.lu_panel_rows_live"] == sum(range(W, N + W, W))
    assert counters["ooc.lu_panel_rows_factored"] == 4 * 512 + 2 * 256 \
        + 2 * 128
    obs.disable()
    assert KIND.ipiv_valid(F[1], N)
    cell.answers, cell.walls = [answer_of(cell, F, x)], [0.1]
    got = cell.check()
    assert got["correct"] is True and got["failed"] == 0, got
    # the same pivots as the plain reference's search over the live rows
    np.testing.assert_array_equal(
        np.asarray(F[1]), plainref_streamlu.gesv(cell.sys.a, cell.sys.b)[0][1])


# -- the comparison that decides `correct` ---------------------------------

def rehearsal_cell(seed):
    cfg = {**CFG, **CFG["rehearsal"]}
    return cfg, KIND.Cell(cfg, {"warm_solves": 1}, seed)


def answer_of(cell, F, x):
    lu, ipiv = F
    rows, cols = cell.rows
    return x, (lu[rows], lu[:, cols], np.array(ipiv))


@pytest.fixture
def products_at_high():
    """Control (ii) where the CPU has no `high`: every product the
    program binds at `highest` is computed as the chip computes one at
    `high` (three bfloat16 passes, plainref.matmul_bf16x3's
    arithmetic), for programs traced inside the fixture."""
    from jax._src.lax import lax as _lax
    hi = jax.lax.Precision.HIGHEST
    real = _lax.dot_general_p.bind

    def split(x):
        top = x.astype(jnp.bfloat16).astype(x.dtype)
        return top, (x - top).astype(jnp.bfloat16).astype(x.dtype)

    def bind(lhs, rhs, **kw):
        p = kw.get("precision")
        if not (isinstance(p, tuple) and hi in p
                and lhs.dtype == jnp.float32):
            return real(lhs, rhs, **kw)
        (ah, al), (bh, bl) = split(lhs), split(rhs)
        return real(ah, bh, **kw) + (real(ah, bl, **kw)
                                     + real(al, bh, **kw))
    jax.clear_caches()
    _lax.dot_general_p.bind = bind
    yield
    _lax.dot_general_p.bind = real
    jax.clear_caches()


@pytest.mark.parametrize("answer", [
    "sound", "tournament", "reference", "control_i", "control_iii",
    "bf16_answer", "ipiv_out_of_range", "ipiv_wrong_rows", "nan",
    "wrong_shape", "wrong_dtype"])
def test_check_refuses_what_the_deployment_refuses(answer):
    cfg, cell = rehearsal_cell(4600000007)
    a, b = cell.sys.a, cell.sys.b
    if answer == "tournament":
        cell.sys.pivot = "tournament"
    F, x = cell.sys.solve()
    if answer == "reference":
        F, x = plainref_streamlu.gesv(a, b)
    elif answer == "control_i":
        F, x = plainref_streamlu.gesv(a, b, plainref.matmul_bf16x3)
    elif answer == "control_iii":
        F, x = plainref_streamlu.gesv(a, b, pivot=False)
    x, (lr, uc, ipiv) = answer_of(cell, F, x)
    if answer == "bf16_answer":
        x = x.astype(plainref.BF16).astype(np.float32)
    elif answer == "ipiv_out_of_range":
        ipiv[5] = 3                     # a swap target above its row
    elif answer == "ipiv_wrong_rows":
        ipiv[:2] = [cfg["n"] - 1, cfg["n"] - 2]     # valid, not this P
    elif answer == "nan":
        x = np.full_like(x, np.nan)
    elif answer == "wrong_shape":
        x = x[:-1]
    elif answer == "wrong_dtype":
        x = x.astype(np.float64)
    cell.answers, cell.walls = [(x, (lr, uc, ipiv))] * 2, [0.1]
    got = cell.check()
    sound = answer in ("sound", "tournament", "reference")
    assert got["correct"] is sound, got
    assert got["failed"] == (0 if sound else 1) and got["attempted"] == 1
    # NaN equals nothing, itself included: graded twice
    assert got["distinct_answers"] == (2 if answer == "nan" else 1)
    assert [c[0] for c in got["compared"]] == list(KIND.NUMBERS)
    over = {c[0] for c in got["compared"] if not c[1] <= c[2]}
    assert over == {
        "control_i": {"factor_residual_rms", "scaled_residual_max"},
        "control_iii": {"factor_residual_rms", "scaled_residual_max"},
        "ipiv_out_of_range": {"factor_residual_rms", "ipiv_invalid"},
        "ipiv_wrong_rows": {"factor_residual_rms"},
    }.get(answer, set() if sound else {"scaled_residual_max"}), got


def test_check_refuses_the_program_at_high(products_at_high):
    """Control (ii): the program itself with its `highest` products at
    `high` fails both limits at the rehearsal's size."""
    cfg, cell = rehearsal_cell(4600000008)
    F, x = cell.sys.solve()
    cell.answers, cell.walls = [answer_of(cell, F, x)], [0.1]
    got = cell.check()
    assert got["correct"] is False and got["failed"] == 1
    over = {c[0] for c in got["compared"] if not c[1] <= c[2]}
    assert over == {"factor_residual_rms", "scaled_residual_max"}, got


def test_every_answer_is_graded_and_a_bad_warm_up_fails_the_run():
    cfg, cell = rehearsal_cell(4600000009)
    F, x = cell.sys.solve()
    good = answer_of(cell, F, x)
    bad = (good[0] * np.float32(1.01), good[1])
    cell.answers, cell.walls = [bad, good, good], [0.1, 0.1]
    got = cell.check()          # the warm-up's answer: not a failed
    assert got["correct"] is False      # solve, and still not correct
    assert (got["attempted"], got["failed"]) == (2, 0)
    assert got["distinct_answers"] == 2


def test_generator_is_hpls_law():
    a, b = system(4600000150)
    assert a.dtype == b.dtype == np.float32
    assert a.shape == (N, N) and b.shape == (N, NRHS)
    for m in (a, b):
        assert -0.5 <= m.min() < -0.49 and 0.49 < m.max() < 0.5
        assert abs(m.mean()) < 0.01
        assert abs(m.var() - 1 / 12) < 0.005
    assert np.array_equal(a, system(4600000150)[0])
    assert not np.array_equal(a, system(4600000151)[0])


def test_reference_is_lapacks_contract():
    a, b = system(4600000152, 256)
    (lu, ipiv), x = plainref_streamlu.gesv(a, b)
    perm = KIND.swaps_to_perm(ipiv, 256)
    l = np.tril(lu.astype(np.float64), -1) + np.eye(256)
    u = np.triu(lu.astype(np.float64))
    assert np.abs(l @ u - a[perm]).max() < 1e-4
    assert np.abs(l).max() <= 1.0           # partial pivoting
    assert plainref_streamlu.growth(a, lu) == pytest.approx(
        np.abs(u).max() / np.abs(a).max())
    assert np.abs(a.astype(np.float64) @ x - b).max() < 1e-2
    # without its row exchanges the same recursion is another factor
    (lu3, piv3), _ = plainref_streamlu.gesv(a, b, pivot=False)
    assert np.array_equal(piv3, np.arange(256))
    assert np.abs(np.tril(lu3, -1)).max() > 1.0


# -- the counts and the readers --------------------------------------------

def _run(trace, **kw):
    return {"workload": CELL, "trace": trace, "counters": {},
            "histograms": {}, "spans": {}, "device_kind": "TPU v5 lite",
            "config": CFG, "records": {"solves": 4, "slice_solves": 1},
            **kw}


def test_counts_by_hand():
    flops, nbytes = streamlucount.gesv_ooc(32768, 8)
    assert flops == pytest.approx(2.347e13, rel=1e-3)
    assert nbytes == 4 * (2 * 32768 ** 2 + 2 * 32768 * 8)
    pf, pb = streamlucount.panel_factors(32768, 4096)
    # eight panels of 4096 columns, 32768 down to 4096 live rows
    assert pf == pytest.approx(sum(
        4096 ** 2 * (m - 4096 / 3) for m in range(4096, 32769, 4096)))
    assert pb == 2 * 4 * 4096 * sum(range(4096, 32769, 4096))
    assert 0.09 < pf / flops < 0.11
    run = _run({"busy_s": 6.0, "window_s": 12.0, "module_launches": 200})
    # compute-bound: 0.119 s at the bf16 peak
    assert streamlutrace.solve_roofline(run) == \
        pytest.approx(100 * (flops / 197e12) / 6.0)
    run.update(counters={"ooc.h2d_bytes": 1e11, "ooc.cache.hits": 10,
                         "ooc.cache.misses": 30,
                         "ooc.lu_invalidation_bytes": 8e9},
               spans={"ooc::lu_fixup": 10.0})
    assert streamlutrace.counter_gb_per_solve(run, "ooc.h2d_bytes") == 25.0
    assert streamlutrace.cache_hit_share(run) == 25.0
    assert streamlutrace.span_s_per_solve(run, "ooc::lu_fixup") == 2.5
    # tournament raises no invalidation and opens no fixup: 0, not None
    run.update(counters={"ooc.h2d_bytes": 5.6e10}, spans={})
    assert streamlutrace.counter_gb_per_solve(
        run, "ooc.lu_invalidation_bytes") == 0.0
    assert streamlutrace.span_s_per_solve(run, "ooc::lu_fixup") == 0.0
    # a program that published nothing of the stream: nothing
    assert streamlutrace.counter_gb_per_solve(_run(None), "x") is None


def test_busy_by_step_sorts_the_programs():
    ordered = [(0, "jit__lu_visit", 0.2), (1, "jit__lu_panel_factor", 0.5),
               (2, "jit__tnt_select", 0.1), (3, "jit__tnt_factor", 0.05),
               (4, "jit__lu_back_visit", 0.01)]
    got = streamlutrace.busy_by_step(ordered)
    assert got["panel"] == pytest.approx(0.65)
    assert got["all"] == pytest.approx(0.86)
    assert streamlutrace.busy_by_step(ordered[:1] + ordered[-1:]) is None


@pytest.mark.parametrize("pivot", streamlutrace.BOTH)
def test_readers_on_the_recorded_trace(pivot):
    """benchmarks/tests/data/streamlu-<discipline>.xplane.pb: one small
    solve on the chip (tools/streamlu_breakdown.py --record, stripped
    to the path's spans and the device's `XLA Modules` line)."""
    sl, steps = streamlutrace.read(RECORDED[pivot])
    assert len(sl.spans[streamlutrace.ROOT]) == 1
    for name, modes in streamlutrace.SPANS.items():
        assert bool(sl.spans.get(name)) == (pivot in modes), name
    assert steps["all"] > steps["panel"] > 0
    for names in ((streamlutrace.FIXUP,), (streamlutrace.PIVOTS,),
                  tuple(streamlutrace.SPANS)):
        assert 0.0 <= sl.cover(names) <= 100.0
    assert (sl.cover((streamlutrace.FIXUP,)) > 0) == (pivot == "partial")


@pytest.mark.parametrize("pivot", streamlutrace.BOTH)
@pytest.mark.parametrize("name", METRICS)
def test_metric_reads_the_recorded_trace(name, pivot, monkeypatch):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == entry["moves"])
    assert CELL in moved["workloads"]
    assert entry["moves"] == CFG["wall_metric"]
    assert os.path.isfile(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       name + ".py"))
    compute = bench_run.load_module("layer_metrics", name).compute
    # a rehearsal on the CPU with the bus off, or a program that
    # published no such span, program or counter: nothing, and no raise
    assert compute(_run(None)) is None
    sl, steps = streamlutrace.read(RECORDED[pivot])
    monkeypatch.setattr(streamlutrace, "load", lambda run: (sl, steps))
    busy = steps["all"]
    got = compute(_run(
        {"busy_s": busy, "window_s": busy * 1.5, "module_launches": 40},
        config={**CFG, "n": 4096, "panel_cols": 512},   # the recording's
        counters={"ooc.h2d_bytes": 4e8, "ooc.cache.hits": 9,
                  "ooc.cache.misses": 27},
        spans={"ooc::lu_fixup": 0.25} if pivot == "partial" else {}))
    assert isinstance(got, float) and np.isfinite(got) and got >= 0
    if entry["unit"] == "%":
        assert got <= 100.0


def test_configuration_is_as_the_issue_states_it():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry["reduced"] == CFG["reduced"] == ["n"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    for word in ("SLATE 2023.11.05 tester gesv --origin h --target d",
                 "test/test_gesv.cc", "src/gesv.cc", "HPL 2.3",
                 "uniform(-0.5,0.5)", "arXiv:2112.09017", "n=65536",
                 "panel 8192"):
        assert word in entry["source"], word
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "repeat", 1) and len(cell["why"]) <= 200
    # appended after what was there (a later PR appends after it)
    assert BENCH["workloads"].index(cell) == 8
    assert BENCH["configs"].index(entry) == 8
    assert (CFG["n"], CFG["nrhs"], CFG["panel_cols"], CFG["panels"],
            CFG["resident_panels"], CFG["dtype"], CFG["kind"],
            CFG["routine"], CFG["architecture"]) == \
        (32768, 8, 4096, 8, 5, "float32", "streamlu", "gesv_ooc", None)
    posv = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "ooc-posv-n32768.json"))
    for key in ("n", "nrhs", "panel_cols", "panels", "resident_panels",
                "dtype", "wall_metric"):    # the factorization differs,
        assert CFG[key] == posv[key], key   # and nothing else
    assert set(CFG["tolerance"]) == set(KIND.NUMBERS) | {"reason"}
    assert CFG["tolerance"]["ipiv_invalid"] == 0
    assert set(CFG["rehearsal"]["tolerance"]) == set(KIND.NUMBERS)
    assert (CFG["rehearsal"]["n"], CFG["rehearsal"]["panel_cols"]) == (N, W)
    for key in ("source", "reduced_why", "assumed", "deployment",
                "guarantee", "tolerance", "rehearsal", "matrix",
                "reference_growth"):
        assert CFG[key], key
    for key in ("nrhs", "resident_panels", "dtype", "lu_pivot"):
        assert CFG["assumed"][key], key
    assert MethodLUPivot.resolve(CFG["n"], np.float32).value \
        in CFG["assumed"]["lu_pivot"]
    assert sorted(m["name"] for m in BENCH["per_layer"]
                  if m.get("workloads") == [CELL]) == sorted(METRICS)
    e2e = next(m for m in BENCH["end_to_end"]
               if m["name"] == "stream_solve_s")
    # appended there too (PR 49's `grid-gesv` came after it)
    assert e2e["workloads"].index(CELL) == 4 and e2e["bound"] == 0.13


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
         "--workload", CELL, "--seed", "4600000019", "--seconds", "1.5",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert window["compiles_in_window"]["programs"] == 0
    # the device metrics read nothing on a CPU and are left out; what
    # counters and bus spans carry is there
    assert sorted(last["metrics"]) == sorted(ON_CPU)
    assert last["metrics"]["streamlu.h2d_gb"]["value"] > 0
    assert last["metrics"]["streamlu.fixup_s"]["value"] > 0
    from benchmarks.lib import reduce_trace
    xplane = next(ln["xplane"] for ln in lines if ln.get("phase") == "trace")
    seen = {e[2]: e for e in streamlutrace.host_events(
        reduce_trace.load(xplane))}
    mode = seen["getrf_ooc"][3]["lu_pivot"]
    assert mode == MethodLUPivot.resolve(N, np.float32).value
    want = {n for n, modes in streamlutrace.SPANS.items()
            if mode in modes} - GONE[mode]
    assert want | set(streamlutrace.ROOTS) <= set(seen), \
        sorted(want - set(seen))
