"""The grid deployment `grid2x2-gesv-n49152` (PR 49) at sizes the CPU
tier holds, on grids of the virtual devices: `st.gesv` under
`Option.Grid` against the benchmark's plain reference at the cell's
route (nt=96, the staged scan form of the pivoted LU, its blocks and
its row exchanges on the chips that own them), at a size whose blocks
straddle two chips (the masked form) and at an unrolled size; the two
forms against each other and against the single-device scan; the row
exchange alone; `info` on singular input; the guard that no value in
the programs is more of the matrix than a device's block of a stage's
square; the counters by hand; the readers of the cell's per-layer
metrics on planes made by hand; and a rehearsal of the cell."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.core.methods import MethodFactor
from slate_tpu.core.options import Option
from slate_tpu.linalg import blocked
from slate_tpu.linalg import lu as lumod
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import metrics as obs_metrics
from slate_tpu.parallel.sharding import place

from benchmarks import run as bench_run
from benchmarks.lib import (gen, gridlucount, gridlutrace, gridtrace,
                            plainref, plainref_gridlu, reduce_trace,
                            refcheck, streamlugen)

from benchmarks.kinds import streamlu

from test_grid_posv import _RUN, _beyond_a_block

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL, CONFIG = "grid-gesv", "grid2x2-gesv-n49152"
CFG = bench_run.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       CONFIG + ".json"))
METRICS = ["idle_share.gridlu", "gridlu.launches_per_solve",
           "gridlu.solve_roofline", "gridlu.collective_share",
           "gridlu.busy_imbalance", "gridlu.h2d_gb", "gridlu.upload_s",
           "gridlu.idle_upload_share", "gridlu.block_local_share",
           "gridlu.update_work_ratio", "gridlu.panel_busy_share",
           "gridlu.panel_roofline", "gridlu.exchange_busy_share",
           "gridlu.exchange_gb"]


def grid_of(p, q):
    return st.make_grid(p, q, devices=jax.devices()[:p * q])


@pytest.fixture(scope="module")
def grid():
    return grid_of(2, 2)


@pytest.fixture
def bus():
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()
    yield
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()


def opts_of(grid):
    return {Option.Grid: grid, Option.MethodFactor: MethodFactor.Tiled}


def system(seed, n, nrhs=8):
    return streamlugen.system(gen.rng(seed, "solve"), n, nrhs)


def solve_on(grid, a, b, mb):
    A = st.Matrix(a, mb=mb, grid=grid)
    B = st.Matrix(b, mb=mb, grid=grid)
    return st.gesv(A, B, opts_of(grid))


def forget():
    """The grid programs are kept by grid and shape, whatever the form
    their helpers took when they were traced."""
    lumod._grid_getrf_programs.cache_clear()
    lumod._grid_getrs_program.cache_clear()


# -- the system against the plain reference --------------------------------

#: (n, mb) -> limits on max|X - X_ref| / max|X_ref| and on the cell's
#: own factor number (kinds/streamlu.py `factor_resid` on 32 seeded
#: rows and columns, growth 1: the factor against the matrix under its
#: OWN pivots, since two f32 eliminations of uniform data part ways at
#: a near-tie of some late column, 3 of 15 readings at n=768, and their
#: factors can then not be compared entry by entry). Read on seeds 1-4
#: and 3000000019 on the three grids (CPU, f32): at n=768 X lies
#: 6.1e-5 - 1.5e-4 from the f32 reference and 1.75e-3 - 4.8e-3 from
#: the reference with its products at `high` (bf16x3), the factor
#: reads 6.8-8.9 (the f32 reference's own 9.5-11.7) against bf16x3's
#: 587-750; at n=776 (388 rows a device: the masked form) 4.7e-5 -
#: 3.3e-4 against 1.06e-3 - 1.7e-2 and 7.3-8.5 against 567-703; at
#: n=96 (no scan) 4.1e-6 - 1.9e-5 against 9.1e-5 - 1.7e-3 and 1.3-1.64
#: against 99-119. Each limit sits between: two f32 LUs differ by the
#: order of their sums, a few eps times the growth and the condition;
#: a product at `high` is wrong by 2^-18 of its terms.
LIMITS = {(768, 8): (5.0e-4, 70.0), (776, 8): (6.0e-4, 70.0),
          (96, 8): (4.0e-5, 12.0)}


@pytest.mark.parametrize("p,q", [(2, 2), (1, 4), (4, 1)])
@pytest.mark.parametrize("n,mb,form,blocks,stages", [
    (768, 8, "scan", "local", blocked.CHOL_SCAN_STAGES),
    (776, 8, "scan", "masked", 1), (96, 8, "pipelined", "slice", 0)])
def test_grid_gesv_agrees_with_the_plain_reference(bus, p, q, n, mb, form,
                                                   blocks, stages):
    grid = grid_of(p, q)
    if n == 776 and (p, q) != (2, 2):
        # 194 rows a device of four: on (4, 1) and (1, 4) too a block
        # of 8 straddles two
        assert blocked.grid_blocks(n, mb, grid) == "masked"
    a, b = system(3000000019, n)
    rows = refcheck.factor_sample(n, gen.rng(3000000019, "sample"), 32)
    cols = refcheck.factor_sample(n, gen.rng(3000000019, "columns"), 32)
    obs.enable()
    F, X = solve_on(grid, a, b, mb)
    route = [e for e in obs.bus_events(cat="driver")
             if e.name == "getrf"][-1].args
    assert (route["form"], route["nb"], route["grid"], route["blocks"],
            route["stages"]) == (form, mb, "%dx%d" % (p, q), blocks, stages)
    counters = obs.snapshot()["metrics"]["counters"]
    steps = {k.rsplit("_", 1)[1]: v for k, v in counters.items()
             if k.startswith("grid.lu_block_steps_")}
    # nt for the factor, nt for each of the solve's two sweeps
    assert steps == ({} if form != "scan" else {blocks: 3 * (n // mb)})
    assert len(X.data.sharding.device_set) == p * q
    x, lu = X.to_numpy(), np.asarray(F.LU.data)
    ipiv = np.asarray(F.pivots)
    assert x.dtype == np.float32 and int(F.info) == 0
    assert streamlu.ipiv_valid(ipiv, n)
    # the composed permutation rides with the factor
    np.testing.assert_array_equal(
        np.asarray(F.perm), np.asarray(lumod._compose_swaps(F.pivots, n)))

    def factor_number(lu, ipiv):
        return streamlu.factor_resid(a, lu[rows], lu[:, cols], ipiv, rows,
                                     cols, 1.0)

    def apart(matmul):
        (lur, pivr), xr = plainref_gridlu.gesv(a, b, matmul, nb=64)
        return (np.abs(x - xr).max() / np.abs(xr).max(),
                factor_number(lur, pivr))

    x_lim, f_lim = LIMITS[(n, mb)]
    dx, ref = apart(plainref.matmul_f32)
    assert dx <= x_lim and factor_number(lu, ipiv) <= f_lim >= ref, \
        (dx, factor_number(lu, ipiv), ref)
    # the same comparison refuses the reference one precision down
    cx, cf = apart(plainref.matmul_bf16x3)
    assert cx > x_lim and cf > f_lim, (cx, cf)


def test_rehearsal_limits_refuse_the_controls(grid):
    """At the rehearsal's size the cell's own numbers hold at the
    rehearsal's limits for the program, and each limit refuses the
    reference one precision down and the reference without its row
    exchanges."""
    kind = bench_run.load_module("kinds", "gridlu")
    cfg = {**CFG, **CFG["rehearsal"]}
    tol = cfg["tolerance"]
    cell = kind.Cell(cfg, {}, 3000000019, system=kind._Host)
    a, b = cell.sys.a, cell.sys.b
    rows, cols = cell.rows
    F, X = solve_on(grid, a, b, cfg["mb"])
    lu = np.asarray(F.LU.data)
    nums = cell.grade(X.to_numpy(), (lu[rows], lu[:, cols],
                                     np.asarray(F.pivots)))
    assert all(nums[k] <= tol[k] for k in tol), nums
    for mm, pivot in ((plainref.matmul_bf16x3, True),
                      (plainref.matmul_f32, False)):
        (lur, pivr), xr = plainref_gridlu.gesv(a, b, mm, pivot, nb=64)
        nums = cell.grade(xr, (lur[rows], lur[:, cols], pivr))
        assert nums["scaled_residual_max"] > tol["scaled_residual_max"]
        assert nums["factor_residual_rms"] > tol["factor_residual_rms"]


# -- the forms against each other ------------------------------------------

def test_local_form_is_bitwise_the_masked_form(grid, monkeypatch):
    """At the rehearsal's size every block lies on one device: the
    factor, the pivots and X by the per-device slices are bit for bit
    what the masked sums over the whole square give (both add exact
    zeros), in one stage as in four."""
    n, mb = 768, 8
    a, b = system(23, n)
    monkeypatch.setattr(blocked, "chol_scan_stages",
                        lambda n, nb, grid=None: ((0, n),))
    got = {}
    for form in ("local", "masked"):
        if form == "masked":
            monkeypatch.setattr(blocked, "block_on_one_chip",
                                lambda *a: False)
        assert blocked.grid_blocks(n, mb, grid) == form
        forget()
        try:
            F, X = solve_on(grid, a, b, mb)
            got[form] = (np.asarray(F.LU.data), np.asarray(F.pivots),
                         X.to_numpy())
        finally:
            forget()
    for one, other in zip(got["local"], got["masked"]):
        assert one.tobytes() == other.tobytes()


def test_staged_scan_against_the_single_device_scan(grid):
    """Factor, pivots and X against `_lu_scan` on one device: the same
    pivots; the values to a tolerance, because the arithmetic differs:
    under a grid U12 is inv(L11) @ A12 where one device solves, and a
    stage's update sums over the stage's columns where the one-stage
    form sums over all n (read on seeds 1-5: 2.4e-6 - 6.1e-6 of
    max|LU|, X 1.5e-5 - 8.9e-5)."""
    n, mb = 768, 8
    a, b = system(31, n)
    F, X = solve_on(grid, a, b, mb)
    lu1, piv1 = jax.jit(lambda a: lumod._lu_scan(a, mb, True))(
        jnp.asarray(a))
    np.testing.assert_array_equal(np.asarray(F.pivots), np.asarray(piv1))
    lu, lu1 = np.asarray(F.LU.data), np.asarray(lu1)
    assert np.abs(lu - lu1).max() <= 3.0e-5 * np.abs(lu1).max()
    x1 = st.getrs(lumod.LUFactors(st.Matrix(lu1, mb=mb), piv1),
                  st.Matrix(b, mb=mb)).to_numpy()
    x = X.to_numpy()
    assert np.abs(x - x1).max() <= 4.0e-4 * np.abs(x1).max()
    # with no grid the staged form is the same arithmetic as itself on
    # the mesh but for U12: the same pivots and composed permutation
    lu0, piv0, perm0 = jax.jit(
        lambda a: lumod._lu_scan_grid(a, mb, True, None))(jnp.asarray(a))
    np.testing.assert_array_equal(np.asarray(piv0), np.asarray(piv1))
    np.testing.assert_array_equal(np.asarray(perm0), np.asarray(F.perm))
    assert np.abs(np.asarray(lu0) - lu).max() <= 3.0e-5 * np.abs(lu).max()


@pytest.mark.parametrize("method", ["nopiv", "tntpiv"])
def test_the_other_pivot_disciplines_take_the_staged_form(grid, method, rng):
    """`getrf_nopiv` and `getrf_tntpiv` under a grid call the same
    scan: the factor reproduces the matrix."""
    n, mb = 768, 8
    a = rng.standard_normal((n, n)).astype(np.float32)
    if method == "nopiv":
        a += np.float32(0.5 * n) * np.eye(n, dtype=np.float32)
    A = st.Matrix(a, mb=mb, grid=grid)
    F = {"nopiv": st.getrf_nopiv, "tntpiv": st.getrf_tntpiv}[method](
        A, opts_of(grid))
    lu = np.asarray(F.LU.data, np.float64)
    perm = np.asarray(lumod._compose_swaps(F.pivots, n))
    L = np.tril(lu, -1) + np.eye(n)
    err = np.abs(L @ np.triu(lu) - a[perm]).max() / np.abs(a).max()
    assert err < 2e-3, err
    assert len(F.LU.data.sharding.device_set) == 4


# -- the row exchange -------------------------------------------------------

@pytest.mark.parametrize("p,q", [(2, 2), (1, 4), (4, 1), (0, 0)])
@pytest.mark.parametrize("cross", [True, False])
def test_rows_are_exchanged_on_their_owners(p, q, cross, rng):
    """`_exchange_rows` against `a[perm]`: the rows a panel's swaps
    touch, on matrices whose pivots cross the `p` axis and on ones
    whose pivots stay inside a device's rows; a row named twice; the
    result keeps the operand's sharding."""
    g = grid_of(p, q) if p else None
    n, w = 64, 8
    a = rng.standard_normal((n, 40)).astype(np.float32)
    k0 = 16
    # swap targets of a panel at rows k0..k0+w: anywhere below, or
    # inside the 16 rows the first device of p=4 still holds
    hi = n if cross else k0 + 16
    piv = rng.integers(k0 + np.arange(w), hi)
    piv[3] = piv[1]                         # a row named twice
    perm = np.arange(n)
    for j, t in enumerate(piv):
        perm[[k0 + j, t]] = perm[[t, k0 + j]]
    touched = np.concatenate([k0 + np.arange(w), piv])
    A = place(a, g, a.shape) if g else jnp.asarray(a)
    got = jax.jit(lambda a, d, s: blocked._exchange_rows(a, d, s, g))(
        A, touched, perm[touched])
    np.testing.assert_array_equal(np.asarray(got), a[perm])
    if g:
        assert got.sharding.is_equivalent_to(A.sharding, 2)
    # rows the mesh axis does not divide: every device holds them all
    odd = rng.standard_normal((n + 1, 8)).astype(np.float32)
    O = place(odd, g, odd.shape) if g else jnp.asarray(odd)
    got = jax.jit(lambda a, d, s: blocked._exchange_rows(a, d, s, g))(
        O, touched, perm[touched])
    np.testing.assert_array_equal(np.asarray(got)[:n], odd[:n][perm])


# -- info -------------------------------------------------------------------

@pytest.mark.parametrize("fault", ["rank", "zero_block"])
def test_singular_input_reports_info_as_one_device_does(grid, fault, rng):
    n, mb = 768, 8
    a = (rng.random((n, n), dtype=np.float32) - np.float32(0.5))
    if fault == "rank":
        a[:, 500] = a[:, 17]                # a repeated column
    else:
        a[:, 304:312] = 0.0                 # an exactly singular block
    F = st.getrf(st.Matrix(a, mb=mb, grid=grid), opts_of(grid))
    one = st.getrf(st.Matrix(a, mb=mb),
                   {Option.MethodFactor: MethodFactor.Tiled})
    assert int(F.info) == int(one.info)
    if fault == "zero_block":
        assert int(F.info) == 305
    ipiv = np.asarray(F.pivots)
    assert np.all(ipiv >= np.arange(n)) and np.all(ipiv < n)


# -- no more of the matrix on a device than its block -------------------------

def test_no_whole_matrix_on_one_device(grid):
    """Neither the arrays `st.gesv` leaves nor any value inside its
    factor and solve programs is more of the n x n matrix than a
    device's own block, and past the first stage a device's share is
    its block of the stage's square; the check can see the gather the
    form before PR 49 made (`_lu_scan` under a grid: its
    `dynamic_slice`s, its roll and its `a[perm]`)."""
    n, mb = 768, 8
    a, b = system(11, n)
    A = st.Matrix(a, mb=mb, grid=grid)
    B = st.Matrix(b, mb=mb, grid=grid)
    before = {id(x) for x in jax.live_arrays()}
    F, X = st.gesv(A, B, opts_of(grid))
    jax.block_until_ready(X.data)
    for arr in jax.live_arrays():
        if id(arr) not in before and arr.size >= n * n:
            assert {s.data.shape for s in arr.addressable_shards} == \
                {(n // 2, n // 2)}
    programs = {
        "factor": lumod._grid_getrf_programs(grid)[1].lower(
            A.data, mb, 1, mb, n, n),
        "solve": lumod._grid_getrs_program(grid).lower(
            F.LU, F.perm, B)}
    for name, lowered in programs.items():
        text = lowered.compile().as_text()
        assert "f32[%d,%d]" % (n // 2, n // 2) in text, name
        assert _beyond_a_block(text, n) == [], name
        # nor a device's block viewed by its blocks (the masked form)
        assert "[%d,%d,%d]" % (n // 2, n // mb // 2, mb) not in text, name
    text = programs["factor"].compile().as_text()
    plan = blocked.chol_scan_stages(n, mb, grid)
    assert len(plan) == blocked.CHOL_SCAN_STAGES
    for r, _w in plan[1:]:
        assert "f32[%d,%d]" % ((n - r) // 2, (n - r) // 2) in text, r
    # the rows a step exchanges, and no more of them
    assert "f32[%d,%d]" % (2 * mb, n // 2) in text
    old = jax.jit(lambda a: lumod._lu_scan(a, mb, True)).lower(A.data)
    assert _beyond_a_block(old.compile().as_text(), n)


# -- counters ----------------------------------------------------------------

def test_the_plan_by_hand(grid):
    """n=49152, nb=512 on a 2x2: four stages of 24 steps on squares of
    49152, 36864, 24576 and 12288."""
    n, nb = 49152, 512
    plan = lumod.lu_scan_plan(n, nb, grid)
    hs = [49152, 36864, 24576, 12288]
    assert plan["stages"] == 4 and plan["heights"] == hs
    assert plan["blocks"] == "local" and plan["steps"] == 96
    assert plan["update_flops"] == sum(2 * m * m * (n // 4) for m in hs)
    assert plan["update_flops_needed"] == 2 * n ** 3 // 3
    assert plan["update_flops"] / plan["update_flops_needed"] == \
        pytest.approx(1.40625)
    # 2 nb rows a step: of the square, and past the first stage of the
    # n-wide result too
    assert plan["exchange_bytes"] == 24 * 2 * nb * 4 * (
        hs[0] + sum(m + n for m in hs[1:]))
    assert plan["exchange_bytes_full"] == 96 * n * n * 4
    assert plan["exchange_bytes"] / 1e9 == pytest.approx(26.575, abs=1e-3)
    assert plan["panel_rows_factored"] == 24 * sum(hs)
    assert plan["panel_rows_live"] == sum(n - j * nb for j in range(96))
    # one stage where the order is no multiple of nb lcm(p, q)
    one = lumod.lu_scan_plan(776, 8, grid)
    assert (one["stages"], one["blocks"]) == (1, "masked")
    assert one["update_flops"] == 2 * 776 ** 3
    assert one["exchange_bytes"] == 97 * 16 * 776 * 4


def test_getrf_counts_its_plan_at_dispatch(grid, bus):
    n, mb = 768, 8
    a, b = system(5, n)
    solve_on(grid, a, b, mb)                # traced before the bus is on
    obs.enable()
    solve_on(grid, a, b, mb)
    c = obs.snapshot()["metrics"]["counters"]
    plan = lumod.lu_scan_plan(n, mb, grid)
    assert c["grid.lu_block_steps_local"] == 3 * (n // mb)
    for name in ("update_flops", "update_flops_needed", "exchange_bytes",
                 "exchange_bytes_full", "panel_rows_live",
                 "panel_rows_factored"):
        assert c["grid.lu_" + name] == plan[name], name
    assert c["grid.h2d_bytes"] == a.nbytes + b.nbytes


# -- spans -------------------------------------------------------------------

def test_gridlu_spans_reach_the_host_plane(grid, bus, host_plane):
    n, mb = 96, 8
    a, b = system(13, n)
    solve_on(grid, a, b, mb)                # compiled before the session
    obs.enable()
    seen = host_plane(lambda: solve_on(grid, a, b, mb), gridlutrace.SPANS)
    by_name = {}
    for ev in seen:
        by_name.setdefault(ev[2], []).append(ev)
    assert set(by_name) == set(gridlutrace.SPANS)
    assert len(by_name["grid::place"]) == 2
    getrf, gesv = by_name["getrf"][0], by_name["gesv"][0]
    assert {k: getrf[3][k] for k in ("factor", "form", "nb", "grid")} == \
        {"factor": "tiled", "form": "pipelined", "nb": 8, "grid": "2x2"}
    assert max(e[1] for e in by_name["grid::place"]) <= gesv[0]
    for child, parent in (("getrf::prep", getrf),
                          ("getrf::grid_factor", getrf),
                          ("getrf", gesv), ("getrs::grid_solve", gesv)):
        ev = by_name[child][0]
        assert parent[0] <= ev[0] <= ev[1] <= parent[1], child


def test_a_solve_is_a_handful_of_dispatches(grid, bus, dispatches_under):
    """Under a grid `getrf` is one program and `getrs` one (a general
    matrix that fills its tiles needs no prep)."""
    n, mb = 768, 8
    a, b = system(19, n)
    A, B = st.Matrix(a, mb=mb, grid=grid), st.Matrix(b, mb=mb, grid=grid)
    st.gesv(A, B, opts_of(grid))
    obs.enable()
    got = dispatches_under(lambda: st.gesv(A, B, opts_of(grid)),
                           ["getrf", "getrs::grid_solve"])
    assert got["getrf"] == [["factor"]]
    assert got["getrs::grid_solve"] == [["solve"]]


# -- the readers of the traced solve ------------------------------------------

NB = 512


def _chip():
    """One chip's `XLA Ops` line by hand: a stage loop [0, 1000] whose
    body holds the panel kernel's block loop [100, 400] (with a column
    loop and a fusion under it), the exchange's gather [400, 450],
    all-reduce [450, 520] and scatter [520, 560], a block's all-reduce
    [560, 600] and the update [600, 950]; then the solve's loop [1000,
    1200] with one all-reduce in it."""
    f32 = "f32[%d,%d]{1,0}"
    rows, blk = f32 % (2 * NB, 24576), f32 % (24576, 24576)
    pan = f32 % (49152, NB)
    return [
        (0, 1000, "%%while.1 = (s32[], %s, s32[49152]{0}) while(%%t), "
         "body=%%b" % blk),
        (100, 400, "%%while.2 = (s32[], %s, s32[%d]{0}) while(%%t2), "
         "body=%%b2" % (pan, NB)),
        (110, 390, "%while.3 = (s32[], f32[65,49152]{1,0}) while(%t3)"),
        (120, 380, "%fusion.9 = f32[65,49152]{1,0} fusion(%x)"),
        (400, 450, "%%gather_fusion = %s fusion(%s %%p)" % (rows, blk)),
        (450, 520, "%%all-reduce.4 = %s all-reduce(%s %%g), to_apply=%%add"
         % (rows, rows)),
        (520, 560, "%%scatter_fusion = %s fusion(%s %%p, %s %%r)"
         % (blk, blk, rows)),
        (560, 600, "%%all-reduce.5 = %s all-reduce(%%c)" % (f32 % (24576, NB))),
        (600, 950, "%%convolution_fusion = %s fusion(%%l, %%u)" % blk),
        (1000, 1200, "%%while.7 = (s32[], %s) while(%%s)"
         % (f32 % (24576, 32))),
        (1010, 1050, "%%all-reduce.8 = %s all-reduce(%%x)" % (f32 % (NB, 32))),
    ]


def test_phases_by_hand():
    table = {}
    got = gridlutrace.phases(_chip(), NB, table)
    assert got["panel"] == pytest.approx(300e-9)
    assert got["exchange"] == pytest.approx(160e-9)
    assert got["collective"] == pytest.approx(80e-9)
    # the loops' own time, the update and what the solve's loop does
    assert got["rest"] == pytest.approx((1200 - 300 - 160 - 80) * 1e-9)
    assert sum(got.values()) == pytest.approx(1200e-9)
    assert table[("panel", "%fusion.9 fusion f32[65,49152]")] == \
        [pytest.approx(260e-9), 1]
    # a loop nested in a loop that holds no array nb wide is no panel,
    # and an array 2 nb tall inside the panel is the panel's
    odd = [(0, 100, "%while.1 = (s32[]) while(%t)"),
           (10, 90, "%while.2 = (s32[], f32[1024,24576]{1,0}) while(%u)")]
    assert gridlutrace.phases(odd, NB)["panel"] == 0.0


def _run(**kw):
    return {"workload": CELL, "trace": {"busy_s": 1.0, "window_s": 2.0},
            "counters": {}, "histograms": {}, "spans": {},
            "device_kind": "TPU v5 lite",
            "config": {"routine": "gesv", "n": 49152, "nrhs": 64,
                       "mb": NB, "grid": [2, 2]},
            "records": {"solves": 5, "slice_solves": 1}, **kw}


def test_gridlu_metrics_by_hand(monkeypatch, grid):
    ph = gridlutrace.phases(_chip(), NB)
    half = {k: v / 2 for k, v in ph.items()}
    sl = gridlutrace.slice_of(
        [[(1000, 1400), (1500, 1900)]] * 4,
        [(0, 900, "grid::place"), (0, 850, "matrix::h2d"),
         (900, 1000, "grid::place"), (900, 990, "matrix::h2d"),
         (1000, 2000, "gesv"), (1000, 1500, "getrf"),
         (1500, 2000, "getrs::grid_solve")])
    # per chip: the lead [0,1000], the gap, the tail
    assert sl.idle[0] == [[0, 1000], [1400, 1500], [1900, 2000]]
    t = {"slice": sl, "phase_s": [ph, ph, half, half],
         "busy_s": [1200e-9, 1200e-9, 600e-9, 1200e-9]}
    monkeypatch.setattr(gridlutrace, "load", lambda run: t)
    load = bench_run.load_module
    plan = lumod.lu_scan_plan(49152, NB, grid)
    counters = {"grid.lu_" + k: 5 * v for k, v in plan.items()
                if isinstance(v, int) and k not in ("stages", "steps")}
    counters["grid.lu_block_steps_local"] = 5 * 288
    counters["grid.h2d_bytes"] = 5 * 4 * (49152 ** 2 + 49152 * 64)
    run = _run(counters=counters, spans={"grid::place": 7.5})
    share = load("layer_metrics", "gridlu.panel_busy_share").compute(run)
    assert share == pytest.approx(100 * (3 * 0.25 + 0.125) / 4)
    assert load("layer_metrics", "gridlu.exchange_busy_share").compute(
        run) == pytest.approx(100 * (3 * 160 / 1200 + 80 / 1200) / 4)
    # one chip's flops at the heights the panels ran, over its peak
    flops = NB * NB * (plan["panel_rows_factored"] - 96 * NB / 3)
    assert gridlucount.panel_factors(49152, NB,
                                     plan["panel_rows_factored"]) == \
        (flops, 8.0 * NB * plan["panel_rows_factored"])
    panel_s = (300e-9 * 2 + 150e-9 * 2) / 4
    assert load("layer_metrics", "gridlu.panel_roofline").compute(run) == \
        pytest.approx(100 * max(flops / 197e12, 8.0 * NB * plan[
            "panel_rows_factored"] / 819e9) / panel_s)
    assert load("layer_metrics", "gridlu.idle_upload_share").compute(
        run) == pytest.approx(100 * 1000 / 1200)
    assert load("layer_metrics", "gridlu.exchange_gb").compute(run) == \
        plan["exchange_bytes"] / 1e9
    assert load("layer_metrics", "gridlu.update_work_ratio").compute(
        run) == pytest.approx(1.40625)
    assert load("layer_metrics", "gridlu.block_local_share").compute(
        run) == 100.0
    assert load("layer_metrics", "gridlu.h2d_gb").compute(run) == \
        4 * (49152 ** 2 + 49152 * 64) / 1e9
    assert load("layer_metrics", "gridlu.upload_s").compute(run) == 1.5
    # lib/gridtrace.py's readers serve the routine as they are
    g = {"slice": sl, "busy_s": [4.0, 4.0, 4.0, 3.0],
         "collective_s": [0.4, 0.4, 0.4, 0.6], "launches": [2, 2, 2, 2]}
    monkeypatch.setattr(gridtrace, "load", lambda run: g)
    least = (2 * 49152 ** 3 / 3 + 2 * 49152 ** 2 * 64) / (4 * 197e12)
    assert load("layer_metrics", "gridlu.solve_roofline").compute(run) == \
        pytest.approx(100 * least / 3.75)
    assert load("layer_metrics", "gridlu.launches_per_solve").compute(
        run) == 2
    assert load("layer_metrics", "gridlu.collective_share").compute(
        run) == pytest.approx((3 * 10.0 + 20.0) / 4)
    assert load("layer_metrics", "gridlu.busy_imbalance").compute(run) == \
        pytest.approx(100 / 3.75)
    assert load("layer_metrics", "idle_share.gridlu").compute(run) == 50.0


@pytest.mark.parametrize("name", METRICS)
def test_gridlu_metric_is_found_and_silent_without_a_trace(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == entry["moves"])
    assert CELL in moved["workloads"]
    assert entry["moves"] == CFG["wall_metric"]
    compute = bench_run.load_module("layer_metrics", name).compute
    run = _run(trace=None)
    assert compute(run) is None
    # a reduced trace but no xplane of this run to read, or a program
    # that published no such span or counter (the parent commit):
    # nothing, and no raise
    run["trace"] = {"busy_s": 1.0, "window_s": 2.0}
    got = compute(run)
    assert got is None or isinstance(got, float)


def test_the_cell_is_the_benchmarks_tenth():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "repeat", 4)
    assert len(BENCH["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 2
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == ["n"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    assert {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())} == set(METRICS)
    tol = CFG["tolerance"]
    assert tol["reason"] and tol["ipiv_invalid"] == 0
    assert 0 < tol["scaled_residual_max"] and 0 < tol["factor_residual_rms"]


# -- a rehearsal of the cell ---------------------------------------------------

def test_rehearsal_publishes_the_cells_spans(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c",
         _RUN % {"root": ROOT, "trace": str(tmp_path / "trace")},
         "--workload", CELL, "--seed", "3000000019", "--seconds", "1.5",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == 4
    # the rehearsal's n=768, mb=8 is the cell's route: nt=96, four stages
    m = last["metrics"]
    assert m["gridlu.h2d_gb"]["value"] == 4 * (768 * 768 + 768 * 8) / 1e9
    assert m["gridlu.upload_s"]["value"] > 0
    assert m["gridlu.block_local_share"]["value"] == 100.0
    assert m["gridlu.update_work_ratio"]["value"] == 1.40625
    assert m["gridlu.exchange_gb"]["value"] == 24 * 16 * 4 * (
        768 + 3 * 768 + 576 + 384 + 192) / 1e9
    xplane = next(ln["xplane"] for ln in lines if ln.get("phase") == "trace")
    seen = {e[2]: e for e in gridlutrace.host_events(
        reduce_trace.load(xplane))}
    assert set(gridlutrace.SPANS) <= set(seen), \
        sorted(set(gridlutrace.SPANS) - set(seen))
    route = seen["getrf"][3]
    assert (route["form"], route["nt"], route["stages"], route["blocks"],
            route["grid"]) == ("scan", 96, 4, "local", "2x2")
