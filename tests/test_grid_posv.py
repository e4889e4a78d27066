"""The grid deployment `grid2x2-posv-n49152` (PR 27) at sizes the CPU
tier holds, on a 2x2 grid of the virtual devices: `st.posv` under
`Option.Grid` against the benchmark's plain reference at the cell's
route (nt=96, the scan form, its blocks read and written on the chips
that own them, PR 32), at a size whose blocks straddle two chips (the
masked form) and at an unrolled size, the two forms against each
other and against the plain slices, the placement
that never puts a matrix whole on one device, the spans and the route
the per-layer metrics read, the reader of a four-chip trace on planes
made by hand, and a rehearsal of the cell."""

import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.core.methods import MethodFactor
from slate_tpu.core.options import Option
from slate_tpu.linalg import chol
from slate_tpu.linalg.blocked import CHOL_SCAN_STAGES, chol_scan_stages
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import metrics as obs_metrics
from slate_tpu.parallel.sharding import place

from benchmarks import run as bench_run
from benchmarks.lib import gen, gridtrace, plainref, reduce_trace, refcheck

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "grid-posv"
GRID_METRICS = ["grid.h2d_gb", "grid.upload_s", "grid.collective_share",
                "grid.busy_imbalance", "grid.launches_per_solve",
                "grid.solve_roofline", "idle_share.grid",
                "grid.idle_upload_share", "grid.block_local_share",
                "grid.update_work_ratio"]


@pytest.fixture(scope="module")
def grid():
    return st.make_grid(2, 2, devices=jax.devices()[:4])


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
    r = gen.rng(seed, "solve")
    return gen.spd_gram(r, n), gen.rhs(r, n, nrhs)


def solve_on(grid, a, b, mb):
    A = st.HermitianMatrix(st.Uplo.Lower, a, mb=mb, grid=grid)
    B = st.Matrix(b, mb=mb, grid=grid)
    return st.posv(A, B, opts_of(grid))


# -- the system against the plain reference --------------------------------

#: (n, mb) -> limits on max|X - X_ref| / max|X_ref| and on the same for
#: the sampled rows of L. Read on seeds 1-4 and 3000000019 (CPU, f32):
#: at n=768 the program lies 1.00e-6 - 1.26e-6 (X) and 1.5e-7 - 4.1e-7
#: (L) from the f32 reference and 4.15e-6 - 5.93e-6 and 1.19e-6 -
#: 1.42e-6 from the reference with its products at `high` (bf16x3); at
#: n=96, 2.7e-7 - 4.9e-7 and 7.4e-8 - 8.1e-8 against 1.26e-6 - 1.59e-6
#: and 5.7e-7 - 7.9e-7. Each limit sits between: two f32 Cholesky
#: solves differ by the order of their sums, a few eps times the
#: growth; a product at `high` is wrong by 2^-18 of its terms. At n=776
#: (388 rows a device, so a block of 8 can straddle two: the masked
#: form, PR 32) the same seeds read 8.9e-7 - 1.03e-6 and 1.6e-7 -
#: 2.4e-7 against 3.86e-6 - 6.11e-6 and 1.19e-6 - 1.43e-6: n=768's
#: limits lie between.
LIMITS = {(768, 8): (2.3e-6, 7.0e-7), (96, 8): (8.0e-7, 2.2e-7),
          (776, 8): (2.3e-6, 7.0e-7)}


@pytest.mark.parametrize("n,mb,form,blocks,stages", [
    (768, 8, "scan", "local", CHOL_SCAN_STAGES),
    (96, 8, "unrolled", "slice", 0), (776, 8, "scan", "masked", 1)])
def test_grid_posv_agrees_with_the_plain_reference(grid, bus, n, mb, form,
                                                   blocks, stages):
    a, b = system(3000000019, n)
    rows = refcheck.factor_sample(n, gen.rng(3000000019, "sample"), 32)
    obs.enable()
    L, X = solve_on(grid, a, b, mb)
    route = [e for e in obs.bus_events(cat="driver")
             if e.name == "potrf"][-1].args
    assert (route["form"], route["nt"], route["grid"], route["blocks"],
            route["stages"]) == (form, n // mb, "2x2", blocks, stages)
    # the block steps of the scan forms dispatched, by how they reach
    # their blocks: nt for the factor, nt for each of the two sweeps
    counters = obs.snapshot()["metrics"]["counters"]
    steps = {k.rsplit("_", 1)[1]: v for k, v in counters.items()
             if k.startswith("grid.block_steps_")}
    assert steps == ({} if form == "unrolled" else {blocks: 3 * (n // mb)})
    # and the update FLOPs of the factor's stages beside the n^3/3 a
    # Cholesky needs: the whole matrix at every step in one stage, the
    # trailing squares of the rehearsal's four (48 units of 16 rows,
    # 12 a stage: 1 + (3/4)^2 + (1/2)^2 + (1/4)^2 quarters of 2 n^3)
    flops = {k.rsplit(".", 1)[1]: v for k, v in counters.items()
             if k.startswith("grid.update_flops")}
    if form == "unrolled":
        assert flops == {}
    else:
        assert flops["update_flops_needed"] == n ** 3 // 3
        assert flops["update_flops"] == (
            2 * n ** 3 if stages == 1 else
            sum(2 * (n - r) ** 2 * (n // stages)
                for r in range(0, n, n // stages)))
        assert (stages > 1) == (blocks == "local")
    assert len(X.data.sharding.device_set) == 4
    x, l = X.to_numpy(), np.tril(np.asarray(L.data))[rows]
    assert x.dtype == np.float32

    def apart(matmul):
        fac = []
        xr = plainref.chol_solve(a, b, matmul, factor=fac)
        return (np.abs(x - xr).max() / np.abs(xr).max(),
                np.abs(l - fac[0][rows]).max() / np.abs(fac[0]).max())

    x_lim, l_lim = LIMITS[(n, mb)]
    dx, dl = apart(plainref.matmul_f32)
    assert dx <= x_lim and dl <= l_lim, (dx, dl)
    # the same comparison refuses the reference one precision down
    cx, cl = apart(plainref.matmul_bf16x3)
    assert cx > x_lim and cl > l_lim, (cx, cl)
    # and at the rehearsal's size the cell's own two numbers hold at
    # the rehearsal's limits
    reh = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "configs",
        "grid2x2-posv-n49152.json"))["rehearsal"]
    if (n, mb) == (reh["n"], reh["mb"]):
        assert refcheck.hpl_resid_blocked(a, x, b, n) \
            <= reh["tolerance"]["scaled_residual_max"]
        lr = np.asarray(L.data)[rows]
        assert refcheck.factor_resid(a[np.ix_(rows, rows)], lr, rows) \
            <= reh["tolerance"]["factor_residual_rms"]


# -- a block on the chip that owns it ---------------------------------------

def test_local_form_is_bitwise_the_masked_form(grid, monkeypatch):
    """At the rehearsal's size every block lies on one device: L and X
    by the per-device slices and sums are bit for bit what the masked
    sums over the whole matrix give (both add exact zeros)."""
    from slate_tpu.linalg import blocked
    n, mb = 768, 8
    assert blocked.grid_blocks(n, mb, grid) == "local"
    a, b = system(23, n)
    L, X = solve_on(grid, a, b, mb)
    local = np.asarray(L.data), X.to_numpy()
    def forget():
        # the programs are kept by grid and shape, whatever the form
        chol._grid_potrf_programs.cache_clear()
        chol._grid_potrs_program.cache_clear()

    monkeypatch.setattr(blocked, "block_on_one_chip", lambda *a: False)
    assert blocked.grid_blocks(n, mb, grid) == "masked"
    forget()
    try:
        L, X = solve_on(grid, a, b, mb)
        masked = np.asarray(L.data), X.to_numpy()
    finally:
        forget()
    assert local[0].tobytes() == masked[0].tobytes()
    assert local[1].tobytes() == masked[1].tobytes()


def test_a_straddling_block_is_seen_from_the_shapes():
    from slate_tpu.linalg.blocked import block_on_one_chip
    assert block_on_one_chip(49152, 512, 2)         # 48 blocks a chip
    assert block_on_one_chip(768, 8, 2)
    assert not block_on_one_chip(776, 8, 2)         # 388 rows a chip
    assert block_on_one_chip(776, 8, 1)
    assert block_on_one_chip(770, 8, 4)             # not spread at all


@pytest.mark.parametrize("p,q", [(2, 2), (1, 4), (4, 1)])
@pytest.mark.parametrize("axis", [0, 1])
def test_blocks_round_trip_on_their_owners(p, q, axis):
    """Every block of both axes, taken and put back changed, against
    the plain slices; the taken block is whole along its axis."""
    from slate_tpu.linalg.blocked import _put_block, _take_block
    g = st.make_grid(p, q, devices=jax.devices()[:4])
    n, nb = 64, 8
    a = np.arange(n * n, dtype=np.float32).reshape(n, n)
    A = place(a, g, (n, n))
    take = jax.jit(lambda a, k: _take_block(a, k, nb, axis, g))
    put = jax.jit(lambda a, blk, k: _put_block(a, blk, k, nb, axis, g))
    for k in range(n // nb):
        at = [slice(None), slice(None)]
        at[axis] = slice(k * nb, (k + 1) * nb)
        at = tuple(at)
        blk = take(A, k)
        np.testing.assert_array_equal(np.asarray(blk), a[at])
        assert {s.data.shape[axis] for s in blk.addressable_shards} == {nb}
        want = a.copy()
        want[at] = -a[at] - 1
        got = put(A, -blk - 1, k)
        np.testing.assert_array_equal(np.asarray(got), want)
        assert got.sharding.is_equivalent_to(A.sharding, 2)


@pytest.mark.parametrize("p,q", [(2, 2), (1, 4), (4, 1), (0, 0)])
def test_a_rectangle_moves_between_orders(p, q, rng, monkeypatch):
    """What a stage boundary of the scan form does: a static rectangle
    of one spread matrix written into another of another order, in
    place or into a new one, against the plain slices (no grid: (0,
    0)); in strips of a few columns too."""
    from slate_tpu.linalg import blocked
    g = st.make_grid(p, q, devices=jax.devices()[:4]) if p else None

    def on(a):
        return place(a, g, a.shape) if g else jax.numpy.asarray(a)

    src = rng.standard_normal((48, 64)).astype(np.float32)
    dst = rng.standard_normal((32, 40)).astype(np.float32)
    cases = [((32, 32), (16, 16), (0, 0)),      # a trailing square
             ((32, 8), (16, 0), (0, 0)), ((17, 23), (30, 5), (11, 17)),
             ((1, 1), (47, 63), (31, 39)), ((32, 40), (3, 9), (0, 0))]
    for piece_bytes in (blocked.MOVE_PIECE_BYTES, 1):
        monkeypatch.setattr(blocked, "MOVE_PIECE_BYTES", piece_bytes)
        for (h, w), (r0, c0), (r1, c1) in cases:
            for new in (False, True):
                want = np.zeros_like(dst) if new else dst.copy()
                want[r1:r1 + h, c1:c1 + w] = src[r0:r0 + h, c0:c0 + w]
                got = jax.jit(
                    lambda s, d: blocked._move_rect(
                        s, dst.shape if new else d, (h, w), (r0, c0),
                        (r1, c1), g))(on(src), on(dst))
                np.testing.assert_array_equal(np.asarray(got), want)
                if g:
                    assert got.sharding.is_equivalent_to(
                        on(dst).sharding, 2)


# -- placement -------------------------------------------------------------

def test_placement_sends_each_device_its_own_block(grid, bus):
    n, mb = 96, 8
    a, b = system(7, n)
    obs.enable()
    A = st.HermitianMatrix(st.Uplo.Lower, a, mb=mb, grid=grid)
    B = st.Matrix(b, mb=mb, nb=8, grid=grid)
    for M, host in ((A, a), (B, b)):
        shards = M.data.addressable_shards
        assert len({s.device for s in shards}) == 4
        # each device holds its block and only its block
        assert {s.data.shape for s in shards} == \
            {(host.shape[0] // 2, host.shape[1] // 2)}
        for s in shards:
            np.testing.assert_array_equal(np.asarray(s.data),
                                          host[s.index])
        assert np.asarray(M.data).tobytes() == host.tobytes()
    counters = obs.snapshot()["metrics"]["counters"]
    assert counters["grid.h2d_bytes"] == a.nbytes + b.nbytes
    spans = [(e.name, e.args["bytes"], e.args["devices"])
             for e in obs.bus_events(cat="staging")]
    # the hand-over closes inside the placement, which waits for the
    # shards
    assert spans == [("matrix::h2d", a.nbytes, 4),
                     ("grid::place", a.nbytes, 4),
                     ("matrix::h2d", b.nbytes, 4),
                     ("grid::place", b.nbytes, 4)]


def test_placement_pads_on_the_mesh(grid, bus, rng):
    """A shape the tiles do not divide: the source crosses once, as it
    is, and the padding is added where it lands."""
    a = rng.standard_normal((50, 20)).astype(np.float32)
    obs.enable()
    M = st.Matrix(a, mb=8, grid=grid)
    assert M.data.shape == (56, 24) and (M.m, M.n) == (50, 20)
    assert len(M.data.sharding.device_set) == 4
    want = np.zeros((56, 24), np.float32)
    want[:50, :20] = a
    np.testing.assert_array_equal(np.asarray(M.data), want)
    assert obs.snapshot()["metrics"]["counters"]["grid.h2d_bytes"] \
        == a.nbytes
    # what is on devices already is spread the same way, uncounted
    D = place(jax.numpy.asarray(a), grid, (56, 24))
    np.testing.assert_array_equal(np.asarray(D), want)
    assert obs.snapshot()["metrics"]["counters"]["grid.h2d_bytes"] \
        == a.nbytes
    Z = st.TiledMatrix.zeros(50, 20, 8, grid=grid)
    assert Z.data.shape == (56, 24) \
        and len(Z.data.sharding.device_set) == 4


def _beyond_a_block(hlo_text, n):
    """The 2-D shapes of a partitioned program that hold more than a
    device's own (n/2, n/2) block of an n x n matrix."""
    return [m for m in re.findall(r"\[(\d+),(\d+)\]", hlo_text)
            if int(m[0]) * int(m[1]) > n * n // 4]


def test_no_whole_matrix_on_one_device(grid):
    """Neither the arrays `st.posv` leaves nor any value inside its two
    programs is more of the n x n matrix than a device's own block
    (the scan form's `dynamic_slice` gathered the matrix onto every
    device: the whole of it on the TPU, PERF.md PR 27)."""
    n, mb = 768, 8
    a, b = system(11, n)
    A = st.HermitianMatrix(st.Uplo.Lower, a, mb=mb, grid=grid)
    B = st.Matrix(b, mb=mb, grid=grid)
    before = {id(x) for x in jax.live_arrays()}
    L, X = st.posv(A, B, opts_of(grid))
    jax.block_until_ready(X.data)
    for arr in jax.live_arrays():
        if id(arr) not in before and arr.size >= n * n:
            assert {s.data.shape for s in arr.addressable_shards} == \
                {(n // 2, n // 2)}
    _prep, factor = chol._grid_potrf_programs(grid)
    programs = {
        "factor": factor.lower(A.data, mb, lookahead=1),
        "solve": chol._grid_potrs_program(grid).lower(L, B)}
    for name, lowered in programs.items():
        text = lowered.compile().as_text()
        assert "f32[%d,%d]" % (n // 2, n // 2) in text, name
        assert _beyond_a_block(text, n) == [], name
        # nor a device's block viewed by its blocks, which the masked
        # form rewrote at every step (PR 32)
        assert "[%d,%d,%d]" % (n // 2, n // mb // 2, mb) not in text, name
    # the factor runs in stages: after the first, a device's share of
    # the update is its block of the stage's trailing square, spread
    # over the four again, and no larger value is made on the way
    # (the partitioner's own answer to the slice copies full-height
    # strips of its source, and gathers the matrix where a stage's
    # columns straddle two devices': PERF.md, PR 41)
    text = programs["factor"].compile().as_text()
    plan = chol_scan_stages(n, mb, grid)
    assert len(plan) == CHOL_SCAN_STAGES
    for r, _w in plan[1:]:
        assert "f32[%d,%d]" % ((n - r) // 2, (n - r) // 2) in text, r
    # the check can see a gather: a column block at a traced offset by
    # `dynamic_slice`, as the scan form took it
    from slate_tpu.linalg.blocked import _take_block
    for g, whole in ((None, True), (grid, False)):
        text = jax.jit(lambda a, k: _take_block(a, k, mb, 1, g)).lower(
            A.data, 3).compile().as_text()
        assert bool(_beyond_a_block(text, n)) is whole


# -- spans -----------------------------------------------------------------

def test_grid_spans_reach_the_host_plane(grid, bus, host_plane):
    n, mb = 96, 8
    a, b = system(13, n)
    solve_on(grid, a, b, mb)                # compiled before the session
    obs.enable()
    seen = host_plane(lambda: solve_on(grid, a, b, mb), gridtrace.SPANS)
    by_name = {}
    for ev in seen:
        by_name.setdefault(ev[2], []).append(ev)
    assert set(by_name) == set(gridtrace.SPANS)
    assert len(by_name["grid::place"]) == 2
    potrf, posv = by_name["potrf"][0], by_name["posv"][0]
    assert {k: potrf[3][k] for k in ("factor", "form", "nb", "nt",
                                     "grid")} == \
        {"factor": "tiled", "form": "unrolled", "nb": 8, "nt": 12,
         "grid": "2x2"}
    # nested as opened: the placements before the driver, the steps in
    # it, the hand-over inside its placement
    assert max(e[1] for e in by_name["grid::place"]) <= posv[0]
    for child, parent in (("posv::prep", potrf), ("posv::factor", potrf),
                          ("potrf", posv), ("potrs", posv),
                          ("posv::solve", posv)):
        ev = by_name[child][0]
        assert parent[0] <= ev[0] <= ev[1] <= parent[1], child
    for h2d, plc in zip(by_name["matrix::h2d"], by_name["grid::place"]):
        assert plc[0] <= h2d[0] <= h2d[1] <= plc[1]


def test_grid_sites_are_one_branch_when_off(grid, bus, monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(obs_events, "_annotation", Counting)
    a, b = system(17, 96)
    solve_on(grid, a, b, 8)
    assert made == [] and obs.bus_events() == []
    assert obs.snapshot()["metrics"]["counters"] == {}


# -- the reader of a trace of several chips --------------------------------

def _four_chips():
    """Four chips, each busy [1000,1400] and [1500,1900] but the last,
    which ends at 1700; the upload of A is open over [0,900] and of B
    over [900,1000]; `posv` over [1000,2000]."""
    busy = [(1000, 1400), (1500, 1900)]
    planes = [list(busy), list(busy), list(busy),
              [(1000, 1400), (1500, 1700)]]
    spans = [(0, 900, "grid::place"), (900, 1000, "grid::place"),
             (1000, 2000, "posv"), (1000, 1500, "potrf"),
             (1500, 2000, "potrs"), (1000, 1010, "posv::factor")]
    return gridtrace.GridSlice(planes, spans)


def test_grid_slice_counts_the_upload_as_idle():
    sl = _four_chips()
    # per chip: the lead [0,1000], the gap [1400,1500], the tail
    assert sl.idle[0] == [[0, 1000], [1400, 1500], [1900, 2000]]
    assert sl.idle[3][-1] == [1700, 2000]
    assert sl.idle_ns == 3 * 1200 + 1400
    assert sl.busy_ns == [800, 800, 800, 600]
    assert sl.cover(["grid::place"]) == pytest.approx(100 * 4000 / 5000)
    # a root span covers the rest, and a span open while the chips ran
    # covers nothing
    assert sl.cover(["posv"]) == pytest.approx(100 * 1000 / 5000)
    assert sl.cover(["posv::factor"]) == 0.0
    # the device's clock runs ahead: its intervals are moved back
    late = gridtrace.GridSlice([[(1100, 1500)]], [(0, 1000, "grid::place"),
                                                   (1000, 1400, "posv")],
                               offset_ns=100.0)
    assert late.idle == [[[0, 1000]]] and late.cover(["grid::place"]) == 100


def test_collectives_are_told_from_compute():
    names = {
        "%all-gather-start.3 = (f32[8]{0}, f32[32]{0}) all-gather-start("
        "%p), channel_id=1": True,
        "%all-gather-done.3 = f32[32]{0} all-gather-done(%s)": True,
        "%all-reduce.5 = f32[8,8]{1,0} all-reduce(%x), to_apply=%add": True,
        "%collective-permute-done.1 = f32[2]{0} collective-permute-done("
        "%s)": True,
        "%all-to-all.2 = f32[4]{0} all-to-all(%x)": True,
        "%reduce-scatter.9 = f32[4]{0} reduce-scatter(%x)": True,
        "%fusion.90 = f32[24576,24576]{1,0:T(8,128)} fusion(%a)": False,
        "%while.4 = (s32[], f32[2]{0}) while(%t), body=%b": False,
        "%reduce.7 = f32[8]{0} reduce(%x, %z)": False,
        "%copy.23 = f32[2,2]{1,0} copy(%all-to-all.8)": False,
    }
    for name, want in names.items():
        assert gridtrace.is_collective(gridtrace.opcode(name)) is want, name


def _run(t, **kw):
    return {"workload": CELL, "trace": {"busy_s": 1.0, "window_s": 2.0},
            "counters": {}, "histograms": {}, "spans": {},
            "device_kind": "TPU v5 lite",
            "config": {"routine": "posv", "n": 49152, "nrhs": 64},
            "records": {"solves": 5, "slice_solves": 1}, **kw}


def test_grid_metrics_by_hand(monkeypatch):
    t = {"slice": _four_chips(), "busy_s": [4.0, 4.0, 4.0, 3.0],
         "collective_s": [0.4, 0.4, 0.4, 0.6], "launches": [3, 3, 3, 3]}
    monkeypatch.setattr(gridtrace, "load", lambda run: t)
    run = _run(t)
    assert gridtrace.collective_share(run) == \
        pytest.approx((3 * 10.0 + 20.0) / 4)
    assert gridtrace.busy_imbalance(run) == pytest.approx(100 / 3.75)
    # one chip's launches, not the planes' sum
    assert gridtrace.launches_per_solve(run) == 3
    # n^3/3 + 2 n^2 nrhs flops over four chips' peak, over 3.75 s busy
    least = (49152 ** 3 / 3 + 2 * 49152 ** 2 * 64) / (4 * 197e12)
    assert gridtrace.solve_roofline(run) == \
        pytest.approx(100 * least / 3.75)
    assert 0 < gridtrace.solve_roofline(run) < 100
    assert gridtrace.idle_cover(run, ["grid::place"]) == pytest.approx(80)
    h2d = bench_run.load_module("layer_metrics", "grid.h2d_gb").compute
    up = bench_run.load_module("layer_metrics", "grid.upload_s").compute
    run = _run(t, counters={"grid.h2d_bytes": 5 * 9676259328},
               spans={"grid::place": 7.5})
    assert h2d(run) == 9.676259328 and up(run) == 1.5
    # five solves of 96 + 192 block steps on their owners; then one
    # operand of them all whose blocks straddle
    local = bench_run.load_module("layer_metrics",
                                  "grid.block_local_share").compute
    assert local(_run(t, counters={"grid.block_steps_local": 5 * 288})) \
        == 100.0
    assert local(_run(t, counters={"grid.block_steps_local": 4 * 288,
                                   "grid.block_steps_masked": 288})) == 80.0
    assert local(_run(t, counters={"grid.block_steps_masked": 288})) == 0.0
    assert local(_run(t, counters={"grid.h2d_bytes": 7})) is None
    # five solves in four even stages: 6 (1/4) (1 + 9/16 + 1/4 + 1/16)
    # of the n^3/3; in one stage 6; a program that counts neither
    work = bench_run.load_module("layer_metrics",
                                 "grid.update_work_ratio").compute
    n = 49152
    assert work(_run(t, counters={
        "grid.update_flops": 5 * sum(2 * (n * j // 4) ** 2 * (n // 4)
                                     for j in (4, 3, 2, 1)),
        "grid.update_flops_needed": 5 * (n ** 3 // 3)})) == \
        pytest.approx(2.8125)
    assert work(_run(t, counters={
        "grid.update_flops": 2 * n ** 3,
        "grid.update_flops_needed": n ** 3 // 3})) == pytest.approx(6.0)
    assert work(_run(t, counters={"grid.block_steps_local": 288})) is None


@pytest.mark.parametrize("name", GRID_METRICS)
def test_grid_metric_is_found_and_silent_without_a_trace(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == entry["moves"])
    assert CELL in moved["workloads"]
    cfg = next(c for c in BENCH["configs"]
               if c["name"] == "grid2x2-posv-n49152")
    wall = bench_run.load_json(os.path.join(ROOT, cfg["file"]))[
        "wall_metric"]
    assert entry["moves"] == wall
    compute = bench_run.load_module("layer_metrics", name).compute
    run = _run(None, trace=None)
    assert compute(run) is None
    # a reduced trace but no xplane of this run to read, or a program
    # that published no such span or counter (the parent commit):
    # nothing, and no raise
    run["trace"] = {"busy_s": 1.0, "window_s": 2.0}
    got = compute(run)
    assert got is None or isinstance(got, float)


# -- a rehearsal of the cell -----------------------------------------------

#: run.py as it is, but for the trace's directory: its fixed
#: `.bench_trace` is one per checkout (tests/test_hostspans.py)
_RUN = """
import sys
sys.path.insert(0, %(root)r)
from benchmarks import run
from benchmarks.lib.tracer import Tracer
init = Tracer.__init__
Tracer.__init__ = lambda self, directory: init(self, %(trace)r)
sys.exit(run.main(sys.argv[1:]))
"""


def test_rehearsal_publishes_the_grid_cells_spans(tmp_path):
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
    # the rehearsal's n=768, mb=8 is the cell's route: nt=96
    assert last["metrics"]["grid.h2d_gb"]["value"] == \
        4 * (768 * 768 + 768 * 8) / 1e9
    assert last["metrics"]["grid.upload_s"]["value"] > 0
    xplane = next(ln["xplane"] for ln in lines if ln.get("phase") == "trace")
    seen = {e[2]: e for e in gridtrace.host_events(
        reduce_trace.load(xplane))}
    assert set(gridtrace.SPANS) <= set(seen), \
        sorted(set(gridtrace.SPANS) - set(seen))
    assert seen["potrf"][3]["form"] == "scan" \
        and seen["potrf"][3]["nt"] == 96
