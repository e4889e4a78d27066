"""The singular value decomposition deployment `svd-geo-n8192-cond1e4`
(PR 39) at sizes the CPU tier holds, through the rehearsal's tune
entry (n=256, leaves of 32): `st.svd` with no option, the route the
cell times (the polar of A on the eigensolver's own `dc_sign` program,
then its divide and conquer on the Hermitian factor), against the
benchmark's plain reference and numpy's f64 SVD on the configuration's
law, a clustered spectrum, a rank-deficient and a diagonal matrix; the
one executable a bucket that serves both callers; the route on size,
dtype, shape and under a caller's jit; the spans and the counters the
per-layer metrics read; an unconverged polar reported; the kind's
`check()` against sound and unsound answers; the generator; the
readers on planes made by hand; and a rehearsal of the cell
`incore-svd`."""

import importlib
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
from slate_tpu.linalg import polar, spectral_dc
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import metrics as obs_metrics
from slate_tpu.tune import cache as tune_cache

from benchmarks import run as bench_run
from benchmarks.lib import (gen, plainref, plainref_svd, reduce_trace,
                            svdcount, svdgen, svdtrace, uploadtrace)

svd_program = importlib.import_module("slate_tpu.linalg.svd")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL, CONFIG = "incore-svd", "svd-geo-n8192-cond1e4"
CFG = bench_run.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       CONFIG + ".json"))
KIND = bench_run.load_module("kinds", "svd")
EPS = float(np.finfo(np.float32).eps)
METRICS = ["svd.launches_per_solve", "svd.polar_busy_share",
           "svd.eig_busy_share", "svd.polar_iters_per_solve",
           "svd.eig_polar_iters_per_split", "svd.solve_roofline",
           "idle_share.svd", "svd.idle_agenda_share",
           "svd.upload_ready_s", "svd.idle_upload_share"]
OWN_SPANS = ("svd::prep", "svd::polar", "svd::form", "svd::eig",
             "svd::compose", "svd::agenda")
N, LEAF = 256, 32       # ladder 32, 128, 256

#: limits of this file, in the kind's units (n eps_f32, and ||A||_2
#: where the number has a scale): the configuration's rehearsal
#: limits. On the CPU at n=256 the program reads orthogonality
#: 0.6-0.7, residual_max 0.8-0.9 (the eigensolver's dropped coupling
#: blocks and the skew part of U_p^T A) and singular_value_error_max
#: 0.008-0.013, the plain reference 0.6, 0.04 and 0.002.
LIMITS = {k: v for k, v in CFG["rehearsal"]["tolerance"].items()}


@pytest.fixture
def bus():
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()
    yield
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()


@pytest.fixture
def tuned():
    """The library's own tune table, in memory, sends n=256 f32 down
    the cell's route with leaves of 32 (what `--rehearse` does)."""
    KIND.tune_for_rehearsal({**CFG, **CFG["rehearsal"]})
    yield
    tune_cache.reset_cache()


def matrix(law, seed, n=N):
    r = gen.rng(seed, "solve")
    if law == "geo":                # the configuration's own
        return svdgen.geo_general(r, n, CFG["matrix"]["cond"])[0]
    if law == "diagonal":
        return np.diag(r.standard_normal(n)).astype(np.float32)
    if law == "clustered":          # n/4 equal singular values
        sv = np.where(np.arange(n) < n // 4, 0.5, np.linspace(1.0, 0.01, n))
    else:                           # `rank_deficient`: half of them zero
        sv = np.where(np.arange(n) < n // 2, np.linspace(1.0, 0.1, n), 0.0)
    qu, _ = np.linalg.qr(r.standard_normal((n, n)))
    qv, _ = np.linalg.qr(r.standard_normal((n, n)))
    return ((qu * sv) @ qv.T).astype(np.float32)


def graded(a, u, s, vh):
    s_ref, norm2 = KIND.reference_spectrum(a)
    return KIND.grade(a, np.asarray(u), np.asarray(s), np.asarray(vh),
                      s_ref, norm2)


def svd(a, mb=64, **kw):
    res = st.svd(st.Matrix(a, mb=mb), **kw)
    return (None if res.U is None else res.U.to_numpy(), np.asarray(res.s),
            None if res.Vh is None else res.Vh.to_numpy())


def route_of(cat="driver"):
    return [e for e in obs.bus_events(cat=cat) if e.name == "svd"][-1].args


# -- the route the cell times, against two references ----------------------

@pytest.mark.parametrize("law,seed", [
    ("geo", 391), ("geo", 392), ("clustered", 393),
    ("rank_deficient", 394), ("diagonal", 395)])
def test_svd_is_a_backward_stable_decomposition(bus, tuned, law, seed):
    a = matrix(law, seed)
    obs.enable(beacon=False)
    u, s, vh = svd(a)
    assert route_of()["method"] == "qdwh_dc"
    assert s.shape == (N,) and u.shape == vh.shape == (N, N)
    assert {x.dtype for x in (u, s, vh)} == {np.dtype(np.float32)}
    assert (np.diff(s) <= 0).all() and (s >= 0).all()
    got = graded(a, u, s, vh)
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got
    # numpy's f64 SVD of the same data
    s64 = np.linalg.svd(a.astype(np.float64), compute_uv=False)
    assert np.abs(s - s64).max() <= \
        LIMITS["singular_value_error_max"] * N * EPS * s64[0]
    # the plain reference is one too, and the two spectra agree
    up, sp, vhp = plainref_svd.svd(a)
    ref = graded(a, up, sp, vhp)
    assert all(ref[k] <= LIMITS[k] for k in LIMITS), ref
    assert np.abs(s - sp).max() <= \
        2 * LIMITS["singular_value_error_max"] * N * EPS * s64[0]
    # a well separated singular subspace is the same one: the
    # projector onto the right vectors above the widest relative gap
    gap = int(np.argmax(s64[:-1] - s64[1:])) + 1
    p, pp = vh[:gap].T @ vh[:gap], vhp[:gap].T @ vhp[:gap]
    assert np.abs(p - pp).max() <= 1e-3


_WHOLE = {}


def whole(seed):
    """(a, (U, s, Vh)) with both factors, once a process."""
    if seed not in _WHOLE:
        a = matrix("geo", seed)
        _WHOLE[seed] = (a, svd(a))
    return _WHOLE[seed]


@pytest.mark.parametrize("want_u,want_vh", [(True, False), (False, True),
                                            (False, False)])
def test_unwanted_factors_are_not_formed(tuned, want_u, want_vh):
    a, (u0, s0, vh0) = whole(396)
    if not (want_u or want_vh):
        u, s, vh = None, np.asarray(st.svd_vals(st.Matrix(a, mb=64))), None
    else:
        u, s, vh = svd(a, want_u=want_u, want_vh=want_vh)
    assert (u is None) is (not want_u) and (vh is None) is (not want_vh)
    assert np.array_equal(s, s0)
    assert u is None or np.array_equal(u, u0)
    assert vh is None or np.array_equal(vh, vh0)


def test_one_executable_a_bucket_serves_both_callers(tuned):
    """`dc_sign` at the root's bucket is the eigensolver's sign of a
    shifted Hermitian block and the SVD's polar of a general matrix:
    one trace, one executable, two callers (a second polar program at
    n=8192 would be another 41 MB of a 192 MiB compile cache)."""
    a, _ = whole(396)
    sign = spectral_dc._programs(N)["sign"]
    # the SVD ran both callers already (the polar, then the root split)
    traced = sign._cache_size()
    assert traced >= 1
    h = (a + a.T) * np.float32(0.5)
    st.heev(st.HermitianMatrix(st.Uplo.Lower, h, mb=64))
    up, flags = spectral_dc.polar_general(jnp.asarray(a), LEAF)
    assert sign._cache_size() == traced
    # and what the general caller gets is the orthogonal polar factor
    up, flags = np.asarray(up, np.float64), np.asarray(flags)
    assert flags[0] == 0 and flags[1] == 1 and 3 <= flags[2] <= 14
    assert np.linalg.norm(up.T @ up - np.eye(N)) <= 1.0 * N * EPS
    hh = up.T @ a.astype(np.float64)
    assert np.linalg.norm(hh - hh.T) <= 20 * N * EPS
    assert np.linalg.eigvalsh((hh + hh.T) / 2).min() >= -N * EPS
    assert not np.array_equal(up, up.T)
    # the Hermitian caller's answer is symmetric
    s_h, _ = sign(jnp.asarray(h), np.int32(N), jax.device_put(np.False_),
                  np.False_, np.int32(128), l0=None)
    assert np.array_equal(np.asarray(s_h), np.asarray(s_h).T)
    assert sign._cache_size() == traced


# -- route, spans, counters ------------------------------------------------

@pytest.mark.parametrize("case,method", [
    ("auto", "qdwh_dc"), ("dc", "qdwh_dc"), ("small", "xla_svd"),
    ("float64", "xla_svd"), ("rectangular", "xla_svd"),
    ("no_tune_entry", "xla_svd"), ("jit", "xla_svd")])
def test_svd_takes_the_route_on_size_dtype_and_shape(bus, tuned, case,
                                                     method):
    a = matrix("geo", 398)
    kw, cat = {}, "driver"
    if case == "dc":
        kw["opts"] = {st.Option.MethodSVD: st.MethodSVD.DC}
    elif case == "small":
        a = a[:64, :64]
    elif case == "float64":
        a = a.astype(np.float64)
    elif case == "rectangular":
        a = a[:, :128]
    elif case == "no_tune_entry":
        tune_cache.reset_cache()        # off the chip: never
    obs.enable(beacon=False)
    if case == "jit":
        cat = "jit"
        s = np.asarray(jax.jit(
            lambda x: st.svd(st.Matrix(x, mb=64)).s)(a))
    else:
        s = svd(a, **kw)[1]
    route = route_of(cat)
    assert route["method"] == method
    if method == "qdwh_dc":
        assert route["form"] == "agenda" and route["leaf"] == LEAF
        assert route["buckets"] == "32,128,256"
    else:
        assert route["form"] == "native" and "leaf" not in route
    s64 = np.linalg.svd(a.astype(np.float64), compute_uv=False)
    assert np.abs(s - s64).max() <= 0.2 * max(a.shape) * EPS * s64[0]


def test_svd_routes_on_heevs_threshold_on_the_chip(bus, monkeypatch):
    """With no tune entry the chip's threshold is `heev`'s own, read
    from one place by both drivers."""
    import slate_tpu.ops.pallas_kernels as pk
    monkeypatch.setattr(spectral_dc, "SPECTRAL_DC_MIN_N", 128)
    monkeypatch.setattr(spectral_dc, "LEAF", LEAF)
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    a = matrix("geo", 399)
    assert svd_program.agenda_leaf(a) == LEAF
    assert svd_program.agenda_leaf(a[:128, :128]) is None
    assert spectral_dc.route(jnp.asarray((a + a.T) / 2)) == LEAF
    obs.enable(beacon=False)
    u, s, vh = svd(a)
    assert route_of()["method"] == "qdwh_dc"
    got = graded(a, u, s, vh)
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got


def test_svd_spans_and_counters(bus, tuned):
    a = matrix("geo", 400)
    svd(a)                                  # compiled before the bus
    obs.enable(beacon=False)
    svd(a)
    seen = Counter(e.name for e in obs.bus_events(cat="phase"))
    assert set(seen) == set(svdtrace.SPANS) - {"svd", "matrix::h2d"}
    assert all(seen[name] == 1 for name in OWN_SPANS)
    c = obs.snapshot()["metrics"]["counters"]
    assert c["svd.solves"] == 1 and 3 <= c["svd.polar_iters"] <= 14
    assert "svd.unconverged" not in c and "heev.unconverged" not in c
    # the eigensolver ran inside `svd::eig`, as itself: no `heev`
    # driver span, no `heev.solves`, its own splits and leaves
    assert "heev.solves" not in c and c["heev.splits"] >= 7
    assert seen["heev::split"] == seen["heev::agenda"] == c["heev.splits"]
    ev = {e.name: e for e in obs.bus_events(cat="phase")
          if e.name in OWN_SPANS}
    eig = ev["svd::eig"]
    for e in obs.bus_events(cat="phase"):
        if e.name.startswith("heev::"):
            assert eig.t0 <= e.t0 and e.t1 <= eig.t1
    order = sorted(OWN_SPANS, key=lambda k: ev[k].t0)
    assert order == list(OWN_SPANS)


def test_svd_sites_are_one_branch_when_off(bus, tuned, monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(obs_events, "_annotation", Counting)
    svd(matrix("geo", 401))
    assert made == [] and obs.bus_events() == []
    assert obs.snapshot()["metrics"]["counters"] == {}


def test_svd_spans_reach_the_host_plane(bus, tuned, host_plane):
    a = matrix("geo", 402)
    svd(a)
    obs.enable()
    seen = host_plane(lambda: svd(a), svdtrace.SPANS)
    by_name = {}
    for ev in seen:
        by_name.setdefault(ev[2], []).append(ev)
    assert set(by_name) == set(svdtrace.SPANS)
    root = by_name["svd"][0]
    assert root[3]["method"] == "qdwh_dc" and root[3]["form"] == "agenda"
    for child in svdtrace.SPANS:
        if child not in ("svd", "matrix::h2d"):
            for ev in by_name[child]:
                assert root[0] <= ev[0] <= ev[1] <= root[1], child
    assert max(e[1] for e in by_name["matrix::h2d"]) <= root[0]


def test_an_unconverged_polar_is_always_reported(bus, tuned, monkeypatch):
    def two_steps(h, l0=None, general=False):
        u, k, conv = polar.polar_unitary(h, l0=l0, max_iterations=2)
        return jnp.where(general, u, 0.5 * (u + u.conj().T)), k, conv

    monkeypatch.setattr(spectral_dc, "sign_hermitian", two_steps)
    spectral_dc._programs.cache_clear()     # traced with the patch
    try:
        obs.enable(beacon=False)
        with pytest.warns(UserWarning, match="polar iteration of A"):
            svd(matrix("geo", 403))
        c = obs.snapshot()["metrics"]["counters"]
        assert c["svd.unconverged"] == 1 and c["svd.polar_iters"] == 2
    finally:
        spectral_dc._programs.cache_clear()


# -- the comparison that decides `correct` ---------------------------------

def rehearsal_cell(seed):
    cfg = {**CFG, **CFG["rehearsal"]}
    return cfg, KIND.Cell(cfg, {"warm_solves": 1}, seed)


@pytest.mark.parametrize("answer", [
    "sound", "program", "high", "bf16_u", "bf16_vh", "bf16_values",
    "not_descending", "negative", "nan", "wrong_shape", "wrong_dtype"])
def test_check_refuses_what_the_deployment_refuses(tuned, answer):
    cfg, cell = rehearsal_cell(3900000007)
    a = cell.sys.a
    u, s, vh = plainref_svd.svd(a)
    if answer == "program":
        u, s, vh = svd(a)
    elif answer == "high":
        u, s, vh = plainref_svd.svd(a, plainref.matmul_bf16x3)
    elif answer == "bf16_u":
        u = u.astype(plainref.BF16).astype(np.float32)
    elif answer == "bf16_vh":
        vh = vh.astype(plainref.BF16).astype(np.float32)
    elif answer == "bf16_values":
        s = np.sort(s.astype(plainref.BF16).astype(np.float32))[::-1].copy()
    elif answer == "not_descending":
        s = s[::-1].copy()
    elif answer == "negative":
        s, u = s.copy(), u.copy()
        s[-1], u[:, -1] = -s[-1], -u[:, -1]     # still A = U diag(s) Vh
    elif answer == "nan":
        u = np.full_like(u, np.nan)
    elif answer == "wrong_shape":
        vh = vh[:-1]
    elif answer == "wrong_dtype":
        s = s.astype(np.float64)
    cell.answers, cell.walls = [(u, s, vh)] * 2, [0.1]
    got = cell.check()
    sound = answer in ("sound", "program")
    assert got["correct"] is sound, got
    assert got["failed"] == (0 if sound else 1)
    assert got["attempted"] == 1
    assert got["distinct_answers"] == \
        (answer not in ("wrong_shape", "wrong_dtype"))
    assert [c[0] for c in got["compared"]] == list(KIND.NUMBERS)


def test_answers_of_the_same_bytes_are_held_once():
    _, cell = rehearsal_cell(3900000008)
    u, s, vh = np.linalg.svd(cell.sys.a)

    class M:
        def __init__(self, x):
            self.x = x

        def to_numpy(self):
            return self.x.copy()

    first = cell.sys.to_host(s, (M(u), M(vh)), None)
    assert cell.sys.to_host(s.copy(), (M(u), M(vh)), None) is first
    assert cell.sys.to_host(s, (M(-u), M(-vh)), None) is not first
    assert len(cell.sys.held) == 2


def test_generator_states_its_spectrum():
    n, cond = 512, CFG["matrix"]["cond"]
    a, s = svdgen.geo_general(gen.rng(350, "solve"), n, cond)
    assert a.dtype == np.float32 and a.flags.c_contiguous
    assert a.shape == (n, n) and not np.array_equal(a, a.T)
    # the configuration's own s is the reference spectrum `check()`
    # compares against: LAPACK's f64 SVD of the f32 data agrees with it
    # to the rounding of the entries (Weyl)
    s_lapack, norm2 = KIND.reference_spectrum(a)
    assert np.abs(s_lapack - s).max() <= 2.0 ** -24 * np.linalg.norm(s)
    assert norm2 == pytest.approx(1.0, abs=1e-8) and s[0] == 1.0
    assert s[-1] == pytest.approx(1.0 / cond)
    np.testing.assert_allclose(s[1:] / s[:-1], s[1] / s[0], rtol=1e-9)
    # every seed has the same multiset, in another order and another A
    b, other = svdgen.geo_general(gen.rng(351, "solve"), n, cond)
    assert np.array_equal(other, s) and not np.array_equal(a, b)
    assert not np.array_equal(svdgen.spectrum(gen.rng(350, "solve"), n, cond),
                              svdgen.spectrum(gen.rng(351, "solve"), n, cond))
    # dense, with no heavy entry: U's and V's are all about 1/sqrt(n)
    assert np.abs(a).max() <= 60 / n and (a != 0).mean() > 0.99
    # it is the product it says it is
    from scipy.linalg import hadamard
    r = gen.rng(352, "solve")
    m = 64
    small, _ = svdgen.geo_general(r, m, cond)
    r = gen.rng(352, "solve")
    sp = svdgen.spectrum(r, m, cond)
    d1u, d2u, d1v, d2v = (r.choice([-1.0, 1.0], size=m) for _ in range(4))
    h = hadamard(m) / np.sqrt(m)
    u = (d2u[:, None] * h * d1u) @ h
    v = (d2v[:, None] * h * d1v) @ h
    np.testing.assert_allclose(small, (u * sp) @ v.T, atol=1e-7)
    with pytest.raises(ValueError):
        svdgen.geo_general(gen.rng(1, "solve"), 96, cond)


def test_the_laws_tree_by_the_generators_arithmetic():
    """ISSUE 39's check of the law, a split at the mean of a block's
    eigenvalues: three splits at the full size (8192, some 6220 and
    4490 rows against the ladder's 4224), then the 4224 bucket; no
    split of over 1300 rows within 30 rows of a rung. Three of
    1123-1163 rows lie 11 to 29 rows from the rung at 1152: a flip
    there moves one split between buckets of 1152 and 2176, 0.02 s of
    a 5 s solve (the configuration's `assumed.cond` says so)."""
    n = CFG["n"]
    lam = CFG["matrix"]["cond"] ** (-np.arange(n) / (n - 1))
    ladder = spectral_dc._bucket_ladder(n, spectral_dc.LEAF)
    assert ladder == [256, 384, 640, 1152, 2176, 4224]
    tree = svdgen.mean_split_sizes(lam, 900)
    assert [m for m, _ in tree[:4]] == [8192, 6217, 4487, 3042]
    assert [spectral_dc._bucket_of(ladder, n, m) for m, _ in tree[:4]] == \
        [8192, 8192, 8192, 4224]
    near = [m for m, _ in tree if min(abs(m - b) for b in ladder) < 30]
    assert near == [1163, 1126, 1123]
    assert all(m < 1300 for m in near)
    assert len(svdgen.mean_split_sizes(lam, spectral_dc.LEAF)) == 46


def test_compile_probe_asks_the_program(tuned, monkeypatch):
    cfg = {**CFG, **CFG["rehearsal"]}
    KIND.compile_probe(cfg)
    # a size, or a backend, at which `st.svd` is jax's fused program
    with pytest.raises(SystemExit) as exc:
        KIND.compile_probe({**cfg, "n": 64})
    assert exc.value.code == 4
    tune_cache.reset_cache()
    with pytest.raises(SystemExit) as exc:
        KIND.compile_probe(cfg)
    assert exc.value.code == 4
    # the parent's program: no route to ask about
    KIND.tune_for_rehearsal(cfg)
    monkeypatch.delattr(svd_program, "agenda_leaf")
    with pytest.raises(SystemExit) as exc:
        KIND.compile_probe(cfg)
    assert exc.value.code == 4


# -- the readers -----------------------------------------------------------

def _run(trace, **kw):
    return {"workload": CELL, "trace": trace, "counters": {},
            "histograms": {}, "spans": {}, "device_kind": "TPU v5 lite",
            "config": CFG, "records": {"solves": 8, "slice_solves": 1},
            **kw}


def test_svd_slice_counts_the_upload_and_both_reads():
    # the hand-over of A at 0, the first operation at 1000; busy
    # [1000,1400] and [1500,1900]; `svd` over [100,1600], an agenda
    # read of the eigensolver's over [1390,1480] (10 of it before the
    # device fell idle) and the polar's flags read over [1480,1490]
    sl = uploadtrace.UploadSlice(
        [[(1000, 1400), (1500, 1900)]],
        [(0, 50, "matrix::h2d"), (100, 1600, "svd"),
         (110, 1390, "svd::polar"), (1390, 1480, "heev::agenda"),
         (1480, 1490, "svd::agenda")], 0.0, "svd")
    assert sl.idle == [[[0, 1000], [1400, 1500]]]
    assert sl.idle_ns == 1100
    assert sl.cover(svdtrace.AGENDA) == pytest.approx(100 * 90 / 1100)
    assert sl.cover(("svd::agenda",)) == pytest.approx(100 * 10 / 1100)


def test_busy_by_step_finds_the_launch_the_polar_dispatched():
    class E:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur

    class L:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class P:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    pd = type("PD", (), {})()
    pd.planes = [
        P("/device:TPU:0", [
            L(reduce_trace.MODULES,
              [E("jit__svd_form(4)", 900, 1e8),
               E("jit_dc_sign_8192(7)", 0, 8e8),        # the polar's
               E("jit_dc_take_root_8192(2)", 1000, 1e7),
               E("jit_dc_sign_8192(7)", 2000, 7e8),     # the root split's
               E("jit_dc_basis_8192(3)", 3000, 4e8),
               E("jit_dc_leaf_256(1)", 4000, 9e7),
               E("jit_dc_vectors(5)", 5000, 1e8),
               E("jit__svd_compose(6)", 6000, 1e7)]),
            L(reduce_trace.OPS, [E("%fusion.1 = f32[8]{0} fusion()", 0, 9e9)])]),
        P("/host:CPU", [L("main", [E("jit_dc_sign_8192(7)", 0, 4e9)])])]
    ordered = svdtrace.launches(pd)
    assert [name for _, name, _ in ordered][:3] == \
        ["jit_dc_sign_8192", "jit__svd_form", "jit_dc_take_root_8192"]
    got = svdtrace.busy_by_step(ordered)
    assert got["polar"] == pytest.approx(0.8)
    assert got["eig"] == pytest.approx(0.01 + 0.7 + 0.4 + 0.09 + 0.1)
    assert got["all"] == pytest.approx(0.8 + 1.3 + 0.1 + 0.01)
    assert 95 < 100 * (got["polar"] + got["eig"]) / got["all"] < 100
    # a program before PR 39 has no `jit__svd_form`: nothing to read
    assert svdtrace.busy_by_step(
        [x for x in ordered if x[1] != svdtrace.FORM]) is None


def test_svd_metrics_by_hand():
    flops, nbytes = svdcount.svd(8192)
    assert flops == 21 * 8192 ** 3
    assert nbytes == 4 * (3 * 8192 ** 2 + 8192)
    run = _run({"busy_s": 5.5, "window_s": 5.6, "module_launches": 254})
    assert svdtrace.solve_roofline(run) == \
        pytest.approx(100 * (flops / 197e12) / 5.5)
    assert 0 < svdtrace.solve_roofline(run) < 5
    run["counters"] = {"svd.solves": 8, "svd.polar_iters": 48,
                       "heev.splits": 368, "heev.polar_iters": 1840}
    assert svdtrace.polar_iters_per_solve(run) == 6.0
    assert svdtrace.eig_polar_iters_per_split(run) == 5.0
    run["spans"] = {uploadtrace.READY: 0.4}
    assert uploadtrace.span_s_per_solve(run, uploadtrace.READY) == 0.05


@pytest.mark.parametrize("name", METRICS)
def test_svd_metric_is_found_and_silent_without_a_trace(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == entry["moves"])
    assert CELL in moved["workloads"]
    assert entry["moves"] == CFG["wall_metric"] == "stream_solve_s"
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
    assert entry["reduced"] == CFG["reduced"] == ["n"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    for word in ("SLATE 2023.11.05 svd", "src/svd.cc", "ex10_svd.cc",
                 "tester svd", "matgen svd", "geo", "--cond", "psgesvd"):
        assert word in entry["source"], word
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "repeat", 1) and len(cell["why"]) <= 200
    # appended after what was there (a later PR appends after it)
    assert BENCH["workloads"].index(cell) == 6
    assert BENCH["configs"].index(entry) == 6
    assert (CFG["n"], CFG["mb"], CFG["dtype"], CFG["vectors"]) == \
        (8192, 512, "float32", "both")
    assert CFG["matrix"]["cond"] == 1e4 and CFG["routine"] == "svd"
    assert "tune" not in CFG and set(CFG["tolerance"]) == \
        set(KIND.NUMBERS) | {"reason"}
    assert set(CFG["rehearsal"]["tolerance"]) == set(KIND.NUMBERS)
    for key in ("source", "reduced_why", "assumed", "deployment",
                "guarantee", "tolerance", "rehearsal", "wall_metric_why"):
        assert CFG[key], key
    for key in ("dtype", "mb", "cond", "shape", "generator"):
        assert CFG["assumed"][key], key
    # the cell's own sizes take the route with the frozen threshold,
    # `heev`'s: no tune key or frozen row of the SVD's own
    assert CFG["n"] > tune_cache.FROZEN[("heev", "spectral_dc_min_n")]
    assert not [k for k in tune_cache.FROZEN
                if k[0] == "svd" and "dc" in k[1]]
    assert CFG["rehearsal"]["tune"] == bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "heev-geo-n8192-cond1e4.json")
    )["rehearsal"]["tune"]


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


def test_rehearsal_takes_the_cells_route_and_publishes_its_spans(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         _RUN % {"root": ROOT, "trace": str(tmp_path / "trace")},
         "--workload", CELL, "--seed", "3900000019", "--seconds", "1.5",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert window["compiles_in_window"]["programs"] == 0
    assert 3 <= last["metrics"]["svd.polar_iters_per_solve"]["value"] <= 14
    assert 3 <= last["metrics"]["svd.eig_polar_iters_per_split"]["value"] \
        <= 14
    xplane = next(ln["xplane"] for ln in lines if ln.get("phase") == "trace")
    seen = {e[2]: e for e in svdtrace.host_events(
        reduce_trace.load(xplane))}
    assert set(svdtrace.SPANS) <= set(seen), \
        sorted(set(svdtrace.SPANS) - set(seen))
    assert seen["svd"][3]["method"] == "qdwh_dc" \
        and seen["svd"][3]["form"] == "agenda"
