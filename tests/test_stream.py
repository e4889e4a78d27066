"""OOC streaming engine v2 (linalg/stream.py): the panel-residency
cache + async pipeline must be INVISIBLE numerically — cache-on
results bit-identical to cache-off for every OOC driver, including
under forced eviction and under getrf's row-swap invalidation — while
measurably cutting the left-looking H2D revisit volume (the ISSUE 4
acceptance: >= 40% reduction at nt >= 8 with a budget holding >= nt/2
panels, read from the obs metrics snapshot)."""

import os
import sys
import threading
import time

import numpy as np
import pytest

from slate_tpu.linalg import ooc, stream
from slate_tpu.linalg.stream import PanelCache, StreamEngine


@pytest.fixture
def rng():
    return np.random.default_rng(77)


@pytest.fixture
def obs_on():
    """Event bus + metrics on, reset around the test."""
    from slate_tpu import obs
    from slate_tpu.obs import metrics
    obs.enable()
    obs.clear()
    metrics.reset()
    yield obs
    obs.disable()
    obs.clear()
    metrics.reset()


def _spd(rng, n, dtype=np.float64):
    x = rng.standard_normal((n, n)).astype(dtype)
    return x @ x.T / n + 4.0 * np.eye(n, dtype=dtype)


# -- PanelCache unit behavior ---------------------------------------------

def _arr(nbytes):
    return np.zeros(nbytes // 8, np.float64)


def test_panel_cache_lru_vs_mru_eviction():
    """lru evicts the least recently served unpinned entry; mru the
    most recent one (the cyclic-scan policy the frozen default ships
    — LRU degenerates to zero hits on a left-looking revisit once
    the factor outgrows the budget)."""
    for policy, evicted in (("lru", 2), ("mru", 3)):
        c = PanelCache(budget_bytes=4 * 800, policy=policy)
        for i in range(4):
            assert c.put(("L", 0, i), _arr(800))
        # bump recency AND pin {0, 1} (get pins; deque maxlen=2):
        # recency order is now 2 < 3 < 0 < 1
        assert c.get(("L", 0, 0)) is not None
        assert c.get(("L", 0, 1)) is not None
        assert c.put(("L", 0, 4), _arr(800))
        held = {k[2] for k in c._entries}
        assert evicted not in held, (policy, held)
        assert held == {0, 1, 2, 3, 4} - {evicted}
        assert c.evictions == 1


def test_panel_cache_pinning_and_overbudget():
    c = PanelCache(budget_bytes=1000, policy="mru")
    assert not c.put(("L", 0, 0), _arr(1600))   # alone over budget
    assert c.put(("L", 0, 1), _arr(800))
    # pins hold the only entry: a second insert finds no victim
    assert not c.put(("L", 0, 2), _arr(800))
    assert c.get(("L", 0, 1)) is not None
    assert c.hits == 1 and c.misses == 0


def test_panel_cache_epoch_invalidation():
    """invalidate() bumps the buffer epoch: old entries are dropped
    and the NEW key no longer matches them — the getrf row-swap
    wrong-answer guard at the cache layer."""
    c = PanelCache(budget_bytes=10_000, policy="mru")
    k0 = c.key("LU", 0)
    c.put(k0, _arr(800))
    assert c.get(k0) is not None
    dropped = c.invalidate("LU")
    assert dropped == 1 and c.invalidations == 1
    k1 = c.key("LU", 0)
    assert k1 != k0
    assert c.get(k1) is None            # stale entry not served
    assert c.resident_bytes == 0


def test_engine_budget_zero_is_uncached():
    """The frozen-default budget (0) disables the cache entirely —
    the budget contract every driver's cold start rides on."""
    eng = stream.engine_for(256, 32, np.float64)
    try:
        assert not eng.caching
        assert eng.cache.budget == 0
    finally:
        eng.finish()


def test_engine_auto_budget_never_invents_memory(monkeypatch):
    """"auto" derives from the device's reported bytes_limit minus
    the working-set reserve; an unreporting backend yields 0 (cache
    off), never a made-up budget."""
    import jax

    class _Dev:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    # backend reports no limit (CPU-style): auto MUST resolve to 0
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev({})])
    assert stream.auto_budget_bytes(1 << 20, 8192, 4) == 0
    eng = stream.engine_for(64, 16, np.float64, budget_bytes="auto")
    try:
        assert eng.cache.budget == 0 and not eng.caching
    finally:
        eng.finish()
    # HBM-style limit: 90% headroom minus the 4-panel reserve
    limit = 16 << 30
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_Dev({"bytes_limit": limit})])
    n, w, item = 1 << 16, 8192, 4
    expect = int(limit * stream.AUTO_BUDGET_FRACTION) \
        - stream.RESERVE_PANELS * n * w * item
    assert stream.auto_budget_bytes(n, w, item) == expect
    # a reserve larger than the device clamps to 0, never negative
    assert stream.auto_budget_bytes(1 << 22, 1 << 20, 8) == 0
    with pytest.raises(ValueError, match="auto"):
        stream.engine_for(64, 16, np.float64, budget_bytes="never")


def test_d2h_writes_into_preallocated_slice(rng):
    """_d2h(out=...) fills the caller's slice chunk-by-chunk (no
    concatenate copy), including non-contiguous column views and the
    chunked >=2048-row path."""
    import jax.numpy as jnp
    x = rng.standard_normal((2304, 6))
    d = jnp.asarray(x)
    host = np.zeros((2304, 10))
    got = ooc._d2h(d, out=host[:, 2:8])
    np.testing.assert_array_equal(host[:, 2:8], np.asarray(d))
    assert got.base is host or got.shape == (2304, 6)
    # small path too
    h2 = np.zeros((64, 6))
    ooc._d2h(d[:64], out=h2)
    np.testing.assert_array_equal(h2, np.asarray(d)[:64])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows,cap,chunks", [
    # cap: FETCH_CHUNK_BYTES, in rows of six f32 (twice as many bf16);
    # chunks: fetched, f32 and bf16
    (64, None, (0, 0)), (2047, None, (0, 0)),   # under 2048: one copy
    (2048, None, (8, 8)), (2309, None, (8, 8)),     # the last one short
    (4096, 300, (14, 8)),       # eighths are 512 rows: the cap holds f32
    (2309, 100, (24, 12)),
])
def test_d2h_fills_a_strided_slice_bit_identically(rng, obs_on, monkeypatch,
                                                   rows, cap, chunks, dtype):
    """The write-back's copy: under and over the 2048 rows where the
    chunk threads start, a row count the chunks do not divide, both
    resident dtypes, `out` given and not, and chunks held to
    FETCH_CHUNK_BYTES where an eighth of the block is more (PR 37: at
    the streamed cell's shapes an eighth is up to 67 MB)."""
    import jax.numpy as jnp
    from slate_tpu.obs import metrics
    if cap is not None:
        monkeypatch.setattr(stream, "FETCH_CHUNK_BYTES", cap * 6 * 4)
    chunks = chunks[dtype == "bfloat16"]
    d = jnp.asarray(rng.standard_normal((rows, 6)), dtype=dtype)
    want = np.array(d)
    host = np.zeros((rows + 3, 10), want.dtype)
    got = stream._d2h(d, out=host[3:, 2:8])
    assert got.base is host
    assert host[3:, 2:8].tobytes() == want.tobytes()
    assert not host[:3].any() and not host[:, :2].any() \
        and not host[:, 8:].any()
    fresh = stream._d2h(d)                      # out=None
    assert fresh.flags.writeable and fresh.tobytes() == want.tobytes()
    assert metrics.snapshot()["counters"]["ooc.d2h_bytes"] \
        == 2 * want.nbytes
    spans = [e.name for e in obs_on.bus_events(cat="staging")]
    assert spans.count("ooc::d2h") == 2
    assert spans.count("ooc::d2h_chunk") == 2 * chunks


def test_chunk_rows_at_the_streamed_cells_shapes():
    """The cap where it matters, by the rule `_d2h` applies: the cell's
    panels are (32768 - 4096 k, 4096) f32; an eighth of the four
    tallest is over the allocator's 32 MiB ceiling and the fifth is at
    it, and every chunk is now 1024 rows, 16 MiB; a bf16 panel of the
    mixed mode is cut at the same bytes, and a narrow block (the
    right-hand side) stays in eighths."""
    assert stream.FETCH_CHUNK_BYTES == 16 << 20

    def rows(m, w, item):
        return stream._chunk_rows(m, m * w * item, 8)

    assert [rows(32768 - 4096 * k, 4096, 4) for k in range(8)] \
        == [1024] * 7 + [512]
    assert [32768 * 4096 * 4 // 8 > 32 << 20, 16384 * 4096 * 4 // 8] \
        == [True, 32 << 20]
    assert rows(32768, 4096, 2) == 2048 and rows(8192, 4096, 2) == 1024
    assert rows(32768, 8, 4) == 4096
    assert rows(4096, 0, 4) == 512          # nothing to divide by


def test_two_writebacks_in_flight_land_each_in_its_own_slice(rng):
    """WRITES_IN_FLIGHT panels queued at once, different data, one
    writer: each lands in its own slice, also once the panels
    themselves are gone."""
    import jax.numpy as jnp
    host = np.zeros((2, 2304, 10), np.float32)
    panels = [jnp.asarray(rng.standard_normal((2304, 6)), jnp.float32)
              for _ in range(4)]
    want = [np.array(p) for p in panels]
    with StreamEngine() as eng:
        for rnd in (0, 2):
            for k in (0, 1):
                eng.write("L", k, panels[rnd + k], host[k][:, 2:8])
            eng.wait_writes()
            for k in (0, 1):
                assert host[k][:, 2:8].tobytes() == want[rnd + k].tobytes()
    del panels
    assert host[1][:, 2:8].tobytes() == want[3].tobytes()


# -- cache-on == cache-off, driver by driver ------------------------------

def test_ooc_drivers_cache_bit_identical_under_eviction(rng):
    """Every OOC driver: a budget too small for the factor (evictions
    forced) and a comfortable budget both reproduce the budget-0
    result EXACTLY. tiny n, panels much smaller than the matrix."""
    n, w = 160, 32
    tiny = int(1.5 * n * w * 8)          # ~1.5 panels -> evictions
    big = 64 * n * w * 8
    a = _spd(rng, n)
    g = rng.standard_normal((n, n))
    b = rng.standard_normal((n, 3))

    L0 = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=0)
    for budget in (tiny, big):
        Lc = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=budget)
        np.testing.assert_array_equal(L0, Lc)
        xc = ooc.potrs_ooc(L0, b, panel_cols=w,
                           cache_budget_bytes=budget)
        np.testing.assert_array_equal(
            ooc.potrs_ooc(L0, b, panel_cols=w, cache_budget_bytes=0),
            xc)

    lu0, piv0 = ooc.getrf_ooc(g, panel_cols=w, cache_budget_bytes=0)
    x0 = ooc.getrs_ooc(lu0, piv0, b, panel_cols=w,
                       cache_budget_bytes=0)
    qr0, tau0 = ooc.geqrf_ooc(g, panel_cols=w, cache_budget_bytes=0)
    y0 = ooc.unmqr_ooc(qr0, tau0, b, trans=True, panel_cols=w,
                       cache_budget_bytes=0)
    for budget in (tiny, big):
        lu1, piv1 = ooc.getrf_ooc(g, panel_cols=w,
                                  cache_budget_bytes=budget)
        np.testing.assert_array_equal(lu0, lu1)
        np.testing.assert_array_equal(piv0, piv1)
        np.testing.assert_array_equal(
            x0, ooc.getrs_ooc(lu0, piv0, b, panel_cols=w,
                              cache_budget_bytes=budget))
        qr1, tau1 = ooc.geqrf_ooc(g, panel_cols=w,
                                  cache_budget_bytes=budget)
        np.testing.assert_array_equal(qr0, qr1)
        np.testing.assert_array_equal(tau0, tau1)
        np.testing.assert_array_equal(
            y0, ooc.unmqr_ooc(qr0, tau0, b, trans=True, panel_cols=w,
                              cache_budget_bytes=budget))


def test_ooc_composite_drivers_cache_bit_identical(rng):
    """posv/gesv/gels/gemm through the engine: budgeted == budget-0,
    bit for bit (gels exercises the shared factor->apply->R-sweep
    engine; gemm the pipeline-only path)."""
    n, w = 128, 32
    budget = 3 * n * w * 8
    a = _spd(rng, n)
    g = rng.standard_normal((n, n)) + 0.2 * n * np.eye(n)
    b = rng.standard_normal((n, 2))
    L0, x0 = ooc.posv_ooc(a, b, panel_cols=w, cache_budget_bytes=0)
    L1, x1 = ooc.posv_ooc(a, b, panel_cols=w,
                          cache_budget_bytes=budget)
    np.testing.assert_array_equal(L0, L1)
    np.testing.assert_array_equal(x0, x1)
    (lu0, p0), y0 = ooc.gesv_ooc(g, b, panel_cols=w,
                                 cache_budget_bytes=0)
    (lu1, p1), y1 = ooc.gesv_ooc(g, b, panel_cols=w,
                                 cache_budget_bytes=budget)
    np.testing.assert_array_equal(lu0, lu1)
    np.testing.assert_array_equal(p0, p1)
    np.testing.assert_array_equal(y0, y1)
    m, k = 200, 64
    ta = rng.standard_normal((m, k))
    tb = rng.standard_normal((m, 2))
    (_, _), z0 = ooc.gels_ooc(ta, tb, panel_cols=32,
                              cache_budget_bytes=0)
    (_, _), z1 = ooc.gels_ooc(ta, tb, panel_cols=32,
                              cache_budget_bytes=budget)
    np.testing.assert_array_equal(z0, z1)
    c = rng.standard_normal((m, 5))
    bb = rng.standard_normal((k, 5))
    g0 = ooc.gemm_ooc(1.5, ta, bb, -0.5, c, row_panel=64,
                      cache_budget_bytes=0)
    g1 = ooc.gemm_ooc(1.5, ta, bb, -0.5, c, row_panel=64,
                      cache_budget_bytes=budget)
    np.testing.assert_array_equal(g0, g1)


def test_getrf_ooc_rowswap_invalidates_stale_panels(rng):
    """The wrong-answer guard (ISSUE 4), as the partial stream keeps
    it since PR 47: a written factor panel is never rewritten, on the
    host or in the cache, and a later visit gathers it on the chip
    through the relative permutation r = inv(P_j)[P_now]. So nothing
    is retired, and the panel gathered through r has to be the panel
    the host fixup used to leave: the input is built to pivot ACROSS
    panel boundaries at every step (later rows strictly dominate), so
    a visit served a panel in the order it was stored in cannot hide;
    cached == uncached == in-core, bit for bit on the pivot sequence,
    with hits inside the factorization."""
    import slate_tpu as st
    n, w = 128, 32
    a = rng.standard_normal((n, n))
    # growing magnitudes toward the bottom: every panel's pivot
    # search selects rows from LATER panels -> cross-panel swaps
    a *= (1.0 + np.arange(n))[:, None]
    lu0, piv0 = ooc.getrf_ooc(a, panel_cols=w, cache_budget_bytes=0)
    lu1, piv1 = ooc.getrf_ooc(a, panel_cols=w,
                              cache_budget_bytes=64 * n * w * 8)
    s = stream.last_stats()
    assert s["invalidations"] == 0 and s["invalidated_bytes"] == 0
    # every visit (6) and every repair (3) of a panel is served
    assert (s["hits"], s["misses"]) == (9, 0)
    perm = ooc._swaps_to_perm(piv1, n)
    assert all((perm[k0:] >= k0 + w).any() for k0 in range(0, n - w, w)), \
        "input did not pivot across panel boundaries"
    np.testing.assert_array_equal(piv0, piv1)
    np.testing.assert_array_equal(lu0, lu1)
    F = st.getrf(st.Matrix(a, mb=w))
    np.testing.assert_array_equal(piv1, np.asarray(F.pivots)[:n])


def test_prefetch_depth_and_policy_knobs_bit_identical(rng,
                                                       monkeypatch):
    """Turning the async H2D prefetch off (depth 0) and switching the
    eviction policy must not change a single bit — the pipeline is a
    scheduling change only. Knobs flow through tune/select's FROZEN
    table (the registration path)."""
    from slate_tpu.tune import cache as tcache
    n, w = 160, 32
    a = _spd(rng, n)
    budget = 3 * n * w * 8
    ref = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=budget)
    monkeypatch.setitem(tcache.FROZEN, ("ooc", "prefetch_depth"), 0)
    monkeypatch.setitem(tcache.FROZEN, ("ooc", "cache_policy"), "lru")
    got = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=budget)
    np.testing.assert_array_equal(ref, got)
    monkeypatch.setitem(tcache.FROZEN, ("ooc", "cache_policy"),
                        "fifo")
    got = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=budget)
    np.testing.assert_array_equal(ref, got)


# -- transfer-volume acceptance (obs snapshot) ----------------------------

def test_potrf_cache_cuts_h2d_volume(rng, obs_on):
    """ISSUE 4 acceptance: at nt=8 panels with a budget holding >=
    nt/2 panels, the residency cache cuts ooc.h2d_bytes by >= 40%
    for a left-looking factorization, with hit/miss/eviction
    counters present in the obs snapshot."""
    from slate_tpu.obs import metrics
    n, w = 256, 32          # nt = 8
    a = _spd(rng, n)
    L0 = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=0)
    base = metrics.snapshot()["counters"]["ooc.h2d_bytes"]
    assert base > 0
    metrics.reset()
    budget = 6 * n * w * 8          # 6 full panels (>= nt/2 = 4)
    L1 = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=budget)
    c = metrics.snapshot()["counters"]
    np.testing.assert_array_equal(L0, L1)
    cached = c["ooc.h2d_bytes"]
    assert cached <= 0.6 * base, \
        "h2d reduction %.1f%% < 40%% (base %d, cached %d)" \
        % (100 * (1 - cached / base), base, cached)
    # counters the bench extras / report surface
    assert c["ooc.cache.hits"] > 0
    assert "ooc.cache.misses" in c
    assert "ooc.cache.evictions" in c
    assert c["ooc.cache.served_bytes"] > 0
    assert c["ooc.prefetch.issued"] > 0


def test_geqrf_cache_cuts_h2d_volume(rng, obs_on):
    """Same acceptance shape for the reflector-panel stream (no
    invalidation path): first visit uploads, later visits hit."""
    from slate_tpu.obs import metrics
    n, w = 256, 32
    g = rng.standard_normal((n, n))
    qr0, _ = ooc.geqrf_ooc(g, panel_cols=w, cache_budget_bytes=0)
    base = metrics.snapshot()["counters"]["ooc.h2d_bytes"]
    metrics.reset()
    qr1, _ = ooc.geqrf_ooc(g, panel_cols=w,
                           cache_budget_bytes=8 * n * w * 8)
    c = metrics.snapshot()["counters"]
    np.testing.assert_array_equal(qr0, qr1)
    assert c["ooc.h2d_bytes"] <= 0.6 * base
    assert c["ooc.cache.hits"] > 0


def test_solve_drivers_instrumented(rng, obs_on):
    """Satellite: potrs/getrs/posv/unmqr_ooc now carry
    @instrument_driver — their spans and call counters land in the
    obs snapshot like the factor drivers'."""
    from slate_tpu import obs
    n, w = 96, 32
    a = _spd(rng, n)
    b = rng.standard_normal((n, 2))
    L, _ = ooc.posv_ooc(a, b, panel_cols=w)
    ooc.potrs_ooc(L, b, panel_cols=w)
    g = rng.standard_normal((n, n)) + 0.2 * n * np.eye(n)
    lu, piv = ooc.getrf_ooc(g, panel_cols=w)
    ooc.getrs_ooc(lu, piv, b, panel_cols=w)
    qr, tau = ooc.geqrf_ooc(g, panel_cols=w)
    ooc.unmqr_ooc(qr, tau, b, panel_cols=w)
    drv = obs.snapshot()["drivers"]
    for op in ("posv_ooc", "potrs_ooc", "getrs_ooc", "unmqr_ooc"):
        assert drv[op]["calls"] >= 1, op


def test_gemm_and_getrf_uploads_counted(rng, obs_on):
    """Satellite: gemm_ooc's B/A/C uploads and getrf_ooc's input
    panels are routed through _h2d, so ooc.h2d_bytes covers the
    FULL transfer volume (it used to undercount the jnp.asarray
    paths)."""
    from slate_tpu.obs import metrics
    m, k = 128, 48
    a = rng.standard_normal((m, k))
    bb = rng.standard_normal((k, 4))
    c = rng.standard_normal((m, 4))
    ooc.gemm_ooc(1.0, a, bb, 1.0, c, row_panel=64)
    got = metrics.snapshot()["counters"]["ooc.h2d_bytes"]
    expect = a.nbytes + bb.nbytes + c.nbytes
    assert got >= expect, (got, expect)
    metrics.reset()
    g = rng.standard_normal((96, 96))
    ooc.getrf_ooc(g, panel_cols=32)
    got = metrics.snapshot()["counters"]["ooc.h2d_bytes"]
    assert got >= g.nbytes          # every panel read counted once


def test_engine_stats_surface():
    """stream.last_stats() carries the fields bench --ooc ships."""
    rng = np.random.default_rng(3)
    a = _spd(rng, 96)
    ooc.potrf_ooc(a, panel_cols=32, cache_budget_bytes=6 * 96 * 32 * 8)
    s = stream.last_stats()
    for key in ("hits", "misses", "evictions", "invalidations",
                "hit_rate", "served_bytes", "prefetch_issued",
                "prefetch_overlap_fraction", "d2h_overlap_fraction",
                "budget_bytes", "policy"):
        assert key in s, key
    assert s["hits"] > 0


# -- the host staging ring (PR 26) ----------------------------------------

@pytest.fixture
def ring(monkeypatch):
    """A ring of this test's own in place of the process-wide one."""
    r = stream._StageRing("ooc")
    monkeypatch.setattr(stream, "_ring", r)
    return r


def _f32(rng, m, n):
    return rng.standard_normal((m, n)).astype(np.float32)


@pytest.mark.parametrize("guarded", [True, False])
def test_ring_recycled_slot_leaves_earlier_panel_intact(
        rng, ring, monkeypatch, guarded):
    """Two panels staged in turn through ONE slot: on a backend whose
    device arrays may alias host memory (this one) the first device
    array survives only because _h2d makes the put copy; with that
    guard forced off the recycled slot rewrites it."""
    if not guarded:
        monkeypatch.setattr(stream, "_aliases_host", lambda: False)
    ring._cap = 1
    a = _f32(rng, 1024, 2048)
    # the backend aliases only a well aligned buffer: give the slot one
    slot, _ = ring.acquire(a.nbytes // 2)
    raw = np.empty(a.nbytes // 2 + 4096, np.uint8)
    off = -raw.ctypes.data % 4096
    slot.buf = raw[off:off + a.nbytes // 2]
    ring.release(slot, None)
    first = stream._h2d(a[:, :1024])
    second = stream._h2d(a[:, 1024:])
    assert len(ring._slots) == 1
    assert np.array_equal(np.asarray(second), a[:, 1024:])
    assert np.array_equal(np.asarray(first), a[:, :1024]) == guarded


class _Transfer:
    """A stand-in for a device array whose transfer the test ends."""

    def __init__(self):
        self.done = threading.Event()
        self.waited_for = threading.Event()

    def is_ready(self):
        return self.done.is_set()

    def block_until_ready(self):
        self.waited_for.set()
        assert self.done.wait(30)
        return self


def test_ring_acquire_waits_for_the_slots_last_transfer(ring, obs_on):
    """A slot whose last transfer is not ready is not handed out: a
    ring with room makes another slot, a full one blocks under
    ooc::wait_ring until the transfer is over."""
    slot, reused = ring.acquire(1 << 16)
    assert not reused
    inflight = _Transfer()
    ring.release(slot, inflight)
    other, _ = ring.acquire(1 << 16)            # room: a second slot
    assert other is not slot and len(ring._slots) == 2
    ring.release(other, None)
    ring._cap = 2
    got = []
    ring.release(ring.acquire(1 << 16)[0], _Transfer())   # both in flight
    t = threading.Thread(
        target=lambda: got.append(ring.acquire(1 << 16)), daemon=True)
    t.start()
    assert inflight.waited_for.wait(30)
    t.join(0.1)
    assert t.is_alive() and not got             # blocked on the transfer
    inflight.done.set()
    t.join(30)
    assert not t.is_alive()
    assert got[0][0] is slot and got[0][1]      # the oldest release, reused
    waits = [e for e in obs_on.bus_events(cat="staging")
             if e.name == "ooc::wait_ring"]
    assert len(waits) == 1 and waits[0].dur >= 0.09
    assert waits[0].args["on"] == "transfer"
    # every slot busy: the next acquire waits for a release
    t2 = threading.Thread(
        target=lambda: got.append(ring.acquire(1 << 16)), daemon=True)
    for s in ring._slots:
        if not s.busy:
            s.last.done.set()
            ring.acquire(1 << 16)
    t2.start()
    t2.join(0.2)
    assert t2.is_alive()
    ring.release(slot, None)
    t2.join(30)
    assert not t2.is_alive() and got[1][0] is slot
    assert len(ring._slots) == 2


def test_ring_eight_threads_each_get_their_own_bytes(rng, ring):
    """More staging threads than slots, a shortened switch interval:
    every device array holds the bytes of the source it was made from,
    and the ring stays within its bound."""
    srcs = [_f32(rng, 256, 512) for _ in range(8)]
    bad, old = [], sys.getswitchinterval()

    def stage(t):
        for rep in range(20):
            src = srcs[t][:, rep % 4::4]        # a strided view
            if not np.array_equal(np.asarray(stream._h2d(src)), src):
                bad.append((t, rep))

    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=stage, args=(t,), daemon=True)
              for t in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts) and not bad, bad
    assert 1 <= len(ring._slots) <= 2           # no engine reserved more
    assert not any(s.busy for s in ring._slots)


def test_ring_is_bounded_and_reused_across_engines(rng, ring, obs_on):
    """At most prefetch_depth + 2 slots, and posv_ooc's second engine
    (and a second call) find the first one's slots touched: nothing
    fresh is staged once the slots hold the largest panel."""
    from slate_tpu.obs import metrics
    n, w = 256, 32
    a = _spd(rng, n, np.float32)
    b = _f32(rng, n, 2)
    ooc.posv_ooc(a, b, panel_cols=w)            # two engines
    assert 1 <= len(ring._slots) <= 3           # how many: the threads' luck
    assert not any(s.busy or s.last is not None for s in ring._slots)
    # fill the ring to its bound, every slot as large as the largest panel
    held = [ring.acquire(n * w * 4)[0] for _ in range(3)]
    for slot in held:
        ring.release(slot, None)
    assert len(ring._slots) == 3
    bufs = {id(s.buf) for s in ring._slots}
    c0 = metrics.snapshot()["counters"]
    ooc.posv_ooc(a, b, panel_cols=w)            # two more engines
    c1 = metrics.snapshot()["counters"]
    assert c1["ooc.h2d_stage_fresh_bytes"] == c0["ooc.h2d_stage_fresh_bytes"]
    assert c1["ooc.h2d_stage_reuse_bytes"] > c0["ooc.h2d_stage_reuse_bytes"]
    assert {id(s.buf) for s in ring._slots} == bufs
    with StreamEngine(prefetch_depth=3):
        assert ring._cap == 5                   # the deepest engine seen
    with StreamEngine(prefetch_depth=0):
        assert ring._cap == 5


def test_potrf_factor_bitwise_what_fresh_staging_gave(rng, ring,
                                                       monkeypatch):
    """The ring and the untouched factor buffer change no bit: the
    factor at the streamed cell's rehearsal size equals the one the
    parent's staging (a fresh contiguous copy per panel, a zero-filled
    buffer) returns, cached and uncached, and its strictly upper
    blocks are exact zeros."""
    import jax.numpy as jnp
    n, w = 512, 64
    a = _spd(rng, n, np.float32)
    budget = 5 * n * w * 4
    got = [ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=bb)
           for bb in (budget, 0)]
    assert ring._slots                          # the ring did stage them
    monkeypatch.setattr(
        stream, "_h2d", lambda x: jnp.asarray(np.ascontiguousarray(x)))
    monkeypatch.setattr(np, "zeros", lambda shape, dtype=float, **kw:
                        np.full(shape, 0, dtype))
    want = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=budget)
    for L in got:
        assert L.dtype == want.dtype and np.array_equal(L, want)
        assert L.tobytes() == want.tobytes()
        assert not np.triu(L, 1).any()
    assert np.abs(want @ want.T - a).max() < 1e-3 * np.abs(a).max()


#: the streamed drivers on a square f32 problem: (what to call with
#: (a_spd, a_general, b, w, budget), the bytes of ooc.h2d_bytes whose
#: source is contiguous already and so passes _h2d uncopied)
_DRIVERS = {
    "potrf_ooc": (
        lambda spd, g, b, w, bud: ooc.potrf_ooc(
            spd, panel_cols=w, cache_budget_bytes=bud),
        lambda n, nt, w, b: 0),
    # the partial-pivot stream stages its input panels as they lie
    # (PR 47; the host gather it made before arrived contiguous)
    "getrf_ooc": (
        lambda spd, g, b, w, bud: ooc.getrf_ooc(
            g, panel_cols=w, cache_budget_bytes=bud),
        lambda n, nt, w, b: 0),
    "getrf_tntpiv_ooc": (
        lambda spd, g, b, w, bud: ooc.getrf_tntpiv_ooc(
            g, panel_cols=w, cache_budget_bytes=bud),
        lambda n, nt, w, b: 0),
    # one row of w taus beside every visiting reflector panel
    "geqrf_ooc": (
        lambda spd, g, b, w, bud: ooc.geqrf_ooc(
            g, panel_cols=w, cache_budget_bytes=bud),
        lambda n, nt, w, b: (nt * (nt - 1) // 2) * w * 4),
    "posv_ooc": (
        lambda spd, g, b, w, bud: ooc.posv_ooc(
            spd, b, panel_cols=w, cache_budget_bytes=bud),
        lambda n, nt, w, b: b.nbytes),
}


@pytest.mark.parametrize("driver", sorted(_DRIVERS))
def test_stream_staging_goes_through_the_ring(rng, ring, obs_on, driver):
    """ooc.h2d_stage_reuse_bytes + ooc.h2d_stage_fresh_bytes is the
    share of ooc.h2d_bytes that needed the host-side copy: every panel
    a streamed driver stages (a column slice of a host matrix), the
    revisits a two-panel cache forces among them, and not the sources
    that are contiguous already. A path that copies a panel into a
    buffer of its own and hands _h2d the copy leaves its bytes out of
    the sum."""
    from slate_tpu.obs import metrics
    n, w = 256, 32
    nt = n // w
    run, contiguous = _DRIVERS[driver]
    b = _f32(rng, n, 3)
    run(_spd(rng, n, np.float32), _f32(rng, n, n), b, w, 2 * n * w * 4)
    c = metrics.snapshot()["counters"]
    assert c["ooc.cache.misses"] > nt           # panels staged again
    staged = c["ooc.h2d_stage_reuse_bytes"] + c["ooc.h2d_stage_fresh_bytes"]
    assert staged == c["ooc.h2d_bytes"] - contiguous(n, nt, w, b)
    assert staged >= nt * n * w * 4 // 2
    assert c["ooc.h2d_stage_fresh_bytes"] > 0
    # a contiguous source passes through and is counted in neither
    stream._h2d(np.ascontiguousarray(b))
    c2 = metrics.snapshot()["counters"]
    assert c2["ooc.h2d_bytes"] == c["ooc.h2d_bytes"] + b.nbytes
    assert c2["ooc.h2d_stage_reuse_bytes"] + \
        c2["ooc.h2d_stage_fresh_bytes"] == staged


def test_stream_copies_host_panels_in_h2d_only():
    """By reading linalg/stream.py: no function but _h2d makes a host
    copy of a panel (np.ascontiguousarray, or joining panels into a new
    array), so no staging path goes around the ring."""
    import ast
    tree = ast.parse(open(stream.__file__).read())
    fresh = {"ascontiguousarray", "concatenate", "stack", "hstack"}
    found = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in fresh \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == "np":
                found.append((fn.name, node.func.attr))
    assert found == [("_h2d", "ascontiguousarray")]


@pytest.mark.parametrize("driver", sorted(_DRIVERS))
def test_stream_rerun_compiles_nothing(rng, obs_on, driver):
    """A second call at the same shape traces and compiles nothing
    (the tier-1 twin of benchmarks/run.py's exit 3): the jit.* counters
    of the compile listener and obs.metrics.recompiles() stay where the
    first call left them, while a program jitted anew moves them."""
    import jax
    from slate_tpu.obs import metrics
    n, w = 256, 32          # the staging test's shapes: compiled once
    run, _ = _DRIVERS[driver]
    args = (_spd(rng, n, np.float32), _f32(rng, n, n), _f32(rng, n, 3),
            w, 2 * n * w * 4)

    def jit_counters():
        c = metrics.snapshot()["counters"]
        return {k: v for k, v in c.items() if k.startswith("jit.")}

    run(*args)
    before, recompiled = jit_counters(), metrics.recompiles()
    run(*args)
    assert jit_counters() == before
    assert metrics.recompiles() == recompiled
    jax.jit(lambda x: x * 3.25 + 1.0)(np.ones(7, np.float32))
    assert jit_counters().get("jit.backend_compile_seconds", 0.0) \
        > before.get("jit.backend_compile_seconds", 0.0)


# -- what one posv_ooc stages, from its schedule --------------------------

def _posv_staged_panels(nt, resident):
    """Host model of posv_ooc's staging, in blocks of w x w (a
    full-height panel is nt of them): the schedule of linalg/ooc.py
    restated, with the panel cache as a list. Returns (staged,
    trimmed): what crosses the link,
    and the rows above a factor panel's diagonal block that a trimmed
    upload leaves on the host (PR 34: a cache miss of L[:, j] and
    every upload of potrs_ooc's sweeps stage rows j0: only). The cache
    holds `resident` full-height panels; when full it gives up the
    most recently used one that is not one of the last two touched
    (policy mru, two pins)."""
    held, pins, staged, trimmed = [], [], 0, 0

    def touch(j):
        """A fetch or a put of panel j; True when it was resident."""
        nonlocal pins
        hit = j in held
        if hit:
            held.remove(j)
        else:
            while len(held) >= resident:
                victim = next((p for p in reversed(held)
                               if p not in pins), None)
                if victim is None:
                    return False            # only pinned panels: not kept
                held.remove(victim)
        held.append(j)
        pins = (pins + [j])[-2:]
        return hit

    for k in range(nt):                     # potrf_ooc, left-looking
        below = nt - k                      # rows k0: of a panel
        staged += below                     # A[k0:, k], never cached
        for j in range(k):                  # the visits of L[:, j]
            if not resident:
                staged += below             # uncached: rows k0: only
            elif not touch(j):
                staged += nt - j            # cached: rows j0:, embedded
                trimmed += j
        if resident:
            touch(k)                        # the factored panel is put
    held, pins = [], []                     # potrs_ooc: an engine of its own
    for j in [*range(nt), *reversed(range(nt))]:
        if not resident or not touch(j):    # forward, then backward
            staged += nt - j
            trimmed += j
    return staged, trimmed


@pytest.mark.parametrize("nt,resident,blocks,left", [
    (8, 5, 109, 55), (8, 0, 192, 56), (8, 8, 72, 28), (6, 3, 95, 34),
    (4, 2, 25, 13)])
def test_posv_ooc_staged_bytes_match_schedule(rng, obs_on, nt, resident,
                                              blocks, left):
    """ooc.h2d_bytes of one posv_ooc is what its schedule says: the
    input panels below their diagonal, the visits a cache of `resident`
    full-height panels misses and potrs_ooc's two sweeps, each from the
    panel's diagonal block down, and the right-hand side;
    ooc.h2d_trimmed_bytes is the rows above those diagonal blocks,
    which the parent staged too (164 blocks at (8, 5), PR 26).
    `blocks` and `left` are the model's answers, written down so that a
    change of schedule shows in the diff; (8, 5) is the streamed cell's
    shape: 109 blocks of w x w and the right-hand side, which at
    n=32768, w=4096, nrhs=8 is the 7.315914752 GB the benchmark reads
    as stream.h2d_gb, and 55 blocks, 3.69098752 GB, left on the host
    (stream.h2d_trim_share 33.5%)."""
    from slate_tpu.obs import metrics
    assert _posv_staged_panels(nt, resident) == (blocks, left)
    assert 109 * 4096 * 4096 * 4 + 32768 * 8 * 4 == 7315914752
    assert 55 * 4096 * 4096 * 4 == 3690987520
    w, nrhs = 32, 8
    n = nt * w
    a, b = _spd(rng, n, np.float32), _f32(rng, n, nrhs)
    ooc.posv_ooc(a, b, panel_cols=w,
                 cache_budget_bytes=resident * n * w * 4)
    c = metrics.snapshot()["counters"]
    assert c["ooc.h2d_bytes"] == blocks * w * w * 4 + n * nrhs * 4
    assert c["ooc.h2d_trimmed_bytes"] == left * w * w * 4


# -- factor panels staged from their diagonal block down (PR 34) ----------

@pytest.mark.parametrize("n,w,staged,trimmed", [
    (512, 64, 109 * 64 * 64, 55 * 64 * 64),
    # no multiple of w: 8 panels, the last 52 columns wide and tall
    (500, 64, None, None)])
def test_posv_ooc_trimmed_uploads_bitwise_the_uncached_route(
        rng, obs_on, n, w, staged, trimmed):
    """The streamed cell's shape in small (8 panels, a budget of 5): a
    factor panel reaches the device from its diagonal block down and is
    zero-embedded there, L and X equal the budget-0 route's bit for bit
    (whose potrf_ooc stages rows k0: as it always did), and the counters
    are the cell's block arithmetic: 109 blocks sent, 55 left behind."""
    from slate_tpu.obs import metrics
    a, b = _spd(rng, n, np.float32), _f32(rng, n, 3)
    L0, X0 = ooc.posv_ooc(a, b, panel_cols=w, cache_budget_bytes=0)
    metrics.reset()
    L, X = ooc.posv_ooc(a, b, panel_cols=w,
                        cache_budget_bytes=5 * n * w * 4)
    c = metrics.snapshot()["counters"]
    assert L.tobytes() == L0.tobytes() and X.tobytes() == X0.tobytes()
    assert not np.triu(L, 1).any()
    if staged is None:
        # by hand: panel j is (n - j w) x min(w, n - j w); the same
        # uploads as at n=512 (8 A; L2, L5, L2, L3, L6; L0..L7; L4,
        # L3, L2)
        ws = [min(w, n - j * w) for j in range(8)]
        ups = [2, 5, 2, 3, 6, *range(8), 4, 3, 2]
        staged = sum((n - j * w) * ws[j] for j in [*range(8), *ups])
        trimmed = sum(j * w * ws[j] for j in ups)
    assert c["ooc.h2d_bytes"] == staged * 4 + b.nbytes
    assert c["ooc.h2d_trimmed_bytes"] == trimmed * 4
    assert c["ooc.cache.misses"] == 16          # the L uploads, as before


@pytest.mark.parametrize("budget_panels", [0, 3, 8])
def test_potrs_ooc_never_reads_above_the_diagonal_block(rng, budget_panels):
    """Both sweeps stage rows k0: of panel k: a factor whose strictly
    upper blocks are NaN gives the clean factor's X, bit for bit."""
    n, w = 256, 32
    L = ooc.potrf_ooc(_spd(rng, n, np.float32), panel_cols=w)
    b = _f32(rng, n, 3)
    dirty = L.copy()
    for j in range(1, n // w):
        dirty[:j * w, j * w:(j + 1) * w] = np.nan
    bud = budget_panels * n * w * 4
    want = ooc.potrs_ooc(L, b, panel_cols=w, cache_budget_bytes=bud)
    got = ooc.potrs_ooc(dirty, b, panel_cols=w, cache_budget_bytes=bud)
    assert np.isfinite(want).all()
    assert got.tobytes() == want.tobytes()


def test_getrs_ooc_sweeps_stay_full_height(rng, obs_on):
    """An LU panel carries U above its diagonal block: getrs_ooc's two
    sweeps stage 2 nt whole columns with the cache off, and nothing is
    trimmed anywhere in gesv_ooc."""
    from slate_tpu.obs import metrics
    n, w, nrhs = 256, 32, 3
    nt = n // w
    g, b = _f32(rng, n, n), _f32(rng, n, nrhs)
    lu, ipiv = ooc.getrf_ooc(g, panel_cols=w)
    metrics.reset()
    x = ooc.getrs_ooc(lu, ipiv, b, panel_cols=w, cache_budget_bytes=0)
    c = metrics.snapshot()["counters"]
    assert c["ooc.h2d_bytes"] == 2 * nt * n * w * 4 + b.nbytes
    assert "ooc.h2d_trimmed_bytes" not in c
    assert np.abs(g @ x - b).max() < 1e-2
    ooc.gesv_ooc(g, b, panel_cols=w, cache_budget_bytes=3 * n * w * 4)
    assert "ooc.h2d_trimmed_bytes" not in metrics.snapshot()["counters"]


def test_trimmed_uploads_add_no_embed_program(rng):
    """_embed_rows is compiled for every (n - k0, w) of a solve by
    potrf_ooc's writeback: the trimmed uploads of a warm solve find
    their programs there, and a second solve adds none."""
    n, w = 512, 64
    a, b = _spd(rng, n, np.float32), _f32(rng, n, 3)
    bud = 5 * n * w * 4
    ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=8 * n * w * 4)
    after_writebacks = stream._embed_rows._cache_size()
    ooc.posv_ooc(a, b, panel_cols=w, cache_budget_bytes=bud)
    assert stream._embed_rows._cache_size() == after_writebacks
    ooc.posv_ooc(a, b, panel_cols=w, cache_budget_bytes=bud)
    assert stream._embed_rows._cache_size() == after_writebacks


def test_engine_embed_is_the_full_height_panel(rng, obs_on):
    """fetch(embed=(off, n)) and prefetch(embed=) hand the cache, view=
    and the caller the array a whole-column upload gives; off == 0 is a
    plain upload and counts nothing."""
    from slate_tpu.obs import metrics
    n, w, off = 96, 16, 32
    col = np.zeros((n, w), np.float32)
    col[off:] = _f32(rng, n - off, w)
    host = np.zeros((n, 3 * w), np.float32)
    host[:, w:2 * w] = col                      # a strided source
    with StreamEngine(budget_bytes=4 * n * w * 4) as eng:
        whole = eng.fetch("W", 0, lambda: host[:, w:2 * w])
        got = eng.fetch("T", 0, lambda: host[off:, w:2 * w],
                        embed=(off, n))
        assert got.shape == (n, w) and got.dtype == whole.dtype
        assert np.asarray(got).tobytes() == np.asarray(whole).tobytes()
        # the cached entry is the embedded one, and view= slices it
        tail = eng.fetch("T", 0, lambda: 1 / 0, view=(off + 8, 24))
        assert np.array_equal(np.asarray(tail), col[off + 8:off + 32])
        eng.prefetch("P", 0, lambda: host[off:, w:2 * w], embed=(off, n))
        assert np.array_equal(np.asarray(
            eng.fetch("P", 0, lambda: 1 / 0, embed=(off, n))), col)
        c = metrics.snapshot()["counters"]
        assert c["ooc.h2d_trimmed_bytes"] == 2 * off * w * 4
        assert c["ooc.h2d_bytes"] == (n + 2 * (n - off)) * w * 4
        plain = eng.fetch("Z", 0, lambda: host[:, w:2 * w], embed=(0, n))
        assert np.array_equal(np.asarray(plain), col)
        assert metrics.snapshot()["counters"]["ooc.h2d_trimmed_bytes"] \
            == c["ooc.h2d_trimmed_bytes"]
        assert eng.cache.stats()["uploaded_bytes"] \
            == (2 * n + 2 * (n - off)) * w * 4


# -- the benchmark's metric ------------------------------------------------

def _trim_share():
    from benchmarks import run as bench_run
    return bench_run, bench_run.load_module(
        "layer_metrics", "stream.h2d_trim_share").compute


def test_h2d_trim_share_is_declared_and_found():
    bench_run, compute = _trim_share()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    per_layer = bench_run.load_json(
        os.path.join(root, "BENCHMARK.json"))["per_layer"]
    m = [x for x in per_layer if x["name"] == "stream.h2d_trim_share"]
    assert m == [{"name": "stream.h2d_trim_share", "unit": "%",
                  "better": "higher", "source": "program_counter",
                  "layer": "stream engine", "moves": "stream_solve_s",
                  "workloads": ["stream-posv"]}]
    # appended after PR 33's heev metrics; later PRs append after it
    at = per_layer.index(m[0])
    assert per_layer[at - 1]["name"] == "heev.pad_rows_share"
    assert callable(compute)


@pytest.mark.parametrize("counted,want", [
    # the streamed cell by its schedule: 55 blocks of 164
    ({"ooc.h2d_trimmed_bytes": 3690987520,
      "ooc.h2d_bytes": 7315914752}, 100.0 * 3690987520 / 11006902272),
    ({"ooc.h2d_trimmed_bytes": 100, "ooc.h2d_bytes": 300}, 25.0),
    ({"ooc.h2d_bytes": 11006902272}, None),     # the parent: no trim
    ({}, None),
    ({"grid.h2d_bytes": 9, "ooc.h2d_stage_reuse_bytes": 5}, None),
])
def test_h2d_trim_share_by_hand(counted, want):
    assert _trim_share()[1]({"counters": counted}) == want


def test_h2d_trim_share_from_a_solve(rng, obs_on):
    """One posv_ooc at the cell's shape reads 55 / 164.5 blocks (the
    right-hand side is half a block here); getrs_ooc, whose panels stay
    whole, reads nothing."""
    from slate_tpu.obs import metrics
    compute = _trim_share()[1]
    n, w = 256, 32
    a, g, b = _spd(rng, n, np.float32), _f32(rng, n, n), _f32(rng, n, 2)
    ooc.gesv_ooc(g, b, panel_cols=w, cache_budget_bytes=5 * n * w * 4)
    assert compute({"counters": metrics.snapshot()["counters"]}) is None
    metrics.reset()
    ooc.posv_ooc(a, b, panel_cols=w, cache_budget_bytes=5 * n * w * 4)
    got = compute({"counters": metrics.snapshot()["counters"]})
    assert got == 100.0 * 55 / 164.5 and 33.0 < got < 34.0


def test_writeback_spans_nest_over_a_solve(rng, obs_on):
    """posv_ooc at the cell's shape in small (8 panels, 5 resident,
    panels tall enough for the chunk threads): every byte written back
    is counted once, and the writer's spans open one inside the
    other."""
    from slate_tpu.obs import metrics
    n, w = 4096, 512
    g = _f32(rng, n, n)
    a = g @ g.T / 64 + np.eye(n, dtype=np.float32)
    ooc.posv_ooc(a, _f32(rng, n, 8), panel_cols=w,
                 cache_budget_bytes=5 * n * w * 4)
    assert metrics.snapshot()["counters"]["ooc.d2h_bytes"] \
        == 36 * w * w * 4
    wb, d2h, chunks = ([e for e in obs_on.bus_events(cat="staging")
                        if e.name == name]
                       for name in ("ooc::writeback", "ooc::d2h",
                                    "ooc::d2h_chunk"))

    def inside(e, outers):
        return any(o.t0 <= e.t0 and e.t1 <= o.t1 for o in outers)

    assert len(wb) == len(d2h) == 8
    assert all(inside(d, [o for o in wb if o.thread == d.thread])
               for d in d2h)
    assert all(inside(ch, d2h) for ch in chunks)
    # panel k has 4096 - 512 k rows: 2048 or more for k <= 4
    assert len(chunks) == 5 * 8


def test_writebacks_in_flight_are_bounded(monkeypatch, obs_on):
    """The engine's backpressure: queuing a writeback while
    WRITES_IN_FLIGHT are unfinished waits for the oldest, so a driver
    cannot dispatch a whole factorization ahead of the device."""
    gates = [threading.Event() for _ in range(4)]
    started = []

    def slow_d2h(dev, out=None):
        started.append(dev)
        assert gates[dev].wait(30)
        return out

    monkeypatch.setattr(stream, "_d2h", slow_d2h)
    out = np.zeros((4, 2))
    with StreamEngine() as eng:
        queued = []

        def driver():
            for k in range(4):
                eng.write("L", k, k, out[k:k + 1])
                queued.append(k)

        def settles_at(count):
            deadline = time.monotonic() + 30
            while len(queued) < count and time.monotonic() < deadline:
                time.sleep(0.01)
            t.join(0.15)                        # and goes no further
            return len(queued) == count

        t = threading.Thread(target=driver, daemon=True)
        t.start()
        assert settles_at(stream.WRITES_IN_FLIGHT)
        gates[0].set()                          # the oldest finishes
        assert settles_at(stream.WRITES_IN_FLIGHT + 1)
        for g in gates:
            g.set()
        t.join(30)
        assert not t.is_alive() and queued == [0, 1, 2, 3]
        eng.wait_writes()
        assert started == [0, 1, 2, 3]
        assert eng.d2h_wait_seconds >= 0.25
    waits = [e for e in obs_on.bus_events(cat="staging")
             if e.name == "ooc::wait_write" and e.args.get("throttle")]
    assert len(waits) == 2
