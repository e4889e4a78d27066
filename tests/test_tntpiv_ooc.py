"""Tournament-pivot (CALU) out-of-core LU (ISSUE 10):
getrf_tntpiv_ooc's factorization contract (LAPACK packed + ipiv,
getrs-consumable), the zero-invalidation cache behavior its
original-row-order store buys, the MethodLUPivot arbitration (cold
cache keeps the PR 9 partial path bit-identically), adversarial
pivot-quality coverage (Wilkinson-style growth, cross-chunk ties,
rank-deficient chunks), the ooc.lu_invalidations per-cause counter
(0 on either path since PR 47), and checkpoint/resume with the lu_pivot mode in
the durable identity."""

import json

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.core.methods import MethodLUPivot
from slate_tpu.linalg import ooc, stream
from slate_tpu.resil import faults


@pytest.fixture
def obs_on():
    from slate_tpu import obs
    from slate_tpu.obs import metrics
    obs.enable()
    obs.clear()
    metrics.reset()
    yield obs
    obs.disable()
    obs.clear()
    metrics.reset()


def _lu_residual(a, lu, ipiv):
    """Relative ||A[perm] - L U|| of the packed factor."""
    m, n = a.shape
    kmax = min(m, n)
    perm = ooc._swaps_to_perm(ipiv, m)
    L = np.tril(lu, -1)[:, :kmax] + np.eye(m, kmax)
    U = np.triu(lu[:kmax])
    return np.abs(a[perm] - L @ U).max() / max(np.abs(a).max(), 1.0)


# -- factorization contract -----------------------------------------------

def test_tntpiv_ooc_factors_and_solves(rng):
    n, w = 160, 32
    a = rng.standard_normal((n, n))
    lu, ipiv = ooc.getrf_tntpiv_ooc(a, panel_cols=w)
    assert _lu_residual(a, lu, ipiv) < 1e-12
    # the packed contract is getrf_ooc's exactly: getrs_ooc consumes
    # it unchanged, either mode's factor through one solve path
    b = rng.standard_normal((n, 5))
    x = ooc.getrs_ooc(lu, ipiv, b, panel_cols=w)
    assert np.abs(a @ x - b).max() < 1e-9


def test_tntpiv_ooc_rect_and_ragged(rng):
    for shape, w in (((96, 160), 32), ((200, 64), 32), ((100, 100), 32),
                     ((96, 96), 40)):
        a = rng.standard_normal(shape)
        lu, ipiv = ooc.getrf_tntpiv_ooc(a, panel_cols=w)
        assert ipiv.shape == (min(shape),)
        assert _lu_residual(a, lu, ipiv) < 1e-12, (shape, w)


def test_tntpiv_ooc_cached_bitwise_and_zero_invalidations(rng):
    """The tentpole property: factor panels are immutable (original-
    row-order store), so a budgeted run serves every left-looking
    revisit from the cache with ZERO invalidations — and is bitwise
    the uncached schedule."""
    n, w = 160, 32
    a = rng.standard_normal((n, n))
    a *= (1.0 + np.arange(n))[:, None]   # cross-panel pivots galore
    lu0, piv0 = ooc.getrf_tntpiv_ooc(a, panel_cols=w,
                                     cache_budget_bytes=0)
    lu1, piv1 = ooc.getrf_tntpiv_ooc(a, panel_cols=w,
                                     cache_budget_bytes=64 * n * w * 8)
    s = stream.last_stats()
    np.testing.assert_array_equal(lu0, lu1)
    np.testing.assert_array_equal(piv0, piv1)
    assert s["invalidations"] == 0
    assert s["invalidated_bytes"] == 0
    assert s["hits"] > 0                 # the MRU cache finally works
    # under a forced-eviction budget the result is still bitwise
    lu2, piv2 = ooc.getrf_tntpiv_ooc(a, panel_cols=w,
                                     cache_budget_bytes=3 * n * w * 8)
    np.testing.assert_array_equal(lu0, lu2)
    np.testing.assert_array_equal(piv0, piv2)


def test_tntpiv_ooc_selection_matches_incore_when_single_chunk(rng):
    """With one tournament chunk (the native-cap default at test
    sizes) round 0 IS a partial-pivot LU of the whole live block, so
    the selected pivot ROWS must match in-core getrf's choices
    (values differ only in the no-pivot factor's operation order)."""
    n, w = 96, 32
    a = rng.standard_normal((n, n))
    _, ipiv = ooc.getrf_tntpiv_ooc(a, panel_cols=w)
    F = st.getrf(st.Matrix(a, mb=w))
    np.testing.assert_array_equal(ipiv, np.asarray(F.pivots)[:n])


# -- MethodLUPivot arbitration --------------------------------------------

def test_cold_cache_pins_partial_path(rng):
    """Acceptance pin: cold-cache getrf_ooc/gesv_ooc (no pivot
    argument) is bit-identical to the explicit partial route — the
    PR 9 body, untouched."""
    n, w = 128, 32
    a = rng.standard_normal((n, n))
    a *= (1.0 + np.arange(n))[:, None]
    b = rng.standard_normal((n, 3))
    assert MethodLUPivot.resolve(n, a.dtype) is MethodLUPivot.Partial
    lu0, piv0 = ooc.getrf_ooc(a, panel_cols=w)
    lu1, piv1 = ooc.getrf_ooc(a, panel_cols=w, pivot="partial")
    np.testing.assert_array_equal(lu0, lu1)
    np.testing.assert_array_equal(piv0, piv1)
    (lu2, piv2), x2 = ooc.gesv_ooc(a, b, panel_cols=w)
    (lu3, piv3), x3 = ooc.gesv_ooc(a, b, panel_cols=w,
                                   pivot="partial")
    np.testing.assert_array_equal(lu2, lu3)
    np.testing.assert_array_equal(x2, x3)
    np.testing.assert_array_equal(lu0, lu2)


def test_pivot_arg_and_tuned_entry_route_tournament(rng, monkeypatch):
    n, w = 96, 32
    a = rng.standard_normal((n, n))
    ref = ooc.getrf_tntpiv_ooc(a, panel_cols=w)
    via_arg = ooc.getrf_ooc(a, panel_cols=w, pivot="tournament")
    np.testing.assert_array_equal(ref[0], via_arg[0])
    np.testing.assert_array_equal(ref[1], via_arg[1])
    # a measured cache entry reroutes the Auto path the same way
    from slate_tpu.tune import select as tsel
    real = tsel.resolve

    def fake(op, param, **kw):
        if (op, param) == ("ooc", "lu_pivot"):
            return "tournament"
        return real(op, param, **kw)

    monkeypatch.setattr(tsel, "resolve", fake)
    via_tune = ooc.getrf_ooc(a, panel_cols=w)
    np.testing.assert_array_equal(ref[0], via_tune[0])
    np.testing.assert_array_equal(ref[1], via_tune[1])


def test_partial_mode_rejects_checkpoint(rng, tmp_path):
    a = rng.standard_normal((64, 64))
    from slate_tpu.core.exceptions import SlateError
    with pytest.raises((SlateError, AssertionError, ValueError)):
        ooc.getrf_ooc(a, panel_cols=32, pivot="partial",
                      ckpt_path=str(tmp_path), ckpt_every=1)


# -- pivot-growth bounds (adversarial panels) -----------------------------

def _wilkinson_growth(n, dtype=np.float64):
    """The classic 2^(n-1)-growth matrix for partial pivoting:
    unit lower triangle of -1s, ones on the diagonal and in the last
    column. Any pivoting scheme that selects the diagonal (partial
    pivoting does; the tournament's single-chunk bracket does too)
    doubles the last column per elimination step."""
    a = -np.tril(np.ones((n, n), dtype), -1)
    a += np.eye(n, dtype=dtype)
    a[:, -1] = 1.0
    return a


def test_growth_matrix_tournament_vs_partial(rng):
    """Wilkinson-style growth panels: both disciplines factor it
    (residual scaled by the 2^(n-1) growth is fine at n=48 in f64),
    and the tournament's residual stays within a small factor of
    partial pivoting's — the documented CALU trade, pinned so a
    selection regression (growth beyond the CALU bound) fails
    loudly."""
    n, w = 48, 16
    a = _wilkinson_growth(n)
    lu_p, piv_p = ooc.getrf_ooc(a, panel_cols=w, pivot="partial")
    lu_t, piv_t = ooc.getrf_ooc(a, panel_cols=w, pivot="tournament",
                                chunk=16)
    rp = _lu_residual(a, lu_p, piv_p)
    rt = _lu_residual(a, lu_t, piv_t)
    # growth 2^47 ~ 1.4e14 against eps 2.2e-16: residuals up to ~1e-1
    # are the matrix's fault, not the factorization's
    assert np.isfinite(rt) and np.isfinite(rp)
    assert rt <= max(100.0 * rp, 1e-10), (rt, rp)
    # the perturbed variant (random signs break the exact ties)
    b = a + 1e-8 * rng.standard_normal((n, n))
    lu_t2, piv_t2 = ooc.getrf_ooc(b, panel_cols=w,
                                  pivot="tournament", chunk=16)
    assert np.isfinite(_lu_residual(b, lu_t2, piv_t2))


def test_cross_chunk_tie_pivots_deterministic(rng):
    """Exact |max| ties straddling tournament chunk boundaries: the
    bracket must resolve them deterministically (two runs bitwise
    equal) and still factor accurately."""
    n, w, chunk = 128, 32, 32
    a = rng.standard_normal((n, n))
    # plant exact-magnitude ties across chunk boundaries in the
    # leading columns of every panel
    for j in range(0, n, w):
        a[(j + 7) % n, j] = 17.0
        a[(j + chunk + 7) % n, j] = -17.0
        a[(j + 2 * chunk + 7) % n, j] = 17.0
    r1 = ooc.getrf_tntpiv_ooc(a, panel_cols=w, chunk=chunk)
    r2 = ooc.getrf_tntpiv_ooc(a, panel_cols=w, chunk=chunk)
    np.testing.assert_array_equal(r1[0], r2[0])
    np.testing.assert_array_equal(r1[1], r2[1])
    assert _lu_residual(a, r1[0], r1[1]) < 1e-12


def test_rank_deficient_chunks(rng):
    """Chunks that are individually rank-deficient (duplicated rows,
    zero blocks) while the panel stays full-rank: local LUs nominate
    from degenerate chunks, the combine rounds must still surface
    the true pivots."""
    n, w, chunk = 128, 32, 32
    a = rng.standard_normal((n, n))
    a[32:64] = a[:32]                   # chunk 1 duplicates chunk 0
    a[64:96] = 0.0                      # chunk 2 is all zeros
    a += np.diag(np.linspace(2.0, 3.0, n))   # keep the panel regular
    lu, ipiv = ooc.getrf_tntpiv_ooc(a, panel_cols=w, chunk=chunk)
    assert _lu_residual(a, lu, ipiv) < 1e-11
    # degenerate selection repair: a panel whose live block has a
    # zero column must still produce a valid permutation
    z = rng.standard_normal((96, 96))
    z[:, 0] = 0.0
    lu_z, piv_z = ooc.getrf_tntpiv_ooc(z, panel_cols=32, chunk=32)
    perm = ooc._swaps_to_perm(piv_z, 96)
    assert sorted(perm.tolist()) == list(range(96))


# -- the ooc.lu_invalidations per-cause counter ---------------------------

def test_lu_invalidation_counter_partial_vs_tournament(rng, obs_on):
    """The per-cause counters (ooc.lu_invalidations /
    ooc.lu_invalidation_bytes) read 0 under BOTH disciplines since
    PR 47: the partial stream's written panels are as immutable as
    the tournament's, its one repair (ooc::lu_fixup, once) moves rows
    j1: of each panel before the last and retires nothing."""
    from slate_tpu.obs import metrics
    n, w = 128, 32
    a = rng.standard_normal((n, n))
    a *= (1.0 + np.arange(n))[:, None]
    budget = 64 * n * w * 8
    for pivot in ("partial", "tournament"):
        metrics.reset()
        obs_on.clear()
        ooc.getrf_ooc(a, panel_cols=w, cache_budget_bytes=budget,
                      pivot=pivot)
        c = metrics.snapshot()["counters"]
        assert c.get("ooc.lu_invalidations", 0) == 0
        assert c.get("ooc.lu_invalidation_bytes", 0) == 0
        assert stream.last_stats()["invalidated_bytes"] == 0
        assert c["ooc.cache.hits"] > 0 and c["ooc.lu_panels_swapped"] == 3
        fixups = [e for e in obs_on.bus_events()
                  if e.name == "ooc::lu_fixup"]
        assert len(fixups) == (pivot == "partial")
        assert c.get("ooc.lu_fixup_bytes", 0) == sum(
            e.args["bytes"] for e in fixups) == (pivot == "partial") \
            * sum(2 * (n - j1) * w * 8 for j1 in range(w, n, w))


# -- checkpoint/resume ----------------------------------------------------

def test_tntpiv_ckpt_crash_resume_bitwise(rng, tmp_path):
    """Interrupted mid-stream, the resume rebuilds the visit gathers
    from the durable permutation snapshots and lands on the BITWISE
    factor — the checkpoint the partial path structurally cannot
    offer (its fixups rewrite committed panels)."""
    n, w = 160, 32
    a = rng.standard_normal((n, n))
    ref_lu, ref_piv = ooc.getrf_tntpiv_ooc(a, panel_cols=w)
    faults.install(faults.FaultPlan(
        [{"site": "step",
          "match": {"op": "getrf_tntpiv_ooc", "step": 3},
          "times": 1}]))
    with pytest.raises(faults.InjectedFault):
        ooc.getrf_tntpiv_ooc(a, panel_cols=w,
                             ckpt_path=str(tmp_path), ckpt_every=1)
    faults.clear()
    meta = json.loads((tmp_path / "meta.json").read_text())
    assert meta["epoch"] == 3
    assert meta["lu_pivot"] == "tournament"
    lu1, piv1 = ooc.getrf_tntpiv_ooc(a, panel_cols=w,
                                     ckpt_path=str(tmp_path),
                                     ckpt_every=1)
    np.testing.assert_array_equal(ref_lu, lu1)
    np.testing.assert_array_equal(ref_piv, piv1)
    # completed checkpoint resumes as a no-op with the same result
    lu2, piv2 = ooc.getrf_tntpiv_ooc(a, panel_cols=w,
                                     ckpt_path=str(tmp_path),
                                     ckpt_every=1)
    np.testing.assert_array_equal(ref_lu, lu2)
    np.testing.assert_array_equal(ref_piv, piv2)


def test_ckpt_mode_mismatch_starts_fresh(rng, tmp_path):
    """The fingerprint guard extends to the pivot mode: a checkpoint
    whose meta records a different ``lu_pivot`` is rejected (the
    resume starts fresh at epoch 0) instead of mixing two pivot
    disciplines' panels in one factor."""
    from slate_tpu.resil import checkpoint as rc
    n, w, nt = 96, 32, 3
    a = rng.standard_normal((n, n))
    arrays = {"ipiv": ((n,), np.int64), "perms": ((nt, n), np.int64)}
    ck = rc.maybe_checkpointer(str(tmp_path), "getrf_tntpiv_ooc", a,
                               w, nt, every=1, extra_arrays=arrays,
                               extra_meta={"lu_pivot": "tournament"})
    ck.commit(2)
    same = rc.maybe_checkpointer(str(tmp_path), "getrf_tntpiv_ooc", a,
                                 w, nt, every=1, extra_arrays=arrays,
                                 extra_meta={"lu_pivot": "tournament"})
    assert same.epoch == 2
    other = rc.maybe_checkpointer(str(tmp_path), "getrf_tntpiv_ooc",
                                  a, w, nt, every=1,
                                  extra_arrays=arrays,
                                  extra_meta={"lu_pivot": "partial"})
    assert other.epoch == 0
