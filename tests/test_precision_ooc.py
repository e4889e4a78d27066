"""Mixed-precision streaming (ISSUE 12): the FROZEN ``ooc/precision``
= "f32" cold route is bitwise the PR 11 stream for all three
factorizations (the 2-process mesh leg lives in
tests/shard_ooc_worker.py), bf16 residency halves staged H2D bytes
and fits ~2x the panels at equal cache budget, the refinement-
finished solves match the f32 stream at 1e-5, an ill-conditioned
system trips the residual sentinel and escalates ``mixed_to_full``
through the resil guard funnel, and the engine_for itemsize
satellite warns once instead of silently assuming f64."""

import numpy as np
import pytest

from slate_tpu.core.methods import MethodPrecision
from slate_tpu.core.options import Option
from slate_tpu.dist import shard_ooc
from slate_tpu.linalg import ooc, stream
from slate_tpu.resil import guard


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


def _spd(rng, n):
    x = rng.standard_normal((n, n)).astype(np.float32)
    return x @ x.T / n + 4.0 * np.eye(n, dtype=np.float32), x


def _counters():
    from slate_tpu.obs import metrics
    return dict(metrics.snapshot()["counters"])


# -- frozen-row cold route ------------------------------------------------

def test_cold_cache_resolves_full_precision():
    """The FROZEN ``ooc/precision`` row is "f32": Auto resolves to
    Full on a cold cache (conftest isolates the tune cache), so the
    mixed path is an earned/explicit decision. Dtypes without a
    lower pair demote to the full path instead of erroring."""
    assert MethodPrecision.resolve(1024, np.float32) \
        is MethodPrecision.Full
    assert ooc._resolve_precision(None, 1024, np.float32) is None
    assert ooc._resolve_precision("f32", 1024, np.float32) is None
    assert ooc._resolve_precision("bf16", 1024, np.float32) \
        == np.dtype("bfloat16")
    # f64's lo pair is f32 (the reference d->s pairing)
    assert ooc._resolve_precision("bf16", 1024, np.float64) \
        == np.dtype(np.float32)
    # complex64 has no lo pair: Mixed demotes to the full path
    assert ooc._resolve_precision("bf16", 1024, np.complex64) is None


def test_cold_route_bitwise_all_three_factorizations(rng, obs_on):
    """Acceptance: the default (cold-cache) route and explicit
    precision="f32" produce BITWISE-identical factors for
    potrf/geqrf/getrf — the PR 11 stream is untouched — and the cast
    counters never fire on the full-precision path."""
    n, w = 128, 32
    a, x = _spd(rng, n)
    g = (x + 0.2 * n * np.eye(n, dtype=np.float32))
    budget = 3 * n * w * 4

    L0 = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=budget)
    L1 = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=budget,
                       precision="f32")
    assert np.array_equal(L0, L1)

    q0, t0 = ooc.geqrf_ooc(g, panel_cols=w,
                           cache_budget_bytes=budget)
    q1, t1 = ooc.geqrf_ooc(g, panel_cols=w,
                           cache_budget_bytes=budget,
                           precision="f32")
    assert np.array_equal(q0, q1) and np.array_equal(t0, t1)

    l0, p0 = ooc.getrf_tntpiv_ooc(g, panel_cols=w,
                                  cache_budget_bytes=budget)
    l1, p1 = ooc.getrf_tntpiv_ooc(g, panel_cols=w,
                                  cache_budget_bytes=budget,
                                  precision="f32")
    assert np.array_equal(l0, l1) and np.array_equal(p0, p1)

    c = _counters()
    assert c.get("ooc.cast_demote_bytes", 0) == 0
    assert c.get("ooc.cast_promote_bytes", 0) == 0


def test_shard_cold_route_bitwise_and_bf16_frames(rng, grid8,
                                                  obs_on):
    """The sharded layer's cold route is bitwise too, and the bf16
    mode's broadcast frames carry exactly half the bytes over the
    ppermute tree (the deterministic halving bench --shard gates
    on), with the factor identical across the demote/promote mirror
    path to bf16-update accuracy."""
    from slate_tpu.obs import metrics
    n, w = 160, 32
    a, _ = _spd(rng, n)
    budget = 64 * n * w * 4
    L0 = shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=w,
                                   cache_budget_bytes=budget)
    c0 = _counters()
    L1 = shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=w,
                                   cache_budget_bytes=budget,
                                   precision="f32")
    assert np.array_equal(L0, L1)
    c1 = _counters()
    f32_bcast = c1["ooc.shard.bcast_bytes"] \
        - c0["ooc.shard.bcast_bytes"]
    Lb = shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=w,
                                   cache_budget_bytes=budget,
                                   precision="bf16")
    c2 = _counters()
    bf16_bcast = c2["ooc.shard.bcast_bytes"] \
        - c1["ooc.shard.bcast_bytes"]
    assert bf16_bcast * 2 == f32_bcast
    assert c2.get("ooc.cast_demote_bytes", 0) > 0
    assert c2.get("ooc.cast_promote_bytes", 0) > 0
    assert np.allclose(L0, Lb, rtol=5e-2, atol=5e-2)
    # lookahead composes with the mixed frames: depth 1 applies the
    # SAME lo frames in the same per-panel order — bitwise vs its
    # own depth 0
    Lb1 = shard_ooc.shard_potrf_ooc(a, grid8, panel_cols=w,
                                    cache_budget_bytes=budget,
                                    precision="bf16", lookahead=1)
    assert np.array_equal(Lb, Lb1)


def test_shard_getrf_bf16_pivot_row_pair(rng, grid8):
    """The mixed LU frame's byte-split pivot encoding: the sharded
    bf16 stream factors a cross-panel-pivoting matrix to a valid
    factorization (the selection decodes identically on every
    consumer), at bf16-update residual."""
    n, w = 160, 32
    _, x = _spd(rng, n)
    g = (x + 0.1 * n * np.eye(n, dtype=np.float32)) \
        * (1.0 + np.arange(n, dtype=np.float32))[:, None]
    lu, piv = shard_ooc.shard_getrf_ooc(g, grid8, panel_cols=w,
                                        cache_budget_bytes=0,
                                        precision="bf16")
    perm = ooc._swaps_to_perm(piv, n)
    L = np.tril(lu, -1) + np.eye(n, dtype=np.float32)
    resid = np.abs(g[perm] - L @ np.triu(lu)).max() \
        / np.abs(g).max()
    assert resid < 5e-2                   # bf16-grade, but a factor


# -- byte and budget accounting -------------------------------------------

def test_bf16_residency_cuts_staged_bytes(rng, obs_on):
    """bf16 residency at an EQUAL tight budget: the f32 stream
    thrashes (the factor outgrows the budget) while the demoted
    residents mostly fit AND the remaining uploads ship half the
    bytes — >= 40% staged-H2D reduction (the bench --ooc acceptance
    band) with the demotion volume on the cast counter."""
    n, w = 256, 32
    a, _ = _spd(rng, n)
    budget = 3 * n * w * 4
    c0 = _counters()
    ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=budget)
    c1 = _counters()
    ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=budget,
                  precision="bf16")
    c2 = _counters()
    h_f32 = c1["ooc.h2d_bytes"] - c0.get("ooc.h2d_bytes", 0)
    h_bf16 = c2["ooc.h2d_bytes"] - c1["ooc.h2d_bytes"]
    assert h_bf16 <= 0.6 * h_f32
    assert c2.get("ooc.cast_demote_bytes", 0) \
        > c1.get("ooc.cast_demote_bytes", 0)


def test_bf16_trimmed_upload_is_the_resident_panel(rng, obs_on):
    """A budget of three bf16 panels makes the mixed stream stage
    evicted factor panels again, from their diagonal block down
    (demote_host of fewer rows, the frame made in bf16 on the device):
    the factor equals the all-resident budget's, which uploads no
    factor panel at all, bit for bit, and potrs_ooc's lo sweeps read
    the same X from both."""
    n, w = 256, 32
    a, _ = _spd(rng, n)
    b = rng.standard_normal((n, 2)).astype(np.float32)
    want = ooc.potrf_ooc(a, panel_cols=w, precision="bf16",
                         cache_budget_bytes=8 * n * w * 2)
    c0 = _counters()
    assert "ooc.h2d_trimmed_bytes" not in c0    # nothing was re-staged
    got = ooc.potrf_ooc(a, panel_cols=w, precision="bf16",
                        cache_budget_bytes=3 * n * w * 2)
    c1 = _counters()
    assert got.tobytes() == want.tobytes()
    trimmed = c1["ooc.h2d_trimmed_bytes"]
    assert trimmed > 0 and trimmed % (w * w * 2) == 0   # bf16 blocks
    # the demotion reads only the rows it stages: f32 bytes in, half out
    staged_l = c1["ooc.h2d_bytes"] - c0["ooc.h2d_bytes"] \
        - (n // w) * (n // w + 1) // 2 * w * w * 4      # less the A panels
    demoted_host = c1["ooc.cast_demote_bytes"] \
        - 2 * c0["ooc.cast_demote_bytes"]       # less the puts' demotions
    assert demoted_host == 2 * staged_l
    x0 = ooc.potrs_ooc(want, b, panel_cols=w, precision="bf16",
                       cache_budget_bytes=8 * n * w * 2)
    x1 = ooc.potrs_ooc(got, b, panel_cols=w, precision="bf16",
                       cache_budget_bytes=3 * n * w * 2)
    assert x1.tobytes() == x0.tobytes()
    c2 = _counters()
    # forward L0..L7 and backward L4..L0 at a budget of three: all
    # but L0's two uploads are trimmed
    assert c2["ooc.h2d_trimmed_bytes"] > trimmed


def test_bf16_residency_fits_2x_panels():
    """Budget accounting: at an equal byte budget the cache holds
    ~2x the panels when residents are demoted — pinned directly on
    the engine (put through demote_dev halves each entry's
    charge)."""
    import jax.numpy as jnp
    n, w, panels = 64, 16, 8
    budget = 4 * n * w * 4          # exactly 4 f32 panels
    e32 = stream.StreamEngine(budget_bytes=budget)
    e16 = stream.StreamEngine(budget_bytes=budget,
                              resident_dtype=np.dtype("bfloat16"))
    assert e16.cache.stats()["resident_dtype"] == "bfloat16"
    for k in range(panels):
        arr = jnp.ones((n, w), jnp.float32) * (k + 1)
        e32.put("L", k, arr)
        e16.put("L", k, stream.demote_dev(arr, np.dtype("bfloat16")))
    s32, s16 = e32.cache.stats(), e16.cache.stats()
    e32.finish()
    e16.finish()
    assert s32["entries"] == 4
    assert s16["entries"] == 8
    assert s16["resident_bytes"] == s32["resident_bytes"]


# -- refinement-guarded solves --------------------------------------------

def test_posv_gesv_bf16_refined_to_f32_accuracy(rng, obs_on):
    """The mixed solves finish with iterative refinement: the bf16
    answers land within 1e-5 of the f32 stream's (the acceptance
    tolerance), no escalation, and the sweep count is observable."""
    from slate_tpu.obs import metrics
    n, w = 192, 32
    a, x = _spd(rng, n)
    g = x + 0.2 * n * np.eye(n, dtype=np.float32)
    b = rng.standard_normal((n, 4)).astype(np.float32)
    guard.reset_counts()
    _, X_f = ooc.posv_ooc(a, b, panel_cols=w)
    _, X_b = ooc.posv_ooc(a, b, panel_cols=w, precision="bf16")
    assert np.abs(X_b - X_f).max() <= 1e-5 * np.abs(X_f).max()
    _, Y_f = ooc.gesv_ooc(g, b, panel_cols=w)
    _, Y_b = ooc.gesv_ooc(g, b, panel_cols=w, precision="bf16")
    assert np.abs(Y_b - Y_f).max() <= 1e-5 * np.abs(Y_f).max()
    assert guard.counts().get("resil.fallback.mixed_to_full", 0) == 0
    h = metrics.snapshot()["histograms"].get("refine.ooc.iters")
    assert h is not None and h["count"] == 2


def test_residual_sentinel_escalates_mixed_to_full(rng):
    """An ill-conditioned system the bf16 factor cannot refine trips
    the residual sentinel: ``mixed_to_full`` is recorded through THE
    guard funnel (counted with obs off, like every ladder rung) and
    the returned answer is the full-f32 fallback BITWISE (the
    fallback reruns exactly the f32 factor+solve)."""
    n, w = 128, 32
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    d = np.logspace(0, -7, n)
    ill = ((q * d) @ q.T).astype(np.float64)
    ill = ((ill + ill.T) / 2 + 1e-7 * np.eye(n)).astype(np.float32)
    b = rng.standard_normal((n, 2)).astype(np.float32)
    opts = {Option.MaxIterations: 3}
    guard.reset_counts()
    L_b, X_b = ooc.posv_ooc(ill, b, panel_cols=w, precision="bf16",
                            opts=opts)
    assert guard.counts().get("resil.fallback.mixed_to_full", 0) == 1
    L_f, X_f = ooc.posv_ooc(ill, b, panel_cols=w)
    assert np.array_equal(X_b, X_f)
    assert np.array_equal(L_b, L_f)       # the f32 factor is returned


def test_mixed_lu_is_tournament_only():
    """precision="bf16" with an explicit partial pivot mode is a loud
    error (the mixed path needs the immutable tournament store); with
    pivot unset, bf16 implies tournament."""
    from slate_tpu.core.exceptions import SlateError
    rng = np.random.default_rng(0)
    g = rng.standard_normal((64, 64)).astype(np.float32) \
        + 16 * np.eye(64, dtype=np.float32)
    with pytest.raises(SlateError, match="tournament-only"):
        ooc.getrf_ooc(g, panel_cols=32, pivot="partial",
                      precision="bf16")
    lu, piv = ooc.getrf_ooc(g, panel_cols=32, precision="bf16")
    lt, pt = ooc.getrf_tntpiv_ooc(g, panel_cols=32,
                                  precision="bf16")
    assert np.array_equal(lu, lt) and np.array_equal(piv, pt)


# -- checkpoint identity guard --------------------------------------------

def test_ckpt_precision_mismatch_starts_fresh(rng, tmp_path):
    """The checkpoint meta records the resolved precision mode: a
    resume under a DIFFERENT ``ooc/precision`` must start fresh
    instead of serving the other mode's durable panels as its own
    (the PR 10 lu_pivot identity-guard play)."""
    n, w = 128, 32
    a, _ = _spd(rng, n)
    ck = str(tmp_path / "ck")
    # copy out of the live memmaps: later runs rewrite the same
    # durable file underneath them
    L_b = np.array(ooc.potrf_ooc(a, panel_cols=w, ckpt_path=ck,
                                 ckpt_every=1, precision="bf16"))
    # a completed checkpoint of the SAME mode resumes as a no-op
    L_b2 = np.array(ooc.potrf_ooc(a, panel_cols=w, ckpt_path=ck,
                                  ckpt_every=1, precision="bf16"))
    assert np.array_equal(L_b, L_b2)
    # a different mode must NOT adopt those panels: fresh run ==
    # the checkpoint-free f32 stream bitwise, != the bf16 factor
    L_f = ooc.potrf_ooc(a, panel_cols=w, ckpt_path=ck, ckpt_every=1)
    assert np.array_equal(L_f, ooc.potrf_ooc(a, panel_cols=w))
    assert not np.array_equal(L_f, L_b)


# -- engine_for satellite -------------------------------------------------

def test_engine_for_unknown_dtype_warns_once(monkeypatch):
    """The silent `itemsize = 8` fallback is gone: an unknown dtype
    warns ONCE (per process) and the mixed residency sizes the auto
    budget at the resident itemsize."""
    monkeypatch.setattr(stream, "_warned_unknown_dtype", False)
    with pytest.warns(UserWarning, match="no dtype supplied"):
        eng = stream.engine_for(64, 16, None, budget_bytes=0)
    eng.finish()
    # second call: flag holds, no second warning
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng = stream.engine_for(64, 16, None, budget_bytes=0)
    eng.finish()


def test_engine_for_auto_budget_uses_resident_itemsize(monkeypatch):
    """An "auto" budget's working-set reserve is sized at the
    RESIDENT (post-demotion) itemsize — at bf16 residency the
    reserve halves, so the cache budget grows by exactly the
    difference (4 panels x 2 bytes saved)."""
    import jax

    class FakeDev:
        def memory_stats(self):
            return {"bytes_limit": 1 << 30}

    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDev()])
    n, w = 4096, 512
    b32 = stream.auto_budget_bytes(n, w, 4)
    b16 = stream.auto_budget_bytes(n, w, 2)
    e32 = stream.engine_for(n, w, np.float32, budget_bytes="auto")
    e16 = stream.engine_for(n, w, np.float32, budget_bytes="auto",
                            resident_dtype=np.dtype("bfloat16"))
    s32, s16 = e32.cache.budget, e16.cache.budget
    e32.finish()
    e16.finish()
    assert s32 == b32 and s16 == b16
    assert s16 - s32 == stream.RESERVE_PANELS * n * w * 2


def test_solve_sweeps_bf16_staging(rng, obs_on):
    """potrs/getrs precision: the lo sweeps stage demoted factor
    panels (half the H2D bytes of the f32 sweeps) and stay close
    enough for the refinement loop to finish."""
    n, w = 128, 32
    a, x = _spd(rng, n)
    L = ooc.potrf_ooc(a, panel_cols=w)
    b = rng.standard_normal((n, 2)).astype(np.float32)
    c0 = _counters()
    X_f = ooc.potrs_ooc(L, b, panel_cols=w)
    c1 = _counters()
    X_b = ooc.potrs_ooc(L, b, panel_cols=w, precision="bf16")
    c2 = _counters()
    h_f = c1["ooc.h2d_bytes"] - c0["ooc.h2d_bytes"]
    h_b = c2["ooc.h2d_bytes"] - c1["ooc.h2d_bytes"]
    # factor panels halve; the RHS upload stays f32
    assert h_b < 0.6 * h_f
    assert np.allclose(X_f, X_b, rtol=5e-2, atol=5e-2)
