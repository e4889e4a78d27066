"""Block-recursive Pallas panel kernels (ISSUE 6): the adversarial
pivoting suite for lu_panel_rec (bitwise pivot parity with
lu_panel_fori), the tall-panel split path, the blocked Givens-chain
apply, and the routing arbitration (cold cache == the pre-round-10
chains, cached entries reroute).

All kernels run through the Pallas INTERPRETER on the CPU tier
(pallas_kernels.pallas_interpret), so tier-1 executes the real kernel
bodies."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from slate_tpu.core.methods import MethodLUPanel
from slate_tpu.linalg.lu import _lu_panel, lu_panel_fori
from slate_tpu.ops import pallas_kernels as pk
from slate_tpu.tune import cache as tcache
from slate_tpu.tune import select as tune_select


@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    """Isolated tune cache (same contract as test_tune.py)."""
    monkeypatch.setenv("SLATE_TPU_TUNE_CACHE", str(tmp_path))
    monkeypatch.delenv("SLATE_TPU_TUNE", raising=False)
    tcache.reset_cache()
    yield tmp_path
    tcache.reset_cache()


# -- adversarial pivoting panels -----------------------------------------

def _dyadic_noise(rng, m, w):
    """Exactly representable small values (k/16, |k| <= 8): products
    and sums stay exact long enough that the forced-pivot margins
    below survive any update rounding differences."""
    return (rng.integers(-8, 9, (m, w)) / 16.0).astype(np.float32)


def _spiked(rng, m, w, spike_rows, noise=True):
    """Panel with a dominant (value 64.0) spike per column j at
    original row spike_rows[j]. The spikes force the pivot SEQUENCE
    regardless of rounding: noise is <= 1/2 after any number of
    update steps (multipliers <= 1/512, update terms <= 1/8), so the
    pivot search margin never closes — both kernels must return the
    bitwise-identical pivot sequence even where update rounding
    differs."""
    a = _dyadic_noise(rng, m, w) if noise \
        else np.zeros((m, w), np.float32)
    for j, r in enumerate(spike_rows):
        a[r, j] = 64.0
    return jnp.asarray(a)


def _panel_cases(rng, m, w, ib):
    """The adversarial suite: cross-half pivots at every recursion
    boundary, exact ties, a zero column, and a bottom-block random
    permutation (pivot rows never disturbed until consumed — spikes
    live in rows >= m - w, swaps only touch the consumed row and the
    current pivot row, which are distinct spikes)."""
    cases = {}
    # pivots from the far bottom: every column's pivot crosses every
    # row-half and the swap lands across every column-recursion
    # boundary (w/2, w/4, ..., ib)
    cases["antidiag"] = _spiked(rng, m, w, [m - 1 - j
                                            for j in range(w)])
    # pivot always in the NEXT ib-segment: the swap crosses each
    # base-case boundary exactly at the recursion seam
    cases["boundary"] = _spiked(
        rng, m, w, [min((j // ib + 1) * ib, m - 1) for j in range(w)])
    # random permutation confined to the bottom w rows
    sigma = rng.permutation(w)
    cases["randperm"] = _spiked(rng, m, w,
                                [m - w + int(s) for s in sigma])
    # exact ties: duplicate equal spikes per column, zero noise (all
    # values stay pristine, so the tie compare sees bitwise-equal
    # magnitudes in both kernels; first-max must win)
    a = np.zeros((m, w), np.float32)
    for j in range(w):
        a[m - w + j, j] = 64.0
        a[m - w // 2 + j // 2, j] = 64.0
    cases["ties"] = jnp.asarray(a)
    # a zero column (j = w//2) among spiked ones: pivot degenerates
    # to the diagonal row, safe-divide path taken
    rows = [m - 1 - j for j in range(w)]
    z = _spiked(rng, m, w, rows, noise=False)
    z = z.at[:, w // 2].set(0.0)
    cases["zerocol"] = z
    return cases


def test_lu_panel_rec_adversarial_bitwise_pivots(rng):
    m, w, ib = 256, 32, 8
    for kind, a in _panel_cases(rng, m, w, ib).items():
        packed, piv = pk.lu_panel_rec(a, ib=ib)
        ref, piv_ref = lu_panel_fori(a)
        assert np.array_equal(np.asarray(piv), np.asarray(piv_ref)), \
            "pivot sequence diverged on %r" % kind
        if kind in ("ties", "zerocol"):
            # zero-noise panels: every arithmetic op is exact, so the
            # packed factors must match BITWISE, not just closely
            assert np.array_equal(np.asarray(packed),
                                  np.asarray(ref)), kind
        else:
            # noise kinds: pivots are forced (bitwise above) but the
            # update ORDER differs (rank-ib matmuls vs rank-1 chain),
            # so values agree only to f32 rounding
            np.testing.assert_allclose(np.asarray(packed),
                                       np.asarray(ref), atol=1e-4,
                                       rtol=1e-4, err_msg=kind)


def test_lu_panel_rec_default_ib_matches_fori(rng):
    # the frozen ib (tune ("lu_panel", "ib") = 32) path, w = ib * 2^k
    m, w = 256, 128
    a = _spiked(rng, m, w, [m - 1 - j for j in range(w)])
    packed, piv = pk.lu_panel_rec(a)
    ref, piv_ref = lu_panel_fori(a)
    assert np.array_equal(np.asarray(piv), np.asarray(piv_ref))
    np.testing.assert_allclose(np.asarray(packed), np.asarray(ref),
                               atol=1e-4)


def test_lu_panel_rec_reconstructs(rng):
    # generic float panel: P A = L U to f32 accuracy
    m, w = 256, 64
    a = jnp.asarray(rng.standard_normal((m, w)).astype(np.float32))
    packed, piv = pk.lu_panel_rec(a, ib=16)
    perm = np.asarray(
        jax.lax.linalg.lu_pivots_to_permutation(piv, m))
    pk_np = np.asarray(packed)
    L = np.tril(pk_np, -1)[:, :w] + np.eye(m, w, dtype=np.float32)
    U = np.triu(pk_np[:w])
    np.testing.assert_allclose(np.asarray(a)[perm], L @ U,
                               atol=1e-4)


def test_lu_panel_rec_tall_split_exact_pivoting(rng):
    """The tall-panel path: a panel over the single-dispatch element
    budget factors through the JAX-level halving with the
    row-block-gridded trailing update, with the pivot sequence
    bitwise equal to the full-height fori panel. The budget is forced
    down so the split machinery runs; the height is the tallest the
    gate admits (LU_REC_MAX_M — the v5e compiler refuses taller
    panels however narrow, PR 22 compile-only finding, so heights
    above methods.NATIVE_LU_MAX_M no longer route here)."""
    from slate_tpu.core.methods import NATIVE_LU_MAX_M
    m, w = pk.LU_REC_MAX_M, 32
    assert pk._rec_shape_reason(NATIVE_LU_MAX_M + 128, w,
                                jnp.float32) == "height"
    a_np = np.zeros((m, w), np.float32)
    rng2 = np.random.default_rng(7)
    a_np[:] = (rng2.integers(-8, 9, (m, w)) / 16.0)
    for j in range(w):
        a_np[m - 1 - j, j] = 64.0
    a = jnp.asarray(a_np)
    # budget fits only (m, 8): two JAX-level splits + gridded updates
    packed, piv = pk.lu_panel_rec(a, ib=8, max_elems=m * 8)
    ref, piv_ref = lu_panel_fori(a)
    assert np.array_equal(np.asarray(piv), np.asarray(piv_ref))
    np.testing.assert_allclose(np.asarray(packed), np.asarray(ref),
                               atol=1e-4)


def test_rank_update_gridded_matches_matmul(rng):
    # the row-block-gridded trailing update is value-identical to the
    # plain matmul on exactly representable inputs
    a22 = jnp.asarray(
        (rng.integers(-8, 9, (256, 32)) / 16.0).astype(np.float32))
    l21 = jnp.asarray(
        (rng.integers(-8, 9, (256, 16)) / 16.0).astype(np.float32))
    u12 = jnp.asarray(
        (rng.integers(-8, 9, (16, 32)) / 16.0).astype(np.float32))
    out = pk._rank_update(a22, l21, u12)
    ref = np.asarray(a22) - np.asarray(l21) @ np.asarray(u12)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-6)


# -- blocked Givens-chain apply ------------------------------------------

def test_givens_chain_factors_compose_to_dense(rng):
    """The banded block factors, embedded at their anchors and
    multiplied in group order, ARE the dense chain matrix."""
    from slate_tpu.linalg.svd import _givens_chain_matrix
    n, blk = 256, 64
    th = rng.standard_normal(n - 1)
    cs, sn = jnp.asarray(np.cos(th)), jnp.asarray(np.sin(th))
    dense = np.asarray(_givens_chain_matrix(cs, sn, n, jnp.float64))
    facs = np.asarray(pk.givens_chain_factors(cs, sn, n, blk,
                                              jnp.float64))
    G = np.eye(n)
    for j in range(n // blk):
        a0 = pk._chain_anchor(j, n, blk)
        B = np.eye(n)
        B[a0:a0 + 2 * blk, a0:a0 + 2 * blk] = facs[j]
        G = G @ B
    np.testing.assert_allclose(G, dense, atol=1e-12)


def test_givens_chain_apply_matches_dense(rng):
    from slate_tpu.linalg.svd import _givens_chain_matrix
    n = 256
    th = rng.standard_normal(n - 1)
    cs, sn = jnp.asarray(np.cos(th)), jnp.asarray(np.sin(th))
    Z = jnp.asarray(rng.standard_normal((n, n)))
    out = pk.givens_chain_apply(Z, cs, sn)
    assert out is not None
    ref = np.asarray(Z) @ np.asarray(
        _givens_chain_matrix(cs, sn, n, jnp.float64))
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-6)


def test_steqr2_chain_pallas_rec_matches_dense(tune_env, rng):
    """A cached ('steqr2', 'chain') = 'pallas_rec' entry reroutes the
    sweep accumulation through the blocked kernel; on a clustered
    spectrum the eigendecomposition matches the dense-compose run to
    <= 1e-6 (the d/e recurrence is identical — only Z's accumulation
    route changes)."""
    from slate_tpu.linalg.eig import steqr2_qr
    n = 64
    d = jnp.asarray(np.concatenate([np.ones(n // 2),
                                    2.0 * np.ones(n // 2)])
                    + 1e-8 * np.arange(n))
    e = jnp.asarray(1e-3 * np.ones(n - 1))
    w_ref, Z_ref, info_ref = steqr2_qr(d, e)      # cold: dense route
    tcache.get_cache().put("steqr2", np.float64, n,
                           {"chain": "pallas_rec"})
    tcache.get_cache().put("steqr2", np.float64, None,
                           {"chain_blk": 16})
    w_b, Z_b, info_b = steqr2_qr(d, e)            # blocked route
    assert int(info_b) == 0 and int(info_ref) == 0
    np.testing.assert_allclose(np.asarray(w_b), np.asarray(w_ref),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(Z_b), np.asarray(Z_ref),
                               atol=1e-6)
    # and it is a real eigendecomposition of the tridiagonal
    T = np.diag(np.asarray(d)) + np.diag(np.asarray(e), 1) \
        + np.diag(np.asarray(e), -1)
    Zb = np.asarray(Z_b)
    np.testing.assert_allclose(Zb.T @ T @ Zb,
                               np.diag(np.asarray(w_b)), atol=1e-8)


# -- routing arbitration -------------------------------------------------

def test_chain_apply_cold_routes_dense(tune_env):
    """Cold cache: steqr2/bdsqr keep the dense compose (the applier
    selector returns None, meaning the callers' unchanged code path
    runs)."""
    from slate_tpu.linalg.svd import _select_chain_apply
    assert _select_chain_apply("steqr2", 256, 256, jnp.float64) is None
    assert _select_chain_apply("bdsqr", 256, 256, jnp.float64) is None


def test_lu_panel_cold_routes_exactly_as_before(tune_env, rng,
                                                monkeypatch):
    """Acceptance: with the tune cache cold, _lu_panel routes exactly
    as the pre-round-10 chain — native for dtypes the custom call
    takes (CPU: f32/f64), fori for bf16 (pallas_available is False
    off-TPU), and the Pallas entries are never consulted."""
    calls = []
    orig_rec, orig_r1 = pk.lu_panel_rec, pk.lu_panel
    monkeypatch.setattr(pk, "lu_panel_rec",
                        lambda a, **k: calls.append("rec")
                        or orig_rec(a, **k))
    monkeypatch.setattr(pk, "lu_panel",
                        lambda a: calls.append("pallas")
                        or orig_r1(a))
    a32 = jnp.asarray(rng.standard_normal((256, 64))
                      .astype(np.float32))
    lu_, piv = _lu_panel(a32)
    nat, npiv, _ = jax.lax.linalg.lu(a32)
    assert np.array_equal(np.asarray(lu_), np.asarray(nat))
    assert np.array_equal(np.asarray(piv),
                          np.asarray(npiv.astype(jnp.int32)))
    ab = a32.astype(jnp.bfloat16)
    lu_b, piv_b = _lu_panel(ab)
    ref_b, piv_rb = lu_panel_fori(ab)
    assert np.array_equal(np.asarray(lu_b.astype(jnp.float32)),
                          np.asarray(ref_b.astype(jnp.float32)))
    assert np.array_equal(np.asarray(piv_b), np.asarray(piv_rb))
    assert calls == []          # cold cache never touches Pallas
    assert MethodLUPanel.cold_default(256, 64, jnp.float32) \
        is MethodLUPanel.Native
    assert MethodLUPanel.cold_default(256, 64, jnp.bfloat16) \
        is MethodLUPanel.Fori


@pytest.mark.parametrize("m,w,dtype,route", [
    (32768, 256, "float32", "blocked"), (16384, 1024, "float32", "blocked"),
    (8192, 256, "float32", "native"), (8193, 256, "float32", "blocked"),
    (16384, 32768, "float32", "fori"),        # m < w
    (32768, 100, "float32", "fori"),          # no base block divides
    (32768, 256, "bfloat16", "fori"),         # not the native LU's dtype
    (8192, 256, "complex64", "blocked"),      # itemsize 8: 4096 rows
    (4096, 256, "complex64", "native")])
def test_cold_route_reads_height_width_and_dtype(tune_env, monkeypatch,
                                                 m, w, dtype, route):
    """PR 48: above the native LU's height a panel of a dtype it takes
    runs `lu_panel_blocked`, and the fori kernel keeps what is left.
    The native height is the chip's (the CPU's LU has none)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert MethodLUPanel.cold_default(m, w, jnp.dtype(dtype)).value == route
    assert MethodLUPanel.resolve(m, w, jnp.dtype(dtype)).value == route


def test_cached_blocked_route_is_revalidated(tune_env, monkeypatch):
    """A measured 'blocked' entry reroutes the panels it can take and
    no other: its bucket spans widths no base block divides."""
    monkeypatch.setattr(tune_select, "tuned_method",
                        lambda *a, **k: MethodLUPanel.Blocked)
    assert MethodLUPanel.resolve(256, 64, jnp.float32) \
        is MethodLUPanel.Blocked
    assert MethodLUPanel.resolve(256, 100, jnp.float32) \
        is MethodLUPanel.Native
    assert MethodLUPanel.resolve(256, 64, jnp.bfloat16) \
        is MethodLUPanel.Fori
    a = jnp.asarray(np.random.default_rng(5).standard_normal((256, 64)),
                    jnp.float32)
    lu_, piv = _lu_panel(a)
    ref, rpiv = lu_panel_fori(a)
    assert np.array_equal(np.asarray(piv), np.asarray(rpiv))
    assert np.abs(np.asarray(lu_) - np.asarray(ref)).max() < 2e-4


def test_probe_times_the_blocked_route_beside_fori(tune_env):
    """`probe_lu_panel` sees `lu_panel_blocked` under its own label
    where the kernel's gates accept the shape, and not elsewhere."""
    from slate_tpu.tune import probe
    labels = [r["method"] for r in probe.probe_lu_panel(
        256, 64, jnp.float32, reps=1)]
    assert {None, "fori", "blocked"} <= set(labels)
    labels = [r["method"] for r in probe.probe_lu_panel(
        256, 100, jnp.float32, reps=1)]
    assert "fori" in labels and "blocked" not in labels


def test_lu_panel_cached_pallas_rec_reroutes(tune_env, rng,
                                             monkeypatch):
    """A measured method_lu_panel = 'pallas_rec' entry lifts the
    panel onto the recursive kernel (and through _lu_panel, every LU
    consumer)."""
    calls = []
    orig = pk.lu_panel_rec
    monkeypatch.setattr(pk, "lu_panel_rec",
                        lambda a, **k: calls.append("rec")
                        or orig(a, **k))
    m, w = 256, 64
    tcache.get_cache().put("lu_panel", np.float32, m,
                           {"method_lu_panel": "pallas_rec"})
    a = jnp.asarray(rng.standard_normal((m, w)).astype(np.float32))
    packed, piv = _lu_panel(a)
    assert calls == ["rec"]
    perm = np.asarray(jax.lax.linalg.lu_pivots_to_permutation(piv, m))
    pk_np = np.asarray(packed)
    L = np.tril(pk_np, -1)[:, :w] + np.eye(m, w, dtype=np.float32)
    U = np.triu(pk_np[:w])
    np.testing.assert_allclose(np.asarray(a)[perm], L @ U, atol=1e-4)


def test_lu_panel_cached_rec_ineligible_falls_back(tune_env, rng,
                                                   monkeypatch):
    """A cached pallas_rec route on a shape the kernel rejects (w not
    ib*2^k-compatible after clamping... here: unaligned m) must fall
    back to the cold chain, not fail."""
    m, w = 200, 24                      # m % 128 != 0 -> rec rejects
    tcache.get_cache().put("lu_panel", np.float32, m,
                           {"method_lu_panel": "pallas_rec"})
    a = jnp.asarray(rng.standard_normal((m, w)).astype(np.float32))
    packed, piv = _lu_panel(a)
    nat, npiv, _ = jax.lax.linalg.lu(a)   # CPU cold default = native
    assert np.array_equal(np.asarray(packed), np.asarray(nat))


def test_fori_fallback_surfaced_once_per_shape(rng):
    """ISSUE 6 satellite: the silent fori fallback now publishes ONE
    obs instant per (m, w, dtype) with the rejection reason."""
    from slate_tpu import obs
    from slate_tpu.linalg import lu as lu_mod
    lu_mod._FORI_FALLBACK_SEEN.clear()
    a = jnp.asarray(rng.standard_normal((96, 16))
                    .astype(np.float32)).astype(jnp.bfloat16)
    obs.enable()
    try:
        obs.clear()
        _lu_panel(a)
        _lu_panel(a)
        evs = [e for e in obs.bus_events()
               if e.name == "getrf.panel_fori_fallback"]
        assert len(evs) == 1
        assert evs[0].args["reason"] == "platform"   # CPU tier
        assert evs[0].args["m"] == 96
    finally:
        obs.disable()
        obs.clear()


def test_kernel_reject_reasons():
    """The eligibility gates report WHY (ISSUE 6 satellite)."""
    # off-TPU everything is 'platform' first
    assert pk.lu_panel_reject_reason(256, 64, jnp.float32) \
        == "platform"
    assert pk.lu_panel_rec_reject_reason(256, 64, jnp.float32) \
        == "platform"
    # shape diagnostics (platform-independent helpers)
    assert pk._rec_shape_reason(256, 1024, jnp.float32) == "width"
    assert pk._rec_shape_reason(128, 256, jnp.float32) == "aspect"
    assert pk._rec_shape_reason(200, 64, jnp.float32) == "align"
    assert pk._rec_shape_reason(1 << 20, 64, jnp.float32,
                                max_elems=1024) == "height"
    assert pk._rec_shape_reason(256, 64, jnp.float32) is None


def test_frozen_rows_match_kernel_constants():
    """The tune-table rows the kernel registry lints against stay in
    sync with the module constants (drift guard, the
    test_frozen_table_matches_module_constants pattern)."""
    assert tcache.FROZEN[("lu_panel", "ib")] == pk.LU_REC_IB
    assert tcache.FROZEN[("lu_panel", "max_w")] == pk.LU_PANEL_MAX_W
    assert tcache.FROZEN[("steqr2", "chain_blk")] \
        == pk.GIVENS_CHAIN_BLK
    assert tcache.FROZEN[("qr_panel", "max_w")] == pk.QR_PANEL_MAX_W
    assert tcache.FROZEN[("chol_panel", "fused_max")] \
        == pk.CHOL_FUSED_MAX
    assert tcache.FROZEN[("trtri", "fused_max")] == pk.TRTRI_FUSED_MAX
    assert tcache.FROZEN[("steqr2", "chain")] == "dense"
    assert tcache.FROZEN[("bdsqr", "chain")] == "dense"
    # every registered tune op has a FROZEN row (the lint's contract,
    # checked live here, statically in tools/check_instrumented.py)
    frozen_ops = {k[0] for k in tcache.FROZEN}
    assert {t for _, t in pk.KERNEL_REGISTRY.values()} <= frozen_ops
