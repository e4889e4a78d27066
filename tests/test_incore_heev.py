"""The symmetric eigenproblem deployment `heev-geo-n8192-cond1e4`
(PR 33) at sizes the CPU tier holds: `spectral_dc.eigh_dc`, the route
`st.heev` takes above its threshold and the one the cell times, called
directly against the benchmark's plain reference and numpy's f64
`eigh` on the configuration's law, a Wigner matrix and a repeated
eigenvalue; its agenda against the parent's frozen digits, the full-size fallback
of a lopsided split; the route, the
spans and the counters the per-layer metrics read; an unconverged
split reported; ADVICE.md's two findings on the polar iteration; the
kind's `check()` against sound and unsound answers; the readers on
planes made by hand; a rehearsal of the cell `incore-heev`; and PR 43's
choice of a split's shift: the estimate of a block's spectral
distribution against known spectra, the rule walked on the host over
the two cells' multisets, and the counters it publishes."""

import functools
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
from benchmarks.lib import (gen, heevcount, heevgen, heevtrace, plainref,
                            plainref_heev, reduce_trace)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL, CONFIG = "incore-heev", "heev-geo-n8192-cond1e4"
CFG = bench_run.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                       CONFIG + ".json"))
KIND = bench_run.load_module("kinds", "heev")
EPS = float(np.finfo(np.float32).eps)
METRICS = ["heev.launches_per_solve", "idle_share.heev",
           "heev.idle_agenda_share", "heev.solve_roofline",
           "heev.root_busy_share", "heev.polar_iters_per_split",
           "heev.pad_rows_share"]
N, LEAF = 256, 32       # ladder 32, 128, 256: splits at 256, ~128, ~64

#: limits of this file, in the kind's units (n eps_f32, and ||A||_2
#: where the number has a scale). On the CPU at n=256 the program
#: reads eigenvalue_error_max 0.006-0.02, orthogonality 0.55-0.75 and
#: residual_max 0.2-2.3 (the split's dropped coupling block, 10 eps
#: ||H||_F a level), the plain reference 0.002, 0.75 and 0.03; LAPACK's
#: own testers accept 50.
LIMITS = {"eigenvalue_error_max": 0.2, "orthogonality": 3.0,
          "residual_max": 8.0}


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
        return heevgen.geo_symmetric(r, n, CFG["matrix"]["cond"],
                                     CFG["matrix"]["sign_seed"])[0]
    if law == "wigner":             # chip_smoke.py's heev phase
        g = r.standard_normal((n, n)).astype(np.float32)
        return (g + g.T) * np.float32(0.5)
    # `repeated`: one eigenvalue of multiplicity n/4 in a rotated basis
    lam = np.where(np.arange(n) < n // 4, 0.5,
                   np.linspace(-1.0, 1.0, n))
    q, _ = np.linalg.qr(r.standard_normal((n, n)))
    a = ((q * lam) @ q.T).astype(np.float32)
    return (a + a.T) * np.float32(0.5)


def graded(a, w, v):
    w_ref, norm2 = KIND.reference_spectrum(a)
    return KIND.grade(a, np.asarray(w), np.asarray(v), w_ref, norm2)


def heev(a, mb=64):
    res = st.heev(st.HermitianMatrix(st.Uplo.Lower, a, mb=mb))
    return np.asarray(res.values), res.vectors.to_numpy()


# -- the route the cell times, against two references ----------------------

@pytest.mark.parametrize("law,seed", [("geo", 331), ("geo", 332),
                                      ("wigner", 333), ("repeated", 334)])
def test_eigh_dc_is_a_backward_stable_eigendecomposition(law, seed):
    a = matrix(law, seed)
    w, v, ok = spectral_dc.eigh_dc(jnp.asarray(a), leaf=LEAF)
    assert ok is True
    assert w.shape == (N,) and v.shape == (N, N) and w.dtype == jnp.float32
    w, v = np.asarray(w), np.asarray(v)
    assert (np.diff(w) >= 0).all()
    got = graded(a, w, v)
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got
    # numpy's f64 eigh of the same data is the kind's reference spectrum
    w64 = np.linalg.eigh(a.astype(np.float64))[0]
    assert np.abs(w - w64).max() <= LIMITS["eigenvalue_error_max"] \
        * N * EPS * np.abs(w64).max()
    # the plain reference is one too, and the two spectra agree
    wp, vp = plainref_heev.eigh(a)
    ref = graded(a, wp, vp)
    assert all(ref[k] <= LIMITS[k] for k in LIMITS), ref
    assert np.abs(w - wp).max() <= 2 * LIMITS["eigenvalue_error_max"] \
        * N * EPS * np.abs(w64).max()
    # every invariant subspace that is well separated is the same one:
    # the projector onto the eigenvalues below the widest gap
    gap = int(np.argmax(np.diff(w64))) + 1
    p, pp = v[:, :gap] @ v[:, :gap].T, vp[:, :gap] @ vp[:, :gap].T
    assert np.abs(p - pp).max() <= 1e-3


#: the parent's `eigh_dc` (ONE jitted program: a `while_loop` over a
#: `lax.switch` with a branch a bucket; commit 3b316a0) on these
#: matrices with leaves of 32, read in this sandbox: nine of its
#: eigenvalues (indices PARENT_AT) and the sum of |V|. The agenda
#: read the same bytes there.
PARENT_AT = [0, 1, 31, 64, 127, 128, 200, 254, 255]
PARENT = {
    ("geo", 335): ([-0.9645257592201233, -0.8654758334159851,
                    -0.08272697776556015, -0.010945006273686886,
                    -0.0001782285689841956, -0.00017190245853271335,
                    0.019507279619574547, 0.930309534072876,
                    1.000000238418579], 3324.00048828125),
    ("wigner", 336): ([-22.269710540771484, -21.988983154296875,
                       -14.562766075134277, -9.277620315551758,
                       0.030218392610549927, 0.10137736052274704,
                       10.534222602844238, 21.89940071105957,
                       22.570173263549805], 3268.68310546875),
    ("repeated", 337): ([-0.49803924560546875, -0.4901960790157318,
                         -0.2549019753932953, 0.003921582829207182,
                         0.49803924560546875, 0.4999990463256836,
                         0.5686275362968445, 0.9921568632125854,
                         1.0000001192092896], 3261.763427734375),
}


@pytest.mark.parametrize("law,seed", sorted(PARENT))
def test_agenda_reads_the_parents_digits(law, seed, monkeypatch):
    """The agenda's per-bucket programs run the steps the parent's one
    program ran: the same answer to rounding (the same bytes where the
    frozen digits were read). The parent split every block at the
    median of its diagonal, so no bucket estimates here (PR 43): with
    no rung `dc_sign` is that parent's arithmetic."""
    monkeypatch.setattr(spectral_dc, "SHIFT_MIN_LEAVES", 1 << 20)
    spectral_dc._programs.cache_clear()     # traced with no rung
    a = jnp.asarray(matrix(law, seed))
    w, v, ok = spectral_dc.eigh_dc(a, leaf=LEAF)
    assert ok is True
    w_parent, absv_parent = PARENT[law, seed]
    w = np.asarray(w)
    assert np.abs(w[PARENT_AT] - w_parent).max() <= \
        4 * EPS * np.abs(w).max()
    assert abs(float(np.abs(np.asarray(v)).sum()) - absv_parent) <= \
        1e-5 * absv_parent


def test_eigh_dc_refuses_a_tracer():
    a = jnp.asarray(matrix("wigner", 336))
    with pytest.raises(TypeError, match="host agenda"):
        jax.jit(lambda x: spectral_dc.eigh_dc(x, leaf=LEAF))(a)


@pytest.mark.parametrize("size,bucket", [
    (8192, 8192), (5824, 8192), (4709, 8192), (4225, 8192),
    (4224, 4224), (3613, 4224), (2368, 4224), (2177, 4224),
    (2176, 2176), (1153, 2176), (641, 1152), (257, 384)])
def test_a_childs_bucket_and_the_full_size_fallback(size, bucket):
    """ISSUE 33's cell: a root split of 2,368 against 5,824 at n=8192,
    then 4,709 and 3,613. A child over n/1.98 rows outgrows the ladder
    and runs at the full size, in the root's own two heavy programs."""
    ladder = spectral_dc._bucket_ladder(8192, spectral_dc.LEAF)
    assert ladder == [256, 384, 640, 1152, 2176, 4224]
    assert spectral_dc._bucket_of(ladder, 8192, size) == bucket


def test_a_lopsided_split_takes_the_fallback_and_answers(bus):
    """A child that outgrows the ladder at a size the CPU holds: at
    n=1024 the law's root split is a third against two thirds, and the
    top rung is 640."""
    n = 1024
    assert spectral_dc._bucket_ladder(n, LEAF) == [32, 128, 256, 384, 640]
    a = matrix("geo", 352, n=n)
    obs.enable()
    w, v, ok = spectral_dc.eigh_dc(jnp.asarray(a), leaf=LEAF)
    obs.disable()
    sizes = [e.args for e in obs.bus_events(cat="phase")
             if e.name == "heev::split"]
    assert ok is True and sizes[0] == {"bucket": n, "size": n}
    assert any(s["bucket"] == n and s["size"] < n for s in sizes[1:])
    got = graded(a, w, v)
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got


@pytest.mark.parametrize("what", ["diagonal", "one_block_diagonal",
                                  "at_the_leaf"])
def test_blocks_that_need_no_split(what):
    r = np.random.default_rng(338)
    if what == "diagonal":
        a = np.diag(r.standard_normal(N)).astype(np.float32)
    elif what == "one_block_diagonal":
        # a dense block beside a diagonal one: some child is diagonal
        a = np.diag(np.linspace(2.0, 3.0, N)).astype(np.float32)
        g = r.standard_normal((N // 2, N // 2)).astype(np.float32)
        a[:N // 2, :N // 2] = (g + g.T) * np.float32(0.05)
    else:
        a = matrix("wigner", 339, n=LEAF)
    w, v, ok = spectral_dc.eigh_dc(jnp.asarray(a), leaf=LEAF)
    assert ok is True
    got = graded(a, w, v)
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got


# -- where a split takes its shift from (PR 43) ----------------------------

def spectrum_of(law, n, seed=360):
    """A sorted multiset of n eigenvalues in [-1, 1]."""
    r = np.random.default_rng(seed)
    if law == "heev":               # incore-heev's
        return np.sort(heevgen.spectrum(r, n, CFG["matrix"]["cond"],
                                        CFG["matrix"]["sign_seed"]))
    if law == "svd":                # incore-svd's eigen-stage
        from benchmarks.lib import svdgen
        return np.sort(svdgen.spectrum(r, n, CFG["matrix"]["cond"]))
    if law == "uniform":
        return np.linspace(-1.0, 1.0, n)
    if law == "normal":
        x = np.sort(r.standard_normal(n))
        return x / np.abs(x).max()
    # `clusters`: a third of the eigenvalues about -0.5, the rest
    # about 0.5
    return np.sort(np.concatenate([r.normal(-0.5, 0.05, n // 3),
                                   r.normal(0.5, 0.05, n - n // 3)]))


@pytest.mark.parametrize("law,form,err", [
    ("uniform", "diagonal", 0.015), ("uniform", "rotated", 0.015),
    ("normal", "rotated", 0.02), ("clusters", "rotated", 0.03),
    ("svd", "rotated", 0.12), ("heev", "rotated", 0.20)])
def test_the_estimate_reads_the_share_under_a_shift(law, form, err):
    """`_spectral_measure` and `_share_under` on an f32 matrix of known
    spectrum, padded to a bucket as a split's block is: the share of
    the eigenvalues under sigma to 2% of the block where the density
    is smooth, to 3% between two clusters, and to the weight of the
    node that stands for a cluster where hundreds of eigenvalues lie
    closer together than the recurrence resolves (the decaying laws of
    the two cells: 12% and 20%, which is why the rule aims off a
    rung's edge and not at it)."""
    m, B = 480, 512
    lam = spectrum_of(law, m)
    if form == "diagonal":
        a = np.diag(lam)
    else:
        q, _ = np.linalg.qr(np.random.default_rng(361)
                            .standard_normal((m, m)))
        a = (q * lam) @ q.T
    h = np.zeros((B, B), np.float32)
    h[:m, :m] = (a + a.T) / 2
    h = jnp.asarray(h)
    nodes, weights = jax.jit(lambda h: spectral_dc._spectral_measure(
        lambda x: x @ h.T, np.int32(m), B, h.dtype))(h)
    assert nodes.shape == weights.shape == (spectral_dc.SHIFT_PROBES,
                                            spectral_dc.SHIFT_STEPS)
    assert nodes.dtype == weights.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 1.0,
                               atol=1e-4)
    assert (np.diff(np.asarray(nodes), axis=1) >= 0).all()
    # the Ritz values lie inside the spectrum, and reach its ends
    assert lam[0] - 1e-4 <= float(nodes.min()) <= lam[0] + 0.05
    assert lam[-1] - 0.05 <= float(nodes.max()) <= lam[-1] + 1e-4
    sigmas = np.quantile(lam, [0.1, 0.25, 0.4, 0.5, 0.6, 0.75, 0.9])
    got = np.asarray(spectral_dc._share_under(
        nodes, weights, jnp.asarray(sigmas, jnp.float32)))
    true = np.array([(lam < x).mean() for x in sigmas])
    assert np.abs(got - true).max() <= err, (got, true)
    assert (np.diff(got) >= 0).all() and 0 <= got.min() <= got.max() <= 1
    # and the shift it names for a share is where it reads that share
    for share in (0.3, 0.5, 0.7):
        sigma = spectral_dc._shift_for(nodes, weights, share)
        assert abs(float(spectral_dc._share_under(nodes, weights, sigma))
                   - share) <= 1e-3


def test_a_probe_that_exhausts_its_subspace_weighs_nothing_more():
    """Three distinct eigenvalues: the recurrence ends after three
    steps, the later nodes carry no weight, and the estimate still
    reads the three shares."""
    m = B = 256
    lam = np.repeat([-0.5, 0.1, 0.8], [64, 128, 64])
    q, _ = np.linalg.qr(np.random.default_rng(362).standard_normal((m, m)))
    h = jnp.asarray(((q * lam) @ q.T).astype(np.float32))
    nodes, weights = spectral_dc._spectral_measure(
        lambda x: x @ h.T, np.int32(m), B, h.dtype)
    assert np.isfinite(np.asarray(nodes)).all()
    got = np.asarray(spectral_dc._share_under(
        nodes, weights, jnp.asarray([-0.2, 0.5], jnp.float32)))
    assert np.abs(got - [0.25, 0.75]).max() <= 0.03, got


@functools.lru_cache(maxsize=None)
def _rule_at(B):
    """`_estimated_shift` on diag(lam), compiled for the bucket B."""
    return jax.jit(lambda lam, m, rung, sigma_d, key:
                   spectral_dc._estimated_shift(
                       lambda x: x * lam[None, :], m, B, lam.dtype, rung,
                       sigma_d, jnp.abs(lam).max(), key))


def walk(lam, leaf, key=None):
    """[(rows, bucket, rows under the shift, moved or None)] of every
    split of `eigh_dc`'s tree on a matrix of eigenvalues `lam`, oldest
    first, by the solver's own ladder, fallback and rule: in the
    eigenbasis the block is diag(lam) and the probes are as Gaussian
    as in any other, so `_estimated_shift` runs as on the device; the
    median of a block's diagonal is the mean of its eigenvalues
    (`svdgen.mean_split_sizes`'s arithmetic, which the traced sizes
    follow to a few rows). `key` = None walks the parent's tree: every
    split at the mean."""
    n = lam.size
    ladder = spectral_dc._bucket_ladder(n, leaf)
    todo, out = [(np.sort(lam), n)], []
    while todo:
        blk, B = todo.pop(0)
        m = blk.size
        rung = spectral_dc._rung_under(ladder, B, leaf)
        sigma, moved = np.float32(blk.mean()), None
        if rung and key is not None:
            pad = np.zeros(B, np.float32)
            pad[:m] = blk
            key, sub = jax.random.split(key)
            sigma, moved = _rule_at(B)(jnp.asarray(pad), np.int32(m), rung,
                                       sigma, sub)
            moved = bool(moved)
        k = min(max(int((blk < float(sigma)).sum()), 1), m - 1)
        out.append((m, B, k, moved))
        todo += [(c, spectral_dc._bucket_of(ladder, n, c.size))
                 for c in (blk[:k], blk[k:]) if c.size > leaf]
    return out


@pytest.mark.parametrize("law,full_before", [("heev", 3), ("svd", 3)])
def test_the_rule_halves_the_larger_child_once_on_the_cells_laws(
        law, full_before):
    """ISSUE 43: at n=8192 the parent's tree runs three splits in the
    root's bucket on either cell's multiset; with the estimated shift
    it runs two, and on six probe keys (in the eigenbasis a probe key
    is a seed's rotation) the splits that estimate run the same
    sequence of buckets, none of their children within 30 rows of the
    edge of a rung that costs."""
    n, leaf = 8192, spectral_dc.LEAF
    lam = spectrum_of(law, n)
    before = walk(lam, leaf)
    assert sum(B == n for _, B, _, _ in before) == full_before
    if law == "heev":
        assert [m for m, B, _, _ in before if B == n] == [8192, 5824, 4709]
    ladder = spectral_dc._bucket_ladder(n, leaf)
    seen = set()
    for i in range(6):
        tree = walk(lam, leaf, jax.random.PRNGKey(4300 + i))
        est = [(m, B, k) for m, B, k, moved in tree if moved is not None]
        full = [(m, k) for m, B, k in est if B == n]
        assert len(full) == 2 and full[0][0] == n
        seen.add(tuple(B for _, B, _ in est))
        # the second full-size split leaves both children under 4224
        m, k = full[1]
        assert max(k, m - k) <= ladder[-1] - 30, full
        # and no child of an estimating split is within 30 rows of the
        # rungs at 2176 and 4224 (a flip there is 0.15 s and more; one
        # at 1152 is 0.02 s, the configurations' `assumed` say so)
        for m, B, k in est:
            for child in (k, m - k):
                assert min(abs(child - b) for b in ladder[-2:]) >= 30, \
                    (m, B, k)
        assert len(tree) <= len(before) + 4
    assert len(seen) == 1, seen


@pytest.mark.parametrize("law", ["uniform", "normal", "clusters"])
def test_the_rule_leaves_a_spectrum_the_mean_balances(law):
    """The controls: where the median of the diagonal already halves
    the root, the estimate keeps it (its own reading of the share
    under a shift is the coarser of the two), and no law runs more
    splits in the root's bucket than the parent's tree does."""
    n, leaf = 8192, spectral_dc.LEAF
    lam = spectrum_of(law, n)
    parent = walk(lam, leaf)
    before = sum(B == n for _, B, _, _ in parent)
    for i in range(3):
        tree = walk(lam, leaf, jax.random.PRNGKey(4310 + i))
        assert sum(B == n for _, B, _, _ in tree) <= before
        if law != "clusters":
            assert before == 1 and tree[0][3] is False
            assert tree[0][2] == parent[0][2]


@pytest.mark.parametrize("fault", ["nan", "blown_up"])
def test_an_estimate_that_failed_keeps_the_diagonals_median(fault):
    """On the chip the recurrence, compiled alone, read NaN and Ritz
    values of 1e13 where the same code inside `dc_sign` reads the
    spectrum (PERF.md, PR 43): whatever an estimate reads beyond the
    bound of the block's spectral radius, the split keeps the median
    of its diagonal, the parent's shift."""
    m = B = 256
    lam = jnp.asarray(spectrum_of("svd", m), jnp.float32)
    sigma_d = jnp.float32(np.mean(np.asarray(lam)))

    def broken(x):
        y = x * lam[None, :]
        return y * jnp.float32(jnp.nan) if fault == "nan" else 1e6 * y + x

    args = (np.int32(m), B, lam.dtype, np.int32(128), sigma_d,
            jnp.float32(1.0))
    sigma, moved = spectral_dc._estimated_shift(broken, *args)
    assert not bool(moved) and float(sigma) == float(sigma_d)
    sigma, moved = spectral_dc._estimated_shift(
        lambda x: x * lam[None, :], *args)
    assert bool(moved) and np.isfinite(float(sigma))
    assert float(sigma) != float(sigma_d)


@pytest.mark.parametrize("bucket,leaf,rung", [
    (8192, 256, 4224), (4224, 256, 2176), (2176, 256, 1152),
    (1152, 256, None), (640, 256, None), (384, 256, None),
    (256, 32, 128), (128, 32, None)])
def test_a_bucket_estimates_from_eight_leaves_up(bucket, leaf, rung):
    """ISSUE 43: the cells' 8192, 4224 and 2176 and the rehearsal's
    root estimate; under eight leaves `dc_sign` gets no rung and is
    the parent's code, with no estimate compiled into it."""
    n = 8192 if leaf == 256 else 256
    ladder = spectral_dc._bucket_ladder(n, leaf)
    got = spectral_dc._rung_under(ladder, bucket, leaf)
    assert got == rung and (rung is None or got.dtype == np.int32)


@pytest.mark.parametrize("law,seed", [("geo", 363), ("wigner", 364),
                                      ("repeated", 365)])
def test_eigh_dc_with_the_estimate_in_every_large_bucket(bus, law, seed):
    """n=512 with leaves of 32: the root and the buckets of 384 and 256
    rows all estimate (256 rows is eight leaves). Every law stays inside
    `test_eigh_dc_is_a_backward_stable_eigendecomposition`'s limits,
    and the agenda counts what the device says it did."""
    n = 512
    assert spectral_dc._bucket_ladder(n, LEAF) == [32, 128, 256, 384]
    a = matrix(law, seed, n=n)
    obs.enable()
    w, v, ok = spectral_dc.eigh_dc(jnp.asarray(a), leaf=LEAF)
    obs.disable()
    assert ok is True
    got = graded(a, np.asarray(w), np.asarray(v))
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got
    c = obs.snapshot()["metrics"]["counters"]
    sizes = [e.args for e in obs.bus_events(cat="phase")
             if e.name == "heev::split"]
    big = sum(s["bucket"] >= spectral_dc.SHIFT_MIN_LEAVES * LEAF
              for s in sizes)
    assert 1 <= c["heev.shift_estimated"] <= big
    assert c.get("heev.shift_moved", 0) <= c["heev.shift_estimated"]
    assert c["heev.full_size_splits"] == \
        sum(s["bucket"] == n for s in sizes) >= 1


def test_sign_split_says_where_its_shift_came_from():
    """`dc_sign`'s fourth flag: 0 with no rung (and the parent's sign
    matrix, to the bit: a program of its own, with no estimate in
    it), 1 or 2 with one; a rung under the `general` flag estimates
    nothing and shifts nothing, in the program the root split runs."""
    a = jnp.asarray(matrix("geo", 366))
    sign = spectral_dc._programs(N)["sign"]
    no, m = jax.device_put(np.False_), np.int32(N)
    s0, f0 = sign(a, m, no, np.False_, None, l0=None)
    assert "eigh" not in str(jax.make_jaxpr(
        lambda a: spectral_dc.dc_sign(a, m, no, np.False_, None))(a))
    sigma = np.median(np.diag(np.asarray(a)))
    want, _, _ = polar.sign_hermitian(
        a - jnp.float32(sigma) * jnp.eye(N, dtype=jnp.float32))
    assert int(f0[3]) == 0 and np.array_equal(np.asarray(s0),
                                              np.asarray(want))
    s1, f1 = sign(a, m, no, np.False_, np.int32(128), l0=None)
    assert int(f1[3]) in (1, 2)
    # the sign matrix counts the eigenvalues under the shift it took
    w64 = np.linalg.eigvalsh(np.asarray(a, np.float64))
    under = (N - float(np.trace(np.asarray(s1)))) / 2
    if int(f1[3]) == 2:     # 128 of 256 is no room: the larger child
        assert abs(max(under, N - under) / N     # goes over the rung
                   - (0.5 + spectral_dc.SHIFT_ROOM)) <= 0.2
    else:
        assert abs(under - (w64 < sigma).sum()) <= 1
    g0, fg0 = sign(a, m, no, np.True_, None, l0=None)
    g1, fg1 = sign(a, m, no, np.True_, np.int32(128), l0=None)
    assert int(fg0[3]) == int(fg1[3]) == 0
    assert np.array_equal(np.asarray(g0), np.asarray(g1))
    assert np.array_equal(np.asarray(g0),
                          np.asarray(polar.polar_unitary(a)[0]))
    # two programs: with the estimate and without
    assert sign._cache_size() == 2


# -- route, spans, counters ------------------------------------------------

def test_heev_takes_the_route_on_size_and_dtype(bus, tuned):
    a = matrix("geo", 340)
    heev(a)                                 # compiled before the bus
    obs.enable()
    w, v = heev(a)
    got = graded(a, w, v)
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got
    route = [e for e in obs.bus_events(cat="driver")
             if e.name == "heev"][-1].args
    assert route["method"] == "spectral_dc" and route["form"] == "agenda"
    assert route["leaf"] == LEAF and route["buckets"] == "32,128,256"
    seen = Counter(e.name for e in obs.bus_events(cat="phase"))
    assert set(seen) == set(heevtrace.SPANS) - {"heev", "matrix::h2d"}
    c = obs.snapshot()["metrics"]["counters"]
    # the agenda: every split was dispatched once and read once, a
    # split has two children, and every other node is a leaf
    assert c["heev.solves"] == 1
    assert seen["heev::split"] == seen["heev::agenda"] == c["heev.splits"]
    assert seen["heev::leaf"] == c["heev.leaves"] == c["heev.splits"] + 1
    assert c["heev.splits"] >= 7            # three levels at least
    assert "heev.unconverged" not in c
    assert c["heev.split_rows_true"] <= c["heev.split_rows_padded"]
    sizes = [e.args for e in obs.bus_events(cat="phase")
             if e.name == "heev::split"]
    assert sizes[0] == {"bucket": N, "size": N}
    assert all(s["size"] <= s["bucket"] for s in sizes)
    assert sum(s["size"] for s in sizes) == c["heev.split_rows_true"]
    assert 3 <= c["heev.polar_iters"] / c["heev.splits"] <= 14
    # PR 43: the splits in the root's bucket, and those that estimated
    # (the rehearsal's root alone: 256 rows is eight leaves)
    assert c["heev.full_size_splits"] == \
        sum(s["bucket"] == N for s in sizes) >= 1
    assert c["heev.shift_estimated"] == c["heev.full_size_splits"]
    assert c.get("heev.shift_moved", 0) <= c["heev.shift_estimated"]
    # under the threshold, and for a complex matrix, XLA's own eigh
    obs_events.clear()
    heev(matrix("wigner", 341, n=64), mb=32)
    assert [e for e in obs.bus_events(cat="driver")
            if e.name == "heev"][-1].args["method"] == "xla_eigh"


def test_heev_under_a_callers_jit_takes_xla_eigh(bus, tuned):
    """The agenda reads sizes on the host; a tracer cannot be read, so
    `st.heev` under a caller's jit is XLA's one-program eigh."""
    a = matrix("geo", 342)
    obs.enable()
    w = jax.jit(lambda x: st.heev(st.HermitianMatrix(
        st.Uplo.Lower, x, mb=64)).values)(a)
    route = [e for e in obs.bus_events(cat="jit")
             if e.name == "heev"][-1].args
    assert route["method"] == "xla_eigh" and route["form"] == "native"
    w64 = np.linalg.eigvalsh(a.astype(np.float64))
    assert np.abs(np.asarray(w) - w64).max() <= \
        LIMITS["eigenvalue_error_max"] * N * EPS


@pytest.mark.parametrize("on_chip,method", [(False, "xla_eigh"),
                                            (True, "spectral_dc")])
def test_heev_routes_on_the_platform_without_a_tune_entry(
        bus, monkeypatch, on_chip, method):
    """Off the chip the frozen default is XLA's (LAPACK's) eigh at
    every size; only a tune entry written on that backend (the
    rehearsal's, `tuned`) sends it down the chip's route."""
    import slate_tpu.ops.pallas_kernels as pk
    monkeypatch.setattr(spectral_dc, "SPECTRAL_DC_MIN_N", 128)
    monkeypatch.setattr(pk, "_on_tpu", lambda: on_chip)
    a = matrix("wigner", 353)
    obs.enable()
    w, v = heev(a)
    assert [e for e in obs.bus_events(cat="driver")
            if e.name == "heev"][-1].args["method"] == method
    got = graded(a, w, v)
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got


def test_heev_sites_are_one_branch_when_off(bus, tuned, monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(obs_events, "_annotation", Counting)
    heev(matrix("geo", 343))
    assert made == [] and obs.bus_events() == []
    assert obs.snapshot()["metrics"]["counters"] == {}


def test_heev_spans_reach_the_host_plane(bus, tuned, host_plane):
    a = matrix("geo", 344)
    heev(a)
    obs.enable()
    seen = host_plane(lambda: heev(a), heevtrace.SPANS)
    by_name = {}
    for ev in seen:
        by_name.setdefault(ev[2], []).append(ev)
    assert set(by_name) == set(heevtrace.SPANS)
    root = by_name["heev"][0]
    assert root[3]["form"] == "agenda"
    for child in heevtrace.SPANS:
        if child not in ("heev", "matrix::h2d"):
            for ev in by_name[child]:
                assert root[0] <= ev[0] <= ev[1] <= root[1], child
    assert max(e[1] for e in by_name["matrix::h2d"]) <= root[0]


# -- ADVICE.md's two findings, on the configuration's own input ------------

def test_an_unconverged_split_is_always_reported(bus, tuned, monkeypatch):
    """ADVICE r5 (`spectral_dc.py:128`): the polar's converged flag
    was dropped, then read only under SLATE_TPU_CHECK_POLAR=1. The
    agenda reads it with each split's sizes, so `st.heev` warns with
    no switch set."""
    def two_steps(h, l0=None, general=False):
        u, k, conv = polar.polar_unitary(h, l0=l0, max_iterations=2)
        return 0.5 * (u + u.conj().T), k, conv

    monkeypatch.setattr(spectral_dc, "sign_hermitian", two_steps)
    spectral_dc._programs.cache_clear()     # traced with the patch
    try:
        a = matrix("geo", 345)
        obs.enable()
        with pytest.warns(UserWarning, match="polar"):
            heev(a)
        c = obs.snapshot()["metrics"]["counters"]
        assert c["heev.unconverged"] >= 1
        assert spectral_dc.eigh_dc(jnp.asarray(a), leaf=LEAF)[2] is False
    finally:
        spectral_dc._programs.cache_clear()
    assert "SLATE_TPU_CHECK_POLAR" not in open(
        os.path.join(ROOT, "slate_tpu", "linalg", "eig.py")).read()


@pytest.mark.parametrize("law,seed", [("geo", 346), ("geo", 347),
                                      ("repeated", 348)])
def test_polar_converges_on_a_cluster_at_the_split_point(law, seed):
    """ADVICE r5 (`polar.py:153`, `:212`): half the configuration's
    eigenvalues lie within 1e-2 of the root's split point, and a
    repeated eigenvalue can sit on it: the sign iteration has to
    converge inside its cap and return a sign matrix."""
    a = matrix(law, seed)
    sigma = np.median(np.diag(a))
    s, iters, conv = polar.sign_hermitian(
        jnp.asarray(a - sigma * np.eye(N, dtype=np.float32)))
    assert bool(conv) and int(iters) <= 14
    s = np.asarray(s, np.float64)
    assert np.abs(s @ s - np.eye(N)).max() <= 2e-4
    w64 = np.linalg.eigvalsh(a.astype(np.float64))
    # its trace counts the eigenvalues on either side of sigma, up to
    # those within rounding of it
    near = int((np.abs(w64 - sigma) <= 50 * EPS).sum())
    assert abs((N - np.trace(s)) / 2 - (w64 < sigma).sum()) <= near + 0.01


@pytest.mark.parametrize("spread", [1.0, 1.3, 4.0])
def test_sigma_estimate_is_reliable_only_where_it_is_a_bound(spread):
    """ADVICE r5 (`polar.py:153`): the estimator's `reliable` flag
    gates on the power iteration having converged, not on the
    estimate's size alone. Where a cluster of small singular values
    leaves it unconverged the flag is off; where it is on, the
    deflated estimate 0.7 sig is at most sigma_min."""
    n, c = 96, jnp.float32(3e5)
    r = np.random.default_rng(349)
    sv = np.ones(n)
    sv[:24] = 1e-3 * spread ** r.random(24)
    q1, _ = np.linalg.qr(r.standard_normal((n, n)))
    q2, _ = np.linalg.qr(r.standard_normal((n, n)))
    u = jnp.asarray(((q1 * sv) @ q2.T).astype(np.float32))
    a, b = 2 * jnp.sqrt(1 + c) - 1, None
    b = (a - 1) ** 2 / 4
    for it in range(4):
        _, rfac = polar._chol_halley(u, a, b, c)
        sig, reliable = polar._sigma_min_estimate(rfac, c, it)
        if bool(reliable):
            assert 0.7 * float(sig) <= sv.min() * 1.02, (it, float(sig))


# -- the comparison that decides `correct` ---------------------------------

def rehearsal_cell(seed):
    cfg = {**CFG, **CFG["rehearsal"]}
    return cfg, KIND.Cell(cfg, {"warm_solves": 1}, seed)


@pytest.mark.parametrize("answer", ["sound", "program", "high",
                                    "bf16_vectors", "bf16_values",
                                    "not_ascending",
                                    "nan", "wrong_shape"])
def test_check_refuses_what_the_deployment_refuses(answer):
    cfg, cell = rehearsal_cell(3300000007)
    a = cell.sys.a
    w, v = plainref_heev.eigh(a)
    if answer == "program":
        w, v, _ = spectral_dc.eigh_dc(jnp.asarray(a), leaf=LEAF)
        w, v = np.asarray(w), np.asarray(v)
    elif answer == "high":
        w, v = plainref_heev.eigh(a, plainref.matmul_bf16x3)
    elif answer == "bf16_vectors":
        v = v.astype(plainref.BF16).astype(np.float32)
    elif answer == "bf16_values":
        w = np.sort(w.astype(plainref.BF16).astype(np.float32))
    elif answer == "not_ascending":
        w = w[::-1].copy()
    elif answer == "nan":
        v = np.full_like(v, np.nan)
    elif answer == "wrong_shape":
        v = v[:, :-1]
    cell.answers, cell.walls = [(w, v)] * 2, [0.1]
    got = cell.check()
    sound = answer in ("sound", "program")
    assert got["correct"] is sound, got
    assert got["failed"] == (0 if sound else 1)
    assert got["attempted"] == 1
    assert got["distinct_answers"] == (answer != "wrong_shape")
    assert [c[0] for c in got["compared"]] == list(KIND.NUMBERS)


def test_answers_of_the_same_bytes_are_held_once():
    _, cell = rehearsal_cell(3300000008)
    w, v = np.linalg.eigh(cell.sys.a)

    class V:
        def __init__(self, x):
            self.x = x

        def to_numpy(self):
            return self.x.copy()

    first = cell.sys.to_host(w, V(v), None)
    assert cell.sys.to_host(w.copy(), V(v), None) is first
    assert cell.sys.to_host(w, V(-v), None) is not first
    assert len(cell.sys.held) == 2


def test_generator_states_its_spectrum():
    n, draw = 512, CFG["matrix"]["sign_seed"]
    a, lam = heevgen.geo_symmetric(gen.rng(350, "solve"), n, 1e4, draw)
    assert a.dtype == np.float32 and (a == a.T).all()
    w = np.linalg.eigvalsh(a.astype(np.float64))
    assert np.abs(w - lam).max() <= 1e-6
    assert np.abs(lam).max() == 1.0
    assert np.abs(lam).min() == pytest.approx(1e-4)
    ratios = np.sort(np.abs(lam))[1:] / np.sort(np.abs(lam))[:-1]
    np.testing.assert_allclose(ratios, ratios[0], rtol=1e-9)
    # the signs are independent (matgen) and the configuration's one
    # draw: every seed has the same eigenvalues, another draw others
    signs = np.sign(lam[np.argsort(-np.abs(lam))])
    assert abs((signs[1:] * signs[:-1]).sum()) <= 4 * np.sqrt(n)
    assert 0.4 * n < (lam < 0).sum() < 0.6 * n
    _, other = heevgen.geo_symmetric(gen.rng(351, "solve"), n, 1e4, draw)
    assert np.array_equal(other, lam)
    _, redrawn = heevgen.geo_symmetric(gen.rng(350, "solve"), n, 1e4,
                                       draw + 1)
    assert not np.array_equal(redrawn, lam)
    # dense, with no heavy entry: U's are all about 1/sqrt(n)
    assert np.abs(a).max() <= 60 / n and (a != 0).mean() > 0.99
    # the split point of the solver's root sits at the spectrum's mean
    assert np.abs(np.median(np.diag(a)) - lam.mean()) <= 2e-3
    # the transform is the Hadamard matrix's
    from scipy.linalg import hadamard
    x = gen.rng(351, "solve").standard_normal((64, 5))
    np.testing.assert_allclose(heevgen.fwht0(x.copy(), cols=2),
                               hadamard(64) @ x, atol=1e-12)
    with pytest.raises(ValueError):
        heevgen.geo_symmetric(gen.rng(1, "solve"), 96, 1e4, draw)


def test_compile_probe_asks_the_program(monkeypatch):
    KIND.compile_probe()
    # the parent's form: every bucket under one jit
    monkeypatch.setattr(spectral_dc, "eigh_dc",
                        jax.jit(lambda h: (h, h, True)))
    with pytest.raises(SystemExit) as exc:
        KIND.compile_probe()
    assert exc.value.code == 4


# -- the readers -----------------------------------------------------------

def _run(trace, **kw):
    return {"workload": CELL, "trace": trace, "counters": {},
            "histograms": {}, "spans": {}, "device_kind": "TPU v5 lite",
            "config": CFG, "records": {"solves": 9, "slice_solves": 1},
            **kw}


def test_heev_slice_counts_the_upload_and_the_agenda():
    # the hand-over of A at 0, the first operation at 1000; busy
    # [1000,1400] and [1500,1900]; `heev` over [100,1600], one agenda
    # read over [1390,1480], 10 of it before the device fell idle
    sl = heevtrace.HeevSlice(
        [[(1000, 1400), (1500, 1900)]],
        [(0, 50, "matrix::h2d"), (100, 1600, "heev"),
         (110, 1390, "heev::split"), (1390, 1480, "heev::agenda")])
    assert sl.idle == [[[0, 1000], [1400, 1500]]]
    assert sl.idle_ns == 1100
    assert sl.cover(("heev::agenda",)) == pytest.approx(100 * 80 / 1100)
    assert sl.cover(("heev",)) == pytest.approx(100 * 1000 / 1100)


def test_modules_by_program_name():
    class E:
        def __init__(self, name, dur):
            self.name, self.duration_ns = name, dur

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
              [E("jit_dc_sign_8192(7)", 2e9), E("jit_dc_sign_8192(9)", 1e9),
               E("jit_dc_basis_4224(3)", 5e8), E("jit_dc_leaf_256(1)", 1e8)]),
            L(reduce_trace.OPS, [E("%fusion.1 = f32[8]{0} fusion()", 9e9)])]),
        P("/host:CPU", [L("main", [E("jit_dc_sign_8192(7)", 4e9)])])]
    assert heevtrace.modules(pd) == {
        "jit_dc_sign_8192": [2, 3.0], "jit_dc_basis_4224": [1, 0.5],
        "jit_dc_leaf_256": [1, 0.1]}


def test_heev_metrics_by_hand():
    flops, nbytes = heevcount.heev(8192)
    assert flops == 9 * 8192 ** 3 and nbytes == 4 * (2 * 8192 ** 2 + 8192)
    run = _run({"busy_s": 5.0, "window_s": 5.5, "module_launches": 400})
    assert heevtrace.solve_roofline(run) == \
        pytest.approx(100 * (flops / 197e12) / 5.0)
    assert 0 < heevtrace.solve_roofline(run) < 5
    run["counters"] = {"heev.splits": 60, "heev.polar_iters": 390,
                       "heev.split_rows_true": 30000,
                       "heev.split_rows_padded": 40000}
    assert heevtrace.polar_iters_per_split(run) == 6.5
    assert heevtrace.pad_rows_share(run) == 25.0


@pytest.mark.parametrize("solves", ["heev.solves", "svd.solves"])
def test_full_size_splits_by_hand(solves):
    """PR 43's metric, from its own file: the window's splits in the
    root's bucket over its solves, under either driver's count; left
    out where the program publishes no such counter (the parent)."""
    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == "heev.full_size_splits")
    assert entry == {
        "name": "heev.full_size_splits", "unit": "count",
        "better": "lower", "source": "program_counter", "layer": "kernels",
        "moves": "stream_solve_s",
        "workloads": ["incore-heev", "incore-svd"]}
    assert BENCH["per_layer"][-1] is entry
    compute = bench_run.load_module("layer_metrics",
                                    "heev.full_size_splits").compute
    run = _run(None)
    assert compute(run) is None
    run["counters"] = {solves: 9, "heev.splits": 414}
    assert compute(run) is None             # the parent's counters
    run["counters"]["heev.full_size_splits"] = 18
    assert compute(run) == 2.0
    run["counters"] = {"heev.full_size_splits": 18}
    assert compute(run) is None


@pytest.mark.parametrize("name", METRICS)
def test_heev_metric_is_found_and_silent_without_a_trace(name):
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
    assert entry["reduced"] == CFG["reduced"] == ["n"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "repeat", 1) and len(cell["why"]) <= 200
    # appended behind PR 31's, and PR 39's appended behind them
    assert BENCH["workloads"][5] is cell and BENCH["configs"][5] is entry
    assert (CFG["n"], CFG["mb"], CFG["dtype"], CFG["vectors"]) == \
        (8192, 512, "float32", "all")
    assert CFG["matrix"]["cond"] == 1e4 and CFG["routine"] == "heev"
    assert CFG["matrix"]["sign_seed"] == 10
    assert "tune" not in CFG and set(CFG["tolerance"]) == \
        set(KIND.NUMBERS) | {"reason"}
    for key in ("source", "reduced_why", "assumed", "deployment",
                "guarantee", "tolerance", "rehearsal"):
        assert CFG[key], key
    # the cell's own sizes take the route with the frozen threshold
    assert CFG["n"] > tune_cache.FROZEN[("heev", "spectral_dc_min_n")]
    assert spectral_dc._bucket_ladder(CFG["n"], spectral_dc.LEAF) == \
        [256, 384, 640, 1152, 2176, 4224]


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
         "--workload", CELL, "--seed", "3300000019", "--seconds", "1.5",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert window["compiles_in_window"]["programs"] == 0
    assert 3 <= last["metrics"]["heev.polar_iters_per_split"]["value"] <= 14
    assert 0 <= last["metrics"]["heev.pad_rows_share"]["value"] < 100
    assert 1 <= last["metrics"]["heev.full_size_splits"]["value"] <= 4
    xplane = next(ln["xplane"] for ln in lines if ln.get("phase") == "trace")
    seen = {e[2]: e for e in heevtrace.host_events(
        reduce_trace.load(xplane))}
    assert set(heevtrace.SPANS) <= set(seen), \
        sorted(set(heevtrace.SPANS) - set(seen))
    assert seen["heev"][3]["method"] == "spectral_dc" \
        and seen["heev"][3]["form"] == "agenda"
