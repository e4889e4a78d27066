#!/usr/bin/env python
"""Benchmark driver: streams one JSON line per measurement, ends with ONE
headline JSON line.

Headline: f32 Cholesky (potrf) GFLOP/s on the attached TPU chip at
n=16384, the reference's ex07 north-star config on one chip
(BASELINE.md; TPU has no f64 MXU path, so f32 is the native headline
precision — the reference's own mixed-precision solvers deliver
d-accuracy, see slate_tpu.linalg.lu.gesv_mixed). n=16384 leads because
the reference's headline regime is large matrices (BASELINE.json north
star is n=131072) and per-kernel overheads amortize with n (measured
potrf/gemm: 0.39 at 4096, 0.56 at 8192, ~0.70 at 16384); n=8192 and
n=4096 follow for round-over-round comparability with earlier rounds.
The BASELINE.md routines (gemm/potrf/getrf/geqrf) are all measured at
the two largest sizes; the lookahead pair runs at n=8192 only (the
Tiled potrf at 16384 is a long compile for a number that tracks the
8192 one) and the smallest size gets a reduced set.

vs_baseline: potrf GFLOP/s divided by measured big-gemm GFLOP/s on the
same chip in the same process — the fraction of the chip's attainable
matmul rate the full factorization sustains (self-calibrating analogue
of "within X% of cuBLAS" from BASELINE.json). The ratio is measured
same-process because the chip's absolute f32 rate drifts 20-40% between
processes (thermal/clock), while same-process ratios are stable.

Loss-proofing (the round-2 run died mid-flight and took every completed
measurement with it): every routine×size measurement's JSON line is
printed (flushed) the moment it exists, so a run that dies still
leaves everything measured so far on stdout. A sweep that dies makes
the run exit non-zero; there is no backend probe and no CPU fallback —
the run uses the backend jax finds and fails where it finds none.

Flags (combinable with the default sweep unless noted): ``--micro``
``--tune`` ``--ooc`` ``--serve`` ``--serve-daemon`` ``--shard``
``--faults`` ``--lint``
run their own suites; ``--obs`` enables the observability bus for the
whole run, ships the metrics/driver/analysis snapshot in the headline
extras, AND runs the **regression leg** (ISSUE 14): the current run's
per-driver walls, counters, and shared numeric extras are compared
against the most recent ``BENCH_r*.json`` in the checkout and the
per-metric deltas land in ``extras["obs_regression"]`` — the BENCH
trajectory read back instead of write-only. ``--shard`` additionally
gates on the flight-recorder attribution leg (>= 95% of the measured
sharded-potrf wall attributed to named ledger phases).

Timing notes: each measurement chains K dependency-linked iterations
inside one jit and uses the two-point slope (T(k2)-T(k1))/(k2-k1),
which cancels the dispatch floor and one-off costs (the floor this was
sized for is not measured on the current machine; chip_smoke.py prints
the current one). Matrices are generated ON DEVICE (jax.random) and
are passed as jit arguments, never closure-captured (a captured
concrete array becomes an HLO constant shipped with every compile).
Both sides use Precision.HIGHEST so vs_baseline compares f32-accurate
math to f32-accurate math.
"""

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from slate_tpu.utils import compile_cache  # noqa: E402


def emit(obj):
    """Print one JSON line immediately — never buffer a measurement."""
    print(json.dumps(obj), flush=True)


def _slope(f2, x0, aux, est_hint, reps=5, target=0.6):
    """Per-iteration time of f2, robust to a large and drifting
    dispatch floor (not measured on the current machine): chain k
    dependency-linked iterations inside
    one jit (k is a *runtime* trip count — one compile serves every k)
    and take the two-point slope with k2 sized so the signal
    (k2-k1)*t >= `target` seconds, far above the floor's jitter.
    `est_hint`: rough seconds/iter used only to pick k before the
    measured estimate refines it."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(x, aux, k):
        return jax.lax.fori_loop(0, k, lambda i, x: f2(x, aux), x)

    def once(k, r=reps):
        float(jnp.ravel(run(x0, aux, k))[0])     # compile + warm
        best = float("inf")
        for _ in range(r):
            t0 = time.perf_counter()
            out = run(x0, aux, k)
            float(jnp.ravel(out)[0])        # scalar fetch forces sync
            best = min(best, time.perf_counter() - t0)
        return best

    # refine the estimate with a cheap two-point probe; clamp the probe
    # trip counts so a small-n/slow-backend run (CPU smoke test) cannot
    # explode into thousands of chained iterations
    ka = min(max(2, int(0.05 / est_hint)), 32)
    kb = ka + min(max(4, int(0.15 / est_hint)), 64)
    est = max((once(kb, 3) - once(ka, 3)) / (kb - ka), est_hint / 10)
    k2 = min(max(int(target / est), 8), 512)
    k1 = max(2, k2 // 8)
    t = (once(k2) - once(k1)) / (k2 - k1)
    return max(t, 1e-9)


def bench_size(st, tl, n, with_geqrf, results, budget_scale=1.0,
               with_lookahead=False, with_getrf=True,
               headline_best_of=1):
    """Measure gemm/potrf[/getrf][/geqrf][/lookahead pair] at size n.
    Each routine is individually guarded; successes are emitted
    immediately and stored in `results` under '<routine>_n<n>'.
    headline_best_of > 1 repeats the potrf measurement that many
    times and keeps the best — the headline metric was swinging +-9%
    on run noise between rounds (VERDICT r5 weak #4), and a best-of-3
    slope is stable where a single slope is not."""
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.enums import Diag, MatrixType, Op, Uplo
    HI = jax.lax.Precision.HIGHEST

    @jax.jit
    def gen():
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (n, n), jnp.float32)
        spd = jnp.matmul(x, x.T, precision=HI) / n \
            + 4.0 * jnp.eye(n, dtype=jnp.float32)
        return x, spd

    xj, spd_j = gen()
    xj.block_until_ready()

    scale = (n / 4096.0) ** 3
    nb = 512

    def record(name, gflops):
        key = "%s_n%d" % (name, n)
        results[key] = round(gflops, 1)
        emit({"metric": "%s_f32_gflops_n%d" % (name, n),
              "value": round(gflops, 1), "unit": "GFLOP/s"})

    def guarded(name, fn):
        failed = False
        try:
            fn()
        except Exception as e:
            results["%s_n%d_error" % (name, n)] = str(e)[:160]
            emit({"metric": "%s_f32_gflops_n%d" % (name, n),
                  "error": str(e)[:160]})
            failed = True
        if failed:
            # a failed attempt (esp. OOM) pins device buffers via the
            # exception's traceback frames; those frames are only
            # released once the except block EXITS, so the collect
            # must happen here, after it
            import gc
            gc.collect()

    def m_gemm():
        t = _slope(lambda c, g: jnp.matmul(g, c, precision=HI)
                   * (1.0 / n),
                   xj, xj, est_hint=5e-3 * scale,
                   target=0.6 * budget_scale)
        record("gemm", 2.0 * n ** 3 / t / 1e9)

    H = tl.TiledMatrix(data=spd_j, m=n, n=n, mb=nb, nb=nb,
                       mtype=MatrixType.Hermitian, uplo=Uplo.Lower,
                       op=Op.NoTrans, diag=Diag.NonUnit)

    def m_potrf():
        def potrf_f(d, aux):
            L = st.potrf(dataclasses.replace(H, data=d))
            return aux + L.data * 1e-30
        # best-of-N independent slope measurements for the headline
        # size (module doc of bench_size); each repeat re-enters the
        # same jitted executable, so repeats cost steady-state time
        # only, not recompiles
        t = min(_slope(potrf_f, spd_j, spd_j, est_hint=2e-3 * scale,
                       target=0.6 * budget_scale)
                for _ in range(max(headline_best_of, 1)))
        record("potrf", (n ** 3 / 3.0) / t / 1e9)

    G = tl.TiledMatrix(data=xj, m=n, n=n, mb=nb, nb=nb,
                       mtype=MatrixType.General, uplo=Uplo.General,
                       op=Op.NoTrans, diag=Diag.NonUnit)

    def m_getrf():
        def getrf_f(d, aux):
            F = st.getrf(dataclasses.replace(G, data=d))
            return aux + F.LU.data * 1e-30
        t = _slope(getrf_f, xj, xj, est_hint=3e-3 * scale * scale,
                   target=0.6 * budget_scale)
        record("getrf", (2.0 * n ** 3 / 3.0) / t / 1e9)

    def m_getrf_fused():
        # XLA's native LU, the baseline the default (Tiled carry) path
        # is chosen over — measured so the policy stays data-backed
        from slate_tpu.core.methods import MethodFactor
        from slate_tpu.core.options import Option
        fo = {Option.MethodFactor: MethodFactor.Fused}

        def getrf_f(d, aux):
            F = st.getrf(dataclasses.replace(G, data=d), fo)
            return aux + F.LU.data * 1e-30
        t = _slope(getrf_f, xj, xj, est_hint=3e-3 * scale * scale,
                   reps=3, target=0.4 * budget_scale)
        record("getrf_fused", (2.0 * n ** 3 / 3.0) / t / 1e9)

    def m_lookahead():
        # lookahead evidence (VERDICT r2 item 2): the Tiled potrf with
        # the software-pipelined loop (Option.Lookahead=1) vs the plain
        # right-looking order, same method/path otherwise
        from slate_tpu.core.methods import MethodFactor
        from slate_tpu.core.options import Option
        for la in (0, 1):
            opts = {Option.MethodFactor: MethodFactor.Tiled,
                    Option.Lookahead: la}

            def f(d, aux, opts=opts):
                L = st.potrf(dataclasses.replace(H, data=d), opts)
                return aux + L.data * 1e-30

            t = _slope(f, spd_j, spd_j, est_hint=4e-3 * scale, reps=3,
                       target=0.4 * budget_scale)
            record("potrf_tiled_la%d" % la, (n ** 3 / 3.0) / t / 1e9)

    def m_geqrf():
        def geqrf_f(d, aux):
            F = st.geqrf(dataclasses.replace(G, data=d))
            return aux + F.QR.data * 1e-30
        # geqrf's many Pallas panel compiles are the flakiest part of
        # the run — reps=3 keeps it inside the time budget
        t = _slope(geqrf_f, xj, xj, est_hint=2e-2 * scale, reps=3,
                   target=0.5 * budget_scale)
        record("geqrf", (4.0 * n ** 3 / 3.0) / t / 1e9)
        # fused alternative (ONE whole-matrix native geqrf, packed
        # contract): measured so the blocked-vs-fused default can be
        # chosen from hardware data
        from slate_tpu.core.methods import MethodFactor
        from slate_tpu.core.options import Option
        fopts = {Option.MethodFactor: MethodFactor.Fused}

        def geqrf_fused_f(d, aux):
            F = st.geqrf(dataclasses.replace(G, data=d), fopts)
            return aux + F.QR.data * 1e-30

        t = _slope(geqrf_fused_f, xj, xj, est_hint=1e-2 * scale,
                   reps=3, target=0.4 * budget_scale)
        record("geqrf_fused", (4.0 * n ** 3 / 3.0) / t / 1e9)

    guarded("gemm", m_gemm)
    guarded("potrf", m_potrf)
    if with_getrf:
        guarded("getrf", m_getrf)
        guarded("getrf_fused", m_getrf_fused)
    if with_geqrf:
        guarded("geqrf", m_geqrf)
    if with_lookahead:
        guarded("potrf_tiled_la", m_lookahead)
    import gc
    gc.collect()


def bench_large(st, tl, n, results, budget_scale=0.5):
    """LU/QR entries at n beyond the native-LU compile limit (the
    round-3 gap: no getrf/geqrf number at the 16384 headline).
    Routes that work there: the Tiled carry LU whose tall panels fall
    back to the masked fori_loop kernel (true partial pivoting, slow
    but real), the CALU tournament LU whose chunked native rounds
    sidestep the height limit at matmul-ish rate (getrf_tntpiv), and
    the blocked carry geqrf with the n-scaled block size (19 TF/s;
    the 64-step nb=256 unroll RESOURCE_EXHAUSTS here, which is why
    Auto widens nb with n — scan form kept as the guarded
    fallback)."""
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.enums import Diag, MatrixType, Op, Uplo
    from slate_tpu.core.methods import MethodLU
    from slate_tpu.core.options import Option

    @jax.jit
    def gen():
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (n, n), jnp.float32)
        return x + 0.05 * n * jnp.eye(n, dtype=jnp.float32)

    xj = gen()
    xj.block_until_ready()
    G = tl.TiledMatrix(data=xj, m=n, n=n, mb=512, nb=512,
                       mtype=MatrixType.General, uplo=Uplo.General,
                       op=Op.NoTrans, diag=Diag.NonUnit)

    def record(name, gflops):
        results["%s_n%d" % (name, n)] = round(gflops, 1)
        emit({"metric": "%s_f32_gflops_n%d" % (name, n),
              "value": round(gflops, 1), "unit": "GFLOP/s"})

    def guarded(name, fn):
        try:
            fn()
        except Exception as e:
            results["%s_n%d_error" % (name, n)] = str(e)[:160]
            emit({"metric": "%s_f32_gflops_n%d" % (name, n),
                  "error": str(e)[:160]})
            import gc
            gc.collect()

    def m_getrf_tntpiv():
        opts = {Option.MethodLU: MethodLU.CALU}

        def f(d, aux):
            F = st.getrf_tntpiv(dataclasses.replace(G, data=d), opts)
            return aux + F.LU.data * 1e-30
        t = _slope(f, xj, xj, est_hint=3e-1, reps=3,
                   target=0.6 * budget_scale)
        record("getrf_tntpiv", (2.0 * n ** 3 / 3.0) / t / 1e9)

    def m_getrf_tiled():
        def f(d, aux):
            F = st.getrf(dataclasses.replace(G, data=d))
            return aux + F.LU.data * 1e-30
        t = _slope(f, xj, xj, est_hint=1.5, reps=3,
                   target=0.5 * budget_scale)
        record("getrf", (2.0 * n ** 3 / 3.0) / t / 1e9)

    def m_geqrf(opts=None):
        def f(d, aux):
            F = st.geqrf(dataclasses.replace(G, data=d), opts)
            return aux + F.QR.data * 1e-30
        t = _slope(f, xj, xj, est_hint=4e-1, reps=3,
                   target=0.5 * budget_scale)
        record("geqrf", (4.0 * n ** 3 / 3.0) / t / 1e9)

    def m_geqrf_routed():
        # Auto routes to the blocked carry form with the n-scaled nb
        # (1024 at 16384: 19.0 TF/s measured round 4). If a smaller
        # HBM ever RESOURCE_EXHAUSTs it, fall back to the fixed-shape
        # scan form (BlockSize=128 pushes the step count past the
        # scan threshold; bounded live intermediates, ~4 TF/s). Only
        # OOM reroutes — any other failure must surface as a geqrf
        # error, not be silently remeasured as the scan. The retry is
        # best-effort: a post-OOM process can keep failing allocations
        # (PERF.md round-4b), so the fallback emits a marker line and
        # the guarded() wrapper still records a total loss honestly.
        try:
            m_geqrf()
        except Exception as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            import gc
            gc.collect()
            # distinct key: consumers keyed on 'metric' must not see
            # two records for geqrf_f32_gflops_n%d (ADVICE r4)
            emit({"metric": "geqrf_f32_fallback_n%d" % n,
                  "note": "carry form RESOURCE_EXHAUSTED; the "
                          "geqrf_f32_gflops value below is the "
                          "scan-form fallback in the same (possibly "
                          "poisoned) process"})
            m_geqrf({Option.BlockSize: 128})

    guarded("getrf_tntpiv", m_getrf_tntpiv)
    guarded("getrf", m_getrf_tiled)
    guarded("geqrf", m_geqrf_routed)
    import gc
    gc.collect()


def bench_solvers(st, tl, full_n, results, budget_scale=0.5):
    """Solver-level entries (BASELINE.md configs ex06-ex11; reference
    test/ sweeps every driver): posv + gesv at full_n with 64 rhs,
    tall-skinny gels, heev and svd with vectors at 4096. GFLOP/s uses
    the NOMINAL classical counts (LAPACK convention: posv n^3/3 +
    2n^2 r, gesv 2n^3/3 + 2n^2 r, gels 2n^2(m - n/3), heev 4/3 n^3,
    svd 8/3 n^3) so ratios against gemm read as fractions of chip
    rate, not algorithm-internal flops."""
    import jax
    import jax.numpy as jnp
    from slate_tpu.core.enums import Diag, MatrixType, Op, Uplo
    HI = jax.lax.Precision.HIGHEST
    nrhs = 64

    def record(name, gflops):
        results[name] = round(gflops, 1)
        emit({"metric": "%s_f32_gflops" % name,
              "value": round(gflops, 1), "unit": "GFLOP/s"})

    def guarded(name, fn):
        try:
            fn()
        except Exception as e:
            results["%s_error" % name] = str(e)[:160]
            emit({"metric": name, "error": str(e)[:160]})
            import gc
            gc.collect()

    def mk(data, mtype=MatrixType.General, uplo=Uplo.General, nb=512):
        return tl.TiledMatrix(data=data, m=data.shape[0],
                              n=data.shape[1], mb=nb, nb=nb,
                              mtype=mtype, uplo=uplo, op=Op.NoTrans,
                              diag=Diag.NonUnit)

    n = full_n
    scale = (n / 4096.0) ** 3

    @jax.jit
    def gen():
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (n, n), jnp.float32)
        spd = jnp.matmul(x, x.T, precision=HI) / n \
            + 4.0 * jnp.eye(n, dtype=jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(1), (n, nrhs),
                              jnp.float32)
        return x + 0.05 * n * jnp.eye(n, dtype=jnp.float32), spd, b

    xj, spd_j, bj = gen()
    xj.block_until_ready()

    def m_posv():
        def f(d, aux):
            _, X = st.posv(mk(d, MatrixType.Hermitian, Uplo.Lower),
                           mk(aux))
            return d + X.data[:, :1] * 1e-30
        t = _slope(f, spd_j, bj, est_hint=4e-3 * scale, reps=3,
                   target=0.5 * budget_scale)
        record("posv_n%d_r%d" % (n, nrhs),
               (n ** 3 / 3.0 + 2.0 * n * n * nrhs) / t / 1e9)

    def m_gesv():
        def f(d, aux):
            _, X = st.gesv(mk(d), mk(aux))
            return d + X.data[:, :1] * 1e-30
        t = _slope(f, xj, bj, est_hint=8e-3 * scale, reps=3,
                   target=0.5 * budget_scale)
        record("gesv_n%d_r%d" % (n, nrhs),
               (2.0 * n ** 3 / 3.0 + 2.0 * n * n * nrhs) / t / 1e9)

    gm, gn = 4 * full_n, max(full_n // 4, 64)   # tall-skinny (ex09)

    @jax.jit
    def gen_ls():
        key = jax.random.PRNGKey(2)
        return (jax.random.normal(key, (gm, gn), jnp.float32),
                jax.random.normal(jax.random.PRNGKey(3), (gm, nrhs),
                                  jnp.float32))

    def m_gels():
        aj, bbj = gen_ls()

        def f(d, aux):
            X = st.gels(mk(d), mk(aux))
            return d + X.data[:1, :1] * 1e-30
        t = _slope(f, aj, bbj, est_hint=2e-2, reps=3,
                   target=0.4 * budget_scale)
        record("gels_m%d_n%d_r%d" % (gm, gn, nrhs),
               2.0 * gn * gn * (gm - gn / 3.0) / t / 1e9)

    # eigen/SVD sizes: 4096 for round-over-round comparability AND
    # 8192 — the size the eigensolver perf work is judged at (VERDICT
    # r5 weak #6: the one gap being worked on was not tracked by the
    # harness that drives the verdict)
    eig_sizes = [min(4096, full_n)]
    if full_n >= 8192 and 8192 not in eig_sizes:
        eig_sizes.append(8192)

    def gen_eig(ne):
        @jax.jit
        def g():
            key = jax.random.PRNGKey(4)
            x = jax.random.normal(key, (ne, ne), jnp.float32)
            return jnp.matmul(x, x.T, precision=HI) / ne \
                + jnp.eye(ne, dtype=jnp.float32)
        return g()

    def m_heev(ne):
        hj = gen_eig(ne)

        def f(d, aux):
            r = st.heev(mk(d, MatrixType.Hermitian, Uplo.Lower))
            return d + r.vectors.data * 1e-30
        t = _slope(f, hj, hj, est_hint=5e-1 * (ne / 4096.0) ** 3,
                   reps=3, target=0.4 * budget_scale)
        record("heev_n%d" % ne, (4.0 * ne ** 3 / 3.0) / t / 1e9)

    def m_svd(ne):
        sj = gen_eig(ne)

        def f(d, aux):
            r = st.svd(mk(d))
            return d + r.U.data * 1e-30
        t = _slope(f, sj, sj, est_hint=9e-1 * (ne / 4096.0) ** 3,
                   reps=3, target=0.4 * budget_scale)
        record("svd_n%d" % ne, (8.0 * ne ** 3 / 3.0) / t / 1e9)

    guarded("posv", m_posv)
    guarded("gesv", m_gesv)
    guarded("gels", m_gels)
    if full_n >= 4096:       # QDWH at 1024+ is too slow for the CPU
        for ne in eig_sizes:      # smoke tier; real runs always hit
            # size-qualified guard names: a failure at one size must
            # not collide with (or shadow) the other size's record
            guarded("heev_n%d" % ne, lambda ne=ne: m_heev(ne))
            guarded("svd_n%d" % ne, lambda ne=ne: m_svd(ne))
            import gc
            gc.collect()
    import gc
    gc.collect()


def bench_micro(st, results):
    """`--micro`: regenerate the microbenchmarks behind the in-code
    perf claims (VERDICT r2 'perf-claim hygiene') — the v5e numbers
    quoted in blocked.py's module docstring (dense vs lower-only
    trailing updates), invert_triangular/trtri, the Pallas panel
    kernels, and XLA's native kernels that set the Fused/Tiled policy
    (methods.py). Times are milliseconds per call via the same slope
    method as the main bench; each line is emitted as measured."""
    import jax
    import jax.numpy as jnp
    HI = jax.lax.Precision.HIGHEST

    key = jax.random.PRNGKey(0)
    # calibrate a platform speed factor so the slope probes pick sane
    # trip counts on slow backends (est_hints below are v5e-scale; a
    # CPU run is ~100-1000x slower per call). The calibration itself
    # must be slope-based: a single timed call carries the dispatch
    # floor (not measured on the current machine), which would
    # inflate `speed` and wreck every downstream est_hint.
    xcal = jax.random.normal(key, (1024, 1024), jnp.float32)

    @jax.jit
    def fcal(x, aux, k):
        # aux passed as an argument, never closure-captured (a captured
        # concrete array becomes an HLO constant shipped per compile)
        return jax.lax.fori_loop(
            0, k, lambda i, x: jnp.matmul(x, aux, precision=HI)
            * (1.0 / 32.0), x)

    def tcal(k):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fcal(xcal, xcal, k).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    fcal(xcal, xcal, 2).block_until_ready()        # compile
    t_mm = max((tcal(34) - tcal(2)) / 32.0, 1e-6)
    speed = max(t_mm / 1e-4, 1.0)

    def emit_ms(name, t):
        results[name + "_ms"] = round(t * 1e3, 3)
        emit({"metric": name + "_ms", "value": round(t * 1e3, 3),
              "unit": "ms"})

    def guarded(name, fn):
        try:
            fn()
        except Exception as e:
            results[name + "_error"] = str(e)[:160]
            emit({"metric": name, "error": str(e)[:160]})

    def m_trtri():
        # hot-path inversion (XLA solve leaf since round 3) vs the
        # retired Pallas substitution kernel — the measurement behind
        # the round-3 rerouting (PERF.md)
        from slate_tpu.linalg.blocked import invert_triangular
        from slate_tpu.ops import pallas_kernels as pk
        l = jnp.tril(jax.random.normal(key, (512, 512), jnp.float32)) \
            + 8.0 * jnp.eye(512, dtype=jnp.float32)
        t = _slope(lambda x, aux: invert_triangular(x, True) + aux * 0,
                   l, l, est_hint=3e-4 * speed, reps=3, target=0.3)
        emit_ms("micro_trtri_lower_512", t)
        if pk.pallas_available(l.dtype):
            t = _slope(lambda x, aux: pk.trtri_lower(x) + aux * 0,
                       l, l, est_hint=3e-4 * speed, reps=3, target=0.3)
            emit_ms("micro_pallas_trtri_512", t)

    def m_xla_trisolve():
        # the number that retired invert-then-matmul from the
        # single-device paths: TriangularSolve at matmul rate
        l = jnp.tril(jax.random.normal(key, (256, 256), jnp.float32)) \
            + 8.0 * jnp.eye(256, dtype=jnp.float32)
        b = jax.random.normal(key, (256, 256), jnp.float32)
        t = _slope(lambda x, aux: jax.lax.linalg.triangular_solve(
            aux, x, left_side=True, lower=True), b, l,
            est_hint=5e-4 * speed, reps=3, target=0.3)
        emit_ms("micro_xla_triangular_solve_256", t)

    def m_chol_panel():
        # hot-path diag factor (XLA cholesky since round 3) vs the
        # retired Pallas panel
        from slate_tpu.linalg.blocked import chol_diag_factor
        from slate_tpu.ops import pallas_kernels as pk
        x = jax.random.normal(key, (512, 512), jnp.float32)
        s = jnp.matmul(x, x.T, precision=HI) / 512 \
            + 4.0 * jnp.eye(512, dtype=jnp.float32)
        t = _slope(lambda d, aux: chol_diag_factor(d) + aux * 0,
                   s, s, est_hint=5e-4 * speed, reps=3, target=0.3)
        emit_ms("micro_chol_panel_512", t)
        if pk.pallas_available(s.dtype):
            t = _slope(lambda d, aux: pk.chol_panel(d) + aux * 0,
                       s, s, est_hint=5e-4 * speed, reps=3, target=0.3)
            emit_ms("micro_pallas_chol_512", t)

    def m_lu_panel():
        # the LU panel wall table (PERF.md Round-4/Round-10): the
        # routed _lu_panel (native custom call where it can compile)
        # vs the rank-1 Pallas kernel vs the block-recursive
        # lu_panel_rec, per-column µs per size. On TPU the widths
        # bracket the production nb choices AND the >NATIVE_LU_MAX_M
        # heights the native call cannot compile at all (there the
        # only exact-pivoting alternatives are fori and the rec
        # kernel); on the CPU tier the kernels run INTERPRETED at
        # reduced sizes — recorded as informational (the TPU numbers
        # ride the consolidated hardware round, ROADMAP).
        from slate_tpu.linalg.lu import _lu_panel, lu_panel_fori
        from slate_tpu.ops import pallas_kernels as pk
        on_tpu = jax.default_backend() not in ("cpu",)
        sizes = [(4096, 128), (4096, 256), (4096, 512)] if on_tpu \
            else [(512, 64), (512, 128)]
        tall = [(16384, 256), (32768, 128)] if on_tpu else [(1024, 64)]
        results["micro_lu_panel_informational"] = not on_tpu

        def line(name, m, w, fn, hint):
            t = _slope(lambda d, aux: fn(d)[0] + aux * 0, p, p,
                       est_hint=hint * speed, reps=3, target=0.3)
            emit_ms("micro_%s_%dx%d" % (name, m, w), t)
            results["micro_%s_%dx%d_uspercol" % (name, m, w)] = \
                round(t * 1e6 / w, 3)

        for m, w in sizes:
            p = jax.random.normal(key, (m, w), jnp.float32)
            line("lu_panel", m, w, _lu_panel, 2e-3)
            if pk.lu_panel(p) is not None:
                line("pallas_lu_panel", m, w, pk.lu_panel, 2e-3)
            if pk.lu_panel_rec(p) is not None:
                line("pallas_lu_panel_rec", m, w, pk.lu_panel_rec,
                     2e-3)
        for m, w in tall:
            # beyond the native height cap: fori (the current exact-
            # pivoting fallback) vs the recursive kernel's split path.
            # The CPU tier forces the split with a reduced budget so
            # the tall machinery is exercised (informational).
            p = jax.random.normal(key, (m, w), jnp.float32)
            # the forced budget must still fit an (m, ib) base panel
            cap = None if on_tpu else m * max(w // 2, 32)
            line("lu_panel_fori", m, w, lu_panel_fori, 2e-2)
            if pk.lu_panel_rec(p, max_elems=cap) is not None:
                line("pallas_lu_panel_rec_tall", m, w,
                     lambda d: pk.lu_panel_rec(d, max_elems=cap),
                     2e-2)

    def m_givens_chain():
        # steqr2/bdsqr sweep accumulation: dense chain compose + one
        # (n, n) matmul vs the blocked Pallas apply (banded (2b, 2b)
        # factors, O(n^2 b) per sweep) — ISSUE 6
        from slate_tpu.linalg.svd import _givens_chain_matrix
        from slate_tpu.ops import pallas_kernels as pk
        on_tpu = jax.default_backend() not in ("cpu",)
        n = 2048 if on_tpu else 512
        th = jax.random.uniform(key, (n - 1,), jnp.float32)
        cs, sn = jnp.cos(th), jnp.sin(th)
        Z = jax.random.normal(key, (n, n), jnp.float32)

        def dense(z, aux):
            G = _givens_chain_matrix(cs, sn, n, jnp.float32)
            return jnp.matmul(z, G, precision=HI) + aux * 0

        t = _slope(dense, Z, Z, est_hint=2e-3 * speed, reps=3,
                   target=0.3)
        emit_ms("micro_givens_dense_n%d" % n, t)
        if pk.givens_chain_eligible(n, n, Z.dtype):
            t = _slope(lambda z, aux: pk.givens_chain_apply(z, cs, sn)
                       + aux * 0, Z, Z, est_hint=2e-3 * speed,
                       reps=3, target=0.3)
            emit_ms("micro_givens_chain_apply_n%d" % n, t)

    def m_trailing():
        # blocked.py claim: dense full-square trailing update beats
        # lower-only variants (m=7680, k=512). The panel is perturbed
        # by the carried state so the matmul cannot be hoisted out of
        # the timing loop as a loop invariant.
        pan = jax.random.normal(key, (7680, 512), jnp.float32)
        x0 = jnp.zeros((7680, 7680), jnp.float32)

        def f(x, pan):
            p2 = pan + x[:, :512] * 1e-30
            return jnp.matmul(p2, p2.T, precision=HI)

        t = _slope(f, x0, pan, est_hint=2e-3 * speed, reps=3,
                   target=0.3)
        emit_ms("micro_dense_trailing_7680x512", t)

    def m_native():
        # methods.py policy inputs: XLA native cholesky/lu/qr at 4096
        x = jax.random.normal(key, (4096, 4096), jnp.float32)
        s = jnp.matmul(x, x.T, precision=HI) / 4096 \
            + 4.0 * jnp.eye(4096, dtype=jnp.float32)
        t = _slope(lambda d, aux: jax.lax.linalg.cholesky(
            d, symmetrize_input=False) * 1e-30 + d, s, s,
            est_hint=5e-3 * speed, reps=3, target=0.4)
        emit_ms("micro_xla_cholesky_4096", t)
        t = _slope(lambda d, aux: jax.lax.linalg.lu(d)[0] * 1e-30 + d,
                   x, x, est_hint=1e-2 * speed, reps=3, target=0.4)
        emit_ms("micro_xla_lu_4096", t)

    guarded("micro_trtri", m_trtri)
    guarded("micro_xla_trisolve", m_xla_trisolve)
    guarded("micro_chol_panel", m_chol_panel)
    guarded("micro_lu_panel", m_lu_panel)
    guarded("micro_givens_chain", m_givens_chain)
    guarded("micro_dense_trailing", m_trailing)
    guarded("micro_native", m_native)


def bench_tune():
    """`--tune`: populate the persistent autotuning cache (ISSUE 1)
    and record before/after numbers into the BENCH trajectory.

    Per op: measure the frozen-defaults configuration (tune.select
    bypassed), run the microbenchmark probe over candidate configs,
    persist the winner (tune.cache), then re-measure with tuned
    selection live. One JSON line per op carries both numbers, the
    chosen config, and whether it differs from the frozen default;
    the final line carries the tune.stats counter snapshot (decisions
    by source, cache hits/misses, probe wall time), so the BENCH_*
    trajectory can attribute every win to a measured decision."""
    import jax
    import numpy as np
    from slate_tpu.tune import cache as tcache
    from slate_tpu.tune import probe, select, stats

    platform = jax.default_backend()
    try:
        n = int(os.environ.get("SLATE_TUNE_N", "0"))
    except ValueError:
        n = 0
    if not n:
        # CPU default 1024: below that the n-scaled frozen defaults
        # are already optimal on the CI box and the run demonstrates
        # nothing; at 1024 the measured winner (nb=1024, ~1.5x over
        # the frozen 512) is genuinely non-default
        n = 2048 if platform == "tpu" else 1024
    cands = [c for c in (64, 128, 256, 512, 1024) if c <= n]
    # potrf is not in the default set: its probed nb is tile-size
    # guidance only (the driver takes nb from the caller's tiles;
    # probe._blocksize_runner) — opt in via SLATE_TUNE_OPS=potrf,...
    ops = [s.strip() for s in os.environ.get(
        "SLATE_TUNE_OPS", "getrf,geqrf").split(",") if s.strip()]
    emit({"tune": "start", "platform": platform, "n": n,
          "candidates": cands, "ops": ops,
          "cache": tcache.cache_path()})

    from slate_tpu.tune.probe import _blocksize_runner

    for op in ops:
        try:
            if op == "heev":
                # method-routing probe: Auto default is the baseline;
                # a staged route is cached only if it beats it
                n_eig = min(n, 512)
                results = probe.probe_method_eig(n_eig, np.float32,
                                                 reps=2)
                auto_t = next(r["seconds"] for r in results
                              if r["method"] == "auto")
                best = results[0]
                non_default = best["method"] != "auto" \
                    and best["seconds"] \
                    < (1.0 - probe.WIN_MARGIN) * auto_t
                if non_default:
                    tcache.get_cache().put(
                        "heev", np.float32, n_eig,
                        {"method_eig": best["method"]},
                        meta={"n": n_eig, "results": results})
                    tcache.get_cache().save()
                emit({"tune": op, "n": n_eig,
                      "before_ms": round(auto_t * 1e3, 3),
                      "after_ms": round(best["seconds"] * 1e3, 3),
                      "default_method": "auto",
                      "chosen_method": best["method"]
                      if non_default else "auto",
                      "non_default": non_default,
                      "speedup": round(
                          auto_t / max(best["seconds"], 1e-12), 3),
                      "results": results})
                continue
            if op == "ooc":
                frozen_w = min(8192, n)      # label only
                cands_ooc = sorted({max(n // 8, 32), max(n // 4, 64),
                                    max(n // 2, 128)})
                # baseline (panel_cols=None, the driver's frozen
                # width) is measured inside the probe
                results = probe.probe_ooc_panel(n, cands_ooc, reps=2)
                before = next(r["seconds"] for r in results
                              if r["panel_cols"] is None)
                best = results[0]
                non_default = best["panel_cols"] is not None \
                    and best["seconds"] \
                    < (1.0 - probe.WIN_MARGIN) * before
                if non_default:
                    tcache.get_cache().put(
                        "ooc", np.float32, n,
                        {"panel_cols": best["panel_cols"]},
                        meta={"n": n, "results": results})
                    tcache.get_cache().save()
                emit({"tune": op, "n": n,
                      "before_ms": round(before * 1e3, 3),
                      "after_ms": round(best["seconds"] * 1e3, 3),
                      "default_panel_cols": frozen_w,
                      "chosen_panel_cols": best["panel_cols"]
                      if non_default else frozen_w,
                      "non_default": non_default,
                      "speedup": round(
                          before / max(best["seconds"], 1e-12), 3),
                      "results": results})
                continue
            # frozen_nb labels the emitted line — taken from the
            # drivers' own helpers, never re-derived here
            if op == "getrf":
                from slate_tpu.linalg.lu import _lu_nb
                with select.disabled():
                    frozen_nb = _lu_nb(None, min(256, n), (n, n),
                                       None)
            elif op == "geqrf":
                from slate_tpu.linalg.qr import geqrf_default_nb
                frozen_nb = geqrf_default_nb(n, min(256, n))
            else:
                frozen_nb = 256
            # probe_blocksize measures the driver's own default path
            # (entry nb=None, cache bypassed) as the baseline every
            # winner must beat — never-regress by construction
            results = probe.probe_blocksize(
                op, n, np.float32, sorted(set(cands) | {frozen_nb}))
            before = next(r["seconds"] for r in results
                          if r["nb"] is None)
            best = results[0]
            # a winner must beat the default baseline beyond the
            # noise margin (which also discards a candidate that is
            # configuration-identical to the baseline, e.g. the
            # explicit frozen nb ranked first by jitter)
            non_default = best["nb"] is not None \
                and best["seconds"] < (1.0 - probe.WIN_MARGIN) * before
            if non_default and op != "geqrf" \
                    and best["nb"] == frozen_nb:
                # for geqrf a Tiled winner at the frozen nb still
                # differs from the Fused default route, so only the
                # non-geqrf ops treat frozen-nb equality as default
                non_default = False
            if non_default:
                chosen = {"nb": best["nb"]}
                if op == "geqrf":
                    # Tiled winner: route the bucket to it (Auto
                    # would take the Fused crossover and skip nb)
                    chosen["fused_max_n"] = 0
                tcache.get_cache().put(op, np.float32, n, chosen,
                                       meta={"n": n,
                                             "results": results})
                tcache.get_cache().save()
            emit({"tune": op, "n": n,
                  "before_ms": round(before * 1e3, 3),
                  "after_ms": round(best["seconds"] * 1e3, 3),
                  "default_nb": frozen_nb,
                  "chosen_nb": best["nb"] if non_default
                  else frozen_nb,
                  "non_default": non_default,
                  "speedup": round(
                      before / max(best["seconds"], 1e-12), 3),
                  "results": results})
        except Exception as e:
            emit({"tune": op, "error": str(e)[:200]})
            import gc
            gc.collect()

    # demonstrate the cached decision being TAKEN: a fresh driver call
    # with default options must now resolve the tuned value and the
    # decision must land in the stats counters
    probe_snap = stats.snapshot()      # keep probe wall time/decisions
    stats.reset()
    try:
        import dataclasses as _dc                       # noqa: F401
        import slate_tpu as st
        from slate_tpu.core.enums import Diag, MatrixType, Op, Uplo
        from slate_tpu.core.tiles import TiledMatrix
        import jax.numpy as jnp
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (n, n), jnp.float32)
        G = TiledMatrix(data=x, m=n, n=n, mb=min(256, n),
                        nb=min(256, n), mtype=MatrixType.General,
                        uplo=Uplo.General, op=Op.NoTrans,
                        diag=Diag.NonUnit)
        jax.block_until_ready(st.getrf(G).LU.data)
    except Exception as e:
        emit({"tune": "decision_check", "error": str(e)[:200]})
    snap = stats.snapshot()
    emit({"metric": "tune", "value": 1, "unit": "suite",
          "vs_baseline": 1,
          "extras": {"probe_seconds": probe_snap["probe_seconds"],
                     "probe_stats": probe_snap["decisions"],
                     "decision_check": snap}})
    return 0


def bench_ooc():
    """`--ooc`: streamed-driver smoke (ISSUE 4) — small-n potrf_ooc +
    getrf_ooc through the stream engine, uncached (budget 0, the
    frozen default = the pre-engine schedule) vs cached (budget
    holding ~3/4 of the factor panels), with the engine's stats (hit
    rate, h2d/d2h bytes, prefetch/writeback overlap fractions,
    eviction/invalidation counts) shipped into the BENCH_*.json
    extras. Numbers come from the obs metrics registry (counter
    deltas around each run) plus stream.last_stats(), so trajectory
    diffs can attribute transfer-volume changes to cache decisions."""
    import numpy as np
    from slate_tpu import obs
    from slate_tpu.linalg import ooc, stream
    from slate_tpu.obs import metrics as om

    obs.enable()
    try:
        n = int(os.environ.get("SLATE_OOC_N", "1024"))
    except ValueError:
        n = 1024
    w = max(n // 8, 32)
    nt = (n + w - 1) // w
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n)).astype(np.float32)
    a = x @ x.T / n + 4.0 * np.eye(n, dtype=np.float32)
    g = x + 0.2 * n * np.eye(n, dtype=np.float32)
    b = rng.standard_normal((n, 8)).astype(np.float32)
    budget = 6 * n * w * 4        # ~3nt/4 full f32 panels at nt=8
    extras = {"n": n, "panel_cols": w, "nt": nt,
              "cache_budget_bytes": budget}

    def counters():
        return dict(om.snapshot()["counters"])

    def delta(after, before, key):
        return int(after.get(key, 0) - before.get(key, 0))

    results = {}

    def run(name, fn, budget_bytes, engine_stats=True,
            keep_result=False):
        """engine_stats=False for composite drivers (posv = potrf +
        potrs, TWO engines): stream.last_stats() reflects only the
        last-finished engine, so pairing it with byte deltas that
        span both phases would misattribute — composite records
        carry the cross-phase deltas only. Cache counters for ALL
        engines still accumulate in the obs ooc.cache.* counters,
        which are reported as deltas here too. `keep_result` retains
        the driver's return value for cross-leg comparisons — only
        the solve legs ask for it: at hardware-round n (65536) a
        retained factor is 16 GB, and a dozen of them would OOM a
        host whose premise is that ONE matrix barely fits."""
        c0 = counters()
        t0 = time.perf_counter()
        try:
            out = fn(budget_bytes)
            results[name] = out if keep_result else True
            del out
        except Exception as e:
            extras["%s_error" % name] = str(e)[:160]
            emit({"ooc": name, "error": str(e)[:160]})
            return
        wall = time.perf_counter() - t0
        c1 = counters()
        rec = {"wall_s": round(wall, 3),
               "h2d_bytes": delta(c1, c0, "ooc.h2d_bytes"),
               "d2h_bytes": delta(c1, c0, "ooc.d2h_bytes"),
               "cache_hits": delta(c1, c0, "ooc.cache.hits"),
               "cache_misses": delta(c1, c0, "ooc.cache.misses"),
               "cache_evictions":
                   delta(c1, c0, "ooc.cache.evictions"),
               "cache_invalidations":
                   delta(c1, c0, "ooc.cache.invalidations"),
               "lu_invalidations":
                   delta(c1, c0, "ooc.lu_invalidations"),
               "lu_invalidation_bytes":
                   delta(c1, c0, "ooc.lu_invalidation_bytes"),
               "cast_demote_bytes":
                   delta(c1, c0, "ooc.cast_demote_bytes"),
               "cast_promote_bytes":
                   delta(c1, c0, "ooc.cast_promote_bytes"),
               "mixed_to_full":
                   delta(c1, c0, "resil.fallback.mixed_to_full"),
               "served_bytes":
                   delta(c1, c0, "ooc.cache.served_bytes")}
        if engine_stats:
            s = stream.last_stats()
            rec.update({
                "hit_rate": s.get("hit_rate", 0.0),
                "prefetch_overlap_fraction":
                    s.get("prefetch_overlap_fraction", 0.0),
                "d2h_overlap_fraction":
                    s.get("d2h_overlap_fraction", 0.0)})
        extras[name] = rec
        emit(dict({"ooc": name}, **rec))

    run("potrf_uncached",
        lambda bb: ooc.potrf_ooc(a, panel_cols=w,
                                 cache_budget_bytes=bb), 0)
    run("potrf_cached",
        lambda bb: ooc.potrf_ooc(a, panel_cols=w,
                                 cache_budget_bytes=bb), budget)
    run("getrf_uncached",
        lambda bb: ooc.getrf_ooc(g, panel_cols=w,
                                 cache_budget_bytes=bb), 0)
    run("getrf_cached",
        lambda bb: ooc.getrf_ooc(g, panel_cols=w,
                                 cache_budget_bytes=bb), budget)
    # the tournament-pivot LU stream (ISSUE 10): immutable factor
    # panels, so lu_invalidations stays 0 and the budget actually
    # serves revisits. The diagonally-shifted `g` above never pivots
    # across panels (its fixups are no-ops), so the per-cause delta
    # runs on a row-scaled matrix whose every panel pivots across
    # panel boundaries — the partial path's invalidation storm vs
    # the tournament path's 0, side by side at the same budget
    gp = g * (1.0 + np.arange(n, dtype=np.float32))[:, None]
    run("getrf_pivoting_cached",
        lambda bb: ooc.getrf_ooc(gp, panel_cols=w,
                                 cache_budget_bytes=bb), budget)
    run("getrf_tntpiv_pivoting_cached",
        lambda bb: ooc.getrf_tntpiv_ooc(gp, panel_cols=w,
                                        cache_budget_bytes=bb),
        budget)
    run("posv_cached",
        lambda bb: ooc.posv_ooc(a, b, panel_cols=w,
                                cache_budget_bytes=bb), budget,
        engine_stats=False)      # two engines: deltas only
    # mixed-precision legs (ISSUE 12): bf16 residency vs the f32
    # stream at EQUAL cache budget. The pair runs in the thrash/fit
    # regime — a budget holding 3 f32 panels (the f32 stream must
    # re-upload evicted revisits) holds 6 demoted ones (bf16 revisits
    # mostly hit, and the uploads that remain ship half the bytes) —
    # which is exactly where the byte/flop win lives; the solve legs
    # price the refinement's accuracy contract against the f32
    # answers (residual <= 1e-5 or a recorded mixed_to_full
    # escalation, the acceptance gate)
    pbudget = 3 * n * w * 4
    extras["precision_budget_bytes"] = pbudget
    # the f32 baselines are PINNED explicit — once a measured bf16
    # ooc/precision entry lands in the tune cache (the outcome these
    # legs exist to justify), an Auto baseline would silently become
    # a vacuous bf16-vs-bf16 comparison
    run("potrf_f32_eqbudget",
        lambda bb: ooc.potrf_ooc(a, panel_cols=w,
                                 cache_budget_bytes=bb,
                                 precision="f32"), pbudget)
    run("potrf_bf16_eqbudget",
        lambda bb: ooc.potrf_ooc(a, panel_cols=w,
                                 cache_budget_bytes=bb,
                                 precision="bf16"), pbudget)
    run("posv_f32",
        lambda bb: ooc.posv_ooc(a, b, panel_cols=w,
                                cache_budget_bytes=bb,
                                precision="f32"), budget,
        engine_stats=False, keep_result=True)
    run("posv_bf16",
        lambda bb: ooc.posv_ooc(a, b, panel_cols=w,
                                cache_budget_bytes=bb,
                                precision="bf16"), budget,
        engine_stats=False, keep_result=True)
    run("gesv_bf16",
        lambda bb: ooc.gesv_ooc(g, b, panel_cols=w,
                                cache_budget_bytes=bb,
                                precision="bf16"), budget,
        engine_stats=False, keep_result=True)
    run("gesv_f32",
        lambda bb: ooc.gesv_ooc(g, b, panel_cols=w,
                                cache_budget_bytes=bb,
                                precision="f32"), budget,
        engine_stats=False, keep_result=True)
    ok = True
    pf, pb = extras.get("potrf_f32_eqbudget"), \
        extras.get("potrf_bf16_eqbudget")
    if pf and pb and pf.get("h2d_bytes"):
        red = 1.0 - pb["h2d_bytes"] / pf["h2d_bytes"]
        extras["precision_h2d_reduction"] = round(red, 4)
        ok &= red >= 0.40            # acceptance: >= 40% at equal
        #                              budget on the CPU protocol
    else:
        ok = False

    def _rel(name_lo, name_hi, pick):
        if name_lo not in results or name_hi not in results:
            return None
        xb, xf = pick(results[name_lo]), pick(results[name_hi])
        return float(np.abs(xb - xf).max()
                     / max(np.abs(xf).max(), 1e-30))

    rel_posv = _rel("posv_bf16", "posv_f32", lambda r: r[1])
    rel_gesv = _rel("gesv_bf16", "gesv_f32", lambda r: r[1])
    # the escalation excuse is PER LEG (the run() rec's own counter
    # delta): one leg's legitimate mixed_to_full fallback must not
    # blanket-pass another leg's unconverged-but-unescalated answer
    for key, rel, leg in (
            ("precision_posv_rel_vs_f32", rel_posv, "posv_bf16"),
            ("precision_gesv_rel_vs_f32", rel_gesv, "gesv_bf16")):
        if rel is None:
            ok = False
            continue
        extras[key] = rel
        ok &= rel <= 1e-5 \
            or extras.get(leg, {}).get("mixed_to_full", 0) > 0
    # the refine sweep count (obs satellite): how many lo-solve
    # corrections the bf16 answers needed
    extras["refine_ooc_iters"] = \
        om.snapshot()["histograms"].get("refine.ooc.iters")
    extras["precision_ok"] = ok
    pu, pc = extras.get("potrf_uncached"), extras.get("potrf_cached")
    if pu and pc and pu.get("h2d_bytes"):
        extras["potrf_h2d_reduction"] = round(
            1.0 - pc["h2d_bytes"] / pu["h2d_bytes"], 4)
    gc, gt = extras.get("getrf_pivoting_cached"), \
        extras.get("getrf_tntpiv_pivoting_cached")
    if gc and gt:
        # the per-cause delta: bytes the partial path's row-swap
        # fixups evicted (re-uploaded later) that the tournament
        # path never pays
        extras["getrf_lu_invalidation_bytes_removed"] = \
            gc.get("lu_invalidation_bytes", 0) \
            - gt.get("lu_invalidation_bytes", 0)
        if gc.get("h2d_bytes"):
            extras["getrf_tntpiv_h2d_reduction_vs_partial"] = round(
                1.0 - gt["h2d_bytes"] / gc["h2d_bytes"], 4)
    emit({"metric": "ooc", "value": 1 if ok else 0, "unit": "suite",
          "vs_baseline": 1 if ok else 0, "extras": extras})
    return 0


def bench_shard():
    """`--shard`: the sharded out-of-core layer (ISSUE 7) —
    shard_potrf_ooc / shard_geqrf_ooc over a grid spanning every
    local device vs the single-engine stream, with per-host staging
    bytes (obs ooc.h2d_bytes deltas — one host here; the 2-process
    protocol lives in tests/test_shard_multiproc.py), the ownership
    schedule's exact byte prediction, tree-broadcast counts
    (ooc.shard.bcast_* + the scheduled ppermutes), spill counts and
    overlap fractions in the BENCH extras. The lookahead depth sweep
    (ISSUE 11: *_shard_la1 / potrf_shard_la2 legs vs the FROZEN
    depth-0 *_shard baselines) reports per-leg broadcast-wait wall,
    update-compute wall, overlap fraction, and H2D bytes — bitwise
    equality and the exact-schedule prediction are ASSERTED at every
    depth, and the spill-regime overlap probe (nt=16) gates the
    suite on the depth-1 overlap-fraction gain; the absolute
    broadcast-wait walls are REPORTED, not gated (2-core-box flap,
    PERF Round-13 — the TPU round judges them). On the CPU tier
    main() pins 8 virtual devices before jax initializes; on real
    hardware the grid is whatever the process sees."""
    import numpy as np
    import jax
    from slate_tpu import obs
    import slate_tpu as st
    from slate_tpu.dist import shard_ooc
    from slate_tpu.dist.tree import schedule_ppermutes
    from slate_tpu.linalg import ooc, stream
    from slate_tpu.obs import metrics as om

    obs.enable()
    try:
        n = int(os.environ.get("SLATE_SHARD_N", "1024"))
    except ValueError:
        n = 1024
    w = max(n // 8, 32)
    nt = (n + w - 1) // w
    grid = st.make_grid()
    nranks = grid.p * grid.q
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n)).astype(np.float32)
    a = x @ x.T / n + 4.0 * np.eye(n, dtype=np.float32)
    g = x + 0.2 * n * np.eye(n, dtype=np.float32)
    budget = 64 * n * w * 4
    extras = {"n": n, "panel_cols": w, "nt": nt,
              "grid": [grid.p, grid.q],
              "cache_budget_bytes": budget,
              "tree_ppermutes_per_bcast":
                  schedule_ppermutes(nranks, 2)}

    def counters():
        return dict(om.snapshot()["counters"])

    def delta(after, before, key):
        return int(after.get(key, 0) - before.get(key, 0))

    results = {}

    def fdelta(after, before, key):
        return float(after.get(key, 0.0) - before.get(key, 0.0))

    def run(name, fn):
        c0 = counters()
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception as e:
            extras["%s_error" % name] = str(e)[:160]
            emit({"shard": name, "error": str(e)[:160]})
            return None
        wall = time.perf_counter() - t0
        c1 = counters()
        s = stream.last_stats()
        # lookahead attribution (ISSUE 11): the per-leg broadcast-wait
        # wall, issue-to-completion wall, ahead-issue count, and the
        # overlap fraction the depth sweep is judged on
        bwait = fdelta(c1, c0, "ooc.shard.bcast_wait_seconds")
        bflight = fdelta(c1, c0, "ooc.shard.bcast_inflight_seconds")
        rec = {"wall_s": round(wall, 3),
               "h2d_bytes": delta(c1, c0, "ooc.h2d_bytes"),
               "d2h_bytes": delta(c1, c0, "ooc.d2h_bytes"),
               "bcast_panels": delta(c1, c0, "ooc.shard.bcast_panels"),
               "bcast_bytes": delta(c1, c0, "ooc.shard.bcast_bytes"),
               "bcast_ahead": delta(c1, c0, "ooc.shard.bcast_ahead"),
               "bcast_compiles":
                   delta(c1, c0, "ooc.shard.bcast_compiles"),
               "bcast_wait_s": round(bwait, 4),
               "bcast_inflight_s": round(bflight, 4),
               "bcast_overlap_fraction":
                   round(max(0.0, 1.0 - bwait / bflight), 4)
                   if bflight > 0 else 0.0,
               "update_s": round(
                   fdelta(c1, c0, "ooc.shard.update_seconds"), 4),
               "ppermutes_scheduled":
                   delta(c1, c0, "comms.ppermute.scheduled"),
               "lu_invalidations":
                   delta(c1, c0, "ooc.lu_invalidations"),
               "lu_invalidation_bytes":
                   delta(c1, c0, "ooc.lu_invalidation_bytes"),
               "spills": s.get("spills", 0),
               "prefetch_overlap_fraction":
                   s.get("prefetch_overlap_fraction", 0.0),
               "d2h_overlap_fraction":
                   s.get("d2h_overlap_fraction", 0.0)}
        extras[name] = rec
        emit(dict({"shard": name}, **rec))
        results[name] = out
        return out

    sched = shard_ooc.CyclicSchedule(nt, grid)
    extras["my_panels"] = sched.my_panels()
    extras["expected_shard_h2d_bytes"] = sched.staged_bytes(
        {k: n - k * w for k in range(nt)}, w, n - (nt - 1) * w, 4)
    # the QR and LU streams stage FULL-height columns (QR panel
    # states / original-row-order store, ISSUE 10), so their
    # per-host predictions use height m
    extras["expected_shard_fullheight_h2d_bytes"] = \
        sched.staged_bytes({k: n for k in range(nt)}, w,
                           n - (nt - 1) * w, 4)
    extras["expected_shard_getrf_h2d_bytes"] = \
        extras["expected_shard_fullheight_h2d_bytes"]
    # the pivot mode the cold/tuned cache resolves for this size —
    # recorded so the TPU hardware round can earn (or refuse) a
    # measured ooc/lu_pivot entry against these numbers
    from slate_tpu.core.methods import MethodLUPivot
    extras["lu_pivot_resolved"] = MethodLUPivot.resolve(
        n, np.float32).value
    run("potrf_single",
        lambda: ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=0))
    # equal-budget single-engine legs: on a SINGLE-process mesh every
    # panel is "mine", so shard-vs-uncached mostly measures the
    # residency cache; the apples-to-apples sharding delta is against
    # the single engine at the SAME budget (the per-host split needs
    # a real multi-process mesh — tests/test_shard_multiproc.py)
    run("potrf_single_cached",
        lambda: ooc.potrf_ooc(a, panel_cols=w,
                              cache_budget_bytes=budget))
    run("potrf_shard",
        lambda: shard_ooc.shard_potrf_ooc(
            a, grid, panel_cols=w, cache_budget_bytes=budget))
    run("geqrf_single",
        lambda: ooc.geqrf_ooc(g, panel_cols=w, cache_budget_bytes=0))
    run("geqrf_single_cached",
        lambda: ooc.geqrf_ooc(g, panel_cols=w,
                              cache_budget_bytes=budget))
    run("geqrf_shard",
        lambda: shard_ooc.shard_geqrf_ooc(
            g, grid, panel_cols=w, cache_budget_bytes=budget))
    # LU legs (ISSUE 10): the uncached partial-pivot single engine
    # (the fixup/invalidation baseline), the equal-budget partial
    # engine (shows the invalidation storm eating the cache), the
    # tournament single engine at equal budget, and the sharded
    # tournament stream
    run("getrf_single",
        lambda: ooc.getrf_ooc(g, panel_cols=w, cache_budget_bytes=0))
    run("getrf_single_cached",
        lambda: ooc.getrf_ooc(g, panel_cols=w,
                              cache_budget_bytes=budget))
    run("getrf_tntpiv_cached",
        lambda: ooc.getrf_tntpiv_ooc(g, panel_cols=w,
                                     cache_budget_bytes=budget))
    run("getrf_shard",
        lambda: shard_ooc.shard_getrf_ooc(
            g, grid, panel_cols=w, cache_budget_bytes=budget))
    # lookahead depth sweep (ISSUE 11): the *_shard legs above run at
    # the FROZEN depth 0 (the synchronous baseline); these re-run the
    # same problems with 1 and 2 broadcast frames in flight. The per-
    # leg extras carry the broadcast-wait wall, overlap fraction, and
    # H2D bytes the TPU round prices a nonzero default against
    run("potrf_shard_la1",
        lambda: shard_ooc.shard_potrf_ooc(
            a, grid, panel_cols=w, cache_budget_bytes=budget,
            lookahead=1))
    run("potrf_shard_la2",
        lambda: shard_ooc.shard_potrf_ooc(
            a, grid, panel_cols=w, cache_budget_bytes=budget,
            lookahead=2))
    run("geqrf_shard_la1",
        lambda: shard_ooc.shard_geqrf_ooc(
            g, grid, panel_cols=w, cache_budget_bytes=budget,
            lookahead=1))
    run("getrf_shard_la1",
        lambda: shard_ooc.shard_getrf_ooc(
            g, grid, panel_cols=w, cache_budget_bytes=budget,
            lookahead=1))
    # mixed-precision leg (ISSUE 12): the bf16 broadcast frames —
    # every ppermute hop carries half the payload bytes (the
    # deterministic halving the TPU round prices against accuracy);
    # the factor itself is bf16-update-grade, compared loosely
    run("potrf_shard_bf16",
        lambda: shard_ooc.shard_potrf_ooc(
            a, grid, panel_cols=w, cache_budget_bytes=budget,
            precision="bf16"))

    ok = True
    # overlap probe (ISSUE 11 acceptance): the eviction-free legs
    # above have near-zero per-step host work after step 0, so the
    # CPU protocol's async dispatch already hides most update
    # execution under the depth-0 wait — the wait delta only shows
    # where each step does real synchronous staging. Probe in the
    # SPILL regime (nt = 16 >= 8, a 3-panel budget re-stages the
    # trailing shard every step), median of 3 alternating reps per
    # depth; the overlap-fraction gain is the gated criterion and
    # the wait walls are the reported data (see the gate comment
    # below)
    n2 = 2 * n
    w2 = max(n2 // 16, 32)
    x2 = rng.standard_normal((n2, n2)).astype(np.float32)
    a2 = x2 @ x2.T / n2 + 4.0 * np.eye(n2, dtype=np.float32)
    budget2 = 3 * n2 * w2 * 4
    try:
        for d in (0, 1):          # warm every program first
            shard_ooc.shard_potrf_ooc(a2, grid, panel_cols=w2,
                                      cache_budget_bytes=budget2,
                                      lookahead=d)
        waits = {0: [], 1: []}
        fracs = {0: [], 1: []}
        for _rep in range(3):
            for d in (0, 1):
                c0 = counters()
                shard_ooc.shard_potrf_ooc(
                    a2, grid, panel_cols=w2,
                    cache_budget_bytes=budget2, lookahead=d)
                c1 = counters()
                bw = fdelta(c1, c0, "ooc.shard.bcast_wait_seconds")
                bf = fdelta(c1, c0,
                            "ooc.shard.bcast_inflight_seconds")
                waits[d].append(bw)
                fracs[d].append(max(0.0, 1.0 - bw / bf)
                                if bf > 0 else 0.0)
        med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
        # compare the UNROUNDED medians — on hardware where the wait
        # wall is microseconds, rounding first would zero the
        # baseline and make the strict reduction unpassable
        w0, w1 = med(waits[0]), med(waits[1])
        f0, f1 = med(fracs[0]), med(fracs[1])
        probe = {"n": n2, "panel_cols": w2, "nt": n2 // w2,
                 "cache_budget_bytes": budget2,
                 "la0_wait_s": round(w0, 6),
                 "la1_wait_s": round(w1, 6),
                 "la0_overlap_fraction": round(f0, 4),
                 "la1_overlap_fraction": round(f1, 4)}
        probe["wait_reduced"] = w1 < w0
        probe["wait_reduction"] = round(1.0 - w1 / w0, 4) \
            if w0 > 0 else 0.0
        probe["overlap_gain"] = round(f1 - f0, 4)
        extras["potrf_overlap_probe"] = probe
        emit(dict({"shard": "potrf_overlap_probe"}, **probe))
        # gate on the overlap-fraction gain (5-13x on every CPU-tier
        # rep — the window the schedule opens is robustly
        # attributable); the absolute wait delta is REPORTED but not
        # gated: on a 2-core box the 8 virtual devices' collective IS
        # host compute, so a 3-rep median flaps ±10% with no code
        # defect (PERF Round-13 records +8.4% median-of-3 when quiet;
        # the TPU round judges the wall on real DMA/ICI pipes)
        ok &= probe["overlap_gain"] > 0.05
    except Exception as e:
        extras["potrf_overlap_probe_error"] = str(e)[:160]
        ok = False

    # flight-recorder attribution leg (ISSUE 14 acceptance): re-run
    # the depth-1 sharded potrf with the obs/ledger.py recorder on
    # and require >= 95% of the measured driver wall attributed to
    # the named step phases (factor/update/bcast_wait/stage/cache/
    # other — the per-step split is exhaustive, so the fraction
    # measures how much of the run the step loop actually covers)
    from slate_tpu.obs import ledger as obs_ledger
    from slate_tpu.obs import xprof as obs_xprof
    try:
        obs_ledger.reset()
        obs_ledger.enable()
        t0 = time.perf_counter()
        shard_ooc.shard_potrf_ooc(a, grid, panel_cols=w,
                                  cache_budget_bytes=budget,
                                  lookahead=1)
        wall = time.perf_counter() - t0
        att = obs_xprof.attribute_run(
            records=obs_ledger.records("shard_potrf_ooc"))
        frac = att["total_wall_s"] / wall if wall > 0 else 0.0
        rec = {"wall_s": round(wall, 4),
               "ledger_records": att["records"],
               "attributed_s": att["total_wall_s"],
               "fraction_attributed": round(frac, 4),
               "buckets": att["buckets"],
               "compile_s": att["compile_s"],
               "slowest_panel": (att["top_panels"] or [None])[0]}
        extras["ledger_attribution"] = rec
        emit(dict({"shard": "ledger_attribution"}, **rec))
        ok &= frac >= 0.95
    except Exception as e:
        extras["ledger_attribution_error"] = str(e)[:160]
        ok = False
    finally:
        obs_ledger.reset()

    # every leg must have RUN for the suite to emit green — run()
    # swallows a leg's exception into extras, which must read as
    # failure, not as a vacuously-passed comparison
    ok &= len(results) == 15
    if "potrf_shard" in results and "potrf_shard_bf16" in results:
        ph, pm = extras["potrf_shard"], extras["potrf_shard_bf16"]
        if ph.get("bcast_bytes"):
            red = 1.0 - pm["bcast_bytes"] / ph["bcast_bytes"]
            extras["potrf_bf16_bcast_reduction"] = round(red, 4)
            ok &= red >= 0.45        # frames demote exactly 2x
        close = bool(np.allclose(results["potrf_shard"],
                                 results["potrf_shard_bf16"],
                                 rtol=5e-2, atol=5e-2))
        extras["potrf_bf16_allclose_loose"] = close
        ok &= close
    if "potrf_single" in results and "potrf_shard" in results:
        p_ok = bool(np.allclose(results["potrf_single"],
                                results["potrf_shard"],
                                rtol=1e-5, atol=1e-5))
        extras["potrf_allclose"] = p_ok
        ok &= p_ok
        ps, ph = extras["potrf_single"], extras["potrf_shard"]
        if ps.get("h2d_bytes"):
            extras["potrf_h2d_reduction_vs_uncached"] = round(
                1.0 - ph["h2d_bytes"] / ps["h2d_bytes"], 4)
        pc = extras.get("potrf_single_cached")
        if pc and pc.get("h2d_bytes"):
            extras["potrf_h2d_reduction_vs_cached"] = round(
                1.0 - ph["h2d_bytes"] / pc["h2d_bytes"], 4)
        extras["potrf_h2d_exact_schedule"] = \
            ph["h2d_bytes"] == extras["expected_shard_h2d_bytes"]
    if "geqrf_single" in results and "geqrf_shard" in results:
        q_ok = bool(np.allclose(results["geqrf_single"][0],
                                results["geqrf_shard"][0],
                                rtol=1e-4, atol=1e-4))
        extras["geqrf_allclose"] = q_ok
        ok &= q_ok
    if "getrf_tntpiv_cached" in results and "getrf_shard" in results:
        # acceptance (ISSUE 10): sharded LU bitwise == the
        # single-engine tournament stream at the same pivot mode,
        # per-host staged bytes exactly the schedule prediction, and
        # the H2D reduction vs the uncached single engine in the
        # potrf/geqrf band
        lt, pt = results["getrf_tntpiv_cached"], results["getrf_shard"]
        g_ok = bool(np.array_equal(lt[0], pt[0])
                    and np.array_equal(lt[1], pt[1]))
        extras["getrf_shard_bitwise_vs_tntpiv"] = g_ok
        ok &= g_ok
        from slate_tpu.linalg.ooc import _swaps_to_perm
        perm = _swaps_to_perm(pt[1], n)
        L = np.tril(pt[0], -1) + np.eye(n, dtype=np.float32)
        resid = float(np.abs(g[perm] - L @ np.triu(pt[0])).max()
                      / max(np.abs(g).max(), 1.0))
        extras["getrf_shard_relative_residual"] = resid
        ok &= resid < 1e-4
        gs, gh = extras.get("getrf_single"), extras["getrf_shard"]
        if gs and gs.get("h2d_bytes"):
            extras["getrf_h2d_reduction_vs_uncached"] = round(
                1.0 - gh["h2d_bytes"] / gs["h2d_bytes"], 4)
        gc = extras.get("getrf_single_cached")
        if gc and gc.get("h2d_bytes"):
            extras["getrf_h2d_reduction_vs_cached"] = round(
                1.0 - gh["h2d_bytes"] / gc["h2d_bytes"], 4)
        extras["getrf_h2d_exact_schedule"] = \
            gh["h2d_bytes"] == extras["expected_shard_getrf_h2d_bytes"]
    # lookahead acceptance (ISSUE 11): every depth is BITWISE the
    # depth-0 schedule and stages exactly the (depth-invariant)
    # schedule prediction — both asserted here; the overlap criterion
    # is gated by the probe above
    if "potrf_shard" in results:
        for leg in ("potrf_shard_la1", "potrf_shard_la2"):
            if leg not in results:
                continue
            bit = bool(np.array_equal(results["potrf_shard"],
                                      results[leg]))
            extras["%s_bitwise_vs_la0" % leg] = bit
            ok &= bit
            exact = extras[leg]["h2d_bytes"] \
                == extras["expected_shard_h2d_bytes"]
            extras["%s_h2d_exact_schedule" % leg] = exact
            ok &= exact
    if "geqrf_shard" in results and "geqrf_shard_la1" in results:
        q0, q1 = results["geqrf_shard"], results["geqrf_shard_la1"]
        bit = bool(np.array_equal(q0[0], q1[0])
                   and np.array_equal(q0[1], q1[1]))
        extras["geqrf_shard_la1_bitwise_vs_la0"] = bit
        ok &= bit
        extras["geqrf_shard_la1_h2d_exact_schedule"] = \
            extras["geqrf_shard_la1"]["h2d_bytes"] \
            == extras["expected_shard_fullheight_h2d_bytes"]
        ok &= extras["geqrf_shard_la1_h2d_exact_schedule"]
    if "getrf_shard" in results and "getrf_shard_la1" in results:
        l0, l1 = results["getrf_shard"], results["getrf_shard_la1"]
        bit = bool(np.array_equal(l0[0], l1[0])
                   and np.array_equal(l0[1], l1[1]))
        extras["getrf_shard_la1_bitwise_vs_la0"] = bit
        ok &= bit
        extras["getrf_shard_la1_h2d_exact_schedule"] = \
            extras["getrf_shard_la1"]["h2d_bytes"] \
            == extras["expected_shard_getrf_h2d_bytes"]
        ok &= extras["getrf_shard_la1_h2d_exact_schedule"]
    emit({"metric": "shard", "value": 1 if ok else 0,
          "unit": "suite", "vs_baseline": 1 if ok else 0,
          "extras": extras})
    return 0


def bench_elastic():
    """`--elastic`: the elastic mesh (ISSUE 19) — throughput-driven
    panel re-ownership under a seeded straggler, on a REAL 2-process
    mesh. Three gated legs:

      * **straggler**: a seeded ``slow`` plan stalls host 1 on every
        panel it OWNS (``{"host": 1, "mine": true}`` — the injected
        cost is ownership-proportional, a deterministic multiplier on
        the straggler's step wall). The FROZEN static route pays it
        for half the stream; the elastic route measures, agrees, and
        re-owns panels off the straggler. GATE: elastic wall >= 15%
        under static wall, both factors bitwise vs the single-engine
        stream, and the elastic leg actually remapped. Extras report
        remap count, panels moved, and the straggler-idle fraction
        (fast-host bcast_wait / wall) per leg.
      * **shrink**: a seeded kill takes host 1 down mid-stream
        (checkpointing on); :func:`~slate_tpu.dist.elastic.
        shrink_to_fit` records the ``shard_shrink`` rung and the
        survivor resume (this process, same checkpoint root) must
        complete BITWISE vs the unfaulted single-engine stream.
      * **attribution**: a single-process elastic run with installed
        skewed speeds (real remaps) under the flight recorder —
        remap decisions land on the bus while >= 95% of the wall
        stays attributed to named ledger phases (the ISSUE 17 gate
        carried onto the segmented route)."""
    import numpy as np
    from slate_tpu import obs
    import slate_tpu as st
    from slate_tpu.dist import elastic, shard_ooc
    from slate_tpu.linalg import ooc
    from slate_tpu.obs import metrics as om
    from slate_tpu.resil import faults, guard
    from slate_tpu.testing import multiproc as mp

    obs.enable()
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "elastic_worker.py")
    extras = {}
    ok = True

    def worker_recs(outs):
        return [mp.results(out).get("elastic", {}) for out in outs]

    # -- leg 1: seeded straggler, static vs elastic wall ------------
    slow_plan = faults.FaultPlan([
        {"site": "step",
         "match": {"op": "shard_potrf_ooc", "host": 1, "mine": True},
         "kind": "slow", "times": 10 ** 6, "slow_s": 2.0}])
    legs = {}
    for mode in ("slow_static", "slow_elastic"):
        try:
            procs, outs = mp.launch(
                worker, num_processes=2, extra_args=[mode],
                env=faults.install_env_var(slow_plan), timeout=300)
            mp.assert_success(procs, outs)
            recs = worker_recs(outs)
            wall = max(r.get("wall_s", 0.0) for r in recs)
            rec = {"wall_s": wall,
                   "remaps": max(r.get("remaps", 0) for r in recs),
                   "panels_moved": max(r.get("panels_moved", 0)
                                       for r in recs),
                   # host 0 is the FAST host: its broadcast wait is
                   # time spent idle behind the straggler
                   "straggler_idle_fraction": round(
                       recs[0].get("bcast_wait_s", 0.0)
                       / max(recs[0].get("wall_s", 0.0), 1e-9), 4),
                   "bitwise": all(r.get("bitwise_vs_stream", False)
                                  for r in recs)}
            legs[mode] = rec
            extras[mode] = rec
            emit(dict({"elastic": mode}, **rec))
            ok &= rec["bitwise"]
        except Exception as e:
            extras["%s_error" % mode] = str(e)[:160]
            emit({"elastic": mode, "error": str(e)[:160]})
            ok = False
    if "slow_static" in legs and "slow_elastic" in legs:
        sw = legs["slow_static"]["wall_s"]
        ew = legs["slow_elastic"]["wall_s"]
        imp = 1.0 - ew / sw if sw > 0 else 0.0
        extras["elastic_wall_improvement"] = round(imp, 4)
        extras["elastic_remapped"] = legs["slow_elastic"]["remaps"] >= 1
        ok &= imp >= 0.15
        ok &= legs["slow_elastic"]["remaps"] >= 1
        ok &= legs["slow_static"]["remaps"] == 0
    else:
        ok = False

    # -- leg 2: seeded WorkerLost -> shrink-to-fit survivor resume --
    import tempfile
    kill_plan = faults.FaultPlan([
        {"site": "step",
         "match": {"op": "shard_potrf_ooc", "step": 3, "host": 1},
         "times": 1, "kind": "kill"}])
    n, w = 160, 32
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n)).astype(np.float32)
    a = x @ x.T / n + 4.0 * np.eye(n, dtype=np.float32)
    with tempfile.TemporaryDirectory() as ck:
        def primary():
            procs, outs = mp.launch(
                worker, num_processes=2, extra_args=["crash", ck],
                env=faults.install_env_var(kill_plan), timeout=300,
                death_grace=10.0)
            mp.assert_success(procs, outs)   # a no-kill run is a bug
            return None

        def survivors(exc):
            # this process IS the survivor mesh: resume from the
            # same checkpoint root (host0's mirror holds every
            # committed panel — the complete() mirror contract)
            grid = st.make_grid()
            return shard_ooc.shard_potrf_ooc(
                a, grid, panel_cols=w, cache_budget_bytes=0,
                ckpt_path=ck, ckpt_every=1)

        try:
            c0 = guard.counts()
            L = elastic.shrink_to_fit(primary, survivors,
                                      op="shard_potrf_ooc")
            L0 = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=0)
            shr = guard.counts().get(
                "resil.fallback.shard_shrink", 0) \
                - c0.get("resil.fallback.shard_shrink", 0)
            rec = {"completed": L is not None,
                   "shrink_escalations": shr,
                   "bitwise_vs_unfaulted":
                       bool(np.array_equal(np.asarray(L), L0))}
            extras["shrink"] = rec
            emit(dict({"elastic": "shrink"}, **rec))
            ok &= rec["completed"] and shr == 1 \
                and rec["bitwise_vs_unfaulted"]
        except Exception as e:
            extras["shrink_error"] = str(e)[:160]
            emit({"elastic": "shrink", "error": str(e)[:160]})
            ok = False

    # -- leg 3: remap decisions on the bus, wall still attributed ---
    from slate_tpu.obs import ledger as obs_ledger
    from slate_tpu.obs import xprof as obs_xprof
    try:
        grid = st.make_grid()
        nranks = grid.p * grid.q
        elastic.install_speeds([1.0] * (nranks // 2)
                               + [0.25] * (nranks - nranks // 2))
        obs_ledger.reset()
        obs_ledger.enable()
        c0 = dict(om.snapshot()["counters"])
        t0 = time.perf_counter()
        shard_ooc.shard_potrf_ooc(a, grid, panel_cols=w,
                                  cache_budget_bytes=0,
                                  ownership="elastic")
        wall = time.perf_counter() - t0
        c1 = dict(om.snapshot()["counters"])
        att = obs_xprof.attribute_run(
            records=obs_ledger.records("shard_potrf_ooc"))
        frac = att["total_wall_s"] / wall if wall > 0 else 0.0
        remaps = int(c1.get("ooc.shard.remaps", 0)
                     - c0.get("ooc.shard.remaps", 0))
        rec = {"wall_s": round(wall, 4), "remaps": remaps,
               "ledger_records": att["records"],
               "attributed_s": att["total_wall_s"],
               "fraction_attributed": round(frac, 4)}
        extras["elastic_ledger_attribution"] = rec
        emit(dict({"elastic": "ledger_attribution"}, **rec))
        ok &= frac >= 0.95 and remaps >= 1
    except Exception as e:
        extras["elastic_ledger_attribution_error"] = str(e)[:160]
        ok = False
    finally:
        elastic.install_speeds(None)
        obs_ledger.disable()
        obs_ledger.reset()

    emit({"metric": "elastic", "value": 1 if ok else 0,
          "unit": "suite", "vs_baseline": 1 if ok else 0,
          "extras": extras})
    return 0


def bench_faults():
    """`--faults`: resilience smoke lane (ISSUE 9) — a seeded fault
    plan injected into a small potrf_ooc stream, reporting retry
    counts (transient H2D/D2H faults absorbed by the guard, result
    bitwise the clean run's), checkpoint overhead (MUST be 0 bytes at
    the FROZEN ``resil/ckpt_every`` = 0 — the off-state contract —
    and the measured on-disk/wall cost at a real cadence), the
    interrupt->resume bitwise pin, and one shard->stream escalation
    (the degradation ladder's first rung) with its ``resil.*``
    counters in the BENCH extras."""
    import tempfile
    import numpy as np
    import slate_tpu as st
    from slate_tpu import obs
    from slate_tpu.core.methods import MethodOOC
    from slate_tpu.linalg import ooc
    from slate_tpu.resil import faults, guard

    obs.enable()
    try:
        n = int(os.environ.get("SLATE_FAULTS_N", "256"))
    except ValueError:
        n = 256
    w = max(n // 8, 32)
    nt = (n + w - 1) // w
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n)).astype(np.float32)
    a = x @ x.T / n + 4.0 * np.eye(n, dtype=np.float32)
    extras = {"n": n, "panel_cols": w, "nt": nt}
    ok = True

    def dir_bytes(d):
        return sum(os.path.getsize(os.path.join(r, f))
                   for r, _dirs, fs in os.walk(d) for f in fs)

    guard.reset_counts()
    t0 = time.perf_counter()
    L0 = ooc.potrf_ooc(a, panel_cols=w)
    clean_wall = time.perf_counter() - t0
    extras["clean_wall_s"] = round(clean_wall, 4)

    # -- off-state contract: a ckpt_path at the FROZEN cadence (0)
    # touches NOTHING and stays bit-identical
    ckdir_off = tempfile.mkdtemp(prefix="slate_faults_off_")
    L_off = ooc.potrf_ooc(a, panel_cols=w, ckpt_path=ckdir_off)
    extras["ckpt_bytes_at_every0"] = dir_bytes(ckdir_off)
    extras["ckpt_off_bitwise"] = bool(np.array_equal(L0, L_off))
    ok &= extras["ckpt_bytes_at_every0"] == 0
    ok &= extras["ckpt_off_bitwise"]

    # -- transient transfer faults absorbed by the retry guard
    guard.reset_counts()
    plan = faults.install(faults.FaultPlan([
        {"site": "h2d", "match": {"buf": "A", "idx": 1}, "times": 1},
        {"site": "d2h", "match": {"buf": "L", "idx": 2}, "times": 1},
    ], seed=0))
    t0 = time.perf_counter()
    L1 = ooc.potrf_ooc(a, panel_cols=w)
    faulted_wall = time.perf_counter() - t0
    faults.clear()
    c = guard.counts()
    extras["retry"] = {
        "injected": plan.fired(), "retries": c.get("resil.retries", 0),
        "bitwise": bool(np.array_equal(L0, L1)),
        "wall_s": round(faulted_wall, 4)}
    ok &= extras["retry"]["bitwise"] and plan.fired() == 2

    # -- interrupt at an injected fault, resume from checkpoint
    guard.reset_counts()
    ckdir = tempfile.mkdtemp(prefix="slate_faults_ck_")
    faults.install(faults.FaultPlan([
        {"site": "step", "match": {"op": "potrf_ooc", "step": nt // 2},
         "times": 1}]))
    interrupted = False
    t0 = time.perf_counter()
    try:
        ooc.potrf_ooc(a, panel_cols=w, ckpt_path=ckdir, ckpt_every=2)
    except faults.InjectedFault:
        interrupted = True
    faults.clear()
    part_wall = time.perf_counter() - t0
    ck_bytes = dir_bytes(ckdir)
    t0 = time.perf_counter()
    L2 = ooc.potrf_ooc(a, panel_cols=w, ckpt_path=ckdir, ckpt_every=2)
    resume_wall = time.perf_counter() - t0
    extras["resume"] = {
        "interrupted": interrupted, "ckpt_bytes": ck_bytes,
        "commits": guard.counts().get("resil.ckpt_commits", 0),
        "bitwise": bool(np.array_equal(L0, np.asarray(L2))),
        "interrupted_wall_s": round(part_wall, 4),
        "resume_wall_s": round(resume_wall, 4),
        "ckpt_overhead_vs_clean": round(
            (part_wall + resume_wall) / clean_wall, 3)
        if clean_wall else None}
    ok &= interrupted and extras["resume"]["bitwise"] and ck_bytes > 0

    # -- degradation ladder: sharded route fails -> single-engine
    # stream (needs the virtual-device mesh main() pins on CPU)
    try:
        guard.reset_counts()
        grid = st.make_grid()
        faults.install(faults.FaultPlan([
            {"site": "ppermute", "match": {"op": "shard_bcast"},
             "times": 999}]))
        L3 = ooc.potrf_ooc(a, panel_cols=w, grid=grid,
                           method=MethodOOC.Sharded)
        faults.clear()
        c = guard.counts()
        extras["escalation"] = {
            "retries": c.get("resil.retries", 0),
            "shard_to_stream":
                c.get("resil.fallback.shard_to_stream", 0),
            "bitwise": bool(np.array_equal(L0, L3))}
        ok &= extras["escalation"]["shard_to_stream"] == 1
        ok &= extras["escalation"]["bitwise"]
    except Exception as e:
        faults.clear()
        extras["escalation_error"] = str(e)[:160]
        ok = False

    extras["counters"] = {k: v for k, v in guard.counts().items()}
    emit({"metric": "faults", "value": 1 if ok else 0,
          "unit": "suite", "vs_baseline": 1 if ok else 0,
          "extras": extras})
    return 0


def bench_lint():
    """--lint: the slate_lint static-analysis smoke leg (ISSUE 13
    satellite). No backend, no jax — this runs the AST analyzers over
    the checkout and reports per-analyzer wall time, so the tier-1
    budget the lint consumes stays visible in the BENCH trajectory
    (the suite gates on zero live findings, same as CI)."""
    t0 = time.perf_counter()
    try:
        from tools.slate_lint import core as lint_core
        res = lint_core.run()
    except Exception as e:
        emit({"metric": "lint", "value": 0, "unit": "suite",
              "vs_baseline": 0, "error": str(e)[:160]})
        return 0
    wall = time.perf_counter() - t0
    extras = {
        "wall_s": round(wall, 3),
        "analyzers": len(res.timings),
        "timings_ms": {k: round(v * 1e3, 1)
                       for k, v in sorted(res.timings.items())},
        "findings": [f.render() for f in res.findings][:20],
        "exempted": len(res.exempted),
        "baselined": len(res.baselined),
    }
    ok = res.ok
    emit({"metric": "lint", "value": 1 if ok else 0, "unit": "suite",
          "vs_baseline": 1 if ok else 0, "extras": extras})
    return 0


def bench_serve():
    """`--serve`: the batched serving tier (ISSUE 5) — a synthetic
    lognormal problem-size stream (SLATE_SERVE_REQS requests, n
    clipped to [64, 1024]) of SPD solves pushed through the
    coalescing micro-batch queue, against per-request dispatch of the
    SAME vmapped drivers (batch size 1: bit-identical results, the
    drivers.py determinism contract). Reports matrices/sec, p50/p99
    submit-to-result latency, dispatches-saved, batch occupancy and
    padding-waste fractions — the occupancy/waste numbers also land
    in obs.snapshot() (batch.* metrics) and everything ships in the
    BENCH extras. Equal-results policy: bitwise vs the per-request
    dispatch for same-bucket exact-size requests, allclose for
    padded ones, plus an allclose spot-check against the UNBATCHED
    single-matrix core (vmap lowers batched matmuls through a
    different contraction kernel, so cross-form bitwise is not a
    thing — measured ~1e-15 relative). The ragged leg (ISSUE 15) runs
    the same stream under strategy="ragged" and gates on a >= 40%
    padding_waste_flops reduction vs the bucket strategy at equal
    results, dispatch count reported (kernels interpreted on the CPU
    tier — wall flagged, the TPU round prices it)."""
    import numpy as np
    from slate_tpu import batch, obs
    from slate_tpu.obs import metrics as om

    obs.enable()
    try:
        reqs = int(os.environ.get("SLATE_SERVE_REQS", "256"))
    except ValueError:
        reqs = 256
    rng = np.random.default_rng(0)
    # lognormal size stream: median ~180, clipped to the serving band
    sizes = np.clip(np.rint(np.exp(rng.normal(np.log(180.0), 0.6,
                                              reqs))).astype(int),
                    64, 1024)
    mats = []
    for n in sizes:
        x = rng.standard_normal((n, n)).astype(np.float32)
        mats.append(x @ x.T / n + 4.0 * np.eye(n, dtype=np.float32))
    buckets = sorted({batch.bucket_for(int(n)) for n in sizes})
    extras = {"requests": reqs, "op": "potrf",
              "n_range": [int(sizes.min()), int(sizes.max())],
              "buckets": buckets}
    emit({"serve": "stream", "requests": reqs, "buckets": buckets})

    def stream(max_batch, strategy=None):
        q = batch.CoalescingQueue(max_batch=max_batch, max_wait_us=0,
                                  strategy=strategy)
        with q:
            t0 = time.perf_counter()
            tickets = [q.submit("potrf", a) for a in mats]
            q.flush()
            outs = [t.result() for t in tickets]
            wall = time.perf_counter() - t0
            lats = sorted(t.latency_s for t in tickets)
        s = q.stats()
        rec = {"wall_s": round(wall, 3),
               "matrices_per_s": round(reqs / wall, 1),
               "p50_ms": round(lats[reqs // 2] * 1e3, 3),
               "p99_ms": round(lats[min(int(reqs * 0.99), reqs - 1)]
                               * 1e3, 3),
               "dispatches": s["dispatches"],
               "dispatches_saved": s["dispatches_saved"],
               "mean_occupancy": round(s["mean_occupancy"], 2),
               "max_occupancy": s["max_occupancy"],
               "padding_waste": round(s["mean_padding_waste"], 4),
               "padding_waste_flops":
                   round(s["mean_padding_waste_flops"], 4),
               "mean_occupancy_weighted":
                   round(s["mean_occupancy_weighted"], 2),
               "ragged_dispatches": s["ragged_dispatches"]}
        return outs, rec

    # warmup both phases (compile), then measure; jit cache persists
    for mb in (1, None):
        try:
            stream(mb)
        except Exception as e:
            extras["warmup_error"] = str(e)[:160]
            emit({"error": "serve warmup died: %s" % str(e)[:160]})
            emit({"metric": "serve", "value": 0, "unit": "suite",
                  "vs_baseline": 0, "extras": extras})
            return 0
    per_req, rec1 = stream(1)
    emit(dict({"serve": "per_request"}, **rec1))
    coal, recb = stream(None)
    emit(dict({"serve": "coalesced"}, **recb))
    extras["per_request"] = rec1
    extras["coalesced"] = recb
    ratio = rec1["dispatches"] / max(recb["dispatches"], 1)
    extras["dispatch_reduction"] = round(ratio, 2)
    extras["throughput_gain"] = round(
        recb["matrices_per_s"] / max(rec1["matrices_per_s"], 1e-9), 3)

    # equal-results: bitwise vs per-request dispatch where the request
    # hits its bucket exactly; allclose (f32) for padded requests
    exact = padded = 0
    bitwise_ok = close_ok = True
    for n, a, b in zip(sizes, per_req, coal):
        if int(n) in buckets and int(n) == batch.bucket_for(int(n)):
            exact += 1
            bitwise_ok &= bool(np.array_equal(a, b))
        else:
            padded += 1
            close_ok &= bool(np.allclose(a, b, rtol=1e-5, atol=1e-5))
    extras["equal_results"] = {
        "exact_size_requests": exact, "bitwise_ok": bitwise_ok,
        "padded_requests": padded, "allclose_ok": close_ok}
    # cross-form spot check vs the unbatched single-matrix core (one
    # jit per distinct n — sampled, not the full stream, to keep the
    # compile budget bounded)
    import jax
    from slate_tpu.batch import drivers as bd
    sample = list(range(0, reqs, max(reqs // 6, 1)))[:6]
    spot_ok = True
    for i in sample:
        ref = np.asarray(jax.jit(bd.potrf_core)(mats[i]))
        spot_ok &= bool(np.allclose(coal[i], ref, rtol=1e-4,
                                    atol=1e-4))
    extras["single_core_spot_allclose"] = spot_ok

    # ragged leg (ISSUE 15): the SAME lognormal stream through the
    # ragged strategy — the coalescing key drops the bucket dimension
    # (every potrf request shares one bucket, flushing at max_batch),
    # each flush stacks at ITS max live size with the per-element
    # sizes vector, and the masked ragged Pallas kernels bound work to
    # true extents. On the CPU tier the kernels execute under the
    # Pallas interpreter, so the wall is informational (flagged); the
    # gates are the ones hardware keeps: padding_waste_flops reduction
    # >= 40% vs the bucket strategy at equal results (allclose), and
    # no more dispatches than the bucket leg.
    ragged_ok = False
    try:
        rag, recr = stream(None, strategy="ragged")
        recr["wall_flagged"] = "interpreted Pallas kernels (CPU tier)"
        emit(dict({"serve": "ragged"}, **recr))
        extras["ragged"] = recr
        r_close = all(
            np.allclose(a, b, rtol=1e-4, atol=1e-4)
            for a, b in zip(per_req, rag))
        red = 1.0 - recr["padding_waste_flops"] / max(
            recb["padding_waste_flops"], 1e-12)
        extras["ragged_allclose_ok"] = r_close
        extras["ragged_waste_flops_reduction"] = round(red, 4)
        ragged_ok = r_close and red >= 0.4 \
            and recr["dispatches"] <= recb["dispatches"]
        emit({"metric": "serve_ragged_waste_reduction",
              "value": round(red, 3), "unit": "fraction",
              "vs_baseline": 1 if ragged_ok else 0})
    except Exception as e:
        extras["ragged_error"] = str(e)[:200]
        emit({"error": "serve ragged leg died: %s" % str(e)[:200]})

    snap = om.snapshot()
    extras["obs_batch_counters"] = {
        k: v for k, v in snap["counters"].items()
        if k.startswith("batch.")}
    extras["obs_batch_histograms"] = {
        k: v for k, v in snap["histograms"].items()
        if k.startswith("batch.")}
    ok = bitwise_ok and close_ok and spot_ok and ratio >= 10 \
        and ragged_ok
    emit({"metric": "serve_dispatch_reduction",
          "value": round(ratio, 2), "unit": "x",
          "vs_baseline": 1 if ok else 0, "extras": extras})
    return 0


def bench_serve_daemon():
    """`--serve-daemon`: the serving daemon (ISSUE 16) — a
    repeated-solve stream (the BLASX scheduler-reuse pattern: many
    solves against the SAME small set of operators) through
    :class:`slate_tpu.serve.Server` with the factor cache off vs on.
    Per round every warm operator gets BOTH a potrf and a posv
    request; cache-off that is two fused dispatches per round (one
    potrf bucket + one posv bucket), cache-on the potrf requests are
    served from cache (ZERO dispatches) and the posv requests ride
    the solve-only potrs bucket (one dispatch) — the repeat-leg gate
    is dispatch reduction >= 2x at BITWISE-equal results (the
    split-factor-vs-fused contract drivers.py pins). The drain leg
    injects a transient fault at the queue dispatch site plus one at
    ``serve_drain`` and gates on graceful drain completing every
    in-flight ticket through the retry ladder."""
    import numpy as np
    from slate_tpu import serve
    from slate_tpu.batch.queue import CoalescingQueue
    from slate_tpu.resil import faults

    try:
        n_ops = int(os.environ.get("SLATE_SERVE_DAEMON_OPS", "4"))
        rounds = int(os.environ.get("SLATE_SERVE_DAEMON_ROUNDS", "6"))
    except ValueError:
        n_ops, rounds = 4, 6
    n = 128
    rng = np.random.default_rng(7)
    operators = []
    for _ in range(n_ops):
        x = rng.standard_normal((n, n)).astype(np.float32)
        operators.append(x @ x.T + 2.0 * n
                         * np.eye(n, dtype=np.float32))
    rhss = [rng.standard_normal((n, 2)).astype(np.float32)
            for _ in range(rounds)]
    extras = {"operators": n_ops, "rounds": rounds, "n": n}
    emit({"serve_daemon": "stream", "operators": n_ops,
          "rounds": rounds})

    def run(cache_mb):
        # non-background queue: each round's requests coalesce into
        # full-occupancy buckets flushed by the first result() —
        # deterministic dispatch counts on both legs
        q = CoalescingQueue(background=False)
        srv = serve.Server(queue=q, cache_mb=cache_mb)
        outs = []
        warm_disp = 0
        t0 = time.perf_counter()
        for r in range(rounds):
            ts = []
            for a in operators:
                ts.append(srv.submit("potrf", a))
                ts.append(srv.submit("posv", a, rhss[r]))
            outs.append([np.asarray(t.result(timeout=120))
                         for t in ts])
            if r == 0:
                # round 0 is the warm phase (cache-on pays its
                # factorizations here); the gate measures the rest
                warm_disp = q.stats()["dispatches"]
        wall = time.perf_counter() - t0
        s = srv.stats()
        rec = {"wall_s": round(wall, 3),
               "dispatches_total": s["queue"]["dispatches"],
               "dispatches_repeat":
                   s["queue"]["dispatches"] - warm_disp,
               "cache": s["cache"],
               "admission": s["admission"]}
        srv.close()
        return outs, rec

    try:
        off, rec_off = run(0)
        emit(dict({"serve_daemon": "cache_off"}, **rec_off))
        on, rec_on = run(64)
        emit(dict({"serve_daemon": "cache_on"}, **rec_on))
    except Exception as e:
        extras["error"] = str(e)[:200]
        emit({"error": "serve daemon stream died: %s" % str(e)[:200]})
        emit({"metric": "serve_daemon", "value": 0, "unit": "suite",
              "vs_baseline": 0, "extras": extras})
        return 0
    extras["cache_off"] = rec_off
    extras["cache_on"] = rec_on
    ratio = rec_off["dispatches_repeat"] / max(
        rec_on["dispatches_repeat"], 1)
    extras["repeat_dispatch_reduction"] = round(ratio, 2)
    bitwise = all(
        np.array_equal(a, b)
        for ra, rb in zip(off, on) for a, b in zip(ra, rb))
    extras["bitwise_ok"] = bitwise

    # drain leg: one transient fault at the queue dispatch site and
    # one at serve_drain; the retry ladder must absorb both and every
    # in-flight ticket must still complete
    drain_ok = False
    try:
        faults.install(faults.FaultPlan([
            {"site": "batch", "match": {"op": "posv"}, "times": 1},
            {"site": "serve_drain", "times": 1},
        ]))
        srv = serve.Server(queue=CoalescingQueue(background=False),
                           cache_mb=0)
        ts = [srv.submit("posv", operators[i % n_ops], rhss[0])
              for i in range(n_ops)]
        summary = srv.drain(timeout=120)
        srv.close()
        extras["drain"] = summary
        drain_ok = (summary["drained"] == len(ts)
                    and summary["failed"] == 0)
        emit(dict({"serve_daemon": "drain"}, **summary))
    except Exception as e:
        extras["drain_error"] = str(e)[:200]
        emit({"error": "serve daemon drain leg died: %s"
              % str(e)[:200]})
    finally:
        faults.clear()

    # telemetry leg (ISSUE 18 satellite): the cache-off stream again
    # with request tracing + SLO series ON — prices the enabled-state
    # overhead (the off-state is already pinned bitwise by tests) and
    # reports the daemon's p50/p95/p99 plus the admit/queue/dispatch/
    # solve wall split from the sketches themselves
    from slate_tpu.obs import reqtrace, series
    try:
        reqtrace.enable()
        series.enable()
        traced, rec_tr = run(0)
        emit(dict({"serve_daemon": "traced"}, **rec_tr))
        extras["trace_bitwise_ok"] = all(
            np.array_equal(a, b)
            for ra, rb in zip(off, traced) for a, b in zip(ra, rb))
        lat = {}
        split = {}
        for op_ in ("potrf", "posv"):
            q_ = series.quantiles("serve.latency_s",
                                  tenant="default", op=op_)
            if q_:
                lat[op_] = {k: round(v * 1e3, 4)
                            for k, v in q_.items()}
            for ph_ in ("admit_wait", "queue_wait", "dispatch",
                        "solve"):
                sm = series.summary("serve.%s_s" % ph_,
                                    tenant="default", op=op_)
                if sm:
                    split[ph_] = round(split.get(ph_, 0.0)
                                       + sm["sum"] * 1e3, 4)
        extras["latency_ms"] = lat
        extras["phase_split_ms"] = split
        extras["reqtrace_overhead_pct"] = round(
            (rec_tr["wall_s"] / max(rec_off["wall_s"], 1e-9) - 1)
            * 100, 2)
        emit({"serve_daemon": "telemetry", "latency_ms": lat,
              "phase_split_ms": split,
              "overhead_pct": extras["reqtrace_overhead_pct"]})
    except Exception as e:
        extras["telemetry_error"] = str(e)[:200]
        emit({"error": "serve daemon telemetry leg died: %s"
              % str(e)[:200]})
    finally:
        reqtrace.reset()
        series.reset()

    ok = bitwise and ratio >= 2.0 and drain_ok
    emit({"metric": "serve_daemon_repeat_dispatch_reduction",
          "value": round(ratio, 2), "unit": "x",
          "vs_baseline": 1 if ok else 0, "extras": extras})
    return 0


def bench_obs_regression(extras):
    """`--obs` regression leg (ISSUE 14 satellite): compare THIS
    run's per-driver walls and obs counters against the most recent
    ``BENCH_r*.json`` in the checkout — the BENCH trajectory finally
    read back instead of write-only. Emits per-metric deltas (shared
    numeric extras keys as cur/base ratios, per-driver wall deltas
    when both sides ran --obs, changed counters) into
    ``extras["obs_regression"]`` plus one summary line. Best-effort:
    a missing/mismatched baseline records why and never fails the
    run."""
    import glob
    here = os.path.dirname(os.path.abspath(__file__))
    files = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
    if not files:
        extras["obs_regression"] = {"skipped": "no BENCH_r*.json"}
        return
    path = files[-1]
    try:
        with open(path) as f:
            base = json.load(f)
        parsed = base.get("parsed") or {}
        bex = parsed.get("extras") or {}
    except Exception as e:
        extras["obs_regression"] = {
            "skipped": "unreadable %s: %s"
            % (os.path.basename(path), str(e)[:80])}
        return
    out = {"baseline_file": os.path.basename(path),
           "baseline_metric": parsed.get("metric"),
           "baseline_value": parsed.get("value")}
    deltas = {}
    for k in sorted(bex):
        v, cur = bex[k], extras.get(k)
        if isinstance(v, (int, float)) and not isinstance(v, bool) \
                and isinstance(cur, (int, float)) \
                and not isinstance(cur, bool):
            deltas[k] = {"base": v, "cur": cur,
                         "ratio": round(cur / v, 4) if v else None}
        if len(deltas) >= 60:
            break
    out["metric_deltas"] = deltas
    bobs = bex.get("obs") or {}
    cobs = extras.get("obs") or {}
    bdrv = bobs.get("drivers") or {}
    cdrv = cobs.get("drivers") or {}
    if bdrv and cdrv:
        dd = {}
        for op in sorted(set(bdrv) & set(cdrv)):
            b, c = bdrv[op], cdrv[op]
            dd[op] = {"wall_base_s": b.get("wall_seconds"),
                      "wall_cur_s": c.get("wall_seconds"),
                      "calls_delta": c.get("calls", 0)
                      - b.get("calls", 0)}
        out["driver_wall_deltas"] = dd
    bc = (bobs.get("metrics") or {}).get("counters") or {}
    cc = (cobs.get("metrics") or {}).get("counters") or {}
    if bc or cc:
        cd = {}
        for k in sorted(set(bc) | set(cc)):
            if bc.get(k, 0) != cc.get(k, 0):
                cd[k] = {"base": bc.get(k, 0), "cur": cc.get(k, 0)}
            if len(cd) >= 40:
                break
        out["counter_deltas"] = cd
    extras["obs_regression"] = out
    emit({"obs": "regression", "baseline": out["baseline_file"],
          "metric_deltas": len(deltas),
          "driver_wall_deltas": len(out.get("driver_wall_deltas",
                                            {})),
          "counter_deltas": len(out.get("counter_deltas", {}))})


def bench_obs_analyze(st, tl, n, results):
    """`--obs`: compiled-program attribution for the headline driver
    (ISSUE 3): jit potrf at size n, pull the compiler cost model
    (analytic FLOPs, bytes, peak memory), the compile-vs-execute wall
    split, and the collective counts from the compiled HLO. The record
    lands in the obs analyses registry (merged into the headline
    extras) and one summary line is emitted immediately."""
    import jax
    import jax.numpy as jnp
    from slate_tpu import obs
    from slate_tpu.core.enums import Diag, MatrixType, Op, Uplo
    HI = jax.lax.Precision.HIGHEST

    @jax.jit
    def gen():
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (n, n), jnp.float32)
        return jnp.matmul(x, x.T, precision=HI) / n \
            + 4.0 * jnp.eye(n, dtype=jnp.float32)

    spd_j = gen()
    spd_j.block_until_ready()
    H = tl.TiledMatrix(data=spd_j, m=n, n=n, mb=512, nb=512,
                       mtype=MatrixType.Hermitian, uplo=Uplo.Lower,
                       op=Op.NoTrans, diag=Diag.NonUnit)

    @jax.jit
    def f(d):
        return st.potrf(dataclasses.replace(H, data=d)).data

    rec = obs.analyze("potrf_n%d" % n, f, spd_j)
    emit({"obs": "analyze", "label": rec["label"],
          "flops": rec.get("flops"),
          "peak_bytes": rec.get("peak_bytes"),
          "compile_seconds": rec.get("compile_seconds"),
          "execute_seconds": rec.get("execute_seconds"),
          "collectives": rec.get("collectives")})
    results["obs_potrf_flops_n%d" % n] = rec.get("flops")


def main():
    # SLATE_BENCH_SIZES=1024 lets CI smoke-test the full flow cheaply;
    # the driver always runs the default 16384,8192,4096. A malformed
    # falls back to the default — this script must always emit a
    # headline and exit 0.
    try:
        sizes = [int(s) for s in
                 os.environ.get("SLATE_BENCH_SIZES",
                                "16384,8192,4096").split(",") if s.strip()]
        assert sizes
    except Exception:
        sizes = [16384, 8192, 4096]
    headline_n = sizes[0]

    micro = "--micro" in sys.argv[1:]
    tune = "--tune" in sys.argv[1:]
    ooc = "--ooc" in sys.argv[1:]
    serve = "--serve" in sys.argv[1:]
    serve_daemon = "--serve-daemon" in sys.argv[1:]
    shard = "--shard" in sys.argv[1:]
    with_faults = "--faults" in sys.argv[1:]
    with_elastic = "--elastic" in sys.argv[1:]
    with_obs = "--obs" in sys.argv[1:]

    if "--lint" in sys.argv[1:]:
        # pure AST — runs (and must stay green) with no backend at all
        return bench_lint()

    if (shard or with_faults or with_elastic) and (
            os.environ.get("JAX_PLATFORMS", "").startswith("cpu")):
        # the sharded-OOC suite needs a mesh: on the CPU tier pin 8
        # virtual devices BEFORE the in-process backend initializes
        # (real hardware keeps whatever the process sees)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    compile_cache.enable()
    import jax
    emit({"platform": jax.devices()[0].platform})

    if tune:
        return bench_tune()
    if ooc:
        return bench_ooc()
    if serve_daemon:
        return bench_serve_daemon()
    if serve:
        return bench_serve()
    if shard:
        return bench_shard()
    if with_faults:
        return bench_faults()
    if with_elastic:
        return bench_elastic()

    import slate_tpu as st
    import slate_tpu.core.tiles as tl

    if with_obs:
        # metrics/bus on for the whole run: driver counters, compile
        # accounting and recompile detection accumulate alongside the
        # measurements and ship in the headline extras (ISSUE 3)
        from slate_tpu import obs
        obs.enable()
        emit({"obs": "enabled"})

    if micro:
        results = {}
        bench_micro(st, results)
        if with_obs:
            # the micro path returns before the headline emit, so the
            # obs snapshot must ride the suite line itself
            try:
                from slate_tpu import obs as _obs
                snap = _obs.snapshot()
                results["obs"] = {"metrics": snap["metrics"],
                                  "drivers": snap["drivers"],
                                  "events_recorded": snap["events"]}
            except Exception as e:
                results["obs_snapshot_error"] = str(e)[:160]
        emit({"metric": "micro", "value": 1, "unit": "suite",
              "vs_baseline": 1, "extras": results})
        return 0

    results = {}
    died = False
    for i, n in enumerate(sizes):
        try:
            # n=16384: XLA's native LU cannot compile there (scoped-
            # vmem height limit, methods.NATIVE_LU_MAX_M) and the
            # unrolled geqrf exceeds HBM under the chained harness —
            # bench_size covers gemm+potrf and bench_large adds the
            # routes that DO work at that size (fori-panel Tiled LU,
            # CALU tournament LU, scan-form geqrf). Full set at 8192
            # (+ the lookahead pair); gemm/potrf/getrf at 4096.
            full_n = 8192 if 8192 in sizes else sizes[0]
            bench_size(st, tl, n,
                       with_getrf=(n <= 8192),
                       with_geqrf=(n == full_n and n <= 8192),
                       results=results,
                       budget_scale=1.0 if i == 0 else 0.5,
                       with_lookahead=(n == full_n and n <= 8192),
                       headline_best_of=3 if n == headline_n else 1)
            if n > 8192:
                bench_large(st, tl, n, results, budget_scale=0.5)
        except Exception as e:       # belt over the per-routine braces
            died = True
            results["n%d_fatal" % n] = str(e)[:160]
            emit({"error": "n%d sweep died: %s" % (n, str(e)[:160])})
        import gc
        gc.collect()     # outside the handler: its frames pin buffers

    if os.environ.get("SLATE_BENCH_SOLVERS", "1") != "0":
        try:
            # solver-level entries (BASELINE.md ex06-ex11 configs)
            bench_solvers(st, tl, full_n, results, budget_scale=0.5)
        except Exception as e:
            died = True
            results["solvers_fatal"] = str(e)[:160]
            emit({"error": "solver sweep died: %s" % str(e)[:160]})
        gc.collect()

    if with_obs:
        try:
            # attribution at the smallest size: one extra compile,
            # bounded (the 16384 headline compile would double the
            # run's compile budget for a number that scales with n^3)
            bench_obs_analyze(st, tl, min(sizes), results)
        except Exception as e:
            died = True
            results["obs_fatal"] = str(e)[:160]
            emit({"error": "obs analyze died: %s" % str(e)[:160]})

    def ratio(a, b):
        va, vb = results.get(a), results.get(b)
        return round(va / vb, 4) if isinstance(va, float) \
            and isinstance(vb, float) and vb else None

    extras = dict(results)
    if with_obs:
        try:
            from slate_tpu import obs
            snap = obs.snapshot()
            # the metrics snapshot + collective counts ride the
            # headline JSON next to the --tune stats (ISSUE 3); bus
            # events stay out (they are the Perfetto export's payload,
            # not trajectory data)
            extras["obs"] = {"metrics": snap["metrics"],
                             "drivers": snap["drivers"],
                             "analyses": snap["analyses"],
                             "events_recorded": snap["events"]}
        except Exception as e:
            extras["obs_snapshot_error"] = str(e)[:160]
    for nn in sizes:
        for r in ("potrf", "getrf", "getrf_tntpiv", "geqrf"):
            v = ratio("%s_n%d" % (r, nn), "gemm_n%d" % nn)
            if v is not None:
                extras["%s_vs_gemm_n%d" % (r, nn)] = v
    for key in list(results):
        for r in ("posv", "gesv", "heev", "svd"):
            if key.startswith(r + "_n"):
                nn = key.split("_n")[1].split("_")[0]
                v = ratio(key, "gemm_n%s" % nn)
                if v is not None:
                    extras["%s_vs_gemm_n%s" % (r, nn)] = v

    if with_obs:
        # regression leg (ISSUE 14): read the trajectory back. AFTER
        # the *_vs_gemm_* ratios land in extras — those normalized
        # efficiency numbers are the most size-independent regression
        # signals the baseline carries
        try:
            bench_obs_regression(extras)
        except Exception as e:
            extras["obs_regression"] = {
                "skipped": "error: %s" % str(e)[:120]}

    potrf = results.get("potrf_n%d" % headline_n)
    vsb = ratio("potrf_n%d" % headline_n, "gemm_n%d" % headline_n)
    emit({
        "metric": "potrf_f32_gflops_n%d" % headline_n,
        "value": potrf if potrf is not None else 0,
        "unit": "GFLOP/s",
        "vs_baseline": vsb if vsb is not None else 0,
        "extras": extras,
    })
    # a sweep that died still left its completed lines above, but the
    # run is a failure
    return 1 if died else 0


if __name__ == "__main__":
    sys.exit(main())
