"""Polar decomposition / matrix sign function, TPU-tuned.

The spectral divide & conquer eigensolver (spectral_dc.py) needs, per
split, the orthogonal polar factor U of the shifted Hermitian matrix
H - sigma*I — the matrix sign function. The stock implementation
(jax's QDWH; algorithm family: Nakatsukasa-Bai-Gygi SIMAX 2010;
Nakatsukasa-Higham SISC 2013) starts from the maximally pessimistic
lower bound l0 = eps on sigma_min, which forces its first ~2
iterations through the QR-based form — a QR factorization of a
stacked (2n, n) matrix plus Q1 Q2^H formation per iteration, the
dominant cost of the whole eigensolver (measured v5e @4096: 123.5 ms
per polar, 55 n^3-flop-equivalents, vs 4.41 ms per 2n^3 gemm).

TPU-tuned redesign — CAPPED-WEIGHT all-Cholesky iteration:

The dynamically weighted Halley map x -> x (a + b x^2)/(1 + c x^2)
needs c ~ 1/l^2 to be optimal for the current lower bound l, and the
Cholesky evaluation of the map solves against X = c U^H U + I with
cond(X) ~ min(c, 1/sigma_min(U)^2). The stock scheme therefore
switches to the expensive QR form whenever c > 100. Instead, this
implementation CAPS the weights: c_k = min(c_opt(l_k), c_max) with
a = 2 sqrt(1 + c) - 1 (the fixed-point normalization f(1) = 1 and
the optimal-family relation b = (a-1)^2/4 are kept, so each capped
step is still a valid sign-iteration, just sub-optimally weighted).
Consequences, both measured here:
  * cond(X) <= 1 + c_max stays inside the dtype's Cholesky comfort
    zone, so EVERY iteration runs the Cholesky form (one Gram matmul
    + potrf + two triangular solves, ~4.3 n^3) — the (2n, n)-QR
    phase vanishes;
  * tiny singular values grow by ~a ~ 2 sqrt(c_max) per capped step
    (vs 3x for unweighted Halley), so starting from the SAFE l0 = eps
    costs only ~2 extra Cholesky iterations instead of the ~5 slow
    tail steps a lifted-l0 scheme pays when the lift guess is wrong
    (first cut of this module lifted l0 to 1e-3: measured 9
    iterations on a v5e 4096 split because real gaps at the median
    are ~spread/n ~ l0).

A final Newton-Schulz refinement (4 n^3) restores orthogonality lost
to the mildly ill-conditioned early solves, same role as in the
stock implementation. No H factor is formed (the eigensolver only
consumes U; the stock qdwh always forms h = u^H x and symmetrizes).

The scalar weight recurrence runs ON DEVICE (f32), so one compiled
program serves every split of the D&C recursion; the stock version
evaluates the schedule in Python floats at trace time, baking one l0
into the compiled program.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST

#: weight caps keeping cond(c U^H U + I) ~ c inside the dtype's
#: Cholesky range: forward error of the solves ~ eps * c, which must
#: stay well below 1 for the iteration's self-correction (and the
#: closing Newton-Schulz) to absorb it.
C_MAX_F32 = 3.0e5
C_MAX_F64 = 1.0e12


def _capped_params(l, c_max):
    """Weighted Halley coefficients for lower bound l, with the
    c-weight capped at c_max (module doc). Returns (a, b, c, l').

    The schedule runs in f32 scalars; 1/l^4 overflows f32 below
    l ~ 1e-8, so l is clamped — harmless, because a_opt(1e-8) ~ 7e10
    already exceeds every cap, i.e. the capped branch governs there
    (measured failure before the clamp: f64 l0 = eps64 = 2.2e-16 ->
    inf - inf -> NaN polar)."""
    l = jnp.maximum(l, 1e-8)
    l2 = l * l
    dd = jnp.cbrt(4.0 * (1.0 / l2 - 1.0) / l2)
    sqd = jnp.sqrt(1.0 + dd)
    a_opt = sqd + jnp.sqrt(2.0 - dd + 2.0 * (2.0 - l2) / (l2 * sqd))
    # capped family member: a = 2 sqrt(1+c) - 1 solves a+b-1 = c with
    # b = (a-1)^2/4
    a_cap = 2.0 * jnp.sqrt(1.0 + c_max) - 1.0
    a = jnp.minimum(a_opt, a_cap)
    b = (a - 1.0) ** 2 / 4.0
    c = a + b - 1.0
    lnew = l * (a + b * l2) / (1.0 + c * l2)
    lnew = jnp.clip(lnew, l, 1.0)
    return a, b, c, lnew


def _lift_estimate(sg, a, b, c):
    """Lower bound of the scalar map f(x) = x (a + b x^2)/(1 + c x^2)
    over the whole interval [sg, 1], given a lower bound sg on the
    pre-step sigma_min. In the capped-weight regime f is NON-monotone
    on [sg, 1]: writing e = b/c, f(x) = e x + (a-e) x/(1 + c x^2) has
    an interior dip (~0.12 in f32, asymptotically 2 sqrt(e (a-e)/c)),
    so mapping sg through f alone can EXCEED the true post-step
    sigma_min when a singular value sits near the dip — up to ~8x,
    breaking the l-is-a-lower-bound invariant the whole schedule
    rests on (ADVICE r5). The safe lift is the interval minimum
    min(f(sg), f(x*)) with x* the analytic interior minimizer:
    f'(x) = 0 with s = 1 + c x^2 gives e s^2 - (a-e) s + 2(a-e) = 0,
    whose larger root is the dip (the smaller is the local max); no
    real root (or x* outside (sg, 1)) means f is monotone on the
    interval and f(sg) stands. A (1 - 1e-5) deflation absorbs the
    f32 scalar roundoff of the root evaluation."""
    e = b / c
    fsg = sg * (a + b * sg * sg) / (1.0 + c * sg * sg)
    amee = a - e
    disc = amee * (amee - 8.0 * e)
    tiny = jnp.asarray(jnp.finfo(jnp.float32).tiny, fsg.dtype)
    s = (amee + jnp.sqrt(jnp.maximum(disc, 0.0))) \
        / jnp.maximum(2.0 * e, tiny)
    x2 = jnp.maximum(s - 1.0, 0.0) / c
    x = jnp.sqrt(x2)
    fdip = x * (a + b * x2) / (1.0 + c * x2)
    valid = (disc > 0.0) & (x > sg) & (x < 1.0)
    return jnp.where(valid, jnp.minimum(fsg, fdip), fsg) \
        * (1.0 - 1e-5)


#: widest block of right-hand-side columns one forward substitution
#: takes (`_forward_solve`)
FORWARD_SOLVE_COLS = 1280


def _forward_solve(r, b):
    """r^{-1} b for lower-triangular r, a block of b's columns at a
    time under one `fori_loop`. The columns are independent, so this
    is the same arithmetic as one solve; but the TPU's forward
    substitution with an (n, n) right-hand side counts temporaries
    that grow with n/128 blocks times its width (3.7 n^2 words at
    n=2176, 14.7 at 4096, 35 at 8192: 9.5 GB, compiled for a described
    v5e, PR 33), where the back substitution counts none. In blocks
    of at most FORWARD_SOLVE_COLS columns it counts an eighth of that
    at n=8192, in one copy of the solve's code."""
    n, m = b.shape
    chunks = 1
    while m // chunks > FORWARD_SOLVE_COLS and m % (2 * chunks) == 0:
        chunks *= 2
    if chunks == 1:
        return jax.lax.linalg.triangular_solve(
            r, b, left_side=True, lower=True)
    w = m // chunks
    zero = jnp.zeros((), jnp.int32)

    def body(i, out):
        at = (zero, i * w)
        t = jax.lax.linalg.triangular_solve(
            r, jax.lax.dynamic_slice(b, at, (n, w)), left_side=True,
            lower=True)
        return jax.lax.dynamic_update_slice(out, t, at)

    return jax.lax.fori_loop(0, chunks, body, jnp.zeros_like(b))


def _chol_halley(u, a, b, c):
    """One weighted Halley iteration in the Cholesky form:
    u <- (b/c) u + (a - b/c) u (I + c u^H u)^{-1} (SISC 2013 eq. 5.5
    family: the inverse applied via Cholesky of I + c u^H u and two
    triangular solves). Returns (u_new, r) with r the lower Cholesky
    factor of x = I + c u^H u, which `_sigma_min_estimate` rides on:
    an iteration is ONE Gram product, ONE factorization and two
    solves whether or not the schedule asks for the estimate."""
    n = u.shape[0]
    dt = u.dtype
    e = b / c
    g = jnp.matmul(u.conj().T, u, precision=HI)
    x = c.astype(dt) * g + jnp.eye(n, dtype=dt)
    r = jax.lax.linalg.cholesky(x, symmetrize_input=False)
    # z = u x^{-1}: with x = r r^H, solve r t = u^H, then r^H s = t,
    # giving s = x^{-1} u^H and z = s^H
    z = _forward_solve(r, u.conj().T)
    z = jax.lax.linalg.triangular_solve(
        r, z, left_side=True, lower=True, transpose_a=True,
        conjugate_a=True).conj().T
    return e.astype(dt) * u + (a - e).astype(dt) * z, r


def _sigma_min_estimate(r, c, it=0):
    """Estimate of sigma_min(u), the PRE-map iterate's smallest
    singular value, from the Cholesky factor r of x = I + c u^H u that
    `_chol_halley` already holds: power iteration on x^{-1} =
    (r r^H)^{-1} via per-step triangular solves with a thin block of
    vectors (O(n^2 k) — noise next to the step's 4.3 n^3). The
    Rayleigh-type ratio ||x^{-1} v|| / ||v|| lower-bounds
    lambda_max(x^{-1}), so 1/ratio UPPER-bounds lambda_min(x) =
    1 + c sigma_min(u)^2 and the derived sigma_est is an
    over-estimate — callers must apply a safety factor before using it
    as a schedule lower bound. The returned `reliable` flag
    additionally requires the power iteration itself to have CONVERGED
    (relative ratio delta between the last two steps below 5%): 4
    steps from a ~1/sqrt(n) overlap can leave the ratio far below
    lambda_max(x^{-1}) when small singular values cluster, inflating
    sigma_est beyond what the 0.7 safety factor absorbs (ADVICE r5).
    `it` (the schedule iteration counter) is folded into the estimator
    PRNG key so a start block that happens to be orthogonal to the
    small-eigenvector subspace is not retried identically every
    iteration. Returns (sigma_est f32, reliable)."""
    n = r.shape[0]
    dt = r.dtype
    # start block: e_j at the weakest Cholesky pivot (strongly aligned
    # with the small eigenvector) + fixed pseudo-random columns
    k = 4
    rdiag = jnp.abs(jnp.diagonal(r))
    j0 = jnp.argmin(rdiag)
    v0 = jnp.zeros((n, k), dt).at[j0, 0].set(1.0)
    key = jax.random.fold_in(jax.random.PRNGKey(7),
                             jnp.asarray(it, jnp.int32))
    vr = jax.random.normal(key, (n, k - 1), jnp.float32).astype(dt)
    v = v0.at[:, 1:].set(vr)
    v = v / jnp.sqrt(jnp.sum(jnp.abs(v) ** 2, axis=0))[None, :]

    rdt = jnp.zeros((), dt).real.dtype

    def pstep(i, carry):
        v, _, last = carry
        w = jax.lax.linalg.triangular_solve(
            r, v, left_side=True, lower=True)
        w = jax.lax.linalg.triangular_solve(
            r, w, left_side=True, lower=True, transpose_a=True,
            conjugate_a=True)
        nrm = jnp.sqrt(jnp.sum(jnp.abs(w) ** 2, axis=0))
        ratio = jnp.max(nrm)                 # <= lambda_max(x^{-1})
        tiny = jnp.finfo(rdt).tiny
        return w / jnp.maximum(nrm, tiny)[None, :], last, ratio

    _, ratio_prev, ratio = jax.lax.fori_loop(
        0, 4, pstep, (v, jnp.ones((), rdt), jnp.ones((), rdt)))
    lam_min_x = 1.0 / jnp.maximum(ratio, jnp.finfo(rdt).tiny)
    sig2 = (lam_min_x - 1.0) / c.astype(rdt)
    # converged power iteration (docstring): the last two ratios agree
    # to 5%, so the 0.7 caller safety factor covers the residual gap
    pw_ok = jnp.abs(ratio - ratio_prev) <= 0.05 * ratio
    reliable = (lam_min_x - 1.0 > 0.5) & pw_ok
    sig = jnp.sqrt(jnp.maximum(sig2, 0.0))
    return sig.astype(jnp.float32), reliable


@partial(jax.jit, static_argnames=("max_iterations", "newton_schulz"))
def polar_unitary(x: jax.Array, l0: Optional[float] = None,
                  eps: Optional[float] = None,
                  max_iterations: int = 14,
                  newton_schulz: bool = True):
    """Orthogonal polar factor of square x by capped-weight
    all-Cholesky dynamically weighted Halley iteration (module doc).
    For Hermitian x this is the matrix sign function up to the
    spectral split.

    Returns (u, num_iters, converged). The weight schedule runs
    on-device; iteration continues until both the l-schedule reaches
    1 and the iterate stops moving (||u_k - u_{k-1}||_F below the
    cube-rooted tolerance — cubic convergence makes the kept iterate
    a full tolerance better than the measured difference)."""
    dt = x.dtype
    if eps is None:
        eps = float(jnp.finfo(dt).eps)
    if l0 is None:
        l0 = eps
    c_max = C_MAX_F64 if jnp.finfo(dt).eps < 1e-10 else C_MAX_F32
    tol_l = 5.0 * eps
    tol_norm = jnp.cbrt(5.0 * eps)

    # alpha >= ||x||_2 via sqrt(||x||_1 ||x||_inf)
    one_norm = jnp.max(jnp.sum(jnp.abs(x), axis=0))
    inf_norm = jnp.max(jnp.sum(jnp.abs(x), axis=1))
    alpha_inv = jax.lax.rsqrt(one_norm) * jax.lax.rsqrt(inf_norm)
    alpha_inv = jnp.where(one_norm == 0, 1.0, alpha_inv)
    u0 = x * alpha_inv.astype(dt)
    xnorm = jnp.sqrt(jnp.sum(jnp.abs(u0) * jnp.abs(u0)))

    def cond_f(state):
        u, l, k, diff = state
        unfinished = (l + tol_l < 1.0) | (diff > tol_norm)
        return unfinished & (k < max_iterations)

    #: run the sigma_min estimator only while the schedule is still in
    #: the capped-growth phase — once l is macroscopic the optimal
    #: weights converge in ~2 steps and the solves would be pure waste
    est_gate = 0.02

    def body_f(state):
        u, l, k, _ = state
        a, b, c, lnew = _capped_params(l, c_max)
        u2, r = _chol_halley(u, a, b, c)

        def lifted(r):
            # bound the NEW iterate's sigma_min from the (pre-step,
            # safety-deflated) estimate via the INTERVAL minimum of
            # this step's scalar map (_lift_estimate — f is
            # non-monotone under capped weights, so f(sg) alone is
            # not a bound); estimator over-estimates (docstring), so
            # only lift the schedule, never finish it outright
            sig, rel = _sigma_min_estimate(r, c, k)
            lest = _lift_estimate(0.7 * sig, a, b, c)
            lest = jnp.clip(lest, 0.0, 0.98)
            return jnp.where(rel, jnp.maximum(lnew, lest), lnew)

        # only the estimator's thin solves sit under the branch: the
        # Gram product, the factorization and the two full solves are
        # in the program once (they were in it twice, one copy a
        # branch, which doubled every bucket's share of the
        # eigensolver's executable; PR 33)
        lnew = jax.lax.cond(l < est_gate, lifted, lambda r: lnew, r)
        diff = jnp.sqrt(jnp.sum(jnp.abs(u2 - u) ** 2))
        return u2, lnew, k + 1, diff

    u, l, k, diff = jax.lax.while_loop(
        cond_f, body_f,
        (u0, jnp.asarray(l0, jnp.float32),
         jnp.zeros((), jnp.int32), xnorm))

    if newton_schulz:
        g = jnp.matmul(u.conj().T, u, precision=HI)
        u = 1.5 * u - 0.5 * jnp.matmul(u, g, precision=HI)

    converged = diff <= tol_norm
    return u, k, converged


def sign_hermitian(h: jax.Array, l0: Optional[float] = None,
                   general=False):
    """Matrix sign of a Hermitian matrix (the spectral-split operator:
    sign(H - sigma I) separates the spectrum at sigma). The sign of a
    Hermitian matrix is Hermitian; symmetrizing removes the skew part
    left by finite iteration. Where `general` (a traced flag) is set,
    h is any square matrix and its polar factor is returned as the
    iteration left it: one program serves both uses
    (spectral_dc.dc_sign)."""
    u, k, conv = polar_unitary(h, l0=l0)
    return jnp.where(general, u, 0.5 * (u + u.conj().T)), k, conv
