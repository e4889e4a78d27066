"""Round-5 session-2 profile: the numbers that decide the eigensolver
redesign. bf16 gemm rate (is low-precision a 2-4x lever?), polar @8192
(iters x per-iter cost), QR-complete @8192 (subspace extraction cost),
vmapped-vs-sequential Jacobi leaves (does a level-batched agenda pay?),
and a bf16 Halley step (can early polar iterations run at bf16 rate?).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import _slope, emit  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from slate_tpu.linalg.polar import polar_unitary, _chol_halley  # noqa: E402

HI = jax.lax.Precision.HIGHEST


def guarded(name, fn):
    try:
        fn()
    except Exception as e:
        emit({"metric": name, "error": str(e)[:200]})


# ---- bf16 vs f32-HIGHEST gemm rate --------------------------------------
for n in (4096, 8192):
    @jax.jit
    def gen(n=n):
        x = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
        return x, x.astype(jnp.bfloat16)

    xf, xb = gen()
    xf.block_until_ready()

    def m_f32(n=n, xf=xf):
        t = _slope(lambda c, a: jnp.matmul(a, c, precision=HI) * (1.0 / n),
                   xf, xf, est_hint=5e-3 * (n / 4096.0) ** 3, reps=3,
                   target=0.4)
        emit({"metric": "gemm_f32_hi_%d" % n,
              "gflops": round(2.0 * n ** 3 / t / 1e9, 1)})

    def m_bf16(n=n, xb=xb):
        t = _slope(lambda c, a: jnp.matmul(a, c,
                                           precision=jax.lax.Precision.DEFAULT)
                   .astype(jnp.bfloat16) * (1.0 / n),
                   xb, xb, est_hint=1e-3 * (n / 4096.0) ** 3, reps=3,
                   target=0.4)
        emit({"metric": "gemm_bf16_%d" % n,
              "gflops": round(2.0 * n ** 3 / t / 1e9, 1)})

    def m_f32_default(n=n, xf=xf):
        # f32 inputs, DEFAULT precision (bf16x6 or bf16x3 passes?)
        t = _slope(lambda c, a: jnp.matmul(a, c,
                                           precision=jax.lax.Precision.DEFAULT)
                   * (1.0 / n),
                   xf, xf, est_hint=2e-3 * (n / 4096.0) ** 3, reps=3,
                   target=0.4)
        emit({"metric": "gemm_f32_default_%d" % n,
              "gflops": round(2.0 * n ** 3 / t / 1e9, 1)})

    guarded("gemm_f32_hi_%d" % n, m_f32)
    guarded("gemm_bf16_%d" % n, m_bf16)
    guarded("gemm_f32_default_%d" % n, m_f32_default)

# ---- polar @8192: iteration count and total time ------------------------
n = 8192


@jax.jit
def gen_h(n=n):
    x = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
    a = jnp.matmul(x, x.T, precision=HI) / n + jnp.eye(n, dtype=jnp.float32)
    sig = jnp.median(jnp.diagonal(a))
    return a, a - sig * jnp.eye(n, dtype=jnp.float32)


an, hs = gen_h()
an.block_until_ready()


def m_polar_iters():
    u, k, conv = polar_unitary(hs)
    emit({"metric": "polar_iters_8192", "value": int(k), "conv": bool(conv)})


def m_polar():
    def f(d, aux):
        u, k, c = polar_unitary(d)
        return d + u * 1e-30
    t = _slope(f, hs, hs, est_hint=0.6, reps=3, target=0.4)
    emit({"metric": "polar_8192_ms", "value": round(t * 1e3, 1)})


def m_chstep():
    a = jnp.asarray(3.0, jnp.float32)
    b = jnp.asarray(1.0, jnp.float32)
    c = jnp.asarray(3.0, jnp.float32)

    def f(d, aux):
        return _chol_halley(d, a, b, c)[0] * (1.0 - 1e-30)
    t = _slope(f, hs, hs, est_hint=0.12, reps=3, target=0.4)
    emit({"metric": "chol_step_8192_ms", "value": round(t * 1e3, 1)})


def m_chstep_bf16():
    # the same Halley step with the gram + solves in bf16 storage:
    # viability + rate of a low-precision early iteration
    a = jnp.asarray(3.0, jnp.float32)
    b = jnp.asarray(1.0, jnp.float32)
    c = jnp.asarray(3.0, jnp.float32)

    def step_bf(u, a, b, c):
        ub = u.astype(jnp.bfloat16)
        g = jnp.matmul(ub.T, ub, precision=jax.lax.Precision.DEFAULT)
        g = g.astype(jnp.float32)
        x = c * g + jnp.eye(u.shape[0], dtype=jnp.float32)
        r = jax.lax.linalg.cholesky(x, symmetrize_input=False)
        z = jax.lax.linalg.triangular_solve(
            r, u.T, left_side=True, lower=True)
        z = jax.lax.linalg.triangular_solve(
            r, z, left_side=True, lower=True, transpose_a=True).T
        e = b / c
        return e * u + (a - e) * z

    def f(d, aux):
        return step_bf(d, a, b, c) * (1.0 - 1e-30)
    t = _slope(f, hs, hs, est_hint=0.08, reps=3, target=0.4)
    emit({"metric": "chol_step_bf16gram_8192_ms", "value": round(t * 1e3, 1)})


def m_qr_complete():
    def f(d, aux):
        q, _ = jnp.linalg.qr(d, mode="complete")
        return d + q * 1e-30
    t = _slope(f, hs, hs, est_hint=0.11, reps=3, target=0.4)
    emit({"metric": "qr_complete_8192_ms", "value": round(t * 1e3, 1)})


def m_trisolve_8192():
    # one full-width triangular solve at 8192 (polar inner op)
    r = jnp.tril(an) + 8.0 * jnp.eye(n, dtype=jnp.float32)

    def f(d, aux):
        return jax.lax.linalg.triangular_solve(
            aux, d, left_side=True, lower=True) * (1.0 - 1e-30)
    t = _slope(f, hs, r, est_hint=0.02, reps=3, target=0.4)
    emit({"metric": "trisolve_8192_full_ms", "value": round(t * 1e3, 1)})


guarded("polar_iters_8192", m_polar_iters)
guarded("polar_8192", m_polar)
guarded("chstep_8192", m_chstep)
guarded("chstep_bf16_8192", m_chstep_bf16)
guarded("qr_complete_8192", m_qr_complete)
guarded("trisolve_8192", m_trisolve_8192)


# ---- batched leaf eigh: vmap(32 x 256) vs known 1.92 ms sequential ------
def m_jacobi_batched():
    @jax.jit
    def genb():
        x = jax.random.normal(jax.random.PRNGKey(2), (32, 256, 256),
                              jnp.float32)
        return jnp.einsum("bij,bkj->bik", x, x) / 256

    hb = genb()
    hb.block_until_ready()

    def f(d, aux):
        v, w = jax.vmap(lambda h: jax.lax.linalg.eigh(
            h, symmetrize_input=False))(d)
        return d + v * 1e-30
    t = _slope(f, hb, hb, est_hint=0.03, reps=3, target=0.4)
    emit({"metric": "jacobi_vmap32x256_ms", "value": round(t * 1e3, 1)})


def m_polar_batched():
    # 2 x 4096 batched polar-step matmul/chol/solve (level-2 agenda
    # batching candidate): per-step cost vs 2x sequential 4096 steps
    @jax.jit
    def genb():
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 4096, 4096),
                              jnp.float32)
        return jnp.einsum("bij,bkj->bik", x, x) / 4096

    hb = genb()
    hb.block_until_ready()
    a = jnp.asarray(3.0, jnp.float32)
    b = jnp.asarray(1.0, jnp.float32)
    c = jnp.asarray(3.0, jnp.float32)

    def f(d, aux):
        return jax.vmap(lambda u: _chol_halley(u, a, b, c)[0])(d) \
            * (1.0 - 1e-30)
    t = _slope(f, hb, hb, est_hint=0.03, reps=3, target=0.4)
    emit({"metric": "chol_step_vmap2x4096_ms", "value": round(t * 1e3, 1)})


guarded("jacobi_vmap32x256", m_jacobi_batched)
guarded("chol_step_vmap2x4096", m_polar_batched)

emit({"metric": "r5b_polar_profile_done"})
