"""Round-5 profiling: decompose the heev/svd cost on the chip.

VERDICT r4 weak #2: "nobody has profiled where the time goes". This
script times, on the real TPU:
  1. jax.lax.linalg.eigh (the QDWH spectral D&C Auto path) @ 4096, 8192
  2. one qdwh polar decomposition @ 4096 (per-split dominant cost)
  3. one complete QR @ 4096 (subspace extraction per split)
  4. the Jacobi base case @ 256 (and batched x16)
  5. he2hb stage-1 @ 4096/8192 (staged-path ingredient)
  6. stedc_solve on a tridiagonal @ 4096/8192 (staged-path ingredient)
  7. gemm reference rate @ 4096

Timing uses bench.py's _slope (chained fori two-point slope) with a
scalar fetch as the sync point.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _slope, emit  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

HI = jax.lax.Precision.HIGHEST


def sym(n, key=0):
    @jax.jit
    def gen():
        x = jax.random.normal(jax.random.PRNGKey(key), (n, n), jnp.float32)
        return jnp.matmul(x, x.T, precision=HI) / n + jnp.eye(n, dtype=jnp.float32)
    a = gen()
    a.block_until_ready()
    return a


def guarded(name, fn):
    try:
        fn()
    except Exception as e:
        emit({"metric": name, "error": str(e)[:200]})


def main():
    # 7. gemm reference
    a4 = sym(4096)

    def m_gemm():
        t = _slope(lambda c, g: jnp.matmul(g, c, precision=HI) * (1.0 / 4096),
                   a4, a4, est_hint=5e-3, reps=3, target=0.4)
        emit({"metric": "gemm_4096_ms", "value": round(t * 1e3, 2),
              "gflops": round(2 * 4096**3 / t / 1e9, 1)})
    guarded("gemm", m_gemm)

    # 1. full eigh
    for n in (4096, 8192):
        an = sym(n)

        def m_eigh(an=an, n=n):
            def f(d, aux):
                v, w = jax.lax.linalg.eigh(d)   # (vectors, values)
                return d + v * 1e-30 + w[None, :] * 1e-30
            t = _slope(f, an, an, est_hint=0.7 * (n / 4096) ** 3, reps=3,
                       target=0.3)
            emit({"metric": "lax_eigh_%d_ms" % n, "value": round(t * 1e3, 1),
                  "nominal_gflops": round(4 / 3 * n**3 / t / 1e9, 1)})
        guarded("eigh_%d" % n, m_eigh)

    # 2. one qdwh polar @4096 (hermitian shifted matrix, like a split)
    from jax._src.tpu.linalg import qdwh as _qdwh

    def m_qdwh():
        def f(d, aux):
            u, h, iters, conv = _qdwh.qdwh(d, is_hermitian=True)
            return d + u * 1e-30
        t = _slope(f, a4, a4, est_hint=0.2, reps=3, target=0.3)
        emit({"metric": "qdwh_4096_ms", "value": round(t * 1e3, 1),
              "xn3_flops": round(t * 30.7e12 / 4096**3, 1)})
    guarded("qdwh", m_qdwh)

    def m_qdwh_iters():
        u, h, iters, conv = _qdwh.qdwh(a4, is_hermitian=True)
        emit({"metric": "qdwh_4096_iters", "value": int(iters)})
    guarded("qdwh_iters", m_qdwh_iters)

    # 3. complete QR @4096 (subspace extraction); also @2048
    for n in (2048, 4096):
        an = sym(n)

        def m_qr(an=an, n=n):
            def f(d, aux):
                q, _ = jnp.linalg.qr(d, mode="complete")
                return d + q * 1e-30
            t = _slope(f, an, an, est_hint=0.05 * (n / 4096) ** 3, reps=3,
                       target=0.3)
            emit({"metric": "qr_complete_%d_ms" % n, "value": round(t * 1e3, 1)})
        guarded("qr_%d" % n, m_qr)

    # 4. Jacobi base case @256, single and batched
    a256 = sym(256)

    def m_jacobi():
        def f(d, aux):
            v, w = jax.lax.linalg.eigh(
                d, sort_eigenvalues=False,
                implementation=jax.lax.linalg.EighImplementation.JACOBI)
            return d + v * 1e-30
        t = _slope(f, a256, a256, est_hint=5e-3, reps=3, target=0.3)
        emit({"metric": "jacobi_256_ms", "value": round(t * 1e3, 2)})
    guarded("jacobi", m_jacobi)

    def m_jacobi_batch():
        ab = jnp.broadcast_to(a256, (16, 256, 256)) + \
            1e-3 * jax.random.normal(jax.random.PRNGKey(9), (16, 256, 256))

        def f(d, aux):
            v, w = jax.lax.linalg.eigh(
                d, sort_eigenvalues=False,
                implementation=jax.lax.linalg.EighImplementation.JACOBI)
            return d + v * 1e-30
        t = _slope(f, ab, ab, est_hint=5e-2, reps=3, target=0.3)
        emit({"metric": "jacobi_256_x16_ms", "value": round(t * 1e3, 2)})
    guarded("jacobi_batch", m_jacobi_batch)

    # 5. he2hb stage 1 (nb=512) @4096/8192
    import dataclasses
    from slate_tpu.core.tiles import TiledMatrix
    from slate_tpu.core.enums import Diag, MatrixType, Op, Uplo
    from slate_tpu.linalg.eig import he2hb

    for n in (4096, 8192):
        an = sym(n)
        H = TiledMatrix(data=an, m=n, n=n, mb=512, nb=512,
                        mtype=MatrixType.Hermitian, uplo=Uplo.Lower,
                        op=Op.NoTrans, diag=Diag.NonUnit)

        def m_he2hb(an=an, H=H, n=n):
            def f(d, aux):
                B, Q = he2hb(dataclasses.replace(H, data=d), want_q=True)
                return d + B.data * 1e-30 + Q.data * 1e-30
            t = _slope(f, an, an, est_hint=0.1 * (n / 4096) ** 3, reps=3,
                       target=0.3)
            emit({"metric": "he2hb_%d_nb512_ms" % n, "value": round(t * 1e3, 1)})
        guarded("he2hb_%d" % n, m_he2hb)

    # 6. stedc_solve on a tridiagonal @4096/8192
    from slate_tpu.linalg.stedc import stedc_solve

    for n in (4096, 8192):
        key = jax.random.PRNGKey(3)
        d0 = jax.random.normal(key, (n,), jnp.float32)
        e0 = jax.random.normal(jax.random.PRNGKey(4), (n - 1,), jnp.float32)

        def m_stedc(d0=d0, e0=e0, n=n):
            def f(d, e):
                w, v = stedc_solve(d, e)
                return d + w * 1e-30 + v[:, 0] * 1e-30
            t = _slope(f, d0, e0, est_hint=0.2 * (n / 4096) ** 2, reps=3,
                       target=0.3)
            emit({"metric": "stedc_%d_ms" % n, "value": round(t * 1e3, 1)})
        guarded("stedc_%d" % n, m_stedc)

    emit({"metric": "profile_done", "value": 1})


if __name__ == "__main__":
    main()
