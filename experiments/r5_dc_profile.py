"""Decompose eigh_dc cost on chip: capped polar, split, chol-step."""
import os, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import _slope, emit
import jax, jax.numpy as jnp
from slate_tpu.linalg.polar import polar_unitary, _chol_halley
from slate_tpu.linalg.spectral_dc import _sign_split, _split_basis, eigh_dc
HI = jax.lax.Precision.HIGHEST

def guarded(name, fn):
    try:
        fn()
    except Exception as e:
        emit({"metric": name, "error": str(e)[:200]})

for n in (4096, 8192):
    @jax.jit
    def gen(n=n):
        x = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
        return jnp.matmul(x, x.T, precision=HI) / n + jnp.eye(n, dtype=jnp.float32)
    an = gen(); an.block_until_ready()
    sig = jnp.median(jnp.diagonal(an))
    hs = an - sig * jnp.eye(n, dtype=jnp.float32)

    def m_iters(hs=hs, n=n):
        u, k, conv = polar_unitary(hs)
        emit({"metric": "polar_iters_%d" % n, "value": int(k), "conv": bool(conv)})
    guarded("it%d" % n, m_iters)

    def m_polar(hs=hs, n=n):
        def f(d, aux):
            u, k, c = polar_unitary(d)
            return d + u * 1e-30
        t = _slope(f, hs, hs, est_hint=0.06 * (n / 4096) ** 3, reps=3, target=0.3)
        emit({"metric": "polar_%d_ms" % n, "value": round(t * 1e3, 1)})
    guarded("polar%d" % n, m_polar)

    def m_chstep(hs=hs, n=n):
        a = jnp.asarray(3.0, jnp.float32)
        b = jnp.asarray(1.0, jnp.float32)
        c = jnp.asarray(3.0, jnp.float32)
        def f(d, aux):
            return _chol_halley(d, a, b, c)[0] * (1.0 - 1e-30)
        t = _slope(f, hs, hs, est_hint=0.015 * (n / 4096) ** 3, reps=3, target=0.3)
        emit({"metric": "chol_step_%d_ms" % n, "value": round(t * 1e3, 1)})
    guarded("chstep%d" % n, m_chstep)

    def m_split(an=an, n=n):
        def f(d, aux):
            m = jnp.asarray(n, jnp.int32)
            Q, W, _ = _split_basis(d, _sign_split(d, m, None)[0], m)
            return d + Q * 1e-30 + W * 1e-30
        t = _slope(f, an, an, est_hint=0.15 * (n / 4096) ** 3, reps=3, target=0.3)
        emit({"metric": "split_%d_ms" % n, "value": round(t * 1e3, 1)})
    guarded("split%d" % n, m_split)

    def m_dc(an=an, n=n):
        # a host agenda dispatches eigh_dc's programs: it takes a
        # concrete array and cannot sit inside _slope's jit
        import time
        jax.block_until_ready(eigh_dc(an)[:2])
        t0 = time.perf_counter()
        jax.block_until_ready(eigh_dc(an)[:2])
        t = time.perf_counter() - t0
        emit({"metric": "eigh_dc_%d_ms" % n, "value": round(t * 1e3, 1),
              "nominal_gflops": round(4 / 3 * n**3 / t / 1e9, 1)})
    guarded("dc%d" % n, m_dc)
emit({"metric": "dc_profile_done"})
