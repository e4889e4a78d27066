"""Shared mixed-precision iterative-refinement machinery (reference
src/gesv_mixed.cc, posv_mixed.cc, gesv_mixed_gmres.cc,
posv_mixed_gmres.cc).

The pattern: factor in lo precision (TPU-native pair f32->bf16; f64->f32
when x64 enabled), refine the hi-precision residual with lo-precision
solves, optionally fall back to a full-precision solve (reference
Option::UseFallbackSolver). FGMRES-IR right-preconditions restarted
GMRES with the lo solve.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from ..core.exceptions import slate_assert
from ..core.options import Option, OptionsLike, get_option
from ..core.tiles import TiledMatrix


def lo_dtype(dtype):
    """Precision pairs: reference pairs (d->s, z->c); TPU adds f32->bf16."""
    d = jnp.dtype(dtype)
    if d == jnp.float64:
        return jnp.float32
    if d == jnp.complex128:
        return jnp.complex64
    if d == jnp.float32:
        return jnp.bfloat16
    return d


def demote(name: str, A: TiledMatrix, opts: OptionsLike = None
           ) -> TiledMatrix:
    """A in its lo dtype, once a call, under the driver's
    `<name>::demote` phase."""
    import dataclasses
    from ..utils.trace import phases
    r = A.resolve()
    with phases(opts)(name + "::demote"):
        return dataclasses.replace(r, data=r.data.astype(lo_dtype(r.dtype)))


def tri_sweep(a, y, *, lower: bool, nb: int, unit_diagonal: bool = False,
              adjoint: bool = False):
    """Solve T x = y for a few right-hand sides against a STORED
    factor: T the `lower` (or upper) triangle of the square `a`, or
    with `adjoint` its conjugate transpose; y (n, k) in f32 or wider;
    nb divides n. One block row a step under `fori_loop`: x_k =
    T_kk^{-1} (y_k - T[k, solved] x[solved]), the block row read once
    where it lies and raised to y's dtype as it is read, so a bf16
    factor is substituted against in f32 and rounded by the caller.

    Why not XLA's TriangularSolve on the whole factor: its expander
    unrolls n / 128 block steps, and at n=16384 with one right-hand
    side the two solves of a `getrs` kept the chip's compiler over
    five minutes for ONE program (compiled for a described v5e, PR
    42); this is O(1) in n, and with few right-hand sides either is
    bound by reading the factor once."""
    n = a.shape[0]
    nt = n // nb
    rows = jnp.arange(n)
    forward = lower != adjoint          # the effective triangle is lower

    def step(i, x):
        i = jnp.asarray(i, jnp.int32)
        k0, zero = (i if forward else nt - 1 - i) * nb, jnp.zeros_like(i)
        if adjoint:
            blk = jnp.conj(jax.lax.dynamic_slice(a, (zero, k0), (n, nb)).T)
        else:
            blk = jax.lax.dynamic_slice(a, (k0, zero), (nb, n))
        blk = blk.astype(x.dtype)
        solved = rows < k0 if forward else rows >= k0 + nb
        rhs = jax.lax.dynamic_slice(x, (k0, zero), (nb, x.shape[1])) \
            - jnp.matmul(blk, jnp.where(solved[:, None], x, 0),
                         precision=jax.lax.Precision.HIGHEST)
        xk = jax.lax.linalg.triangular_solve(
            jax.lax.dynamic_slice(blk, (zero, k0), (nb, nb)), rhs,
            left_side=True, lower=forward, unit_diagonal=unit_diagonal)
        return jax.lax.dynamic_update_slice(x, xk, (k0, zero))

    return jax.lax.fori_loop(0, nt, step, y)


def lo_work_dtype(lo):
    """The dtype a lo solve substitutes in: the lo dtype itself, but
    f32 for a factor stored below it (bf16 values, f32 arithmetic, the
    answer rounded once)."""
    d = jnp.dtype(lo)
    return jnp.float32 if d.itemsize < 4 else d


def _as_hi(lo_solve, ctx, factors, rhs):
    """One lo solve of a hi right-hand side: demoted, solved by
    `lo_solve(ctx, factors, rhs_lo)`, promoted."""
    lo = factors[0].dtype
    return lo_solve(ctx, factors, rhs.astype(lo)).astype(rhs.dtype)


def _resid(a_hi, b_hi, x):
    return b_hi - jnp.matmul(a_hi, x, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("lo_solve", "ctx"))
def _ir_solve0(lo_solve, ctx, factors, b_hi):
    """The first lo solve, x0 = solve_lo(b): one compiled program a
    (lo_solve, ctx, shapes), reused by every later call."""
    return _as_hi(lo_solve, ctx, factors, b_hi)


@functools.partial(jax.jit,
                   static_argnames=("lo_solve", "ctx", "itermax"))
def _ir_sweeps(lo_solve, ctx, factors, a_hi, b_hi, x, itermax: int):
    """The refinement after x0 as ONE compiled program (reference
    gesv_mixed.cc:24-40 control flow): the hi residual b - A x at
    `highest` against the hi A, the correction by the lo factors,
    SLATE's stopping rule max|r| <= max|x| ||A||_inf eps sqrt(n),
    at most `itermax` sweeps.

    One polish step past the normwise criterion, only when it was
    met (MaxIterations stays an upper bound on lo solves for a system
    that does not converge): the stopping bound guarantees about
    anorm * eps normwise, one more lo solve buys the contraction
    factor again and puts small solution entries at elementwise
    accuracy too; not counted in the sweeps. It is the loop's last
    pass, so the program holds ONE lo solve and one residual.

    Returns (x, verdict) with verdict = int32 [converged, sweeps]:
    what the host reads, once."""
    hi = a_hi.dtype
    n = a_hi.shape[0]
    cte = (jnp.abs(a_hi).sum(axis=1).max() * jnp.finfo(hi).eps
           * jnp.sqrt(jnp.asarray(float(n), hi)))

    def met(x, r_):
        return jnp.abs(r_).max() <= jnp.abs(x).max() * cte

    r_ = _resid(a_hi, b_hi, x)
    ok = met(x, r_)
    zero = jnp.zeros((), jnp.int32)
    if itermax <= 0:
        return x, jnp.stack([ok.astype(jnp.int32), zero])

    def body(carry):
        x, r_, it, ok, _ = carry
        x = x + _as_hi(lo_solve, ctx, factors, r_)
        r_ = _resid(a_hi, b_hi, x)
        # entered converged: that was the polish, done. Else a sweep:
        # done when it ran out unconverged; converged goes round once
        # more for its polish
        it = jnp.where(ok, it, it + 1)
        now = ok | met(x, r_)
        return x, r_, it, now, ok | (~now & (it >= itermax))

    x, r_, it, ok, _ = jax.lax.while_loop(
        lambda c: ~c[4], body, (x, r_, zero, ok, jnp.zeros((), bool)))
    return x, jnp.stack([ok.astype(jnp.int32), it])


def _finish(name: str, kind: str, x, verdict, full_solve: Callable,
            opts: OptionsLike):
    """Read the verdict (`<name>::verdict`, the one place the host
    waits) and decide on the host: converged, or the reference's
    UseFallbackSolver path, `full_solve()` called as a caller would
    (`<name>::fallback`). Returns (x, iters), iters < 0 on fallback."""
    from ..utils.trace import phases
    slate_assert(not isinstance(verdict, jax.core.Tracer),
                 "a mixed-precision solve decides its fallback on the "
                 "host: call it outside jax.jit")
    ph = phases(opts)
    with ph(name + "::verdict"):
        # THE host read: flag and sweep count in one transfer;
        # everything before it was dispatched without waiting
        ok, iters = (int(v) for v in jax.device_get(verdict))
    if not ok and get_option(opts, Option.UseFallbackSolver, True):
        iters = -iters - 1
        # the rung goes on record BEFORE the fallback's work, so a
        # fallback that itself fails still left it
        _record_refine(kind, iters)
        with ph(name + "::fallback"):
            return full_solve(), iters
    _record_refine(kind, iters)
    return x, iters


def iterative_refinement(A: TiledMatrix, B: TiledMatrix,
                         lo_solve: Callable, ctx, factors,
                         full_solve: Callable, opts: OptionsLike = None,
                         name: str = "refine"):
    """Generic IR (reference gesv_mixed.cc:24-40) as two compiled
    programs and one host read. `lo_solve(ctx, factors, rhs_lo)` is a
    MODULE-LEVEL function (it and the hashable `ctx` are the programs'
    static keys, so a second call at the same shapes traces and
    compiles nothing): lo dense rhs -> lo dense solution by the lo
    `factors` (a tuple of arrays, the factor's data first).
    `full_solve`: () -> dense solution at full precision, run on the
    host's verdict only. `name`: the driver's, for the phase spans
    `<name>::solve0`, `::refine` (the dispatch), `::verdict`,
    `::fallback`. Returns (x_dense, iters), iters < 0 on fallback."""
    from ..utils.trace import phases
    ph = phases(opts)
    itermax = int(get_option(opts, Option.MaxIterations, 30))
    a_hi = A.to_dense()
    b_hi = B.to_dense()
    with ph(name + "::solve0"):
        x = _ir_solve0(lo_solve, ctx, factors, b_hi)
    with ph(name + "::refine"):
        x, verdict = _ir_sweeps(lo_solve, ctx, factors, a_hi, b_hi, x,
                                itermax)
    return _finish(name, "ir", x, verdict, full_solve, opts)


def _record_refine(kind: str, iters: int) -> None:
    """The refinement's record: the `mixed_to_full` rung through the
    resil funnel whenever the fallback was taken (counted with the bus
    off too, like every ladder step), and with the bus on the call
    count, the sweep count and the fallback flag (iters < 0 per the
    reference info convention). `iters` is the host's own number since
    the verdict moved there: the same with the bus off or on, and
    reading it waits for nothing more."""
    from ..obs import events as obs_events
    from ..obs import metrics as obs_metrics
    # decode the info convention BEFORE observing: iters < 0 encodes
    # "fallback taken after -iters-1 refinement sweeps", and the
    # histogram must hold actual sweep counts, not the encoding
    sweeps = iters if iters >= 0 else -iters - 1
    if iters < 0:
        # degradation-ladder rung (resil/, ISSUE 9): non-convergence
        # took the reference's UseFallbackSolver full-precision path —
        # route it through THE escalation funnel so it lands in the
        # resil.* counters + the resil::fallback instant stream like
        # every other rung (check_instrumented rule 4)
        from ..resil.guard import record_escalation
        record_escalation("mixed_to_full", kind=kind,
                          sweeps=int(sweeps))
    if not obs_events.enabled():
        return
    obs_metrics.inc("refine.%s.calls" % kind)
    obs_metrics.observe("refine.%s.iters" % kind, sweeps)
    if iters < 0:
        obs_metrics.inc("refine.%s.fallback" % kind)


@functools.partial(jax.jit, static_argnames=(
    "lo_solve", "ctx", "restart", "ncycles"))
def _fgmres_program(lo_solve, ctx, factors, a_hi, b, restart: int,
                    ncycles: int):
    """Restarted FGMRES right-preconditioned by the lo solve, as one
    compiled program. Returns (x (n,), verdict int32 [converged,
    iters])."""
    hi = a_hi.dtype
    n = a_hi.shape[0]

    def precond(v):
        return _as_hi(lo_solve, ctx, factors, v[:, None])[:, 0]

    def matvec(v):
        return jnp.matmul(a_hi, v, precision=jax.lax.Precision.HIGHEST)

    eps = jnp.finfo(hi).eps
    anorm = jnp.abs(a_hi).sum(axis=1).max()
    tol = eps * jnp.sqrt(jnp.asarray(float(n), hi)) * anorm

    x = precond(b)

    def cycle(x):
        r_ = b - matvec(x)
        beta = jnp.linalg.norm(r_)
        safe_beta = jnp.where(beta == 0, 1.0, beta)
        V = jnp.zeros((restart + 1, n), hi).at[0].set(r_ / safe_beta)
        Z = jnp.zeros((restart, n), hi)
        H = jnp.zeros((restart + 1, restart), hi)

        def arnoldi(j, carry):
            V, Z, H = carry
            z = precond(V[j])
            w = matvec(z)

            def mgs(i, wh):
                w, H = wh
                hij = jnp.vdot(V[i], w)
                H = H.at[i, j].set(jnp.where(i <= j, hij, H[i, j]))
                w = jnp.where(i <= j, w - hij * V[i], w)
                return w, H

            w, H = jax.lax.fori_loop(0, restart, mgs, (w, H))
            hnext = jnp.linalg.norm(w)
            H = H.at[j + 1, j].set(hnext)
            V = V.at[j + 1].set(w / jnp.where(hnext == 0, 1.0, hnext))
            Z = Z.at[j].set(z)
            return V, Z, H

        V, Z, H = jax.lax.fori_loop(0, restart, arnoldi, (V, Z, H))
        e1 = jnp.zeros((restart + 1,), hi).at[0].set(beta)
        y = jnp.linalg.lstsq(H, e1)[0]
        return x + Z.T @ y

    def met(x):
        return jnp.linalg.norm(b - matvec(x)) <= tol * jnp.linalg.norm(x)

    def not_done(carry):
        x, c = carry
        return ~met(x) & (c < ncycles)

    def step(carry):
        x, c = carry
        return cycle(x), c + 1

    x, cycles = jax.lax.while_loop(
        not_done, step, (x, jnp.zeros((), jnp.int32)))
    return x, jnp.stack([met(x).astype(jnp.int32), cycles * restart])


def fgmres_ir(A: TiledMatrix, B: TiledMatrix, lo_solve: Callable, ctx,
              factors, full_solve: Callable, restart_cap: int,
              opts: OptionsLike = None, name: str = "refine"):
    """Restarted FGMRES right-preconditioned by the lo-precision solve
    (reference gesv_mixed_gmres.cc: restart=min(30, itermax, mb-1)).
    Single RHS. `lo_solve`, `ctx`, `factors`, `full_solve`, `name` as
    in `iterative_refinement`: one compiled program (`<name>::refine`),
    the same host verdict. Returns (x_dense (n,1), iters)."""
    from ..utils.trace import phases
    itermax = int(get_option(opts, Option.MaxIterations, 30))
    a_hi = A.to_dense()
    b = B.to_dense().reshape(a_hi.shape[0])
    restart = int(max(1, min(30, itermax, restart_cap)))
    ncycles = max(1, -(-itermax // restart))
    with phases(opts)(name + "::refine"):
        x, verdict = _fgmres_program(lo_solve, ctx, factors, a_hi, b,
                                     restart, ncycles)
    return _finish(name, "fgmres", x[:, None], verdict, full_solve, opts)


def host_ir(op: str, a, b, x, solve_lo: Callable,
            full_solve: Callable, opts: OptionsLike = None):
    """Host-loop iterative refinement for the OOC mixed-precision
    solves (ISSUE 12) — the gesv_mixed/posv_mixed control flow
    carried to host-resident operands: the factor was computed with
    lo-precision trailing updates (and the solve sweeps stage lo
    panels), so the first solution is lo-grade; each sweep computes
    the FULL-precision residual on the host (the matrix is
    host-resident at OOC scale — one O(n^2 nrhs) host matmul per
    sweep, no extra streaming) and corrects with one more lo solve.
    The stopping criterion is iterative_refinement's normwise bound
    (max|r| <= max|x| * anorm * eps * sqrt(n) at the input dtype's
    eps).

    Non-convergence within ``Option.MaxIterations`` is the residual
    sentinel: the ``mixed_to_full`` rung is recorded through the
    resil guard funnel (record_escalation — counted even with obs
    off, like every ladder step) and ``full_solve()`` supplies the
    full-precision answer, the reference's UseFallbackSolver path.
    Returns (x, iters) with iters < 0 on fallback (the info
    convention). Obs: the whole loop runs under an ``ooc::refine``
    span and the sweep count lands in the ``refine.ooc.*``
    counters/histograms (the bench --ooc extras read them)."""
    import numpy as np
    from ..obs import events as obs_events
    from ..obs import metrics as obs_metrics
    itermax = int(get_option(opts, Option.MaxIterations, 30))
    use_fallback = get_option(opts, Option.UseFallbackSolver, True)
    a = np.asarray(a)
    b = np.asarray(b)
    hi = a.dtype
    n = a.shape[0]
    eps = np.finfo(hi).eps
    anorm = np.abs(a).sum(axis=1).max()
    cte = anorm * eps * np.sqrt(n)

    def resid(x):
        return b - np.matmul(a, x)

    def converged(x, r):
        return bool(np.abs(r).max() <= np.abs(x).max() * cte)

    with obs_events.span("ooc::refine", cat="refine", op=op):
        x = np.asarray(x, dtype=hi)
        r = resid(x)
        it = 0
        while not converged(x, r) and it < itermax:
            x = x + np.asarray(solve_lo(r), dtype=hi)
            r = resid(x)
            it += 1
        iters = it
        if not converged(x, r) and use_fallback:
            iters = -it - 1
            # THE residual sentinel: route the rung through the resil
            # funnel BEFORE the fallback work, so a fallback that
            # itself fails still left the escalation on record
            from ..resil.guard import record_escalation
            record_escalation("mixed_to_full", kind="ooc", op=op,
                              sweeps=int(it))
            x = np.asarray(full_solve(), dtype=hi)
    if obs_events.enabled():
        obs_metrics.inc("refine.ooc.calls")
        obs_metrics.observe("refine.ooc.iters",
                            iters if iters >= 0 else -iters - 1)
        if iters < 0:
            obs_metrics.inc("refine.ooc.fallback")
    return x, iters
