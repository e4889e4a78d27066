"""Parameter-sweep tester CLI (reference test/ `tester` binary on
TestSweeper + test/run_tests.py; SURVEY §4 tier 2).

Sweeps routine x dim x dtype x block size x grid, times each config,
computes GFLOP/s and a residual check (reference-style error bounds, or
--ref y to compare against numpy/scipy on gathered arrays — the
ScaLAPACK-compare role).

Usage:
    python -m slate_tpu.testing.tester gemm potrf --dim 256:1024:*2 \
        --type s,d --nb 64,128 --grid 1x1 --check y
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Callable, Dict

import numpy as np

DTYPES = {"s": np.float32, "d": np.float64,
          "c": np.complex64, "z": np.complex128}


def _parse_dims(spec: str):
    out = []
    for part in spec.split(","):
        if ":" in part:
            lo, hi, step = part.split(":")
            lo, hi = int(lo), int(hi)
            if step.startswith("*"):
                f = int(step[1:])
                v = lo
                while v <= hi:
                    out.append(v)
                    v *= f
            else:
                out.extend(range(lo, hi + 1, int(step)))
        else:
            out.append(int(part))
    return out


def _gflops(routine: str, m: int, n: int, k: int) -> float:
    f = {
        "gemm": 2.0 * m * n * k,
        "potrf": m ** 3 / 3.0,
        "posv": m ** 3 / 3.0 + 2.0 * m * m * k,
        "getrf": 2.0 * m ** 3 / 3.0,
        "gesv": 2.0 * m ** 3 / 3.0 + 2.0 * m * m * k,
        "geqrf": 2.0 * m * n * n - 2.0 * n ** 3 / 3.0,
        "gels": 2.0 * m * n * n,
        "trsm": 1.0 * m * m * k,
        "herk": 1.0 * m * m * k,
        "heev": 4.0 * m ** 3 / 3.0,
        "svd": 4.0 * m * n * min(m, n),
        "hesv": m ** 3 / 3.0 + 2.0 * m * m * k,
        # band routines: FLOPs depend on kd; the sweep reports time
        # only (gflops column 0), like the reference tester's norm rows
    }.get(routine, 0.0)
    return f / 1e9


def _mk_band(a, kd):
    """Zero a outside the band |i - j| <= kd (no index-array scratch)."""
    return np.triu(np.tril(a, kd), -kd)


def run_one(routine: str, n: int, dtype, nb: int, check: bool,
            ref: bool, seed: int = 42, grid=None) -> Dict:
    """Run one (routine, n, dtype, nb[, grid]) config. With a
    ProcessGrid, inputs are built on the mesh (the constructors'
    ``grid=``) and the drivers get Option.Grid + MethodFactor.Tiled —
    the reference tester's `-p -q` grid sweep (test.cc:685)."""
    import slate_tpu as st
    from slate_tpu.core.methods import MethodFactor
    from slate_tpu.core.options import Option

    opts = None
    if grid is not None:
        opts = {Option.Grid: grid, Option.MethodFactor:
                MethodFactor.Tiled}

    rng = np.random.default_rng(seed)
    real = np.float64 if dtype in (np.float64, np.complex128) \
        else np.float32
    eps = np.finfo(real).eps

    def mk(shape, herm=False, spd=False):
        a = rng.standard_normal(shape)
        if np.issubdtype(dtype, np.complexfloating):
            a = a + 1j * rng.standard_normal(shape)
        if spd:
            a = a @ a.conj().T / shape[0] + 4 * np.eye(shape[0])
        elif herm:
            a = (a + a.conj().T) / 2
        return a.astype(dtype)

    nrhs = 10
    t0 = time.perf_counter()
    err = None
    if routine == "gemm":
        a, b, c = mk((n, n)), mk((n, n)), mk((n, n))
        C = st.gemm(1.0, st.Matrix(a, mb=nb, grid=grid),
                    st.Matrix(b, mb=nb, grid=grid),
                    0.0, st.Matrix(c, mb=nb, grid=grid), opts)
        out = C.to_numpy()
        t = time.perf_counter() - t0
        if check:
            err = np.linalg.norm(out - a @ b) / (
                np.linalg.norm(a) * np.linalg.norm(b) * n * eps)
        if ref:
            # --ref y: direct comparison to the numpy result (the
            # reference tester's ScaLAPACK-compare role)
            err = np.linalg.norm(out - a @ b) / (
                np.linalg.norm(a @ b) * n * eps + 1e-300)
    elif routine in ("potrf", "posv"):
        a = mk((n, n), spd=True)
        A = st.HermitianMatrix(st.Uplo.Lower, a, mb=nb, grid=grid)
        if routine == "potrf":
            L = st.potrf(A, opts)
            out = L.to_numpy()
            t = time.perf_counter() - t0
            if check:
                err = np.linalg.norm(out @ out.conj().T - a) / (
                    np.linalg.norm(a) * n * eps)
            if ref:
                lref = np.linalg.cholesky(a)
                err = np.linalg.norm(np.tril(out) - lref) / (
                    np.linalg.norm(lref) * n * eps + 1e-300)
        else:
            b = mk((n, nrhs))
            _, X = st.posv(A, st.Matrix(b, mb=nb, grid=grid), opts)
            x = X.to_numpy()
            t = time.perf_counter() - t0
            if check:
                err = np.linalg.norm(b - a @ x) / (
                    np.linalg.norm(a) * np.linalg.norm(x) * n * eps)
            if ref:
                xr = np.linalg.solve(a, b)
                err = np.linalg.norm(x - xr) / (
                    np.linalg.norm(xr) * n * eps
                    * max(np.linalg.cond(a), 1.0))
    elif routine in ("getrf", "gesv"):
        a = mk((n, n))
        if routine == "getrf":
            F = st.getrf(st.Matrix(a, mb=nb, grid=grid), opts)
            out = F.LU.to_numpy()
            t = time.perf_counter() - t0
            if check:
                lu = out
                L = np.tril(lu, -1) + np.eye(n)
                U = np.triu(lu)
                pa = a.copy()
                piv = np.asarray(F.pivots)[:n]
                for j in range(n):
                    pa[[j, piv[j]]] = pa[[piv[j], j]]
                err = np.linalg.norm(L @ U - pa) / (
                    np.linalg.norm(a) * n * eps)
            if ref:
                # external reference via SOLVES: element-wise factor
                # comparison against scipy assumes identical pivot
                # choices, which near-tie magnitudes legitimately
                # break. Solving the same rhs through both factor
                # stacks compares the factorizations' actual function
                # while staying pivot-choice-independent.
                import scipy.linalg as _sla
                b = mk((n, nrhs))
                xr = _sla.lu_solve(_sla.lu_factor(a), b)
                x = st.getrs(F, st.Matrix(b, mb=nb, grid=grid),
                             opts).to_numpy()
                err = np.linalg.norm(x - xr) / (
                    np.linalg.norm(xr) * n * eps
                    * max(np.linalg.cond(a), 1.0) + 1e-300)
        else:
            b = mk((n, nrhs))
            _, X = st.gesv(st.Matrix(a, mb=nb, grid=grid),
                           st.Matrix(b, mb=nb, grid=grid), opts)
            x = X.to_numpy()
            t = time.perf_counter() - t0
            if check:
                err = np.linalg.norm(b - a @ x) / (
                    np.linalg.norm(a) * np.linalg.norm(x) * n * eps)
            if ref:
                xr = np.linalg.solve(a, b)
                err = np.linalg.norm(x - xr) / (
                    np.linalg.norm(xr) * n * eps
                    * max(np.linalg.cond(a), 1.0))
    elif routine in ("geqrf", "gels"):
        m2 = n
        a = mk((m2, n))
        if routine == "geqrf":
            F = st.geqrf(st.Matrix(a, mb=nb, grid=grid), opts)
            t = time.perf_counter() - t0
            if check:
                R = np.triu(F.QR.to_numpy())
                from slate_tpu import Side
                eye = np.eye(m2, dtype=dtype)
                Q = st.unmqr(Side.Left, F, st.Matrix(eye, mb=nb),
                             trans=False).to_numpy()
                err = np.linalg.norm(Q @ R - a) / (
                    np.linalg.norm(a) * n * eps)
        else:
            b = mk((m2, nrhs))
            X = st.gels(st.Matrix(a, mb=nb, grid=grid),
                        st.Matrix(b, mb=nb, grid=grid), opts)
            x = X.to_numpy()[:n]
            t = time.perf_counter() - t0
            if check:
                # normal-equations residual for LS solutions
                rr = b - a @ x
                err = np.linalg.norm(a.conj().T @ rr) / (
                    np.linalg.norm(a) ** 2 * np.linalg.norm(x) * n * eps)
            if ref:
                xr = np.linalg.lstsq(a, b, rcond=None)[0]
                err = np.linalg.norm(x - xr) / (
                    np.linalg.norm(xr) * n * eps
                    * max(np.linalg.cond(a), 1.0))
    elif routine == "heev":
        a = mk((n, n), herm=True)
        A = st.HermitianMatrix(st.Uplo.Lower, a, mb=nb, grid=grid)
        w, V = st.heev(A, opts)
        t = time.perf_counter() - t0
        if check:
            v = V.to_numpy()
            err = np.linalg.norm(a @ v - v * np.asarray(w)[None, :]) / (
                np.linalg.norm(a) * n * eps)
        if ref:
            wr = np.linalg.eigvalsh(a)
            err = np.linalg.norm(np.asarray(w)[:n] - wr) / (
                np.linalg.norm(wr) * n * eps + 1e-300)
    elif routine == "svd":
        a = mk((n, n))
        s, U, Vh = st.svd(st.Matrix(a, mb=nb, grid=grid), opts)
        t = time.perf_counter() - t0
        if check:
            rec = (U.to_numpy() * np.asarray(s)[None, :]) @ Vh.to_numpy()
            err = np.linalg.norm(rec - a) / (np.linalg.norm(a) * n * eps)
        if ref:
            sr = np.linalg.svd(a, compute_uv=False)
            err = np.linalg.norm(np.asarray(s)[: len(sr)] - sr) / (
                np.linalg.norm(sr) * n * eps + 1e-300)
    elif routine == "hesv":
        a = mk((n, n), herm=True)        # indefinite
        b = mk((n, nrhs))
        A = st.HermitianMatrix(st.Uplo.Lower, a, mb=nb, grid=grid)
        _, X = st.hesv(A, st.Matrix(b, mb=nb, grid=grid), opts)
        x = X.to_numpy()
        t = time.perf_counter() - t0
        if check:
            err = np.linalg.norm(b - a @ x) / (
                np.linalg.norm(a) * np.linalg.norm(x) * n * eps)
        if ref:
            xr = np.linalg.solve(a, b)
            err = np.linalg.norm(x - xr) / (
                np.linalg.norm(xr) * n * eps
                * max(np.linalg.cond(a), 1.0))
    elif routine in ("gbsv", "pbsv"):
        kd = max(min(nb // 2, n // 4), 1)
        a = _mk_band(mk((n, n)), kd)
        if routine == "pbsv":
            a = ((a + a.conj().T) / 2
                 + 4 * np.sqrt(n) * np.eye(n)).astype(dtype)
            A = st.HermitianBandMatrix(st.Uplo.Lower, kd, a, mb=nb,
                                       grid=grid)
            solve = st.pbsv
        else:
            a = (a + 4 * np.eye(n, dtype=dtype)).astype(dtype)
            A = st.BandMatrix(kd, kd, a, mb=nb, grid=grid)
            solve = st.gbsv
        b = mk((n, nrhs))
        _, X = solve(A, st.Matrix(b, mb=nb, grid=grid), opts)
        x = X.to_numpy()
        t = time.perf_counter() - t0
        if check:
            err = np.linalg.norm(b - a @ x) / (
                np.linalg.norm(a) * np.linalg.norm(x) * n * eps)
        if ref:
            import scipy.linalg as _sla
            if routine == "pbsv":
                ab = np.zeros((kd + 1, n), a.dtype)
                for i in range(kd + 1):
                    ab[i, : n - i] = np.diagonal(a, -i)
                xr = _sla.solveh_banded(ab, b, lower=True)
            else:
                ab = np.zeros((2 * kd + 1, n), a.dtype)
                for i in range(-kd, kd + 1):
                    row = kd - i
                    if i >= 0:
                        ab[row, i:] = np.diagonal(a, i)
                    else:
                        ab[row, : n + i] = np.diagonal(a, i)
                xr = _sla.solve_banded((kd, kd), ab, b)
            err = np.linalg.norm(x - xr) / (
                np.linalg.norm(xr) * n * eps
                * max(np.linalg.cond(a), 1.0))
    elif routine == "gbmm":
        kd = max(min(nb // 2, n // 4), 1)
        a = _mk_band(mk((n, n)), kd).astype(dtype)
        b = mk((n, n))
        A = st.BandMatrix(kd, kd, a, mb=nb, grid=grid)
        C = st.gbmm(1.0, A, st.Matrix(b, mb=nb, grid=grid), 0.0,
                    st.Matrix(np.zeros_like(b), mb=nb, grid=grid), opts)
        out = C.to_numpy()
        t = time.perf_counter() - t0
        if check or ref:
            # the numpy product IS the external reference here
            err = np.linalg.norm(out - a @ b) / (
                np.linalg.norm(a) * np.linalg.norm(b) * n * eps
                + 1e-300)
    else:
        # ValueError (not SystemExit) so sweep() records one FAILED row
        # and the rest of the sweep still runs
        raise ValueError(f"unknown routine {routine}")

    k_inner = n if routine == "gemm" else nrhs
    gf = _gflops(routine, n, n, k_inner) / t if t > 0 else 0.0
    status = "pass" if (err is None or err < 100) else "FAILED"
    return dict(routine=routine, n=n, dtype=np.dtype(dtype).name, nb=nb,
                time=t, gflops=gf, error=err, status=status)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("routines", nargs="+")
    p.add_argument("--dim", default="256")
    p.add_argument("--type", default="s", dest="types")
    p.add_argument("--nb", default="64")
    p.add_argument("--grid", default="1x1",
                   help="p x q process grid (uses available jax devices)")
    p.add_argument("--check", default="y")
    p.add_argument("--ref", default="n")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write a Perfetto/Chrome trace JSON of the "
                        "sweep (obs event bus: driver spans, phases, "
                        "tuner decisions) to PATH")
    args = p.parse_args(argv)

    if args.trace_out:
        from .. import obs
        obs.enable()

    # whatever backend jax finds (JAX_PLATFORMS=cpu for the CPU
    # tier); no probe, no fallback — a missing backend raises
    import jax
    print(f"# backend: {jax.devices()[0].platform}")

    rows = sweep(args.routines, args.dim, args.types, args.nb,
                 args.grid, args.check == "y", args.ref == "y")
    nfail = sum(r["status"] == "FAILED" for r in rows)
    print(f"\n{'All tests passed' if nfail == 0 else f'{nfail} FAILED'}")
    if args.trace_out:
        from ..obs import export as obs_export
        obs_export.write_trace(args.trace_out, clear=True)
        print(f"# trace written: {args.trace_out}")
    return 1 if nfail else 0


def _parse_grids(spec: str):
    """'1x1,2x4' -> ProcessGrid list; grids needing more devices than
    available are skipped with a note (the reference Jenkinsfile-mpi
    runs the same sweep at --np 4)."""
    import jax

    from ..parallel.mesh import make_grid
    grids = []
    nd = len(jax.devices())
    for part in spec.split(","):
        p, q = (int(x) for x in part.lower().split("x"))
        if p * q > nd:
            print(f"# grid {p}x{q} skipped: only {nd} devices")
            continue
        grids.append(make_grid(p, q) if p * q > 1 else None)
    return grids or [None]


def sweep(routines, dim_spec, type_spec, nb_spec, grid_spec,
          check=True, ref=False, out=sys.stdout):
    """The full sweep loop, reusable by run_tests.py; returns result
    row dicts (each also carries 'grid')."""
    dims = _parse_dims(dim_spec)
    nbs = [int(x) for x in nb_spec.split(",")]
    types = [DTYPES[t] for t in type_spec.split(",")]
    grids = _parse_grids(grid_spec)

    header = (f"{'routine':10s} {'type':8s} {'n':>7s} {'nb':>5s} "
              f"{'grid':>6s} {'time(s)':>9s} {'gflops':>9s} "
              f"{'error':>10s}  status")
    print(header, file=out)
    print("-" * len(header), file=out)
    rows = []
    for routine in routines:
        for dtype in types:
            for n in dims:
                for nb in nbs:
                    for grid in grids:
                        gname = "1x1" if grid is None \
                            else f"{grid.p}x{grid.q}"
                        try:
                            r = run_one(routine, n, dtype, nb, check,
                                        ref, grid=grid)
                        except Exception as e:   # noqa: BLE001
                            r = dict(routine=routine, n=n,
                                     dtype=np.dtype(dtype).name, nb=nb,
                                     time=0.0, gflops=0.0, error=None,
                                     status="FAILED",
                                     detail=f"{type(e).__name__}: {e}")
                        r["grid"] = gname
                        err = "-" if r["error"] is None \
                            else f"{r['error']:.2e}"
                        shown = r["status"] if r["status"] == "pass" \
                            else (r.get("detail", r["status"])[:40]
                                  or "FAILED")
                        print(f"{r['routine']:10s} {r['dtype']:8s} "
                              f"{n:7d} {nb:5d} {gname:>6s} "
                              f"{r['time']:9.3f} {r['gflops']:9.1f} "
                              f"{err:>10s}  {shown}", file=out)
                        if r["status"] != "pass":
                            r["status"] = "FAILED"
                        rows.append(r)
    return rows


if __name__ == "__main__":
    # placed here, not in main(): tests call main() in-process and
    # must not place a persistent cache under pytest
    from ..utils import compile_cache
    compile_cache.enable()
    sys.exit(main())
