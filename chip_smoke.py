#!/usr/bin/env python3
"""Quickest proof that slate_tpu still starts on the chip.

One process drives the main paths once through the entry points a user
calls — in-core solves, the streamed (out-of-core) solve with partial
device residency, the serving tier on its bucket and ragged routes —
and checks every answer against a plain host reference (numpy, f64)
outside any timing. One JSON object per line; the LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exits non-zero, and prints no result, when JAX finds no TPU. No phase
is wrapped in an `except`: a failed check is a traceback and exit 1.

    python chip_smoke.py            # one chip, what the driver runs
    python chip_smoke.py --mesh     # four chips: ONLY the 2x2 grid phase
    python chip_smoke.py --tiny     # CPU rehearsal: sizes cut, and the
                                    # platform == "tpu" assertion skipped

Every number printed is ONE reading, not a benchmark.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

EPS32 = float(np.finfo(np.float32).eps)

#: HPL's acceptance bound on the scaled residual of a solve
#: ||AX-B||_inf / (eps n (||A||_inf ||X||_inf + ||B||_inf)): O(1) for a
#: backward-stable solve whatever the conditioning; 16 is HPL's own
#: threshold
HPL_BOUND = 16.0
#: heev: ||AV-V diag(w)||_F / (||A||_F n eps) and ||V^T V - I||_F /
#: (n eps). 50 is the LAPACK test-suite threshold family (xDRVST uses
#: 50 for the symmetric eigensolvers)
EIG_BOUND = 50.0
#: gels: forward error against the host f64 QR solution, in units of
#: eps * cond2(A): a backward-stable least-squares solve of a
#: near-consistent system lands within a modest multiple
GELS_BOUND = 64.0


def emit(obj):
    print(json.dumps(obj), flush=True)


def check(cond, what, **info):
    if not cond:
        raise AssertionError("chip_smoke check failed: %s %s"
                             % (what, json.dumps(info, default=str)))


class Compiles:
    """Backend compiles, their seconds, and persistent-cache hits, as
    jax.monitoring reports them (a cache hit still fires the compile
    duration event — its seconds are then the retrieval)."""

    def __init__(self):
        import jax.monitoring as jmon
        self.n = self.hits = self.misses = 0
        self.seconds = 0.0
        jmon.register_event_duration_secs_listener(self._dur)
        jmon.register_event_listener(self._ev)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _ev(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return (self.n, self.seconds, self.hits, self.misses)

    def since(self, s0):
        return {"programs": self.n - s0[0],
                "compile_seconds": round(self.seconds - s0[1], 3),
                "cache_hits": self.hits - s0[2],
                "cache_misses": self.misses - s0[3]}


def wall(fn):
    """(result, seconds) with the device work waited for."""
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def hpl_resid(a64, x, b64, n):
    """HPL scaled residual in f64 on the host."""
    x64 = np.asarray(x, np.float64)
    r = np.abs(a64 @ x64 - b64).sum(axis=1).max()
    an = np.abs(a64).sum(axis=1).max()
    xn = np.abs(x64).sum(axis=1).max()
    bn = np.abs(b64).sum(axis=1).max()
    return float(r / (EPS32 * n * (an * xn + bn)))


def sym_dominant(rng, n):
    """Symmetric, strictly diagonally dominant => SPD, made in O(n^2)."""
    r = rng.uniform(-1.0, 1.0, (n, n)).astype(np.float32)
    a = (r + r.T) * np.float32(0.5)
    a[np.arange(n), np.arange(n)] = np.float32(n)
    return a


# -- phases ----------------------------------------------------------------

def phase_device(args, rng, comp):
    import jax
    import jax.numpy as jnp
    import jaxlib
    from importlib import metadata

    import slate_tpu  # noqa: F401
    from slate_tpu import native
    from slate_tpu.linalg import stream
    from slate_tpu.ops import pallas_kernels as pk
    from slate_tpu.tune import cache as tcache
    dev = jax.devices()[0]
    if not args.tiny:
        check(pk.pallas_interpret() is False, "pallas_interpret() on tpu")
        check(pk.pallas_available(jnp.float32) is True,
              "pallas_available(f32) on tpu")
    check(not os.path.exists(tcache.cache_path()),
          "tune cache must be cold (every route below is the FROZEN "
          "one)", path=tcache.cache_path())
    stats = dev.memory_stats() or {}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    n, pc = args.stream_n, args.panel_cols
    budget = stream.auto_budget_bytes(n, pc, 4)
    if not args.tiny:
        check(budget > 0, "stream.auto_budget_bytes found no device "
              "memory limit", stats=stats)

    # dispatch floor: wall of a trivial jitted op, call to ready
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8, 128), jnp.float32)
    for _ in range(20):
        f(x).block_until_ready()
    ts = []
    for _ in range(200):
        t = time.perf_counter()
        f(x).block_until_ready()
        ts.append(time.perf_counter() - t)
    emit({"phase": "device", "jax": jax.__version__,
          "jaxlib": jaxlib.__version__, "libtpu": libtpu,
          "platform": dev.platform, "device_kind": dev.device_kind,
          "count": len(jax.devices()),
          "bytes_limit": stats.get("bytes_limit"),
          "compile_cache_dir": args.cache_dir,
          "compile_cache_was_empty": args.cache_was_empty,
          "tune_cache_path": tcache.cache_path(), "tune_cache_cold": True,
          "native_layout": "built" if native.get_lib() is not None
          else "numpy",
          "pallas_interpret": pk.pallas_interpret(),
          "stream_auto_budget_bytes": budget,
          "dispatch_floor_us_median_of_200":
              float(np.median(ts) * 1e6)})


def phase_incore(args, rng, comp):
    import slate_tpu as st
    n, nrhs, mb = args.n, args.nrhs, args.mb

    def solve_pair(name, a, b, call):
        s0 = comp.snap()
        (_, X), first = wall(call)
        (_, X), second = wall(call)
        res = hpl_resid(a.astype(np.float64), X.to_numpy(),
                        b.astype(np.float64), n)
        emit({"phase": "incore", "routine": name, "n": n, "nrhs": nrhs,
              "mb": mb, "first_call_s": round(first, 3),
              "second_call_s": round(second, 4),
              "hpl_scaled_residual": res, "bound": HPL_BOUND,
              **comp.since(s0)})
        check(np.isfinite(res) and res <= HPL_BOUND, name, resid=res)

    b = rng.standard_normal((n, nrhs)).astype(np.float32)
    a = sym_dominant(rng, n)
    A = st.HermitianMatrix(st.Uplo.Lower, a, mb=mb)
    B = st.Matrix(b, mb=mb)
    solve_pair("posv", a, b, lambda: st.posv(A, B))
    a = rng.standard_normal((n, n)).astype(np.float32)
    A = st.Matrix(a, mb=mb)
    solve_pair("gesv", a, b, lambda: st.gesv(A, B))
    del A, B

    # gels: tall-skinny least squares vs the host f64 QR solution
    m, k = args.gels_m, args.gels_n
    a = rng.standard_normal((m, k)).astype(np.float32)
    b = rng.standard_normal((m, nrhs)).astype(np.float32)
    A, B = st.Matrix(a, mb=mb), st.Matrix(b, mb=mb)
    s0 = comp.snap()
    X, first = wall(lambda: st.gels(A, B))
    X, second = wall(lambda: st.gels(A, B))
    q, r = np.linalg.qr(a.astype(np.float64))
    xref = np.linalg.solve(r, q.T @ b.astype(np.float64))
    cond = float(np.linalg.cond(r))
    x = np.asarray(X.to_numpy(), np.float64)[:k]
    err = float(np.linalg.norm(x - xref) / np.linalg.norm(xref)
                / (EPS32 * cond))
    emit({"phase": "incore", "routine": "gels", "m": m, "n": k,
          "nrhs": nrhs, "first_call_s": round(first, 3),
          "second_call_s": round(second, 4), "cond2": cond,
          "forward_err_over_eps_cond": err, "bound": GELS_BOUND,
          **comp.since(s0)})
    check(x.shape == xref.shape and np.isfinite(err)
          and err <= GELS_BOUND, "gels", err=err)
    del A, B, q, r

    # heev above dc_min_n: the TPU-only spectral D&C branch
    ne = args.heev_n
    g = rng.standard_normal((ne, ne)).astype(np.float32)
    a = (g + g.T) * np.float32(0.5)
    A = st.HermitianMatrix(st.Uplo.Lower, a, mb=mb)
    s0 = comp.snap()
    res, first = wall(lambda: st.heev(A))
    res, second = wall(lambda: st.heev(A))
    w = np.asarray(res.values, np.float64)
    v = np.asarray(res.vectors.to_numpy(), np.float64)
    a64 = a.astype(np.float64)
    r1 = float(np.linalg.norm(a64 @ v - v * w)
               / (np.linalg.norm(a64) * ne * EPS32))
    r2 = float(np.linalg.norm(v.T @ v - np.eye(ne)) / (ne * EPS32))
    emit({"phase": "incore", "routine": "heev", "n": ne,
          "first_call_s": round(first, 3),
          "second_call_s": round(second, 4),
          "resid_AV_VL": r1, "orth_VtV_I": r2, "bound": EIG_BOUND,
          **comp.since(s0)})
    check(w.shape == (ne,) and np.all(np.diff(w) >= 0)
          and max(r1, r2) <= EIG_BOUND, "heev", r1=r1, r2=r2)


def phase_stream(args, rng, comp):
    """posv_ooc on the Round-4c input family (banded, diagonally
    dominant SPD: the residual is exact through a band matvec, no
    O(n^3) host product) with 5 of the 8 factor panels resident."""
    from slate_tpu import obs
    from slate_tpu.linalg import ooc
    n, pc, nrhs, bw = args.stream_n, args.panel_cols, 8, 4
    cut = None
    with open("/proc/meminfo") as fh:
        avail = next(int(ln.split()[1]) * 1024 for ln in fh
                     if ln.startswith("MemAvailable"))
    # A and L on the host, plus staging copies of a few panels
    while not args.tiny and avail < 3 * n * n * 4:
        n //= 2
        pc //= 2
        cut = "host RAM %d bytes: n halved to %d" % (avail, n)
    nt = n // pc
    resident = (5 * nt) // 8
    budget = resident * n * pc * 4
    a = np.zeros((n, n), np.float32)
    i = np.arange(n)
    a[i, i] = np.float32(2 * bw + 1)
    bands = []
    for k in range(1, bw + 1):
        v = rng.uniform(-1.0, 1.0, n - k).astype(np.float32)
        a[i[k:], i[:-k]] = v
        a[i[:-k], i[k:]] = v
        bands.append(v.astype(np.float64))
    b = rng.standard_normal((n, nrhs)).astype(np.float32)

    obs.enable()
    obs.metrics.reset()
    s0 = comp.snap()
    t0 = time.perf_counter()
    L, X = ooc.posv_ooc(a, b, panel_cols=pc, cache_budget_bytes=budget)
    took = time.perf_counter() - t0
    c = obs.snapshot()["metrics"]["counters"]
    staging = {}
    for e in obs.bus_events(cat="staging"):
        staging[e.name] = staging.get(e.name, 0.0) + e.dur
    obs.disable()
    obs.clear()

    # exact residual through the bands, f64
    x64 = np.asarray(X, np.float64)
    ax = (2 * bw + 1) * x64
    for k, v in enumerate(bands, start=1):
        ax[k:] += v[:, None] * x64[:-k]
        ax[:-k] += v[:, None] * x64[k:]
    b64 = b.astype(np.float64)
    r = np.abs(ax - b64).sum(axis=1).max()
    an = (2 * bw + 1) + 2.0 * bw
    res = float(r / (EPS32 * n * (an * np.abs(x64).sum(axis=1).max()
                                  + np.abs(b64).sum(axis=1).max())))
    emit({"phase": "stream", "routine": "posv_ooc", "n": n,
          "panel_cols": pc, "nrhs": nrhs, "panels": nt,
          "resident_panels": resident, "cache_budget_bytes": budget,
          "size_cut": cut, "wall_s": round(took, 3),
          "h2d_bytes": c.get("ooc.h2d_bytes"),
          "d2h_bytes": c.get("ooc.d2h_bytes"),
          "staging_span_seconds": {k: round(v, 3)
                                   for k, v in sorted(staging.items())},
          "hpl_scaled_residual": res, "bound": HPL_BOUND,
          **comp.since(s0)})
    check(L.shape == (n, n) and np.isfinite(res) and res <= HPL_BOUND,
          "posv_ooc", resid=res)
    check((c.get("ooc.h2d_bytes") or 0) > n * n * 4
          and (c.get("ooc.d2h_bytes") or 0) >= n * n * 2,
          "stream staged and wrote back", counters=c)


def phase_serve(args, rng, comp):
    """64 requests through an in-process serve.Server (cold defaults:
    bucket route), then the same 64 on the ragged route — the only
    place a Pallas kernel runs natively on a served route."""
    from slate_tpu import batch, obs, serve
    per, nrhs = args.requests, 4
    lo, hi = args.serve_lo, args.serve_hi
    ns = np.clip(np.exp(rng.normal(np.log((lo * hi) ** 0.5), 0.7,
                                   2 * per)), lo, hi).astype(int)
    ns[0], ns[per] = hi, hi          # both ops reach the top ceiling
    probs = []
    for j, n in enumerate(ns):
        op = "posv" if j < per else "gesv"
        a = sym_dominant(rng, n) if op == "posv" \
            else rng.standard_normal((n, n)).astype(np.float32)
        probs.append((op, a, rng.standard_normal((n, nrhs))
                      .astype(np.float32)))

    def grade(route, answers):
        worst = 0.0
        for (op, a, b), x in zip(probs, answers):
            x = np.asarray(x)
            check(x.shape == b.shape and x.dtype == np.float32,
                  "%s %s answer shape/dtype" % (route, op),
                  shape=x.shape, dtype=x.dtype)
            worst = max(worst, hpl_resid(a.astype(np.float64), x,
                                         b.astype(np.float64),
                                         a.shape[0]))
        check(np.isfinite(worst) and worst <= HPL_BOUND, route,
              worst=worst)
        return worst

    s0 = comp.snap()
    t0 = time.perf_counter()
    with serve.Server() as srv:
        tickets = [srv.submit(op, a, b) for op, a, b in probs]
        bucket = [t.result(timeout=900) for t in tickets]
    took = time.perf_counter() - t0
    emit({"phase": "serve", "route": "bucket", "requests": len(probs),
          "n_min": int(ns.min()), "n_max": int(ns.max()),
          "n_median": float(np.median(ns)), "wall_s": round(took, 3),
          "worst_hpl_scaled_residual": grade("bucket", bucket),
          "bound": HPL_BOUND, **comp.since(s0)})

    obs.enable()
    obs.metrics.reset()
    s0 = comp.snap()
    t0 = time.perf_counter()
    ragged = []
    for op in ("posv", "gesv"):
        sel = [p for p in probs if p[0] == op]
        ragged += batch.run(op, [p[1] for p in sel],
                            [p[2] for p in sel], strategy="ragged")
    took = time.perf_counter() - t0
    c = obs.snapshot()["metrics"]["counters"]
    rejects = sorted({e.name for e in obs.bus_events()
                      if e.name.startswith("pallas.ragged_")
                      and e.name.endswith(".reject")})
    obs.disable()
    obs.clear()
    # tests/test_ragged.py holds ragged to bucket at rtol=atol=1e-10
    # in f64, i.e. 4.5e5 eps; the same multiple of f32's eps here,
    # relative to each answer's largest entry
    tol = 1e-10 / float(np.finfo(np.float64).eps) * EPS32
    diff = max(float(np.abs(np.asarray(r) - np.asarray(k)).max()
                     / np.abs(np.asarray(k)).max())
               for r, k in zip(ragged, bucket))
    emit({"phase": "serve", "route": "ragged", "requests": len(probs),
          "wall_s": round(took, 3),
          "ragged_dispatches": c.get("batch.ragged_dispatches"),
          "pallas_rejects": rejects,
          "worst_hpl_scaled_residual": grade("ragged", ragged),
          "bound": HPL_BOUND, "max_rel_diff_vs_bucket": diff,
          "diff_tol": tol, **comp.since(s0)})
    check((c.get("batch.ragged_dispatches") or 0) >= 2 and not rejects,
          "ragged kernels dispatched", counters=c, rejects=rejects)
    check(diff <= tol, "ragged matches bucket", diff=diff, tol=tol)


def phase_mesh(args, rng, comp):
    """Option.Grid on the four local chips (2x2) against the same two
    calls on device 0 alone, in this one process."""
    import jax
    import jax.numpy as jnp

    import slate_tpu as st
    from slate_tpu.core.methods import MethodFactor, MethodGemm
    from slate_tpu.core.options import Option
    check(len(jax.devices()) >= 4, "--mesh needs four devices",
          count=len(jax.devices()))
    n, nrhs, mb = args.n, args.nrhs, args.mb
    grid = st.make_grid(2, 2, devices=jax.devices()[:4])
    a = sym_dominant(rng, n)
    b = rng.standard_normal((n, nrhs)).astype(np.float32)
    g = rng.standard_normal((n, n)).astype(np.float32)

    def run(on, opts):
        """`on`: the grid, or None for the default device (chip 0)."""
        A = st.HermitianMatrix(st.Uplo.Lower, a, mb=mb, grid=on)
        B = st.Matrix(b, mb=mb, grid=on)
        G = st.Matrix(g, mb=mb, grid=on)
        C = st.TiledMatrix.zeros(n, n, mb, dtype=jnp.float32, grid=on)
        gopts = dict(opts)
        if Option.Grid in opts:
            gopts[Option.MethodGemm] = MethodGemm.Summa
        posv = jax.jit(lambda A, B: st.posv(A, B, opts)[1].data)
        gemm = jax.jit(lambda A, G, C:
                       st.gemm(1.0, A, G, 0.0, C, gopts).data)
        out = {}
        for name, fn, ops in (("posv", posv, (A, B)),
                              ("gemm", gemm, (A, G, C))):
            y, first = wall(lambda: fn(*ops))
            y, second = wall(lambda: fn(*ops))
            out[name] = (y, first, second)
        return out

    tiled = {Option.MethodFactor: MethodFactor.Tiled}
    with grid.mesh:
        four = run(grid, {Option.Grid: grid, **tiled})
    one = run(None, tiled)
    a64 = np.tril(a).astype(np.float64)
    a64 = a64 + np.tril(a64, -1).T
    for name in ("posv", "gemm"):
        y4, f4, s4 = four[name]
        y1, f1, s1 = one[name]
        devs = sorted({str(s.device) for s in y4.addressable_shards})
        check(len(devs) == 4, "%s result on four devices" % name,
              devices=devs)
        check(len({str(s.device) for s in y1.addressable_shards}) == 1,
              "%s one-chip comparison on one device" % name)
        h4, h1 = np.asarray(y4), np.asarray(y1)
        if name == "posv":
            res = hpl_resid(a64, h4[:n, :nrhs], b.astype(np.float64), n)
            check(res <= HPL_BOUND, "mesh posv", resid=res)
        else:
            ref = a.astype(np.float64) @ g.astype(np.float64)
            res = float(np.abs(h4[:n, :n] - ref).max()
                        / (EPS32 * n * np.abs(ref).max()))
            check(res <= 1.0, "mesh gemm", resid=res)
        diff = float(np.abs(h4 - h1).max() / np.abs(h1).max())
        emit({"phase": "mesh", "routine": name, "n": n, "grid": "2x2",
              "shard_devices": devs,
              "four_chip_first_s": round(f4, 3),
              "four_chip_second_s": round(s4, 4),
              "one_chip_first_s": round(f1, 3),
              "one_chip_second_s": round(s1, 4),
              "scaled_residual": res,
              "max_rel_diff_four_vs_one": diff})
        check(diff <= 1e3 * EPS32, "%s four vs one chip" % name,
              diff=diff)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--mesh", action="store_true",
                   help="four chips: run only the 2x2 grid phase and "
                        "its one-chip comparison")
    p.add_argument("--tiny", action="store_true",
                   help="CPU rehearsal: cut sizes, skip the "
                        "platform == 'tpu' assertion")
    args = p.parse_args(argv)
    # sizes: BENCH_r05's in-core sizes, ROADMAP Queue 2 item 2's cut
    # of the stream; --tiny changes sizes only
    sizes = dict(n=8192, nrhs=64, mb=512, gels_m=32768, gels_n=2048,
                 heev_n=4096, stream_n=32768, panel_cols=4096,
                 requests=32, serve_lo=64, serve_hi=1024)
    if args.tiny:
        sizes = dict(n=256, nrhs=8, mb=64, gels_m=512, gels_n=64,
                     heev_n=128, stream_n=512, panel_cols=64,
                     requests=4, serve_lo=16, serve_hi=96)
        if args.mesh and "xla_force_host_platform_device_count" \
                not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
    for k, v in sizes.items():
        setattr(args, k, v)

    t_start = time.perf_counter()
    from slate_tpu.utils import compile_cache
    args.cache_dir = compile_cache.enable()
    args.cache_was_empty = not (os.path.isdir(args.cache_dir)
                                and os.listdir(args.cache_dir))
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        print("chip_smoke: no TPU (jax found platform %r); use --tiny "
              "for the CPU rehearsal" % dev.platform, file=sys.stderr)
        return 2
    comp = Compiles()
    rng = np.random.default_rng(args.seed)
    phases = (phase_mesh,) if args.mesh else (
        phase_device, phase_incore, phase_stream, phase_serve)
    for phase in phases:
        s0, t0 = comp.snap(), time.perf_counter()
        phase(args, rng, comp)
        emit({"phase": phase.__name__[len("phase_"):],
              "wall_s": round(time.perf_counter() - t0, 3),
              **comp.since(s0)})
    emit({"phase": "total",
          "wall_s": round(time.perf_counter() - t_start, 3),
          **comp.since((0, 0.0, 0, 0))})
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
