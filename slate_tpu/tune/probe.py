"""Microbenchmark driver (ISSUE 1 tentpole, part 1): measures
candidate configurations on the LIVE backend and writes the winners
into the persistent cache.

Timing discipline (the part that makes numbers trustworthy):

  * warmup / steady state separated — the first call of every
    candidate compiles (jit cache fill) and is EXCLUDED from timing;
  * jit-cache-aware repetition — every timed repetition re-enters the
    same compiled executable, so reps measure run time, not trace
    time; the reported figure is the min over reps (noise floor);
  * too-fast guards — when one call is below `min_time`, calls are
    chained until the measured span is above it, and the per-call
    time is the span divided by the chain length.

Probing is NEVER automatic: it runs only through `autotune()` (or
``python bench.py --tune``). Normal driver calls only READ the cache
(tune/select.py), so the cold-start path stays allocation- and
probe-free.
"""

from __future__ import annotations

import time
from typing import Dict, Iterable, List, Optional, Sequence

from . import cache as _cache
from . import stats


#: a probed winner must beat the default baseline by this relative
#: margin before it is persisted — noise-level "wins" (including over
#: a candidate configuration identical to the default) stay uncached
WIN_MARGIN = 0.02


def measure(fn, warmup: int = 1, reps: int = 3,
            min_time: float = 0.02) -> float:
    """Steady-state seconds per call of zero-arg `fn` (module doc)."""
    import jax
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn())          # compile + cache fill
    # size the chain so one rep's span is measurable
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    once = time.perf_counter() - t0
    k = max(1, int(min_time / max(once, 1e-9)))
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        out = None
        for _ in range(k):
            out = fn()
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / k)
    return best


def _spd(n: int, dtype):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen():
        x = jax.random.normal(jax.random.PRNGKey(0), (n, n),
                              jnp.float32)
        s = jnp.matmul(x, x.T,
                       precision=jax.lax.Precision.HIGHEST) / n \
            + 4.0 * jnp.eye(n, dtype=jnp.float32)
        return x.astype(dtype), s.astype(dtype)
    x, s = gen()
    jax.block_until_ready(s)
    return x, s


def _tiled(data, mtype, uplo, nb):
    from ..core.enums import Diag, MatrixType, Op, Uplo
    from ..core.tiles import TiledMatrix
    return TiledMatrix(data=data, m=data.shape[0], n=data.shape[1],
                       mb=nb, nb=nb, mtype=mtype, uplo=uplo,
                       op=Op.NoTrans, diag=Diag.NonUnit)


def _blocksize_runner(op: str, n: int, dtype):
    """Build the op's timed closure factory: cand -> zero-arg fn.
    The candidate block size enters through the channel the driver
    actually tunes on: getrf/geqrf through Option.BlockSize; for
    potrf through the tile geometry (Tiled method) — NOTE the potrf
    winner is ADVISORY (tile-size guidance for callers): the potrf
    driver takes its block size from the caller's tiles, so a cached
    potrf "nb" is never auto-selected (only potrf's lookahead /
    method_factor entries are). cand=None measures the driver's own
    default configuration (no explicit block size) — the before
    baseline of bench.py --tune."""
    from ..core.enums import MatrixType, Uplo
    from ..core.methods import MethodFactor
    from ..core.options import Option
    from .. import linalg
    x, spd = _spd(n, dtype)

    if op == "potrf":
        def mk(cand):
            A = _tiled(spd, MatrixType.Hermitian, Uplo.Lower,
                       cand or 256)
            opts = {Option.MethodFactor: MethodFactor.Tiled}
            return lambda: linalg.potrf(A, opts).data
        return mk
    if op == "getrf":
        def mk(cand):
            G = _tiled(x, MatrixType.General, Uplo.General,
                       min(256, n))
            opts = {Option.BlockSize: cand} if cand else None
            return lambda: linalg.getrf(G, opts).LU.data
        return mk
    if op == "geqrf":
        def mk(cand):
            G = _tiled(x, MatrixType.General, Uplo.General,
                       min(256, n))
            # cand=None is the TRUE Auto default (which routes Fused
            # below the fused_max_n crossover); candidates pin Tiled
            # with an explicit width — a Tiled winner is cached
            # together with fused_max_n=0 so the driver actually
            # routes to it (autotune)
            opts = ({Option.BlockSize: cand,
                     Option.MethodFactor: MethodFactor.Tiled}
                    if cand else None)
            return lambda: linalg.geqrf(G, opts).QR.data
        return mk
    raise KeyError("probe_blocksize: unknown op %r" % op)


def probe_blocksize(op: str, n: int, dtype,
                    candidates: Sequence[int],
                    reps: int = 3) -> List[Dict]:
    """Time `op` at size n for the driver's OWN default configuration
    (entry {"nb": None}, measured with cached entries bypassed — the
    cold-cache baseline every winner must beat) plus every candidate
    nb. Returns fastest first."""
    from ..utils import trace
    from . import select as _select
    t0 = time.perf_counter()
    mk = _blocksize_runner(op, n, dtype)
    out = []
    with trace.block("tune::probe::%s" % op):
        with _select.disabled():
            out.append({"nb": None, "seconds": measure(mk(None),
                                                       reps=reps)})
        for cand in candidates:
            t = measure(mk(int(cand)), reps=reps)
            out.append({"nb": int(cand), "seconds": t})
    stats.add_probe_time(time.perf_counter() - t0)
    return sorted(out, key=lambda d: d["seconds"])


def probe_method_eig(n: int, dtype, reps: int = 2) -> List[Dict]:
    """Time heev's Auto DEFAULT route (the fused QDWH path — the
    baseline a cached decision must beat) against the explicitly
    routed staged pipelines (MethodEig.DC = two-stage Cuppen,
    MethodEig.QRIteration = two-stage QR iteration) at size n.
    Returns results fastest first; "auto" winning means KEEP the
    default (autotune caches nothing in that case, so a probe can
    never regress Auto below the cold-cache behavior). Runs under
    select.disabled() so the Auto measurement is the frozen default,
    not a previously-cached reroute."""
    from ..core.enums import MatrixType, Uplo
    from ..core.methods import MethodEig
    from ..core.options import Option
    from ..utils import trace
    from .. import linalg
    from . import select as _select
    t0 = time.perf_counter()
    _, spd = _spd(n, dtype)
    A = _tiled(spd, MatrixType.Hermitian, Uplo.Lower, min(128, n))
    candidates = [
        ("auto", None),
        ("dc", {Option.MethodEig: MethodEig.DC}),
        ("qr_iteration", {Option.MethodEig: MethodEig.QRIteration}),
    ]
    out = []
    with trace.block("tune::probe::heev"), _select.disabled():
        for label, mopts in candidates:
            t = measure(
                lambda mo=mopts: linalg.heev(A, mo).values,
                reps=reps)
            out.append({"method": label, "seconds": t})
    stats.add_probe_time(time.perf_counter() - t0)
    return sorted(out, key=lambda d: d["seconds"])


def probe_lu_panel(m: int, w: int, dtype, reps: int = 3) -> List[Dict]:
    """Time the LU panel-route candidates at (m, w) (ISSUE 6): the
    cold-default route (entry {"method": None} — lu._lu_panel with
    cached entries bypassed, the baseline a winner must beat), the
    masked fori kernel, the left-looking `blocked` kernel where its
    gates accept (lu.lu_panel_blocked: a probe of a tall shape sees it
    beside `fori`), and the two Pallas kernels (rank-1 `pallas`,
    block-recursive `pallas_rec`) where their entry gates accept.
    Fastest first; a persisted winner reroutes _lu_panel for the
    whole (backend, device, dtype, bucket) class — and through it
    every LU consumer."""
    import jax
    import jax.numpy as jnp
    from ..core.methods import MethodLUPanel
    from ..linalg.lu import (_blocked_ib, _lu_panel, lu_panel_blocked,
                             lu_panel_fori)
    from ..ops import pallas_kernels as pk
    from ..utils import trace
    from . import select as _select
    t0 = time.perf_counter()
    key = jax.random.PRNGKey(0)
    p = jax.random.normal(key, (m, w), jnp.float32).astype(dtype)
    out = []
    with trace.block("tune::probe::lu_panel"):
        with _select.disabled():
            out.append({"method": None,
                        "seconds": measure(lambda: _lu_panel(p)[0],
                                           reps=reps)})
        out.append({"method": "fori",
                    "seconds": measure(lambda: lu_panel_fori(p)[0],
                                       reps=reps)})
        if MethodLUPanel.blocked_ok(m, w, p.dtype):
            blocked = jax.jit(lu_panel_blocked, static_argnums=1)
            out.append({"method": "blocked",
                        "seconds": measure(
                            lambda: blocked(
                                p, _blocked_ib(w, m, p.dtype))[0],
                            reps=reps)})
        for label, fn in (("pallas", pk.lu_panel),
                          ("pallas_rec", pk.lu_panel_rec)):
            if fn(p) is None:        # entry gate rejected this shape
                continue
            out.append({"method": label,
                        "seconds": measure(lambda fn=fn: fn(p)[0],
                                           reps=reps)})
    stats.add_probe_time(time.perf_counter() - t0)
    return sorted(out, key=lambda d: d["seconds"])


def probe_ooc_panel(n: int, candidates: Sequence[int],
                    reps: int = 2) -> List[Dict]:
    """Time the streamed Cholesky at the frozen default width (entry
    {"panel_cols": None}, resolved by the driver with cached entries
    bypassed — the cold-cache baseline) and at each candidate panel
    width (host-resident input, the ooc.py contract); fastest
    first."""
    import numpy as np
    from ..linalg.ooc import potrf_ooc
    from ..utils import trace
    from . import select as _select
    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n)).astype(np.float32)
    a = x @ x.T / n + 4.0 * np.eye(n, dtype=np.float32)
    out = []

    def timed(cand):
        best = float("inf")
        potrf_ooc(a, panel_cols=cand)                 # compile fill
        for _ in range(max(reps, 1)):
            t1 = time.perf_counter()
            potrf_ooc(a, panel_cols=cand)
            best = min(best, time.perf_counter() - t1)
        return best

    with trace.block("tune::probe::ooc"):
        with _select.disabled():
            out.append({"panel_cols": None, "seconds": timed(None)})
        for cand in candidates:
            out.append({"panel_cols": int(cand),
                        "seconds": timed(int(cand))})
    stats.add_probe_time(time.perf_counter() - t0)
    return sorted(out, key=lambda d: d["seconds"])


def autotune(ops: Iterable[str] = ("getrf", "geqrf"),
             n: int = 1024, dtype=None,
             nb_candidates: Optional[Sequence[int]] = None,
             write: bool = True, reps: int = 3) -> Dict:
    """Probe each op at size n and (optionally) persist the winners.
    Returns {op: {"chosen": {...}, "results": [...]}}. Accepted op
    names: getrf/geqrf (block size — auto-selected by the drivers),
    potrf (tile-size guidance, ADVISORY: see _blocksize_runner),
    heev (method routing), ooc (panel width), lu_panel (panel-route
    method at height n — native vs fori vs the Pallas kernels,
    ISSUE 6; n is the panel HEIGHT here).

    Never-regress contract: every probe measures the driver's own
    default configuration as a baseline candidate, and a winner is
    persisted ONLY when it beat that baseline by more than the
    WIN_MARGIN ("chosen" is empty otherwise) — so a probe can never
    leave the cache slower than a cold start, and a noise-level
    "win" over a configuration identical to the default is never
    persisted as a measured improvement."""
    import numpy as np
    dtype = np.dtype(dtype or np.float32)
    if nb_candidates is None:
        nb_candidates = [c for c in (64, 128, 256, 512, 1024)
                         if c <= max(n, 64)]
    report: Dict[str, Dict] = {}
    c = _cache.get_cache()

    def beats_default(results, key, default_label=None):
        base = next(r["seconds"] for r in results
                    if r[key] == default_label)
        best = results[0]
        return best[key] != default_label \
            and best["seconds"] < (1.0 - WIN_MARGIN) * base

    for op in ops:
        if op == "heev":
            results = probe_method_eig(n, dtype, reps=reps)
            chosen = {"method_eig": results[0]["method"]} \
                if beats_default(results, "method", "auto") else {}
        elif op == "lu_panel":
            # panel probes key the cache by the panel HEIGHT bucket
            # (the _lu_panel lookup key); width = the driver's frozen
            # cap for the shape class
            w = min(max(n // 16, 64), 512)
            results = probe_lu_panel(n, w, dtype, reps=reps)
            chosen = {"method_lu_panel": results[0]["method"]} \
                if beats_default(results, "method") else {}
        elif op == "ooc":
            cands = [p for p in (max(n // 8, 32), max(n // 4, 64),
                                 max(n // 2, 128))
                     if p <= n] or [n]
            results = probe_ooc_panel(n, sorted(set(cands)),
                                      reps=reps)
            chosen = {"panel_cols": results[0]["panel_cols"]} \
                if beats_default(results, "panel_cols") else {}
        else:
            results = probe_blocksize(op, n, dtype, nb_candidates,
                                      reps=reps)
            chosen = {"nb": results[0]["nb"]} \
                if beats_default(results, "nb") else {}
            if chosen and op == "geqrf":
                # the winner is a Tiled configuration; route the
                # bucket to it (Auto would otherwise take the Fused
                # crossover below fused_max_n and never read nb)
                chosen["fused_max_n"] = 0
        report[op] = {"chosen": chosen, "results": results}
        if write and chosen:
            c.put(op, dtype, n, chosen,
                  meta={"n": n, "results": results})
    if write:
        report["_cache_path"] = c.save()
    return report
