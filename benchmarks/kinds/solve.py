"""Kind `solve`: one caller solves the same large system back to back
through a public entry point (closed loop), and every answer is held
to the configuration's residual tolerance.

The configuration's `routine` picks the entry point:
  gesv      st.gesv on st.Matrix (in core), general matrix
  posv_ooc  linalg.ooc.posv_ooc (streamed, host array in and out),
            dense SPD matrix, `resident_panels` of `panels` on chip
A solve is timed from the host arrays to the result being ready (in
core: `block_until_ready` of X on the device; streamed: X on the host),
and the median wall is reported under the configuration's
`wall_metric`, so that deployments whose walls spread differently are
held to bounds of their own.
"""

import gc
import statistics
import time

import numpy as np

from benchmarks.lib import gen, refcheck


class _System:
    def residual(self, x):
        return refcheck.hpl_resid_blocked(self.a, x, self.b,
                                          self.a.shape[0])


class _InCore(_System):
    def __init__(self, cfg, r):
        n, self.mb = cfg["n"], cfg["mb"]
        self.a = gen.general(r, n)
        self.b = gen.rhs(r, n, cfg["nrhs"])

    def solve(self):
        import jax
        import slate_tpu as st
        F, X = st.gesv(st.Matrix(self.a, mb=self.mb),
                       st.Matrix(self.b, mb=self.mb))[:2]
        jax.block_until_ready(X.data)
        return F, X

    def to_host(self, F, X, rows):
        return X.to_numpy(), None


class _Streamed(_System):
    def __init__(self, cfg, r):
        n, self.pc = cfg["n"], cfg["panel_cols"]
        self.a = gen.spd_gram(r, n)
        self.b = gen.rhs(r, n, cfg["nrhs"])
        self.budget = cfg["resident_panels"] * n * self.pc * 4

    def solve(self):
        from slate_tpu.linalg import ooc
        return ooc.posv_ooc(self.a, self.b, panel_cols=self.pc,
                            cache_budget_bytes=self.budget)

    def to_host(self, L, X, rows):
        return np.asarray(X), np.asarray(L)[rows]


class Cell:
    def __init__(self, cfg, mix, seed):
        self.cfg, self.mix = cfg, mix
        r = gen.rng(seed, "solve")
        self.sys = {"posv_ooc": _Streamed,
                    "gesv": _InCore}[cfg["routine"]](cfg, r)
        self.rows = refcheck.factor_sample(cfg["n"],
                                           gen.rng(seed, "sample"))
        self.answers = []       # host (X, sampled factor rows) per solve
        self.walls = []         # window solves only

    def _one(self, tracer=None):
        if tracer is not None:
            tracer.start()
        t0 = time.perf_counter()
        F, X = self.sys.solve()
        took = time.perf_counter() - t0
        if tracer is not None:
            tracer.stop()
        self.answers.append(self.sys.to_host(F, X, self.rows))
        # outside the timed region: the stream engine leaves reference
        # cycles that keep about 2 GB of panels on the device per solve
        # until a full collection; without this the fifth back-to-back
        # streamed solve fails RESOURCE_EXHAUSTED (PERF.md, PR 24)
        del F, X
        gc.collect()
        return took

    def warm(self):
        for _ in range(self.mix["warm_solves"]):
            self._one()

    def window(self, seconds, tracer):
        """Whole solves back to back; one that STARTS inside the window
        is finished and counted. The traced run puts the profiler
        around the window's first solve."""
        t_end = time.perf_counter() + seconds
        while not self.walls or time.perf_counter() < t_end:
            self.walls.append(self._one(None if self.walls else tracer))
        return {"solves": len(self.walls),
                "slice_solves": 1 if tracer is not None else 0}

    def end_to_end(self):
        return {self.cfg["wall_metric"]: statistics.median(self.walls)}

    def check(self):
        """Every answer made, warm-up included: the scaled residual of
        X, and where the routine hands back a Cholesky factor its
        residual on the sampled rows. Answers with identical bytes are
        graded once."""
        tol = self.cfg["tolerance"]
        shape, sysm, rows = self.sys.b.shape, self.sys, self.rows
        a_ss = sysm.a[np.ix_(rows, rows)]
        graded = []                     # (x, rows of L, numbers)
        worst = {"scaled_residual_max": 0.0}
        bad = 0
        n_warm = len(self.answers) - len(self.walls)
        for i, (x, lr) in enumerate(self.answers):
            if x.shape != shape or x.dtype != np.float32:
                nums = {"scaled_residual_max": float("inf")}
            else:
                nums = next((g[2] for g in graded
                             if np.array_equal(g[0], x)
                             and (lr is None or np.array_equal(g[1], lr))),
                            None)
                if nums is None:
                    nums = {"scaled_residual_max": sysm.residual(x)}
                    if lr is not None:
                        nums["factor_residual_rms"] = \
                            refcheck.factor_resid(a_ss, lr, rows)
                    graded.append((x, lr, nums))
            ok = True
            for k, v in nums.items():
                v = v if np.isfinite(v) else float("inf")
                worst[k] = max(worst.get(k, 0.0), v)
                ok = ok and v <= tol[k]
            bad += (not ok) and i >= n_warm
        compared = [[k, worst[k], tol[k]] for k in sorted(worst)]
        return {"attempted": len(self.walls), "failed": bad,
                "correct": all(v <= lim for _, v, lim in compared),
                "compared": compared, "distinct_answers": len(graded)}


def setup(cfg, mix, seed):
    return Cell(cfg, mix, seed)
