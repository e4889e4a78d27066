"""Kind `lstsq`: one caller solves the same tall least-squares problem
back to back through the public entry point with NO option set (closed
loop), and every answer is held to the configuration's guarantee: a
QR-grade solution.

It is kind `solve`'s `Cell` (kinds/solve.py: warm-up, window, median
wall under the configuration's `wall_metric`, timed from the host
arrays to `block_until_ready` of X on the device) with a system of
its own and a comparison of its own. The number compared is the
forward error of X against the f64 solution of the same f32 data,

    solution_error_max = max over answers and columns of
                         ||x - x_ref||_2 / ||x_ref||_2,

computed on the host after the window. The normal-equations residual
A^T (B - A X) is NOT compared: CholQR makes it small by construction,
and it would pass the answers this deployment exists to refuse.

The reference solution is the f64 normal equations of the f32 data,
accumulated a block of rows at a time, with one step of refinement on
the f64 residual: at cond 1e4 its own error is cond^2 2^-53 = 1e-8
before the refinement, five orders under the limit. It costs two
passes over A in f64 (2.2e12 flops each at the cell's size; some 25 s
of the host after the window).
"""

import sys

import numpy as np
from scipy.linalg import cho_factor, cho_solve

from benchmarks.kinds import solve
from benchmarks.lib import gen, lstsqgen


def reference_solution(a32, b32, rows=4096):
    """argmin ||A X - B|| in f64 for f32 data, without a second copy
    of A: normal equations accumulated over row blocks, then one
    refinement step X += (A^T A)^-1 A^T (B - A X)."""
    n = a32.shape[1]
    b64 = np.asarray(b32, np.float64)

    def passes(x):
        g = np.zeros((n, n)) if x is None else None
        c = np.zeros((n, b64.shape[1]))
        for i in range(0, a32.shape[0], rows):
            blk = a32[i:i + rows].astype(np.float64)
            rhs = b64[i:i + rows]
            if x is None:
                g += blk.T @ blk
            else:
                rhs = rhs - blk @ x
            c += blk.T @ rhs
        return g, c

    g, c = passes(None)
    fac = cho_factor(g, lower=True, check_finite=False)
    x = cho_solve(fac, c, check_finite=False)
    return x + cho_solve(fac, passes(x)[1], check_finite=False)


def solution_error(x, x_ref):
    """max over columns of ||x - x_ref|| / ||x_ref||; inf for an
    answer that is not finite."""
    x = np.asarray(x, np.float64)
    if not np.isfinite(x).all():
        return float("inf")
    return float((np.linalg.norm(x - x_ref, axis=0)
                  / np.linalg.norm(x_ref, axis=0)).max())


class _TallLstsq:
    #: what the caller passes to st.gels: nothing, the route is the
    #: library's (tools/lstsq_control.py forces one for control (c))
    opts = None

    def __init__(self, cfg, r):
        mx = cfg["matrix"]
        self.mb = cfg["mb"]
        self.a, self.b = lstsqgen.tall_lstsq(
            r, cfg["m"], cfg["n"], cfg["nrhs"], mx["cond"], mx["noise"])

    def solve(self):
        import jax
        import slate_tpu as st
        X = st.gels(st.Matrix(self.a, mb=self.mb),
                    st.Matrix(self.b, mb=self.mb), self.opts)
        jax.block_until_ready(X.data)
        return None, X

    def to_host(self, F, X, rows):
        return X.to_numpy()


class Cell(solve.Cell):
    def __init__(self, cfg, mix, seed):
        self.cfg, self.mix = cfg, mix
        self.sys = _TallLstsq(cfg, gen.rng(seed, "solve"))
        self.rows = None
        self.answers = []       # host X per solve, warm-up included
        self.walls = []

    def check(self):
        """Every answer made, warm-up included, against the f64
        solution; answers with identical bytes are graded once."""
        limit = self.cfg["tolerance"]["solution_error_max"]
        shape = (self.cfg["n"], self.cfg["nrhs"])
        x_ref = reference_solution(self.sys.a, self.sys.b)
        graded = []                         # (x, error)
        worst, bad = 0.0, 0
        n_warm = len(self.answers) - len(self.walls)
        for i, x in enumerate(self.answers):
            if x.shape != shape or x.dtype != np.float32:
                e = float("inf")
            else:
                e = next((g[1] for g in graded
                          if np.array_equal(g[0], x)), None)
                if e is None:
                    e = solution_error(x, x_ref)
                    graded.append((x, e))
            worst = max(worst, e)
            bad += (not e <= limit) and i >= n_warm
        return {"attempted": len(self.walls), "failed": bad,
                "correct": bool(worst <= limit),
                "compared": [["solution_error_max", worst, limit]],
                "distinct_answers": len(graded)}


def route_probe():
    """Whether this program's `st.gels` with no option can run the
    configuration at all: one 512 x 32 consistent system at cond 1e5,
    whose Gram matrix is not numerically positive definite (cond^2
    eps = 1e3). A program that routes on the shape alone hands back
    NaN or noise without an error there (the parent of PR 31 does);
    Householder QR reads 2e-3. Exits 4 at once, before the 1 GiB of
    inputs is made, so that a commit that cannot carry the deployment
    fails cleanly instead of timing wrong answers."""
    import slate_tpu as st
    a, b = lstsqgen.tall_lstsq(gen.rng(0, "probe"), 512, 32, 2, 1e5, 0.0)
    x = st.gels(st.Matrix(a, mb=32), st.Matrix(b, mb=32)).to_numpy()
    err = solution_error(x, reference_solution(a, b))
    if not err <= 0.1:
        print("kinds/lstsq.py: st.gels with no option returned an answer "
              "%r off the f64 solution of a 512 x 32 system at cond 1e5, "
              "with no error raised: this program cannot run a "
              "configuration of kind `lstsq`" % (err,), file=sys.stderr)
        raise SystemExit(4)


def setup(cfg, mix, seed):
    route_probe()
    return Cell(cfg, mix, seed)
