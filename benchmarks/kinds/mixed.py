"""Kind `mixed`: one caller solves the same dense system back to back
through `st.gesv_mixed` with NO option (closed loop): LU in the chip's
low precision, bf16, refinement back to f32. HPL-MxP's shape of run:
the matrix is generated in set-up and is resident on the device before
the window (HPL-MxP generates in place and does not time it), the
right-hand side likewise, and X is left on the device.

It is kind `solve`'s `Cell` (kinds/solve.py: warm-up, window, median
wall under the configuration's `wall_metric`) with a system and a
comparison of its own. A solve is timed from the device arrays to
`block_until_ready` of X. Every answer the window made is graded:

    scaled_residual_max  HPL's number with f32 eps, on the host in f64
                         (lib/refcheck.py hpl_resid_blocked)
    fallbacks            answers with iters < 0: the f32 deployment's
                         answer, not this one's; limit 0
    refine_sweeps_max    the largest sweep count; a factor below the
                         stated precision needs more

NaN, a wrong shape or dtype fails. A window's solves return the same
bytes, and an answer whose bytes were seen is graded once.
"""

import inspect
import sys

import numpy as np

from benchmarks.kinds import solve
from benchmarks.lib import gen, refcheck

NUMBERS = ("fallbacks", "refine_sweeps_max", "scaled_residual_max")


def hplmxp_system(r, n):
    """HPL-MxP's family (hpl-ai's generator's law): off-diagonal
    entries uniform on (-0.5, 0.5), each diagonal entry the sum of the
    absolute values of its row (about n/4), so no pivot leaves the
    diagonal and a low-precision factor refines in a few sweeps; b
    uniform on (-0.5, 0.5). f32, made in bulk on the host."""
    a = r.random((n, n), dtype=np.float32)
    a -= np.float32(0.5)
    idx = np.arange(n)
    a[idx, idx] = 0
    a[idx, idx] = np.abs(a).sum(axis=1, dtype=np.float32)
    b = r.random((n, 1), dtype=np.float32) - np.float32(0.5)
    return a, b


def sweeps_of(iters):
    """The sweep count in `iters` (reference info convention: a
    fallback after k sweeps is -k - 1)."""
    return iters if iters >= 0 else -iters - 1


class _Resident(solve._System):
    #: what the caller passes to st.gesv_mixed: nothing
    opts = None

    def __init__(self, cfg, r):
        import jax
        self.n, self.mb = cfg["n"], cfg["mb"]
        self.a, self.b = hplmxp_system(r, self.n)
        # on the device before the window; the host copies stay for
        # the comparison
        self.a_dev, self.b_dev = jax.device_put(self.a), \
            jax.device_put(self.b)
        jax.block_until_ready((self.a_dev, self.b_dev))

    def solve(self):
        import jax
        import slate_tpu as st
        F, X, iters = st.gesv_mixed(st.Matrix(self.a_dev, mb=self.mb),
                                    st.Matrix(self.b_dev, mb=self.mb),
                                    self.opts)
        jax.block_until_ready(X.data)
        return (F, int(iters)), X

    def to_host(self, F, X, rows):
        return X.to_numpy(), F[1]


class Cell(solve.Cell):
    def __init__(self, cfg, mix, seed):
        self.cfg, self.mix = cfg, mix
        self.sys = _Resident(cfg, gen.rng(seed, "solve"))
        self.rows = None
        self.answers = []       # host (X, iters) per solve, warm-up too
        self.walls = []

    def check(self):
        """Every answer made, warm-up included; answers of the same
        bytes and sweep count are graded once."""
        tol = self.cfg["tolerance"]
        shape = self.sys.b.shape
        graded = []                         # (x, iters, residual)
        worst = dict.fromkeys(NUMBERS, 0.0)
        bad = 0
        n_warm = len(self.answers) - len(self.walls)
        for i, (x, iters) in enumerate(self.answers):
            if x.shape != shape or x.dtype != np.float32:
                resid = float("inf")
            else:
                resid = next((g[2] for g in graded if g[1] == iters
                              and np.array_equal(g[0], x)), None)
                if resid is None:
                    resid = self.sys.residual(x)
                    graded.append((x, iters, resid))
            nums = {"scaled_residual_max":
                    resid if np.isfinite(resid) else float("inf"),
                    "fallbacks": float(iters < 0),
                    "refine_sweeps_max": float(sweeps_of(iters))}
            ok = all(v <= tol[k] for k, v in nums.items())
            bad += (not ok) and i >= n_warm
            worst["fallbacks"] += nums.pop("fallbacks")
            for k, v in nums.items():
                worst[k] = max(worst[k], v)
        compared = [[k, worst[k], tol[k]] for k in NUMBERS]
        return {"attempted": len(self.walls), "failed": bad,
                "correct": all(v <= lim for _, v, lim in compared),
                "compared": compared, "distinct_answers": len(graded)}


def compile_probe(cfg):
    """Whether this program's `st.gesv_mixed` is the form a window can
    hold: the lo solve and the refinement compiled once a shape, the
    fallback decided on the host, the lo factor with a composed
    permutation. Asked of the program itself, before the inputs are
    made. A commit before PR 42 traces a `lax.while_loop` over fresh
    closures at every call, with a whole f32 `getrf` at the full size
    inside a `lax.cond`, and hands a bf16 operand to an unrolled LU
    with fori panels 1024 columns wide at 16384 rows: it would compile
    for longer than a run lasts, at every call. Exits 4 at once, so
    that such a commit fails cleanly."""
    from slate_tpu.linalg import lu, refine
    takes = inspect.signature(refine.iterative_refinement).parameters
    if "factors" not in takes or "perm" not in lu.LUFactors._fields:
        print("kinds/mixed.py: this program's st.gesv_mixed traces its "
              "refinement anew at every call with the f32 fallback "
              "compiled into it, and has no lo LU route at n=%d: this "
              "program cannot run a configuration of kind `mixed`"
              % cfg["n"], file=sys.stderr)
        raise SystemExit(4)


def setup(cfg, mix, seed):
    compile_probe(cfg)
    return Cell(cfg, mix, seed)
