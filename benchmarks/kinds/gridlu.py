"""Kind `gridlu`: one caller solves the same large GENERAL system back
to back on a p x q grid of chips, through `st.gesv` under `Option.Grid`
(closed loop): ScaLAPACK's `psgesv`, HPL's P x Q. It is kind `solve`'s
`Cell` (kinds/solve.py: warm-up, window, median wall under the
configuration's `wall_metric`) with kind `grid`'s deployment
(kinds/grid.py: host arrays placed through the constructors' `grid=`
argument, the wall closed with X on the host, the factor's samples
fetched chip by chip, `rows_to_host`) and kind `streamlu`'s comparison
(kinds/streamlu.py: `grade` and `check`), all three by import.

A solve is what a user with host arrays does:

    A = st.Matrix(a, mb=mb, grid=grid); B = st.Matrix(b, mb=mb, grid=grid)
    F, X = st.gesv(A, B, {Option.Grid: grid,
                          Option.MethodFactor: MethodFactor.Tiled})
    x = X.to_numpy()

Every answer the window made, warm-up included, is graded on the host
in f64 (streamlu.NUMBERS): HPL's scaled residual of X; the returned
packed factor and pivots against the matrix on 128 seeded rows and 128
seeded columns (the last of each among them), the factor's samples
read from each chip's own block, never gathered; `ipiv` a valid
sequence of swap targets. NaN, a wrong shape or dtype fails.

A program that cannot run the deployment fails in the warm-up, before
the window, with the compiler's or the allocator's own error and exit
code 1 (a commit before PR 49 asks a chip for more than it has).
"""

import numpy as np

from benchmarks.kinds import solve, streamlu
from benchmarks.kinds.grid import rows_to_host
from benchmarks.lib import gen, refcheck, streamlugen


def cols_to_host(arr, cols):
    """`arr[:, cols]` of an array spread over chips, on the host:
    `rows_to_host`'s twin along the other axis."""
    out = np.empty((arr.shape[0], len(cols)), arr.dtype)
    for sh in arr.addressable_shards:
        lo, hi, _ = sh.index[1].indices(arr.shape[1])
        mine = (cols >= lo) & (cols < hi)
        if mine.any():
            out[sh.index[0], mine] = np.asarray(sh.data[:, cols[mine] - lo])
    return out


class _Host(solve._System):
    """The system alone, on the host (tools/gridlu_control.py grades
    the plain reference on it)."""

    def __init__(self, cfg, r):
        self.a, self.b = streamlugen.system(r, cfg["n"], cfg["nrhs"])


class _OnGrid(_Host):
    def __init__(self, cfg, r):
        import jax
        import slate_tpu as st
        from slate_tpu.core.methods import MethodFactor
        from slate_tpu.core.options import Option
        super().__init__(cfg, r)
        self.mb = cfg["mb"]
        p, q = cfg["grid"]
        self.grid = st.make_grid(p, q, devices=jax.devices()[:p * q])
        self.opts = {Option.Grid: self.grid,
                     Option.MethodFactor: MethodFactor(cfg["method"])}

    def solve(self):
        import slate_tpu as st
        A = st.Matrix(self.a, mb=self.mb, grid=self.grid)
        B = st.Matrix(self.b, mb=self.mb, grid=self.grid)
        F, X = st.gesv(A, B, self.opts)
        return F, X.to_numpy()

    def to_host(self, F, x, sample):
        rows, cols = sample
        lu = F.LU.data
        return x, (rows_to_host(lu, rows), cols_to_host(lu, cols),
                   np.array(F.pivots))


class Cell(streamlu.Cell):
    def __init__(self, cfg, mix, seed, system=_OnGrid):
        self.cfg, self.mix = cfg, mix
        self.sys = system(cfg, gen.rng(seed, "solve"))
        # (rows, cols) of the factor each answer keeps
        self.rows = (refcheck.factor_sample(cfg["n"],
                                            gen.rng(seed, "sample")),
                     refcheck.factor_sample(cfg["n"],
                                            gen.rng(seed, "columns")))
        self.answers = []       # host (X, (L rows, U cols, ipiv)) a solve
        self.walls = []


def setup(cfg, mix, seed):
    return Cell(cfg, mix, seed)
