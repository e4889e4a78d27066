"""Kind `grid`: one caller solves the same large system back to back on
a p x q grid of chips, through the public entry point under
`Option.Grid` (closed loop), and every answer is held to the
configuration's tolerance exactly as kind `solve` holds its own
(kinds/solve.py's `Cell`: warm-up, window, median wall under the
configuration's `wall_metric`, grading of X and of the sampled rows of
the factor).

A solve is the whole of what a user with host arrays does: A and B go
from the host to the mesh through the constructors' `grid=` argument
(each chip is sent its own block; the matrix is never whole on one),
`st.posv` factors and solves there, and the wall closes when X is
ready on the mesh; where the configuration's `wall_metric` is
`stream_solve_s`, whose definition ends with X on the host, when it is
there. The factor stays on the mesh: its sampled rows come to the host
chip by chip, outside the timed region.

The configuration's `routine` (`posv`) also names what lib/opcount.py
counts and which plain reference stands in for the program
(`plainref.SOLVERS`, tools/grid_control.py).
"""

import numpy as np

from benchmarks.kinds import solve
from benchmarks.lib import gen, refcheck


def rows_to_host(arr, rows):
    """`arr[rows]` of an array spread over chips, on the host: each
    chip's block gives the sampled rows it holds (no gather across the
    mesh, which could collect the whole array on every chip)."""
    out = np.empty((len(rows), arr.shape[1]), arr.dtype)
    for sh in arr.addressable_shards:
        lo, hi, _ = sh.index[0].indices(arr.shape[0])
        mine = (rows >= lo) & (rows < hi)
        if mine.any():
            out[mine, sh.index[1]] = np.asarray(sh.data[rows[mine] - lo])
    return out


class _OnGrid(solve._System):
    def __init__(self, cfg, r):
        import jax
        import slate_tpu as st
        from slate_tpu.core.methods import MethodFactor
        from slate_tpu.core.options import Option
        n, self.mb = cfg["n"], cfg["mb"]
        self.a = gen.spd_gram(r, n)
        self.b = gen.rhs(r, n, cfg["nrhs"])
        p, q = cfg["grid"]
        self.grid = st.make_grid(p, q, devices=jax.devices()[:p * q])
        self.opts = {Option.Grid: self.grid,
                     Option.MethodFactor: MethodFactor(cfg["method"])}
        self.x_to_host = cfg["wall_metric"] == "stream_solve_s"

    def solve(self):
        import jax
        import slate_tpu as st
        A = st.HermitianMatrix(st.Uplo.Lower, self.a, mb=self.mb,
                               grid=self.grid)
        B = st.Matrix(self.b, mb=self.mb, grid=self.grid)
        L, X = st.posv(A, B, self.opts)
        if self.x_to_host:
            return L, X.to_numpy()
        jax.block_until_ready(X.data)
        return L, X

    def to_host(self, L, X, rows):
        x = X if isinstance(X, np.ndarray) else X.to_numpy()
        return x, rows_to_host(L.data, rows)


class Cell(solve.Cell):
    def __init__(self, cfg, mix, seed):
        self.cfg, self.mix = cfg, mix
        self.sys = _OnGrid(cfg, gen.rng(seed, "solve"))
        self.rows = refcheck.factor_sample(cfg["n"],
                                           gen.rng(seed, "sample"))
        self.answers = []
        self.walls = []


def setup(cfg, mix, seed):
    return Cell(cfg, mix, seed)
