"""Kind `heev`: one caller diagonalises the same dense real symmetric
matrix back to back through the public entry point with NO option set
(closed loop), all eigenvalues and all eigenvectors, and every answer
is held to the configuration's guarantee: a backward-stable
eigendecomposition.

It is kind `solve`'s `Cell` (kinds/solve.py: warm-up, window, median
wall under the configuration's `wall_metric`) with a system and a
comparison of its own. A solve is timed from the host array to
`block_until_ready` of w and V on the device. Three numbers are
compared, on the host in f64 after the window, each over n eps_f32
(and ||A||_2 where it has a scale):

    residual_max         = max_j ||A v_j - w_j v_j||_2 / ||A||_2
    orthogonality        = ||V^T V - I||_F
    eigenvalue_error_max = max_i |w_i - w_ref_i| / ||A||_2

w_ref is the f64 `eigvalsh` of the same f32 data and ||A||_2 its
largest magnitude. NaN, a wrong shape or dtype, or w not ascending
fails. The solves of a window return the same bytes, and an answer
whose bytes were seen is held once and graded once; the products are
formed a block of columns at a time.
"""

import sys

import numpy as np

from benchmarks.kinds import solve
from benchmarks.lib import gen, heevgen

EPS32 = float(np.finfo(np.float32).eps)
NUMBERS = ("eigenvalue_error_max", "orthogonality", "residual_max")


def reference_spectrum(a32):
    """(w_ref ascending, ||A||_2) in f64 for f32 data."""
    w = np.linalg.eigvalsh(a32.astype(np.float64))
    return w, float(np.abs(w).max())


def grade(a32, w, v, w_ref, norm2, cols=1024):
    """The three compared numbers of one answer (w, V); every one inf
    for an answer that is not finite or whose w is not ascending."""
    n = a32.shape[0]
    if not (np.isfinite(w).all() and np.isfinite(v).all()
            and (np.diff(w) >= 0).all()):
        return dict.fromkeys(NUMBERS, float("inf"))
    w64 = np.asarray(w, np.float64)
    a64, v64 = a32.astype(np.float64), np.asarray(v, np.float64)
    resid, gram2 = 0.0, 0.0
    for j in range(0, n, cols):
        vj = v64[:, j:j + cols]
        r = a64 @ vj - vj * w64[j:j + cols]
        resid = max(resid, float(np.linalg.norm(r, axis=0).max()))
        g = v64.T @ vj
        k = np.arange(vj.shape[1])
        g[j + k, k] -= 1.0
        gram2 += float((g * g).sum())
    scale = n * EPS32
    return {"residual_max": resid / (scale * norm2),
            "orthogonality": float(np.sqrt(gram2)) / scale,
            "eigenvalue_error_max":
                float(np.abs(w64 - w_ref).max()) / (scale * norm2)}


def worst_eigenvalue(w, w_ref):
    """[index, w_ref there] of the eigenvalue farthest from the
    reference spectrum: a diagnosis, not compared (beside a split
    point of the solver it is the split's dropped coupling; among the
    largest magnitudes, rounding). None for a malformed answer."""
    if np.shape(w) != w_ref.shape or not np.isfinite(w).all():
        return None
    i = int(np.abs(np.asarray(w, np.float64) - w_ref).argmax())
    return [i, float(w_ref[i])]


class _SymEig:
    #: what the caller passes to st.heev: nothing, the route is the
    #: library's
    opts = None

    def __init__(self, cfg, r):
        mx = cfg["matrix"]
        self.n, self.mb = cfg["n"], cfg["mb"]
        self.a, _ = heevgen.geo_symmetric(r, self.n, mx["cond"],
                                             mx["sign_seed"])
        self.held = []          # distinct host answers (w, V)

    def solve(self):
        import jax
        import slate_tpu as st
        res = st.heev(st.HermitianMatrix(st.Uplo.Lower, self.a,
                                         mb=self.mb), self.opts)
        jax.block_until_ready((res.values, res.vectors.data))
        return res.values, res.vectors

    def to_host(self, w, V, rows):
        """The answer on the host; one whose bytes were seen before is
        the held one (a window's solves return the same digits, and
        sixteen copies of V are 4 GB of the host for nothing)."""
        got = (np.asarray(w), V.to_numpy())
        for held in self.held:
            if all(np.array_equal(x, y) for x, y in zip(held, got)):
                return held
        self.held.append(got)
        return got


class Cell(solve.Cell):
    def __init__(self, cfg, mix, seed):
        self.cfg, self.mix = cfg, mix
        self.sys = _SymEig(cfg, gen.rng(seed, "solve"))
        self.rows = None
        self.answers = []       # host (w, V) per solve, warm-up included
        self.walls = []

    def check(self):
        """Every answer made, warm-up included; answers that are one
        held object are graded once."""
        tol = self.cfg["tolerance"]
        n = self.cfg["n"]
        w_ref, norm2 = reference_spectrum(self.sys.a)
        graded = {}                         # id(answer) -> numbers
        worst = dict.fromkeys(NUMBERS, 0.0)
        bad = 0
        n_warm = len(self.answers) - len(self.walls)
        for i, ans in enumerate(self.answers):
            w, v = ans
            if (w.shape != (n,) or v.shape != (n, n)
                    or w.dtype != np.float32 or v.dtype != np.float32):
                nums = dict.fromkeys(NUMBERS, float("inf"))
            else:
                if id(ans) not in graded:
                    graded[id(ans)] = grade(self.sys.a, w, v, w_ref, norm2)
                nums = graded[id(ans)]
            ok = True
            for k, x in nums.items():
                x = x if np.isfinite(x) else float("inf")
                worst[k] = max(worst[k], x)
                ok = ok and x <= tol[k]
            bad += (not ok) and i >= n_warm
        compared = [[k, worst[k], tol[k]] for k in NUMBERS]
        return {"attempted": len(self.walls), "failed": bad,
                "correct": all(x <= lim for _, x, lim in compared),
                "compared": compared, "distinct_answers": len(graded),
                "eigenvalue_error_at": worst_eigenvalue(
                    self.answers[-1][0], w_ref) if self.answers else None}


def compile_probe():
    """Whether this program's spectral divide and conquer is a set of
    programs the compile cache can hold, asked of the program itself:
    where `eigh_dc` is ONE `jax.jit` (it then has `lower`), every
    bucket of the ladder and both full-size splits are one executable,
    which at n=4096 was 240 MB, over the cache's 192 MiB an entry, and
    took 258-275 s to compile in every process (PR 22); at the cell's
    size it is larger again. Exits 4 at once, before the inputs are
    made, so that a commit that cannot carry the deployment fails
    cleanly instead of compiling for a run's whole time."""
    from slate_tpu.linalg import spectral_dc
    if hasattr(spectral_dc.eigh_dc, "lower"):
        print("kinds/heev.py: this program's spectral_dc.eigh_dc is one "
              "jitted program holding every bucket of its ladder; its "
              "executable cannot be cached and compiles for minutes in "
              "every process: this program cannot run a configuration "
              "of kind `heev`", file=sys.stderr)
        raise SystemExit(4)


def tune_for_rehearsal(cfg):
    """`--rehearse` only (the configuration's `rehearsal.tune`): the
    library's own tune table is told, in memory, the routing threshold
    and leaf size at which the CPU's toy size takes the cell's route
    and splits three levels deep. The call itself stays st.heev with
    no option."""
    from slate_tpu.tune import cache
    cache.get_cache().put("heev", np.dtype(cfg["dtype"]), cfg["n"],
                          dict(cfg["tune"]))


def setup(cfg, mix, seed):
    compile_probe()
    if "tune" in cfg:
        tune_for_rehearsal(cfg)
    return Cell(cfg, mix, seed)
