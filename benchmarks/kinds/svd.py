"""Kind `svd`: one caller decomposes the same dense real square matrix
back to back through the public entry point with NO option set (closed
loop), all singular values and both sets of singular vectors, and
every answer is held to the configuration's guarantee: a
backward-stable singular value decomposition.

It is kind `solve`'s `Cell` (kinds/solve.py: warm-up, window, median
wall under the configuration's `wall_metric`) with a system and a
comparison of its own, as kind `heev` is. A solve is timed from the
host array to `block_until_ready` of U, s and Vh on the device. Four
numbers are compared, on the host in f64 after the window, each over
n eps_f32 (and ||A||_2 where it has a scale), with v_j the rows of Vh:

    residual_max             = max_j ||A v_j - s_j u_j||_2 / ||A||_2
    orthogonality_u          = ||U^T U - I||_F
    orthogonality_v          = ||Vh Vh^T - I||_F
    singular_value_error_max = max_i |s_i - s_ref_i| / ||A||_2

s_ref is the configuration's own singular values, which the generator
returns in f64 (lib/svdgen.py), and ||A||_2 the largest of them, 1:
the f64 singular values of the f32 data lie within the rounding of the
entries of them (Weyl: at most ||A32 - A||_F <= 2^-24 ||A||_F =
1.3e-6 at n=8192, a thousandth of n eps; 2.8e-10 read), and LAPACK's
values-only SVD of the data is 170-190 s of the host at n=8192, in
every run (PR 39; `reference_spectrum` is that SVD, which tier-1
holds the generator to at small sizes). NaN, a wrong shape or dtype, a negative s or s not
descending fails. The solves of a window return the same bytes, and an
answer whose bytes were seen is held once and graded once; the
products are formed a block of columns at a time.
"""

import sys

import numpy as np

from benchmarks.kinds import solve
from benchmarks.lib import gen, svdgen

EPS32 = float(np.finfo(np.float32).eps)
NUMBERS = ("orthogonality_u", "orthogonality_v", "residual_max",
           "singular_value_error_max")


def reference_spectrum(a32):
    """(s_ref descending, ||A||_2) in f64 for f32 data, by LAPACK."""
    s = np.linalg.svd(a32.astype(np.float64), compute_uv=False)
    return s, float(s[0])


def _gram_defect2(q, cols):
    """||Q^T Q - I||_F^2 for f64 Q, a block of columns at a time."""
    total = 0.0
    for j in range(0, q.shape[1], cols):
        g = q.T @ q[:, j:j + cols]
        k = np.arange(g.shape[1])
        g[j + k, k] -= 1.0
        total += float((g * g).sum())
    return total


def grade(a32, u, s, vh, s_ref, norm2, cols=1024):
    """The four compared numbers of one answer (U, s, Vh); every one
    inf for an answer that is not finite, or whose s is negative or
    not descending."""
    n = a32.shape[0]
    if not (np.isfinite(s).all() and np.isfinite(u).all()
            and np.isfinite(vh).all() and (s >= 0).all()
            and (np.diff(s) <= 0).all()):
        return dict.fromkeys(NUMBERS, float("inf"))
    s64 = np.asarray(s, np.float64)
    a64, u64 = a32.astype(np.float64), np.asarray(u, np.float64)
    v64 = np.asarray(vh, np.float64).T
    resid = 0.0
    for j in range(0, n, cols):
        r = a64 @ v64[:, j:j + cols] - u64[:, j:j + cols] * s64[j:j + cols]
        resid = max(resid, float(np.linalg.norm(r, axis=0).max()))
    scale = n * EPS32
    return {"residual_max": resid / (scale * norm2),
            "orthogonality_u": float(np.sqrt(_gram_defect2(u64, cols)))
            / scale,
            "orthogonality_v": float(np.sqrt(_gram_defect2(v64, cols)))
            / scale,
            "singular_value_error_max":
                float(np.abs(s64 - s_ref).max()) / (scale * norm2)}


class _General:
    #: what the caller passes to st.svd: nothing, the route is the
    #: library's
    opts = None

    def __init__(self, cfg, r):
        self.n, self.mb = cfg["n"], cfg["mb"]
        self.a, self.s_ref = svdgen.geo_general(r, self.n,
                                                cfg["matrix"]["cond"])
        self.held = []          # distinct host answers (U, s, Vh)

    def solve(self):
        import jax
        import slate_tpu as st
        res = st.svd(st.Matrix(self.a, mb=self.mb), self.opts)
        jax.block_until_ready((res.s, res.U.data, res.Vh.data))
        return res.s, (res.U, res.Vh)

    def to_host(self, s, factors, rows):
        """The answer on the host; one whose bytes were seen before is
        the held one (a window's solves return the same digits, and a
        copy of U and Vh for each is 4 GB of the host for nothing)."""
        got = (factors[0].to_numpy(), np.asarray(s),
               factors[1].to_numpy())
        for held in self.held:
            if all(np.array_equal(x, y) for x, y in zip(held, got)):
                return held
        self.held.append(got)
        return got


class Cell(solve.Cell):
    def __init__(self, cfg, mix, seed):
        self.cfg, self.mix = cfg, mix
        self.sys = _General(cfg, gen.rng(seed, "solve"))
        self.rows = None
        self.answers = []       # host (U, s, Vh) per solve, warm-up too
        self.walls = []

    def check(self):
        """Every answer made, warm-up included; answers that are one
        held object are graded once."""
        tol = self.cfg["tolerance"]
        n = self.cfg["n"]
        s_ref, norm2 = self.sys.s_ref, float(self.sys.s_ref[0])
        graded = {}                         # id(answer) -> numbers
        worst = dict.fromkeys(NUMBERS, 0.0)
        bad = 0
        n_warm = len(self.answers) - len(self.walls)
        for i, ans in enumerate(self.answers):
            u, s, vh = ans
            if (s.shape != (n,) or u.shape != (n, n) or vh.shape != (n, n)
                    or any(x.dtype != np.float32 for x in ans)):
                nums = dict.fromkeys(NUMBERS, float("inf"))
            else:
                if id(ans) not in graded:
                    graded[id(ans)] = grade(self.sys.a, u, s, vh, s_ref,
                                            norm2)
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
                "compared": compared, "distinct_answers": len(graded)}


def compile_probe(cfg):
    """Whether this program's `st.svd` with no option would take its
    own route at the cell's size (the polar and divide-and-conquer
    programs `incore-heev` already holds, dispatched from a host
    agenda), asked of the program itself: `linalg.svd.agenda_leaf` is
    what the driver routes on. Where the program has no such route, or
    would not take it here, `st.svd` is jax's fused QDWH-SVD, ONE
    program holding a polar iteration and a whole spectral divide and
    conquer: 826 MB of code at n=2048, 440 s to compile (compiled for
    a described v5e, PR 39), far over the compile cache's 192 MiB, so
    every process would compile it again, for longer than a run lasts.
    Exits 4 at once, before the inputs are made, so that a commit that
    cannot carry the deployment fails cleanly."""
    import importlib
    import jax
    # the package re-exports the driver under the module's name
    program = importlib.import_module("slate_tpu.linalg.svd")
    asked = getattr(program, "agenda_leaf", None)
    n = cfg["n"]
    shape = jax.ShapeDtypeStruct((n, n), np.dtype(cfg["dtype"]))
    if asked is None or asked(shape) is None:
        print("kinds/svd.py: this program's st.svd with no option is "
              "jax.lax.linalg.svd at n=%d, one fused program the compile "
              "cache cannot hold and that compiles for longer than a "
              "run: this program cannot run a configuration of kind "
              "`svd`" % n, file=sys.stderr)
        raise SystemExit(4)


def tune_for_rehearsal(cfg):
    """`--rehearse` only (the configuration's `rehearsal.tune`): the
    library's own tune table is told, in memory, the routing threshold
    and leaf size at which the CPU's toy size takes the cell's route.
    It is `heev`'s entry: `st.svd` routes on what `st.heev` routes on.
    The call itself stays st.svd with no option."""
    from slate_tpu.tune import cache
    cache.get_cache().put("heev", np.dtype(cfg["dtype"]), cfg["n"],
                          dict(cfg["tune"]))


def setup(cfg, mix, seed):
    if "tune" in cfg:
        tune_for_rehearsal(cfg)
    compile_probe(cfg)
    return Cell(cfg, mix, seed)
