"""Spectral divide & conquer Hermitian eigensolver, TPU-native.

The production TPU eigensolver path. Replaces `jax.lax.linalg.eigh`'s
QDWH divide & conquer (jax._src.tpu.linalg.eigh — the algorithm of
Nakatsukasa & Higham, "Stable and efficient spectral divide and
conquer algorithms for the symmetric eigenvalue decomposition and the
SVD", SISC 2013) with a re-engineered implementation of the same
published algorithm. Reference parity: src/heev.cc drives the
reference's eigensolver; this module is the TPU replacement for its
whole staged pipeline at the Auto method (eig.py routes it).

Where the time goes in the stock implementation, measured on v5e
(PERF.md "Round-5: in-house spectral divide & conquer"; raw runs in
untracked experiment outputs):
  * lax.linalg.eigh @8192 f32: 4.82 s (152 nominal GFLOP/s).
  * One stock qdwh polar @4096: 123.5 ms = 55 n^3-flop-equivalents at
    the same-process gemm rate — the first 2 iterations go through the
    QR-based form (geqrf of a stacked (2n, n) matrix) because the
    lower bound l0 on sigma_min starts at eps.
  * Every subproblem update copies PADDED full-workspace arrays (the
    stock _update_slice lax.pad's the (N, N) workspace by the (B, B)
    update before writing — ~2.5 GB of copy traffic per update at
    n=8192).

This implementation keeps the algorithm but re-engineers the
execution (design, not translation — written fresh):
  1. All-Cholesky polar (linalg/polar.py): capped Halley weights keep
     cond(c U^H U + I) inside f32 Cholesky range, so the
     (2n, n)-QR phase vanishes via CAPPED weights (polar.py module
     doc). No H factor, one Newton-Schulz.
  2. The ROOT split runs outside the agenda loop at the concrete
     size: its eigenvector compose against the identity basis (2 n^3
     wasted in the stock loop) disappears, and its workspace writes
     are plain in-bounds updates.
  3. The agenda workspace carries a bucket-sized MARGIN so every
     subproblem read/write is an in-bounds dynamic_slice /
     dynamic_update_slice on the touched window only — no lax.pad
     round trips.
  4. Subproblem compression forms W = Q^H (H Q) once per split (4 B^3)
     and slices both diagonal blocks out of it, instead of two
     separate V_i^H H V_i sandwiches (8 B^3).

Shapes shrink down the recursion through the same bucket ladder idea
as the stock implementation (multiplier ~1.98, granularity 128), with
subproblem true sizes handled by masking.

Where a block is split is free in the algorithm (backward stable at
any shift) and decides the tree. The median of a block's diagonal
concentrates at the MEAN of its eigenvalues, not at their median, so
on a decaying spectrum every split is lopsided, and a child of more
than n/1.98 rows runs in the root's bucket again at (n/rows)^3 of its
cost: at n=8192 the two benchmark cells ran THREE splits at the full
size (8192, 5824 and 4709 rows; 8192, 6217 and 4487), 3.7 of 4.4 busy
seconds (PERF.md, PR 33, PR 39). A balanced ROOT would need the split
point to 1.6% of the rows (4224 of 8192), which nothing cheap
resolves inside a cluster of eigenvalues. But the larger child's OWN
split only has to leave both of its children under the top rung: any
point between the 27th and the 72nd percentile of a 5824-row block.
So from SHIFT_MIN_LEAVES leaves to a bucket up, `dc_sign` first
estimates the block's spectral distribution (`_spectral_measure`: a
short Lanczos recurrence from a few probe vectors, B^2 work beside
the split's 50 B^3) and `_estimated_shift` chooses sigma from it:
the diagonal's median where the estimate says both children clear the
rung, else the estimated median where that clears it with room, else
a point deliberately off the rung's edge, so that which bucket a child
lands in does not turn on the estimate's last percent. The larger
child of a lopsided split is then halved once: two full-size splits a
solve for three (PR 43). Smaller buckets keep the diagonal's median.

A split is four steps: `dc_take` (the subproblem out of the
workspace, and whether it is already diagonal), `dc_sign` (the shift
and the polar iteration: half of a split's code), `dc_basis` (the
subspace QRs and the compression: the other half) and `dc_put` (the
eigenvector compose and the children back into the workspace); a
subproblem at or under the leaf size is one `dc_leaf`; `dc_vectors`
sorts.

Each step is a compiled program of its own at each bucket size,
dispatched from a host AGENDA that reads four numbers a split (k,
the polar's converged flag, its iteration count and where the shift
came from) and threads the
donated workspaces from program to program. Splits are dispatched as
soon as their sizes are known and their sizes read oldest first, so
the device waits for the host only where the tree is one node wide.
The root and a lopsided split's full-size fallback share their two
heavy programs (`dc_sign`, `dc_basis` see a (B, B) block and its true
size, not where it came from). No program holds more than half a
bucket: as ONE program (a `while_loop` over a `lax.switch` with a
branch a bucket) the solve at n=4096 was a 240 MB executable that
took 258-275 s to compile and that the compile cache refused, so
every process compiled it again (PR 22; PR 33 made the agenda). The
sizes are read on the host, so the input is a concrete array: under a
caller's `jit` `eigh_dc` raises, and `st.heev` takes XLA's own `eigh`
(linalg/eig.py).
"""

from __future__ import annotations

import functools
import sys
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import events as obs_events
from ..obs import metrics as obs_metrics
from .polar import sign_hermitian

HI = jax.lax.Precision.HIGHEST

#: above this n, on the chip, `st.heev` and `st.svd` with no method set
#: take this module's agenda instead of XLA's one-program eigh / svd
#: (measured crossover, PERF.md "Round-5: in-house spectral divide &
#: conquer"); `route` reads it
SPECTRAL_DC_MIN_N = 2048

#: subproblems at or below this size stop recursing and solve with the
#: TPU Jacobi eigh custom call (scales poorly upward, fine here)
LEAF = 256

#: subspace-iteration refinements of the projector basis per split
SUBSPACE_MAXITER = 2


def _round_up(x, g):
    return ((x + g - 1) // g) * g


def _bucket_ladder(n: int, leaf: int):
    """Static padded sizes for subproblems: n/1.98 rounded up to 128,
    then halving, ending at the leaf size. The 1.98 (not 2) absorbs
    off-median splits without falling back into the parent bucket.

    Rungs a factor of two apart are what a compile cache of 192 MiB
    holds at n=8192: a split's two heavy programs cost 11 KB of cache
    a row of their bucket, whatever the bucket, so the ladder under
    the root may have about as many rows as the root. A lopsided
    split's larger child (over n/1.98 rows) therefore runs at the full
    size again (`_bucket_of`). Rungs 1.5 times 4224 and 2176 (6272,
    3200) held such children and cut the solve of PR 33's cell from
    4.8 to 2.95 s, but made the programs 288 MB, and every run
    compiled all of them again, 570 s (PERF.md, PR 33). What the
    ladder cannot hold the shift avoids instead: a full-size child's
    own split is aimed inside the window that leaves both of its
    children under the top rung (`_estimated_shift`, PR 43), wide
    because a child of r rows only needs a split between rows
    r - 4224 and 4224."""
    buckets = [leaf]
    if n > leaf:
        i = int(n / 1.98)
        while i > leaf:
            buckets.append(_round_up(i, 128))
            i //= 2
    return sorted(set(buckets))


def _mask2(x, m, fill=0.0):
    """Zero (or fill) outside the leading (m, m) block."""
    B = x.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    return jnp.where((i < m) & (j < m), x, jnp.asarray(fill, x.dtype))


def _mask_cols(x, c0, c1, fill=0.0):
    """Keep columns [c0, c1), fill elsewhere."""
    j = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where((j >= c0) & (j < c1), x, jnp.asarray(fill, x.dtype))


#: the estimate of a block's spectral distribution (`_spectral_measure`):
#: steps of the Lanczos recurrence, and probe vectors run side by side
#: (eight fill the sublanes of one f32 tile: they cost what four do)
SHIFT_STEPS = 32
SHIFT_PROBES = 8

#: a split estimates from this many leaves to a bucket up: the estimate
#: is B^2 work in some tens of short sequential steps and a split is
#: B^3, so at 384 and 640 rows under leaves of 256 (27 splits of the
#: cells' 46, 51 ms in all) it would cost what the split does
SHIFT_MIN_LEAVES = 8

#: how far from a rung, as a share of the block's rows, the rule aims a
#: child it cannot place on the rung's near side: the estimate's bias on
#: a smooth spectrum (2.4% read) and its noise from one matrix to the
#: next (under 1% at eight probes)
SHIFT_ROOM = 0.03

#: how many rows under the rung the estimate has to put both children
#: of the diagonal's median for that median to be kept: the estimate
#: repeats to 10 rows at 2048 and 25 at 8192 from one matrix of a
#: spectrum to the next, and a balanced root has 128 to spare
SHIFT_KEEP_ROWS = 32


def _spectral_measure(apply, m, B: int, dt, key=None):
    """Nodes and weights, each (SHIFT_PROBES, SHIFT_STEPS), of Gauss
    quadratures of the spectral measure of the Hermitian operator
    `apply` (rows in, rows out: X -> X H^T) on the leading m of B
    coordinates: SHIFT_STEPS steps of the Lanczos recurrence from each
    of SHIFT_PROBES Gaussian vectors, every new vector orthogonalized
    twice against all kept ones, then the eigenvalues of each
    tridiagonal matrix (nodes, ascending) and the squared first
    components of its eigenvectors (weights, summing to 1 a probe).
    The key is fixed: the same matrix gives the same nodes. A probe
    that exhausts an invariant subspace goes on in its complement with
    a coupling at rounding level, so its later nodes weigh nothing."""
    rdt = jnp.zeros((), dt).real.dtype
    P, S = SHIFT_PROBES, SHIFT_STEPS
    tiny = jnp.finfo(rdt).tiny
    live = jnp.arange(B) < m
    key = jax.random.PRNGKey(43) if key is None else key
    q = jnp.where(live, jax.random.normal(key, (P, B), rdt), 0).astype(dt)
    q = q / jnp.sqrt(jnp.sum(jnp.abs(q) ** 2, axis=1))[:, None]

    def step(j, carry):
        Q, q, alpha, beta = carry
        # a scatter, NOT `lax.dynamic_update_slice(Q, q[None], (j, 0,
        # 0))`: on the chip that form left `Q` something else than
        # what was written (beta 1.6e13 at the FIRST step, compiled
        # alone or under a `cond`; PERF.md, PR 43, call C), where this
        # one, a mask over the steps and the unrolled loop all read
        # the spectrum
        Q = Q.at[j].set(q)
        w = apply(q)
        a = jnp.real(jnp.sum(q.conj() * w, axis=1))
        for _ in range(2):
            c = jnp.einsum("spb,pb->sp", Q.conj(), w, precision=HI)
            w = w - jnp.einsum("sp,spb->pb", c, Q, precision=HI)
        b = jnp.sqrt(jnp.sum(jnp.abs(w) ** 2, axis=1))
        q = w / jnp.maximum(b, tiny)[:, None].astype(dt)
        return Q, q, alpha.at[j].set(a), beta.at[j].set(b)

    _, _, alpha, beta = jax.lax.fori_loop(
        0, S, step, (jnp.zeros((S, P, B), dt), q,
                     jnp.zeros((S, P), rdt), jnp.zeros((S, P), rdt)))
    nodes, vecs = jnp.linalg.eigh(jax.vmap(
        lambda a, o: jnp.diag(a) + jnp.diag(o, 1) + jnp.diag(o, -1))(
            alpha.T, beta[:-1].T))
    return nodes, vecs[:, 0, :] ** 2


def _share_under(nodes, weights, x):
    """The estimated share of the eigenvalues under x (any shape): a
    Gauss quadrature's sums bracket the measure at its nodes (all the
    weight under a node at least, that node's weight more at most), so
    each probe's estimate runs through the brackets' middles, straight
    between nodes, and the probes are averaged."""
    mid = jnp.cumsum(weights, axis=1) - 0.5 * weights
    return jnp.mean(jax.vmap(
        lambda t, c: jnp.interp(x, t, c, left=0.0, right=1.0))(nodes, mid),
        axis=0)


def _shift_for(nodes, weights, share):
    """The shift the estimate puts `share` of the eigenvalues under."""
    xs = jnp.sort(nodes.ravel())
    return jnp.interp(share, _share_under(nodes, weights, xs), xs)


def _estimated_shift(apply, m, B: int, dt, rung, sigma_d, bound, key=None):
    """The split point of a block of m rows in the bucket B whose next
    rung down is `rung`, from an estimate of where its eigenvalues lie
    (module doc). `bound` is any upper bound of the block's spectral
    radius: a Ritz value 1% beyond it, or one that is not a number, says
    the recurrence failed, and the diagonal's median is kept (a first
    form of the recurrence read NaN and Ritz values of 1e13 on the
    chip where the sandbox's CPU read the spectrum: PERF.md, PR 43).
    Returns (sigma, moved):

    (a) sigma_d, the median of the diagonal, where the estimate puts
        both of ITS children SHIFT_KEEP_ROWS under the rung or lower: a
        spectrum the mean balances is not moved (the estimate is the
        coarser of the two there);
    (b) otherwise the estimated median, where the larger child then
        clears the rung by SHIFT_ROOM of the block; where it does not
        (the root: 4224 of 8192 rows is 1.6% over a half), the shift
        that puts the larger child SHIFT_ROOM of the block over the
        rung, on sigma_d's side: that child is in this bucket again
        whatever the estimate's error, its own split has the wide
        window of (a) or (b), and the tree is the same from one matrix
        of a spectrum to the next."""
    nodes, weights = _spectral_measure(apply, m, B, dt, key)
    rows = jnp.asarray(m, nodes.dtype)
    rung = jnp.asarray(rung, nodes.dtype)
    under_d = _share_under(nodes, weights, sigma_d)
    sane = jnp.all(jnp.abs(nodes) <= 1.01 * bound)
    keep = ~sane | (jnp.maximum(under_d, 1.0 - under_d) * rows
                    <= rung - SHIFT_KEEP_ROWS)
    larger = jnp.where((0.5 + SHIFT_ROOM) * rows <= rung, 0.5,
                       rung / rows + SHIFT_ROOM)
    share = jnp.where(under_d < 0.5, 1.0 - larger, larger)
    sigma_e = _shift_for(nodes, weights, share).astype(sigma_d.dtype)
    return jnp.where(keep, sigma_d, sigma_e), ~keep


def _sign_split(H, m, general, rung, l0):
    """sign(H - sigma I) of the masked (m, m) Hermitian block H,
    padded to static (B, B). sigma is the median of the diagonal in a
    bucket that does not estimate (`rung` None: under
    SHIFT_MIN_LEAVES leaves; the program is then the code of before
    PR 43, with no estimate in it), and `_estimated_shift`'s choice
    between that median and a point of the block's estimated spectral
    distribution where `rung` (traced) is the next bucket down. Where
    the traced flag `general` is set the result is the orthogonal
    polar factor of H as it stands (no shift, no estimate: the
    estimate sits under the flag's branch; and the result is not
    symmetrized): the SVD's polar step is the eigensolver's sign step
    at sigma = 0 on a matrix that need not be Hermitian, and ONE
    program a bucket serves both (`dc_sign`). Returns (S, polar
    iterations, converged, shift) with shift 0 where nothing was
    estimated, 1 where the estimate kept the diagonal's median and 2
    where it moved sigma."""
    B = H.shape[0]
    dt = H.dtype
    diag = jnp.real(jnp.diagonal(H))
    ids = jnp.arange(B)
    sigma = jnp.nanmedian(jnp.where(ids < m, diag, jnp.nan))
    shift = jnp.zeros((), jnp.int32)
    if rung is not None:
        def estimated(sigma_d):
            sigma, moved = _estimated_shift(
                lambda X: jax.lax.dot_general(
                    X, H, (((1,), (1,)), ((), ())), precision=HI),
                m, B, dt, rung, sigma_d,
                jnp.max(jnp.sum(jnp.abs(H), axis=0)))
            return sigma, 1 + moved.astype(jnp.int32)

        sigma, shift = jax.lax.cond(
            general, lambda sigma_d: (sigma_d, shift), estimated, sigma)
    sigma = jnp.where(general, jnp.zeros((), sigma.dtype), sigma)
    Hs = H - sigma.astype(dt) * _eye_m(B, m, dt)
    return sign_hermitian(Hs, l0=l0, general=general) + (shift,)


def _eye_m(B, m, dt):
    ids = jnp.arange(B)
    return jnp.where((ids < m)[:, None] & (ids < m)[None, :],
                     jnp.eye(B, dtype=dt), jnp.zeros((), dt))


def _split_basis(H, S, m):
    """The split of the masked (m, m) block H by its sign matrix S:
    projector subspaces via column-norm-sorted complete QR with
    subspace-iteration refinement (the rank-revealing scheme of SISC
    2013 §3; same scheme as the stock implementation, re-written).
    Returns (Q, W, k): Q (B, B) orthogonal, cols [0, k) spanning the
    lower invariant subspace and [k, m) the upper; W = Q^H H Q, block
    diagonal up to the split tolerance; k the rank of the lower
    block."""
    B = H.shape[0]
    dt = H.dtype
    rdt = jnp.float32 if dt != jnp.float64 else jnp.float64
    eps = jnp.finfo(rdt).eps
    ids = jnp.arange(B)
    eye_m = _eye_m(B, m, dt)

    hnorm = jnp.sqrt(jnp.sum(jnp.abs(H) ** 2))
    P_lo = 0.5 * (eye_m - S)
    k = jnp.round(jnp.trace(jnp.real(P_lo))).astype(jnp.int32)
    k = jnp.clip(k, 1, jnp.maximum(m - 1, 1))

    # use the smaller-rank projector for the basis extraction; swap
    # the two output ranges afterwards if it was the upper one
    swap = (m - k) < k
    P = jnp.where(swap, 0.5 * (eye_m + S), P_lo)
    r = jnp.where(swap, m - k, k)

    # rank-revealing initial basis: columns of P by descending norm
    cn = jnp.sum(jnp.abs(P) ** 2, axis=0)
    cn = jnp.where(ids < m, cn, -jnp.inf)
    order = jnp.argsort(-cn)
    X = P[:, order]

    thresh = 10.0 * eps * hnorm

    def qr_pass(X):
        Q, _ = jnp.linalg.qr(_mask2(X, m), mode="complete")
        # columns beyond the true size m span the padding; force them
        # to the padded identity so downstream masking stays exact
        Q = jnp.where((ids < m)[None, :] & (ids < m)[:, None], Q,
                      jnp.eye(B, dtype=dt))
        V1 = _mask_cols(Q, 0, r)
        err_blk = jnp.matmul(
            jnp.matmul(_mask_cols(Q, r, m).conj().T, H, precision=HI),
            V1, precision=HI)
        return Q, jnp.sqrt(jnp.sum(jnp.abs(err_blk) ** 2))

    def refine_cond(state):
        _, err, it = state
        return (it == 0) | ((err > thresh) & (it < SUBSPACE_MAXITER))

    def refreshed(Q):
        Y = jnp.matmul(P, _mask_cols(Q, 0, r), precision=HI)
        # re-complete the basis from the refreshed leading block
        return Y + _mask_cols(Q, r, B)

    # ONE QR in the program: the first pass is the loop's iteration 0
    # (on P's sorted columns) and not a second copy of the QR in
    # front of the loop; only the operand is chosen under the branch
    def refine_body(state):
        Q, _, it = state
        Q, err = qr_pass(jax.lax.cond(it == 0, lambda q: X, refreshed, Q))
        return Q, err, it + 1

    Q, err, _ = jax.lax.while_loop(
        refine_cond, refine_body,
        (X, jnp.asarray(jnp.inf, rdt), jnp.zeros((), jnp.int32)))

    # un-swap: we want cols [0, k) = lower subspace. Column rolls use
    # a doubled-array dynamic_slice (traced shift amounts).
    def _roll_cols_left(x, s):
        d = jnp.concatenate([x, x], axis=1)
        s = jnp.asarray(s, jnp.int32)
        return jax.lax.dynamic_slice(
            d, (jnp.zeros((), jnp.int32), s), (B, B))

    def do_swap(Q):
        lower = _mask_cols(Q, r, m)          # spans the lower subspace
        upper = _mask_cols(Q, 0, r)
        shift_l = _roll_cols_left(lower, r)            # -> [0, m-r)
        shift_u = _roll_cols_left(upper, (2 * B - (m - r)) % B)
        return _mask_cols(shift_l, 0, m - r) + \
            _mask_cols(shift_u, m - r, m) + _mask_cols(Q, m, B)

    Q = jax.lax.cond(swap, do_swap, lambda q: q, Q)

    HQ = jnp.matmul(H, Q, precision=HI)
    W = jnp.matmul(Q.conj().T, HQ, precision=HI)
    return Q, W, k


def _masked_merge_block(work, blk, off_r, off_c, rows, cols):
    """Read-modify-write: write blk's leading (rows, cols) into `work`
    at (off_r, off_c), leaving the rest of the window untouched. All
    in-bounds by workspace-margin construction — no lax.pad round
    trips (module doc, point 3)."""
    B0, B1 = blk.shape
    off_r = jnp.asarray(off_r, jnp.int32)
    off_c = jnp.asarray(off_c, jnp.int32)
    t = jax.lax.dynamic_slice(work, (off_r, off_c), (B0, B1))
    i = jax.lax.broadcasted_iota(jnp.int32, (B0, B1), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (B0, B1), 1)
    t = jnp.where((i < rows) & (j < cols), blk, t)
    return jax.lax.dynamic_update_slice(work, t, (off_r, off_c))


def _info(k, ok, iters, shift):
    """What the host agenda reads of one split, as one transfer:
    [k, converged, polar iterations, shift]; k = 0 says the block was
    (near-)diagonal and has no children; shift is `_sign_split`'s: 0
    where nothing was estimated, 1 where the estimate kept the
    diagonal's median, 2 where it moved sigma."""
    return jnp.stack([jnp.asarray(x, jnp.int32)
                      for x in (k, ok, iters, shift)])


def _window(work, off, axis, B):
    """The (B, B) block window of `blocks` at row `off` (axis 0) or
    the (n, B) column window of `vecs` at column `off` (axis 1)."""
    zero = jnp.zeros((), jnp.int32)
    off = jnp.asarray(off, jnp.int32)
    if axis == 0:
        return jax.lax.dynamic_slice(work, (off, zero), (B, B))
    return jax.lax.dynamic_slice(work, (zero, off), (work.shape[0], B))


def _masked_block(blocks, off, sz, B):
    """The (sz, sz) Hermitian subproblem at row `off` of `blocks`,
    zero-padded to (B, B) and symmetrized."""
    H = _window(blocks, off, 0, B)
    ids = jnp.arange(B)
    inside = (ids < sz)[:, None] & (ids < sz)[None, :]
    H = jnp.where(inside, H, jnp.zeros((), H.dtype))
    return 0.5 * (H + H.conj().T), inside


def _nearly_diagonal(H, h0norm):
    """A (near-)diagonal or noise-level block: its diagonal entries
    are the eigenvalues and the accumulated vectors already stand."""
    eps = float(jnp.finfo(H.dtype).eps)
    hn = jnp.sqrt(jnp.sum(jnp.abs(H) ** 2))
    d = jnp.real(jnp.diagonal(H)).astype(H.dtype)
    offd = jnp.sqrt(jnp.sum(jnp.abs(H - jnp.diagflat(d)) ** 2))
    return (offd <= 5.0 * eps * hn) | (hn < eps * h0norm)


# ---- the steps: each a compiled program of the agenda form ---------

def dc_take_root(h):
    """The input as the root subproblem: symmetrized, with its
    Frobenius norm (the noise cutoff of every later block) and
    whether it is diagonal already. Returns (H, h0norm, nearly)."""
    h = 0.5 * (h + h.conj().T)
    h0norm = jnp.sqrt(jnp.sum(jnp.abs(h) ** 2))
    return h, h0norm, _nearly_diagonal(h, h0norm)


def dc_take(blocks, at, h0norm, B: int):
    """The subproblem `at` = [offset, size] out of the workspace,
    padded to the bucket's static (B, B). Returns (H, nearly)."""
    H, _ = _masked_block(blocks, at[0], at[1], B)
    return H, _nearly_diagonal(H, h0norm)


def dc_sign(H, m, nearly, general, rung, l0=None):
    """The shift and the polar iteration of one split (skipped on a
    block that is diagonal already). Returns (S, flags) with flags =
    [nearly, converged, iterations, shift]. `rung` (traced) is the
    next bucket under this one where the split estimates its block's
    spectral distribution before it shifts, and None where it takes
    the median of the diagonal (`_sign_split`). `general` (traced) asks
    for the polar factor of a general H instead of the sign of the
    shifted Hermitian one: `st.svd` dispatches the executable the
    eigensolver compiled, at the root's bucket (`polar_general`)."""
    def skip(H):
        zero = jnp.zeros((), jnp.int32)
        return H, zero, jnp.ones((), jnp.bool_), zero

    S, iters, conv, shift = jax.lax.cond(
        nearly, skip, lambda H: _sign_split(H, m, general, rung, l0), H)
    return S, _info(nearly, conv, iters, shift)


def dc_basis(H, S, m, flags):
    """The subspace bases and the compression of one split. Returns
    (Q, W, info). On a block that is diagonal already info's k is 0,
    Q = I, and W holds the diagonal (the eigenvalues) in its column 0:
    `_put` then writes the block's eigenvalue column and leaves its
    vectors as they stand, by the writes it makes for any split."""
    def skip(H, S):
        d = jnp.real(jnp.diagonal(H)).astype(H.dtype)
        return (jnp.eye(H.shape[0], dtype=H.dtype),
                jnp.zeros_like(H).at[:, 0].set(d),
                jnp.zeros((), jnp.int32))

    Q, W, k = jax.lax.cond(flags[0] > 0, skip,
                           lambda H, S: _split_basis(H, S, m), H, S)
    return Q, W, _info(k, flags[1], flags[2], flags[3])


def _put(blocks, vecs, off, sz, Q, W, k, compose: bool):
    """Write a split's compressed children + composed eigenvector
    columns into the workspaces. `compose` is False only for the root
    call, whose V0 is the identity (stock implementations pay 2 n^3
    composing against it). No branch here: a conditional over the
    workspaces makes the compiler copy one (0.54 GB a launch at
    n=8192); `dc_basis` shapes a finished block's Q and W so that
    these same writes finish it."""
    n = vecs.shape[0]
    B = Q.shape[0]
    if compose:
        Vnew = jnp.matmul(_window(vecs, off, 1, B), Q, precision=HI)
    else:
        Vnew = Q
    # Q is padded-identity beyond (m, m), so columns of Vnew past sz
    # reproduce V0 exactly; the merge mask still bounds the write
    vecs = _masked_merge_block(vecs, Vnew, 0, off, n, sz)
    # children, left-aligned: W[:k, :k] at (off, 0); W[k:sz, k:sz]
    # at (off + k, 0). The second extraction slides a (B, B) window
    # to (k, k), so pad W locally (a B^2 pad, not the stock
    # implementation's full-workspace pad).
    Wp = jnp.pad(W, ((0, B), (0, B)))
    W22 = jax.lax.dynamic_slice(Wp, (k, k), (B, B))
    blocks = _masked_merge_block(blocks, W, off, 0, k, k)
    blocks = _masked_merge_block(blocks, W22, off + k, 0,
                                 sz - k, sz - k)
    return blocks, vecs


def dc_put_root(Q, W, info):
    """Makes the two workspaces and writes the root split into them:
    `blocks` (2n, n), the subproblems left-aligned, whose column 0
    doubles as the eigenvalue store, and `vecs` (n, 2n), the
    accumulated eigenvectors; each carries a bucket-sized margin so
    every later window is an in-bounds dynamic_slice /
    dynamic_update_slice on the touched window only."""
    n = Q.shape[0]
    zero = jnp.zeros((), jnp.int32)
    return _put(jnp.zeros((2 * n, n), Q.dtype),
                jnp.zeros((n, 2 * n), Q.dtype), zero,
                jnp.asarray(n, jnp.int32), Q, W, info[0], compose=False)


def dc_put(blocks, vecs, at, Q, W, info):
    """Writes the split of the subproblem `at` into the workspaces."""
    return _put(blocks, vecs, at[0], at[1], Q, W, info[0], compose=True)


def dc_leaf(blocks, vecs, at, B: int):
    """Solve the subproblem `at` = [offset, size <= B] outright and
    compose its vectors. Returns (blocks, vecs)."""
    n = vecs.shape[0]
    dt = blocks.dtype
    off, sz = at[0], at[1]
    H, inside = _masked_block(blocks, off, sz, B)
    # pad with a sentinel diagonal ABOVE the leaf's spectral
    # radius (<= its Frobenius norm): any sorted eigh then leaves
    # the real eigenpairs in the leading sz positions and the
    # padding eigenpairs (exact e_i vectors — the matrix is block
    # diagonal) at the tail, so no backend-specific no-sort
    # behavior is relied on (works on CPU LAPACK and TPU Jacobi)
    sent = 2.0 * jnp.sqrt(jnp.sum(jnp.abs(H) ** 2)) + 1.0
    H = H + jnp.where(inside, jnp.zeros((), dt),
                      sent.astype(dt) * jnp.eye(B, dtype=dt))
    V, w = jax.lax.linalg.eigh(H, symmetrize_input=False)
    Vnew = jnp.matmul(_window(vecs, off, 1, B), V, precision=HI)
    vecs = _masked_merge_block(vecs, Vnew, 0, off, n, sz)
    blocks = _masked_merge_block(blocks, w[:, None].astype(dt),
                                 off, 0, sz, 1)
    return blocks, vecs


def dc_vectors(blocks, vecs):
    """(w ascending, V with V[:, i] the eigenvector of w[i]) out of
    the finished workspaces."""
    n = vecs.shape[0]
    w = jnp.real(blocks[:n, 0])
    order = jnp.argsort(w)
    return w[order], vecs[:, :n][:, order]


def dc_small(h):
    """A matrix no larger than the leaf: one sorted eigh."""
    v, w = jax.lax.linalg.eigh(h, symmetrize_input=True)
    order = jnp.argsort(w)
    return w[order], v[:, order]


#: the steps by name; `_programs` compiles each at a bucket size
_STEPS = {"take_root": dc_take_root, "put_root": dc_put_root,
          "take": dc_take, "sign": dc_sign, "basis": dc_basis,
          "put": dc_put, "leaf": dc_leaf}
_JIT = {"take": dict(static_argnames=("B",)),
        "sign": dict(static_argnames=("l0",)),
        "put": dict(donate_argnums=(0, 1)),
        "leaf": dict(static_argnames=("B",), donate_argnums=(0, 1))}


#: `dc_sign`'s traced `general` flag, as its two callers pass it
_HERMITIAN, _GENERAL = np.False_, np.True_


def _split_steps(step, blocks, vecs, at, h0norm, B: int, rung, l0):
    """The four steps of one split dispatched in a row (`step` =
    `_programs(B)`). Returns (blocks, vecs, info)."""
    H, nearly = step["take"](blocks, at, h0norm, B=B)
    S, flags = step["sign"](H, at[1], nearly, _HERMITIAN, rung, l0=l0)
    Q, W, info = step["basis"](H, S, at[1], flags)
    return step["put"](blocks, vecs, at, Q, W, info) + (info,)


def _root_steps(step, h, rung, l0):
    """The root split at the concrete size: no masking overhead, no
    identity compose. Returns (blocks, vecs, h0norm, info)."""
    H, h0norm, nearly = step["take_root"](h)
    m = np.int32(h.shape[0])
    S, flags = step["sign"](H, m, nearly, _HERMITIAN, rung, l0=l0)
    Q, W, info = step["basis"](H, S, m, flags)
    return step["put_root"](Q, W, info) + (h0norm, info)


def _bucket_of(ladder, n: int, sz: int) -> int:
    """The smallest bucket that holds `sz`; the full size n for a
    lopsided split's larger child that outgrew the ladder."""
    return next((b for b in ladder if b >= sz), n)


def _rung_under(ladder, B: int, leaf: int):
    """What a split in the bucket B passes `dc_sign` as its `rung`:
    the next bucket down, whose edge its children should clear, where
    the split estimates its block's spectral distribution (a bucket
    of SHIFT_MIN_LEAVES leaves or more); None where it does not (the
    program of such a bucket holds no estimate)."""
    if B < SHIFT_MIN_LEAVES * leaf:
        return None
    return np.int32(max(b for b in ladder if b < B))


# ---- the agenda -----------------------------------------------------

@functools.lru_cache(maxsize=None)
def _programs(B: int):
    """The compiled steps at bucket size B, each named for its bucket
    (`jit_dc_sign_4224`): a device trace then says which size ran."""
    def program(name, step):
        named = functools.partial(step)
        functools.update_wrapper(named, step)
        named.__name__ = "%s_%d" % (step.__name__, B)
        return jax.jit(named, **_JIT.get(name, {}))

    return {name: program(name, step) for name, step in _STEPS.items()}


_vectors_program = jax.jit(dc_vectors)
_small_program = jax.jit(dc_small)


def _eigh_dc_agenda(h, leaf: int, l0):
    """Host agenda over the compiled steps (module doc). The queue
    holds the splits dispatched and not yet read, oldest first; a
    read (`heev::agenda`) waits for that split alone while the device
    runs on through what was dispatched after it."""
    n = h.shape[0]
    ladder = _bucket_ladder(n, leaf)
    span, inc = obs_events.span, obs_metrics.inc
    leaves = _programs(leaf)
    with span("heev::split", cat="phase", bucket=n, size=n):
        blocks, vecs, h0norm, info = _root_steps(
            _programs(n), h, _rung_under(ladder, n, leaf), l0)
    pending = deque([(0, n, n, info)])
    ok = True
    while pending:
        off, sz, B, info = pending.popleft()
        with span("heev::agenda", cat="phase"):
            k, conv, iters, shift = (int(x) for x in np.asarray(info))
        ok = ok and bool(conv)
        inc("heev.splits")
        inc("heev.split_rows_true", sz)
        inc("heev.split_rows_padded", B)
        inc("heev.polar_iters", iters)
        if B == n:
            inc("heev.full_size_splits")
        if shift:
            inc("heev.shift_estimated")
        if shift > 1:
            inc("heev.shift_moved")
        if not conv:
            inc("heev.unconverged")
        if k == 0:                      # finished as a diagonal block
            continue
        for c_off, c_sz in ((off, k), (off + k, sz - k)):
            at = np.array([c_off, c_sz], np.int32)
            if c_sz <= leaf:
                with span("heev::leaf", cat="phase", size=c_sz):
                    blocks, vecs = leaves["leaf"](blocks, vecs, at, B=leaf)
                inc("heev.leaves")
                continue
            Bc = _bucket_of(ladder, n, c_sz)
            with span("heev::split", cat="phase", bucket=Bc, size=c_sz):
                blocks, vecs, info = _split_steps(
                    _programs(Bc), blocks, vecs, at, h0norm, Bc,
                    _rung_under(ladder, Bc, leaf), l0)
            pending.append((c_off, c_sz, Bc, info))
    with span("heev::vectors", cat="phase"):
        w, v = _vectors_program(blocks, vecs)
    return w, v, ok


def polar_general(a: jax.Array, leaf: int = LEAF, l0=None):
    """The orthogonal polar factor of the square matrix `a` by the
    eigensolver's own `dc_sign` program at the bucket `a.shape[0]`
    (the executable the root split of `eigh_dc(., leaf)` runs: a
    second polar program at n=8192 would be another 41 MB of compile
    cache). Returns (U_p, flags) on the device, flags = [0, converged,
    iterations, 0]; nothing is read here."""
    n = a.shape[0]
    # `nearly` and `rung` as the root split passes them (an array on
    # the device; a rung or none by the root's bucket): the call then
    # finds the executable `eigh_dc` loaded, in this process too. The
    # flag's branch estimates nothing, whatever the rung
    rung = _rung_under(_bucket_ladder(n, leaf), n, leaf)
    return _programs(n)["sign"](a, np.int32(n), jax.device_put(np.False_),
                                _GENERAL, rung, l0=l0)


def route(a, opts=None):
    """The leaf size at which `a` (dense, square) takes this module's
    agenda through `st.heev` or `st.svd` with no method set, or None
    where it keeps XLA's own program: a tracer (the agenda reads sizes
    on the host), a complex matrix (the TPU's Jacobi leaf solver is
    real), or one of no more rows than the routing threshold. The
    threshold and the leaf size are tunable (tune/select.py); their
    frozen defaults are the module constants, so an empty cache
    reproduces today's routing exactly. Off the chip the default is
    never (LAPACK's and XLA's own are the measured routes there): only
    a tune entry, whose key names the backend it was written on, sends
    another backend down this route (a rehearsal's and tier-1's do, in
    memory). Both drivers read the one entry, `heev`'s."""
    from ..ops.pallas_kernels import _on_tpu
    from ..tune.select import tuned_int
    if isinstance(a, jax.core.Tracer) \
            or jnp.issubdtype(a.dtype, jnp.complexfloating):
        return None
    n = a.shape[0]
    min_n = tuned_int("heev", "spectral_dc_min_n",
                      SPECTRAL_DC_MIN_N if _on_tpu() else sys.maxsize,
                      opts=opts, n=n, dtype=a.dtype)
    if n <= min_n:
        return None
    return tuned_int("heev", "dc_leaf", LEAF, opts=opts, n=n, dtype=a.dtype)


def route_note(n: int, leaf: int):
    """What a driver span records of the agenda route at size n."""
    return dict(form="agenda" if n > leaf else "leaf", leaf=leaf,
                buckets=",".join(str(b) for b in _bucket_ladder(n, leaf)))


def eigh_dc(h: jax.Array, leaf: int = LEAF, l0=None):
    """Full Hermitian eigendecomposition by spectral divide & conquer
    (module doc). Returns (w ascending, V with V[:, i] the
    eigenvector of w[i], ok) where `ok`, a Python bool, is the AND of
    every split's polar converged flag — False means at least one
    sign iteration hit its cap without meeting tolerance and the
    results may be degraded (the driver surfaces this; ADVICE r5).
    `h` is a concrete array: the agenda reads each split's sizes on
    the host, which a tracer does not allow."""
    if isinstance(h, jax.core.Tracer):
        raise TypeError(
            "eigh_dc: a host agenda dispatches the splits and reads "
            "their sizes, so it cannot run under a caller's jit; "
            "call it on a concrete array (st.heev under jit takes "
            "XLA's eigh)")
    if h.shape[0] <= leaf:
        return _small_program(h) + (True,)
    return _eigh_dc_agenda(jnp.asarray(h), leaf, l0)
