"""Blocked factorization/solve core for TPU (used by trsm, potrf, getrf).

Backend policy (re-measured round 3, PERF.md): on the current libtpu
XLA's TriangularSolve runs at MXU matmul rate for panel shapes
(24 TF/s at 512x3584 on v5e) — the round-1/2 assumption that it is a
latency-bound expander (~2 ms per 256 block) no longer holds. The
single-device paths therefore use direct XLA solves and XLA's native
cholesky for diagonal blocks. The invert-diagonal-block-then-matmul
formulation is kept for the GRID (SPMD) paths only, where the per-step
matmuls carry the sharding constraints that spread panel work over the
mesh — the role the reference fills with column broadcasts + tile trsm
tasks (work_trsm.cc pipeline).

Numerical note (grid path): the diag-block inverses are computed by
exact forward substitution, so using them via matmul changes the error
constant of the solve by a factor ~cond(A_kk) of the *diagonal blocks*
only; for the factorization drivers the diagonal blocks are the
well-conditioned Cholesky/LU panels, the standard TPU trade.

The trailing Hermitian update is a plain dense rank-k matmul, on
purpose. Lower-triangle-only variants were built and measured on v5e
(m=7680, k=512, f32 HIGHEST): dense full square 1.9 ms, recursive
halving with lower-only leaves 3.2 ms, Pallas packed lower-tile grid
2.6 ms — the 2x FLOP saving of the stored-triangle herk (reference
internal_herk.cc Devices path) is more than repaid by block-assembly
copies / per-tile grid overhead, while the full-square matmul runs at
the chip's peak HIGHEST rate. On TPU the reference's "touch only the
stored triangle" optimization is a pessimization.

`python bench.py --micro` re-measures the surviving kernels behind
these numbers (panel kernels, trtri, the dense trailing update, XLA's
native cholesky/LU and TriangularSolve latency) with the same
slope-timing protocol on the ambient backend; the two LOSING
trailing-update variants (recursive halving, Pallas packed tiles)
were deleted after the measurement, so their quoted times are
historical record, not regenerable.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tiles import ceil_div, round_up

_HI = jax.lax.Precision.HIGHEST


#: block order up to which one XLA solve-against-identity is the
#: inversion leaf; larger blocks recurse on halves (two matmuls per
#: level, MXU rate). Measured v5e (PERF.md): XLA TriangularSolve is
#: matmul-rate on this libtpu (256: 14 µs, 512: 35 µs), beating the
#: fused Pallas substitution kernel (54 / 334 µs) everywhere ≥ 256 —
#: the round-2 "latency-bound expander" rationale is obsolete.
TRTRI_LEAF_MAX = 512


def invert_triangular(a: jax.Array, lower: bool,
                      unit_diagonal: bool = False) -> jax.Array:
    """Inverse of a triangular block: one XLA triangular solve against
    the identity up to TRTRI_LEAF_MAX, block substitution on halves
    (two dense matmuls per level, same error constants) above it.
    Upper inputs reduce to lower via transposition."""
    n = a.shape[0]
    if not lower:
        return invert_triangular(a.T, True, unit_diagonal).T
    if n <= TRTRI_LEAF_MAX:
        return jax.lax.linalg.triangular_solve(
            a, jnp.eye(n, dtype=a.dtype), left_side=True, lower=True,
            unit_diagonal=unit_diagonal)
    # inv([[A, 0], [C, B]]) = [[iA, 0], [-iB C iA, iB]]
    h = round_up(ceil_div(n, 2), 128)
    ia = invert_triangular(a[:h, :h], True, unit_diagonal)
    ib = invert_triangular(a[h:, h:], True, unit_diagonal)
    c = jnp.matmul(jnp.matmul(ib, a[h:, :h], precision=_HI), ia,
                   precision=_HI)
    out = jnp.zeros_like(a)
    out = out.at[:h, :h].set(ia).at[h:, h:].set(ib).at[h:, :h].set(-c)
    return out


#: Cap (bytes) on the estimated progressive-copy temps of one direct
#: XLA TriangularSolve: its TPU expander holds one snapshot of the
#: growing output per 128-column step of the triangle, which at
#: OOC/CholQR shapes is tens of GB on a 16 GB part (measured: a
#: (4096, 4096) triangle vs a 65536-row RHS dies with 15.3 GB of HLO
#: temps — the cholqr Q = A R^-1 case). Above the cap, trsm_left
#: slabs the RHS into independent column blocks (backward-stable —
#: each slab is still a direct solve) and the streamed ooc solves
#: switch to invert-then-matmul (their blocks are Cholesky/unit-LU
#: diagonal blocks, hardware-validated at n=65536).
SOLVE_TEMP_CAP = 2 << 30


def solve_temps_bytes(other: int, tri: int, itemsize: int) -> int:
    """Progressive-copy temp estimate for one triangular solve with a
    (tri, tri) triangle and an output of other * tri elements: ~tri/128
    expander steps (the step count follows the TRIANGLE dimension),
    one DUS snapshot of the growing output per step, each ~half the
    output."""
    return (tri // 128) * other * tri * itemsize // 2


def trsm_left(a: jax.Array, b: jax.Array, lower: bool, nb: int,
              unit_diagonal: bool = False,
              precision=_HI, grid=None) -> jax.Array:
    """Solve A X = B with A (n, n) triangular, B (n, k): blocked
    substitution, right-looking updates, diag blocks by
    invert-then-matmul. With a grid, every block step's update is
    sharding-constrained so SPMD spreads it over the mesh (the
    reference's work::trsm row pipeline, work_trsm.cc:70-110)."""
    from ..parallel.sharding import constrain
    n = a.shape[0]
    nt = ceil_div(n, nb)
    if nt <= 1 or grid is None:
        # single-device: direct XLA solves — matmul-rate on this
        # libtpu at every measured shape (PERF.md: 24 TF/s on 512-diag
        # panels, 15 TF/s at 4096x4096), LAPACK-backed on CPU, and
        # backward stable (no inverse formed). The blocked
        # invert-then-matmul loop below exists for the grid path,
        # whose per-step matmuls carry sharding constraints the
        # one-shot solve cannot express.
        def direct(rhs):
            return jax.lax.linalg.triangular_solve(
                a, rhs, left_side=True, lower=lower,
                unit_diagonal=unit_diagonal)

        per_col = solve_temps_bytes(1, n, b.dtype.itemsize)
        if per_col * b.shape[1] > SOLVE_TEMP_CAP:
            # huge-RHS safety valve (see SOLVE_TEMP_CAP): the RHS
            # columns are independent, so slab them and run one
            # direct solve per slab — same backward stability, temps
            # bounded per slab, a handful of matmul-rate dispatches
            k_slab = (max(int(SOLVE_TEMP_CAP // per_col), 1)
                      if per_col > 0 else 1)
            outs = [direct(b[:, j:j + k_slab])
                    for j in range(0, b.shape[1], k_slab)]
            return jnp.concatenate(outs, axis=1)
        return direct(b)
    if trsm_form(n, nb) == "scan":
        return trsm_left_scan(a, b, lower, nb, unit_diagonal, precision,
                              grid)
    x = b
    order = range(nt) if lower else range(nt - 1, -1, -1)
    for k in order:
        k0, k1 = k * nb, min((k + 1) * nb, n)
        akk = a[k0:k1, k0:k1]
        inv = invert_triangular(akk, lower, unit_diagonal)
        xk = jnp.matmul(inv, x[k0:k1], precision=precision)
        x = x.at[k0:k1].set(xk)
        if lower and k1 < n:
            upd = jnp.matmul(a[k1:, k0:k1], xk, precision=precision)
            x = constrain(x.at[k1:].add(-upd), grid)
        elif not lower and k0 > 0:
            upd = jnp.matmul(a[:k0, k0:k1], xk, precision=precision)
            x = constrain(x.at[:k0].add(-upd), grid)
    return x


def trsm_left_scan(a: jax.Array, b: jax.Array, lower: bool, nb: int,
                   unit_diagonal: bool = False, precision=_HI,
                   grid=None) -> jax.Array:
    """The grid loop of `trsm_left` as ONE compiled block step under
    fori_loop, for nt > CHOL_SCAN_THRESHOLD (n a multiple of nb): the
    step takes column block k of A full-height, rows at or past the
    diagonal block masked to zero, so every step has one shape (twice
    the update FLOPs of the shrinking loop, n^2 k in all). Compiled
    for a v5e 2x2, the two unrolled sweeps of a potrs at n=49152,
    nt=96 kept the compiler 5 min 23 s and were refused (278.72 GB of
    HBM wanted a chip); this form compiles in 3.4 s (PERF.md, PR 27).
    A step reads column block k of A, its diagonal block and block k
    of X and writes block k of X through `_take_block` and
    `_put_block`: under a grid, on the chips that own them."""
    from ..parallel.sharding import constrain
    n = a.shape[0]
    nt = n // nb
    rows = jnp.arange(n)

    def step(i, x):
        k = i if lower else nt - 1 - i
        colblk = _take_block(a, k, nb, 1, grid)
        inv = invert_triangular(
            _take_block(colblk, k, nb, 0, grid, COLUMN_BLOCK),
            lower, unit_diagonal)
        xk = jnp.matmul(inv, _take_block(x, k, nb, 0, grid),
                        precision=precision)
        rest = rows >= (k + 1) * nb if lower else rows < k * nb
        upd = jnp.matmul(jnp.where(rest[:, None], colblk, 0), xk,
                         precision=precision)
        return constrain(_put_block(x - upd, xk, k, nb, 0, grid), grid)

    return jax.lax.fori_loop(0, nt, step, b)


def trsm_dense(a: jax.Array, b: jax.Array, *, left: bool, lower: bool,
               nb: int, unit_diagonal: bool = False,
               precision=_HI, grid=None) -> jax.Array:
    """General entry: reduces the Right case to Left via conjugate
    transposition (X A = B  <=>  A^H X^H = B^H)."""
    if left:
        return trsm_left(a, b, lower, nb, unit_diagonal, precision, grid)
    xh = trsm_left(jnp.conj(a.T), jnp.conj(b.T), not lower, nb,
                   unit_diagonal, precision, grid)
    return jnp.conj(xh.T)


def assemble_packed(panels, strips, nb: int, kmax: int, M: int, N: int,
                    dtype) -> jax.Array:
    """Shared final assembly for the carry-style factorization drivers
    (LU/QR): stack each step's (m_k, w_k) panel under k*nb zero rows,
    concatenate the column blocks, zero-extend to N columns for
    rectangular M < N, and overlay each step's top strip (U12 / R12)
    right of its diagonal block."""
    cols = [jnp.concatenate(
        [jnp.zeros((k * nb, p.shape[1]), dtype), p], axis=0)
        for k, p in enumerate(panels)]
    out = jnp.concatenate(cols, axis=1)            # (M, kmax)
    if N > kmax:
        out = jnp.concatenate(
            [out, jnp.zeros((M, N - kmax), dtype)], axis=1)
    for k, strip in enumerate(strips):
        k0 = k * nb
        k1 = min((k + 1) * nb, kmax)
        out = jax.lax.dynamic_update_slice(out, strip, (k0, k1))
    return out


def chol_diag_factor(s: jax.Array) -> jax.Array:
    """Factor one SPD diagonal block: XLA's native cholesky everywhere
    (LAPACK on CPU; on TPU it beats the fused Pallas panel at every
    size — 256: 33 vs 103 µs, 512: 95 vs 341 µs on v5e, PERF.md).
    symmetrize_input=False because callers hand blocks whose upper
    triangle may hold stale values (lower-only updates); averaging it
    in would corrupt the factor."""
    return jax.lax.linalg.cholesky(s, symmetrize_input=False)


def _chol_panel_solve(lkk: jax.Array, bpanel: jax.Array, grid,
                      precision=_HI):
    """pan = B L^{-H} (the Cholesky panel step). Single-device: one
    direct XLA solve (matmul-rate, PERF.md); `precision` does not
    thread into it because TriangularSolve takes none — its TPU
    expander runs f32-accurate internally (measured: a full blocked
    potrf built on these solves reproduces 4.7e-7 relative residual at
    n=2048 on v5e, PERF.md), so no HIGHEST pin is needed. Under a
    grid: invert-then-matmul at `precision`, because the per-step
    matmul carries the sharding constraint that spreads panel rows
    over the mesh (the reference's column bcast + trsm,
    potrf.cc:108-115) — a one-shot solve would be replicated by
    SPMD."""
    from ..parallel.sharding import constrain, panel_spec
    if grid is None:
        return jax.lax.linalg.triangular_solve(
            lkk, bpanel, left_side=False, lower=True,
            transpose_a=True, conjugate_a=True)
    inv = invert_triangular(lkk, lower=True)
    return constrain(
        jnp.matmul(bpanel, jnp.conj(inv.T), precision=precision),
        grid, panel_spec())


def chol_loop(a: jax.Array, nb: int, diag_factor,
              precision=_HI, grid=None):
    """Shared right-looking blocked Cholesky loop (reference impl::potrf
    task structure, potrf.cc:85-192): per step, factor the diagonal
    block via `diag_factor(s) -> (lkk, local_info)`, solve the panel by
    a direct XLA solve (invert-then-matmul under a grid), apply one
    dense trailing herk (see module docstring for why dense beats
    lower-only on TPU). Returns (L, info)
    with info the first failed global pivot index (0 if none)
    accumulated like reference potrf.cc:104-105 ``info = kk + iinfo``."""
    from ..parallel.sharding import constrain
    n = a.shape[0]
    nt = ceil_div(n, nb)
    info = jnp.zeros((), jnp.int32)
    for k in range(nt):
        k0, k1 = k * nb, min((k + 1) * nb, n)
        lkk, bad = diag_factor(a[k0:k1, k0:k1])
        info = jnp.where((info == 0) & (bad > 0), k0 + bad, info)
        a = a.at[k0:k1, k0:k1].set(lkk)
        if k1 < n:
            # panel rows over the whole mesh (reference column bcast +
            # trsm, potrf.cc:108-115); trailing herk output P('p','q')
            # so every step's FLOPs spread over the full grid — the
            # load-balance role of 2D block-cyclic storage
            pan = _chol_panel_solve(lkk, a[k1:, k0:k1], grid, precision)
            a = a.at[k1:, k0:k1].set(pan)
            upd = jnp.matmul(pan, jnp.conj(pan.T), precision=precision)
            a = constrain(a.at[k1:, k1:].add(-upd), grid)
    return a, info


def chol_loop_pipelined(a: jax.Array, nb: int, diag_factor,
                        precision=_HI, grid=None):
    """Software-pipelined (lookahead-1) form of chol_loop, the
    dataflow shape of the reference's lookahead task columns
    (potrf.cc:136-176): the step-k trailing update is SPLIT into the
    next panel's column (narrow, on the critical path) and the rest
    (wide, the bulk FLOPs). The next panel factors immediately after
    the narrow update, so the wide step-k matmul and the step-k+1
    panel chain are INDEPENDENT nodes in the compiled graph — the
    scheduler (XLA; or concurrent mesh shards under SPMD) is free to
    overlap them instead of serializing panel -> full-trailing ->
    panel the way the plain right-looking order forces.

    Same arithmetic as chol_loop (the narrow+wide split computes the
    identical update), so the LOWER triangles agree to roundoff — the
    strictly-upper strip above each panel keeps stale values here
    (chol_loop's full-square trailing update overwrites it), which the
    triangular output's to_dense masks anyway.

    Measured (n=2048, nb=256, f32): CPU backend 216 ms vs 212 ms plain
    — no change, as expected: XLA CPU runs one op at a time (intra-op
    threading only), so reordering buys nothing there. The payoff
    surface is backends with cross-op concurrency (TPU async compute /
    SPMD mesh shards); bench.py measures the pair on the TPU chip as
    potrf_tiled_la{0,1} extras."""
    from ..parallel.sharding import constrain
    n = a.shape[0]
    nt = ceil_div(n, nb)
    info = jnp.zeros((), jnp.int32)
    # prologue: factor block 0 and its panel
    k1 = min(nb, n)
    lkk, bad = diag_factor(a[:k1, :k1])
    info = jnp.where(bad > 0, bad, info)
    a = a.at[:k1, :k1].set(lkk)
    pan = None
    if k1 < n:
        pan = _chol_panel_solve(lkk, a[k1:, :k1], grid, precision)
        a = a.at[k1:, :k1].set(pan)
    for k in range(nt - 1):
        k1 = min((k + 1) * nb, n)
        k2 = min(k1 + nb, n)
        w = k2 - k1
        # narrow update: the next panel's column only (critical path)
        pan_top = pan[:w]
        colblk = a[k1:, k1:k2] - jnp.matmul(
            pan, jnp.conj(pan_top.T), precision=precision)
        # factor the next diagonal block + panel from it
        lkk, bad = diag_factor(colblk[:w])
        info = jnp.where((info == 0) & (bad > 0), k1 + bad, info)
        a = a.at[k1:k2, k1:k2].set(lkk)
        next_pan = None
        if k2 < n:
            next_pan = _chol_panel_solve(lkk, colblk[w:], grid,
                                         precision)
            a = a.at[k2:, k1:k2].set(next_pan)
            # wide trailing update with step-k's panel — independent
            # of the panel chain above
            pan_rest = pan[w:]
            upd = jnp.matmul(pan_rest, jnp.conj(pan_rest.T),
                             precision=precision)
            a = constrain(a.at[k2:, k2:].add(-upd), grid)
        pan = next_pan
    return a, info


#: block-step count above which the Tiled Cholesky switches from the
#: Python-unrolled shrinking-slice loop (minimal FLOPs, program size
#: O(nt)) to the fori_loop form (`cholesky_scan`: one step body a
#: stage, program size O(CHOL_SCAN_STAGES); a stage's steps update the
#: whole of the stage's trailing square, so the form does some
#: 2.8 times the n^3/3 FLOPs of a factorization at four stages, six
#: times in one) — compile time stays bounded for huge-n distributed
#: runs (reference task emission scales to nt=512, potrf.cc:85).
#: `trsm_left`'s grid loop switches to its scan form at the same count
CHOL_SCAN_THRESHOLD = 64


#: how a scan form holds one column block of a matrix on a grid: as
#: `_take_block` hands it over, its rows over 'p' and whole along its
#: nb columns (spread over 'q' too, the products against it would sum
#: over the mesh: an all-reduce of n/p x n a step)
COLUMN_BLOCK = P("p", None)


def block_on_one_chip(dim: int, nb: int, parts: int) -> bool:
    """Whether every nb-block of a dimension of `dim` spread over
    `parts` chips lies on one of them: a chip's extent is a multiple of
    nb (24576 = 48 x 512 at n=49152 on a 2x2; not the 388 rows a chip
    of n=776, mb=8 on two), or the dimension is not spread at all
    (`fitted_sharding` drops a mesh axis that does not divide it). The
    one static predicate by which `_take_block`, `_put_block` and the
    dispatch counters of `chol.potrf` and `chol.potrs` choose between
    the local and the masked form."""
    return dim % parts != 0 or (dim // parts) % nb == 0


def grid_blocks(n: int, nb: int, grid) -> str:
    """How the scan forms reach the blocks of an order-n operand under
    `grid`: "local" where `block_on_one_chip` holds along both mesh
    axes, else "masked" (a form whose accesses along one axis alone
    fall back is counted masked)."""
    local = all(block_on_one_chip(n, nb, parts)
                for parts in (grid.p, grid.q))
    return "local" if local else "masked"


def _on_owner(a: jax.Array, nb: int, axis: int, grid, spec):
    """What the local form of `_take_block` / `_put_block` needs of an
    operand held as `spec` (fitted to its shape) on `grid`: that
    spec, the same with `axis` replicated (a block's), the mesh axis
    `axis` is spread over (None where it is not), and `where(k)`:
    under `shard_map`, block k's start indices in a chip's shard and
    whether the chip holds it (None where every chip does). None
    where a block can straddle two chips."""
    from ..parallel.sharding import fitted_sharding
    spec = fitted_sharding(a.shape, grid, spec).spec
    name = spec[axis]
    names = name if isinstance(name, tuple) else (name,) if name else ()
    parts = math.prod(grid.mesh.shape[nm] for nm in names)
    if not block_on_one_chip(a.shape[axis], nb, parts):
        return None
    blk_spec = P(*(None if d == axis else e for d, e in enumerate(spec)))
    extent = a.shape[axis] // parts

    def where(k):
        owner = (k * nb) // extent
        start = [0] * a.ndim
        start[axis] = k * nb - owner * extent
        own = None if name is None \
            else jax.lax.axis_index(name) == owner
        return start, own

    return spec, blk_spec, name, where


def _take_block(a: jax.Array, k, nb: int, axis: int, grid,
                spec=None) -> jax.Array:
    """Block k (k traced) of the nb-blocks of `a` along `axis`: rows
    k*nb:(k+1)*nb for axis 0, columns for axis 1. With no grid a
    `dynamic_slice`. Under a grid the same slice at a traced offset
    along a sharded dimension makes the SPMD partitioner all-gather
    the WHOLE operand onto every device (9.0 GB a chip at n=49152 on
    2x2; PERF.md, PR 27), so the block is read on the chip that owns
    it: under `shard_map`, which `a` enters as `spec` (default
    P('p','q'); `fitted_sharding`), every chip slices its own shard
    at the block's offset inside the owner, the chips that do not own
    it zero what they read, and a `psum` along that mesh axis hands
    the block to all of them (exact: every other term is a zero). A
    chip reads the block's bytes and the mesh moves one all-reduce of
    them. The result keeps the other axis' sharding and is replicated
    along `axis`. Where a block straddles two chips
    (`block_on_one_chip` is false) it is picked by a one-hot mask over
    the block axis and summed instead, which stays sharded too but
    passes over the whole of `a` (2.4 GB a chip to deliver 50 MB;
    PERF.md, PR 32)."""
    if grid is None:
        start, size = [0, 0], list(a.shape)
        start[axis], size[axis] = k * nb, nb
        return jax.lax.dynamic_slice(a, start, size)
    local = _on_owner(a, nb, axis, grid, spec)
    if local is not None:
        from ..parallel.smap import shard_map
        spec, blk_spec, name, where = local

        def take(shard, k):
            start, own = where(k)
            size = list(shard.shape)
            size[axis] = nb
            blk = jax.lax.dynamic_slice(shard, start, size)
            if own is None:
                return blk
            return jax.lax.psum(jnp.where(own, blk, 0), name)

        return shard_map(take, grid.mesh, (spec, P()), blk_spec)(
            a, jnp.asarray(k))
    blocks = a.shape[axis] // nb
    sel = (jnp.arange(blocks) == k)[
        tuple(slice(None) if d == axis else None for d in range(3))]
    view = a.shape[:axis] + (blocks, nb) + a.shape[axis + 1:]
    return jnp.sum(jnp.where(sel, a.reshape(view), 0), axis=axis)


def _put_block(a: jax.Array, blk: jax.Array, k, nb: int, axis: int,
               grid, spec=None) -> jax.Array:
    """`a` with block k along `axis` replaced by `blk`. With no grid a
    `dynamic_update_slice`; under a grid, for `_take_block`'s reason,
    the same on every chip's own shard under `shard_map`, at the
    block's offset inside its owner: the owner writes its part of
    `blk` and every other chip what it held there, so a step reads
    and writes the block's bytes in place. Where a block straddles
    two chips, a select over the whole of `a` against the tiled
    block."""
    if grid is None:
        start = [0, 0]
        start[axis] = k * nb
        return jax.lax.dynamic_update_slice(a, blk, start)
    local = _on_owner(a, nb, axis, grid, spec)
    if local is not None:
        from ..parallel.smap import shard_map
        spec, blk_spec, _name, where = local

        def put(shard, blk, k):
            start, own = where(k)
            if own is not None:
                blk = jnp.where(own, blk, jax.lax.dynamic_slice(
                    shard, start, blk.shape))
            return jax.lax.dynamic_update_slice(shard, blk, start)

        return shard_map(put, grid.mesh, (spec, blk_spec, P()), spec)(
            a, blk, jnp.asarray(k))
    reps, here = [1, 1], [None, None]
    reps[axis] = a.shape[axis] // nb
    here[axis] = slice(None)
    here = (jnp.arange(a.shape[axis]) // nb == k)[tuple(here)]
    return jnp.where(here, jnp.tile(blk, reps), a)


def _exchange_rows(a: jax.Array, dst: jax.Array, src: jax.Array,
                   grid) -> jax.Array:
    """`a` with row dst[i] replaced by what row src[i] held, for the
    few rows a pivoted step touches (dst, src traced; a row named
    twice in `dst` is given one content both times). With no grid a
    gather and a scatter. Under a grid `a[perm]` of a matrix held as
    P('p','q') gathers ALL of its rows on every chip to move these
    (9.66 GB read a step at n=49152 for the 201 MB that move), so
    under `shard_map` (ScaLAPACK's `pslaswp`): every chip gathers the
    source rows it holds out of its own shard, zeros for the others,
    a `psum` along the mesh axis the rows are spread over hands each
    row to the chips of its column of the mesh (exact: every other
    term is a zero), and every chip writes the rows that land in its
    shard, in place. A row lies whole on one chip of each mesh column
    whatever the blocking, so there is no masked form; where the mesh
    axis does not divide the rows (`fitted_sharding` drops it) every
    chip holds them all and exchanges its own."""
    if grid is None:
        return a.at[dst].set(a[src])
    from ..parallel.sharding import fitted_sharding
    from ..parallel.smap import shard_map
    spec = fitted_sharding(a.shape, grid).spec
    name = spec[0]

    def exchange(shard, dst, src):
        if name is None:
            return shard.at[dst].set(shard[src])
        extent = shard.shape[0]
        first = jax.lax.axis_index(name) * extent
        at = src - first
        held = (at >= 0) & (at < extent)
        rows = shard.at[jnp.clip(at, 0, extent - 1)].get(
            mode="promise_in_bounds")
        rows = jax.lax.psum(jnp.where(held[:, None], rows, 0), name)
        to = dst - first
        # a row that lands elsewhere is written past the shard: dropped
        to = jnp.where((to >= 0) & (to < extent), to, extent)
        return shard.at[to].set(rows, mode="drop")

    return shard_map(exchange, grid.mesh, (spec, P(), P()), spec)(
        a, jnp.asarray(dst, jnp.int32), jnp.asarray(src, jnp.int32))


def _shared_runs(length: int, at_src: int, ext_src: int, at_dst: int,
                 ext_dst: int, parts: int) -> list:
    """One dimension of `_move_rect`: a run of `length` from `at_src`
    of a dimension held in `parts` parts of `ext_src` to `at_dst` of
    one held in parts of `ext_dst`, as (source part, destination
    part, start inside the source part, start inside the destination
    part, length) for every pair of parts that share some of it."""
    runs = []
    for j in range(parts):
        for i in range(parts):
            lo = max(j * ext_src - at_src, i * ext_dst - at_dst, 0)
            hi = min((j + 1) * ext_src - at_src,
                     (i + 1) * ext_dst - at_dst, length)
            if lo < hi:
                runs.append((j, i, lo + at_src - j * ext_src,
                             lo + at_dst - i * ext_dst, hi - lo))
    return runs


#: bytes of the largest piece one round of `_move_rect` hands from
#: chip to chip: a rectangle is moved in column strips this small, one
#: round after the other, so that a move holds a few hundred MB
#: besides its operands whatever their size
MOVE_PIECE_BYTES = 256 << 20


def _move_rect(src: jax.Array, dst, size, at_src, at_dst,
               grid) -> jax.Array:
    """`dst` with the `size` rectangle of `src` at `at_src` written at
    `at_dst`, all static; `dst` given as a shape is a new array of
    zeros. With no grid a slice and a `dynamic_update_slice`. Under a
    grid both are held as P('p','q') over orders that differ, so a
    chip's part of the rectangle lies on other chips of `dst` (the
    partitioner answers the same slice and update with copies of the
    operands at full height, 5.1 GB of temporaries a chip at n=49152
    in four stages, and gathers `dst` whole where the rectangle
    straddles two chips' columns; compiled for a v5e 2x2, PERF.md,
    PR 41). So under `shard_map`, in rounds: for each column strip of
    at most MOVE_PIECE_BYTES a piece and each shift (d, d') of the
    mesh that some chip sends it along, every chip cuts the piece it
    owes out of its shard (`lax.switch` on its place in the mesh: the
    pieces are static and differ from chip to chip), zero-padded to
    the largest of the round, one `ppermute` an axis hands it over,
    and the receiver writes the part that is meant into a window of
    its shard in place. A round starts when the one before it has
    written (`optimization_barrier`): a chip sends what the other
    needs and holds one round's pieces besides the operands."""
    h, w = size
    new = not hasattr(dst, "shape")
    if grid is None:
        rect = jax.lax.slice(src, at_src, (at_src[0] + h, at_src[1] + w))
        return jax.lax.dynamic_update_slice(
            jnp.zeros(dst, src.dtype) if new else dst, rect, at_dst)
    from ..parallel.smap import shard_map
    p, q = grid.p, grid.q
    shape = tuple(dst) if new else dst.shape
    assert all(d % parts == 0 for arr in (src.shape, shape)
               for d, parts in zip(arr, (p, q)))
    held = (src.shape[0] // p, src.shape[1] // q)
    local = (shape[0] // p, shape[1] // q)
    rows = _shared_runs(h, at_src[0], held[0], at_dst[0], local[0], p)

    def shifts(c, cw):
        """The pieces of columns c:c+cw of the rectangle, by shift."""
        cols = _shared_runs(cw, at_src[1] + c, held[1], at_dst[1] + c,
                            local[1], q)
        by = {}
        for j, i, sr, dr, rh in rows:
            for j2, i2, sc, dc, pw in cols:
                by.setdefault(((j - i) % p, (j2 - i2) % q), []).append(
                    (j * q + j2, i * q + i2, (sr, sc), (dr, dc), (rh, pw)))
        return by

    strip = MOVE_PIECE_BYTES // (src.dtype.itemsize * min(h, held[0]))
    strip = max(strip // 128 * 128, 128)
    rounds = [rnd for c in range(0, w, strip)
              for rnd in sorted(shifts(c, min(strip, w - c)).items())]
    if new:
        # a new array starts as what every chip keeps of its own, in
        # one piece of the array's size: nothing to blend into yet
        rounds = [((0, 0), shifts(0, w).get((0, 0), []))] \
            + [rnd for rnd in rounds if rnd[0] != (0, 0)]

    def cut(sr, sc, rh, cw, pad):
        return lambda shard: jnp.pad(shard[sr:sr + rh, sc:sc + cw], pad)

    def move(shard, out=None):
        here = jax.lax.axis_index("p") * q + jax.lax.axis_index("q")
        for (d, d2), pieces in rounds:
            big = local if out is None else tuple(
                max(x[4][k] for x in pieces) for k in (0, 1))
            send = [lambda shard: jnp.zeros(big, shard.dtype)] * (p * q)
            # per receiving chip: the window's start, the piece's
            # start inside the window, the piece's size
            meant = [[0] * 6 for _ in range(p * q)]
            for sender, receiver, at, to, (rh, cw) in pieces:
                win = [min(to[k], local[k] - big[k]) for k in (0, 1)]
                off = [to[k] - win[k] for k in (0, 1)]
                send[sender] = cut(*at, rh, cw, (
                    (off[0], big[0] - off[0] - rh),
                    (off[1], big[1] - off[1] - cw)))
                meant[receiver] = [*win, *off, rh, cw]
            if out is None:
                out = jax.lax.switch(here, send, shard)
                continue
            shard, out = jax.lax.optimization_barrier((shard, out))
            got = jax.lax.switch(here, send, shard)
            if d:
                got = jax.lax.ppermute(
                    got, "p", [(j, (j - d) % p) for j in range(p)])
            if d2:
                got = jax.lax.ppermute(
                    got, "q", [(j, (j - d2) % q) for j in range(q)])
            r0, c0, ro, co, rh, cw = jnp.asarray(meant, jnp.int32)[here]
            rr, cc = jnp.arange(big[0]), jnp.arange(big[1])
            inside = ((rr >= ro) & (rr < ro + rh))[:, None] \
                & ((cc >= co) & (cc < co + cw))[None, :]
            got = jnp.where(inside, got,
                            jax.lax.dynamic_slice(out, (r0, c0), big))
            out = jax.lax.dynamic_update_slice(out, got, (r0, c0))
        return out

    spec = P("p", "q")
    args = (src,) if new else (src, dst)
    return shard_map(move, grid.mesh, (spec,) * len(args), spec)(*args)


def trsm_form(n: int, nb: int) -> str:
    """Which grid loop of `trsm_left` solves against an order-n
    triangle in nb-blocks: "scan" above CHOL_SCAN_THRESHOLD block
    steps where nb divides n, else "unrolled"."""
    nt = ceil_div(n, nb)
    scan = nt > CHOL_SCAN_THRESHOLD and n == nt * nb
    return "scan" if scan else "unrolled"


def chol_form(n: int, nb: int, guarded: bool = False) -> str:
    """Which loop factors an order-n matrix in nb-blocks: "scan" above
    CHOL_SCAN_THRESHOLD block steps, else "unrolled" (the guarded
    loops of linalg/info.py have no scan form)."""
    scan = ceil_div(n, nb) > CHOL_SCAN_THRESHOLD and not guarded
    return "scan" if scan else "unrolled"


#: stages `cholesky_scan` runs in (fewer where the order has fewer
#: boundaries to offer). Read on a v5e 2x2 at n=49152, nb=512 (the
#: factor alone, `tools/stage_probe.py`, PR 41): 2.2996 s in one stage,
#: 1.3346 in four, 1.2697 in five, 1.2445 in six, 1.2054 in eight. A
#: stage holds its square beside the next one's while that is made, so
#: the program's temporaries grow with the count (3.1, 3.5, 4.1, 5.3
#: GB a chip there); compiled for n=65536 four stages count 12.96 GB a
#: chip, what `potrs`'s program counts there already, and six 15.94,
#: over a chip's 15.75: four, for the last 0.13 s
CHOL_SCAN_STAGES = 4


def chol_scan_stages(n: int, nb: int, grid=None) -> tuple:
    """The stages of `cholesky_scan` on an order-n matrix in nb-blocks:
    ((r, w), ...), stage s factoring the w_s columns from r_s on the
    trailing square a[r_s:, r_s:]. Read from n, nb and the grid alone:
    a boundary is a multiple of nb * lcm(p, q), so that every trailing
    square keeps `block_on_one_chip` true along both mesh axes (1024
    rows at n=49152, nb=512 on a 2x2: 48 units; nb with no grid), and
    the units are dealt to CHOL_SCAN_STAGES stages as evenly as whole
    units go. Where the order is no multiple of the unit (n=776, nb=8
    on a 2x2: no trailing square's blocks would lie on one chip) the
    form keeps ONE stage, the whole matrix at every step."""
    unit = nb * (math.lcm(grid.p, grid.q) if grid is not None else 1)
    units = n // unit
    if n % unit or units < 2:
        return ((0, n),)
    stages = min(CHOL_SCAN_STAGES, units)
    cuts = [unit * (s * units // stages) for s in range(stages + 1)]
    return tuple((r, r1 - r) for r, r1 in zip(cuts, cuts[1:]))


def chol_scan_update_flops(n: int, nb: int, grid=None) -> int:
    """FLOPs of the trailing updates `cholesky_scan` dispatches on an
    order-n matrix: a stage of w columns on a trailing square of order
    m runs w / nb steps of 2 m^2 nb each (n^3/3 is what a Cholesky
    needs)."""
    return sum(2 * (n - r) ** 2 * w for r, w in chol_scan_stages(n, nb, grid))


def cholesky_scan(a: jax.Array, nb: int, precision=_HI,
                  grid=None) -> jax.Array:
    """Lower Cholesky as a compiled block step iterated by fori_loop,
    in the few static stages of `chol_scan_stages`: stage s runs the
    step over its trailing square t = a[r_s:, r_s:], a static slice
    spread over the grid again as P('p','q') so that every chip shares
    every stage's updates evenly (what `chol_loop` does at every step,
    here a handful of times: the square crosses the links once a
    stage), then its w_s factored columns go into the result at
    (r_s, r_s) and the next stage takes t[w_s:, w_s:]. A step takes a
    fixed (m, nb) column block of the square at a traced offset,
    factors the diagonal block, forms the panel full-height (rows
    above the panel masked to zero so the trailing matmul leaves
    factored columns untouched), and applies one trailing update of
    the square's size: the stages' squares shrink, so the form does
    `chol_scan_update_flops` for the 2 n^3 of a single stage. Program
    size goes with the stages, not with nt — the compile-time-safe
    form of chol_loop for nt > CHOL_SCAN_THRESHOLD. Blocks are read
    and written through `_take_block` and `_put_block`: under a grid
    each on the chip that owns it, so that no step gathers the square
    or passes over more of it than the update does, and the write of
    the factored column block is the carry's last use, in place."""
    from ..parallel.sharding import constrain

    def stage(t, steps):
        rows = jnp.arange(t.shape[0])

        def step(k, t):
            k0 = k * nb
            k1 = k0 + nb
            colblk = _take_block(t, k, nb, 1, grid)
            lkk = jnp.tril(chol_diag_factor(
                _take_block(colblk, k, nb, 0, grid, COLUMN_BLOCK)))
            # full-height panel solve: rhs rows are independent in the
            # right-side solve, so the dead rows cost only masked FLOPs
            pan = _chol_panel_solve(lkk, colblk, grid, precision)
            pan = jnp.where((rows >= k1)[:, None], pan, 0)
            upd = jnp.matmul(pan, jnp.conj(pan.T), precision=precision)
            t = constrain(t - upd, grid)
            # write the factored column block: L_kk on the diagonal,
            # the panel below, existing content above (the update is
            # zero in this block's columns, so `colblk` still holds it)
            newblk = _put_block(pan, lkk, k, nb, 0, grid, COLUMN_BLOCK)
            newblk = jnp.where((rows < k0)[:, None], colblk, newblk)
            return _put_block(t, newblk, k, nb, 1, grid)

        return jax.lax.fori_loop(0, steps, step, t)

    n = a.shape[0]
    out = t = a
    for r, w in chol_scan_stages(n, nb, grid):
        m = n - r
        t = stage(t, ceil_div(w, nb))
        # the first stage's square is the matrix itself
        out = t if r == 0 else _move_rect(t, out, (m, w), (0, 0), (r, r),
                                          grid)
        if w < m:
            t = _move_rect(t, (m - w, m - w), (m - w, m - w), (w, w),
                           (0, 0), grid)
            # the next stage starts when this one's columns are home
            out, t = jax.lax.optimization_barrier((out, t))
    return out


def cholesky_blocked(a: jax.Array, nb: int,
                     precision=_HI, grid=None,
                     lookahead: int = 1) -> jax.Array:
    """Lower Cholesky of padded (N, N) with identity-padded diagonal:
    right-looking blocked loop, diagonal blocks via XLA's native
    cholesky, panels by direct XLA solve (invert-then-matmul under a
    grid), trailing updates dense (module docstring). This is the
    tiled/SPMD path;
    the single-device fused path (chol.potrf MethodFactor.Fused)
    delegates whole to XLA's native blocked cholesky.

    lookahead >= 1 (Option.Lookahead, reference default 1) takes the
    software-pipelined loop whose wide trailing update is dataflow-
    independent of the next panel; 0 forces the plain right-looking
    order. The huge-nt scan form has one step body a stage and ignores
    the knob (its fori_loops carry no cross-step independence to
    exploit)."""
    if chol_form(a.shape[0], nb) == "scan":
        return cholesky_scan(a, nb, precision, grid)

    def diag_factor(s):
        return chol_diag_factor(s), jnp.zeros((), jnp.int32)

    loop = chol_loop_pipelined if lookahead >= 1 else chol_loop
    L, _ = loop(a, nb, diag_factor, precision, grid)
    return L
