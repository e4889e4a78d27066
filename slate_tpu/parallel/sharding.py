"""2D block-cyclic tile distribution (reference func.hh:178-185
``process_2d_grid``, BaseMatrix.hh:161 gridinfo).

The reference distributes tile (i, j) to rank (i % p, j % q): as a
factorization sweeps its trailing submatrix, every grid row/column
still owns a share, so no rank idles. A contiguous `NamedSharding`
(P('p','q')) cannot express that assignment directly — after half the
steps of potrf, the devices owning the top block rows have nothing
left to do *if computation follows storage*.

Two TPU-native mechanisms replace it:

1. **Cyclic relayout** (`to_cyclic` / `from_cyclic`): a tile-row/column
   permutation that reorders storage so the block-cyclic assignment
   becomes contiguous — tile i of p=2 moves to storage slot
   [0,2,4,... then 1,3,5,...]. On the permuted array,
   `grid.matrix_sharding()` IS 2D block-cyclic over the logical tiles.
   This is the layout used for ScaLAPACK-style interop and
   `redistribute`, and costs one gather (an all-to-all under SPMD).

2. **Per-step sharding constraints** (`constrain`, used by the Tiled
   factorization drivers): under XLA SPMD the FLOP placement of a
   matmul follows the *sharding of its operands/output*, not the
   storage position of the logical submatrix. Constraining each block
   step's panel and trailing update to P('p','q') makes XLA partition
   every step's work across the full mesh — the load-balancing effect
   block-cyclic storage buys in MPI-land, with the compiler inserting
   the same column/row broadcasts the reference hand-codes
   (potrf.cc:108 tileBcast). This is why the drivers do NOT permute
   tiles: the permutation would destroy the contiguous slab slicing
   that feeds the MXU, while constraints deliver the balance for free.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tiles import TiledMatrix, ceil_div
from .mesh import ProcessGrid


def cyclic_tile_order(nt: int, p: int) -> np.ndarray:
    """Storage order of logical tile indices for a p-fold cyclic
    distribution: all tiles owned by rank 0 first (i % p == 0), then
    rank 1, ... Matches the reference's process_2d_grid row assignment
    (func.hh:178: rank = i % p)."""
    return np.concatenate([np.arange(r, nt, p) for r in range(max(p, 1))])


def _row_perm(npad: int, b: int, p: int) -> np.ndarray:
    nt = npad // b
    order = cyclic_tile_order(nt, p)
    return (order[:, None] * b + np.arange(b)[None, :]).reshape(-1)


def to_cyclic(a: jax.Array, mb: int, nb: int, p: int, q: int
              ) -> jax.Array:
    """Permute a padded (M, N) array into 2D block-cyclic storage order
    for a p x q grid; on the result, contiguous P('p','q') sharding
    assigns logical tile (i, j) to device (i % p, j % q)."""
    M, N = a.shape
    out = a
    if p > 1 and M // mb > 1:
        out = out[jnp.asarray(_row_perm(M, mb, p))]
    if q > 1 and N // nb > 1:
        out = out[:, jnp.asarray(_row_perm(N, nb, q))]
    return out


def from_cyclic(a: jax.Array, mb: int, nb: int, p: int, q: int
                ) -> jax.Array:
    """Inverse of `to_cyclic`."""
    M, N = a.shape
    out = a
    if p > 1 and M // mb > 1:
        out = out[jnp.asarray(np.argsort(_row_perm(M, mb, p)))]
    if q > 1 and N // nb > 1:
        out = out[:, jnp.asarray(np.argsort(_row_perm(N, nb, q)))]
    return out


def cyclic_sharding(grid: ProcessGrid) -> NamedSharding:
    """Sharding to pair with `to_cyclic` storage: contiguous P('p','q')
    on the permuted array == block-cyclic on logical tiles."""
    return grid.matrix_sharding()


def distribute_cyclic(A: TiledMatrix, grid: ProcessGrid) -> TiledMatrix:
    """Place A's storage on the grid in 2D block-cyclic layout
    (permuted storage + contiguous sharding). The result's `data` is
    device-resident; use `undistribute` to recover logical layout.
    Reference analogue: fromScaLAPACK + the default 2D block-cyclic
    constructors (Matrix.hh:73)."""
    import dataclasses
    perm = to_cyclic(A.data, A.mb, A.nb, grid.p, grid.q)
    return dataclasses.replace(
        A, data=jax.device_put(perm, cyclic_sharding(grid)))


def undistribute(A: TiledMatrix, grid: ProcessGrid) -> TiledMatrix:
    """Inverse of distribute_cyclic: gather + un-permute."""
    import dataclasses
    return dataclasses.replace(
        A, data=from_cyclic(A.data, A.mb, A.nb, grid.p, grid.q))


# -- constraint helpers used by the Tiled driver paths --------------------

def fitted_sharding(shape: Tuple[int, ...], grid: ProcessGrid,
                    spec: Optional[P] = None) -> NamedSharding:
    """`spec` (default P('p','q')) on `grid` for an array of `shape`,
    with every mesh axis that does not divide its dimension dropped
    (XLA requires divisibility): a ragged RHS (say 10 columns on a q=4
    grid) keeps its row sharding and replicates over 'q' instead of
    erroring — the balance degrades gracefully exactly where the
    reference's block-cyclic assignment would leave partial tiles."""
    if spec is None:
        spec = P("p", "q")
    sizes = dict(grid.mesh.shape)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    fixed = []
    for dim, e in zip(shape, entries):
        if e is None:
            fixed.append(None)
            continue
        names = e if isinstance(e, tuple) else (e,)
        prod = 1
        for nm in names:
            prod *= sizes[nm]
        fixed.append(e if dim % prod == 0 else None)
    return NamedSharding(grid.mesh, P(*fixed))


def constrain(x: jax.Array, grid: Optional[ProcessGrid],
              spec: Optional[P] = None) -> jax.Array:
    """with_sharding_constraint when a grid is present, identity
    otherwise — lets the blocked drivers be grid-agnostic. The spec is
    fitted to x's shape (`fitted_sharding`)."""
    if grid is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, fitted_sharding(x.shape, grid, spec))


@functools.lru_cache(maxsize=None)
def _pad_program(grid: ProcessGrid):
    """Zero-pad an array on `grid` up to a (static) shape, result on
    `grid`: one jitted function a grid."""
    def pad(x, shape):
        return constrain(jnp.pad(x, [(0, t - s) for s, t
                                     in zip(x.shape, shape)]), grid)
    return jax.jit(pad, static_argnums=1)


def place(a, grid: ProcessGrid,
          shape: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """`a` on `grid` as P('p','q'), zero-padded up to `shape`; what the
    matrix constructors' ``grid=`` argument calls. Each device is sent
    its own block of `a` (from host memory when `a` is a numpy array:
    the whole is never on one chip; the padding is added on the mesh),
    and the call returns when every shard is there.

    With the obs bus on, the placement is the span `grid::place`
    (bytes, devices; open until the shards are ready); a host array's
    hand-over is `matrix::h2d` inside it, and the bytes handed to the
    devices add to the counter `grid.h2d_bytes`: `a.nbytes` when the
    grid's axes divide a's dimensions, more where a block is sent to
    several devices."""
    from ..obs import events as obs_events, metrics as obs_metrics
    sharding = fitted_sharding(a.shape, grid)
    with obs_events.span("grid::place", cat="staging",
                         bytes=int(a.nbytes), devices=grid.nprocs):
        if obs_events.enabled() and isinstance(a, np.ndarray):
            with obs_events.span("matrix::h2d", cat="staging",
                                 bytes=int(a.nbytes),
                                 devices=grid.nprocs):
                out = jax.device_put(a, sharding)
            obs_metrics.inc("grid.h2d_bytes", sum(
                s.data.nbytes for s in out.addressable_shards))
        else:
            out = jax.device_put(a, sharding)
        if shape is not None and tuple(shape) != out.shape:
            out = _pad_program(grid)(out, tuple(shape))
        return jax.block_until_ready(out)


def panel_spec() -> P:
    """Tall-skinny panels: rows over the whole mesh (the reference's
    panel-column rank set, getrf.cc:91)."""
    return P(("p", "q"), None)
