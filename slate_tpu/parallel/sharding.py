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

import concurrent.futures as cf
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.staging import StageRing, aliases_host
from ..core.tiles import TiledMatrix, ceil_div
from .mesh import ProcessGrid
from .smap import shard_map


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


#: bytes a staged chunk of a strided block aims at; the chunk height
#: follows from it and the block (`_chunk_rows`). With two slots a
#: chip (one being packed, one in flight) the ring holds 2.1 GB of
#: host memory on a 2x2 grid, whatever the matrix
STAGE_CHUNK_BYTES = 256 << 20

#: the mesh's ring of reused host staging buffers (core/staging.py)
_ring = StageRing("grid")


def _chunk_rows(block: np.ndarray) -> int:
    """Rows of one staged chunk of `block`: the block cut into the
    fewest equal row chunks of at most `STAGE_CHUNK_BYTES`, the height
    rounded up to the 8 sublanes of a device tile."""
    m = block.shape[0]
    chunks = max(1, ceil_div(block.nbytes, STAGE_CHUNK_BYTES))
    return min(m, ceil_div(ceil_div(m, chunks), 8) * 8)


def _send_block(block: np.ndarray, dev) -> list:
    """`block` of a host array on `dev`, as the row chunks it was sent
    in (one for a contiguous block, which the runtime takes as it is;
    a strided one goes through the ring, chunk by chunk, and the pack
    of a chunk runs under the transfer of the one before). Does not
    wait for the transfers: the ring does, before it rewrites a
    slot."""
    from ..obs import events as obs_events, metrics as obs_metrics
    if block.flags.c_contiguous:
        with obs_events.span("grid::put", cat="shard", device=dev.id):
            return [jax.device_put(block, dev)]
    copies, rows, out = aliases_host(dev), _chunk_rows(block), []
    for r0 in range(0, block.shape[0], rows):
        x = block[r0:r0 + rows]
        nbytes = int(x.nbytes)
        slot, reused = _ring.acquire(nbytes)
        arr = None
        try:
            with obs_events.span("grid::pack", cat="shard",
                                 bytes=nbytes, device=dev.id):
                packed = slot.buf[:nbytes].view(x.dtype).reshape(x.shape)
                np.copyto(packed, x)
            with obs_events.span("grid::put", cat="shard",
                                 device=dev.id):
                arr = jax.device_put(
                    np.array(packed) if copies else packed, dev)
        finally:
            _ring.release(slot, arr)
        obs_metrics.inc("grid.stage_reuse_bytes" if reused
                        else "grid.stage_fresh_bytes", nbytes)
        out.append(arr)
    return out


@functools.lru_cache(maxsize=None)
def _assemble_program(sharding: NamedSharding):
    """Each device's row chunks into its block, all devices in one
    program and nothing crossing between them: one jitted function a
    sharding (and one compiled program a shape and chunk count)."""
    def blocks(chunks):
        return jnp.concatenate(chunks, axis=0)
    return jax.jit(shard_map(blocks, sharding.mesh, (sharding.spec,),
                             sharding.spec), out_shardings=sharding)


def _place_host(a: np.ndarray, sharding: NamedSharding) -> jax.Array:
    """The host array `a` under `sharding`, each addressable device
    sent its own block (`_send_block`), the devices side by side."""
    devs, where = zip(
        *sharding.addressable_devices_indices_map(a.shape).items())
    blocks = [np.asarray(a[idx]) for idx in where]
    if len(blocks) == 1 or all(b.flags.c_contiguous for b in blocks):
        sent = list(map(_send_block, blocks, devs))
    else:
        _ring.reserve(2 * len(blocks))
        with cf.ThreadPoolExecutor(len(blocks), "grid-place") as pool:
            sent = list(pool.map(_send_block, blocks, devs))
    if len(sent[0]) == 1:
        return jax.make_array_from_single_device_arrays(
            a.shape, sharding, [chunks[0] for chunks in sent])
    # chunk k of every device as one array spread like the whole, so
    # that one program makes every device's block from its own chunks
    over = a.shape[0] // blocks[0].shape[0]
    parts = [jax.make_array_from_single_device_arrays(
        (over * ck[0].shape[0],) + a.shape[1:], sharding, list(ck))
        for ck in zip(*sent, strict=True)]
    return _assemble_program(sharding)(parts)


def place(a, grid: ProcessGrid,
          shape: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """`a` on `grid` as P('p','q'), zero-padded up to `shape`; what the
    matrix constructors' ``grid=`` argument calls. Each device is sent
    its own block of `a` (from host memory when `a` is a numpy array:
    the whole is never on one chip; the padding is added on the mesh),
    and the call returns when every shard is there.

    How a host array's blocks travel (PR 28). Handed whole to
    ``jax.device_put(a, sharding)``, the runtime linearizes each chip's
    strided block itself, into fresh pages of its own: four 2.4 GB
    blocks of a 49152 x 49152 f32 matrix reached a 2x2 grid at 1.8
    GB/s together (ledger, PR 27), while a contiguous chunk from a
    reused, already touched host buffer reaches one chip at the link's
    speed (tools/place_probe.py; PERF.md, PR 28). So a block that is
    C-contiguous (a q = 1 grid's row blocks, a 1 x 1 grid, a vector)
    goes to its device as it is, and a strided one is cut into row
    chunks of about `STAGE_CHUNK_BYTES`, each copied into a slot of
    the mesh's ring of reused host buffers (core/staging.py, the stream
    engine's class) and handed over from there, a thread a device, the
    pack of one chunk under the transfer of the one before. One
    program then makes each device's block from its chunks, on the
    device. The result is bitwise what ``device_put`` gives, under the
    same sharding. Host memory: two slots a device. Device memory: a
    block and its chunks, until the block is made. A device array goes
    as before.

    With the obs bus on, the placement is the span `grid::place`
    (bytes, devices; open until the shards are ready); a host array's
    hand-overs are `matrix::h2d` inside it, and in that each chunk's
    copy is `grid::pack` (bytes, device) and each hand-over `grid::put`
    (device), on the device's staging thread. The bytes handed to the
    devices add to the counter `grid.h2d_bytes`: `a.nbytes` when the
    grid's axes divide a's dimensions, more where a block is sent to
    several devices. The bytes copied into a touched slot add to
    `grid.stage_reuse_bytes`, those into a slot's first or regrown
    buffer to `grid.stage_fresh_bytes`; contiguous blocks are in
    neither. What the process's resident set grows by under a host
    array's `matrix::h2d` (the staging threads' packs into pages
    nothing has touched, and whatever the runtime maps meanwhile)
    adds to `grid.pack_touched_bytes`."""
    from ..obs import events as obs_events, metrics as obs_metrics
    sharding = fitted_sharding(a.shape, grid)
    with obs_events.span("grid::place", cat="staging",
                         bytes=int(a.nbytes), devices=grid.nprocs):
        if not isinstance(a, np.ndarray):
            out = jax.device_put(a, sharding)
        else:
            with obs_events.span("matrix::h2d", cat="staging",
                                 bytes=int(a.nbytes),
                                 devices=grid.nprocs,
                                 resident="grid.pack_touched_bytes"):
                out = _place_host(a, sharding)
            if obs_events.enabled():
                obs_metrics.inc("grid.h2d_bytes", sum(
                    s.data.nbytes for s in out.addressable_shards))
        if shape is not None and tuple(shape) != out.shape:
            out = _pad_program(grid)(out, tuple(shape))
        out = jax.block_until_ready(out)
        _ring.sweep()       # the chunks are consumed: let them go
        return out


def panel_spec() -> P:
    """Tall-skinny panels: rows over the whole mesh (the reference's
    panel-column rank set, getrf.cc:91)."""
    return P(("p", "q"), None)
