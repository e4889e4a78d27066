"""TiledMatrix — the central distributed-matrix abstraction.

TPU-native re-design of the reference's Tile / MatrixStorage / BaseMatrix
stack (include/slate/Tile.hh:129, internal/MatrixStorage.hh:151,
BaseMatrix.hh). The reference keeps a hash-map of individually-allocated
mb×nb tiles with per-device MOSI coherency states and explicit MPI
broadcasts; under XLA none of that machinery survives — the compiler owns
residency and communication. What survives is the *semantic* layer:

- tile-aligned storage: canonical form is a zero-padded dense 2D jax array
  whose padded dims are multiples of the tile sizes (mb, nb). Tiles are a
  logical indexing concept (``tile(i, j)`` is a static slice), which keeps
  every op a large, MXU-friendly dense op while preserving the reference's
  blocked-algorithm structure.
- transpose-by-flag (reference BaseMatrix op_): ``transpose()`` /
  ``conj_transpose()`` flip a metadata flag; data is shared. XLA fuses the
  eventual physical transpose into consumers.
- structure flags: uplo/diag and a MatrixType tag replace the reference's
  12-class C++ hierarchy's dispatch role; thin Python subclasses in
  ``matrix.py`` give the same construction vocabulary.
- ``sub()`` / ``slice()`` views (BaseMatrix.hh:104-122): functional slices
  rather than aliasing views — XLA turns them into zero-copy fusion in
  practice.

Padding invariant: out-of-range rows/cols of ``data`` are zero. Routines
that need a nonsingular padded diagonal (trsm, potrf, getrf) locally patch
the padded diagonal block to identity; helpers here provide that.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import events as obs_events
from .enums import Diag, MatrixType, Op, Uplo
from .exceptions import DimensionError, slate_assert


_warned_downcast = False
#: what a host array's `matrix::h2d` and `matrix::h2d_ready` spans
#: share (counted with the obs bus on only)
_upload_seq = itertools.count()


def _asarray_warn_downcast(a, grid=None, shape=None):
    """jnp.asarray with the one-time float64-downcast warning: with jax
    x64 disabled, double input silently becomes single, which changes
    solver accuracy — every TiledMatrix constructor funnels through
    this so the warning cannot be bypassed. With a `grid` the array
    goes to the mesh instead, block by block and padded to `shape`
    there (parallel/sharding.place)."""
    orig_dtype = getattr(a, "dtype", None)
    if grid is not None:
        from ..parallel.sharding import place
        out = place(a if hasattr(a, "nbytes") else np.asarray(a), grid,
                    shape)
    elif obs_events.enabled() and isinstance(a, np.ndarray):
        # a host array: the upload a solve's wall contains.
        # `matrix::h2d` is the hand-over to the runtime; the transfer
        # itself is not waited for here: `matrix::h2d_ready` (same
        # `seq`) stays open on the obs-ready thread until it is over.
        # Under a profiler session a large one launches a clock beacon
        # first, from this thread, so that it runs ahead of every
        # program that waits for `a`
        seq, nbytes = next(_upload_seq), int(a.nbytes)
        obs_events.clock_beacon(nbytes)
        with obs_events.span("matrix::h2d", cat="staging",
                             bytes=nbytes, seq=seq):
            out = jnp.asarray(a)
        obs_events.watch_ready("matrix::h2d_ready", out, bytes=nbytes,
                               seq=seq)
    else:
        out = jnp.asarray(a)
    global _warned_downcast
    if (not _warned_downcast and orig_dtype is not None
            and orig_dtype in (np.float64, np.complex128)
            and out.dtype != orig_dtype):
        import warnings
        warnings.warn(
            "TiledMatrix: float64 input downcast to float32 because "
            "jax x64 is disabled; enable it with "
            "jax.config.update('jax_enable_x64', True) or pass "
            "float32 data (warning shown once)", UserWarning,
            stacklevel=3)
        _warned_downcast = True
    return out


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (x >= 1)."""
    p = 1
    while p < x:
        p *= 2
    return p


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class TiledMatrix:
    """A tiled, padded, optionally-sharded matrix.

    data : (m_pad, n_pad) jax array, m_pad = mt*mb, n_pad = nt*nb,
           zero-padded outside [:m, :n]. If ``op != NoTrans`` the *stored*
           array is the un-transposed original; logical shape is (n, m).

    Non-uniform tiles (reference BaseMatrix.hh:80-101 per-index
    tileMb/tileNb lambdas, examples/ex13_non_uniform_block_size.cc):
    optional ``rb``/``cb`` tuples of tile BOUNDARY offsets
    (0 = b_0 < b_1 < ... < b_mt = m) override the uniform grid for
    tile indexing — tileMb/tileNb, tile(), sub() follow the
    boundaries. On TPU the compute layout stays one dense array (XLA
    wants uniform blocks; the boundaries are static Python metadata,
    free at trace time); ``uniform()`` re-tiles to the uniform padded
    layout the factorization drivers use. Non-uniform storage is
    EXACT (m, n) — no padding — so to_dense/gemm/_store work
    unchanged.
    """

    data: jax.Array
    m: int
    n: int
    mb: int
    nb: int
    mtype: MatrixType = MatrixType.General
    uplo: Uplo = Uplo.General
    op: Op = Op.NoTrans
    diag: Diag = Diag.NonUnit
    kl: int = -1          # band lower bandwidth (band types only)
    ku: int = -1          # band upper bandwidth
    rb: Optional[Tuple[int, ...]] = None   # non-uniform row boundaries
    cb: Optional[Tuple[int, ...]] = None   # non-uniform col boundaries

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        aux = (self.m, self.n, self.mb, self.nb, self.mtype, self.uplo,
               self.op, self.diag, self.kl, self.ku, self.rb, self.cb,
               type(self))
        return (self.data,), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        (data,) = children
        m, n, mb, nb, mtype, uplo, op, diag, kl, ku, rb, cb, klass = aux
        return klass(data=data, m=m, n=n, mb=mb, nb=nb, mtype=mtype,
                     uplo=uplo, op=op, diag=diag, kl=kl, ku=ku,
                     rb=rb, cb=cb)

    # -- basic geometry ----------------------------------------------------
    @property
    def mt(self) -> int:
        """Number of tile rows of the *stored* array (reference mt())."""
        if self.rb is not None:
            return len(self.rb) - 1
        return self.data.shape[0] // self.mb

    @property
    def nt(self) -> int:
        if self.cb is not None:
            return len(self.cb) - 1
        return self.data.shape[1] // self.nb

    @property
    def shape(self) -> Tuple[int, int]:
        """Logical (op-resolved) shape."""
        if self.op is Op.NoTrans:
            return (self.m, self.n)
        return (self.n, self.m)

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def is_complex(self) -> bool:
        return jnp.issubdtype(self.data.dtype, jnp.complexfloating)

    def tileMb(self, i: int) -> int:
        """Rows of tile i (reference tileMb) — ragged last tile, or the
        per-index boundary span when non-uniform."""
        if self.rb is not None:
            return self.rb[i + 1] - self.rb[i]
        return min(self.mb, self.m - i * self.mb)

    def tileNb(self, j: int) -> int:
        if self.cb is not None:
            return self.cb[j + 1] - self.cb[j]
        return min(self.nb, self.n - j * self.nb)

    # -- construction ------------------------------------------------------
    @classmethod
    def from_dense(cls, a, mb: int = 256, nb: Optional[int] = None,
                   mtype: MatrixType = MatrixType.General,
                   uplo: Uplo = Uplo.General, diag: Diag = Diag.NonUnit,
                   kl: int = -1, ku: int = -1, grid=None
                   ) -> "TiledMatrix":
        """Wrap a dense array, padding to tile multiples (reference
        fromLAPACK, Matrix.hh:58). With a ProcessGrid as `grid` the
        storage is laid over the mesh as P('p','q'), each device sent
        its own block straight from `a`: the way to build a matrix
        that no single device could hold (parallel/sharding.place).

        Double-precision input with jax x64 disabled is downcast to
        single by jax; that silently changes solver accuracy, so the
        first occurrence warns (enable x64 via
        ``jax.config.update("jax_enable_x64", True)`` — CPU mesh only;
        TPU has no native f64 path — or pass f32 data explicitly)."""
        if np.ndim(a) != 2:
            raise DimensionError(f"expected 2D, got {np.shape(a)}")
        nb = nb or mb
        m, n = np.shape(a)
        mp, np_ = round_up(max(m, 1), mb), round_up(max(n, 1), nb)
        a = _asarray_warn_downcast(a, grid, (mp, np_))
        if grid is None:
            a = jnp.pad(a, ((0, mp - m), (0, np_ - n)))
        return cls(data=a, m=m, n=n, mb=mb, nb=nb, mtype=mtype, uplo=uplo,
                   diag=diag, kl=kl, ku=ku)

    @staticmethod
    def _boundaries(extent: int, sizes) -> Tuple[int, ...]:
        """Evaluate a per-index tile-size spec (a func.TileSizeFunc
        lambda or a sequence of sizes) into boundary offsets covering
        `extent` exactly."""
        bounds = [0]
        if callable(sizes):
            i = 0
            while bounds[-1] < extent:
                s = int(sizes(i))
                slate_assert(s > 0, f"tile size func gave {s} at {i}")
                bounds.append(min(bounds[-1] + s, extent))
                i += 1
        else:
            for s in sizes:
                s = int(s)
                slate_assert(s > 0, f"tile sizes must be positive, "
                                    f"got {s}")
                bounds.append(bounds[-1] + s)
            slate_assert(bounds[-1] == extent,
                         f"tile sizes sum to {bounds[-1]}, "
                         f"expected {extent}")
        return tuple(bounds)

    @classmethod
    def from_func(cls, a, tileMb, tileNb=None,
                  mtype: MatrixType = MatrixType.General,
                  uplo: Uplo = Uplo.General,
                  diag: Diag = Diag.NonUnit) -> "TiledMatrix":
        """Wrap a dense array with NON-UNIFORM tiles driven by per-index
        size lambdas or explicit size lists (reference
        BaseMatrix.hh:80-101 lambda constructors,
        examples/ex13_non_uniform_block_size.cc; func.uniform_blocksize
        is the uniform special case). Storage stays one exact dense
        array — the boundaries are static indexing metadata (free at
        trace time), which is the TPU-native shape of this feature:
        XLA's layout does not change with the logical tiling."""
        a = _asarray_warn_downcast(a)
        if a.ndim != 2:
            raise DimensionError(f"expected 2D, got {a.shape}")
        m, n = a.shape
        if m == 0 or n == 0:
            raise DimensionError(
                f"from_func: zero-sized matrix {a.shape} not tileable")
        rb = cls._boundaries(m, tileMb)
        cb = cls._boundaries(n, tileNb if tileNb is not None else tileMb)
        return cls(data=a, m=m, n=n,
                   mb=max(b - a_ for a_, b in zip(rb, rb[1:])),
                   nb=max(b - a_ for a_, b in zip(cb, cb[1:])),
                   mtype=mtype, uplo=uplo, diag=diag, rb=rb, cb=cb)

    def uniform(self) -> "TiledMatrix":
        """Re-tile to the uniform padded layout (mb x nb) the
        factorization drivers assume; no-op if already uniform."""
        if self.rb is None and self.cb is None:
            return self
        r = self.resolve()
        return TiledMatrix.from_dense(
            r.data[:r.m, :r.n], r.mb, r.nb, mtype=r.mtype, uplo=r.uplo,
            diag=r.diag, kl=r.kl, ku=r.ku)

    @classmethod
    def zeros(cls, m: int, n: int, mb: int = 256, nb: Optional[int] = None,
              dtype=jnp.float32, grid=None, **kw) -> "TiledMatrix":
        nb = nb or mb
        shape = (round_up(max(m, 1), mb), round_up(max(n, 1), nb))
        where = None
        if grid is not None:
            from ..parallel.sharding import fitted_sharding
            where = fitted_sharding(shape, grid)
        data = jnp.zeros(shape, dtype, device=where)
        return cls(data=data, m=m, n=n, mb=mb, nb=nb, **kw)

    def emptyLike(self, m: Optional[int] = None, n: Optional[int] = None,
                  dtype=None) -> "TiledMatrix":
        """Reference emptyLike (Matrix.hh:117) — preserves structure
        metadata (mtype/uplo/diag/band)."""
        m = self.m if m is None else m
        n = self.n if n is None else n
        return TiledMatrix.zeros(
            m, n, self.mb, self.nb, dtype or self.dtype, mtype=self.mtype,
            uplo=self.uplo, diag=self.diag, kl=self.kl, ku=self.ku)

    # -- transpose-by-flag -------------------------------------------------
    def transpose(self) -> "TiledMatrix":
        new_op = {Op.NoTrans: Op.Trans, Op.Trans: Op.NoTrans,
                  Op.ConjTrans: Op.NoTrans}[self.op]
        # conj_trans -> trans composition would need a conj; handle exactly:
        if self.op is Op.ConjTrans:
            return dataclasses.replace(self, data=jnp.conj(self.data),
                                       op=Op.NoTrans)
        return dataclasses.replace(self, op=new_op)

    def conj_transpose(self) -> "TiledMatrix":
        new = {Op.NoTrans: Op.ConjTrans, Op.ConjTrans: Op.NoTrans,
               Op.Trans: Op.NoTrans}[self.op]
        if self.op is Op.Trans:
            return dataclasses.replace(self, data=jnp.conj(self.data),
                                       op=Op.NoTrans)
        return dataclasses.replace(self, op=new)

    @property
    def T(self) -> "TiledMatrix":
        return self.transpose()

    @property
    def H(self) -> "TiledMatrix":
        return self.conj_transpose()

    # -- views -------------------------------------------------------------
    def tile(self, i: int, j: int) -> jax.Array:
        """Tile (i, j) of the stored array, including padding (static
        indices; reference BaseMatrix::at). Non-uniform tiles slice at
        their boundary offsets (exact size, no padding)."""
        r0 = self.rb[i] if self.rb is not None else i * self.mb
        r1 = self.rb[i + 1] if self.rb is not None else (i + 1) * self.mb
        c0 = self.cb[j] if self.cb is not None else j * self.nb
        c1 = self.cb[j + 1] if self.cb is not None else (j + 1) * self.nb
        return self.data[r0:r1, c0:c1]

    def sub(self, i1: int, i2: int, j1: int, j2: int) -> "TiledMatrix":
        """Tile-index submatrix [i1..i2] x [j1..j2] inclusive (reference
        sub(), BaseMatrix.hh:104). Returns a functional copy-on-write
        view; transposed views resolve first (the reference indexes
        through the op flag, BaseMatrix.hh tileIndex logic — here the
        transpose materializes, which XLA fuses). Non-uniform views
        keep their boundary structure (re-based to the sub's origin)."""
        base = self if self.op is Op.NoTrans else self.resolve()
        if base.rb is not None or base.cb is not None:
            rb = base.rb or tuple(
                min(k * base.mb, base.m)
                for k in range(base.mt + 1))
            cb = base.cb or tuple(
                min(k * base.nb, base.n)
                for k in range(base.nt + 1))
            data = base.data[rb[i1]:rb[i2 + 1], cb[j1]:cb[j2 + 1]]
            new_rb = tuple(b - rb[i1] for b in rb[i1:i2 + 2])
            new_cb = tuple(b - cb[j1] for b in cb[j1:j2 + 2])
            return dataclasses.replace(
                base, data=data, m=new_rb[-1], n=new_cb[-1],
                mtype=MatrixType.General, uplo=Uplo.General,
                rb=new_rb, cb=new_cb)
        mm = min((i2 + 1) * base.mb, base.m) - i1 * base.mb
        nn = min((j2 + 1) * base.nb, base.n) - j1 * base.nb
        data = base.data[i1 * base.mb:(i2 + 1) * base.mb,
                         j1 * base.nb:(j2 + 1) * base.nb]
        return dataclasses.replace(base, data=data, m=mm, n=nn,
                                   mtype=MatrixType.General,
                                   uplo=Uplo.General)

    def slice(self, row1: int, row2: int, col1: int, col2: int
              ) -> "TiledMatrix":
        """Element-index submatrix [row1..row2] x [col1..col2] inclusive
        (reference slice(), BaseMatrix.hh:122). Re-tiles from element 0.

        Slices the *stored* data (not the densified matrix), preserving
        structure flags. For structured types the slice must be
        diagonal-aligned (row1 == col1), matching the reference's
        constraint on trapezoid slices."""
        r = self.resolve()
        if r.mtype is not MatrixType.General:
            slate_assert(row1 == col1,
                         "slice of structured matrix must be "
                         "diagonal-aligned (row1 == col1)")
        d = r.data[:r.m, :r.n][row1:row2 + 1, col1:col2 + 1]
        return TiledMatrix.from_dense(d, r.mb, r.nb, mtype=r.mtype,
                                      uplo=r.uplo, diag=r.diag,
                                      kl=r.kl, ku=r.ku)

    # -- densification -----------------------------------------------------
    def resolve(self) -> "TiledMatrix":
        """Materialize the op flag into the data (XLA fuses the transpose).

        Structure flags travel with the resolve: a transposed Lower
        triangular view resolves to an Upper triangular matrix."""
        if self.op is Op.NoTrans:
            return self
        d = self.data.T
        if self.op is Op.ConjTrans:
            d = jnp.conj(d)
        return dataclasses.replace(
            self, data=d, m=self.n, n=self.m, mb=self.nb, nb=self.mb,
            op=Op.NoTrans, uplo=self.uplo.flip(), kl=self.ku, ku=self.kl,
            rb=self.cb, cb=self.rb)

    def to_dense(self) -> jax.Array:
        """The mathematical (logical) matrix as a dense array: applies op,
        mirrors symmetric/Hermitian triangles, zeroes the unstored triangle
        of triangular/trapezoid types, applies unit diagonals and band
        masks."""
        r = self.resolve()
        a = r.data[:r.m, :r.n]
        mt = self.mtype
        if mt in (MatrixType.Symmetric, MatrixType.Hermitian,
                  MatrixType.HermitianBand):
            ii = jnp.arange(r.m)[:, None]
            jj = jnp.arange(r.n)[None, :]
            if r.uplo is Uplo.Lower:
                tri = jnp.where(ii >= jj, a, 0)
            else:
                tri = jnp.where(ii <= jj, a, 0)
            other = tri.T if mt is MatrixType.Symmetric else jnp.conj(tri.T)
            diag_part = jnp.diagonal(tri)
            if mt in (MatrixType.Hermitian, MatrixType.HermitianBand):
                diag_part = jnp.real(diag_part).astype(a.dtype)
            a = tri + other - jnp.diag(diag_part)
        elif mt in (MatrixType.Triangular, MatrixType.Trapezoid,
                    MatrixType.TriangularBand):
            ii = jnp.arange(r.m)[:, None]
            jj = jnp.arange(r.n)[None, :]
            if r.uplo is Uplo.Lower:
                a = jnp.where(ii >= jj, a, 0)
            else:
                a = jnp.where(ii <= jj, a, 0)
            if r.diag is Diag.Unit:
                k = min(r.m, r.n)
                a = a.at[jnp.arange(k), jnp.arange(k)].set(1)
        if mt in (MatrixType.GeneralBand, MatrixType.TriangularBand,
                  MatrixType.HermitianBand):
            kl = r.kl if r.kl >= 0 else r.m
            ku = r.ku if r.ku >= 0 else r.n
            if mt is MatrixType.HermitianBand:
                # after mirroring, bandwidth kd applies on both sides
                kl = ku = max(kl, ku)
            ii = jnp.arange(r.m)[:, None]
            jj = jnp.arange(r.n)[None, :]
            a = jnp.where((jj - ii <= ku) & (ii - jj <= kl), a, 0)
        return a

    # -- numpy interop for tests ------------------------------------------
    def to_numpy(self) -> np.ndarray:
        return np.asarray(self.to_dense())

    def __repr__(self) -> str:
        return (f"TiledMatrix({self.shape[0]}x{self.shape[1]}, "
                f"tiles {self.mb}x{self.nb}, {self.mtype.name}, "
                f"uplo={self.uplo.name}, op={self.op.name}, "
                f"dtype={self.data.dtype})")


def pad_diag_identity(data: jax.Array, m: int, n: int) -> jax.Array:
    """Set the padded part of the diagonal to 1 so padded triangular solves
    and factorizations stay nonsingular. data is (m_pad, n_pad), logical
    (m, n)."""
    mp, np_ = data.shape
    if min(mp, np_) <= min(m, n):
        return data                   # no padded diagonal to touch
    k = min(mp, np_)
    idx = jnp.arange(k)
    cur = data[idx, idx]
    ones = jnp.ones((k,), data.dtype)
    newdiag = jnp.where(idx < min(m, n), cur, ones)
    return data.at[idx, idx].set(newdiag)
