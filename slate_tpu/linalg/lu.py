"""LU family (reference src/getrf.cc, gesv.cc, getrs.cc, getri.cc,
gesv_mixed.cc, gesv_mixed_gmres.cc, gesv_rbt.cc, gbtrf/gbtrs/gbsv;
SURVEY §3.3, §2.6).

TPU-native design. The reference's LU panel is a latency-bound
host-threaded kernel with MPI_Allreduce(MAXLOC) pivot search inside
(Tile_getrf.hh:162-320). Here the panel is a `lax.fori_loop` over columns
on the full distributed panel: pivot search is a masked argmax (XLA
reduces over the mesh), the row swap is a two-row permutation, and the
rank-1 update is a vector outer product — all compiled into one program.
Block steps (panel -> laswp -> U-row trsm -> trailing gemm) are statically
unrolled like the reference's task loop; XLA overlaps the trailing gemm
with the next panel the way Option::Lookahead does.

Pivots are a flat int32 vector of global row indices (LAPACK ipiv
convention, 0-based) — the reference's Pivots = vector<vector<Pivot>>
(types.hh:~98) collapses to this under single-program semantics.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.enums import Diag, MatrixType, Op, Side, Uplo
from ..core.exceptions import slate_assert
from ..core.methods import MethodFactor, MethodLU, MethodLUPanel
from ..core.options import Option, OptionsLike, get_option
from ..core.tiles import TiledMatrix, ceil_div, pad_diag_identity
from ..obs import events as obs_events
from ..obs import metrics as obs_metrics
from ..obs.events import instrument_driver
from ..resil import guard as _rguard
from .blas3 import _store, trsm
from .blocked import assemble_packed, invert_triangular


class LUFactors(NamedTuple):
    """Packed L\\U factor (unit-lower L below diag, U on/above) plus
    pivots, mirroring LAPACK/SLATE in-place packing. info follows the
    LAPACK getrf convention (0 ok; k > 0: U(k,k) exactly zero, solve
    would divide by zero) — the reference reduces it across ranks
    (internal_reduce_info.cc); here the diagonal scan is a global
    reduction under SPMD."""
    LU: TiledMatrix
    pivots: jax.Array      # (min(m,n)_pad,) int32 global row indices
    info: Optional[jax.Array] = None   # () int32
    #: True when produced by the windowed band gbtrf, whose L blocks
    #: are not retroactively permuted across blocks — such factors must
    #: be solved by gbtrs's interleaved sweeps, never by plain getrs
    band: bool = False
    #: the swaps composed to one permutation of the padded rows (the
    #: factor is that of A[perm]), where the route that made the
    #: factor composed it on the way: the lo carry form does, and a
    #: mixed solve then replays no swap sequence
    perm: Optional[jax.Array] = None


# -- pivot machinery ------------------------------------------------------

def _compose_swaps(piv: jax.Array, m: int) -> jax.Array:
    """Turn a sequence of row swaps (j <-> piv[j]) into one permutation
    of range(m) (LAPACK laswp semantics). XLA's native
    lu_pivots_to_permutation does exactly this composition (and is the
    form its own LU custom call emits) — far cheaper under jit than a
    fori_loop of scalar exchanges on TPU."""
    return jax.lax.linalg.lu_pivots_to_permutation(
        piv.astype(jnp.int32), m)


def _permute_rows(x: jax.Array, perm: jax.Array) -> jax.Array:
    """Row gather with a sub-f32 detour: this libtpu's gather fusion
    on (2,1)-packed bf16 blocks overflows its scoped-vmem budget once
    the block is big enough (measured: every bf16 getrf config at
    n=8192 dies in compile with "Scoped allocation with size 16.39M
    and limit 16.00M ... should not be possible, please file a bug
    against XLA"; n<=4096 compiles). A pure gather is value-exact
    under the f32 round-trip, and the optimization barriers keep XLA
    from folding the casts back into one bf16 gather fusion."""
    if x.dtype.itemsize >= 4:
        return x[perm]
    up = jax.lax.optimization_barrier(x.astype(jnp.float32))
    return jax.lax.optimization_barrier(up[perm]).astype(x.dtype)


def apply_pivots(pivots: jax.Array, B: TiledMatrix,
                 forward: bool = True) -> TiledMatrix:
    """Apply row swaps to B (reference internal::permuteRows,
    internal_swap.cc:82-110). pivots are global swap targets: row j is
    swapped with row pivots[j], in order (reversed if not forward)."""
    r = B.resolve()
    mp = r.data.shape[0]
    if pivots.shape[0] > mp:
        # A's padded length exceeds B's: entries past B's logical rows
        # are identity swaps (targets < n <= mp), truncation is exact
        pivots = pivots[:mp]
    perm = _compose_swaps(pivots, mp)
    if not forward:
        perm = jnp.argsort(perm)
    return dataclasses.replace(r, data=_permute_rows(r.data, perm))


# -- panel ----------------------------------------------------------------

#: (m, w, dtype) panels whose fori fallback was already surfaced —
#: the obs instant fires once per shape, not once per trace step
_FORI_FALLBACK_SEEN: set = set()


def _first_sighting(seen: set, key) -> bool:
    """True once a key, and only with obs on: the one-shot is not
    consumed while obs is off, so the user who enables obs to diagnose
    a slow panel still sees the shape's first traced fall-back."""
    if key in seen or not obs_events.enabled():
        return False
    seen.add(key)
    return True


def _surface_fori_fallback(m: int, w: int, dtype) -> None:
    """ISSUE 6 satellite: the fori fallback used to be silent — now
    the first panel of each (m, w, dtype) publishes an obs instant
    carrying WHY the fused kernels rejected it (dtype / height /
    width / platform, pallas_kernels.lu_panel_reject_reason), so a
    trace of a slow getrf shows the panel route and its reason."""
    if not _first_sighting(_FORI_FALLBACK_SEEN, (m, w, str(dtype))):
        return
    from ..ops import pallas_kernels as pk
    obs_events.instant("getrf.panel_fori_fallback", cat="kernel",
                       m=m, w=w, dtype=str(dtype),
                       reason=pk.lu_panel_reject_reason(m, w, dtype))


def _lu_panel(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Partial-pivot LU of a (m, w) panel. Returns (packed LU, local
    pivot swap indices (w,)).

    Route arbitration (MethodLUPanel): a MEASURED tune-cache entry
    ('method_lu_panel' per (op, size, dtype) bucket) wins, validated
    against the hard gates; a cold cache resolves to the frozen chain
    — by measurement (PERF.md), XLA's native LU where its dtype
    support and height limit allow (v5e, 4096x256: 0.77 ms vs 1.19 ms
    for the round-3 fused panel; tall-panel per-column cost ~3 µs,
    width-independent), the fused Pallas kernel for TPU bf16 panels
    (the mixed-precision lo path), `lu_panel_blocked` for panels of
    a native dtype above the native height, and the masked fori_loop
    (lu_panel_fori) for everything else. The block-recursive
    pallas_rec route (ops/pallas_kernels.lu_panel_rec) enters here
    when probed faster — one winning entry lifts every LU consumer
    (getrf, getrf_tntpiv nomination, band, indefinite, ooc, batch)."""
    from ..ops import pallas_kernels as pk
    m, w = a.shape
    method = MethodLUPanel.resolve(m, w, a.dtype)
    if method is MethodLUPanel.PallasRec:
        fused = pk.lu_panel_rec(a)
        if fused is not None:
            return fused
        method = MethodLUPanel.cold_default(m, w, a.dtype)
    if method is MethodLUPanel.Pallas:
        fused = pk.lu_panel(a)
        if fused is not None:
            return fused
        method = MethodLUPanel.Fori
    if method is MethodLUPanel.Native:
        lu, piv, _perm = jax.lax.linalg.lu(a)
        return lu, piv.astype(jnp.int32)
    if method is MethodLUPanel.Blocked:
        return lu_panel_blocked(a, _blocked_ib(w, m, a.dtype))[:2]
    _surface_fori_fallback(m, w, a.dtype)
    return lu_panel_fori(a)


def lu_panel_fori(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The masked fori_loop panel kernel: per column, argmax pivot
    search over masked magnitudes, two-row swap, rank-1 update —
    true partial pivoting with no custom call underneath. This is the
    panel route the BATCH layer vmaps (slate_tpu/batch/drivers.py):
    PERF.md Round-4 measured the native LU custom call serializing
    over batch, while this kernel's masked argmax/outer-product body
    batches into full-width ops under vmap."""
    m, w = a.shape
    rows = jnp.arange(m)

    def body(j, carry):
        a, piv = carry
        col = a[:, j]
        mag = jnp.where(rows >= j, jnp.abs(col), -jnp.inf)
        p = jnp.argmax(mag).astype(jnp.int32)
        piv = piv.at[j].set(p)
        # swap rows j <-> p
        rowj, rowp = a[j], a[p]
        a = a.at[j].set(rowp).at[p].set(rowj)
        pivval = a[j, j]
        safe = jnp.where(pivval == 0, jnp.ones((), a.dtype), pivval)
        mults = jnp.where(rows > j, a[:, j] / safe, 0)
        a = a.at[:, j].set(jnp.where(rows > j, mults, a[:, j]))
        # rank-1 update of the columns to the right
        cols = jnp.arange(w)
        urow = jnp.where(cols > j, a[j], 0)
        a = a - jnp.outer(mults, urow)
        return a, piv

    piv0 = jnp.zeros((w,), jnp.int32)
    a, piv = jax.lax.fori_loop(0, w, body, (a, piv0))
    return a, piv


#: base-block width of `lu_panel_blocked` under the XLA column loop:
#: the (ib, m) block one column step rewrites. Read on the chip at
#: 16384 x 1024 (PR 42): a block costs 0.29 ms (the solve against the
#: factored columns, the matmul under it) and a column 10.7 us
#: whatever ib is from 16 to 64 (its operations are latency-bound):
#: 30.7 ms a panel at 16, 20.2 at 32, 15.4 at 64, 15.2 at 128, where a
#: pass over the block is 8 MB. Where the VMEM kernel runs the column
#: recurrence the base block is twice as wide (`_blocked_ib`). Read
#: on the chip (PR 50, call 2; ms a panel, kernel at ib 64 / 128 /
#: the XLA loop at 64): (9216, 1024) 6.54 / 5.24 / 11.72, (16384,
#: 1024) 9.46 / 7.86 / 15.32, (32768, 512) 6.64 / 6.08 / 10.82,
#: (36864, 512) 7.29 / 6.83 / 11.76, (49152, 512) 9.88 / 10.14 /
#: 26.05, (49152, 1024) 27.3 / 21.8 / 36.9. A column step of the
#: kernel is 2.3 us at 9216 rows, 3.1 at 16384, 4.8 at 32768 and 6.6
#: at 49152 at ib 64 (2.5, 3.8, 6.8, 9.8 at 128: a step updates twice
#: the sublane groups; with the NaN-proof integer search of the
#: second round, call A: 2.3, 3.1, 5.1, 6.9 and 2.6, 3.9, 7.0,
#: 10.2), so what is left of a block is its XLA part,
#: 0.39 ms at (16384, 1024) and 0.81 at (49152, 512) at ib 64, 0.50
#: and 1.28 at 128: half as many blocks win except at the tallest
LU_BLOCKED_IB = 64


#: widest panel the carry form hands `lu_panel_blocked` above the
#: native height (`_carry_nb`). Read on the chip (PR 48, PERF.md
#: section 6) on `ooc._lu_panel_factor`'s (32768, 4096) operand, one
#: launch at 32768 / at 16384 rows: nb 512 0.1123 / 0.0648 s, 256
#: 0.1204 / 0.0694, 1024 0.1281 / 0.0694, all at ib 64 (ib 128:
#: 0.1263, 0.1373, 0.1220 at 32768; ib 32: 0.1284, 0.1280, 0.1668);
#: the fori form it replaces, in blocks of 256: 0.2270 at every height.
#: A narrower panel pays one more gather of the rest and one more
#: update a step, a wider one reads more of a[w:] at every block.
#: Read again with the VMEM kernel (PR 50, call 2, the same operand):
#: nb 512 0.0839 / 0.0425 s at ib 64 and 0.0809 / 0.0419 at 128; nb
#: 1024 0.0967 / 0.0466 and 0.0823 / 0.0413; nb 256 0.1037 / 0.0528
#: at ib 64 (call 1): 512 stays
LU_TALL_NB = 512


def _blocked_ib(w: int, m: int = 0, dtype=None) -> int:
    """Widest base block of `lu_panel_blocked` that divides `w` (0:
    none does, the caller keeps the fori kernel): LU_BLOCKED_IB or
    under, and twice LU_BLOCKED_IB for an (m, w) panel of `dtype`
    whose blocks that wide the VMEM kernel takes (`_column_kernel`):
    a rule on the platform and the shape."""
    ib = next((ib for ib in (LU_BLOCKED_IB, 32, 16, 8) if w % ib == 0), 0)
    if ib == LU_BLOCKED_IB and w % (2 * ib) == 0 and m \
            and _column_kernel(2 * ib, m, dtype) == "vmem":
        return 2 * ib
    return ib


def _block_columns_xla(tb: jax.Array, j0: jax.Array, ib: int
                       ) -> Tuple[jax.Array, jax.Array]:
    """The column recurrence of one base block of `lu_panel_blocked`
    as an XLA loop: `tb` is the (ib + 1, m) block, row jj the panel's
    column j0 + jj and the last row the row positions. Every backend
    and shape the VMEM kernel does not take runs this; some ten device
    operations a column, each a pass over the whole block (10.7 us a
    column at 16384 rows, 27 at 32768, 30 at 49152, PR 42-49)."""
    m = tb.shape[1]
    rows = jnp.arange(m, dtype=jnp.int32)
    sub = jnp.arange(ib + 1, dtype=jnp.int32)[:, None]
    zero = jnp.zeros((), jnp.int32)

    def column(jj, c):
        tb, pv = c
        jj = jnp.asarray(jj, jnp.int32)
        j = j0 + jj
        col = jax.lax.dynamic_slice(tb, (jj, zero), (1, m))
        p = jnp.argmax(jnp.where(rows[None, :] >= j, jnp.abs(col),
                                 -jnp.inf)).astype(jnp.int32)
        at_j = jax.lax.dynamic_slice(tb, (zero, j), (ib + 1, 1))
        at_p = jax.lax.dynamic_slice(tb, (zero, p), (ib + 1, 1))
        # rows j <-> p of the block (p == j: at_p is at_j)
        tb = jnp.where(rows[None, :] == j, at_p,
                       jnp.where(rows[None, :] == p, at_j, tb))
        pivval = at_p[jj, 0]
        safe = jnp.where(pivval == 0, jnp.ones((), tb.dtype), pivval)
        col = jax.lax.dynamic_slice(tb, (jj, zero), (1, m))
        mult = jnp.where(rows[None, :] > j, col / safe, 0)
        urow = jnp.where((sub > jj) & (sub < ib), at_p, 0)
        tb = jnp.where((sub == jj) & (rows[None, :] > j), mult,
                       tb - urow * mult)
        return tb, pv.at[jj].set(p)

    return jax.lax.fori_loop(0, ib, column,
                             (tb, jnp.zeros((ib,), jnp.int32)))


#: (ib, m, dtype) blocks whose fall-back from the VMEM kernel to the
#: XLA loop on a TPU was already surfaced, as _FORI_FALLBACK_SEEN
_COLUMNS_FALLBACK_SEEN: set = set()


def _column_kernel(ib: int, m: int, dtype) -> str:
    """Which column recurrence a base block of `lu_panel_blocked` runs,
    by platform and shape alone: 'vmem' (the Pallas kernel that holds
    the block in VMEM, ops/pallas_kernels.lu_block_columns) or 'xla'
    (`_block_columns_xla`). The route notes carry it."""
    from ..ops import pallas_kernels as pk
    return "xla" if pk.lu_block_columns_reject_reason(ib, m, dtype) \
        else "vmem"


def _block_columns(tb: jax.Array, j0: jax.Array, ib: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """(factored block, its ib swap targets) of one base block of
    `lu_panel_blocked`: one Pallas kernel where `_column_kernel` says
    so, the XLA loop elsewhere. A TPU run that keeps the XLA loop
    says why once a shape (an obs instant of `_reject`'s form)."""
    from ..ops import pallas_kernels as pk
    m = tb.shape[1]
    reason = pk.lu_block_columns_reject_reason(ib, m, tb.dtype)
    if reason is None:
        return pk.lu_block_columns(tb, j0, ib)
    if reason != "platform" and _first_sighting(
            _COLUMNS_FALLBACK_SEEN, (ib, m, str(tb.dtype))):
        pk._reject("lu_block_columns", reason, ib=ib, m=m,
                   dtype=str(tb.dtype))
    return _block_columns_xla(tb, j0, ib)


def _panel_note(m: int, w: int, dtype,
                method: Optional[MethodLUPanel] = None) -> dict:
    """How an (m, w) panel is factored, for a route note: the panel
    route (`method`, or what `MethodLUPanel.resolve` says), and under
    `blocked` which column recurrence its base blocks run
    (`panel_columns`: vmem or xla, `_column_kernel`)."""
    if method is None:
        method = MethodLUPanel.resolve(m, w, dtype)
    note = {"panel": method.value}
    if method is MethodLUPanel.Blocked:
        note["panel_columns"] = _column_kernel(_blocked_ib(w, m, dtype), m,
                                               dtype)
    return note


def lu_panel_blocked(a: jax.Array, ib: int = LU_BLOCKED_IB
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partial-pivot LU of a tall (m, w) panel, w a multiple of `ib`,
    for heights XLA's native LU refuses (NATIVE_LU_MAX_M): left-
    looking over ib-wide column blocks, one program whatever w / ib
    is. `lu_panel_fori` does a rank-1 update of the WHOLE (m, w) panel
    at every column (two passes over it a column: 0.50 s for the 32
    tall panels of a solve at n=16384, PERF.md); here a column step
    rewrites only its own block, held transposed as (ib, m) so that m
    runs along the lanes and a narrow block pads nothing, and the
    other columns meet the block's update and its pivots once a
    block: one matmul, and an exchange of the at most 2 ib rows its
    swaps touched.

    Block i first takes what the factored columns left of it owe it
    (the solve against their unit-lower square gives its U rows and
    its updated rows inside the top w x w, the matmul the rows under
    that), then factors itself column by column (`_block_columns`):
    masked argmax, the two rows exchanged, the multipliers, and the
    rank-1 update of the block's later columns. On the chip that
    recurrence is one Pallas kernel a block, the block resident in
    VMEM (PR 50); elsewhere an XLA loop, where a device operation
    costs a microsecond or more however small (read on the chip, PR
    42: 4.4 us a column for two scalar updates of a permutation
    vector), so the block's permutation rides in the same pass as one
    more row of the block: the row positions, exchanged with the
    rows. Same pivots as `lu_panel_fori` (ties aside).
    Returns (packed LU, local swap targets (w,), their composed
    permutation (m,))."""
    m, w = a.shape
    slate_assert(m < 1 << 24, "row positions ride in an f32 row")
    rows = jnp.arange(m, dtype=jnp.int32)
    cols = jnp.arange(w, dtype=jnp.int32)
    sub = jnp.arange(ib + 1, dtype=jnp.int32)[:, None]
    zero = jnp.zeros((), jnp.int32)
    positions = rows.astype(a.dtype)[None, :]

    def block(i, carry):
        a, gperm, piv = carry
        j0 = jnp.asarray(i, jnp.int32) * ib
        blk = jax.lax.dynamic_slice(a, (zero, j0), (m, ib))
        done = cols < j0
        # the factored columns' unit-lower square, identity past them:
        # row r < j0 of the solve is U's, row r >= j0 the updated one
        top = jax.lax.linalg.triangular_solve(
            jnp.where(done[None, :], a[:w], 0), blk[:w], left_side=True,
            lower=True, unit_diagonal=True)
        if m > w:
            below = blk[w:] - jnp.matmul(
                a[w:], jnp.where(done[:, None], top, 0), precision=_HIP)
            blk = jnp.concatenate([top, below], axis=0)
        else:
            blk = top

        tb, pv = _block_columns(
            jnp.concatenate([blk.T, positions], axis=0), j0, ib)
        # the other columns into the block's row order: only the rows
        # its swaps touched moved (twice named, a row gets one content)
        touched = jnp.concatenate([j0 + sub[:ib, 0], pv])
        came_from = jnp.real(tb[ib]).astype(jnp.int32)[touched]
        a = a.at[touched].set(a[came_from])
        a = jax.lax.dynamic_update_slice(a, tb[:ib].T, (zero, j0))
        return (a, gperm.at[touched].set(gperm[came_from]),
                jax.lax.dynamic_update_slice(piv, pv, (j0,)))

    a, perm, piv = jax.lax.fori_loop(
        0, w // ib, block, (a, rows, jnp.zeros((w,), jnp.int32)))
    return a, piv, perm


# -- factorizations -------------------------------------------------------

def _tnt_swap_sequence(rows: jax.Array, m: int
                       ) -> Tuple[jax.Array, jax.Array]:
    """Convert an ordered pivot-row selection (w,) into the equivalent
    LAPACK sequential swap targets AND the composed permutation:
    piv[j] = current position of rows[j] after the previous j swaps
    (so laswp-style application reproduces bringing the selected rows
    to the top, in order), and perm = the replay's final
    position->original-row map. The sim's own bookkeeping IS the
    permutation, so returning it saves the separate
    lu_pivots_to_permutation pass (the sequential sim is the dominant
    CALU overhead — ~4.75 ms per 8192x512 panel on v5e, PERF.md)."""
    w = rows.shape[0]

    def body(j, carry):
        cur_of_orig, orig_at_pos, piv = carry
        t = cur_of_orig[rows[j]]
        piv = piv.at[j].set(t.astype(jnp.int32))
        oj = orig_at_pos[j]
        ot = orig_at_pos[t]
        orig_at_pos = orig_at_pos.at[j].set(ot).at[t].set(oj)
        cur_of_orig = cur_of_orig.at[ot].set(j).at[oj].set(t)
        return cur_of_orig, orig_at_pos, piv

    _, perm, piv = jax.lax.fori_loop(
        0, w, body, (jnp.arange(m), jnp.arange(m),
                     jnp.zeros((w,), jnp.int32)))
    return piv, perm


def tnt_swaps_host(sel, mlen: int):
    """Host-side twin of :func:`_tnt_swap_sequence` for the OOC
    tournament streams (linalg/ooc.getrf_tntpiv_ooc and
    dist/shard_ooc.shard_getrf_ooc run their permutation bookkeeping
    in numpy, like ooc._swaps_to_perm): convert an ordered pivot-row
    selection `sel` (live-relative indices, selection order) into
    (piv, lperm) — LAPACK sequential swap targets relative to the
    live block, and the replay's final position->pre-swap-row map
    (lperm[:len(sel)] recovers `sel`'s rows on top, in order). Both
    drivers call this on the SAME broadcast selection, so the derived
    permutations are identical across hosts by construction."""
    import numpy as _np
    sel = _np.asarray(sel, _np.int64)
    w = sel.shape[0]
    cur_of_orig = _np.arange(mlen)     # pre-swap row -> current pos
    orig_at_pos = _np.arange(mlen)     # current pos -> pre-swap row
    piv = _np.empty((w,), _np.int64)
    for j, r in enumerate(sel):
        t = int(cur_of_orig[r])
        piv[j] = t
        oj, ot = orig_at_pos[j], orig_at_pos[t]
        orig_at_pos[j], orig_at_pos[t] = ot, oj
        cur_of_orig[ot], cur_of_orig[oj] = j, t
    return piv, orig_at_pos


def _lu_u12(l11: jax.Array, rhs: jax.Array, grid) -> jax.Array:
    """U12 = L11^{-1} rhs with L11 the packed panel diag block (strict
    lower + implicit unit diagonal). Single-device: one direct XLA
    solve — matmul-rate on TPU, and its expander runs f32-accurate
    internally (PERF.md residuals). Under a grid: invert-then-matmul so
    the bulk op is a matmul the SPMD partitioner can shard."""
    if grid is None:
        return jax.lax.linalg.triangular_solve(
            l11, rhs, left_side=True, lower=True, unit_diagonal=True)
    linv = invert_triangular(l11, lower=True, unit_diagonal=True)
    return jnp.matmul(linv, rhs, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("w", "method"))
def _carry_panel(trail: jax.Array, w: int, method: MethodLUPanel):
    """One step's panel of `_getrf_carry`: the leading `w` columns
    factored, as (packed LU, local swap targets, their composed
    permutation). `method` is the route the caller resolved; it is
    static, so a tune entry that moves it gets a program of its own.
    The native custom call and the blocked kernel return the composed
    permutation themselves."""
    panel = trail[:, :w]
    if method is MethodLUPanel.Native:
        lu, piv, perm = jax.lax.linalg.lu(panel)
        return lu, piv.astype(jnp.int32), perm
    if method is MethodLUPanel.Blocked:
        return lu_panel_blocked(
            panel, _blocked_ib(w, panel.shape[0], panel.dtype))
    # panels the native call cannot take (scoped-vmem height limit /
    # dtype) or that the tune cache routed elsewhere: _lu_panel
    # arbitrates (true partial pivoting preserved)
    lu, piv = _lu_panel(panel)
    return lu, piv, _compose_swaps(piv, trail.shape[0])


def _lo_panel_route(m: int, w: int) -> MethodLUPanel:
    """How the lo carry form factors an (m, w) panel: the f32 copy's
    cold route (`MethodLUPanel.cold_default`, the one decision every
    f32 panel gets): XLA's native LU where that compiles at the
    height, `lu_panel_blocked` above it, the fori kernel where no base
    block divides the width. No tune entry moves it: the mixed
    solves' programs are the same under any cache."""
    return MethodLUPanel.cold_default(m, w, jnp.float32)


@functools.partial(jax.jit, static_argnames=("w", "route"))
def _carry_panel_lo(trail: jax.Array, w: int, route: MethodLUPanel):
    """`_carry_panel` for a factor stored below f32 (the mixed solves'
    bf16): XLA's LU takes no such operand, so the panel alone is
    raised to f32, factored there (`route`: `_lo_panel_route`) and
    stored rounded, as HPL-MxP codes factor the panel above the
    update's precision. The pivot search sees f32 values."""
    panel = trail[:, :w].astype(jnp.float32)
    if route is MethodLUPanel.Native:
        lu, piv, perm = jax.lax.linalg.lu(panel)
    elif route is MethodLUPanel.Blocked:
        lu, piv, perm = lu_panel_blocked(
            panel, _blocked_ib(w, panel.shape[0], panel.dtype))
    else:
        lu, piv = lu_panel_fori(panel)
        perm = _compose_swaps(piv, trail.shape[0])
    return lu.astype(trail.dtype), piv.astype(jnp.int32), perm


@functools.partial(jax.jit, static_argnames="w")
def _carry_swap(trail: jax.Array, perm: jax.Array, w: int) -> jax.Array:
    """The columns right of a step's panel, in the panel's row order.
    `w` is static."""
    return _permute_rows(trail[:, w:], perm)


@jax.jit
def _carry_update(lu: jax.Array, rest: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """One step's U12 strip and the trailing matrix it leaves."""
    w = lu.shape[1]
    if lu.dtype.itemsize < 4:
        # the lo factor: the strip solved in f32 against the STORED
        # (rounded) diagonal block and rounded once; the update one
        # pass of lo x lo products accumulated in f32, the trailing
        # matrix rounded as it is written
        # (by `tri_sweep` in blocks of 128: XLA's TriangularSolve
        # expander unrolls w / 128 steps into 10.7 MB of code for this
        # one strip, a program a step; the sweep is 2.1 MB. Compiled
        # for a described v5e at n=16384, w=1024, PR 42)
        from .refine import tri_sweep
        u12 = tri_sweep(lu[:w].astype(jnp.float32),
                        rest[:w].astype(jnp.float32), lower=True,
                        nb=128 if w % 128 == 0 else w,
                        unit_diagonal=True).astype(lu.dtype)
        if lu.shape[0] == w:
            return u12, rest[w:]
        return u12, (rest[w:].astype(jnp.float32) - jnp.matmul(
            lu[w:], u12, preferred_element_type=jnp.float32)
        ).astype(lu.dtype)
    u12 = jax.lax.linalg.triangular_solve(
        lu[:w], rest[:w], left_side=True, lower=True,
        unit_diagonal=True)
    if lu.shape[0] == w:
        return u12, rest[w:]
    return u12, rest[w:] - jnp.matmul(
        lu[w:], u12, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit,
                   static_argnames=("nb", "kmax", "M", "N", "compose"))
def _carry_finish(panels, perms, urows, pivs, *, nb: int, kmax: int,
                  M: int, N: int, compose: bool = False):
    """The end of `_getrf_carry` as one program: every panel into its
    final row order, the packed factor, the global pivots.

    Panel k was emitted in step k's row order, and the later steps'
    permutations act on its rows below the diagonal block. With q_k
    the composed action of perms[k+1:] on panel k's rows,

        q_{nt-1} = identity,
        q_k      = [0 .. nb) ++ nb + perms[k+1][q_{k+1}],

    so going backward costs one (m_{k+1},) index gather and one panel
    gather a step (the role of the reference's deferred laswp
    application, getrf.cc row-swap tasks). `pivs` are each step's
    local swap targets; step k's global ones are k*nb + pivs[k].
    Under the one jit `assemble_packed`'s strip overlays update in
    place."""
    head = jnp.arange(nb, dtype=jnp.int32)
    reordered = list(panels)        # the last is in its final order
    q = jnp.arange(panels[-1].shape[0], dtype=jnp.int32)
    for k in range(len(panels) - 2, -1, -1):
        q = jnp.concatenate([head, nb + perms[k + 1][q]])
        reordered[k] = _permute_rows(panels[k], q)
    out = assemble_packed(reordered, urows, nb, kmax, M, N,
                          panels[0].dtype)
    pivots = jnp.concatenate([k * nb + p for k, p in enumerate(pivs)])
    if not compose:
        return out, pivots
    # the steps' permutations as one of all M rows (`LUFactors.perm`):
    # step k reorders the rows from k * nb down, nt short gathers
    # where `lu_pivots_to_permutation` replays kmax swaps one by one
    perm = jnp.arange(M, dtype=jnp.int32)
    for k, pk in enumerate(perms):
        perm = jnp.concatenate([perm[:k * nb], perm[k * nb:][pk]])
    return out, pivots, perm


def _carry_nb(M: int, kmax: int, nb: int, dtype) -> int:
    """The carry form's blocking for an operand of M rows: the
    caller's where the native panel takes the height. Above it the
    tall early panels run `lu_panel_blocked`, capped at LU_TALL_NB, or,
    where that kernel cannot take them, the fori kernel, whose cost is
    O(w) sequential full-height passes — narrow panels bound that
    (getrf_tntpiv, CALU, is the matmul-rate alternative at these
    heights)."""
    if MethodFactor.native_lu_ok(dtype, M):
        return nb
    tall = min(nb, LU_TALL_NB)
    if MethodLUPanel.resolve(M, min(tall, kmax), dtype) \
            is MethodLUPanel.Fori:
        return min(nb, 256)
    return tall


def _getrf_carry(a: jax.Array, nb: int, lo: bool = False):
    """Single-device blocked LU that carries the SHRINKING trailing
    matrix as the loop state instead of updating the full matrix in
    place. Functional slice-updates of a big matrix materialize
    O(nt * n^2) of extra HBM traffic (measured: the in-place-update
    form costs 2x this one at n=4096, PERF.md 'composition
    experiments'); carrying the trailing block means each step's only
    big write is the trailing matmul output itself, which must be
    written anyway.

    Row-swap bookkeeping: XLA's native LU returns the panel's COMPOSED
    permutation, which is applied to the remaining columns by one
    gather per step. Already-emitted L panels are NOT touched per step
    — each panel is emitted in its step's row order, and
    `_carry_finish` brings them all into the final order at the end.

    The host dispatches three compiled programs a step (panel, swap,
    update) and one at the end, each under the span that names it.

    `lo`: the factor of a mixed solve, stored below f32. The same
    steps on the same spans with `_carry_panel_lo` for the panel, and
    the finish also hands back the composed row permutation: returns
    (packed LU, pivots, perm)."""
    M, N = a.shape
    kmax = min(M, N)
    nt = ceil_div(kmax, nb)
    trail = a
    panels = []      # (m_k, w_k) packed panel, step-k row order
    urows = []       # (w_k, N - k1) U12 strips
    perms = []       # (m_k,) composed local permutation per step
    pivs = []        # (w_k,) local swap targets per step
    span = obs_events.span
    for k in range(nt):
        k1 = min((k + 1) * nb, kmax)
        w = k1 - k * nb
        # panel-route arbitration (MethodLUPanel), out here at every
        # call, so a measured pallas_rec/fori cache entry reroutes
        # this consumer too
        with span("getrf::panel", cat="step", k=k):
            m = trail.shape[0]
            if lo:
                lu, piv, perm = _carry_panel_lo(
                    trail, w, _lo_panel_route(m, w))
            else:
                method = MethodLUPanel.resolve(m, w, trail.dtype)
                if method is MethodLUPanel.Fori:
                    # its one-shot instant, which a compiled panel
                    # would raise only while it is traced
                    _surface_fori_fallback(m, w, trail.dtype)
                lu, piv, perm = _carry_panel(trail, w, method)
        with span("getrf::pivots", cat="step", k=k):
            pivs.append(piv)
            perms.append(perm)
            panels.append(lu)
            if k1 < N:
                rest = _carry_swap(trail, perm, w)
        if k1 < N:
            with span("getrf::update", cat="step", k=k):
                u12, trail = _carry_update(lu, rest)
                urows.append(u12)
    with span("getrf::reorder", cat="step", nt=nt):
        return _carry_finish(panels, perms, urows, pivs, nb=nb,
                             kmax=kmax, M=M, N=N, compose=lo)


def _getrf_pipelined(a: jax.Array, nb: int, grid=None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Software-pipelined (lookahead-1) partial-pivot blocked LU — the
    LU counterpart of blocked.chol_loop_pipelined (reference
    getrf.cc's lookahead split of the trailing gemm). Panel k+1
    factors right after a NARROW update of its own column block; the
    WIDE remainder of step k's trailing update is dataflow-independent
    of that panel chain. Step-(k+1) row swaps of non-panel columns are
    deferred to the next iteration's head, which is exactly when the
    plain loop would apply them (after the full step-k trailing
    update), so the two orders compute identical results."""
    from ..parallel.sharding import constrain
    M, N = a.shape
    kmax = min(M, N)
    nt = ceil_div(kmax, nb)
    ipiv = jnp.arange(kmax, dtype=jnp.int32)
    # prologue: factor panel 0 (swaps to other columns deferred)
    k1 = min(nb, kmax)
    panel, piv = _lu_panel(a[:, :k1])
    a = a.at[:, :k1].set(panel)
    ipiv = ipiv.at[:k1].set(piv)
    pend_piv, pend_k0 = piv, 0      # swaps not yet applied elsewhere
    for k in range(nt):
        k0, k1 = k * nb, min((k + 1) * nb, kmax)
        k2 = min(k1 + nb, kmax)
        # (1) apply the pending panel swaps to the non-panel columns
        perm = _compose_swaps(pend_piv, M - pend_k0)
        if pend_k0 > 0:
            a = a.at[pend_k0:, :pend_k0].set(
                _permute_rows(a[pend_k0:, :pend_k0], perm))
        if k1 < N:
            a = a.at[pend_k0:, k1:].set(
                _permute_rows(a[pend_k0:, k1:], perm))
        if k1 >= N:
            break
        lkk = a[k0:k1, k0:k1]
        lcol = a[k1:, k0:k1]
        # (2) narrow: update the next panel's column block only
        if k2 > k1:
            u12n = _lu_u12(lkk, a[k0:k1, k1:k2], grid)
            a = a.at[k0:k1, k1:k2].set(u12n)
            a = a.at[k1:, k1:k2].add(
                -jnp.matmul(lcol, u12n,
                            precision=jax.lax.Precision.HIGHEST))
            # (3) factor panel k+1 from it (critical path)
            panel, piv = _lu_panel(a[k1:, k1:k2])
            a = a.at[k1:, k1:k2].set(panel)
            ipiv = ipiv.at[k1:k2].set(k1 + piv)
            pend_piv, pend_k0 = piv, k1
        # (4) wide trailing update — independent of the panel above
        if k2 < N:
            u12w = _lu_u12(lkk, a[k0:k1, k2:], grid)
            a = a.at[k0:k1, k2:].set(u12w)
            upd = jnp.matmul(lcol, u12w,
                             precision=jax.lax.Precision.HIGHEST)
            a = constrain(a.at[k1:, k2:].add(-upd), grid)
    return a, ipiv


def _dense_blocking(M: int, N: int, nb: int, pivot: bool, dtype,
                    tile_nb: Optional[int]) -> Tuple[int, int]:
    """What `_getrf_dense` factors an (M, N) operand in, from its
    shape alone: (the blocking of the carry, pipelined and unrolled
    forms, the blocking of the scan form or 0 where the operand does
    not take it). `getrf` asks it at dispatch for the route a
    compiled program will take."""
    from ..ops import pallas_kernels as pk
    kmax = min(M, N)
    # the fused kernel's width cap, resolved ONCE through the tune
    # arbitration (("lu_panel", "max_w"), FROZEN == LU_PANEL_MAX_W) so
    # the planner and the eligibility gates agree even when a measured
    # entry moves the cap
    lu_max_w = pk._lu_max_w()
    pallas_capped = (pivot
                     and not MethodFactor.native_lu_dtype_ok(dtype)
                     and pk.lu_panel_eligible(
                         min(M, 128), min(nb, lu_max_w),
                         dtype)
                     # capping to the fused width multiplies the step
                     # count, and the unrolled compile grows with it:
                     # the 16-step cap is not measured on the current
                     # machine (bf16 n=8192 at nb=256 = 32 steps did
                     # not compile in 9 min on an earlier one), so
                     # larger kmax keeps the caller's nb and the fori
                     # tall-panel path
                     and ceil_div(kmax, lu_max_w) <= 16)
    if pallas_capped:
        # cap the panel width at the fused kernel's limit so panels
        # are one VMEM-resident dispatch — only for dtypes that
        # actually take the Pallas kernel (bf16); native-LU dtypes
        # keep the caller's nb, since narrower panels would just
        # double the step count for zero fused-kernel benefit. The
        # eligibility probe uses a nominal SHORT height on purpose:
        # the kernel's own height cap is per-panel (lu_panel checks
        # each shrinking panel), so a tall FIRST panel must not stop
        # the nb cap that lets every below-the-cap panel take the
        # fused kernel (the tall ones fall back to the fori kernel,
        # where the narrow width bounds the sequential cost too).
        nb = min(nb, lu_max_w)
    nt = ceil_div(kmax, nb)
    if M == N and nt > LU_SCAN_THRESHOLD:
        # fixed-shape fori_loop form: program size independent of nt
        # (tournament selection runs inside the scan step, so CALU
        # stays CALU at scale; the one-step body has no cross-step
        # independence, so lookahead does not apply). Its fixed-width
        # dynamic_slice steps require nb | N — dynamic_slice clamps at
        # the edge, which would silently misalign the diagonal block.
        # A non-dividing algorithmic nb (Option.BlockSize or the
        # _lu_nb default) is resolved to the widest dividing blocking
        # available: the storage tile size always divides the padded
        # dims, and _scan_nb covers tile-less internal callers. The
        # bf16 Pallas cap (width and %8 alignment) is preserved —
        # widening past lu_panel_eligible's limits would silently
        # demote every panel to the fori_loop kernel. The resolved
        # width is scoped to the scan route only: if it would leave
        # the scan regime entirely (step count back under the
        # threshold), control falls through with the CALLER'S nb on
        # the carry/unrolled forms, which handle non-dividing widths
        # natively (program size grows with nt — the documented trade
        # for honoring an explicit Option.BlockSize there).
        if N % nb == 0:
            return nb, nb
        cand = _scan_nb(N, nb, 8)     # %8 widths suit every panel path
        if tile_nb and N % tile_nb == 0 and \
                (not pallas_capped or (tile_nb <= lu_max_w
                                       and tile_nb % 8 == 0)):
            cand = max(cand, tile_nb)
        if cand >= 8 and ceil_div(kmax, cand) > LU_SCAN_THRESHOLD:
            # a degenerate divisor (N with no usable factor <= nb)
            # would make the scan run absurdly narrow steps; the
            # carry/unrolled fall-through is the better cliff
            return nb, cand
    return nb, 0


def _getrf_dense(a: jax.Array, nb: int, pivot: bool, grid=None,
                 tournament: bool = False, lookahead: int = 1,
                 tile_nb: Optional[int] = None,
                 composed: Optional[list] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Blocked right-looking LU on padded (M, N) dense; returns packed
    LU and global pivot swaps (length min(M,N)). With a grid, trailing
    updates are sharding-constrained over the mesh (the load-balance
    role of the reference's 2D block-cyclic distribution). In the
    unrolled and pipelined forms the partitioner places the panels;
    in the scan form (`_lu_scan_grid`) each panel is factored on
    every chip alike from a replicated copy of its column block (the
    analogue of the reference's panel-column rank set working one
    panel together, getrf.cc:91), and its row exchanges and blocks
    stay on the chips that own them. `composed`, a list, receives the
    swaps composed to one permutation of the rows where the form
    composes it on the way (the scan form under a grid)."""
    from ..parallel.sharding import constrain
    M, N = a.shape
    kmax = min(M, N)
    nb, scan_nb = _dense_blocking(M, N, nb, pivot, a.dtype, tile_nb)
    nt = ceil_div(kmax, nb)
    if scan_nb:
        obs_events.note(form="scan", nb=scan_nb)
        if grid is None:
            return _lu_scan(a, scan_nb, pivot, tournament=tournament)
        lu, ipiv, perm = _lu_scan_grid(a, scan_nb, pivot, grid,
                                       tournament=tournament)
        if composed is not None:
            composed.append(perm)
        return lu, ipiv
    if pivot and not tournament and grid is None and nt > 1 \
            and MethodFactor.native_lu_dtype_ok(a.dtype):
        # single-device fast path: carry-the-trailing-matrix form.
        # Lookahead does not branch here — software pipelining was
        # measured COUNTERPRODUCTIVE on a single sequential TPU core
        # (n=8192 Tiled LU: plain 79.3 ms vs pipelined 91.5 ms, v5e;
        # the narrow+wide split just adds passes when nothing can
        # overlap). The pipelined form remains the grid-path shape,
        # where mesh shards do run concurrently.
        nb = _carry_nb(M, kmax, nb, a.dtype)
        if obs_events.enabled():
            obs_events.note(form="carry", nb=nb,
                            **_panel_note(M, min(nb, kmax), a.dtype))
        return _getrf_carry(a, nb)
    if pivot and not tournament and lookahead >= 1 and nt > 1:
        obs_events.note(form="pipelined", nb=nb)
        return _getrf_pipelined(a, nb, grid)
    obs_events.note(form="unrolled", nb=nb)
    ipiv = jnp.arange(kmax, dtype=jnp.int32)
    span = obs_events.span      # the names of _getrf_carry's steps
    for k in range(nt):
        k0, k1 = k * nb, min((k + 1) * nb, kmax)
        w = k1 - k0
        if pivot and tournament:
            # CALU: tournament selects the pivot rows up front, then
            # the panel factors without further pivoting (reference
            # getrf_tntpiv.cc:169-222)
            from .ca import calu_factor_sorted, tournament_pivot_rows
            sub = a[k0:, k0:k1]
            rows = tournament_pivot_rows(sub)
            piv, perm = _tnt_swap_sequence(rows, M - k0)
            a = a.at[k0:, :].set(_permute_rows(a[k0:, :], perm))
            panel = calu_factor_sorted(a[k0:, k0:k1])
            a = a.at[k0:, k0:k1].set(panel)
            ipiv = ipiv.at[k0:k1].set(k0 + piv)
        elif pivot:
            with span("getrf::panel", cat="step", k=k):
                panel, piv = _lu_panel(a[k0:, k0:k1])
                a = a.at[k0:, k0:k1].set(panel)
            with span("getrf::pivots", cat="step", k=k):
                perm = _compose_swaps(piv, M - k0)
                if k0 > 0:
                    a = a.at[k0:, :k0].set(
                        _permute_rows(a[k0:, :k0], perm))
                if k1 < N:
                    a = a.at[k0:, k1:].set(
                        _permute_rows(a[k0:, k1:], perm))
                ipiv = ipiv.at[k0:k1].set(k0 + piv)
        else:
            with span("getrf::panel", cat="step", k=k):
                panel, _ = _nopiv_panel(a[k0:, k0:k1])
                a = a.at[k0:, k0:k1].set(panel)
        if k1 < N:
            with span("getrf::update", cat="step", k=k):
                u12 = _lu_u12(a[k0:k1, k0:k1], a[k0:k1, k1:], grid)
                a = a.at[k0:k1, k1:].set(u12)
                if k1 < M:
                    upd = jnp.matmul(
                        a[k1:, k0:k1], u12,
                        precision=jax.lax.Precision.HIGHEST)
                    a = constrain(a.at[k1:, k1:].add(-upd), grid)
    return a, ipiv


def _lo_route(opts: OptionsLike, tile_nb: int, shape, dtype) -> dict:
    """The lo carry form's route, as `getrf` notes it on its span and
    the mixed drivers on theirs: the blocking `getrf` resolves, how
    the first (tallest) panel is factored, what is stored and what an
    update multiplies."""
    nb = _lu_nb(opts, tile_nb, shape, None, dtype=dtype)
    m, w = shape[0], min(nb, *shape)
    return dict(form="carry", nb=nb, store=str(dtype),
                **_panel_note(m, w, jnp.float32, _lo_panel_route(m, w)),
                panel_dtype="float32",
                update="one pass %s x %s -> float32" % (dtype, dtype))


def _stored_lo(dtype) -> bool:
    """A real factor dtype below f32 (bf16, the chip's lo of f32; f16):
    what XLA's LU takes no operand of."""
    d = jnp.dtype(dtype)
    return jnp.issubdtype(d, jnp.floating) and d.itemsize < 4


def _nopiv_panel(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """LU panel without pivoting (reference getrf_nopiv)."""
    m, w = a.shape
    rows = jnp.arange(m)

    def body(j, a):
        pivval = a[j, j]
        safe = jnp.where(pivval == 0, jnp.ones((), a.dtype), pivval)
        mults = jnp.where(rows > j, a[:, j] / safe, 0)
        a = a.at[:, j].set(jnp.where(rows > j, mults, a[:, j]))
        cols = jnp.arange(w)
        urow = jnp.where(cols > j, a[j], 0)
        return a - jnp.outer(mults, urow)

    return jax.lax.fori_loop(0, w, body, a), jnp.zeros((w,), jnp.int32)


#: block-step count above which the Tiled LU switches to the
#: fixed-shape fori_loop form (O(1) program size; see
#: blocked.CHOL_SCAN_THRESHOLD for the rationale)
LU_SCAN_THRESHOLD = 64


def _scan_nb(N: int, nb: int, mult: int = 1) -> int:
    """Largest divisor of N that is <= nb, preferring multiples of
    `mult` (the Pallas panel kernel needs w % 8 == 0) when one exists
    — the last-resort scan blocking when no storage tile size is
    available. NOT a gcd: _scan_nb(96, 20) = 16."""
    fallback = 0
    for w in range(min(nb, N), 0, -1):
        if N % w == 0:
            if w % mult == 0:
                return w
            fallback = fallback or w
    return fallback or 1


def _lu_scan(a: jax.Array, nb: int, pivot: bool,
             tournament: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Blocked right-looking LU as ONE compiled block step iterated by
    fori_loop (compile-time-safe form of _getrf_dense for huge nt), on
    one device; a matrix spread over a grid takes `_lu_scan_grid`,
    the same step on the chips that own its blocks.

    The panel is extracted full-height and ROLLED so its diagonal sits
    at row 0 — the packing every panel kernel assumes — with the
    wrapped-around already-factored rows masked to zero (they can never
    win a pivot search against live entries). Local pivots are then
    global-offset swaps; each step applies them as one full-height
    permutation gather. With `tournament`, pivot rows come from the
    CALU tournament over the rolled panel (zero-masked dead rows lose
    every round), so getrf_tntpiv keeps its contract at huge nt
    (reference getrf_tntpiv.cc:169-222). Square matrices only (callers
    guarantee)."""
    N = a.shape[0]
    nt = ceil_div(N, nb)
    rows = jnp.arange(N)
    ipiv = jnp.arange(N, dtype=jnp.int32)

    def step(k, carry):
        a, ipiv = carry
        k0 = k * nb
        live = N - k0                       # rows at/below the panel
        colblk = jax.lax.dynamic_slice(a, (0, k0), (N, nb))
        rolled = jnp.roll(colblk, -k0, axis=0)
        rolled = jnp.where((rows < live)[:, None], rolled, 0)
        if pivot and tournament:
            from .ca import calu_factor_sorted, tournament_pivot_rows
            sel = tournament_pivot_rows(rolled)   # rolled-frame rows
            piv, tperm = _tnt_swap_sequence(sel, N)
            panel = calu_factor_sorted(_permute_rows(rolled, tperm))
        elif pivot:
            panel, piv = _lu_panel(rolled)
        else:
            panel, piv = _nopiv_panel(rolled)
        if pivot:
            # swaps are local to the rolled frame == offsets from k0
            gpiv = k0 + piv
            ipiv = jax.lax.dynamic_update_slice(ipiv, gpiv, (k0,))
            perm = rows

            def swap(j, perm):
                t = gpiv[j]
                s = k0 + j
                pt = perm[t]
                ps = perm[s]
                return perm.at[s].set(pt).at[t].set(ps)

            perm = jax.lax.fori_loop(0, nb, swap, perm)
            a = _permute_rows(a, perm)
        # write the factored panel back (rows >= k0 of the column block)
        unrolled = jnp.roll(
            jnp.where((rows < live)[:, None], panel, 0), k0, axis=0)
        cur = jax.lax.dynamic_slice(a, (0, k0), (N, nb))
        newblk = jnp.where((rows >= k0)[:, None], unrolled, cur)
        a = jax.lax.dynamic_update_slice(a, newblk, (0, k0))
        # U row: u12 = inv(L_kk) A[k0:k1, k1:], applied full-width with
        # the already-factored columns masked out of the update
        lkk = jax.lax.dynamic_slice(a, (k0, k0), (nb, nb))
        rowblk = jax.lax.dynamic_slice(a, (k0, 0), (nb, N))
        cols = jnp.arange(N)
        rowblk_right = jnp.where((cols >= k0 + nb)[None, :], rowblk, 0)
        u12 = _lu_u12(lkk, rowblk_right, None)
        a = jax.lax.dynamic_update_slice(
            a, jnp.where((cols >= k0 + nb)[None, :], u12, rowblk),
            (k0, 0))
        # trailing update with the panel's sub-block, full height masked
        lcol = jax.lax.dynamic_slice(a, (0, k0), (N, nb))
        lcol = jnp.where((rows >= k0 + nb)[:, None], lcol, 0)
        a = a - jnp.matmul(lcol, u12, precision=_HIP)
        return a, ipiv

    a, ipiv = jax.lax.fori_loop(0, nt, step, (a, ipiv))
    return a, ipiv


_HIP = jax.lax.Precision.HIGHEST


def lu_scan_plan(n: int, nb: int, grid, itemsize: int = 4) -> dict:
    """What `_lu_scan_grid` does to an order-n matrix in nb-blocks on
    `grid`, from the shapes alone (`getrf` counts it at dispatch and
    the benchmark's readers hold the counters to it): the stages of
    `blocked.chol_scan_stages`; the FLOPs of the trailing updates (a
    stage of w columns on a trailing square of order m runs w / nb
    steps of 2 m^2 nb each) beside the 2 n^3 / 3 an LU needs; the
    bytes of the rows the steps exchange (2 nb rows a step, of the
    stage's square and, past the first stage, of the result whose
    factored columns left of the square take the same swaps) beside
    what a permutation of the whole matrix a step reads; and the rows
    the panels are factored over (a stage's height at each of its
    steps) beside the rows that are live."""
    from .blocked import chol_scan_stages, grid_blocks
    stages = chol_scan_stages(n, nb, grid)
    steps = [(r, n - r, w // nb) for r, w in stages]
    return {
        "stages": len(stages),
        "heights": [m for _, m, _ in steps],
        "blocks": "slice" if grid is None else grid_blocks(n, nb, grid),
        "steps": n // nb,
        "update_flops": sum(2 * m * m * nb * k for _, m, k in steps),
        "update_flops_needed": 2 * n ** 3 // 3,
        "exchange_bytes": sum(2 * nb * (m + (n if r else 0)) * itemsize * k
                              for r, m, k in steps),
        "exchange_bytes_full": (n // nb) * n * n * itemsize,
        "panel_rows_factored": sum(m * k for _, m, k in steps),
        "panel_rows_live": sum(n - j * nb for j in range(n // nb)),
    }


def _lu_scan_grid(a: jax.Array, nb: int, pivot: bool, grid,
                  tournament: bool = False
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`_lu_scan` for a matrix spread over a grid, built the way
    `blocked.cholesky_scan` is: the block step iterated by fori_loop
    in the few static stages of `chol_scan_stages`, stage s over its
    trailing square t = a[r_s:, r_s:], spread over the grid again as
    P('p','q'); then its factored columns and its finished rows of U
    go into the result through `_move_rect` and the next stage takes
    t[w_s:, w_s:]. Returns (packed LU, global swap targets, the swaps
    composed to one permutation of the rows). Program size goes with
    the stages, not with nt, and no value in it is more of the matrix
    than a chip's block of a stage's square (`_lu_scan`'s
    `dynamic_slice`s, its roll and its `a[perm]` each gather the
    matrix onto every chip: 19 GB asked of a 16 GB chip at n=49152).

    A step of a stage of height m:

    * the column block by `_take_block`, replicated (m x nb: 100 MB
      at the first stage of n=49152), and the panel factored from it
      on every chip alike, under `shard_map`: rolled so that its
      diagonal sits at row 0 and the stage's finished rows masked to
      zero, as `_lu_scan` does, by the kernel `MethodLUPanel` resolves
      for (m, nb) (`_carry_panel`: the native LU, or
      `lu_panel_blocked` above its height), by the CALU tournament, or
      without pivoting;
    * the row exchange: the at most 2 nb rows the panel's swaps
      touched, moved by `blocked._exchange_rows` on the chips that
      hold them, in the square and (past the first stage) in the
      result, whose columns left of the square hold the earlier
      stages' L;
    * the row block by `_take_block`, U12 by `_lu_u12`, one update of
      the square at `highest`, and both blocks written by
      `_put_block`.

    Where a block can straddle two chips (`grid_blocks` is "masked")
    the masked forms of the block helpers carry it in ONE stage; a
    row lies on one chip of each mesh column either way, so the
    exchange is the same. Square matrices, nb | N (callers
    guarantee)."""
    from ..parallel.sharding import constrain
    from ..parallel.smap import shard_map
    from .blocked import (COLUMN_BLOCK, _exchange_rows, _move_rect,
                          _put_block, _take_block, chol_scan_stages)
    from jax.sharding import PartitionSpec as P
    N = a.shape[0]
    head = jnp.arange(nb, dtype=jnp.int32)

    def panel_of(colblk, k0):
        """The factored column block (rows from k0 on), the packed
        diagonal block, the local swap targets, and the rows the swaps
        touched with where each one's content came from (offsets from
        k0). Runs on every chip alike."""
        m = colblk.shape[0]
        rows = jnp.arange(m, dtype=jnp.int32)
        live = (rows < m - k0)[:, None]
        rolled = jnp.where(live, jnp.roll(colblk, -k0, axis=0), 0)
        if pivot and tournament:
            from .ca import calu_factor_sorted, tournament_pivot_rows
            piv, pperm = _tnt_swap_sequence(
                tournament_pivot_rows(rolled), m)
            panel = calu_factor_sorted(_permute_rows(rolled, pperm))
        elif pivot:
            panel, piv, pperm = _carry_panel(
                rolled, nb, MethodLUPanel.resolve(m, nb, rolled.dtype))
        else:
            panel, piv = _nopiv_panel(rolled)
            pperm = rows
        back = jnp.roll(jnp.where(live, panel, 0), k0, axis=0)
        piv = piv.astype(jnp.int32)
        touched = jnp.concatenate([head, piv])
        return (jnp.where((rows >= k0)[:, None], back, colblk),
                panel[:nb], piv, touched, pperm[touched].astype(jnp.int32))

    if grid is not None:
        panel_of = shard_map(panel_of, grid.mesh, P(), P())

    def stage(t, out, ipiv, perm, r, steps):
        m = t.shape[0]
        rows = jnp.arange(m)

        def step(k, carry):
            t, out, ipiv, perm = carry
            k0 = jnp.asarray(k, jnp.int32) * nb
            k1 = k0 + nb
            with jax.named_scope("lu_panel"):
                newcol, lkk, piv, touched, came = panel_of(
                    _take_block(t, k, nb, 1, grid), k0)
            if pivot:
                with jax.named_scope("lu_exchange"):
                    t = _exchange_rows(t, k0 + touched, k0 + came, grid)
                    g0 = r + k0
                    if out is not None:
                        out = _exchange_rows(out, g0 + touched,
                                             g0 + came, grid)
                    ipiv = jax.lax.dynamic_update_slice(
                        ipiv, g0 + piv, (g0,))
                    perm = perm.at[g0 + touched].set(perm[g0 + came])
            with jax.named_scope("lu_update"):
                rowblk = _take_block(t, k, nb, 0, grid)
                right = (rows >= k1)[None, :]
                u12 = _lu_u12(lkk, jnp.where(right, rowblk, 0), grid)
                lcol = constrain(jnp.where((rows >= k1)[:, None],
                                           newcol, 0), grid, COLUMN_BLOCK)
                t = constrain(t - jnp.matmul(lcol, u12, precision=_HIP),
                              grid)
            # the update is zero in both blocks; the row block first,
            # the column block over the diagonal block it left unfactored
            t = _put_block(t, jnp.where(right, u12, rowblk), k, nb, 0,
                           grid)
            t = _put_block(t, constrain(newcol, grid, COLUMN_BLOCK), k,
                           nb, 1, grid)
            return t, out, ipiv, perm

        return jax.lax.fori_loop(0, steps, step, (t, out, ipiv, perm))

    ipiv = jnp.arange(N, dtype=jnp.int32)
    perm = jnp.arange(N, dtype=jnp.int32)
    out, t = None, a
    for r, w in chol_scan_stages(N, nb, grid):
        m = N - r
        t, out, ipiv, perm = stage(t, out, ipiv, perm, r, w // nb)
        if r == 0:
            out = t         # the first stage's square is the matrix
        else:
            out = _move_rect(t, out, (m, w), (0, 0), (r, r), grid)
            if w < m:
                out = _move_rect(t, out, (w, m - w), (0, w),
                                 (r, r + w), grid)
        if w < m:
            t = _move_rect(t, (m - w, m - w), (m - w, m - w), (w, w),
                           (0, 0), grid)
            # the next stage starts when this one's blocks are home
            out, t = jax.lax.optimization_barrier((out, t))
    return out, ipiv, perm


def _prep(A: TiledMatrix) -> Tuple[TiledMatrix, jax.Array]:
    r = A.uniform().resolve()    # non-uniform tiles re-tile at entry
    a = r.data if r.mtype is MatrixType.General else \
        jnp.pad(A.to_dense(), ((0, r.data.shape[0] - r.m),
                               (0, r.data.shape[1] - r.n)))
    a = pad_diag_identity(a, r.m, r.n)
    return r, a


def _lu_nb(opts: OptionsLike, tile_nb: int, shape, grid,
           dtype=None) -> int:
    """Algorithmic LU blocking, decoupled from the storage tile size.
    Grid paths ALWAYS use the tile size — the unit the 2D block-cyclic
    layout distributes — so a single-device-tuned Option.BlockSize in
    a reused options dict cannot desynchronize the panel slices from
    the shard boundaries. Single-device: an explicit Option.BlockSize
    wins, then a measured tune-cache entry (tune/select.py), then the
    frozen n-scaled formula (measured on v5e: nb=512 best at n=4096,
    nb=1024 at n=8192 — wider panels amortize the per-step permutation
    gather while the panel's per-column cost is width-independent,
    PERF.md)."""
    if grid is not None:
        return tile_nb
    n = min(shape)
    from ..tune.select import tuned_int
    nb_frozen = min(1024, max(512, n // 8))
    # an explicit 0 keeps its historical "use the default" meaning
    return tuned_int("getrf", "nb", nb_frozen, opts=opts,
                     option=Option.BlockSize, n=n,
                     dtype=dtype) or nb_frozen


@functools.lru_cache(maxsize=None)
def _grid_getrf_programs(grid):
    """`_prep` and the grid's factorization as compiled programs whose
    results stay on `grid` (`chol._grid_potrf_programs`' pattern); jit
    keys them by the matrix's shape, dtype and structure and by the
    blocking. The factor program hands back the packed factor, the
    swap targets, the swaps composed to one permutation of the padded
    rows (the scan form composes it step by step; for the unrolled
    forms `_compose_swaps` does, inside the program) and info."""
    from ..parallel.sharding import constrain
    from .info import lu_info

    def prep(A):
        return constrain(_prep(A)[1], grid)

    def factor(a, nb, lookahead, tile_nb, m, n):
        composed = []
        lu, ipiv = _getrf_dense(a, nb, pivot=True, grid=grid,
                                lookahead=lookahead, tile_nb=tile_nb,
                                composed=composed)
        perm = composed[0] if composed \
            else _compose_swaps(ipiv, a.shape[0])
        return constrain(lu, grid), ipiv, perm, lu_info(lu, m, n)

    return jax.jit(prep), jax.jit(factor, static_argnums=(1, 2, 3, 4, 5))


def _grid_prep(A: TiledMatrix, grid) -> Tuple[TiledMatrix, jax.Array]:
    """`_prep` under a grid: A's storage as it is where there is
    nothing to prepare (a general matrix that fills its tiles), else
    one compiled program."""
    r = A.uniform().resolve()
    if r.mtype is MatrixType.General and r.data.shape == (r.m, r.n):
        return r, r.data
    return r, _grid_getrf_programs(grid)[0](A)


def _count_lu_block_steps(blocks: str, steps: int) -> None:
    """`steps` block steps of a scan form of the LU or its solve are
    being dispatched under a grid: count them by how the form reaches
    its blocks (`blocked.grid_blocks`)."""
    if blocks == "local":
        obs_metrics.inc("grid.lu_block_steps_local", steps)
    else:
        obs_metrics.inc("grid.lu_block_steps_masked", steps)


def _count_grid_route(shape, nb: int, tile_nb: int, dtype, grid,
                      lookahead: int) -> None:
    """A factorization is being dispatched under `grid`: with the bus
    on, the route its program takes on the driver span that is open,
    and where that is the scan form the counters of `lu_scan_plan`
    (the helpers themselves run at trace time only)."""
    if not obs_events.enabled():
        return
    M, N = shape
    at = "%dx%d" % (grid.p, grid.q)
    nb, scan_nb = _dense_blocking(M, N, nb, True, dtype, tile_nb)
    if not scan_nb:
        # `_getrf_dense`'s choice for a pivoted factor under a grid
        nt = ceil_div(min(M, N), nb)
        obs_events.note(
            form="pipelined" if lookahead >= 1 and nt > 1 else "unrolled",
            nb=nb, nt=nt, grid=at, blocks="slice", stages=0)
        return
    plan = lu_scan_plan(N, scan_nb, grid, jnp.dtype(dtype).itemsize)
    inc = obs_metrics.inc
    _count_lu_block_steps(plan["blocks"], plan["steps"])
    inc("grid.lu_update_flops", plan["update_flops"])
    inc("grid.lu_update_flops_needed", plan["update_flops_needed"])
    inc("grid.lu_exchange_bytes", plan["exchange_bytes"])
    inc("grid.lu_exchange_bytes_full", plan["exchange_bytes_full"])
    inc("grid.lu_panel_rows_live", plan["panel_rows_live"])
    inc("grid.lu_panel_rows_factored", plan["panel_rows_factored"])
    # the stages' panels, each route and column kernel named once
    panels = {}
    for m in plan["heights"]:
        for key, value in _panel_note(m, scan_nb, dtype).items():
            panels.setdefault(key, set()).add(value)
    obs_events.note(
        form="scan", nb=scan_nb, nt=plan["steps"], grid=at,
        blocks=plan["blocks"], stages=plan["stages"],
        **{key: "/".join(sorted(values)) for key, values in panels.items()})


@instrument_driver("getrf")
def getrf(A: TiledMatrix, opts: OptionsLike = None) -> LUFactors:
    """Partial-pivoting LU: P A = L U (reference src/getrf.cc:327;
    MethodLU routing PPLU/CALU/NoPiv)."""
    method = get_option(opts, Option.MethodLU, MethodLU.PartialPiv)
    if method is MethodLU.NoPiv:
        return getrf_nopiv(A, opts)
    if method is MethodLU.CALU:
        return getrf_tntpiv(A, opts)
    grid = get_option(opts, Option.Grid, None)
    with obs_events.span("getrf::prep", cat="step"):
        r, a = _prep(A) if grid is None else _grid_prep(A, grid)
    perm = None
    dtype_ok = MethodFactor.native_lu_dtype_ok(a.dtype)
    fmethod = get_option(opts, Option.MethodFactor, MethodFactor.Auto)
    if fmethod is MethodFactor.Auto:
        # single-device Auto prefers the TILED carry form: it beats
        # XLA's native LU at every measured size — marginally at
        # n=4096 (10.4 vs 10.9 ms) and ~1.9x at n=8192 (49 vs 94 ms,
        # v5e, PERF.md) — because its trailing updates run as full
        # matmuls while the native kernel's stay inside its own
        # blocked while loop; a measured tune-cache entry can reroute
        from ..tune.select import tuned_method
        cached = tuned_method("getrf", "factor", opts=opts,
                              option=Option.MethodFactor,
                              n=min(a.shape), dtype=a.dtype)
        fmethod = cached if cached is not None \
            and cached is not MethodFactor.Auto else MethodFactor.Tiled
        if fmethod is MethodFactor.Fused \
                and not MethodFactor.native_lu_ok(a.dtype, a.shape[0]):
            # a cached Fused must not bypass the native-kernel safety
            # gates (dtype support, NATIVE_LU_MAX_M scoped-vmem
            # height): size buckets span shapes the probe never ran,
            # so revalidate here; silent (the cache, not the user,
            # asked for Fused)
            fmethod = MethodFactor.Tiled
    elif fmethod is MethodFactor.Fused and not dtype_ok:
        import warnings
        warnings.warn(
            f"getrf: XLA's native LU does not implement {a.dtype}; "
            "falling back to the Tiled blocked path", stacklevel=2)
        fmethod = MethodFactor.Tiled
    elif fmethod is MethodFactor.Fused and \
            not MethodFactor.native_lu_ok(a.dtype, a.shape[0]):
        import warnings
        warnings.warn(
            f"getrf: XLA's native LU cannot compile {a.shape[0]} rows "
            "on TPU (scoped-vmem height limit, methods.NATIVE_LU_MAX_M"
            "); falling back to the Tiled blocked path", stacklevel=2)
        fmethod = MethodFactor.Tiled
    # the route, on the driver span that is already open: the routing
    # layer's record (the blocked forms add their form, nb and panel)
    obs_events.note(lu=method.value, factor=fmethod.value)
    if fmethod is MethodFactor.Fused:
        # single fused XLA program (native blocked LU with partial
        # pivoting); pivots come back in the same LAPACK swap-target
        # convention
        lu, ipiv, _ = jax.lax.linalg.lu(a)
        ipiv = ipiv.astype(jnp.int32)
    elif grid is None and _stored_lo(a.dtype):
        # the lo factor of the mixed solves on one device: the carry
        # form with each panel raised to f32 (`_carry_panel_lo`), the
        # factor stored in its own dtype, the update one lo pass. nb
        # as resolved: no panel here runs the fori kernel's O(w)
        # full-panel passes that the f32 route narrows nb for
        route = _lo_route(opts, r.nb, a.shape, a.dtype)
        obs_events.note(**route)
        lu, ipiv, perm = _getrf_carry(a, route["nb"], lo=True)
    elif grid is not None:
        # across a mesh the factorization, the composed permutation
        # and info are one compiled program: dispatched eagerly every
        # mask and slice of the steps is an array and a launch of its
        # own, and some are whole matrices on one device
        nb = _lu_nb(opts, r.nb, a.shape, grid, dtype=a.dtype)
        lookahead = get_option(opts, Option.Lookahead)
        _count_grid_route(a.shape, nb, r.nb, a.dtype, grid, lookahead)
        with obs_events.span("getrf::grid_factor", cat="step"):
            lu, ipiv, perm, info = _grid_getrf_programs(grid)[1](
                a, nb, lookahead, r.nb, r.m, r.n)
        return LUFactors(dataclasses.replace(r, data=lu,
                                             mtype=MatrixType.General),
                         ipiv, info, perm=perm)
    else:
        lu, ipiv = _getrf_dense(
            a, _lu_nb(opts, r.nb, a.shape, grid, dtype=a.dtype),
            pivot=True, grid=grid,
            lookahead=get_option(opts, Option.Lookahead), tile_nb=r.nb)
    from .info import lu_info
    with obs_events.span("getrf::info", cat="step"):
        info = lu_info(lu, r.m, r.n)
    return LUFactors(dataclasses.replace(r, data=lu,
                                         mtype=MatrixType.General), ipiv,
                     info, perm=perm)


def getrf_nopiv(A: TiledMatrix, opts: OptionsLike = None) -> LUFactors:
    """Reference src/getrf_nopiv.cc (slate.hh:608)."""
    r, a = _prep(A)
    lu, _ = _getrf_dense(a, r.nb, pivot=False,
                         grid=get_option(opts, Option.Grid, None),
                         tile_nb=r.nb)
    ipiv = jnp.arange(min(a.shape), dtype=jnp.int32)
    from .info import lu_info
    return LUFactors(dataclasses.replace(r, data=lu,
                                         mtype=MatrixType.General), ipiv,
                     lu_info(lu, r.m, r.n))


@instrument_driver("getrf_tntpiv")
def getrf_tntpiv(A: TiledMatrix, opts: OptionsLike = None) -> LUFactors:
    """Communication-avoiding tournament-pivot LU (reference
    src/getrf_tntpiv.cc:169-222): per panel, chunked local LUs nominate
    candidate pivot rows, a binary tournament (batched LU per round,
    linalg/ca.py) picks the winners, the winners are swapped to the top
    and the panel factors without further pivoting. Pivot growth is
    CALU's (bounded but weaker than partial pivoting — the documented
    trade); the tournament's sequential depth is log2(m/chunk) batched
    rounds instead of one argmax reduction per column. The beyond-HBM
    twin is ooc.getrf_tntpiv_ooc (ISSUE 10), which uses the same
    selection machinery to keep written factor panels immutable."""
    r, a = _prep(A)
    grid = get_option(opts, Option.Grid, None)
    lu, ipiv = _getrf_dense(a, r.nb, pivot=True, grid=grid,
                            tournament=True, tile_nb=r.nb)
    from .info import lu_info
    return LUFactors(dataclasses.replace(r, data=lu,
                                         mtype=MatrixType.General),
                     ipiv, lu_info(lu, r.m, r.n))


# -- solves ---------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _grid_getrs_program(grid):
    """`getrs` (NoTrans) on `grid` as one compiled program
    (`chol._grid_potrs_program`'s pattern): B's rows permuted by the
    factor's composed permutation and the two sweeps of
    `blocked.trsm_dense`,
    which read the two triangles where they lie in the packed factor:
    no masked copy of it is made."""
    from .blocked import trsm_dense

    def solve(LU, perm, B):
        rl = LU.resolve()
        b = B.to_dense()
        y = _permute_rows(
            jnp.pad(b, ((0, rl.data.shape[0] - b.shape[0]), (0, 0))),
            perm)[:rl.n]
        tri = rl.data[:rl.n, :rl.n]
        y = trsm_dense(tri, y, left=True, lower=True, nb=rl.nb,
                       unit_diagonal=True, grid=grid)
        return _store(B, trsm_dense(tri, y, left=True, lower=False,
                                    nb=rl.nb, grid=grid))

    return jax.jit(solve)


def getrs(F: LUFactors, B: TiledMatrix, opts: OptionsLike = None,
          trans=Op.NoTrans) -> TiledMatrix:
    """Solve using getrf factors (reference src/getrs.cc:88-111:
    permuteRows, trsm(L), trsm(U)).

    trans accepts an Op (NoTrans / Trans / ConjTrans, LAPACK 'N'/'T'/'C')
    or a bool for backward compatibility (True == ConjTrans). For real
    dtypes Trans and ConjTrans coincide."""
    if not isinstance(trans, Op):
        # bool-compat (incl. np.bool_): truthy == ConjTrans
        slate_assert(trans in (True, False),
                     f"trans must be an Op or bool, got {trans!r}")
        trans = Op.ConjTrans if trans else Op.NoTrans
    if F.band:
        # band-convention factors (block-local swaps) need gbtrs's
        # interleaved sweeps
        return gbtrs(F, B, opts, trans=trans)
    grid = get_option(opts, Option.Grid, None)
    if grid is not None and trans is Op.NoTrans:
        if obs_events.enabled():
            from .blocked import grid_blocks, trsm_form
            rl = F.LU.resolve()
            if trsm_form(rl.n, rl.nb) == "scan":
                # the two sweeps, rl.n / rl.nb block steps each
                _count_lu_block_steps(grid_blocks(rl.n, rl.nb, grid),
                                      2 * (rl.n // rl.nb))
        with obs_events.span("getrs::grid_solve", cat="step"):
            # a factor `getrf` made under the grid carries its
            # permutation; any other's swaps are composed here
            perm = F.perm if F.perm is not None else _compose_swaps(
                F.pivots, F.LU.resolve().data.shape[0])
            return _grid_getrs_program(grid)(F.LU, perm, B)
    LU = F.LU
    L = dataclasses.replace(LU, mtype=MatrixType.Triangular,
                            uplo=Uplo.Lower, diag=Diag.Unit)
    U = dataclasses.replace(LU, mtype=MatrixType.Triangular,
                            uplo=Uplo.Upper, diag=Diag.NonUnit)
    with obs_events.span("getrs", cat="step", trans=trans.name):
        if trans is Op.NoTrans:
            X = apply_pivots(F.pivots, B)
            X = trsm(Side.Left, 1.0, L, X, opts)
            X = trsm(Side.Left, 1.0, U, X, opts)
        else:
            flip = (lambda M: M.conj_transpose()) \
                if trans is Op.ConjTrans else (lambda M: M.transpose())
            X = trsm(Side.Left, 1.0, flip(U), B, opts)
            X = trsm(Side.Left, 1.0, flip(L), X, opts)
            X = apply_pivots(F.pivots, X, forward=False)
    return X


@instrument_driver("gesv")
def gesv(A: TiledMatrix, B: TiledMatrix, opts: OptionsLike = None
         ) -> Tuple[LUFactors, TiledMatrix]:
    """Reference src/gesv.cc (slate.hh:507)."""
    from ..utils.trace import phases
    ph = phases(opts)
    with ph("gesv::getrf"):
        F = getrf(A, opts)
    with ph("gesv::getrs"):
        X = getrs(F, B, opts)
    return F, X


def gesv_nopiv(A: TiledMatrix, B: TiledMatrix, opts: OptionsLike = None):
    """Reference slate.hh:516."""
    F = getrf_nopiv(A, opts)
    return F, getrs(F, B, opts)


def getri(F: LUFactors, opts: OptionsLike = None) -> TiledMatrix:
    """Matrix inverse from getrf factors (reference src/getri.cc,
    slate.hh:648, out-of-place variant getriOOP)."""
    n = F.LU.m
    eye = TiledMatrix.from_dense(jnp.eye(n, dtype=F.LU.dtype),
                                 F.LU.mb, F.LU.nb)
    return getrs(F, eye, opts)


# -- mixed precision ------------------------------------------------------

def _lo_getrs(ctx, factors, rhs: jax.Array) -> jax.Array:
    """The lo solve the mixed LU drivers hand `refine`'s programs:
    `getrs` on dense arrays. `factors` = (packed lo LU (padded), its
    composed row permutation), `ctx` = (nb, grid); rhs (n, k) in the
    factor's dtype, and so is the answer. The triangles are read
    where they lie in the packed factor, no masked copy made; on one
    device by `refine.tri_sweep`, whose program does not grow with n."""
    from .refine import lo_work_dtype, tri_sweep
    lu, perm = factors
    nb, grid = ctx
    n = rhs.shape[0]
    y = _permute_rows(jnp.pad(rhs, ((0, lu.shape[0] - n), (0, 0))), perm)
    if grid is not None:
        from .blocked import trsm_dense
        y = trsm_dense(lu, y, left=True, lower=True, nb=nb,
                       unit_diagonal=True, grid=grid)
        return trsm_dense(lu, y, left=True, lower=False, nb=nb,
                          grid=grid)[:n]
    y = y.astype(lo_work_dtype(lu.dtype))
    y = tri_sweep(lu, y, lower=True, nb=nb, unit_diagonal=True)
    return tri_sweep(lu, y, lower=False, nb=nb)[:n].astype(rhs.dtype)


def _mixed_factor(name: str, A: TiledMatrix, opts: OptionsLike):
    """What `gesv_mixed` and `gesv_mixed_gmres` share: A demoted once
    (`<name>::demote`), factored through `getrf`'s own routing
    (`<name>::factor`), and the lo solve's operands with the swaps
    composed to a permutation once a call. Returns (F, ctx, factors)
    and notes the route on the driver span."""
    from ..utils.trace import phases
    from .refine import demote
    A_lo = demote(name, A, opts)
    with phases(opts)(name + "::factor"):
        F = getrf(A_lo, opts)
        perm = F.perm if F.perm is not None else _compose_swaps(
            F.pivots, F.LU.data.shape[0])
    obs_events.note(lo=str(A_lo.dtype))
    if F.perm is not None and obs_events.enabled():
        # the lo carry form made it: its route on this span too
        obs_events.note(factor=MethodFactor.Tiled.value, **_lo_route(
            opts, F.LU.nb, F.LU.data.shape, F.LU.dtype))
    return F, (F.LU.nb, get_option(opts, Option.Grid, None)), \
        (F.LU.data, perm)


@instrument_driver("gesv_mixed")
def gesv_mixed(A: TiledMatrix, B: TiledMatrix, opts: OptionsLike = None):
    """Mixed-precision LU with iterative refinement (reference
    src/gesv_mixed.cc:24-40: lo-precision factor + hi-precision residual
    refinement, fallback to full precision on non-convergence).

    The first lo solve and the refinement are two compiled programs
    reused by every later call at the shape (`refine.py`); the host
    reads their verdict once and decides: converged, or the f32
    `getrf` / `getrs` called as a caller would.

    Returns (factors_lo, X, iters) where iters < 0 means the fallback
    full-precision solve produced X (reference info semantics)."""
    from .refine import iterative_refinement
    F, ctx, factors = _mixed_factor("gesv_mixed", A, opts)
    obs_events.note(refine="ir")

    def full_solve():
        return getrs(getrf(A, opts), B, opts).to_dense()

    x, iters = iterative_refinement(A, B, _lo_getrs, ctx, factors,
                                    full_solve, opts, name="gesv_mixed")
    return F, _store(B, x), iters


@instrument_driver("gesv_mixed_gmres")
def gesv_mixed_gmres(A: TiledMatrix, B: TiledMatrix,
                     opts: OptionsLike = None):
    """Mixed-precision FGMRES-IR (reference src/gesv_mixed_gmres.cc:
    restarted FGMRES, restart=min(30, itermax, mb-1), right-
    preconditioned by the lo-precision LU solve). Single-RHS like the
    reference."""
    from .refine import fgmres_ir
    slate_assert(B.shape[1] == 1,
                 "gesv_mixed_gmres supports one right-hand side "
                 "(reference gesv_mixed_gmres.cc nrhs==1 limitation)")
    F, ctx, factors = _mixed_factor("gesv_mixed_gmres", A, opts)
    obs_events.note(refine="fgmres")

    def full_solve():
        return getrs(getrf(A, opts), B, opts).to_dense()

    x, iters = fgmres_ir(A, B, _lo_getrs, ctx, factors, full_solve,
                         restart_cap=max(A.resolve().mb - 1, 1),
                         opts=opts, name="gesv_mixed_gmres")
    return F, _store(B, x), iters


# -- random butterfly transform ------------------------------------------

def _butterfly_diag(key, n: int, depth: int, dtype):
    """Random diagonals for a depth-d recursive butterfly (reference
    src/rbt_generate / internal_gerbt.cc). Entries exp(r/10), r~U(-0.5,0.5)
    following the RBT literature."""
    ks = jax.random.split(key, depth)
    return [jnp.exp(jax.random.uniform(ks[d], (n,), minval=-0.05,
                                       maxval=0.05)).astype(dtype)
            for d in range(depth)]


def _apply_butterfly(diags, x, transpose=False):
    """y = W x (or W^T x) where W is the depth-d recursive butterfly.

    One level on a block [t; b] with half-diagonals R0 = diag(r_top),
    R1 = diag(r_bot):
        W  [t;b] = s [R0 t + R1 b ; R0 t - R1 b],  s = 1/sqrt(2)
        W^T[t;b] = s [R0 (t + b) ; R1 (t - b)]
    Levels compose W = W_1 W_2 ... W_d (level lvl acts on 2^lvl blocks);
    the transpose applies levels in reverse order.
    """
    squeeze = x.ndim == 1
    y = x[:, None] if squeeze else x
    n = y.shape[0]
    depth = len(diags)
    s = jnp.asarray(1 / jnp.sqrt(2.0), y.dtype)
    levels = list(range(depth))
    order = reversed(levels) if transpose else levels
    for lvl in order:
        r = diags[lvl]
        nblk = 2 ** lvl
        blk = n // nblk
        half = blk // 2
        yb = y.reshape(nblk, blk, -1)
        rb = r.reshape(nblk, blk, 1)
        t, b = yb[:, :half], yb[:, half:]
        r0, r1 = rb[:, :half], rb[:, half:]
        if not transpose:
            top = r0 * t + r1 * b
            bot = r0 * t - r1 * b
        else:
            top = r0 * (t + b)
            bot = r1 * (t - b)
        y = (s * jnp.concatenate([top, bot], axis=1)).reshape(n, -1)
    return y[:, 0] if squeeze else y


@instrument_driver("gesv_rbt")
def gesv_rbt(A: TiledMatrix, B: TiledMatrix, opts: OptionsLike = None,
             seed: int = 0):
    """Random Butterfly Transform solver (reference src/gesv_rbt.cc,
    src/gerbt.cc): A' = U^T A V with random butterflies, LU *without
    pivoting* on A', then x = V y — pivoting avoided with high
    probability; one step of iterative refinement like the reference."""
    depth = get_option(opts, Option.Depth, 2)
    r = A.resolve()
    n = r.m
    # pad to 2^depth multiple for clean halving
    mult = 2 ** depth
    npad = ceil_div(n, mult) * mult
    a = jnp.pad(A.to_dense(), ((0, npad - n), (0, npad - n)))
    a = a + jnp.diag(jnp.where(jnp.arange(npad) >= n,
                               jnp.ones(npad, a.dtype), 0))
    b = jnp.pad(B.to_dense(), ((0, npad - B.resolve().m), (0, 0)))
    key = jax.random.PRNGKey(seed)
    ku, kv = jax.random.split(key)
    du = _butterfly_diag(ku, npad, depth, a.dtype)
    dv = _butterfly_diag(kv, npad, depth, a.dtype)
    # A' = W_u A W_v; then A x = b  <=>  A' y = W_u b with x = W_v y
    au = _apply_butterfly(du, a)                          # W_u A (rows)
    av = _apply_butterfly(dv, au.T, transpose=True).T     # ... @ W_v (cols)
    Ap = TiledMatrix.from_dense(av, r.mb, r.nb)
    F = getrf_nopiv(Ap, opts)

    def solve_rbt(rhs):
        bu = _apply_butterfly(du, rhs)
        Y = getrs(F, TiledMatrix.from_dense(bu, B.mb, B.nb), opts)
        return _apply_butterfly(dv, Y.to_dense())

    x = solve_rbt(b)
    # one refinement step on the original system (reference gesv_rbt.cc)
    res = b - jnp.matmul(a, x, precision=jax.lax.Precision.HIGHEST)
    x = x + solve_rbt(res)
    if _rguard.checks_enabled():
        # sentinel-gated degradation rung (resil/, ISSUE 9): the
        # no-pivot RBT factorization breaks down with small
        # probability (an exactly/near-singular leading block after
        # the butterflies) and surfaces as non-finite entries in the
        # solution; step DOWN to partial-pivot gesv instead of
        # returning poison. Gated on enable_checks because the
        # finiteness read synchronizes on x (guard.check_panel doc).
        try:
            _rguard.check_panel("gesv_rbt", 0, x)
        except _rguard.PanelHealthError as e:
            _rguard.record_escalation("rbt_to_getrf", op="gesv_rbt",
                                      reason=e.reason)
            return gesv(A, B, opts)
    X = _store(B, x[:B.resolve().m])
    return F, X


# -- band LU --------------------------------------------------------------

def _use_band_path(A: TiledMatrix) -> bool:
    from .band import band_is_narrow, band_width_of
    r = A.resolve()
    # windowed gbtrf assumes a square matrix (identity-padded windows);
    # rectangular band inputs take the dense fallback
    return A.mtype is MatrixType.GeneralBand and r.kl >= 0 \
        and r.m == r.n and band_is_narrow(r.n, r.nb, band_width_of(r))


def gbtrf(A: TiledMatrix, opts: OptionsLike = None) -> LUFactors:
    """Band LU with partial pivoting (reference src/gbtrf.cc,
    slate.hh:594). Narrow bands run the real O(n*kl*(kl+ku)) windowed
    algorithm (linalg/band.py); pivoting grows the upper bandwidth to
    kl+ku (LAPACK gbtrf fill-in) and the band tags are widened. The
    band factor's L blocks are NOT retroactively permuted across
    blocks (gbtrf convention), so solves must go through gbtrs, which
    replays the blocked swap interleaving."""
    if _use_band_path(A):
        from .band import gbtrf_band
        from .info import lu_info
        r, a = _prep(A)
        lu, ipiv = gbtrf_band(a, r.n, r.nb, r.kl, r.ku)
        out = dataclasses.replace(r, data=lu,
                                  mtype=MatrixType.GeneralBand,
                                  kl=r.kl, ku=r.kl + r.ku)
        return LUFactors(out, ipiv, lu_info(lu, r.m, r.n), band=True)
    F = getrf(A, opts)
    if A.mtype is MatrixType.GeneralBand:
        lu = dataclasses.replace(F.LU, mtype=MatrixType.GeneralBand,
                                 kl=A.kl, ku=A.kl + A.ku)
        return LUFactors(lu, F.pivots, F.info)
    return F


def gbtrs(F: LUFactors, B: TiledMatrix, opts: OptionsLike = None,
          trans=Op.NoTrans) -> TiledMatrix:
    """Reference slate.hh:622. trans as in getrs (Op or bool).

    Band factors (from the windowed gbtrf) use the interleaved blocked
    sweeps: forward swaps+L solve then the U band backward solve
    (LAPACK gbtrs structure); dense factors route through getrs."""
    if not isinstance(trans, Op):
        slate_assert(trans in (True, False),
                     f"trans must be an Op or bool, got {trans!r}")
        trans = Op.ConjTrans if trans else Op.NoTrans
    A = F.LU
    if F.band:
        from .band import (band_trsm_lower, band_trsm_upper,
                           gb_backward_solve_trans, gb_forward_solve)
        r = A.resolve()
        lu_d = r.data
        b = B.to_dense()
        kl = r.kl
        kband = r.ku          # already widened to kl+ku by gbtrf
        if trans is Op.NoTrans:
            y = gb_forward_solve(lu_d, F.pivots, b, r.n, r.nb, kl)
            x = band_trsm_upper(lu_d, y, r.n, r.nb, kband)
        else:
            conj = trans is Op.ConjTrans
            u_as_lower = jnp.conj(lu_d.T) if conj else lu_d.T
            y = band_trsm_lower(u_as_lower, b, r.n, r.nb, kband)
            x = gb_backward_solve_trans(lu_d, F.pivots, y, r.n, r.nb,
                                        kl, conj)
        return _store(B, x)
    return getrs(F, B, opts, trans=trans)


def gbsv(A: TiledMatrix, B: TiledMatrix, opts: OptionsLike = None):
    """Reference slate.hh:499."""
    F = gbtrf(A, opts)
    return F, gbtrs(F, B, opts)


def getriOOP(F: LUFactors, opts: OptionsLike = None) -> TiledMatrix:
    """Out-of-place inverse variant (reference getriOOP, slate.hh:654).
    The functional design is always out-of-place; kept for API parity."""
    return getri(F, opts)
