"""Out-of-core (out-of-HBM) streaming drivers — the huge-n duty of
SURVEY §2.3.8: matrices larger than accelerator memory live in HOST
memory and stream through the chip one column panel at a time.

Reference analogue: SLATE keeps the global matrix distributed and
streams remote tiles through per-device workspace with receive counts
and `releaseRemoteWorkspace` (BaseMatrix.hh:462-479, potrf.cc:179-192)
— residency is managed per tile. XLA owns residency inside one jitted
program, so the TPU-native equivalent hoists the streaming OUTSIDE
jit: a host loop moves one panel (and one visiting block per
left-looking update) host<->device around small jitted kernels, and
the factor accumulates on the host. HBM footprint is O(n * panel_cols)
instead of O(n^2).

Algorithm (potrf_ooc): classic left-looking out-of-core Cholesky —
for each column panel k: S = A[k0:, k0:k1]; for every previous panel
j: S -= L_j[k0:, :] L_j[k0:k1, :]^H (one streamed visit of L_j's
rows); then factor the panel in-core (diag cholesky + one triangular
solve). Per-panel transfer volume is O(n * panel_cols * nt) reads —
the unavoidable left-looking revisit — and one panel write.

getrf_ooc / geqrf_ooc extend the same left-looking schedule to LU and
QR (reference src/getrf.cc:327 / src/geqrf.cc:26 operate at any n the
cluster's aggregate memory holds; one TPU chip reaches the same
regime by streaming through host RAM):

- getrf_ooc: panel k is staged as it lies and put into the CURRENT
  row order on the chip, visited by every earlier factor panel (U12
  strip by one unit-lower solve + trailing rank-w update), then
  factored in-core with partial pivoting CONFINED to the resident
  panel (the standard left-looking OOC-LU pivot discipline —
  LAPACK's out-of-core prototypes and CALU's panel-local search
  share it). The panel is written once, in the order it was factored
  in; a later visit gathers it on the chip into the order of its day,
  and one repair at the end puts the stored rows into the final
  order (PR 47: the host moves no row).
  getrf_tntpiv_ooc (ISSUE 10) is the CALU alternative arbitrated by
  the ``ooc/lu_pivot`` tunable: tournament pivot selection finalizes
  each panel's permutation BEFORE its column is written, the factor
  is stored in original row order with the permutation applied at
  visit time by a device gather — checkpointable, and shardable
  (dist/shard_ooc.shard_getrf_ooc).
- geqrf_ooc: panel k is visited by every earlier panel's compact-WY
  reflector block (V and T rebuilt on the fly from the packed factor
  + taus, exactly like the in-core path), then factored in-core with
  the native panel kernel. No pivoting, so no host-side fixups.
- Both visits run as ONE jitted fixed-shape kernel with a traced
  panel offset (dynamic_slice / masked updates), so the whole stream
  compiles O(1) programs per (panel-width) shape combination, not
  O(nt^2).

Solves stream the same way: getrs_ooc replays pivots then streams
each factor panel twice (unit-lower forward sweep, upper backward
sweep); potrs_ooc runs the non-unit forward sweep then the
conjugate-transposed backward sweep of the Cholesky factor; gels_ooc
applies Q^H by streaming reflector panels against a device-resident
RHS block, then back-substitutes R. posv_ooc/gesv_ooc bundle
factor+solve, so all three north-star families (posv/gesv/gels)
run end-to-end beyond HBM.

gemm_ooc streams A's row panels against a device-resident B (the
common tall-A case); C streams back per panel.

All drivers stream through the shared engine (stream.py, ISSUE 4):
an HBM-budget-aware panel-residency cache (left-looking revisits
served from device memory instead of re-uploaded — O(nt) panel
uploads instead of O(nt^2/2) when the factor fits the budget), async
double-buffered H2D prefetch, and a background D2H writer that
overlaps each panel's writeback with the next panel's visit stream.
The frozen budget default is 0 (cache off) — bit-identical to the
pre-engine schedule; see stream.py's module doc for the contract.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tiles import ceil_div
from ..obs import events as obs_events
from ..obs import health as _health
from ..obs import ledger as _ledger
from ..obs import metrics as obs_metrics
from ..obs.events import instrument_driver
from ..resil import checkpoint as _rckpt
from ..resil import faults as _rfaults
from ..resil import guard as _rguard
# the expander-temps estimate and cap are shared with the in-core
# trsm safety valve (blocked.py)
from .blocked import SOLVE_TEMP_CAP
from .blocked import solve_temps_bytes as _solve_temps_bytes
# the streaming engine (panel-residency cache + async H2D/D2H
# pipeline) and the staging primitives every transfer goes through —
# _h2d/_d2h moved to stream.py with the engine but keep their old
# names here (tests and PERF.md reference ooc._h2d/ooc._d2h)
from . import stream
from .stream import _d2h, _h2d

_HI = jax.lax.Precision.HIGHEST


def _panel_cols(panel_cols: Optional[int], n: int, dtype=None) -> int:
    """Streaming panel width: explicit argument > measured tune-cache
    entry for op "ooc" > the shipped default in the FROZEN table
    (tune/cache.py, 8192 — the single source of truth, no literal
    here). Every OOC driver's `panel_cols=None` default resolves
    through here, so the width probed by `bench.py --tune` applies
    fleet-wide without touching call sites."""
    if panel_cols:
        return int(panel_cols)
    from ..tune.select import resolve
    return int(resolve("ooc", "panel_cols", n=n, dtype=dtype))


def _resolve_precision(precision, n: int, dtype):
    """Precision arbitration for the streaming drivers (ISSUE 12):
    explicit ``precision`` argument > measured ``ooc/precision`` tune
    entry > FROZEN "f32" (core/methods.MethodPrecision — a COLD CACHE
    keeps the full-precision stream bit-identically; bf16 is earned
    or explicit, pinned by test). Returns the LO dtype the mixed
    update path runs in (refine.lo_dtype — bf16 for f32 input, f32
    for f64), or None for the full-precision path — also when the
    input dtype has no lower pair (complex64 etc. demote to Full
    rather than erroring: precision is a performance mode, not a
    contract change)."""
    from ..core.methods import MethodPrecision, str2method
    m = precision if precision is not None else MethodPrecision.Auto
    if isinstance(m, str):
        m = str2method("precision", m)
    if m is MethodPrecision.Auto:
        m = MethodPrecision.resolve(n, dtype)
    if m is not MethodPrecision.Mixed:
        return None
    from .refine import lo_dtype
    lo = np.dtype(lo_dtype(dtype))
    return None if lo == np.dtype(dtype) else lo


def _herm_operand(a: np.ndarray) -> np.ndarray:
    """The Hermitian residual operator for posv_ooc's refinement:
    potrf_ooc reads only the LOWER triangle, so a caller may store
    garbage above the diagonal — the refinement's host residual
    (refine.host_ir's ``b - a @ x``) must not. Symmetric storage
    (the common case) is returned as-is, zero copies; triangle-only
    storage mirrors the designated triangle once (one host copy of
    A — the price of refining a half-stored operand). The symmetry
    check runs in row-panel chunks so the common symmetric case
    allocates no matrix-sized temporary (an OOC-scale host barely
    holds the matrix itself)."""
    n = a.shape[0]
    step = max(1, (1 << 24) // max(n, 1))     # ~16M elements/chunk
    herm = True
    for i0 in range(0, n, step):
        i1 = min(i0 + step, n)
        other = a[:, i0:i1].T
        if np.iscomplexobj(a):
            other = np.conj(other)
        if not np.array_equal(a[i0:i1], other):
            herm = False
            break
    if herm:
        return a
    L = np.tril(a)
    return L + np.conj(np.tril(a, -1).T)


def _precision_meta(lo) -> str:
    """The resolved precision mode as recorded in checkpoint meta
    (resil/checkpoint.py extra_meta — part of the identity guard, so
    a resume under a DIFFERENT ``ooc/precision`` starts fresh instead
    of mixing lo-updated and full-updated durable panels)."""
    return "full" if lo is None else np.dtype(lo).name


def _shard_escalate(primary, fallback, op: str, grid):
    """shard_to_stream rung of the resil degradation ladder, gated to
    SINGLE-PROCESS meshes: there a transient sharded-layer failure
    steps down to the local single-engine stream (recorded + counted
    by guard.record_escalation). On a multi-process mesh the failure
    PROPAGATES instead — one host rerouting unilaterally would desert
    the broadcast collective its peers are blocked in (only injected
    faults fail in lockstep; real ones are one-sided) — and
    coordinated mesh-wide degradation is the serving daemon's policy
    layer (ROADMAP)."""
    multi = len({d.process_index
                 for d in grid.mesh.devices.flat}) > 1
    if multi:
        return primary()
    return _rguard.escalate(primary, fallback, "shard_to_stream",
                            op=op)


def _route_shard(n: int, nt: int, grid, method, dtype):
    """Grid arbitration for the streaming drivers (ISSUE 7): True
    when the call should take the sharded layer (dist/shard_ooc.py).
    Explicit ``method`` wins; ``Auto`` (or None) resolves through the
    tune cache (core/methods.MethodOOC — the FROZEN ``ooc/shard_method``
    default is "stream", so a COLD CACHE keeps the single-device
    stream path bit-identically even with a grid supplied; pinned by
    test). No grid always means the stream path."""
    if grid is None:
        return False
    from ..core.methods import MethodOOC, str2method
    m = method if method is not None else MethodOOC.Auto
    if isinstance(m, str):
        m = str2method("ooc", m)
    if m is MethodOOC.Auto:
        m = MethodOOC.resolve(n, nt, grid.nprocs, dtype)
    return m is MethodOOC.Sharded


@functools.partial(jax.jit, static_argnames=("w",))
def _panel_apply(S: jax.Array, Lj: jax.Array, w: int) -> jax.Array:
    """S -= L_j L_j_top^H for one visiting panel block (left-looking
    update): Lj is (m, wj) = rows k0: of an earlier factor panel,
    whose top w rows align with S's columns."""
    top = Lj[:w]
    return S - jnp.matmul(Lj, jnp.conj(top.T), precision=_HI)


#: Above this estimate of the TriangularSolve expander's progressive
#: output copies (bytes), the streamed solves switch to
#: invert-the-diag-block + one matmul (their triangles are
#: Cholesky/unit-LU diagonal blocks; hardware-validated at n=65536).
#: Measured: the direct solve of a (57344, 8192) below-block at
#: n=65536/panel=8192 holds 55.4 GB of HLO temps on a 16 GB part.
#: One shared value with the in-core trsm valve (blocked.py) —
#: re-exported under this name so tests can pin the OOC gates alone.
OOC_SOLVE_TEMP_CAP = SOLVE_TEMP_CAP

#: Cap on the tournament-LU stream's device-resident permutation
#: index vectors (int32, 4m bytes each — getrf_tntpiv_ooc._g): 256
#: entries bound the pin to ~1 GB even at m=2^20 while covering the
#: most-revisited low panels; past it a visit re-uploads (~1/w of
#: the visit's panel bytes).
_GDEV_MAX = 256


@functools.partial(jax.jit, static_argnames=("w",))
def _panel_factor(S: jax.Array, w: int) -> jax.Array:
    """Factor one (m, w) column panel in-core: diag cholesky, then the
    below-block by one right-side triangular solve (matmul-rate,
    backward stable) — or, when the solve's expander temps would
    exceed OOC_SOLVE_TEMP_CAP, by invert-then-matmul on the diag block
    (blocked.invert_triangular leaf/recursion; same error constants as
    the grid-path trsm_left, blocked.py)."""
    m = S.shape[0]
    lkk = jnp.tril(jax.lax.linalg.cholesky(S[:w], symmetrize_input=False))
    if m > w:
        if _solve_temps_bytes(m - w, w, S.dtype.itemsize) \
                > OOC_SOLVE_TEMP_CAP:
            from .blocked import invert_triangular
            linv = invert_triangular(lkk, lower=True)
            pan = jnp.matmul(S[w:], jnp.conj(linv.T), precision=_HI)
        else:
            pan = jax.lax.linalg.triangular_solve(
                lkk, S[w:], left_side=False, lower=True,
                transpose_a=True, conjugate_a=True)
        return jnp.concatenate([lkk, pan], axis=0)
    return lkk


# -- mixed-precision visit kernels (ISSUE 12) -----------------------------
#
# The bf16 streaming mode's arithmetic contract: panels FACTOR in the
# input dtype (the critical path keeps full precision), visiting
# factor panels arrive in the LO dtype (staged/resident/broadcast at
# half the bytes — linalg/stream.py's demote helpers), and the
# trailing-matrix products run with lo inputs accumulating in the
# full dtype (`preferred_element_type` — the MXU's native
# bf16 x bf16 -> f32 contraction, the reduced-precision play of the
# TPU distributed-linalg paper). The small w x w diagonal blocks the
# strip solves need are promoted to full precision INSIDE the kernels
# (triangular solves are not bf16 territory); the accumulator panel S
# stays full-precision throughout. Each kernel is the mixed twin of
# the f32 kernel directly above it — the f32 path never routes here
# (bit-identity pin).


@functools.partial(jax.jit, static_argnames=("w",))
def _panel_apply_mx(S: jax.Array, Lj: jax.Array, w: int) -> jax.Array:
    """Mixed twin of _panel_apply: Lj arrives in the lo dtype, the
    rank-w product accumulates in S's dtype."""
    top = Lj[:w]
    return S - jnp.matmul(Lj, jnp.conj(top.T), precision=_HI,
                          preferred_element_type=S.dtype)


@functools.partial(jax.jit, static_argnames=("unit",))
def _lu_visit_mx(S: jax.Array, Lj: jax.Array, j0, unit: bool = True
                 ) -> jax.Array:
    """Mixed twin of _lu_visit (LU left-looking visit AND the
    non-unit forward sweep of the streamed solves): the U12 strip
    solve runs in full precision against the promoted diagonal block,
    the trailing rank-w product with lo inputs."""
    m, w = S.shape
    wj = Lj.shape[1]
    lo = Lj.dtype
    rows = jnp.arange(m)
    Ljj = jax.lax.dynamic_slice(Lj, (j0, 0), (wj, wj)).astype(S.dtype)
    Sj = jax.lax.dynamic_slice(S, (j0, 0), (wj, w))
    if _solve_temps_bytes(w, wj, S.dtype.itemsize) > OOC_SOLVE_TEMP_CAP:
        from .blocked import invert_triangular
        linv = invert_triangular(Ljj, lower=True, unit_diagonal=unit)
        U = jnp.matmul(linv, Sj, precision=_HI)
    else:
        U = jax.lax.linalg.triangular_solve(
            Ljj, Sj, left_side=True, lower=True, unit_diagonal=unit)
    below = jnp.where((rows >= j0 + wj)[:, None], Lj, 0)
    S = S - jnp.matmul(below, U.astype(lo), precision=_HI,
                       preferred_element_type=S.dtype)
    return jax.lax.dynamic_update_slice(S, U, (j0, 0))


@jax.jit
def _lu_visit_orig_mx(S: jax.Array, Lj: jax.Array, g: jax.Array, j0
                      ) -> jax.Array:
    """Mixed twin of _lu_visit_orig (the tournament stream's
    original-row-order visit): same gathers, mixed inner visit."""
    Sp = jnp.take(S, g, axis=0)
    Lp = jnp.take(Lj, g, axis=0)
    Sp = _lu_visit_mx(Sp, Lp, j0)
    return jnp.zeros_like(S).at[g].set(Sp)


@jax.jit
def _lu_back_visit_mx(S: jax.Array, Pk: jax.Array, k0) -> jax.Array:
    """Mixed twin of _lu_back_visit (the backward U sweep)."""
    m, w = S.shape
    wk = Pk.shape[1]
    lo = Pk.dtype
    rows = jnp.arange(m)
    Ukk = jax.lax.dynamic_slice(Pk, (k0, 0), (wk, wk)).astype(S.dtype)
    Sk = jax.lax.dynamic_slice(S, (k0, 0), (wk, w))
    if _solve_temps_bytes(w, wk, S.dtype.itemsize) > OOC_SOLVE_TEMP_CAP:
        from .blocked import invert_triangular
        uinv = invert_triangular(Ukk, lower=False)
        X = jnp.matmul(uinv, Sk, precision=_HI)
    else:
        X = jax.lax.linalg.triangular_solve(
            Ukk, Sk, left_side=True, lower=False, unit_diagonal=False)
    above = jnp.where((rows < k0)[:, None], Pk, 0)
    S = S - jnp.matmul(above, X.astype(lo), precision=_HI,
                       preferred_element_type=S.dtype)
    return jax.lax.dynamic_update_slice(S, X, (k0, 0))


@jax.jit
def _chol_back_visit_mx(S: jax.Array, Pk: jax.Array, k0) -> jax.Array:
    """Mixed twin of _chol_back_visit (the backward L^H sweep of the
    streamed Cholesky solve)."""
    m, w = S.shape
    wk = Pk.shape[1]
    lo = Pk.dtype
    rows = jnp.arange(m)
    Lkk = jax.lax.dynamic_slice(Pk, (k0, 0), (wk, wk)).astype(S.dtype)
    Sk = jax.lax.dynamic_slice(S, (k0, 0), (wk, w))
    below = jnp.where((rows >= k0 + wk)[:, None], Pk, 0)
    corr = jnp.matmul(jnp.conj(below.T), S.astype(lo), precision=_HI,
                      preferred_element_type=S.dtype)
    if _solve_temps_bytes(w, wk, S.dtype.itemsize) > OOC_SOLVE_TEMP_CAP:
        from .blocked import invert_triangular
        linv = invert_triangular(Lkk, lower=True)
        X = jnp.matmul(jnp.conj(linv.T), Sk - corr, precision=_HI)
    else:
        X = jax.lax.linalg.triangular_solve(
            Lkk, Sk - corr, left_side=True, lower=True,
            transpose_a=True, conjugate_a=True)
    return jax.lax.dynamic_update_slice(S, X, (k0, 0))


@functools.partial(jax.jit, static_argnames=("trans",))
def _qr_visit_mx(S: jax.Array, Pj: jax.Array, tauj: jax.Array, j0,
                 trans: bool = True) -> jax.Array:
    """Mixed twin of _qr_visit: V unmasked from the lo packed panel,
    T rebuilt in full precision from the promoted V (the w x w T
    algebra is not bf16 territory), the two tall matmuls with lo
    inputs accumulating full."""
    from .qr import _larft, _panel_V
    lo = Pj.dtype
    V = _panel_V(Pj, j0)
    T = _larft(V.astype(S.dtype), tauj)
    W = jnp.matmul(jnp.conj(V.T), S.astype(lo), precision=_HI,
                   preferred_element_type=S.dtype)
    W = jnp.matmul(jnp.conj(T.T) if trans else T, W, precision=_HI)
    return S - jnp.matmul(V, W.astype(lo), precision=_HI,
                          preferred_element_type=S.dtype)


@instrument_driver("potrf_ooc")
def potrf_ooc(a: np.ndarray, panel_cols: Optional[int] = None,
              cache_budget_bytes=None, grid=None,
              method=None, ckpt_path: Optional[str] = None,
              ckpt_every: Optional[int] = None,
              precision=None) -> np.ndarray:
    """Lower Cholesky of a host-resident Hermitian matrix (lower
    triangle read), streaming one column panel through the accelerator
    at a time. Returns the host-resident lower factor; n is bounded by
    host RAM, not HBM.

    Streaming runs through the engine (stream.py): factored panels
    enter the residency cache at factor time (zero re-upload when the
    factor fits the budget — O(nt) panel uploads instead of the
    left-looking O(nt^2/2)), the next input panel prefetches while
    the current one factors, and each panel's writeback overlaps the
    next panel's visit stream. `cache_budget_bytes` 0 (the frozen
    default) reproduces the uncached schedule bit-identically.

    With a ``grid`` (ProcessGrid) the call arbitrates through
    core/methods.MethodOOC (``method`` explicit > tuned
    ``ooc/shard_method`` > frozen "stream"): the Sharded route runs
    the 2D-block-cyclic multi-host stream (dist/shard_ooc.py, bitwise
    the same factor); the cold-cache default keeps this single-device
    path bit-identically.

    ``ckpt_path``/``ckpt_every`` (resil/, ISSUE 9): panel-granular
    durable snapshots — the factor accumulates in a memory-mapped
    file under `ckpt_path` and the committed epoch advances every
    `ckpt_every` panels, so a crashed stream resumes mid-
    factorization to a BITWISE-equal factor (the left-looking visits
    recompute panel k from the input plus durable factors 0..k-1).
    Default off (FROZEN ``resil/ckpt_every`` = 0): no file is
    touched and the stream is bit-identical to the pre-resil driver.

    ``precision`` (ISSUE 12): the mixed-precision mode, resolved
    explicit > tuned ``ooc/precision`` > FROZEN "f32"
    (core/methods.MethodPrecision — a cold cache keeps this
    full-precision body bit-identically, pinned by test). Under
    "bf16" the panel FACTOR stays f32 (critical path) but the
    left-looking visits stage, cache, and multiply the earlier factor
    panels in bf16 (stream.demote_host/demote_dev + _panel_apply_mx),
    halving revisit H2D bytes and doubling the panels a cache budget
    holds; the returned factor is f32 with bf16-grade update error —
    posv_ooc's refinement (or an explicit f32 rerun) is the accuracy
    contract.
    """
    a = np.asarray(a)
    n = a.shape[0]
    panel_cols = _panel_cols(panel_cols, n, a.dtype)
    nt = ceil_div(n, panel_cols)
    lo = _resolve_precision(precision, n, a.dtype)
    if _route_shard(n, nt, grid, method, a.dtype):
        from ..dist.shard_ooc import shard_potrf_ooc
        # guarded route (resil degradation ladder): a transient
        # sharded-layer failure steps DOWN to the single-engine
        # stream instead of dying — single-process meshes only
        # (_shard_escalate doc)
        return _shard_escalate(
            lambda: shard_potrf_ooc(
                a, grid, panel_cols=panel_cols,
                cache_budget_bytes=cache_budget_bytes,
                ckpt_path=ckpt_path, ckpt_every=ckpt_every,
                precision=precision),
            lambda: potrf_ooc(a, panel_cols, cache_budget_bytes,
                              ckpt_path=ckpt_path,
                              ckpt_every=ckpt_every,
                              precision=precision),
            "potrf_ooc", grid)
    ck = _rckpt.maybe_checkpointer(
        ckpt_path, "potrf_ooc", a, panel_cols, nt, every=ckpt_every,
        extra_meta={"precision": _precision_meta(lo)})
    if ck is not None:
        out = ck.factor
    else:
        # the factor's host buffer, mapped and not touched
        # (np.zeros_like would fill all n^2 elements before the first
        # panel is staged): the strictly upper blocks are never
        # written and never read (a factor panel is staged from its
        # diagonal block down and zero-embedded on the device, PR
        # 34), so their pages are never faulted in; the writer's
        # _d2h threads first touch the rest
        with obs_events.span("ooc::alloc", cat="staging",
                             bytes=int(a.nbytes)):
            out = np.zeros(a.shape, a.dtype)
    eng = stream.engine_for(n, panel_cols, a.dtype,
                            budget_bytes=cache_budget_bytes,
                            resident_dtype=lo)
    # the mixed path's loader demotion + visit kernel; the f32 path
    # keeps the identity loader and the exact PR 11 kernel
    ld = stream.host_demoter(lo)
    visit = _panel_apply if lo is None else _panel_apply_mx
    epoch0 = ck.epoch if ck is not None else 0
    led = _ledger.recorder("potrf_ooc", nt=nt, spill_dir=ckpt_path)
    # the panel loop's four phases, named as the ledger names them;
    # panel k's state passes between them through S_live and F, and
    # _writeback drops it before panel k+1 is staged
    S_live, F = {}, {}

    def _stage(k):
        _rfaults.check("step", op="potrf_ooc", step=k)
        k0 = k * panel_cols
        k1 = min(k0 + panel_cols, n)
        with _ledger.frame("stage"):
            S_live[k] = eng.fetch("A", k, lambda: a[k0:, k0:k1],
                                  cache=False)               # H2D
    def _update(k, j):
        k0 = k * panel_cols
        w = min(k0 + panel_cols, n) - k0
        j0 = j * panel_cols
        j1 = min(j0 + panel_cols, n)
        if eng.caching:
            # cached entries are full-height columns (rows above the
            # diagonal block are exact zeros in the lower factor:
            # staged from the diagonal block down, embedded on the
            # device), served sliced to rows k0: — the same
            # (n-k0, wj) block the upload path ships
            with _ledger.frame("stage"):
                Lj = eng.fetch("L", j,
                               lambda j0=j0, j1=j1:
                               ld(out[j0:, j0:j1]),
                               view=(k0, n - k0), embed=(j0, n))
        else:
            with _ledger.frame("stage"):
                Lj = eng.fetch(
                    "L", j,
                    lambda j0=j0, j1=j1: ld(out[k0:, j0:j1]))
        if j + 1 < k:
            j2, j3 = (j + 1) * panel_cols, \
                min((j + 2) * panel_cols, n)
            if eng.caching:
                eng.prefetch("L", j + 1,
                             lambda j2=j2, j3=j3:
                             ld(out[j2:, j2:j3]), embed=(j2, n))
            else:
                eng.prefetch("L", j + 1,
                             lambda j2=j2, j3=j3:
                             ld(out[k0:, j2:j3]))
        with _ledger.frame("update"):
            S_live[k] = visit(S_live[k], Lj, w)

    def _factor(k):
        w = min(k * panel_cols + panel_cols, n) - k * panel_cols
        if k + 1 < nt:
            # next column's input uploads while this one factors
            n0, n1 = (k + 1) * panel_cols, \
                min((k + 2) * panel_cols, n)
            eng.prefetch("A", k + 1,
                         lambda n0=n0, n1=n1: a[n0:, n0:n1],
                         cache=False)
        S = S_live[k]
        with _ledger.frame("factor"):
            Lk = _panel_factor(S, w)
        _rguard.check_panel("potrf_ooc", k, Lk, ref=S)
        F[k] = Lk

    def _writeback(k):
        k0 = k * panel_cols
        k1 = min(k0 + panel_cols, n)
        Lk = F.pop(k)
        S_live.pop(k, None)
        if eng.caching:
            Pk = Lk if lo is None else stream.demote_dev(Lk, lo)
            eng.put("L", k, stream._embed_rows(Pk, k0, n=n))
        eng.write("L", k, Lk, out[k0:, k0:k1])               # D2H

    try:
        for k in range(epoch0, nt):
            if led is not None:
                led.begin(k, epoch=epoch0)
            _health.heartbeat("potrf_ooc", k, nt)
            _stage(k)
            for j in range(k):
                _update(k, j)
            _factor(k)
            _writeback(k)
            if ck is not None and ck.due(k):
                eng.wait_writes()       # every panel <= k is durable
                ck.commit(k + 1)
            if led is not None:
                led.commit()
        _health.heartbeat("potrf_ooc", nt, nt)   # completion beat
        if led is not None:
            led.begin(nt, epoch=epoch0, drain=True)      # final drain record
        eng.wait_writes()
    finally:
        eng.finish()
        if led is not None:
            led.close()
    return out


@jax.jit
def _chol_back_visit(S: jax.Array, Pk: jax.Array, k0) -> jax.Array:
    """Backward L^H sweep step of the streamed Cholesky solve: with
    Pk = L[:, k0:k1] (full column panel, lower factor), eliminate the
    already-solved rows below — (L^H)[k0:k1, k1:] = Pk[k1:]^H — then
    solve L_kk^H x_k = the corrected strip. Traced k0, fixed shapes:
    one compiled program for the whole reverse stream."""
    m, w = S.shape
    wk = Pk.shape[1]
    rows = jnp.arange(m)
    Lkk = jax.lax.dynamic_slice(Pk, (k0, 0), (wk, wk))
    Sk = jax.lax.dynamic_slice(S, (k0, 0), (wk, w))
    below = jnp.where((rows >= k0 + wk)[:, None], Pk, 0)
    corr = jnp.matmul(jnp.conj(below.T), S, precision=_HI)
    if _solve_temps_bytes(w, wk, S.dtype.itemsize) > OOC_SOLVE_TEMP_CAP:
        from .blocked import invert_triangular
        linv = invert_triangular(Lkk, lower=True)
        X = jnp.matmul(jnp.conj(linv.T), Sk - corr, precision=_HI)
    else:
        X = jax.lax.linalg.triangular_solve(
            Lkk, Sk - corr, left_side=True, lower=True,
            transpose_a=True, conjugate_a=True)
    return jax.lax.dynamic_update_slice(S, X, (k0, 0))


def _solve_sweep(eng, buf, mat, w, n, X, order, kernel, prep=None,
                 lower=False):
    """One streamed triangular-solve sweep shared by the OOC solves:
    for each panel start in `order`, fetch the factor column
    `mat[:, k0:k0+w]` through the engine (prefetching the next one),
    then advance the device-resident RHS with `kernel(X, Pk, k0)`,
    which is handed the full-height (n, w) panel either way.
    Forward and backward sweeps differ only in `order`/`kernel`.
    `prep` transforms the host slice before staging (the mixed path's
    stream.demote_host — half the sweep's H2D bytes; None is the
    identity, the full-precision path bit-identically). `lower` says
    the factor is lower triangular by blocks (potrs_ooc's L): the
    column is staged from its diagonal block down, `mat[k0:, ...]`,
    and zero-embedded on the device (stream.fetch `embed`; an offset
    of 0 is a plain upload). The default stages the whole column,
    which getrs_ooc's packed LU needs: U lies above the diagonal
    block."""
    if prep is None:
        prep = lambda sl: sl                              # noqa: E731

    def column(k0):
        """The loader and `embed` of the panel whose columns start
        at k0."""
        off = k0 if lower else 0
        return (lambda: prep(mat[off:, k0:min(k0 + w, n)])), (off, n)

    for i, k0 in enumerate(order):
        load, embed = column(k0)
        Pk = eng.fetch(buf, k0 // w, load, embed=embed)
        if i + 1 < len(order):
            p0 = order[i + 1]
            load, embed = column(p0)
            eng.prefetch(buf, p0 // w, load, embed=embed)
        X = kernel(X, Pk, k0)
    return X


@instrument_driver("potrs_ooc")
def potrs_ooc(l: np.ndarray, b: np.ndarray,
              panel_cols: Optional[int] = None,
              cache_budget_bytes=None, precision=None) -> np.ndarray:
    """Solve A X = B from potrf_ooc's host-resident lower factor
    (A = L L^H): each factor panel streams through the chip twice —
    the non-unit forward sweep (the left-looking visit kernel with
    unit=False) and the conjugate-transposed backward sweep. B stays
    device-resident (nrhs << n), so HBM holds one (n, w) factor panel
    plus the RHS block (reference src/potrs.cc solves from the
    distributed factor the same two-sweep way). A panel is staged
    from its diagonal block down and zero-embedded on the device
    (_solve_sweep's `lower`): nothing above the diagonal block of `l`
    is read. With a cache budget the backward sweep re-serves the
    panels the forward sweep uploaded (reverse order hits whatever
    stayed resident).
    ``precision`` "bf16" (ISSUE 12) stages the factor panels in bf16
    and runs the mixed sweep kernels — the lo solve of the
    refinement loop (posv_ooc), which corrects what the demotion
    costs."""
    l = np.asarray(l)
    n = l.shape[0]
    lo = _resolve_precision(precision, n, l.dtype)
    w = min(_panel_cols(panel_cols, n, l.dtype), n)
    panels = list(range(0, n, w))
    eng = stream.engine_for(n, w, l.dtype,
                            budget_bytes=cache_budget_bytes,
                            resident_dtype=lo)
    prep = stream.host_demoter(lo)
    if lo is None:
        fwd = lambda X, Pk, k0: _lu_visit(X, Pk, k0,     # noqa: E731
                                          unit=False)
        bwd = _chol_back_visit
    else:
        fwd = lambda X, Pk, k0: _lu_visit_mx(X, Pk, k0,  # noqa: E731
                                             unit=False)
        bwd = _chol_back_visit_mx
    try:
        X = _h2d(np.asarray(b))
        X = _solve_sweep(                    # forward: L y = b
            eng, "L", l, w, n, X, panels, fwd, prep=prep,
            lower=True)
        X = _solve_sweep(                    # backward: L^H x = y
            eng, "L", l, w, n, X, panels[::-1], bwd, prep=prep,
            lower=True)
        return np.asarray(X)
    finally:
        eng.finish()


@instrument_driver("posv_ooc")
def posv_ooc(a: np.ndarray, b: np.ndarray,
             panel_cols: Optional[int] = None,
             cache_budget_bytes=None, grid=None, method=None,
             precision=None, opts=None):
    """Factor + solve in one call (the OOC twin of posv): returns
    (L, X) with both the factor and the solution host-resident.
    ``grid``/``method`` route the FACTOR phase through the MethodOOC
    arbitration (see potrf_ooc) — a sharded factor leaves the full L
    on every host, so the solve sweep stays single-engine local.

    ``precision`` "bf16" (ISSUE 12) is the OOC twin of posv_mixed:
    the factor streams with bf16 trailing updates and the solve
    sweeps stage bf16 panels (half the bytes end to end), then the
    solution FINISHES with iterative refinement (refine.host_ir) —
    full-precision host residuals corrected by more lo solves until
    the normwise criterion holds. Non-convergence is the residual
    sentinel: the ``mixed_to_full`` rung is recorded through the
    resil guard funnel and the answer falls back to a full-f32
    factor+solve (whose factor is then the one returned). The frozen
    "f32" mode is this body's first two lines bit-identically."""
    a = np.asarray(a)
    lo = _resolve_precision(precision, a.shape[0], a.dtype)
    L = potrf_ooc(a, panel_cols, cache_budget_bytes, grid=grid,
                  method=method, precision=precision)
    X = potrs_ooc(L, b, panel_cols, cache_budget_bytes,
                  precision=precision)
    if lo is None:
        return L, X
    from .refine import host_ir
    full: dict = {}

    def solve_lo(r):
        return potrs_ooc(L, r, panel_cols, cache_budget_bytes,
                         precision=precision)

    def full_solve():
        # BOTH phases pinned to "f32": a measured bf16 tune entry
        # must not re-resolve inside the full-precision fallback
        full["L"] = potrf_ooc(a, panel_cols, cache_budget_bytes,
                              precision="f32")
        return potrs_ooc(full["L"], np.asarray(b), panel_cols,
                         cache_budget_bytes, precision="f32")

    X, _iters = host_ir("posv_ooc", _herm_operand(a), np.asarray(b),
                        X, solve_lo, full_solve, opts=opts)
    return full.get("L", L), X


@jax.jit
def _gemm_block(Ab: jax.Array, B: jax.Array, beta, Cb: jax.Array):
    return beta * Cb + jnp.matmul(Ab, B, precision=_HI)


@jax.jit
def _gemm_block_overwrite(Ab: jax.Array, B: jax.Array):
    return jnp.matmul(Ab, B, precision=_HI)


# -- out-of-core LU -------------------------------------------------------

def _swaps_to_perm(piv: np.ndarray, mlen: int) -> np.ndarray:
    """Replay LAPACK sequential swap targets (j <-> piv[j], in order)
    on arange(mlen): the host-side twin of lu._compose_swaps."""
    perm = np.arange(mlen)
    for j, t in enumerate(np.asarray(piv)):
        perm[j], perm[t] = perm[t], perm[j]
    return perm


@functools.partial(jax.jit, static_argnames=("unit",))
def _lu_visit(S: jax.Array, Lj: jax.Array, j0, unit: bool = True
              ) -> jax.Array:
    """One left-looking LU visit of panel S (m, w) by an earlier
    factor panel Lj (m, wj), whose diagonal block sits at traced row
    offset j0: compute the U12 strip U = L_jj^{-1} S[j0:j1], subtract
    the trailing product L_j[j1:, :] U, and write the strip in place.
    Fixed shapes + traced offset = one compiled program for every
    (k, j) pair of the stream. `unit=False` makes the same sweep the
    non-unit forward-substitution step of the Cholesky solves."""
    m, w = S.shape
    wj = Lj.shape[1]
    rows = jnp.arange(m)
    Ljj = jax.lax.dynamic_slice(Lj, (j0, 0), (wj, wj))
    Sj = jax.lax.dynamic_slice(S, (j0, 0), (wj, w))
    if _solve_temps_bytes(w, wj, S.dtype.itemsize) > OOC_SOLVE_TEMP_CAP:
        # wide strip vs wide diag block: the direct solve's expander
        # temps blow at OOC panel widths (see OOC_SOLVE_TEMP_CAP)
        from .blocked import invert_triangular
        linv = invert_triangular(Ljj, lower=True, unit_diagonal=unit)
        U = jnp.matmul(linv, Sj, precision=_HI)
    else:
        U = jax.lax.linalg.triangular_solve(
            Ljj, Sj, left_side=True, lower=True, unit_diagonal=unit)
    below = jnp.where((rows >= j0 + wj)[:, None], Lj, 0)
    S = S - jnp.matmul(below, U, precision=_HI)
    return jax.lax.dynamic_update_slice(S, U, (j0, 0))


def _lu_panel_height(m: int, live: int, dtype) -> int:
    """The height `_lu_panel_factor` factors a panel of `live` live
    rows at: the smallest rung of the ladder m, m/2, m/4, ... that
    holds them. The ladder ends at the first rung the native LU takes
    (MethodFactor.native_lu_ok), where a column costs 3 us and a
    shorter program would buy nothing for its compile: three rungs at
    m=32768, one wherever the native LU takes m itself (the CPU, whose
    LU has no height limit; every small stream)."""
    from ..core.methods import MethodFactor
    h = m
    while h % 2 == 0 and h // 2 >= live \
            and not MethodFactor.native_lu_ok(dtype, h):
        h //= 2
    return h


@functools.partial(jax.jit, static_argnames=("nb", "height"))
def _lu_panel_factor(S: jax.Array, k0, nb: int,
                     height: Optional[int] = None):
    """In-core partial-pivot LU of the resident panel's live rows
    [k0:, :] via the measured-fastest blocked form (lu._getrf_dense
    routing). The panel is ROLLED so the diagonal sits at row 0 and
    the dead rows (already factored, wrapped to the bottom) are masked
    to exact zero — they can never win a pivot search against live
    entries, and their L entries come out exactly zero. `height`
    (static; `_lu_panel_height`, None: all m) is how many of the
    rolled rows are factored: those cut are all dead, and the result
    is padded back to m rows of zeros, so the caller sees the operand
    it would of a full-height call. A traced k0 instead of per-k
    shapes = ONE compiled program a rung for the whole stream (compile
    time dominated the first on-chip run). Returns (packed (m, w)
    rolled — live rows first, piv relative to k0)."""
    from .lu import _getrf_dense
    m = S.shape[0]
    h = m if height is None else height
    rolled = jnp.roll(S, -k0, axis=0)[:h]
    rolled = jnp.where((jnp.arange(h) < m - k0)[:, None], rolled, 0)
    packed, piv = _getrf_dense(rolled, nb, pivot=True)
    return jnp.pad(packed, ((0, m - h), (0, 0))), piv


@jax.jit
def _lu_rows(P: jax.Array, idx: jax.Array) -> jax.Array:
    """Rows `idx` of panel P, in that order: how the partial-pivot
    stream applies a row permutation (the running one to an input
    panel staged as it lies, a stored factor panel's order against
    today's before its visit, the final order in the repair). A
    program of its own, so that `_lu_visit` and `_lu_panel_factor`
    are the programs they were, on the operands they saw when the
    host did the gathers; exact, so the factor is too."""
    return jnp.take(P, idx, axis=0, mode="clip")


@jax.jit
def _lu_col(S: jax.Array, packed: jax.Array, k0) -> jax.Array:
    """The factored panel as one full-height column: the visits' U
    rows of S above traced row k0, `_lu_panel_factor`'s rolled result
    (live rows first) rolled back below it. What is written to the
    host and what the cache serves to later visits."""
    m, wf = packed.shape
    return jnp.where((jnp.arange(m) < k0)[:, None], S[:, :wf],
                     jnp.roll(packed, k0, axis=0))


@jax.jit
def _lu_back_visit(S: jax.Array, Pk: jax.Array, k0) -> jax.Array:
    """Backward U sweep step: x_k = U_kk^{-1} S[k0:k1], then eliminate
    U[:k0, k0:k1] x_k from the rows above (streamed upper solve)."""
    m, w = S.shape
    wk = Pk.shape[1]
    rows = jnp.arange(m)
    Ukk = jax.lax.dynamic_slice(Pk, (k0, 0), (wk, wk))
    Sk = jax.lax.dynamic_slice(S, (k0, 0), (wk, w))
    if _solve_temps_bytes(w, wk, S.dtype.itemsize) > OOC_SOLVE_TEMP_CAP:
        from .blocked import invert_triangular
        uinv = invert_triangular(Ukk, lower=False)
        X = jnp.matmul(uinv, Sk, precision=_HI)
    else:
        X = jax.lax.linalg.triangular_solve(
            Ukk, Sk, left_side=True, lower=False, unit_diagonal=False)
    above = jnp.where((rows < k0)[:, None], Pk, 0)
    S = S - jnp.matmul(above, X, precision=_HI)
    return jax.lax.dynamic_update_slice(S, X, (k0, 0))


def _note_lu_route(mode: str, m: int, wf: int, incore_nb: int,
                   dtype) -> None:
    """The streamed LU's route on its root span: the discipline, and
    the panel kernel and inner blocking its tallest (first) panel
    resolves to — what lu._getrf_dense notes on an in-core `getrf`,
    which here runs under a jit and notes nothing."""
    if not obs_events.enabled():
        return
    nb = min(int(incore_nb), max(wf, 1))
    if mode == "tournament":
        obs_events.note(lu_pivot=mode, panel="calu", nb=nb)
        return
    from .lu import _carry_nb, _panel_note
    nb = _carry_nb(m, wf, nb, dtype)
    obs_events.note(lu_pivot=mode, nb=nb,
                    **_panel_note(m, min(nb, wf), dtype))


@instrument_driver("getrf_ooc")
def getrf_ooc(a: np.ndarray, panel_cols: Optional[int] = None,
              incore_nb: int = 1024, cache_budget_bytes=None,
              pivot=None, grid=None, method=None,
              chunk: Optional[int] = None,
              ckpt_path: Optional[str] = None,
              ckpt_every: Optional[int] = None,
              precision=None):
    """LU of a host-resident (m, n) matrix, streaming one column
    panel through the accelerator at a time (left-looking; reference
    src/getrf.cc:327 runs the same factorization at any n the
    cluster's aggregate memory holds). Returns (LU_packed, ipiv):
    the packed host factor (unit-lower L below the diagonal, U on and
    above) and LAPACK-convention global sequential swap targets of
    length min(m, n).

    ``pivot`` arbitrates the pivot discipline (ISSUE 10) through
    core/methods.MethodLUPivot — explicit argument > ``ooc/lu_pivot``
    tune entry > FROZEN "partial", so with no tune entry a call that
    names no discipline runs this partial-pivot body (pinned by
    test). What the two read on a TPU v5e at n=32768, nrhs 8, panels
    of 4096, five of eight resident, on HPL's uniform(-0.5, 0.5)
    matrix, where every panel's pivots leave the panel (gesv_ooc,
    warm, by hand): "partial" 4.3 s a solve, 13.8 GB staged, 1.9 s of
    device time, 0.4 s in the one repair (PERF.md PR 48: each panel
    factored at the height its live rows have, `_lu_panel_height`;
    5.4-5.5 s and 3.1 s with every panel at full height on the fori
    kernel, PR 47; 15.1-15.2 s, 25.2 GB and 3.0 s before that, 9.9 s
    of them host row gathers and fixups);
    "tournament" 11.9-12.4 s, 12.9 GB staged, 4.4 s of device time,
    5.7 s in the final gather (PERF.md PR 46). The default is
    "partial": the cell ``stream-gesv`` is there to judge it.
    (docs/PERF_HISTORY.md's 2,104 s for n=65536 is another machine's.)

      * "partial" (this body): partial pivoting CONFINED to the
        resident panel — each column's pivot search sees rows k0:
        (everything not yet factored), exactly the rows in-core getrf
        would search, so the factorization matches the in-core one up
        to roundoff. The host moves no row (PR 47). STAGED: input
        panel k as it lies (a strided view packed into a ring slot,
        prefetched while panel k-1 is visited), and the factor
        panels the cache does not hold. GATHERED, on the chip, by one
        exact row gather each (_lu_rows): the input panel through
        the running permutation; at each visit the stored panel j
        through r = inv(P_j)[P_now], its order against today's (the
        identity above j1). STORED: panel j once, host and cache, in
        the order it had when it was factored (rows j1: after its own
        pivots; rows above j1 never move again), so nothing written
        is rewritten, nothing cached is retired
        (``ooc.lu_invalidations`` stays 0) and the visits are served
        from the chip as far as the budget goes. REPAIRED, once,
        after the last panel (``ooc::lu_fixup``, ``ooc.lu_fixup_
        bytes``): rows j1: of each panel before the last gathered on
        the chip into the final order, from the resident panel or
        from those rows staged alone, and written over the stored
        ones — each stored row moves at most once, and (lu, ipiv) is
        LAPACK's packed contract. The factor, the pivots and X are
        bitwise what the host-order walk before PR 47 returned. No
        checkpoint support: a committed panel's rows j1: are not
        final until the repair, which breaks the durable-epoch
        contract.
      * "tournament": the CALU stream (getrf_tntpiv_ooc) — the
        factor stored in ORIGINAL row order, three gathers a visit,
        one O(n^2) host gather at the end; checkpoint/resume, and
        the route the sharded layer requires.

    With a ``grid``, the MethodOOC arbitration (see potrf_ooc) can
    route to dist/shard_ooc.shard_getrf_ooc — tournament-only by
    construction (its right-looking schedule needs a panel's rows
    final when they are written; the partial stream's are final only
    after the repair); asking for the sharded route with an explicit
    partial mode is an error.
    HBM residency: three (m, w) panels (the resident one, the
    visitor and its gathered copy; plus the residency cache when a
    budget is set)."""
    from ..core.exceptions import slate_assert
    from ..core.methods import MethodLUPivot, str2method
    a = np.asarray(a)
    m, n = a.shape
    kmax = min(m, n)
    w = min(_panel_cols(panel_cols, n, a.dtype), n)
    mode = pivot
    if isinstance(mode, str):
        mode = str2method("lu_pivot", mode)
    asked = mode if mode is not MethodLUPivot.Auto else None
    if mode is None or mode is MethodLUPivot.Auto:
        mode = MethodLUPivot.resolve(n, a.dtype)
    lo = _resolve_precision(precision, n, a.dtype)
    if lo is not None:
        # the mixed update path is the tournament stream's (ISSUE
        # 12): its visit kernel, its demoted residents and its
        # checkpoint meta; the partial body stages, caches and
        # multiplies in the input dtype only. bf16 implies
        # tournament; asking for both explicitly is an error.
        slate_assert(
            asked is not MethodLUPivot.Partial,
            "the mixed-precision OOC LU is tournament-only (the "
            "partial-pivot stream has no demoted update path); "
            "drop pivot='partial' or precision='bf16'")
        mode = MethodLUPivot.Tournament
    if _route_shard(n, ceil_div(n, w), grid, method, a.dtype):
        slate_assert(
            asked is None or asked is MethodLUPivot.Tournament,
            "the sharded OOC LU is tournament-only (a partial-pivot "
            "panel's rows are final only after the last panel's "
            "pivots); drop pivot='partial' or route method=Stream")
        from ..dist.shard_ooc import shard_getrf_ooc
        return _shard_escalate(
            lambda: shard_getrf_ooc(
                a, grid, panel_cols=w, incore_nb=incore_nb,
                cache_budget_bytes=cache_budget_bytes, chunk=chunk,
                ckpt_path=ckpt_path, ckpt_every=ckpt_every,
                precision=precision),
            lambda: getrf_tntpiv_ooc(
                a, w, incore_nb, cache_budget_bytes, chunk=chunk,
                ckpt_path=ckpt_path, ckpt_every=ckpt_every,
                precision=precision),
            "getrf_ooc", grid)
    if mode is MethodLUPivot.Tournament:
        _note_lu_route("tournament", m, min(w, kmax), incore_nb, a.dtype)
        return getrf_tntpiv_ooc(a, w, incore_nb, cache_budget_bytes,
                                chunk=chunk, ckpt_path=ckpt_path,
                                ckpt_every=ckpt_every,
                                precision=precision)
    slate_assert(
        ckpt_path is None,
        "partial-pivot OOC LU cannot checkpoint (a committed panel's "
        "rows are final only after the repair at the end); use "
        "pivot='tournament'")
    _note_lu_route("partial", m, min(w, kmax), incore_nb, a.dtype)
    perm = np.arange(m)
    out = np.empty_like(a)
    ipiv = np.empty((kmax,), np.int64)
    nt = ceil_div(n, w)
    # where[j][row]: the position of original row `row` in factor
    # panel j AS STORED (the order after panel j's own pivots)
    where = np.empty((ceil_div(kmax, w), m), np.int32)
    eng = stream.engine_for(max(m, n), w, a.dtype,
                            budget_bytes=cache_budget_bytes)
    led = _ledger.recorder("getrf_ooc", nt=nt)
    span = obs_events.span
    word = a.dtype.itemsize
    rows_live = rows_factored = 0       # summed over the panels

    def stored(j0, j1):
        return lambda: out[:, j0:j1]

    try:
        for k0 in range(0, n, w):
            k1 = min(k0 + w, n)
            k = k0 // w
            if led is not None:
                led.begin(k)
            _health.heartbeat("getrf_ooc", k, nt)
            with _ledger.frame("stage"):
                S = eng.fetch("Ain", k, lambda k0=k0, k1=k1: a[:, k0:k1],
                              cache=False)                     # H2D
            if k1 < n:
                eng.prefetch("Ain", k + 1,
                             lambda n0=k1, n1=min(k1 + w, n): a[:, n0:n1],
                             cache=False)
            with _ledger.frame("update"):
                # (a copy of perm: it changes under a gather in flight)
                S = _lu_rows(S, jnp.asarray(perm.astype(np.int32)))
            for j0 in range(0, min(k0, kmax), w):
                j1 = min(j0 + w, kmax)
                with _ledger.frame("stage"):
                    Lj = eng.fetch("LU", j0 // w, stored(j0, j1))
                if j0 + w < min(k0, kmax):
                    eng.prefetch("LU", j0 // w + 1,
                                 stored(j0 + w, min(j0 + 2 * w, kmax)))
                with _ledger.frame("update"):
                    # the stored panel's rows, in today's order
                    Lj = _lu_rows(Lj, jnp.asarray(where[j0 // w][perm]))
                    S = _lu_visit(S, Lj, j0)
            if k0 < kmax:
                wf = min(k1, kmax) - k0
                # dead rows are not factored (k0 stays traced inside
                # a rung of the ladder)
                height = _lu_panel_height(m, m - k0, a.dtype)
                rows_live += m - k0
                rows_factored += height
                with _ledger.frame("factor"):
                    packed, piv = _lu_panel_factor(
                        S[:, :wf], k0, min(incore_nb, max(wf, 1)), height)
                    col = _lu_col(S, packed, k0)
                # the one place a panel step waits for the device
                with span("ooc::lu_pivots", cat="staging", k=k):
                    piv_h = np.asarray(piv)
                lperm = _swaps_to_perm(piv_h, m - k0)
                if k0 > 0 and not np.array_equal(
                        lperm, np.arange(m - k0)):
                    obs_metrics.inc("ooc.lu_panels_swapped")
                perm[k0:] = perm[k0:][lperm]
                where[k][perm] = np.arange(m, dtype=np.int32)
                ipiv[k0:k0 + wf] = k0 + piv_h
                # written once, in the order it was factored in, and
                # served from the chip in that order from now on
                eng.put("LU", k, col)
                eng.write("LU", k, col, out[:, k0:k0 + wf])
                if wf < k1 - k0:
                    # kmax falls inside this panel (m < n): the
                    # columns right of the last diagonal block are
                    # pure U12 rows (live rows == wf here, so the
                    # solve covers them all)
                    rest = S[k0:, wf:][jnp.asarray(lperm)]
                    U = _unit_lower_solve_capped(packed[:wf, :wf],
                                                 rest[:wf])
                    if k0 > 0:
                        eng.write("LU", k, S[:k0, wf:],
                                  out[:k0, k0 + wf:k1])
                    out[k0:k0 + wf, k0 + wf:k1] = np.asarray(U)
            else:
                eng.write("LU", k, S,    # columns past kmax: all U
                          out[:, k0:k1])
            if led is not None:
                led.commit()
        _health.heartbeat("getrf_ooc", nt, nt)   # completion beat
        if led is not None:
            led.begin(nt, drain=True)                # final drain record
        # what the ladder saved: the rows the panels had live against
        # the rows `_lu_panel_factor` was given, on the bus and (for
        # the benchmark's breakdown, which prints the route) the span
        obs_metrics.inc("ooc.lu_panel_rows_live", rows_live)
        obs_metrics.inc("ooc.lu_panel_rows_factored", rows_factored)
        obs_events.note(panel_rows_live=rows_live,
                        panel_rows_factored=rows_factored)
        # the one repair: rows j1: of each panel written before the
        # pivots below it were known are gathered on the chip into the
        # final order and written over the stored ones, from the
        # resident panel where the cache still holds it, from those
        # rows staged alone where it does not
        moved = []
        for j0 in range(0, kmax, w):
            j1 = min(j0 + w, kmax)
            idx = where[j0 // w][perm[j1:]]
            if not np.array_equal(idx, np.arange(j1, m)):
                moved.append((j0, j1, idx))
        if moved:
            fix_bytes = sum(2 * len(idx) * (j1 - j0) * word
                            for j0, j1, idx in moved)
            with span("ooc::lu_fixup", cat="staging", bytes=fix_bytes):
                for j0, j1, idx in moved:
                    j = j0 // w
                    Lj = eng.cache.get(eng.cache.key("LU", j), m - j1) \
                        if eng.caching else None
                    if Lj is None:
                        Lj = eng.fetch("LU", j, lambda j0=j0, j1=j1:
                                       out[j1:, j0:j1], cache=False)
                        idx = idx - j1
                    eng.write("LU", j, _lu_rows(Lj, jnp.asarray(idx)),
                              out[j1:, j0:j1])
                eng.wait_writes()
            obs_metrics.inc("ooc.lu_fixup_bytes", fix_bytes)
        eng.wait_writes()
    finally:
        eng.finish()
        if led is not None:
            led.close()
    return out, ipiv


# -- tournament-pivot (CALU) out-of-core LU -------------------------------
#
# Both streams keep a written factor panel as it was written and apply
# row permutations on the chip at the time of use (the partial-pivot
# stream since PR 47; before it, it rewrote every written L panel on
# the host after each cross-panel pivot and retired the cache). They
# differ in the order stored and in who picks the pivots. Partial:
# panel j in the order it was factored in, ONE gather a visit (the
# visitor; the resident panel is already in today's order), rows j1:
# of each panel repaired once at the end, on the chip. Tournament
# (ISSUE 10): every panel in ORIGINAL row order, three gathers a
# visit (_lu_visit_orig: both operands in, the result back), one
# O(n^2) host gather at the end (_finalize_lapack_order) to the
# standard LAPACK packed layout, so getrs_ooc consumes either mode's
# factor unchanged; a panel's rows are final when it is written,
# which is what the checkpoint and the sharded right-looking
# schedule (dist/shard_ooc.py: a factor step never touches another
# shard's bytes) need. Pivot selection is the CALU tournament
# (ca.tournament_pivot_rows — the structure the TPU-distributed-
# linalg paper uses), finalized BEFORE the panel's column is written.


@jax.jit
def _lu_visit_orig(S: jax.Array, Lj: jax.Array, g: jax.Array, j0
                   ) -> jax.Array:
    """One left-looking LU visit in ORIGINAL-row-order form: S and Lj
    are (m, *) panels whose rows sit in the input's original order;
    `g` is the traced position->original-row permutation AS OF the
    visiting panel j's factor step (perms[j], the order in which its
    diagonal block was eliminated). Gather both operands into that
    order, run the standard visit (U12 strip solve + trailing rank-w
    update, _lu_visit), scatter the result back. The gathers are
    exact, so the arithmetic per row is the same the position-order
    stream performs — and because the left-looking single-engine
    stream and the right-looking sharded stream both call THIS kernel
    with bitwise-identical operands per (panel, step) pair, their
    factors are bitwise equal (pinned by tests)."""
    Sp = jnp.take(S, g, axis=0)
    Lp = jnp.take(Lj, g, axis=0)
    Sp = _lu_visit(Sp, Lp, j0)
    return jnp.zeros_like(S).at[g].set(Sp)


@functools.partial(jax.jit, static_argnames=("wf", "chunk"))
def _tnt_select(S: jax.Array, idx: jax.Array, live, wf: int,
                chunk=None) -> jax.Array:
    """Tournament pivot selection over the LIVE rows of the resident
    panel: `idx` rolls the original-order panel live-rows-first (the
    not-yet-pivoted rows, current permutation order) and the dead
    rows — already-selected pivots, masked to exact zero so they
    cannot outbid a live entry — wrap to the bottom, the same
    roll-and-mask discipline as _lu_panel_factor (ONE compiled
    program for the whole stream, traced `live`). Returns the
    selected live-relative row indices (wf,) in selection order;
    degenerate selections (a zero column among the live rows) are
    repaired host-side by ca.fix_degenerate_selection."""
    from .ca import tournament_pivot_rows
    m = S.shape[0]
    rows = jnp.arange(m)
    rolled = jnp.take(S[:, :wf], idx, axis=0)
    rolled = jnp.where((rows < live)[:, None], rolled, 0)
    return tournament_pivot_rows(rolled, chunk=chunk)


@functools.partial(jax.jit, static_argnames=("wf", "nb"))
def _tnt_factor(S: jax.Array, idx2: jax.Array, live, wf: int,
                nb: int):
    """Factor the panel with its pivot rows already selected: `idx2`
    gathers the original-order panel into sorted live order (selected
    pivot rows on top, remaining live rows after, dead rows wrapped
    to the bottom and masked to exact zero), the CALU no-pivot factor
    runs at matmul rate (ca.calu_factor_sorted — blocked no-pivot LU
    of the top block + one right-side solve for everything below;
    masked dead rows come out exact zero), and the result scatters
    back to the original-order column with the visits' U rows (the
    dead positions) preserved. Returns (col (m, wf) original order,
    packed (m, wf) sorted order — the top block the m<n tail solve
    needs)."""
    from .ca import calu_factor_sorted
    m = S.shape[0]
    rows = jnp.arange(m)
    Sroll = jnp.take(S[:, :wf], idx2, axis=0)
    masked = jnp.where((rows < live)[:, None], Sroll, 0)
    packed = calu_factor_sorted(masked, inner_nb=nb)
    comb = jnp.where((rows < live)[:, None], packed, Sroll)
    col = jnp.zeros((m, wf), S.dtype).at[idx2].set(comb)
    return col, packed


def _unit_lower_solve_capped(Lblk: jax.Array, rhs: jax.Array
                             ) -> jax.Array:
    """One wf-row unit-lower triangular solve behind the
    OOC_SOLVE_TEMP_CAP valve (module doc): above the expander's temp
    estimate, invert-the-unit-diag-block + one matmul replaces the
    direct solve. Shared by both LU streams' U12 tail branches so the
    cap heuristic lives in one place."""
    wf = Lblk.shape[0]
    if _solve_temps_bytes(rhs.shape[1], wf,
                          np.dtype(rhs.dtype).itemsize) \
            > OOC_SOLVE_TEMP_CAP:
        from .blocked import invert_triangular
        linv = invert_triangular(Lblk, lower=True, unit_diagonal=True)
        return jnp.matmul(linv, rhs, precision=_HI)
    return jax.lax.linalg.triangular_solve(
        Lblk, rhs, left_side=True, lower=True, unit_diagonal=True)


def _tnt_tail_cols(S: jax.Array, packed: jax.Array,
                   new_live: np.ndarray, wf: int) -> jax.Array:
    """U12 tail columns of the boundary panel (kmax falls inside the
    panel, m < n): every live row is a pivot row here (live == wf),
    so the tail strip is one unit-lower solve of the selected rows
    against the just-factored top block, written back at the pivot
    rows' original positions (all other rows keep their visit-written
    U values). Eager (runs once per stream)."""
    idx = jnp.asarray(new_live)
    rest = jnp.take(S[:, wf:], idx, axis=0)
    U = _unit_lower_solve_capped(packed[:wf, :wf], rest)
    return S[:, wf:].at[idx].set(U)


def _finalize_lapack_order(stored: np.ndarray, perm: np.ndarray,
                           w: int, out: Optional[np.ndarray] = None
                           ) -> np.ndarray:
    """Convert the original-row-order factor store to the standard
    LAPACK packed layout (row position i = perm[i]'s factor row):
    positions below a panel's diagonal hold L rows of the final
    pivoted order, positions above hold the U rows — which the
    original-order store keeps at exactly the rows the FINAL
    permutation maps there (positions < j1 never move after step j),
    so one uniform row gather per panel finalizes every column. With
    `out` None the gather runs in place panel by panel (O(m*w) extra
    host memory, the no-checkpoint path); a caller-provided `out`
    leaves `stored` untouched (the checkpoint memmap must keep the
    original-order layout a resume expects)."""
    n = stored.shape[1]
    dst = stored if out is None else out
    for j0 in range(0, n, w):
        j1 = min(j0 + w, n)
        dst[:, j0:j1] = stored[perm, j0:j1]
    return dst


@instrument_driver("getrf_tntpiv_ooc")
def getrf_tntpiv_ooc(a: np.ndarray, panel_cols: Optional[int] = None,
                     incore_nb: int = 1024, cache_budget_bytes=None,
                     chunk: Optional[int] = None,
                     ckpt_path: Optional[str] = None,
                     ckpt_every: Optional[int] = None,
                     precision=None):
    """Tournament-pivot (CALU) LU of a host-resident (m, n) matrix,
    streaming one column panel at a time — the out-of-core twin of
    getrf_tntpiv (reference src/getrf_tntpiv.cc:169-222). Returns
    (LU_packed, ipiv) in getrf_ooc's exact contract: the LAPACK
    packed factor (unit-lower L below the diagonal in final pivoted
    row order, U on and above) plus global sequential swap targets —
    getrs_ooc consumes it unchanged.

    What tournament pivoting buys the stream (section comment above):
    the pivot permutation of panel k is FINAL before its column is
    written, factor panels live in original row order and are never
    rewritten, so there are ZERO cache invalidations — `put` at
    factor time makes every left-looking revisit a cache hit under a
    budget, exactly like potrf/geqrf (and, since PR 47, the
    partial-pivot stream). The permutation is applied at visit time
    as a device index gather (_lu_visit_orig); index-vector uploads are NOT
    routed through _h2d, keeping the h2d counters panel-pure (an
    index vector is ~2/w of a panel — the sharded layer's staged-byte
    prediction stays exact).

    Pivot quality is CALU's: growth bounded by 2^(nb*depth) worst
    case vs partial pivoting's 2^(n-1), benign in practice (the
    documented trade; pinned by the adversarial-panel tests).
    ``chunk`` overrides the tournament chunk height (ca.
    tournament_pivot_rows' native-cap default; tests shrink it to
    force multi-round brackets).

    ``ckpt_path``/``ckpt_every`` (resil/): the original-order store,
    ipiv, AND the per-panel permutation snapshots are all durable —
    the snapshots are what let a resumed stream rebuild the visit
    gathers for factors below the epoch — and the checkpoint meta
    records ``lu_pivot="tournament"``, so a resume against a
    partial-mode (or any mismatched) checkpoint starts fresh instead
    of mixing disciplines. The partial-pivot stream cannot
    checkpoint at all (its committed panels' rows are final only
    after the repair at its end); this path's rows are final when
    written, which is what makes the LU checkpoint sound.

    ``precision`` (ISSUE 12): the mixed-precision mode (potrf_ooc
    doc) — under "bf16" the left-looking visits stage/cache/multiply
    the factor columns in bf16 (the immutable store is what makes
    demoted residents sound for LU), select/factor stay f32, and the
    checkpoint meta records the mode so a mismatched resume starts
    fresh. gesv_ooc's refinement is the accuracy contract."""
    from .ca import fix_degenerate_selection
    from .lu import tnt_swaps_host
    a = np.asarray(a)
    m, n = a.shape
    kmax = min(m, n)
    w = min(_panel_cols(panel_cols, n, a.dtype), n)
    nt = ceil_div(n, w)
    nf = ceil_div(kmax, w)          # factor panels (k0 < kmax)
    # mixed update path (ISSUE 12): THIS stream is the one the bf16
    # mode rides for LU — the immutable original-order store means a
    # demoted resident/staged panel is never rewritten under its
    # rounding, so visits stage/cache bf16 columns and run the mixed
    # gather-visit kernel; select/factor stay on the f32 accumulator
    lo = _resolve_precision(precision, n, a.dtype)
    ck = _rckpt.maybe_checkpointer(
        ckpt_path, "getrf_tntpiv_ooc", a, w, nt, every=ckpt_every,
        extra_arrays={"ipiv": ((kmax,), np.int64),
                      "perms": ((nf, m), np.int64)},
        extra_meta={"lu_pivot": "tournament",
                    "precision": _precision_meta(lo)})
    if ck is not None:
        stored, ipiv = ck.factor, ck.array("ipiv")
        perms, epoch = ck.array("perms"), ck.epoch
    else:
        stored = np.empty_like(a)
        ipiv = np.empty((kmax,), np.int64)
        perms = np.empty((nf, m), np.int64)
        epoch = 0
    # current position->original-row map; rebuilt from the last
    # committed snapshot on resume (perm never moves positions below
    # a committed panel's diagonal again, and pure-U panels past kmax
    # never change it)
    perm = perms[min(epoch, nf) - 1].copy() if min(epoch, nf) > 0 \
        else np.arange(m)
    _note_lu_route("tournament", m, min(w, kmax), incore_nb, a.dtype)
    eng = stream.engine_for(max(m, n), w, a.dtype,
                            budget_bytes=cache_budget_bytes,
                            resident_dtype=lo)
    ld = stream.host_demoter(lo)
    visit = _lu_visit_orig if lo is None else _lu_visit_orig_mx
    gdev: dict = {}

    def _g(j: int) -> jax.Array:
        """Device copy of the post-step-j permutation (the visit
        gather), uploaded once per panel and reused by every later
        visit — int32 (row counts are host-RAM-bounded), so the
        resident index set costs 4m bytes per factor panel, 1/(w·
        itemsize/4) of the factor itself (~0.8% at w=128 f32).
        Deliberately NOT via _h2d (docstring); gather indices are
        exact in either width, so the factor is bitwise unchanged.
        The resident set is CAPPED: past _GDEV_MAX entries a visit
        re-uploads its index vector instead of pinning it (~1/w extra
        H2D per visit) — low panels fill the cache first and are
        exactly the most-revisited in a left-looking stream, so the
        cap costs only the long tail while bounding device memory on
        beyond-HBM streams."""
        dev = gdev.get(j)
        if dev is None:
            dev = jnp.asarray(perms[j].astype(np.int32))
            if len(gdev) < _GDEV_MAX:
                gdev[j] = dev
        return dev

    led = _ledger.recorder("getrf_tntpiv_ooc", nt=nt,
                           spill_dir=ckpt_path)
    # the loop's phases by their ledger names (potrf_ooc comment)
    S_live, F = {}, {}

    def _stage(k):
        _rfaults.check("step", op="getrf_tntpiv_ooc", step=k)
        k0, k1 = k * w, min(k * w + w, n)
        with _ledger.frame("stage"):
            S_live[k] = eng.fetch("Ain", k,
                                  lambda k0=k0, k1=k1: a[:, k0:k1],
                                  cache=False)                 # H2D
        if k + 1 < nt:
            n0, n1 = k1, min(k1 + w, n)
            eng.prefetch("Ain", k + 1,
                         lambda n0=n0, n1=n1: a[:, n0:n1],
                         cache=False)

    def _update(k, j):
        k0 = k * w
        j0 = j * w
        j1 = min(j0 + w, kmax)
        with _ledger.frame("stage"):
            Lj = eng.fetch("LU", j,
                           lambda j0=j0, j1=j1:
                           ld(stored[:, j0:j1]))
        if j0 + w < min(k0, kmax):
            p0, p1 = j0 + w, min(j0 + 2 * w, kmax)
            eng.prefetch("LU", p0 // w,
                         lambda p0=p0, p1=p1:
                         ld(stored[:, p0:p1]))
        with _ledger.frame("update"):
            S_live[k] = visit(S_live[k], Lj, _g(j), j0)

    def _factor(k):
        k0, k1 = k * w, min(k * w + w, n)
        wf = min(k1, kmax) - k0
        live = m - k0
        S = S_live[k]
        idx = np.concatenate([perm[k0:], perm[:k0]])
        with _ledger.frame("factor"):
            sel = _tnt_select(S, jnp.asarray(idx), live, wf,
                              chunk=chunk)
            # the one place a panel step waits for the device
            with obs_events.span("ooc::lu_pivots", cat="staging", k=k):
                sel = np.asarray(sel)
            sel = fix_degenerate_selection(sel, live, wf)
        piv_rel, lperm = tnt_swaps_host(sel, live)
        if k0 > 0 and obs_events.enabled() and not np.array_equal(
                lperm, np.arange(live)):
            # pivots that left the panel (`partial` counts the same)
            obs_metrics.inc("ooc.lu_panels_swapped")
        new_live = perm[k0:][lperm]
        idx2 = np.concatenate([new_live, perm[:k0]])
        with _ledger.frame("factor"):
            col, packed = _tnt_factor(
                S, jnp.asarray(idx2), live, wf,
                min(int(incore_nb), max(wf, 1)))
        perm[k0:] = new_live
        ipiv[k0:k0 + wf] = k0 + piv_rel
        perms[k] = perm
        _rguard.check_panel("getrf_tntpiv_ooc", k, col, ref=S)
        F[k] = (col, packed, new_live, wf)

    def _writeback(k):
        k0, k1 = k * w, min(k * w + w, n)
        wk = k1 - k0
        S = S_live.pop(k)
        if k0 < kmax:
            col, packed, new_live, wf = F.pop(k)
            if eng.caching:
                # immutable normal form — zero revisit uploads
                # (demoted under the mixed mode: the resident IS
                # the bytes the upload path would stage)
                eng.put("LU", k, col if lo is None
                        else stream.demote_dev(col, lo))
            eng.write("LU", k, col, stored[:, k0:k0 + wf])
            if wf < wk:
                # kmax falls inside this panel (m < n): the
                # columns right of the last diagonal block
                tail = _tnt_tail_cols(S, packed, new_live, wf)
                eng.write("LU", k, tail, stored[:, k0 + wf:k1])
        else:
            eng.write("LU", k, S,           # columns past kmax: all U
                      stored[:, k0:k1])

    try:
        for k in range(epoch, nt):
            if led is not None:
                led.begin(k, epoch=epoch)
            _health.heartbeat("getrf_tntpiv_ooc", k, nt)
            _stage(k)
            for j in range(ceil_div(min(k * w, kmax), w)):
                _update(k, j)
            if k * w < kmax:
                _factor(k)
            _writeback(k)
            if ck is not None and ck.due(k):
                eng.wait_writes()       # every panel <= k is durable
                ck.commit(k + 1)
            if led is not None:
                led.commit()
        _health.heartbeat("getrf_tntpiv_ooc", nt, nt)   # completion
        if led is not None:
            led.begin(nt, epoch=epoch, drain=True)       # final drain record
        eng.wait_writes()
    finally:
        eng.finish()
        if led is not None:
            led.close()
    with obs_events.span("ooc::lu_finalize", cat="staging",
                         bytes=2 * stored.nbytes):
        if ck is not None:
            out = _finalize_lapack_order(stored, perm, w,
                                         out=np.empty_like(stored))
            return out, np.array(ipiv)
        return _finalize_lapack_order(stored, perm, w), ipiv


@instrument_driver("getrs_ooc")
def getrs_ooc(lu: np.ndarray, ipiv: np.ndarray, b: np.ndarray,
              panel_cols: Optional[int] = None,
              cache_budget_bytes=None, precision=None) -> np.ndarray:
    """Solve A X = B from getrf_ooc's host factor: pivots replayed on
    the RHS, then each factor panel streams through the chip twice —
    the unit-lower forward sweep (the SAME kernel as the left-looking
    visit) and the upper backward sweep. B stays device-resident
    (nrhs << n). With a cache budget the backward sweep re-serves the
    forward sweep's resident panels. ``precision`` "bf16" (ISSUE 12)
    stages the factor panels in bf16 and runs the mixed sweep
    kernels — gesv_ooc's refinement loop is the lo solve's accuracy
    contract."""
    lu = np.asarray(lu)
    n = lu.shape[0]
    lo = _resolve_precision(precision, n, lu.dtype)
    w = min(_panel_cols(panel_cols, n, lu.dtype), n)
    panels = list(range(0, n, w))
    with obs_events.span("ooc::rhs_permute", cat="staging"):
        Pb = np.take(np.asarray(b), _swaps_to_perm(ipiv, n), axis=0)
    eng = stream.engine_for(n, w, lu.dtype,
                            budget_bytes=cache_budget_bytes,
                            resident_dtype=lo)
    prep = stream.host_demoter(lo)
    fwd = _lu_visit if lo is None else _lu_visit_mx
    bwd = _lu_back_visit if lo is None else _lu_back_visit_mx
    try:
        X = _h2d(Pb)
        X = _solve_sweep(                    # forward: L y = P b
            eng, "LU", lu, w, n, X, panels, fwd, prep=prep)
        X = _solve_sweep(                    # backward: U x = y
            eng, "LU", lu, w, n, X, panels[::-1], bwd, prep=prep)
        return np.asarray(X)
    finally:
        eng.finish()


@instrument_driver("gesv_ooc")
def gesv_ooc(a: np.ndarray, b: np.ndarray,
             panel_cols: Optional[int] = None,
             cache_budget_bytes=None, pivot=None, grid=None,
             method=None, precision=None, opts=None):
    """Factor + solve in one call (the OOC twin of gesv).
    ``pivot``/``grid``/``method`` route the FACTOR phase through the
    getrf_ooc arbitration (MethodLUPivot x MethodOOC — with no tune
    entry and no argument, the partial-pivot stream; getrf_ooc's
    docstring has what both disciplines read on the chip); both
    modes return the same LAPACK packed contract, so the solve sweep
    is mode-blind.

    ``precision`` "bf16" (ISSUE 12): the OOC twin of gesv_mixed —
    tournament factor with bf16 trailing updates, bf16-staged solve
    sweeps, then iterative refinement (refine.host_ir) whose
    residual sentinel records ``mixed_to_full`` through the guard
    funnel and falls back to the full-f32 factor+solve on
    non-convergence (that factor is then the one returned)."""
    a = np.asarray(a)
    lo = _resolve_precision(precision, a.shape[1], a.dtype)
    lu, ipiv = getrf_ooc(a, panel_cols,
                         cache_budget_bytes=cache_budget_bytes,
                         pivot=pivot, grid=grid, method=method,
                         precision=precision)
    X = getrs_ooc(lu, ipiv, b, panel_cols, cache_budget_bytes,
                  precision=precision)
    if lo is None:
        return (lu, ipiv), X
    from .refine import host_ir
    full: dict = {}

    def solve_lo(r):
        return getrs_ooc(lu, ipiv, r, panel_cols,
                         cache_budget_bytes, precision=precision)

    def full_solve():
        # BOTH phases pinned to "f32": a measured bf16 tune entry
        # must not re-resolve inside the full-precision fallback
        full["f"] = getrf_ooc(a, panel_cols,
                              cache_budget_bytes=cache_budget_bytes,
                              pivot=pivot, precision="f32")
        flu, fpiv = full["f"]
        return getrs_ooc(flu, fpiv, np.asarray(b), panel_cols,
                         cache_budget_bytes, precision="f32")

    X, _iters = host_ir("gesv_ooc", a, np.asarray(b), X, solve_lo,
                        full_solve, opts=opts)
    return full.get("f", (lu, ipiv)), X


# -- out-of-core QR -------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("trans",))
def _qr_visit(S: jax.Array, Pj: jax.Array, tauj: jax.Array, j0,
              trans: bool = True) -> jax.Array:
    """Apply an earlier panel's compact-WY block reflector to the
    resident panel S: V is unmasked from the packed factor at traced
    diagonal offset j0 (qr._panel_V handles the traced offset), T
    rebuilt by the closed-form larft, and S -= V (T' (V^H S)) with
    T' = T^H for Q^H (trans=True, the left-looking visit) or T for Q
    (trans=False, the reverse-order apply) — two tall matmuls plus
    one (wj, w) one, all at fixed shapes."""
    from .qr import _larft, _panel_V
    V = _panel_V(Pj, j0)
    T = _larft(V, tauj)
    W = jnp.matmul(jnp.conj(V.T), S, precision=_HI)
    W = jnp.matmul(jnp.conj(T.T) if trans else T, W, precision=_HI)
    return S - jnp.matmul(V, W, precision=_HI)


@functools.partial(jax.jit, static_argnames=("ib",))
def _qr_panel_factor(S: jax.Array, k0, ib: int):
    """Factor the live rows [k0:, :] of the resident panel: same
    roll-and-mask discipline as _lu_panel_factor (dead rows at exact
    zero contribute nothing to reflector norms and get V entries of
    exact zero), so one traced-k0 program serves the whole stream."""
    from .qr import _qr_panel_blocked
    m = S.shape[0]
    rows = jnp.arange(m)
    rolled = jnp.where((rows < m - k0)[:, None],
                       jnp.roll(S, -k0, axis=0), 0)
    return _qr_panel_blocked(rolled, ib=ib)


@jax.jit
def _qr_apply_fresh(S_rest: jax.Array, packed: jax.Array,
                    ptau: jax.Array) -> jax.Array:
    """Apply the just-factored panel's reflectors to the remaining
    columns of the SAME resident panel (only reached when kmax falls
    inside a panel, m < n)."""
    from .qr import _larft, _panel_V
    V = _panel_V(packed, 0)
    T = _larft(V, ptau)
    W = jnp.matmul(jnp.conj(V.T), S_rest, precision=_HI)
    W = jnp.matmul(jnp.conj(T.T), W, precision=_HI)
    return S_rest - jnp.matmul(V, W, precision=_HI)


@instrument_driver("geqrf_ooc")
def geqrf_ooc(a: np.ndarray, panel_cols: Optional[int] = None,
              incore_ib: int = 128, cache_budget_bytes=None,
              engine: Optional["stream.StreamEngine"] = None,
              grid=None, method=None,
              ckpt_path: Optional[str] = None,
              ckpt_every: Optional[int] = None,
              precision=None):
    """Householder QR of a host-resident (m, n) matrix, streaming one
    column panel at a time (left-looking; reference src/geqrf.cc:26).
    Returns (QR_packed, taus) in the same packed contract as geqrf:
    V below the diagonal (unit implicit), R on and above, taus of
    length min(m, n). HBM residency: two (m, w) panels plus the
    residency cache — reflector panels never change once written, so
    with a budget each is uploaded at most once for the whole stream
    (no invalidation, unlike LU). `engine` lets a composed driver
    (gels_ooc) share the cache with the unmqr apply that follows.
    With a ``grid``, the MethodOOC arbitration (see potrf_ooc) can
    route to the sharded stream — never when an `engine` is shared
    (the composed gels pipeline is single-engine by construction).

    ``precision`` (ISSUE 12): under "bf16" the reflector-panel visits
    stage/cache the packed columns in bf16 and apply the compact-WY
    block with bf16 tall matmuls (f32 T algebra — _qr_visit_mx); the
    panel factor itself stays f32. No refinement exists for a bare
    factorization, so the result carries bf16-grade update error —
    the mode is for pipelines that can pay it (or measure it).
    Composed runs (engine= shared) never mix: the shared cache must
    hold one dtype's residents."""
    from ..core.exceptions import slate_assert
    a = np.asarray(a)
    m, n = a.shape
    kmax = min(m, n)
    w = min(_panel_cols(panel_cols, n, a.dtype), n)
    if engine is None:
        lo = _resolve_precision(precision, n, a.dtype)
    else:
        # a composed (engine-shared) pipeline is single-dtype by
        # construction: an EXPLICIT mixed request is a loud error,
        # while explicit "f32" (the documented no-op) and the tuned
        # route both keep the full-precision path — a measured bf16
        # entry must not silently mix residents into a shared cache
        lo = _resolve_precision(precision, n, a.dtype) \
            if precision is not None else None
        slate_assert(
            lo is None,
            "geqrf_ooc: a shared engine cannot carry mixed-"
            "precision residents (one cache, one dtype); drop "
            "precision= or the engine=")
    if engine is None and _route_shard(n, ceil_div(n, w), grid,
                                       method, a.dtype):
        from ..dist.shard_ooc import shard_geqrf_ooc
        return _shard_escalate(
            lambda: shard_geqrf_ooc(
                a, grid, panel_cols=w, incore_ib=incore_ib,
                cache_budget_bytes=cache_budget_bytes,
                ckpt_path=ckpt_path, ckpt_every=ckpt_every,
                precision=precision),
            lambda: geqrf_ooc(a, w, incore_ib, cache_budget_bytes,
                              ckpt_path=ckpt_path,
                              ckpt_every=ckpt_every,
                              precision=precision),
            "geqrf_ooc", grid)
    nt = ceil_div(n, w)
    # checkpoint/resume (resil/, ISSUE 9): factor + taus live in
    # durable memmaps; resumed runs start their panel loop at the
    # committed epoch — visits read factors 0..k-1 from the durable
    # file, which holds the same device bytes the uninterrupted run
    # wrote, so the resumed factor is BITWISE equal. Composed runs
    # (engine= shared, gels_ooc) never checkpoint.
    ck = _rckpt.maybe_checkpointer(
        ckpt_path, "geqrf_ooc", a, w, nt, every=ckpt_every,
        extra_arrays={"taus": ((kmax,), a.dtype)},
        extra_meta={"precision": _precision_meta(lo)}) \
        if engine is None else None
    if ck is not None:
        out, taus = ck.factor, ck.array("taus")
    else:
        out = np.empty_like(a)
        taus = np.zeros((kmax,), a.dtype)
    own = engine is None
    eng = stream.engine_for(max(m, n), w, a.dtype,
                            budget_bytes=cache_budget_bytes,
                            resident_dtype=lo) \
        if own else engine
    ld = stream.host_demoter(lo)
    visit = _qr_visit if lo is None else _qr_visit_mx
    epoch0 = ck.epoch if ck is not None else 0
    led = _ledger.recorder("geqrf_ooc", nt=nt,
                           spill_dir=ckpt_path if engine is None
                           else None)
    # the loop's phases by their ledger names (potrf_ooc comment)
    S_live, F = {}, {}

    def _stage(k):
        _rfaults.check("step", op="geqrf_ooc", step=k)
        k0, k1 = k * w, min(k * w + w, n)
        with _ledger.frame("stage"):
            S_live[k] = eng.fetch("Ain", k,
                                  lambda k0=k0, k1=k1: a[:, k0:k1],
                                  cache=False)                 # H2D

    def _update(k, j):
        k0 = k * w
        j0 = j * w
        j1 = min(j0 + w, kmax)
        with _ledger.frame("stage"):
            Pj = eng.fetch("QR", j,
                           lambda j0=j0, j1=j1:
                           ld(out[:, j0:j1]))
        if j0 + w < min(k0, kmax):
            p0, p1 = j0 + w, min(j0 + 2 * w, kmax)
            eng.prefetch("QR", p0 // w,
                         lambda p0=p0, p1=p1:
                         ld(out[:, p0:p1]))
        with _ledger.frame("update"):
            S_live[k] = visit(S_live[k], Pj, _h2d(taus[j0:j1]), j0)

    def _pref_next(k):
        k0 = k * w
        if k0 + w < n:
            # next input panel uploads while this one factors
            n0, n1 = k0 + w, min(k0 + 2 * w, n)
            eng.prefetch("Ain", k + 1,
                         lambda n0=n0, n1=n1: a[:, n0:n1],
                         cache=False)

    def _factor(k):
        k0, k1 = k * w, min(k * w + w, n)
        wf = min(k1, kmax) - k0
        S = S_live[k]
        with _ledger.frame("factor"):
            packed, ptau = _qr_panel_factor(S[:, :wf], k0,
                                            incore_ib)
        _rguard.check_panel("geqrf_ooc", k, packed[:m - k0],
                            ref=S)
        F[k] = (packed, ptau, wf)

    def _writeback(k):
        k0, k1 = k * w, min(k * w + w, n)
        S = S_live.pop(k)
        if k0 < kmax:
            packed, ptau, wf = F.pop(k)
            if k0 > 0:
                eng.write("QR", k, S[:k0],       # R rows from visits
                          out[:k0, k0:k1])
            eng.write("QR", k, packed[:m - k0],
                      out[k0:, k0:k0 + wf])
            taus[k0:k0 + wf] = np.asarray(ptau[:wf])
            if wf < k1 - k0:
                rest = _qr_apply_fresh(S[k0:, wf:],
                                       packed[:m - k0], ptau)
                eng.write("QR", k, rest, out[k0:, k0 + wf:k1])
        else:
            eng.write("QR", k, S, out[:, k0:k1])               # D2H

    try:
        for k in range(epoch0, nt):
            if led is not None:
                led.begin(k, epoch=epoch0)
            _health.heartbeat("geqrf_ooc", k, nt)
            _stage(k)
            for j in range(ceil_div(min(k * w, kmax), w)):
                _update(k, j)
            _pref_next(k)
            if k * w < kmax:
                _factor(k)
            _writeback(k)
            if ck is not None and ck.due(k):
                eng.wait_writes()       # every panel <= k is durable
                ck.commit(k + 1)
            if led is not None:
                led.commit()
        _health.heartbeat("geqrf_ooc", nt, nt)   # completion beat
        if led is not None:
            led.begin(nt, epoch=epoch0, drain=True)      # final drain record
        eng.wait_writes()
    finally:
        if own:
            eng.finish()
        else:
            eng.wait_writes()
        if led is not None:
            led.close()
    return out, taus


@instrument_driver("unmqr_ooc")
def unmqr_ooc(qr: np.ndarray, taus: np.ndarray, c: np.ndarray,
              trans: bool = True,
              panel_cols: Optional[int] = None,
              cache_budget_bytes=None,
              engine: Optional["stream.StreamEngine"] = None
              ) -> np.ndarray:
    """Apply Q (trans=False) or Q^H (True) from geqrf_ooc's host
    factor to a device-resident block C, streaming reflector panels
    (Q^H applies panels forward, Q in reverse). A shared `engine`
    (gels_ooc) serves the panels geqrf_ooc just cached without
    re-uploading them."""
    qr = np.asarray(qr)
    kmax = min(qr.shape)
    w = min(_panel_cols(panel_cols, kmax, qr.dtype), kmax)
    starts = list(range(0, kmax, w))
    if not trans:
        starts.reverse()
    own = engine is None
    eng = stream.engine_for(max(qr.shape), w, qr.dtype,
                            budget_bytes=cache_budget_bytes) \
        if own else engine
    try:
        X = _h2d(np.asarray(c))
        for i, j0 in enumerate(starts):
            _health.heartbeat("unmqr_ooc", i, len(starts))
            j1 = min(j0 + w, kmax)
            Pj = eng.fetch("QR", j0 // w,
                           lambda j0=j0, j1=j1: qr[:, j0:j1])
            if i + 1 < len(starts):
                p0 = starts[i + 1]
                eng.prefetch("QR", p0 // w,
                             lambda p0=p0:
                             qr[:, p0:min(p0 + w, kmax)])
            tj = _h2d(taus[j0:j1])
            X = _qr_visit(X, Pj, tj, j0, trans=trans)
        _health.heartbeat("unmqr_ooc", len(starts), len(starts))
        return np.asarray(X)
    finally:
        if own:
            eng.finish()


@instrument_driver("gels_ooc")
def gels_ooc(a: np.ndarray, b: np.ndarray,
             panel_cols: Optional[int] = None,
             cache_budget_bytes=None, grid=None, method=None):
    """Least squares min ||A X - B|| for host-resident TALL A (m >= n)
    via the streamed QR: Q^H B by reflector-panel visits, then the
    upper back-substitution sweep on R (the same backward kernel as
    getrs_ooc). Returns ((QR_packed, taus), X). One engine spans all
    three phases, so the apply and the R sweep are served from the
    panels the factorization cached. ``grid``/``method`` route the
    FACTOR phase through the MethodOOC arbitration: a sharded
    factorization runs on the mesh first (leaving the full packed
    factor on every host), then the apply + R sweep stream through a
    local engine — the sharded factor's panels are not engine-shared,
    so the apply re-stages them (the factor dominates the volume)."""
    from ..core.exceptions import slate_assert
    a = np.asarray(a)
    m, n = a.shape
    slate_assert(m >= n, "gels_ooc requires tall A (m >= n): the R "
                 "back-substitution sweep indexes n factor rows")
    panel_cols = _panel_cols(panel_cols, n, a.dtype)
    w = min(panel_cols, n)
    sharded = _route_shard(n, ceil_div(n, w), grid, method, a.dtype)
    eng = stream.engine_for(m, w, a.dtype,
                            budget_bytes=cache_budget_bytes)
    try:
        if sharded:
            from ..dist.shard_ooc import shard_geqrf_ooc
            qr_p, taus = _shard_escalate(
                lambda: shard_geqrf_ooc(
                    a, grid, panel_cols=w,
                    cache_budget_bytes=cache_budget_bytes),
                lambda: geqrf_ooc(a, panel_cols, engine=eng),
                "gels_ooc", grid)
        else:
            qr_p, taus = geqrf_ooc(a, panel_cols, engine=eng)
        y = unmqr_ooc(qr_p, taus, np.asarray(b), trans=True,
                      panel_cols=panel_cols, engine=eng)
        X = jnp.asarray(y[:n])
        nsweep = ceil_div(n, w)
        for k0 in reversed(range(0, n, w)):
            _health.heartbeat("gels_ooc", nsweep - 1 - k0 // w,
                              nsweep)
            if eng.caching:
                # the R sweep reads the top n rows of the cached
                # full-height reflector panels
                Pk = eng.fetch("QR", k0 // w,
                               lambda k0=k0:
                               qr_p[:, k0:min(k0 + w, n)],
                               view=(0, n))
            else:
                Pk = eng.fetch("QR", k0 // w,
                               lambda k0=k0:
                               qr_p[:n, k0:min(k0 + w, n)],
                               cache=False)
            X = _lu_back_visit(X, Pk, k0)
        _health.heartbeat("gels_ooc", nsweep, nsweep)
        return (qr_p, taus), np.asarray(X)
    finally:
        eng.finish()


@instrument_driver("gemm_ooc")
def gemm_ooc(alpha, a: np.ndarray, b: np.ndarray, beta,
             c: np.ndarray,
             row_panel: Optional[int] = None,
             cache_budget_bytes=None) -> np.ndarray:
    """C = alpha A B + beta C with A and C streamed through the chip
    in row panels; B stays device-resident (the tall-A regime — for
    B beyond HBM, tile the k dimension at the call site). Host in,
    host out. BLAS convention: C is neither read nor transferred when
    beta == 0 (so an uninitialized C is legal and the streamed input
    volume halves in the overwrite case). Each row panel is visited
    exactly once, so there is nothing for the residency cache to
    reuse — the engine contributes the async pipeline only (A/C
    panel prefetch + C writeback overlap) and the transfer
    accounting (every upload through _h2d)."""
    a = np.asarray(a)
    m = a.shape[0]
    row_panel = _panel_cols(row_panel, m, a.dtype)
    eng = stream.engine_for(m, row_panel, a.dtype,
                            budget_bytes=cache_budget_bytes)
    if beta != 0 and eng.prefetch_depth:
        # one iteration of lookahead here is TWO panels (A row + C
        # row); at the frozen depth the C prefetch would always find
        # the single pending slot taken and silently degrade to a
        # synchronous upload
        eng.prefetch_depth *= 2
    out = np.empty_like(c)
    try:
        Bd = _h2d(np.asarray(b)) * alpha
        starts = list(range(0, m, row_panel))
        for i, r0 in enumerate(starts):
            _health.heartbeat("gemm_ooc", i, len(starts))
            r1 = min(r0 + row_panel, m)
            Ab = eng.fetch("Arow", i, lambda r0=r0, r1=r1: a[r0:r1],
                           cache=False)
            if beta == 0:
                blk = _gemm_block_overwrite(Ab, Bd)
            else:
                Cb = eng.fetch("Crow", i,
                               lambda r0=r0, r1=r1: c[r0:r1],
                               cache=False)
                blk = _gemm_block(Ab, Bd, beta, Cb)
            if i + 1 < len(starts):
                p0 = starts[i + 1]
                p1 = min(p0 + row_panel, m)
                eng.prefetch("Arow", i + 1,
                             lambda p0=p0, p1=p1: a[p0:p1],
                             cache=False)
                if beta != 0:
                    eng.prefetch("Crow", i + 1,
                                 lambda p0=p0, p1=p1: c[p0:p1],
                                 cache=False)
            eng.write("Cout", i, blk, out[r0:r1])
        _health.heartbeat("gemm_ooc", len(starts), len(starts))
        eng.wait_writes()
    finally:
        eng.finish()
    return out
