"""Pallas TPU kernels for structure-aware hot ops.

DESIGN (round 10): the panel path is **block-recursive**. The round-3
generation of these kernels did one rank-1 VPU update per column,
which loses to XLA's native LU panel for the same reason the native
loses to gemm — a latency-bound column recurrence (~4.6 vs 3.0 µs/col
at 4096x256, PERF.md Round-4 "LU panel wall"). ``lu_panel_rec``
factors an (m, w) panel by recursive halving (w -> w/2 -> ... -> ib):
every flop outside the innermost ib-wide base case lands in an
MXU-shaped rank-ib matmul, and only the base case runs the sequential
per-column recurrence (fused argmax + row-select partial pivoting,
done with masked whole-panel selects — Mosaic dynamic row ops
measured ~1 µs each in round 3, so dynamic indexing never appears).
Panels too tall for one VMEM-resident dispatch split at the JAX level
(same halving), with the trailing rank-w/2 update gridded over row
blocks — built for panels the native LU custom call cannot compile
at all (methods.NATIVE_LU_MAX_M), but the v5e compiler refuses it
above LU_REC_MAX_M rows too (PR 22 compile-only finding). The same
blocked-recurrence shape serves the steqr2/bdsqr bulge chase:
``givens_chain_apply`` materializes a sweep's rotation chain as
banded block factors ((2b, 2b) windows) and applies them as MXU
matmuls instead of composing one dense (n, n) rotation matrix.

ARBITRATION CONTRACT: every public kernel entry point here

  * has an eligibility gate (``*_eligible`` / ``*_reject_reason``) the
    routing layers consult, and returns ``None`` instead of computing
    when the gate rejects — the caller keeps its fallback;
  * has a registered tune-cache op (``KERNEL_REGISTRY`` maps entry ->
    (gate, tune op); tools/check_instrumented.py lints both), so the
    drivers' method arbitration (lu._lu_panel, eig.steqr2_qr,
    svd.bdsqr_qr) can route to it per (op, size, dtype) from a
    MEASURED cache entry — with the cache cold the drivers route
    exactly as they did before these kernels existed (native / fori /
    dense compose), so a losing kernel costs nothing;
  * runs under the Pallas interpreter on non-TPU backends
    (``pallas_interpret``), so tier-1 (JAX_PLATFORMS=cpu) exercises
    the kernel bodies instead of silently skipping them. Interpreted
    execution is for correctness coverage, not speed; the ROUTING
    gates (``pallas_available``-based) still require real TPU, so
    driver cold paths are identical on CPU.

ON A MEASURED ROUTE (PR 50): ``lu_block_columns`` is the column
recurrence of one base block of lu.lu_panel_blocked, the (ib + 1, m)
block resident in VMEM, m along the lanes: the tall panels of the
pivoted LU above the native LU's height in the cells `grid-gesv`,
`stream-gesv` and `incore-gesv-mixed`. No tune entry routes it: the
platform and the shape decide (its registry row names `lu_panel`'s op
for the lint; nothing reads a key for it).

Float32/bfloat16 only on hardware (the TPU backend has no complex
support; scalar recurrences run in f32 because Mosaic cannot squeeze
bf16 scalars); the interpreter additionally takes f64 where a kernel
has no f32-hardcoded recurrence (givens_chain_apply).

Retained round-3 kernels (``chol_panel``, ``trtri_lower``,
``qr_panel``, rank-1 ``lu_panel``): bench comparison points and the
bf16 fallbacks where the native custom calls end; see PERF.md.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp


def _on_tpu() -> bool:
    """The one device question every routing gate hangs off. No
    `except`: a detection failure must raise, not select the CPU
    routes quietly."""
    return jax.devices()[0].platform == "tpu"


def pallas_available(dtype) -> bool:
    """ROUTING gate: the fused kernels run natively (real TPU and a
    dtype Mosaic takes). Drivers consult this (via the ``*_eligible``
    gates) before rerouting a hot path — interpret-mode execution
    never changes production routing."""
    return _on_tpu() and jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16)


def pallas_interpret() -> bool:
    """True when kernels invoked on a non-TPU backend run through the
    Pallas interpreter instead of returning None (ISSUE 6 satellite:
    tier-1 runs the kernel bodies). Default ON off-TPU; disable with
    SLATE_TPU_PALLAS_INTERPRET=0."""
    if _on_tpu():
        return False
    return os.environ.get("SLATE_TPU_PALLAS_INTERPRET", "1").lower() \
        not in ("0", "off", "false", "no")


def pallas_runnable(dtype) -> bool:
    """Entry-point gate: can a kernel EXECUTE at all — natively on
    TPU, or interpreted elsewhere. The one helper next to
    ``pallas_available`` that the public kernel entries share; routing
    keeps using ``pallas_available``."""
    if pallas_available(dtype):
        return True
    return pallas_interpret() \
        and jnp.dtype(dtype) in (jnp.float32, jnp.bfloat16)


def _reject(kernel: str, reason: str, **args) -> None:
    """Publish one obs instant for a rejected kernel dispatch (ISSUE 6
    satellite: eligibility gates report WHY). No-op with obs off."""
    from ..obs import events as obs
    if obs.enabled():
        obs.instant("pallas.%s.reject" % kernel, cat="kernel",
                    reason=reason, **args)


#: Mosaic lane tile: a DYNAMIC offset in a ref's last dimension must
#: be provably a multiple of this
_LANE = 128


#: public kernel entry point -> (eligibility gate, tune-cache op).
#: The arbitration contract (module doc): tools/check_instrumented.py
#: statically verifies every entry that dispatches a Pallas kernel is
#: listed here, references its gate, and that the tune op has a
#: FROZEN row (tune/cache.py) — a future kernel cannot ship without
#: arbitration.
KERNEL_REGISTRY = {
    "qr_panel": ("qr_panel_eligible", "qr_panel"),
    "lu_panel": ("lu_panel_eligible", "lu_panel"),
    "lu_panel_rec": ("lu_panel_rec_eligible", "lu_panel"),
    "lu_block_columns": ("lu_block_columns_reject_reason", "lu_panel"),
    "trtri_lower": ("trtri_eligible", "trtri"),
    "chol_panel": ("chol_panel_eligible", "chol_panel"),
    "givens_chain_apply": ("givens_chain_eligible", "steqr2"),
    "ragged_potrf": ("ragged_potrf_eligible", "ragged"),
    "ragged_getrf": ("ragged_getrf_eligible", "ragged"),
    "ragged_trsm": ("ragged_trsm_eligible", "ragged"),
}


# -- fused in-VMEM Householder QR panel kernel ---------------------------

#: widest panel factored in one VMEM-resident kernel
QR_PANEL_MAX_W = 128
#: tallest panel (f32: 4096 x 128 = 2 MB in VMEM)
QR_PANEL_MAX_M = 8192


@functools.partial(jax.jit, static_argnames=("m", "w", "interp"))
def _qr_panel_pallas(a: jax.Array, m: int, w: int, interp: bool):
    """Householder QR of an (m, w) panel in one dispatch: w sequential
    reflections, each a column norm + rank-1 update on the VMEM-resident
    panel. Output: packed V-below-diagonal/R-on-upper plus taus (1, w).
    LAPACK larfg conventions (beta = -sign(alpha)|x|, v0 = 1 implicit).

    Reference analogue: internal::geqrf's device-capable panel kernel
    (geqrf.cc:153, Tile_geqrf.hh) — the latency-critical inner loop the
    reference runs on a dedicated thread team."""
    from jax.experimental import pallas as pl

    def kernel(a_ref, out_ref, tau_ref):
        rows_c = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
        cols_r = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        out_ref[:] = a_ref[:]
        tau_ref[:] = jnp.zeros((1, w), jnp.float32)

        def step(j, _):
            colsel = cols_r == j                            # (1, w)
            # scalar recurrence in f32: Mosaic cannot squeeze bf16
            # scalars, and the reflection scalars need the headroom
            x = jnp.sum(jnp.where(colsel, out_ref[:], 0.0),
                        axis=1, keepdims=True).astype(jnp.float32)
            x = jnp.where(rows_c >= j, x, 0.0)
            alpha = jnp.sum(jnp.where(rows_c == j, x, 0.0))
            nrm2 = jnp.sum(x * x)
            nrm = jnp.sqrt(nrm2)
            sign = jnp.where(alpha >= 0, 1.0, -1.0)
            beta = -sign * nrm
            # tau = (beta - alpha) / beta; zero column -> tau = 0
            degenerate = nrm2 <= 0.0
            safe_beta = jnp.where(degenerate, 1.0, beta)
            tau = jnp.where(degenerate, 0.0,
                            (beta - alpha) / safe_beta)
            # v = x / (alpha - beta) below row j, v_j = 1
            denom = alpha - safe_beta
            denom = jnp.where(denom == 0, 1.0, denom)
            v = jnp.where(rows_c > j, x / denom, 0.0)
            v = v + jnp.where(rows_c == j, 1.0, 0.0)
            # apply H = I - tau v v^T to columns > j (operands cast to
            # f32: Mosaic rejects bf16 contractions on the sublane dim)
            vta = jax.lax.dot_general(
                v, out_ref[:].astype(jnp.float32),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)        # (1, w)
            upd = (tau * v) * jnp.where(cols_r > j, vta, 0.0)
            newpan = out_ref[:] - upd.astype(out_ref.dtype)
            # write packed column j: beta on the diagonal, v below
            newcol = jnp.where(rows_c > j, v, 0.0) \
                + jnp.where(rows_c == j, beta, 0.0)
            keep = jnp.where(rows_c < j,
                             jnp.sum(jnp.where(colsel, newpan, 0.0),
                                     axis=1,
                                     keepdims=True).astype(jnp.float32),
                             newcol)
            out_ref[:] = jnp.where(colsel, keep.astype(out_ref.dtype),
                                   newpan)
            tau_ref[:] = jnp.where(colsel, tau, tau_ref[:])
            return 0

        jax.lax.fori_loop(0, w, step, 0)

    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((m, w), a.dtype),
                   jax.ShapeDtypeStruct((1, w), jnp.float32)),
        interpret=interp,
    )(a)


def qr_panel_eligible(m: int, w: int, dtype) -> bool:
    """ROUTING gate for the fused QR panel (qr._qr_panel consults it
    before dispatching): f32/bf16 on real TPU — bf16 is the
    mixed-precision lo path, which XLA's native geqrf custom call
    cannot take — within the one-dispatch VMEM caps."""
    return pallas_available(dtype) and _qr_shape_ok(m, w)


def _qr_shape_ok(m: int, w: int) -> bool:
    from ..tune.select import tuned_int
    return w <= tuned_int("qr_panel", "max_w", QR_PANEL_MAX_W) \
        and m <= QR_PANEL_MAX_M and m % 128 == 0 and w % 8 == 0


def qr_panel(a: jax.Array):
    """(packed, taus) Householder panel factorization; fused Pallas
    kernel for eligible TPU panels (scalar recurrence runs in f32
    in-kernel) and interpreted off-TPU, else None (caller falls back
    to the masked fori_loop panel)."""
    m, w = a.shape
    if not (pallas_runnable(a.dtype) and _qr_shape_ok(m, w)):
        if not _qr_shape_ok(m, w):
            reason = "shape"
        elif jnp.dtype(a.dtype) not in (jnp.float32, jnp.bfloat16):
            reason = "dtype"
        else:
            reason = "platform"     # off-TPU with interpreter off
        _reject("qr_panel", reason, m=m, w=w, dtype=str(a.dtype))
        return None
    packed, taus = _qr_panel_pallas(a, m, w, pallas_interpret())
    return packed, taus[0].astype(a.dtype)


# -- fused in-VMEM partial-pivot LU panel kernel (rank-1, round 3) -------

#: widest LU panel factored in one VMEM-resident kernel
LU_PANEL_MAX_W = 256
#: tallest LU panel (f32: 8192 x 256 = 8 MB in VMEM)
LU_PANEL_MAX_M = 8192


@functools.partial(jax.jit, static_argnames=("m", "w", "interp"))
def _lu_panel_pallas(a: jax.Array, m: int, w: int, interp: bool):
    """Partial-pivot LU of an (m, w) panel in one dispatch: w sequential
    steps of column-max pivot search, two-row swap, scale, rank-1
    update, all on the VMEM-resident panel. Returns (packed LU, local
    pivot row indices (1, w) as f32 — exact for m < 2^24).

    This is the round-3 rank-1 kernel, kept as the bench comparison
    point and bf16 fallback; the production Pallas route is
    ``lu_panel_rec`` (module doc)."""
    from jax.experimental import pallas as pl

    def kernel(a_ref, out_ref, piv_ref):
        rows_c = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
        cols_r = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        out_ref[:] = a_ref[:]
        piv_ref[:] = jnp.zeros((1, w), jnp.float32)

        def step(j, _):
            colsel = cols_r == j                            # (1, w)
            # pivot search in f32: Mosaic cannot squeeze bf16 scalars,
            # and f32 keeps the row index exact for m < 2^24 (bf16
            # would corrupt indices past 256)
            col = jnp.sum(jnp.where(colsel, out_ref[:], 0.0),
                          axis=1, keepdims=True).astype(jnp.float32)
            mag = jnp.where(rows_c >= j, jnp.abs(col), -1.0)
            mx = jnp.max(mag)
            p = jnp.min(jnp.where(mag == mx, rows_c, m))    # first max
            piv_ref[:] = jnp.where(colsel, p.astype(jnp.float32),
                                   piv_ref[:])
            # swap rows j <-> p
            rowj = jnp.sum(jnp.where(rows_c == j, out_ref[:], 0.0),
                           axis=0, keepdims=True)           # (1, w)
            rowp = jnp.sum(jnp.where(rows_c == p, out_ref[:], 0.0),
                           axis=0, keepdims=True)
            pan = out_ref[:]
            pan = jnp.where(rows_c == j, rowp,
                            jnp.where(rows_c == p, rowj, pan))
            # scale multipliers and rank-1 update of columns > j
            # (scalar division in f32, data ops in the panel dtype)
            pivval = jnp.sum(jnp.where(colsel, rowp,
                                       0.0)).astype(jnp.float32)
            safe = jnp.where(pivval == 0, 1.0, pivval)
            col2 = jnp.sum(jnp.where(colsel, pan, 0.0), axis=1,
                           keepdims=True)                   # (m, 1)
            mults = jnp.where(rows_c > j,
                              col2.astype(jnp.float32) / safe,
                              0.0).astype(pan.dtype)        # (m, 1)
            urow = jnp.where(cols_r > j, rowp, 0.0)          # (1, w)
            pan = pan - mults * urow
            # write the multiplier column (rows > j keep mults)
            newcol = jnp.where(rows_c > j, mults, col2)
            pan = jnp.where(colsel, newcol, pan)
            out_ref[:] = pan.astype(out_ref.dtype)
            return 0

        jax.lax.fori_loop(0, w, step, 0)

    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((m, w), a.dtype),
                   jax.ShapeDtypeStruct((1, w), jnp.float32)),
        interpret=interp,
    )(a)


def _lu_max_w() -> int:
    """The rank-1 kernel's width cap, arbitrated like every other
    kernel knob (tune key ("lu_panel", "max_w"), FROZEN default =
    the measured LU_PANEL_MAX_W) — a probe on wider-VMEM parts can
    raise it without a code change."""
    from ..tune.select import tuned_int
    return tuned_int("lu_panel", "max_w", LU_PANEL_MAX_W)


def _lu_shape_ok(m: int, w: int, dtype) -> bool:
    from ..core.methods import vmem_height_cap
    max_m = vmem_height_cap(LU_PANEL_MAX_M, dtype)
    return w <= _lu_max_w() and m <= max_m \
        and m % 128 == 0 and w % 8 == 0


def lu_panel_reject_reason(m: int, w: int, dtype) -> Optional[str]:
    """Why an (m, w) panel of this dtype will NOT run as one fused
    rank-1 kernel (None == eligible): 'platform' (no TPU), 'dtype'
    (not f32/bf16), 'width' (> LU_PANEL_MAX_W), 'height' (above the
    itemsize-scaled VMEM cap — bf16 halves it: the pivot search and
    scaling run in f32, so a narrower panel dtype buys vmem only on
    the panel itself, not the f32 temporaries; measured on v5e: bf16
    8192x256 dies in compile at 20.24M of scoped-vmem stack vs the
    16M limit, PERF.md round-3 sweep), or 'align' (m % 128 / w % 8).
    The ISSUE 6 satellite contract: gates report WHY, and lu_panel /
    getrf surface it via obs instants instead of a silent fori
    fallback."""
    from ..core.methods import vmem_height_cap
    if not _on_tpu():
        return "platform"
    if jnp.dtype(dtype) not in (jnp.float32, jnp.bfloat16):
        return "dtype"
    if w > _lu_max_w():
        return "width"
    if m > vmem_height_cap(LU_PANEL_MAX_M, dtype):
        return "height"
    if m % 128 != 0 or w % 8 != 0:
        return "align"
    return None


def lu_panel_eligible(m: int, w: int, dtype) -> bool:
    """True iff an (m, w) panel of this dtype will run as one fused
    rank-1 kernel on the TPU — the ROUTING gate shared by lu._lu_panel
    and the driver's panel-width policy (lu_panel_reject_reason has
    the per-condition story)."""
    return lu_panel_reject_reason(m, w, dtype) is None


def lu_panel(a: jax.Array):
    """(packed, piv int32) partial-pivot LU panel via the rank-1
    kernel; fused on eligible TPU panels, interpreted off-TPU, else
    None with the rejection reason published as an obs instant
    (caller falls back to the masked fori_loop panel)."""
    m, w = a.shape
    reason = lu_panel_reject_reason(m, w, a.dtype)
    if reason is not None and not (pallas_runnable(a.dtype)
                                   and _lu_shape_ok(m, w, a.dtype)):
        _reject("lu_panel", reason, m=m, w=w, dtype=str(a.dtype))
        return None
    packed, piv = _lu_panel_pallas(a, m, w, pallas_interpret())
    return packed, piv[0].astype(jnp.int32)


# -- block-recursive partial-pivot LU panel kernel (round 10) ------------

#: widest recursive panel (one dispatch OR the JAX-level tall split).
#: Compile-only against a described v5e (PR 22): 1024x512 takes 79 s
#: of Mosaic compile, 2048x256 20 s — 256 keeps every admitted shape
#: under a minute
LU_REC_MAX_W = 256
#: innermost base-case width (tune key ("lu_panel", "ib"))
LU_REC_IB = 32
#: single-dispatch budget in f32-equivalent panel ELEMENTS (m * w):
#: the kernel holds the panel plus several f32 (m, w) temporaries
#: under the 16 MiB scoped-VMEM default. What the v5e compiler takes
#: (compile-only, PR 22): 2048x256 and 4096x128 f32 compile; 4096x256
#: is refused after minutes (19.83M scoped vs the 16M limit). Sub-f32
#: dtypes shrink it (methods.vmem_height_cap rationale: the
#: temporaries stay f32)
LU_REC_MAX_ELEMS = 2048 * 256
#: tallest panel, single dispatch or split: panels narrower than a
#: lane tile still occupy 128 lanes in VMEM, so halving the width
#: stops buying room there (8192x64 f32 is refused at 17.68M scoped)
LU_REC_MAX_M = LU_REC_MAX_ELEMS // 128


def _rec_ib(w: int, ib: Optional[int]) -> int:
    """Base-case width: the caller's override or the tuned/frozen
    default, clamped to a power-of-two divisor of w (the halving
    contract: w = ib * 2^k)."""
    if ib is None:
        from ..tune.select import tuned_int
        ib = tuned_int("lu_panel", "ib", LU_REC_IB, n=w)
    ib = max(8, min(ib, w))
    while w % ib or (w // ib) & (w // ib - 1):
        ib //= 2
        if ib < 8:
            return 8
    return ib


def _rec_max_elems(dtype, max_elems: Optional[int]) -> int:
    from ..core.methods import vmem_height_cap
    return max_elems if max_elems is not None \
        else vmem_height_cap(LU_REC_MAX_ELEMS, dtype)


def lu_panel_rec_reject_reason(m: int, w: int, dtype,
                               max_elems: Optional[int] = None,
                               ib: Optional[int] = None
                               ) -> Optional[str]:
    """Why (m, w) will not factor through the recursive panel path
    (None == eligible): 'platform'/'dtype' as lu_panel, 'width'
    (> LU_REC_MAX_W or not ib * 2^k after clamping), 'aspect'
    (m < w — recursion assumes a tall panel), 'align' (m % 128 /
    w % 8), or 'height' (m above LU_REC_MAX_M, or too tall even for
    the narrowest JAX-level split: m * ib above the single-dispatch
    element budget)."""
    if not _on_tpu():
        return "platform"
    if jnp.dtype(dtype) not in (jnp.float32, jnp.bfloat16):
        return "dtype"
    return _rec_shape_reason(m, w, dtype, max_elems, ib)


def _rec_shape_reason(m: int, w: int, dtype,
                      max_elems: Optional[int] = None,
                      ib: Optional[int] = None) -> Optional[str]:
    if w > LU_REC_MAX_W or w % 8 != 0:
        return "width"
    if m < w:
        return "aspect"
    if m % 128 != 0:
        return "align"
    if m > LU_REC_MAX_M \
            or m * _rec_ib(w, ib) > _rec_max_elems(dtype, max_elems):
        return "height"
    return None


def lu_panel_rec_eligible(m: int, w: int, dtype) -> bool:
    """ROUTING gate for the block-recursive panel (consulted by
    lu._lu_panel's method arbitration when the tune cache routes
    'pallas_rec')."""
    return lu_panel_rec_reject_reason(m, w, dtype) is None


@functools.partial(jax.jit,
                   static_argnames=("m", "w", "ib", "interp"))
def _lu_panel_rec_pallas(a: jax.Array, m: int, w: int, ib: int,
                         interp: bool):
    """Block-recursive partial-pivot LU of an (m, w) panel in ONE
    dispatch. Trace-time recursion halves the width (w -> w/2 -> ...
    -> ib); at each node the left half factors recursively, then the
    right half gets ONE masked-matmul triangular solve (itself
    recursively halved down to an ib-row substitution) and ONE
    masked rank-w/2 MXU matmul; only the ib-wide base case runs the
    sequential per-column recurrence (argmax pivot search + full-row
    swap + segment-confined rank-1), with whole-panel masked selects
    instead of Mosaic dynamic row ops (round-3 lesson: those are
    ~1 µs each). Returns (packed LU, pivot swap targets (1, w) f32 —
    exact for m < 2^24); bitwise the same pivot sequence as
    lu.lu_panel_fori (pinned by the adversarial suite in
    tests/test_pallas_rec.py)."""
    from jax.experimental import pallas as pl

    def kernel(a_ref, out_ref, piv_ref):
        rows_c = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
        cols_r = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        rows_w = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0)
        out_ref[:] = a_ref[:]
        piv_ref[:] = jnp.zeros((1, w), jnp.float32)

        def mm_update(r0, r1, k0, k1, c0, c1):
            # out[r0:r1, c0:c1] -= out[r0:r1, k0:k1] @ out[k0:k1, c0:c1]
            # as ONE masked MXU matmul: k indexes L columns == U rows,
            # both masked in place of the dynamic-width slices Mosaic
            # cannot express (the _chol_fused_pallas trick). The U
            # operand comes from the top w rows (k1 <= w <= m always).
            L = jnp.where((rows_c >= r0) & (rows_c < r1)
                          & (cols_r >= k0) & (cols_r < k1),
                          out_ref[:], 0.0).astype(jnp.float32)
            U = jnp.where((rows_w >= k0) & (rows_w < k1)
                          & (cols_r >= c0) & (cols_r < c1),
                          out_ref[0:w, :], 0.0).astype(jnp.float32)
            P = jax.lax.dot_general(
                L, U, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            out_ref[:] = (out_ref[:] - P.astype(out_ref.dtype))

        def base(c0, wseg):
            # factor columns [c0, c0+wseg): per-column argmax pivot
            # search, FULL-row swap (all w columns, so earlier L and
            # later unfactored columns stay in panel row order — the
            # lu_panel_fori discipline), scale, rank-1 update confined
            # to this segment's columns (the recursion's whole point:
            # columns right of the segment get rank-ib matmuls later)
            def step(jj, _):
                j = c0 + jj
                colsel = cols_r == j                        # (1, w)
                col = jnp.sum(jnp.where(colsel, out_ref[:], 0.0),
                              axis=1,
                              keepdims=True).astype(jnp.float32)
                mag = jnp.where(rows_c >= j, jnp.abs(col), -1.0)
                mx = jnp.max(mag)
                p = jnp.min(jnp.where(mag == mx, rows_c, m))
                piv_ref[:] = jnp.where(colsel, p.astype(jnp.float32),
                                       piv_ref[:])
                rowj = jnp.sum(jnp.where(rows_c == j, out_ref[:], 0.0),
                               axis=0, keepdims=True)       # (1, w)
                rowp = jnp.sum(jnp.where(rows_c == p, out_ref[:], 0.0),
                               axis=0, keepdims=True)
                pan = out_ref[:]
                pan = jnp.where(rows_c == j, rowp,
                                jnp.where(rows_c == p, rowj, pan))
                pivval = jnp.sum(jnp.where(colsel, rowp,
                                           0.0)).astype(jnp.float32)
                safe = jnp.where(pivval == 0, 1.0, pivval)
                col2 = jnp.sum(jnp.where(colsel, pan, 0.0), axis=1,
                               keepdims=True)               # (m, 1)
                mults = jnp.where(rows_c > j,
                                  col2.astype(jnp.float32) / safe,
                                  0.0).astype(pan.dtype)    # (m, 1)
                urow = jnp.where((cols_r > j) & (cols_r < c0 + wseg),
                                 rowp, 0.0)                 # (1, w)
                pan = pan - mults * urow
                newcol = jnp.where(rows_c > j, mults, col2)
                pan = jnp.where(colsel, newcol, pan)
                out_ref[:] = pan.astype(out_ref.dtype)
                return 0

            jax.lax.fori_loop(0, wseg, step, 0)

        def solve(c0, ws, c1, c2):
            # rows [c0, c0+ws) of cols [c1, c2) := L11^{-1} @ (same),
            # L11 unit-lower at [c0:c0+ws) x [c0:c0+ws): recursive
            # halving; base = ib sequential substitution steps, each
            # a masked (m, 1) x (1, w) outer-product AXPY
            if ws <= ib:
                def srow(rr, _):
                    r = c0 + rr
                    rowr = jnp.sum(jnp.where(rows_c == r, out_ref[:],
                                             0.0),
                                   axis=0, keepdims=True)   # (1, w)
                    rowr = jnp.where((cols_r >= c1) & (cols_r < c2),
                                     rowr, 0.0)
                    lcol = jnp.sum(jnp.where(cols_r == r, out_ref[:],
                                             0.0),
                                   axis=1, keepdims=True)   # (m, 1)
                    lcol = jnp.where((rows_c > r)
                                     & (rows_c < c0 + ws), lcol, 0.0)
                    out_ref[:] = (out_ref[:]
                                  - (lcol * rowr).astype(out_ref.dtype))
                    return 0

                jax.lax.fori_loop(0, ws, srow, 0)
            else:
                h = ws // 2
                solve(c0, h, c1, c2)
                mm_update(c0 + h, c0 + ws, c0, c0 + h, c1, c2)
                solve(c0 + h, ws - h, c1, c2)

        def rec(c0, wseg):
            if wseg <= ib:
                base(c0, wseg)
                return
            w1 = wseg // 2
            rec(c0, w1)
            # U12 then the trailing rank-w1 MXU update
            solve(c0, w1, c0 + w1, c0 + wseg)
            mm_update(c0 + w1, m, c0, c0 + w1, c0 + w1, c0 + wseg)
            rec(c0 + w1, wseg - w1)

        rec(0, w)

    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((m, w), a.dtype),
                   jax.ShapeDtypeStruct((1, w), jnp.float32)),
        interpret=interp,
    )(a)


#: row-block heights the gridded trailing update tries, tallest first
_REC_ROW_BLOCKS = (2048, 1024, 512, 256, 128)


@functools.partial(jax.jit, static_argnames=("rb", "interp"))
def _rank_update_pallas(a22: jax.Array, l21: jax.Array,
                        u12: jax.Array, rb: int, interp: bool):
    """A22 - L21 @ U12 GRIDDED OVER ROW BLOCKS — the tall-panel
    trailing update: each grid step holds one (rb, w) row block plus
    the shared (w1, w) U12 in VMEM, so the update runs at any height
    (this is what lets lu_panel_rec factor panels the native LU
    custom call cannot compile, methods.NATIVE_LU_MAX_M)."""
    from jax.experimental import pallas as pl
    m2, w2 = a22.shape
    w1 = l21.shape[1]

    def kernel(a_ref, l_ref, u_ref, o_ref):
        P = jax.lax.dot_general(
            l_ref[:].astype(jnp.float32), u_ref[:].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        o_ref[:] = a_ref[:] - P.astype(a_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(m2 // rb,),
        in_specs=[pl.BlockSpec((rb, w2), lambda i: (i, 0)),
                  pl.BlockSpec((rb, w1), lambda i: (i, 0)),
                  pl.BlockSpec((w1, w2), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rb, w2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m2, w2), a22.dtype),
        interpret=interp,
    )(a22, l21, u12)


def _rank_update(a22: jax.Array, l21: jax.Array, u12: jax.Array):
    """Trailing update dispatcher: the row-block-gridded Pallas kernel
    when a block height divides, else the plain XLA matmul (value-
    identical in exact arithmetic; the kernel exists for the TPU
    schedule, not different math)."""
    m2 = a22.shape[0]
    for rb in _REC_ROW_BLOCKS:
        if m2 % rb == 0 and m2 >= rb:
            return _rank_update_pallas(a22, l21, u12, rb,
                                       pallas_interpret())
    return a22 - jnp.matmul(l21, u12,
                            precision=jax.lax.Precision.HIGHEST)


def _lu_rec_split(a: jax.Array, ib: int, max_elems: int):
    """JAX-level recursive halving for panels too tall for one
    VMEM-resident dispatch: factor the left half (full height), apply
    its composed pivot permutation to the right half (one gather —
    exactly the deferred-laswp discipline of lu._getrf_carry), solve
    U12, run the row-block-gridded trailing update, recurse on the
    right, then permute the left half's lower rows by the right's
    pivots. The pivot SEQUENCE is identical to factoring the whole
    panel column-by-column (swaps compose), so parity with
    lu_panel_fori survives the split."""
    m, w = a.shape
    if m * w <= max_elems:
        packed, piv = _lu_panel_rec_pallas(a, m, w, _rec_ib(w, ib),
                                           pallas_interpret())
        return packed, piv[0].astype(jnp.int32)
    w1 = w // 2
    left, piv1 = _lu_rec_split(a[:, :w1], ib, max_elems)
    perm1 = jax.lax.linalg.lu_pivots_to_permutation(piv1, m)
    right = a[:, w1:][perm1]
    u12 = jax.lax.linalg.triangular_solve(
        left[:w1, :w1], right[:w1], left_side=True, lower=True,
        unit_diagonal=True)
    a22 = _rank_update(right[w1:], left[w1:, :w1], u12)
    sub, piv2 = _lu_rec_split(a22, ib, max_elems)
    perm2 = jax.lax.linalg.lu_pivots_to_permutation(piv2, m - w1)
    left = jnp.concatenate([left[:w1], left[w1:][perm2]], axis=0)
    packed = jnp.concatenate(
        [left, jnp.concatenate([u12, sub], axis=0)], axis=1)
    return packed, jnp.concatenate([piv1, w1 + piv2])


def lu_panel_rec(a: jax.Array, ib: Optional[int] = None,
                 max_elems: Optional[int] = None):
    """(packed, piv int32) partial-pivot LU panel via BLOCK RECURSION
    (module doc): one VMEM-resident dispatch when (m, w) fits the
    element budget, the JAX-level halving with row-block-gridded
    trailing updates when wider than the element budget allows at its
    height (heights stop at LU_REC_MAX_M). Returns None (with the
    reason as an obs
    instant) when ineligible; `ib` overrides the tuned base-case
    width, `max_elems` the single-dispatch budget (tests force the
    tall split with it)."""
    m, w = a.shape
    reason = lu_panel_rec_reject_reason(m, w, a.dtype, max_elems, ib)
    if reason is not None:
        runnable = pallas_runnable(a.dtype) and _rec_shape_reason(
            m, w, a.dtype, max_elems, ib) is None
        if not runnable:
            _reject("lu_panel_rec", reason, m=m, w=w,
                    dtype=str(a.dtype))
            return None
    return _lu_rec_split(a, ib, _rec_max_elems(a.dtype, max_elems))


# -- VMEM-resident column recurrence of lu.lu_panel_blocked (PR 50) ------

#: bytes of VMEM the resident block may take: the tallest block that
#: was compiled for a v5e (tests/test_chip_compile.py), 65536 lanes x
#: 4 B x the 136 sublanes of a base block of 128, 35.7 MB of a core's
#: 128 MiB. It is the kernel's one large buffer; the tallest a cell
#: runs is 26.7 MB (49152 lanes at 128; 14.2 at 64). A taller block
#: keeps the XLA loop and says so (lu._block_columns)
LU_COLS_MAX_BYTES = 136 * 65536 * 4


#: the bits of |x| above +inf's are NaNs: the column search's one NaN
_NAN_BITS = 0x7f800001


def _cols_rows(ib: int) -> int:
    """Sublanes of the resident block: the ib columns of the base
    block, the row positions, padded to the f32 sublane tile."""
    return -(-(ib + 1) // 8) * 8


def _cols_chunk(m: int) -> int:
    """Lanes one pass of the kernel's loops takes at a time: the most
    whole lane tiles, up to eight, that divide m (every height the
    cells run is a multiple of 1024)."""
    return next(_LANE * k for k in (8, 4, 2, 1) if m % (_LANE * k) == 0)


def lu_block_columns_reject_reason(ib: int, m: int, dtype,
                                   platform: bool = True
                                   ) -> Optional[str]:
    """ROUTING gate of `lu_block_columns`, by platform and shape
    alone (no tune entry moves it): why the column recurrence of an
    (ib + 1, m) base block will not run out of VMEM, None where it
    will. 'platform' (no TPU; `platform=False` leaves the question
    out, for the interpreter), 'dtype' (the block, its row positions
    included, is f32), 'align' (m off the lane tile, ib off the
    sublane tile) or 'height' (the block above LU_COLS_MAX_BYTES)."""
    if platform and not _on_tpu():
        return "platform"
    if jnp.dtype(dtype) != jnp.float32:
        return "dtype"
    if ib % 8 != 0 or m % _LANE != 0:
        return "align"
    if _cols_rows(ib) * m * 4 > LU_COLS_MAX_BYTES:
        return "height"
    return None


@functools.partial(jax.jit, static_argnames=("ib", "m", "interp"))
def _lu_block_columns_pallas(tb: jax.Array, j0: jax.Array, ib: int,
                             m: int, interp: bool):
    """The ib column steps of one base block of lu.lu_panel_blocked on
    the (R, m) block held in VMEM (R = `_cols_rows(ib)`: row jj the
    panel's column j0 + jj, row ib the row positions, m along the
    lanes). One DMA each way a block; a column step is the masked
    first-index argmax over row jj, the two 128-lane tiles that hold
    lanes j and p rewritten for the exchange, and one pass over the
    sublane groups at or below jj's for the multipliers and the
    rank-1 update. Every pass starts at the lane chunk that holds j:
    the lanes left of it are finished rows. Same arithmetic as the
    XLA `column` loop it replaces. Returns (block, swap targets
    (ib,) int32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    R, C = _cols_rows(ib), _cols_chunk(m)
    nchunk = m // C
    i32 = jnp.int32

    def kernel(j0_ref, tb_hbm, out_hbm, pv_ref, buf, sem):
        load = pltpu.make_async_copy(tb_hbm, buf, sem)
        load.start()
        load.wait()
        j0 = j0_ref[0]
        lane = jax.lax.broadcasted_iota(i32, (1, C), 1)
        lane_t = jax.lax.broadcasted_iota(i32, (R, _LANE), 1)
        sub_r = jax.lax.broadcasted_iota(i32, (R, 1), 0)

        def column(jj, _):
            j = j0 + jj
            first = j // C

            def magnitudes(c):
                # |x|'s bits as an integer: they order as the floats
                # do, and a NaN stands above every number (all NaNs
                # alike), so the search never comes back empty-handed
                # and takes the first NaN, as jnp.argmax does on the
                # CPU (the TPU's takes one of them, not always the
                # first: read on the chip, PR 50). 3-5% of a column
                # step over a float search that a NaN sends out of
                # the block
                c0 = pl.multiple_of(c * C, C)
                bits = jax.lax.bitcast_convert_type(
                    jnp.abs(buf[pl.ds(jj, 1), pl.ds(c0, C)]), i32)
                return jnp.minimum(bits, _NAN_BITS)

            def search(c, best):
                val, chunk = best
                mag = magnitudes(c)
                take = mag > val
                return jnp.where(take, mag, val), jnp.where(take, c, chunk)

            # the chunk that holds j is the one with finished rows in
            # it; a slot keeps the first chunk that brought its
            # maximum, so the lowest index wins among equal
            # magnitudes, as in jnp.argmax. Some lane always equals an
            # integer maximum: p is a lane of the block
            val, chunk = jax.lax.fori_loop(
                first + 1, i32(nchunk), search,
                (jnp.where(lane + first * C >= j, magnitudes(first), -1),
                 jnp.full((1, C), first, i32)))
            p = jnp.min(jnp.where(val == jnp.max(val), chunk * C + lane, m))
            pv_ref[jj] = p
            # lanes j <-> p of every row: the two tiles that hold them
            tj = pl.multiple_of((j // _LANE) * _LANE, _LANE)
            tp = pl.multiple_of((p // _LANE) * _LANE, _LANE)
            at_j = jnp.sum(jnp.where(lane_t == j - tj,
                                     buf[:, pl.ds(tj, _LANE)], 0.0),
                           axis=1, keepdims=True)
            tile_p = buf[:, pl.ds(tp, _LANE)]
            at_p = jnp.sum(jnp.where(lane_t == p - tp, tile_p, 0.0),
                           axis=1, keepdims=True)
            buf[:, pl.ds(tp, _LANE)] = jnp.where(lane_t == p - tp, at_j,
                                                 tile_p)
            buf[:, pl.ds(tj, _LANE)] = jnp.where(
                lane_t == j - tj, at_p, buf[:, pl.ds(tj, _LANE)])
            pivval = jnp.sum(jnp.where(sub_r == jj, at_p, 0.0), axis=0,
                             keepdims=True)
            safe = jnp.where(pivval == 0, 1.0, pivval)
            ucol = jnp.where((sub_r > jj) & (sub_r < ib), at_p, 0.0)

            def update(c, _):
                c0 = pl.multiple_of(c * C, C)
                below = lane + c0 > j
                row = buf[pl.ds(jj, 1), pl.ds(c0, C)]
                mult = jnp.where(below, row / safe, 0.0)
                wide = jnp.broadcast_to(mult, (8, C))
                # the groups above jj's are finished rows; the last
                # group (the positions, the padding) is never updated
                for g in range(ib // 8):
                    @pl.when(jj < 8 * g + 8)
                    def _(g=g):
                        buf[8 * g:8 * g + 8, pl.ds(c0, C)] = (
                            buf[8 * g:8 * g + 8, pl.ds(c0, C)]
                            - ucol[8 * g:8 * g + 8] * wide)
                # row jj itself (its ucol is 0) keeps the multipliers
                buf[pl.ds(jj, 1), pl.ds(c0, C)] = jnp.where(below, mult, row)
                return 0

            jax.lax.fori_loop(first, i32(nchunk), update, 0)
            return 0

        jax.lax.fori_loop(i32(0), i32(ib), column, 0)
        store = pltpu.make_async_copy(buf, out_hbm, sem)
        store.start()
        store.wait()

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        in_specs=[smem, any_space],
        out_specs=(any_space, smem),
        out_shape=(jax.ShapeDtypeStruct((R, m), jnp.float32),
                   jax.ShapeDtypeStruct((ib,), i32)),
        scratch_shapes=[pltpu.VMEM((R, m), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        # the block is read once, at the top: it may back the output
        input_output_aliases={1: 0},
        # the block and a step's few vector registers' worth of
        # values, no more: XLA keeps the panel the block loop carries
        # in VMEM too (100.7 MB at (49152, 512)) and moves it out and
        # back around a call that asks for what is left (compiled for
        # a described v5e, PR 50: with 16 MiB of room a block of 128
        # columns cost two such moves a block, with 2 none)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=R * m * 4 + (2 << 20)),
        interpret=interp,
    )(jnp.reshape(j0, (1,)).astype(i32), tb)


def lu_block_columns(tb: jax.Array, j0: jax.Array, ib: int):
    """(factored block, swap targets (ib,) int32) of one base block of
    lu.lu_panel_blocked, `tb` the (ib + 1, m) f32 block (its last row
    the row positions) whose first column is the panel's column `j0`:
    the column recurrence run out of VMEM (`_lu_block_columns_pallas`;
    interpreted off the chip). The caller routes by
    `lu_block_columns_reject_reason` and keeps its XLA loop where
    that refuses; a shape it refuses is an error here."""
    m = tb.shape[1]
    reason = lu_block_columns_reject_reason(ib, m, tb.dtype, platform=False)
    if reason is not None:
        raise ValueError("lu_block_columns: %s (ib=%d, m=%d, %s)"
                         % (reason, ib, m, tb.dtype))
    pad = _cols_rows(ib) - (ib + 1)
    out, pv = _lu_block_columns_pallas(
        jnp.pad(tb, ((0, pad), (0, 0))), j0, ib, m, pallas_interpret())
    return out[:ib + 1], pv


# -- blocked Givens-chain apply (steqr2/bdsqr bulge chase) ---------------

#: rotation-group width b: factors are (2b, 2b) windows on b-spaced
#: anchors (tune key ("steqr2", "chain_blk"))
GIVENS_CHAIN_BLK = 128


def _chain_window_matrix(cs: jax.Array, sn: jax.Array, size: int,
                         dtype) -> jax.Array:
    """Compose adjacent-pair rotations G_0..G_{size-2} (G_k on index
    pair (k, k+1)) into one (size, size) matrix — the ONE chain
    compose (svd._givens_chain_matrix), applied to a window. Identity
    rotations (c=1, s=0) pass through exactly, which is what lets a
    group's factor embed in a larger window."""
    from ..linalg.svd import _givens_chain_matrix
    return _givens_chain_matrix(cs, sn, size, dtype)


def _chain_anchor(j: int, n: int, blk: int) -> int:
    """Window anchor for rotation group j: b-spaced, clamped so the
    last (2b)-wide window stays inside [0, n)."""
    return min(j * blk, n - 2 * blk)


def givens_chain_factors(cs: jax.Array, sn: jax.Array, n: int,
                         blk: int, dtype) -> jax.Array:
    """Materialize the sweep's rotation chain as (n/blk, 2*blk,
    2*blk) banded block factors: group j holds rotations
    [j*blk, min((j+1)*blk, n-1)), whose indices all live inside the
    2*blk window at its anchor, identity-padded. Exact identity
    (pinned by test): embedding the factors at their anchors and
    multiplying in group order reproduces svd._givens_chain_matrix."""
    facs = []
    for j in range(n // blk):
        k0, k1 = j * blk, min((j + 1) * blk, n - 1)
        a0 = _chain_anchor(j, n, blk)
        cw = jnp.ones((2 * blk - 1,), dtype)
        sw = jnp.zeros((2 * blk - 1,), dtype)
        cw = cw.at[k0 - a0:k1 - a0].set(cs[k0:k1])
        sw = sw.at[k0 - a0:k1 - a0].set(sn[k0:k1])
        facs.append(_chain_window_matrix(cw, sw, 2 * blk, dtype))
    return jnp.stack(facs)


@functools.partial(jax.jit,
                   static_argnames=("rows", "n", "blk", "rb", "interp"))
def _givens_apply_pallas(Z: jax.Array, facs: jax.Array, rows: int,
                         n: int, blk: int, rb: int, interp: bool):
    """Apply the banded block factors to Z's columns, GRIDDED OVER ROW
    BLOCKS of Z: each grid step holds one (rb, n) row block plus the
    (g, 2b, 2b) factors in VMEM and sweeps the b-spaced windows left
    to right (consecutive windows overlap by b columns, so the order
    is the rotation order), each window one (rb, 2b) x (2b, 2b) MXU
    matmul — O(n^2 b) per sweep instead of the dense compose's
    O(n^3)."""
    from jax.experimental import pallas as pl
    g = n // blk
    pet = jnp.promote_types(Z.dtype, jnp.float32)

    def kernel(z_ref, f_ref, o_ref):
        o_ref[:] = z_ref[:]
        for j in range(g):
            a0 = _chain_anchor(j, n, blk)
            win = o_ref[:, a0:a0 + 2 * blk]
            o_ref[:, a0:a0 + 2 * blk] = jax.lax.dot_general(
                win.astype(pet), f_ref[j].astype(pet),
                (((1,), (0,)), ((), ())),
                preferred_element_type=pet,
                precision=jax.lax.Precision.HIGHEST).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(rows // rb,),
        in_specs=[pl.BlockSpec((rb, n), lambda i: (i, 0)),
                  pl.BlockSpec((g, 2 * blk, 2 * blk),
                               lambda i: (0, 0, 0))],
        out_specs=pl.BlockSpec((rb, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), Z.dtype),
        interpret=interp,
    )(Z, facs)


def _chain_blk(blk: Optional[int]) -> int:
    if blk is not None:
        return blk
    from ..tune.select import tuned_int
    return tuned_int("steqr2", "chain_blk", GIVENS_CHAIN_BLK)


def _chain_shape_ok(rows: int, n: int, blk: int) -> bool:
    return n % blk == 0 and n >= 2 * blk \
        and rows % 8 == 0 and _chain_rb(rows, n, blk) is not None


#: VMEM budget for one gridded chain-apply step: in + out row blocks
#: PLUS the full (g, 2b, 2b) factor stack must fit with pipelining
#: headroom under the 16 MB core limit
_CHAIN_VMEM_BUDGET = 12 << 20


def _chain_rb(rows: int, n: int, blk: int) -> Optional[int]:
    """Row-block height for the gridded apply: largest divisor of
    `rows` whose grid step fits the VMEM budget — the step holds the
    (rb, n) input AND output blocks plus the whole factor stack
    ((n/blk) * (2*blk)^2 f32 = 16*n*blk bytes), so the stack is part
    of the budget (it grows with blk even though it never re-fetches
    per step)."""
    facs_bytes = 16 * n * blk
    if facs_bytes >= _CHAIN_VMEM_BUDGET:
        return None
    for rb in (512, 256, 128, 64, 32, 16, 8):
        if rows % rb == 0 \
                and 2 * rb * n * 4 + facs_bytes <= _CHAIN_VMEM_BUDGET:
            return rb
    return None


def givens_chain_eligible(rows: int, n: int, dtype,
                          blk: Optional[int] = None) -> bool:
    """ROUTING gate for the blocked chain apply (eig.steqr2_qr /
    svd.bdsqr_qr consult it when the tune cache routes 'pallas_rec'):
    TPU dtypes on hardware, any float under the interpreter (the
    kernel has no f32-hardcoded recurrence), n a multiple of the
    block width with at least two windows, and a row-block height
    that divides."""
    b = _chain_blk(blk)
    if not _chain_shape_ok(rows, n, b):
        return False
    if pallas_available(dtype):
        return True
    return pallas_interpret() \
        and jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


def givens_chain_apply(Z: jax.Array, cs: jax.Array, sn: jax.Array,
                       blk: Optional[int] = None):
    """Z @ G for G the composed Givens chain of (cs, sn) (identical to
    Z @ svd._givens_chain_matrix(cs, sn, n, dt) — pinned by test),
    computed as banded block factors applied window-by-window as MXU
    matmuls. Returns None when ineligible (caller keeps the dense
    compose)."""
    rows, n = Z.shape
    b = _chain_blk(blk)
    if not givens_chain_eligible(rows, n, Z.dtype, b):
        _reject("givens_chain_apply", "shape", rows=rows, n=n,
                dtype=str(Z.dtype))
        return None
    dt = jnp.promote_types(Z.dtype, cs.dtype)
    facs = givens_chain_factors(cs.astype(dt), sn.astype(dt), n, b, dt)
    return _givens_apply_pallas(Z, facs, rows, n, b,
                                _chain_rb(rows, n, b),
                                pallas_interpret())


# -- fused in-VMEM triangular inversion kernel ---------------------------

#: largest block inverted in one VMEM-resident kernel
TRTRI_FUSED_MAX = 512


@functools.partial(jax.jit, static_argnames=("n", "unit", "interp"))
def _trtri_lower_pallas(a: jax.Array, n: int, unit: bool, interp: bool):
    """inv(L) for lower-triangular (n, n) by forward substitution kept
    entirely in VMEM: one dispatch, n sequential row steps, each a
    (1, n) x (n, n) MXU product. Substitution-grade numerics (explicit
    Neumann/product forms overflow for unit-lower LU blocks).

    Reference analogue: the trsm diag-block inversion the reference does
    per-tile with lapack::trtri on the device queue (trsm variants via
    work_trsm.cc); upper inputs are handled by the caller via transpose.
    """
    from jax.experimental import pallas as pl

    def kernel(a_ref, out_ref):
        cols_r = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
        out_ref[:] = jnp.zeros((n, n), a_ref.dtype)

        def row(j, _):
            arow = a_ref[pl.ds(j, 1), :]                     # (1, n)
            lj = jnp.where(cols_r < j, arow, 0.0)
            prod = jax.lax.dot_general(
                lj, out_ref[:], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)         # (1, n)
            ej = jnp.where(cols_r == j, 1.0, 0.0).astype(a_ref.dtype)
            xj = ej - prod.astype(a_ref.dtype)
            if not unit:
                ljj = jnp.sum(jnp.where(cols_r == j, arow, 0.0))
                ljj = jnp.where(ljj == 0, 1.0, ljj).astype(a_ref.dtype)
                xj = xj / ljj
            out_ref[pl.ds(j, 1), :] = xj
            return 0

        jax.lax.fori_loop(0, n, row, 0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interp,
    )(a)


def trtri_eligible(n: int, dtype) -> bool:
    """ROUTING gate for the fused substitution kernel: f32 TPU blocks
    within the one-dispatch cap (bench comparison point since round
    3 — the XLA solve beats it on current libtpu, PERF.md)."""
    return pallas_available(dtype) and jnp.dtype(dtype) == jnp.float32 \
        and _trtri_shape_ok(n)


def _trtri_shape_ok(n: int) -> bool:
    from ..tune.select import tuned_int
    return n <= tuned_int("trtri", "fused_max", TRTRI_FUSED_MAX) \
        and n % 128 == 0


def trtri_lower(a: jax.Array, unit_diagonal: bool = False) -> jax.Array:
    """Lower-triangular inverse of one block: fused Pallas substitution
    when trtri_eligible (or interpreted off-TPU for f32), else XLA
    triangular_solve (LAPACK-backed and fast on CPU)."""
    n = a.shape[0]
    if trtri_eligible(n, a.dtype) or (
            pallas_runnable(a.dtype) and a.dtype == jnp.float32
            and _trtri_shape_ok(n)):
        return _trtri_lower_pallas(a, n, unit_diagonal,
                                   pallas_interpret())
    return jax.lax.linalg.triangular_solve(
        a, jnp.eye(n, dtype=a.dtype), left_side=True, lower=True,
        unit_diagonal=unit_diagonal)


# -- fused in-VMEM Cholesky panel kernel ---------------------------------

_CHOL_BLK = 128

#: largest panel kept fully in VMEM (f32: 4 MB at 1024)
CHOL_FUSED_MAX = 1024


@functools.partial(jax.jit, static_argnames=("n", "interp"))
def _chol_fused_pallas(a: jax.Array, n: int, interp: bool):
    from jax.experimental import pallas as pl

    blk = min(_CHOL_BLK, n)
    nblk = n // blk

    def kernel(a_ref, out_ref):
        # all intermediates kept rank-2 (Mosaic layouts for 1D vectors
        # are fragile); rows_c is an (n,1) column, colsl_r a (1,blk) row
        rows_c = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        colsl_r = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
        out_ref[:] = a_ref[:]

        def stripe(kb, _):
            k0 = kb * blk
            S = out_ref[:, pl.ds(k0, blk)]                  # (n, blk)
            # left-looking update: S -= L[:, :k0] @ L[k0:k1, :k0]^T via
            # full-width masked matmul (masks stand in for the
            # dynamic-width slice, which Mosaic cannot express)
            colmask = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
                       < k0)
            Lm = jnp.where(colmask, out_ref[:], 0.0)
            G = out_ref[pl.ds(k0, blk), :]                  # (blk, n)
            gmask = (jax.lax.broadcasted_iota(jnp.int32, (blk, n), 1)
                     < k0)
            G = jnp.where(gmask, G, 0.0)
            S = S - jax.lax.dot_general(
                Lm, G, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST).astype(S.dtype)

            # projT[r, c] == (r == k0 + c): row-extraction mask standing
            # in for a value dynamic_slice (unsupported in Mosaic)
            projT = (jax.lax.broadcasted_iota(jnp.int32, (n, blk), 0)
                     == jax.lax.broadcasted_iota(jnp.int32, (n, blk), 1)
                     + k0)

            def col(jj, S):
                j = k0 + jj
                sel = colsl_r == jj                          # (1, blk)
                colv = jnp.sum(jnp.where(sel, S, 0.0), axis=1,
                               keepdims=True)               # (n, 1)
                piv = jnp.sum(jnp.where(rows_c == j, colv, 0.0))
                d = jnp.sqrt(piv)
                dsafe = jnp.where(d == 0, 1.0, d).astype(S.dtype)
                v = jnp.where(rows_c > j, colv / dsafe,
                              0.0).astype(S.dtype)          # (n, 1)
                newcol = v + jnp.where(rows_c == j, d,
                                       0.0).astype(S.dtype)
                S = jnp.where(sel, newcol, S)
                vrow = jnp.sum(jnp.where(projT, v, 0.0), axis=0,
                               keepdims=True)               # (1, blk)
                S = S - (v * jnp.where(colsl_r > jj, vrow, 0.0)
                         ).astype(S.dtype)
                return S

            S = jax.lax.fori_loop(0, blk, col, S)
            out_ref[:, pl.ds(k0, blk)] = S
            return 0

        jax.lax.fori_loop(0, nblk, stripe, 0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interp,
    )(a)


def chol_panel_eligible(n: int, dtype) -> bool:
    """ROUTING gate for the fused Cholesky panel: f32 TPU blocks
    within the one-dispatch cap (bench comparison point since round
    3, PERF.md)."""
    return pallas_available(dtype) and jnp.dtype(dtype) == jnp.float32 \
        and _chol_shape_ok(n)


def _chol_shape_ok(n: int) -> bool:
    from ..tune.select import tuned_int
    return n <= tuned_int("chol_panel", "fused_max", CHOL_FUSED_MAX) \
        and n % _CHOL_BLK == 0


def chol_panel(a: jax.Array) -> jax.Array:
    """Lower Cholesky of an SPD block; fused Pallas kernel when
    chol_panel_eligible (or interpreted off-TPU for f32), else XLA's
    cholesky. Upper triangle of the result is unspecified (callers
    mask), matching LAPACK."""
    n = a.shape[0]
    if chol_panel_eligible(n, a.dtype) or (
            pallas_runnable(a.dtype) and a.dtype == jnp.float32
            and _chol_shape_ok(n)):
        return _chol_fused_pallas(a, n, pallas_interpret())
    # symmetrize_input=False: callers hand blocks whose upper triangle
    # may hold stale values (lower-only trailing updates); averaging it
    # in would corrupt the factor
    return jax.lax.linalg.cholesky(a, symmetrize_input=False)


# -- ragged batched kernels (round 15): kill the padding tax -------------
#
# One kernel over a RAGGED batch: the stack is padded to a single
# ceiling shape (the max live size rounded to lane alignment — no pow2
# rounding), and a per-element ``sizes`` vector rides as a
# scalar-prefetch operand (the Ragged Paged Attention play, PAPERS.md,
# applied to dense factorizations). Each grid step owns one element:
# the kernel rebuilds the bucket layer's validity-masked padding
# IN-KERNEL (identity diagonal outside the live block, so garbage in
# the pad region can never leak), bounds its blocked sweep with a
# DYNAMIC trip count ceil(s/blk) — stripes past the element's true
# extent never execute — and masks every base-case op and rank-blk MXU
# update with the whole-panel masks of the PR 6 recursion (no Mosaic
# dynamic row ops). Pivoting discipline matches bucket.py's identity
# padding exactly: live columns hold exact zeros in padded rows, so a
# padded row is unpivotable, and padded columns pivot on their own
# unit diagonal (pinned by tests/test_ragged.py's adversarial suite).
#
# Work accounting: the dynamic trip count confines each element to its
# block-aligned true extent along the FACTOR dimension (ceil(s/blk)
# stripes instead of N/blk), which is where the batch layer's cubic
# padding tax lives; the per-stripe masked matmuls still span the
# ceiling's row/column extent in one VMEM block (a row-block grid over
# the ragged row extent is the TPU hardware round's follow-up). The
# batch queue reports ragged dispatch waste against the block-aligned
# extents (bucket.ragged_report).

#: stripe / base-case width of the ragged batched kernels (tune key
#: ("ragged", "blk")); the ragged ceiling is aligned to lcm(align, blk)
RAGGED_BLK = 32


def ragged_blk(blk: Optional[int] = None, opts=None) -> int:
    """The tuned/frozen ragged block width, clamped to a positive
    multiple of 8 (Mosaic sublane granularity). ``opts`` threads the
    caller's per-call tuning controls (Option.Tune etc.) into the
    cache read."""
    if blk is None:
        from ..tune.select import tuned_int
        blk = tuned_int("ragged", "blk", RAGGED_BLK, opts=opts)
    return max(8, (int(blk) // 8) * 8)


def _ragged_dtype_ok(dtype) -> bool:
    """f32/bf16 on hardware; any float under the interpreter (no
    f32-hardcoded recurrence: arithmetic runs in promote(dtype, f32),
    so tier-1's f64 batches exercise the kernels at full precision)."""
    if pallas_available(dtype):
        return True
    return pallas_interpret() \
        and jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


def ragged_supported(dtype) -> bool:
    """Submit-time routing gate for the batch queue's ragged strategy:
    can the ragged kernels execute for this dtype at all (natively on
    TPU, or interpreted elsewhere). Shape eligibility is checked per
    dispatch by the ``ragged_*_eligible`` gates — the queue constructs
    the ceiling to satisfy them (bucket.ragged_ceiling)."""
    return _ragged_dtype_ok(dtype)


def _ragged_shape_ok(n: int, blk: int) -> bool:
    return n >= blk and n % blk == 0


def _ragged_reject_reason(n: int, dtype, blk: int) -> Optional[str]:
    if not _ragged_dtype_ok(dtype):
        return "dtype" if _on_tpu() or pallas_interpret() else "platform"
    if not _ragged_shape_ok(n, blk):
        return "shape"
    return None


def ragged_potrf_eligible(n: int, dtype, blk: Optional[int] = None
                          ) -> bool:
    """Eligibility gate for the ragged batched Cholesky: runnable
    dtype (hardware or interpreter) and a ceiling that is a positive
    multiple of the ragged block width."""
    return _ragged_reject_reason(n, dtype, ragged_blk(blk)) is None


def ragged_getrf_eligible(n: int, dtype, blk: Optional[int] = None
                          ) -> bool:
    """Eligibility gate for the ragged batched partial-pivot LU (same
    conditions as ragged_potrf_eligible; the pivot vector is exact for
    n < 2^24 — f32 index rows, the lu_panel discipline)."""
    return _ragged_reject_reason(n, dtype, ragged_blk(blk)) is None \
        and n < (1 << 24)


def ragged_trsm_eligible(n: int, k: int, dtype,
                         blk: Optional[int] = None) -> bool:
    """Eligibility gate for the ragged batched triangular solve:
    ragged ceiling conditions plus at least one right-hand-side
    column (rhs lane padding is a TPU hardware-round follow-up; the
    interpreter takes any k)."""
    return _ragged_reject_reason(n, dtype, ragged_blk(blk)) is None \
        and k >= 1


def _ragged_donate_ok() -> bool:
    """Buffer donation is a TPU-side win (drivers._donate_ok
    rationale); on CPU it is an unimplemented per-call warning, so it
    is never enabled there. The ragged kernels additionally alias
    their consumed operand onto the output via pallas
    ``input_output_aliases`` (each kernel reads it exactly once, at
    the top of its grid step), so a donated stack factors in place —
    the bucket path's donation contract carried to the ragged route."""
    return jax.default_backend() != "cpu"


def _lane_up(n: int) -> int:
    """n rounded up to the lane tile. The ragged kernels work at this
    extent of their ceiling, so every kernel-side shape is
    Mosaic-aligned whatever the ceiling's lcm(align, blk) rung. The
    pad is identity (the kernels rebuild blkdiag(A[:s, :s], I) from
    ``sizes``) and is cropped by the wrapper inside the same jit."""
    return -(-n // _LANE) * _LANE


def _ragged_params(n: int, itemsize: int):
    """Mosaic parameters of the ragged kernels: the "whole element in
    VMEM" design needs more than the 16 MiB scoped default (the
    pipelined in/out blocks alone are 4 n^2 words), so the limit is
    sized from the element, capped under v5e's 128 MiB of VMEM."""
    from jax.experimental.pallas import tpu as pltpu
    need = 16 * n * n * max(itemsize, 4)
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(max(need, 32 << 20), 100 << 20)))


def _ragged_potrf_pallas(sizes: jax.Array, stack: jax.Array, B: int,
                         N: int, blk: int, interp: bool):
    """Ragged batched lower Cholesky: grid over the batch, one (N, N)
    element per step, its true order s prefetched from ``sizes``. The
    element is rebuilt as blkdiag(A[:s, :s], I) in VMEM, then the
    fused blocked sweep (_chol_fused_pallas's stripe shape) runs
    ceil(s/blk) stripes — a DYNAMIC trip count, so padded stripes
    never execute; the identity padding factors to identity exactly,
    making the [:s, :s] crop exact (the bucket.py validity-masking
    argument, enforced in-kernel). A stripe is worked inside the
    lane-aligned window that holds it (the only dynamic lane offset
    Mosaic takes): window lanes outside the stripe are masked out of
    every update and written back unchanged."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    ct = jnp.promote_types(stack.dtype, jnp.float32)
    # window width: whole lane tiles covering one stripe wherever it
    # starts (blk | 128 or 128 | blk never straddles; else one more)
    straddles = bool(_LANE % blk and blk % _LANE)
    W = _lane_up(blk) + _LANE * straddles
    Np = _lane_up(N) + _LANE * straddles
    if Np != N:
        stack = jnp.pad(stack, ((0, 0), (0, Np - N), (0, Np - N)))

    def kernel(s_ref, a_ref, o_ref):
        s = s_ref[pl.program_id(0)]
        z = jnp.int32(0)
        rows_c = jax.lax.broadcasted_iota(jnp.int32, (Np, 1), 0)
        cols_r = jax.lax.broadcasted_iota(jnp.int32, (1, Np), 1)
        live = (rows_c < s) & (cols_r < s)
        eye = (rows_c == cols_r).astype(a_ref.dtype)
        o_ref[:] = jnp.where(live, a_ref[:], eye)
        nlive = (s + blk - 1) // blk
        lane_r = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
        wrow_c = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)

        def stripe(kb, _):
            k0 = (kb * blk).astype(jnp.int32)
            w0 = pl.multiple_of((k0 // _LANE) * _LANE, _LANE)
            off = k0 - w0              # stripe = window lanes [off, end)
            end = off + blk
            S = o_ref[:, pl.ds(w0, W)]
            # left-looking update S -= L[:, :k0] @ L[k0:k1, :k0]^T via
            # whole-panel masks (the _chol_fused_pallas trick); the
            # window rows outside the stripe are zeroed, so the other
            # window lanes receive an exact zero
            colmask = (jax.lax.broadcasted_iota(jnp.int32, (Np, Np), 1)
                       < k0)
            Lm = jnp.where(colmask, o_ref[:], 0.0).astype(ct)
            G = o_ref[pl.ds(w0, W), :]
            gmask = ((jax.lax.broadcasted_iota(jnp.int32, (W, Np), 1)
                      < k0) & (wrow_c >= off) & (wrow_c < end))
            G = jnp.where(gmask, G, 0.0).astype(ct)
            S = S - jax.lax.dot_general(
                Lm, G, (((1,), (1,)), ((), ())),
                preferred_element_type=ct,
                precision=jax.lax.Precision.HIGHEST).astype(S.dtype)
            projT = (jax.lax.broadcasted_iota(jnp.int32, (Np, W), 0)
                     == jax.lax.broadcasted_iota(jnp.int32, (Np, W), 1)
                     + w0)

            def col(jj, S):
                j = k0 + jj
                lj = off + jj
                sel = lane_r == lj
                colv = jnp.sum(jnp.where(sel, S, 0.0), axis=1,
                               keepdims=True).astype(ct)     # (Np, 1)
                piv = jnp.sum(jnp.where(rows_c == j, colv, 0.0))
                d = jnp.sqrt(piv)
                dsafe = jnp.where(d == 0, 1.0, d)
                v = jnp.where(rows_c > j, colv / dsafe,
                              0.0).astype(S.dtype)
                newcol = v + jnp.where(rows_c == j, d,
                                       0.0).astype(S.dtype)
                S = jnp.where(sel, newcol, S)
                vrow = jnp.sum(jnp.where(projT, v, 0.0), axis=0,
                               keepdims=True)
                S = S - (v * jnp.where((lane_r > lj) & (lane_r < end),
                                       vrow, 0.0)).astype(S.dtype)
                return S

            S = jax.lax.fori_loop(z, jnp.int32(blk), col, S)
            o_ref[:, pl.ds(w0, W)] = S
            return 0

        jax.lax.fori_loop(z, nlive, stripe, 0)
        o_ref[:] = jnp.where(rows_c >= cols_r, o_ref[:], 0.0)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B,),
        in_specs=[pl.BlockSpec((None, Np, Np),
                               lambda i, *_: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, Np, Np), lambda i, *_: (i, 0, 0)))
    out = pl.pallas_call(
        kernel, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((B, Np, Np), stack.dtype),
        # the stack is read once at the top of each grid step, so it
        # may back the output buffer in place (index 1 = the operand
        # after the scalar-prefetch sizes)
        input_output_aliases={1: 0},
        compiler_params=_ragged_params(Np, stack.dtype.itemsize),
        interpret=interp)(sizes, stack)
    return out[:, :N, :N] if Np != N else out


@functools.lru_cache(maxsize=None)
def _ragged_potrf_fn(B: int, N: int, blk: int, interp: bool,
                     donate: bool):
    fn = functools.partial(_ragged_potrf_pallas, B=B, N=N, blk=blk,
                           interp=interp)
    return jax.jit(fn, donate_argnums=(1,) if donate else ())


def ragged_potrf(stack: jax.Array, sizes, blk: Optional[int] = None,
                 donate: bool = False):
    """Ragged batched lower Cholesky of a (B, N, N) stack with
    per-element true orders ``sizes`` (int32, scalar-prefetched).
    Element i's [:sizes[i], :sizes[i]] block is its exact factor; the
    pad region comes back as the identity's lower triangle.
    ``donate=True`` hands the stack's buffer to XLA on backends that
    implement donation (throwaway padded copies factor in place —
    the kernel aliases it onto the output). Returns None (reason
    published as an obs instant) when ineligible — the caller keeps
    the bucket strategy."""
    B, N = stack.shape[0], stack.shape[-1]
    b = ragged_blk(blk)
    if not ragged_potrf_eligible(N, stack.dtype, b):
        _reject("ragged_potrf", _ragged_reject_reason(N, stack.dtype, b)
                or "shape", n=N, dtype=str(stack.dtype))
        return None
    sizes = jnp.asarray(sizes, jnp.int32)
    fn = _ragged_potrf_fn(B, N, b, pallas_interpret(),
                          donate and _ragged_donate_ok())
    return fn(sizes, stack)


def _ragged_getrf_pallas(sizes: jax.Array, stack: jax.Array, B: int,
                         N: int, ib: int, interp: bool):
    """Ragged batched partial-pivot LU: per element, a blocked
    right-looking sweep with a DYNAMIC trip count ceil(s/ib); each
    step reuses the lu_panel_rec masked discipline verbatim — the
    ib-wide base case runs the sequential argmax/full-row-swap/rank-1
    recurrence with whole-panel masked selects, the U12 strip solves
    by ib masked substitution rows, and the trailing update is ONE
    masked rank-ib MXU matmul. The in-kernel identity padding keeps
    padded rows unpivotable (live columns hold exact zeros there) and
    padded columns pivot on their own unit diagonal, so the pivot
    vector is exactly the per-element lu_panel_fori sequence extended
    by identity swaps. Returns (packed L\\U (B, N, N), pivot swap
    targets (B, 1, N) f32 — exact for N < 2^24)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    ct = jnp.promote_types(stack.dtype, jnp.float32)
    N0, N = N, _lane_up(N)
    if N != N0:
        stack = jnp.pad(stack, ((0, 0), (0, N - N0), (0, N - N0)))

    def kernel(s_ref, a_ref, o_ref, piv_ref):
        s = s_ref[pl.program_id(0)]
        z = jnp.int32(0)
        rows_c = jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0)
        cols_r = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
        live = (rows_c < s) & (cols_r < s)
        eye = (rows_c == cols_r).astype(a_ref.dtype)
        o_ref[:] = jnp.where(live, a_ref[:], eye)
        # identity swap targets everywhere a base step never runs
        piv_ref[:] = cols_r.astype(jnp.float32)
        nlive = (s + ib - 1) // ib

        def base(k0):
            # factor columns [k0, k0+ib): the lu_panel_rec base case
            # (argmax pivot search, full-row swap, segment-confined
            # rank-1) with k0 a traced scalar in the masks
            def step(jj, _):
                j = k0 + jj
                colsel = cols_r == j
                col = jnp.sum(jnp.where(colsel, o_ref[:], 0.0),
                              axis=1, keepdims=True).astype(ct)
                mag = jnp.where(rows_c >= j, jnp.abs(col), -1.0)
                mx = jnp.max(mag)
                p = jnp.min(jnp.where(mag == mx, rows_c, N))
                piv_ref[:] = jnp.where(colsel, p.astype(jnp.float32),
                                       piv_ref[:])
                rowj = jnp.sum(jnp.where(rows_c == j, o_ref[:], 0.0),
                               axis=0, keepdims=True)
                rowp = jnp.sum(jnp.where(rows_c == p, o_ref[:], 0.0),
                               axis=0, keepdims=True)
                pan = o_ref[:]
                pan = jnp.where(rows_c == j, rowp,
                                jnp.where(rows_c == p, rowj, pan))
                pivval = jnp.sum(jnp.where(colsel, rowp,
                                           0.0)).astype(ct)
                safe = jnp.where(pivval == 0, 1.0, pivval)
                col2 = jnp.sum(jnp.where(colsel, pan, 0.0), axis=1,
                               keepdims=True)
                mults = jnp.where(rows_c > j,
                                  col2.astype(ct) / safe,
                                  0.0).astype(pan.dtype)
                urow = jnp.where((cols_r > j) & (cols_r < k0 + ib),
                                 rowp, 0.0)
                pan = pan - mults * urow
                newcol = jnp.where(rows_c > j, mults, col2)
                pan = jnp.where(colsel, newcol, pan)
                o_ref[:] = pan.astype(o_ref.dtype)
                return 0

            jax.lax.fori_loop(z, jnp.int32(ib), step, 0)

        def solve(k0, k1):
            # U12: rows [k0, k1) of cols [k1, N) := L11^{-1} @ (same),
            # ib masked substitution rows (lu_panel_rec's solve base)
            def srow(rr, _):
                r = k0 + rr
                rowr = jnp.sum(jnp.where(rows_c == r, o_ref[:], 0.0),
                               axis=0, keepdims=True)
                rowr = jnp.where(cols_r >= k1, rowr, 0.0)
                lcol = jnp.sum(jnp.where(cols_r == r, o_ref[:], 0.0),
                               axis=1, keepdims=True)
                lcol = jnp.where((rows_c > r) & (rows_c < k1),
                                 lcol, 0.0)
                o_ref[:] = (o_ref[:]
                            - (lcol * rowr).astype(o_ref.dtype))
                return 0

            jax.lax.fori_loop(z, jnp.int32(ib), srow, 0)

        def mm_update(k0, k1):
            # out[k1:, k1:] -= L[k1:, k0:k1] @ U[k0:k1, k1:] as ONE
            # masked rank-ib MXU matmul (lu_panel_rec's mm_update)
            L = jnp.where((rows_c >= k1) & (cols_r >= k0)
                          & (cols_r < k1), o_ref[:], 0.0).astype(ct)
            U = jnp.where((rows_c >= k0) & (rows_c < k1)
                          & (cols_r >= k1), o_ref[:], 0.0).astype(ct)
            P = jax.lax.dot_general(
                L, U, (((1,), (0,)), ((), ())),
                preferred_element_type=ct,
                precision=jax.lax.Precision.HIGHEST)
            o_ref[:] = (o_ref[:] - P.astype(o_ref.dtype))

        def block(kb, _):
            k0 = (kb * ib).astype(jnp.int32)
            k1 = k0 + ib
            base(k0)
            solve(k0, k1)
            mm_update(k0, k1)
            return 0

        jax.lax.fori_loop(z, nlive, block, 0)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B,),
        in_specs=[pl.BlockSpec((None, N, N), lambda i, *_: (i, 0, 0))],
        out_specs=(
            pl.BlockSpec((None, N, N), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((None, 1, N), lambda i, *_: (i, 0, 0))))
    lu, piv = pl.pallas_call(
        kernel, grid_spec=gs,
        out_shape=(jax.ShapeDtypeStruct((B, N, N), stack.dtype),
                   jax.ShapeDtypeStruct((B, 1, N), jnp.float32)),
        # the stack is read once per grid step; alias it onto the
        # packed-LU output (index 1 = after the scalar-prefetch sizes)
        input_output_aliases={1: 0},
        compiler_params=_ragged_params(N, stack.dtype.itemsize),
        interpret=interp)(sizes, stack)
    if N != N0:
        lu, piv = lu[:, :N0, :N0], piv[:, :, :N0]
    return lu, piv


@functools.lru_cache(maxsize=None)
def _ragged_getrf_fn(B: int, N: int, ib: int, interp: bool,
                     donate: bool):
    fn = functools.partial(_ragged_getrf_pallas, B=B, N=N, ib=ib,
                           interp=interp)
    return jax.jit(fn, donate_argnums=(1,) if donate else ())


def ragged_getrf(stack: jax.Array, sizes, blk: Optional[int] = None,
                 donate: bool = False):
    """Ragged batched partial-pivot LU of a (B, N, N) stack with
    per-element true orders ``sizes``. Returns (packed L\\U stack,
    LAPACK swap-target stack (B, N) int32 — identity past each
    element's extent), or None when ineligible (reason published; the
    caller keeps the bucket strategy). ``donate`` as ragged_potrf."""
    B, N = stack.shape[0], stack.shape[-1]
    b = ragged_blk(blk)
    if not ragged_getrf_eligible(N, stack.dtype, b):
        _reject("ragged_getrf", _ragged_reject_reason(N, stack.dtype, b)
                or "shape", n=N, dtype=str(stack.dtype))
        return None
    sizes = jnp.asarray(sizes, jnp.int32)
    fn = _ragged_getrf_fn(B, N, b, pallas_interpret(),
                          donate and _ragged_donate_ok())
    packed, piv = fn(sizes, stack)
    return packed, piv[:, 0, :].astype(jnp.int32)


def _ragged_trsm_pallas(sizes: jax.Array, packed: jax.Array,
                        rhs: jax.Array, B: int, N: int, K: int,
                        blk: int, upper: bool, trans: bool,
                        unit: bool, interp: bool):
    """Ragged batched triangular solve: per element, blocked
    substitution over ceil(s/blk) blocks (DYNAMIC trip count, in
    reverse for the effective-upper system), each block a sequential
    masked-row base case plus ONE masked rank-blk MXU update of the
    remaining rows. The triangular operand is re-masked to
    blkdiag(T[:s, :s], I) in-kernel and rhs rows past s are zeroed, so
    padded rows solve to exact zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    ct = jnp.promote_types(packed.dtype, jnp.float32)
    #: True => the EFFECTIVE system is upper-triangular (backward
    #: substitution): an upper operand, or a lower one applied
    #: transposed
    back = upper != trans
    N0, N = N, _lane_up(N)
    if N != N0:
        packed = jnp.pad(packed, ((0, 0), (0, N - N0), (0, N - N0)))
        rhs = jnp.pad(rhs, ((0, 0), (0, N - N0), (0, 0)))

    def kernel(s_ref, t_ref, b_ref, o_ref):
        s = s_ref[pl.program_id(0)]
        z = jnp.int32(0)
        rows_c = jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0)
        cols_r = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
        live2 = (rows_c < s) & (cols_r < s)
        eye = (rows_c == cols_r).astype(t_ref.dtype)
        t = jnp.where(live2, t_ref[:], eye)
        o_ref[:] = jnp.where(rows_c < s, b_ref[:], 0.0)
        nlive = (s + blk - 1) // blk

        def brow(r, k0, k1):
            # one substitution row: x[r] = (x[r] - T[r, solved] @ x)
            # / T[r, r], with T read transposed when trans (column r
            # of `packed` as the weight vector — no Mosaic transpose).
            # "solved" is confined to THIS block's already-processed
            # rows — cross-block contributions were subtracted by the
            # earlier blocks' rank-blk updates
            if back:
                cmask_r = (cols_r > r) & (cols_r < k1)
                cmask_c = (rows_c > r) & (rows_c < k1)
            else:
                cmask_r = (cols_r < r) & (cols_r >= k0)
                cmask_c = (rows_c < r) & (rows_c >= k0)
            if trans:
                w = jnp.sum(jnp.where(cols_r == r, t, 0.0), axis=1,
                            keepdims=True)                   # (N, 1)
                w = jnp.where(cmask_c, w, 0.0).astype(ct)
                prod = jnp.sum(w * o_ref[:].astype(ct), axis=0,
                               keepdims=True)                # (1, K)
            else:
                w = jnp.sum(jnp.where(rows_c == r, t, 0.0), axis=0,
                            keepdims=True)                   # (1, N)
                w = jnp.where(cmask_r, w, 0.0).astype(ct)
                prod = jax.lax.dot_general(
                    w, o_ref[:].astype(ct), (((1,), (0,)), ((), ())),
                    preferred_element_type=ct,
                    precision=jax.lax.Precision.HIGHEST)     # (1, K)
            if unit:
                d = jnp.ones((), ct)
            else:
                d = jnp.sum(jnp.where((rows_c == r) & (cols_r == r),
                                      t, 0.0)).astype(ct)
                d = jnp.where(d == 0, 1.0, d)
            xr = jnp.sum(jnp.where(rows_c == r, o_ref[:], 0.0),
                         axis=0, keepdims=True).astype(ct)
            new = ((xr - prod) / d).astype(o_ref.dtype)
            o_ref[:] = jnp.where(rows_c == r, new, o_ref[:])

        def block(kbi, _):
            kb = (nlive - 1 - kbi) if back else kbi
            k0 = (kb * blk).astype(jnp.int32)
            k1 = k0 + blk

            def bstep(rr, _):
                brow(k1 - 1 - rr if back else k0 + rr, k0, k1)
                return 0

            jax.lax.fori_loop(z, jnp.int32(blk), bstep, 0)
            # rank-blk MXU update of the not-yet-solved rows
            if back:
                tgt = rows_c < k0
                tgt_c = cols_r < k0
            else:
                tgt = rows_c >= k1
                tgt_c = cols_r >= k1
            X = jnp.where((rows_c >= k0) & (rows_c < k1), o_ref[:],
                          0.0).astype(ct)
            if trans:
                P = jnp.where((rows_c >= k0) & (rows_c < k1) & tgt_c,
                              t, 0.0).astype(ct)
                upd = jax.lax.dot_general(
                    P, X, (((0,), (0,)), ((), ())),
                    preferred_element_type=ct,
                    precision=jax.lax.Precision.HIGHEST)
            else:
                Tb = jnp.where(tgt & (cols_r >= k0) & (cols_r < k1),
                               t, 0.0).astype(ct)
                upd = jax.lax.dot_general(
                    Tb, X, (((1,), (0,)), ((), ())),
                    preferred_element_type=ct,
                    precision=jax.lax.Precision.HIGHEST)
            o_ref[:] = (o_ref[:] - upd.astype(o_ref.dtype))
            return 0

        jax.lax.fori_loop(z, nlive, block, 0)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B,),
        in_specs=[
            pl.BlockSpec((None, N, N), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((None, N, K), lambda i, *_: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, N, K), lambda i, *_: (i, 0, 0)))
    out = pl.pallas_call(
        kernel, grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((B, N, K), rhs.dtype),
        # the rhs is read once per grid step; alias it onto the
        # solution (index 2 = after the sizes and the factors, which
        # stay readable across the whole solve and are NOT aliased)
        input_output_aliases={2: 0},
        compiler_params=_ragged_params(N, packed.dtype.itemsize),
        interpret=interp)(sizes, packed, rhs)
    return out[:, :N0] if N != N0 else out


@functools.lru_cache(maxsize=None)
def _ragged_trsm_fn(B: int, N: int, K: int, blk: int, upper: bool,
                    trans: bool, unit: bool, interp: bool,
                    donate: bool):
    fn = functools.partial(_ragged_trsm_pallas, B=B, N=N, K=K,
                           blk=blk, upper=upper, trans=trans,
                           unit=unit, interp=interp)
    return jax.jit(fn, donate_argnums=(2,) if donate else ())


def ragged_trsm(packed: jax.Array, rhs: jax.Array, sizes,
                upper: bool = False, trans: bool = False,
                unit: bool = False, blk: Optional[int] = None,
                donate: bool = False):
    """Ragged batched triangular solve of (B, N, N) factors against a
    (B, N, K) right-hand-side stack with per-element true orders
    ``sizes``: the `upper`-designated triangle of each packed element
    (optionally `trans`posed, optionally `unit`-diagonal) solves its
    live (s, K) block; padded rows come back zero. ``donate=True``
    donates the RHS buffer (the factors are never donated — the
    posv/gesv compositions reuse them across both sweeps). Returns
    None when ineligible (reason published; the caller keeps the
    bucket strategy)."""
    if rhs is None:
        return None
    B, N = packed.shape[0], packed.shape[-1]
    K = rhs.shape[-1]
    b = ragged_blk(blk)
    if not ragged_trsm_eligible(N, K, packed.dtype, b):
        _reject("ragged_trsm", _ragged_reject_reason(N, packed.dtype, b)
                or "shape", n=N, k=K, dtype=str(packed.dtype))
        return None
    sizes = jnp.asarray(sizes, jnp.int32)
    fn = _ragged_trsm_fn(B, N, K, b, bool(upper), bool(trans),
                         bool(unit), pallas_interpret(),
                         donate and _ragged_donate_ok())
    return fn(sizes, packed, rhs)
