"""Algorithm-variant selection (reference include/slate/method.hh:27-319).

Each family exposes named variants plus an Auto heuristic mirroring the
reference's selection logic (method.hh cites inline).
"""

from __future__ import annotations

import enum


class MethodTrsm(enum.Enum):
    """Reference method.hh:27-60: trsmA broadcasts B to A's ranks (better
    for few RHS); trsmB broadcasts A (better for many RHS)."""
    Auto = "auto"
    A = "A"
    B = "B"

    @staticmethod
    def select(side_left: bool, a_n: int, b_m: int, b_n: int
               ) -> "MethodTrsm":
        # reference heuristic: many RHS relative to A's order -> trsmB.
        # RHS count is B's cols for Left, B's rows for Right.
        nrhs = b_n if side_left else b_m
        return MethodTrsm.B if nrhs >= a_n else MethodTrsm.A


class MethodGemm(enum.Enum):
    """Reference method.hh:79: small n (few C columns) -> gemmA.
    ``Summa`` selects the explicit shard_map SUMMA schedule
    (parallel/collectives.summa_gemm) instead of letting XLA's SPMD
    partitioner pick the communication — the hand-written counterpart
    of the reference's gemmC broadcast loop (gemmC.cc:84-117); requires
    Option.Grid."""
    Auto = "auto"
    A = "A"
    C = "C"
    Summa = "summa"

    @staticmethod
    def select(m: int, n: int, k: int) -> "MethodGemm":
        return MethodGemm.A if n <= 256 and k >= 4 * n else MethodGemm.C


class MethodHemm(enum.Enum):
    """Reference method.hh:132."""
    Auto = "auto"
    A = "A"
    C = "C"

    @staticmethod
    def select(m: int, n: int) -> "MethodHemm":
        return MethodHemm.A if n <= 256 else MethodHemm.C


class MethodCholQR(enum.Enum):
    """Reference method.hh:184: how to form A^H A."""
    Auto = "auto"
    GemmA = "gemmA"
    GemmC = "gemmC"
    HerkA = "herkA"
    HerkC = "herkC"

    @staticmethod
    def select(m: int, n: int) -> "MethodCholQR":
        return MethodCholQR.HerkC


#: Largest observed condition number at which gels' Auto keeps CholQR.
#: CholQR rests on the Gram matrix, where A's conditioning is squared:
#: on the shape alone it read 0.087 cond^2 eps against Householder QR's
#: 0.1-0.4 cond eps (f32, 2048 x 256, rotated geometric spectrum: 30
#: times worse at cond 1e2, NaN from 1e4, where A^H A is no longer
#: numerically positive definite). Its refinement step
#: (qr._cholqr_solve) wins QR grade back while cond^2 times the Gram
#: matrix's own rounding stays far under 1, and that rounding grows
#: with the rows summed: 1e-5 at 65536 rows on the TPU. 8 holds the
#: product under 1e-3 there; the estimate is a lower bound (power
#: iterations on the Gram factor), and a matrix twice as ill
#: conditioned as its estimate is still inside that.
GELS_CHOLQR_MAX_COND = 8.0


class MethodGels(enum.Enum):
    """Reference method.hh:237: QR (robust) vs CholQR (fast,
    well-conditioned tall-skinny). TSQR is the communication-avoiding
    tree QR (reference ttqrt role, linalg/ca.py) — as robust as QR,
    log-depth instead of column-sequential, best for very tall-skinny
    panels over a mesh."""
    Auto = "auto"
    QR = "qr"
    CholQR = "cholqr"
    TSQR = "tsqr"

    @staticmethod
    def tall(m: int, n: int) -> bool:
        """The shape class in which a Gram-based or tree route can
        beat the blocked Householder QR at all."""
        return m >= 3 * n

    @staticmethod
    def select(m: int, n: int, on_grid: bool = False,
               gram_cond: float = float("inf")) -> "MethodGels":
        """The shape says which routes are candidates; what the
        caller observed says whether CholQR is one. `gram_cond` is
        the condition number gels estimated from the Cholesky factor
        of A^H A (qr._gram_factor_cond), inf when that factor failed
        or nothing could be observed (under a jit trace): CholQR only
        at or under GELS_CHOLQR_MAX_COND, like the reference's
        "gels_cholqr for well-conditioned" made a rule. On a mesh the
        tall regime routes to the cross-device TSQR tree
        (dist/tsqr.py): one log-depth R combine of (n, n) blocks
        versus CholQR's gathered Gram + replicated Cholesky, with
        QR-grade robustness (the ttqrt rationale, geqrf.cc:161)."""
        if MethodGels.tall(m, n):
            if on_grid:
                return MethodGels.TSQR
            if gram_cond <= GELS_CHOLQR_MAX_COND:
                return MethodGels.CholQR
        return MethodGels.QR


class MethodLU(enum.Enum):
    """Reference method.hh:281: partial-pivot / communication-avoiding
    tournament / no-pivot (+RBT handled by gesv_rbt)."""
    Auto = "auto"
    PartialPiv = "PPLU"
    CALU = "CALU"
    NoPiv = "NoPiv"
    BEAM = "BEAM"

    @staticmethod
    def select() -> "MethodLU":
        return MethodLU.PartialPiv


#: tallest f32 operand XLA's TPU LuDecompositionBlock custom call can
#: take: it stages (m, 128) column blocks in VMEM, so tall operands
#: trip the 16 MB scoped-vmem limit at compile time ("Ran out of
#: memory in memory space vmem ... f32[32768,128]", v5e, 2026-07-31 —
#: also why the whole-matrix Fused getrf cannot compile at n=16384).
#: Measured f32 boundary: m=10240 compiles, m=11264 does not; 8192
#: kept as the safe margin, scaled by itemsize for wider dtypes
#: (native_lu_ok). CPU has no such limit.
NATIVE_LU_MAX_M = 8192


def vmem_height_cap(base_m: int, dtype) -> int:
    """Itemsize-proportional VMEM height/element cap for kernels whose
    scalar recurrences run in f32 regardless of the panel dtype: a
    narrower panel dtype buys vmem only on the panel itself, not the
    f32 temporaries, so sub-f32 dtypes SHRINK the cap (bf16 halves it
    — measured on v5e: bf16 8192x256 dies in compile at 20.24M of
    scoped-vmem stack vs the 16M limit while f32 4096x256 and bf16
    4096x256 both run, PERF.md round-3 sweep). Wider dtypes clamp at
    the f32 cap. The one height-cap rule every Pallas panel gate
    shares (ops/pallas_kernels.py)."""
    import numpy as _np
    return base_m * min(_np.dtype(dtype).itemsize, 4) // 4


class MethodFactor(enum.Enum):
    """Execution path for the dense factorizations (potrf/getrf/geqrf).

    This is the TPU-native analogue of the reference's Target dispatch
    (potrf.cc:262-277 switching HostTask/Devices): ``Fused`` hands the
    whole factorization to XLA's native blocked kernel — one fused
    device program; ``Tiled`` runs the framework's blocked tile
    algorithm, whose block steps carry sharding constraints so SPMD
    distributes them over a mesh — required for multi-device execution,
    and (since round 3) also the fastest single-device LU: the
    carry-the-trailing-matrix form beats XLA's native LU 1.9x at
    n=8192 on v5e (PERF.md, regenerated by bench.py). Which variant
    ``Auto`` resolves to is per-routine, measured policy: potrf keeps
    Fused (native cholesky won every composition experiment), getrf
    prefers Tiled single-device, geqrf picks by size — see each
    driver."""
    Auto = "auto"
    Fused = "fused"
    Tiled = "tiled"

    @staticmethod
    def native_lu_dtype_ok(dtype) -> bool:
        """XLA's LuDecomposition custom call only implements f32/c64
        (+f64/c128 on CPU); bf16 factors (the mixed-precision lo path
        on TPU) must take the Tiled blocked LU. Cholesky is NOT
        restricted — its TPU lowering is an expander that handles bf16
        (verified on v5e)."""
        import numpy as _np
        return _np.dtype(dtype).name in ("float32", "float64",
                                         "complex64", "complex128")

    @staticmethod
    def native_lu_ok(dtype, m: int) -> bool:
        """dtype support AND the TPU scoped-vmem height limit (see
        NATIVE_LU_MAX_M). On CPU (tests, x64) only the dtype gate
        applies."""
        if not MethodFactor.native_lu_dtype_ok(dtype):
            return False
        import jax
        if jax.default_backend() == "cpu":
            return True
        import numpy as _np
        return m * _np.dtype(dtype).itemsize <= NATIVE_LU_MAX_M * 4

    @staticmethod
    def select(data, dtype_ok: bool = True) -> "MethodFactor":
        """Auto resolution: Tiled iff `data` is a concrete array sharded
        over >1 device, or the driver reports its native kernel cannot
        handle the dtype (`dtype_ok=False` — getrf passes
        native_lu_dtype_ok). Traced (in-jit) arrays resolve to Fused —
        distributed callers inside jit pass MethodFactor.Tiled
        explicitly (as the in-repo mesh tests and dryrun do)."""
        if not dtype_ok:
            return MethodFactor.Tiled
        try:
            s = data.sharding          # tracers raise / lack this
            if len(s.device_set) > 1 and not s.is_fully_replicated:
                return MethodFactor.Tiled
        except Exception:
            pass
        return MethodFactor.Fused


class MethodLUPanel(enum.Enum):
    """Execution route for ONE LU panel factorization (lu._lu_panel) —
    the per-panel arbitration under every LU consumer (getrf carry /
    pipelined / scan, getrf_tntpiv chunk nomination, band windows,
    indefinite Aasen panels, ooc._lu_panel_factor, batch drivers):

      * ``Native``: XLA's LuDecompositionBlock custom call — fastest
        where its dtype support and scoped-vmem height limit allow
        (NATIVE_LU_MAX_M);
      * ``PallasRec``: the block-recursive Pallas panel
        (ops/pallas_kernels.lu_panel_rec) — rank-ib MXU updates
        outside an ib-wide base case, row-block-gridded above the
        one-dispatch height, the only exact-pivoting panel at heights
        the native call cannot compile;
      * ``Pallas``: the round-3 rank-1 fused kernel (bf16 fallback /
        bench comparison point);
      * ``Blocked``: the left-looking blocked kernel
        (lu.lu_panel_blocked) — a column step rewrites only its own
        (ib + 1, m) block; the route of panels the native call takes
        the dtype of but not the height (PR 48: 27 us a column at
        32768 rows where the fori kernel takes 55, PERF.md). Its
        column recurrence runs out of VMEM as one Pallas kernel a
        block where the platform and the shape allow
        (ops/pallas_kernels.lu_block_columns, PR 50), as an XLA loop
        elsewhere: the shape decides, no member or option of its own;
      * ``Fori``: the masked fori_loop kernel — pure XLA, always
        correct, vmappable (the batch layer's route), and what is
        left: a width no base block divides, m < w, other dtypes.

    ``Auto`` resolves via the tune cache (a MEASURED
    ``method_lu_panel`` entry per (op, size, dtype) bucket), falling
    back to ``cold_default``, which reads the route from the panel's
    height, width and dtype alone."""
    Auto = "auto"
    Native = "native"
    Blocked = "blocked"
    Fori = "fori"
    Pallas = "pallas"
    PallasRec = "pallas_rec"

    @staticmethod
    def blocked_ok(m: int, w: int, dtype) -> bool:
        """The hard gates of ``Blocked``: a dtype the native LU takes
        (the kernel's row positions ride in a row of the panel's own
        dtype, which bf16 cannot hold), at least as many rows as
        columns, and a base block that divides the width."""
        from ..linalg.lu import _blocked_ib
        return MethodFactor.native_lu_dtype_ok(dtype) and m >= w \
            and _blocked_ib(w) > 0

    @staticmethod
    def cold_default(m: int, w: int, dtype) -> "MethodLUPanel":
        """The routing chain of a cold cache: native custom call where
        dtype + height allow, the fused rank-1 Pallas kernel where the
        native cannot take the dtype (TPU bf16), the blocked kernel
        where it cannot take the height, else the fori kernel. Pinned
        by test_pallas_rec.py's cold-route tests."""
        if MethodFactor.native_lu_ok(dtype, m):
            return MethodLUPanel.Native
        from ..ops import pallas_kernels as pk
        if pk.lu_panel_eligible(m, w, dtype):
            return MethodLUPanel.Pallas
        if MethodLUPanel.blocked_ok(m, w, dtype):
            return MethodLUPanel.Blocked
        return MethodLUPanel.Fori

    @staticmethod
    def resolve(m: int, w: int, dtype) -> "MethodLUPanel":
        """Measured cache entry (validated against the hard gates),
        else cold_default."""
        from ..tune.select import tuned_method
        cached = tuned_method("lu_panel", "lu_panel", n=m, dtype=dtype)
        if cached is MethodLUPanel.Native \
                and not MethodFactor.native_lu_ok(dtype, m):
            cached = None     # a cached Native must not bypass the
            #                   dtype/height safety gates (size
            #                   buckets span shapes the probe never
            #                   ran — the getrf Fused revalidation
            #                   rule)
        if cached is MethodLUPanel.Blocked \
                and not MethodLUPanel.blocked_ok(m, w, dtype):
            cached = None     # likewise: a bucket spans widths no
            #                   base block divides
        if cached is not None and cached is not MethodLUPanel.Auto:
            return cached
        return MethodLUPanel.cold_default(m, w, dtype)


class MethodOOC(enum.Enum):
    """Execution route for the out-of-core streaming drivers when a
    grid is supplied (ISSUE 7):

      * ``Stream``: the single-device host<->HBM stream
        (linalg/ooc.py through linalg/stream.py) — panels staged and
        factored on this process's device only;
      * ``Sharded``: the 2D-block-cyclic sharded stream
        (dist/shard_ooc.py) — panels owned cyclically by mesh
        positions, each host's StreamEngine staging only its shard,
        factor panels broadcast over the dist/tree.py ppermute tree.

    ``Auto`` resolves through the tune cache (the ``ooc/shard_method``
    tunable; FROZEN default "stream"), so a COLD CACHE ROUTES
    BIT-IDENTICALLY to the single-device stream path even when a grid
    is passed — sharding is an earned (measured) or explicit decision,
    pinned by tests. A measured "sharded" entry is still gated on the
    problem having at least ``ooc/shard_min_panels`` panels per mesh
    rank (below that the cyclic walk cannot balance and the broadcast
    tree is pure overhead).

    The sharded drivers' broadcast-pipeline depth (ISSUE 11) rides the
    companion ``ooc/shard_lookahead`` tunable resolved by
    :meth:`lookahead` — FROZEN 0 is the step-synchronous schedule
    (bit-identical to the pre-lookahead drivers), depth >= 1 overlaps
    each step's trailing updates with the NEXT panel's factor
    broadcast (an earned/explicit decision like every reordering
    here; depth changes only WHEN identical jitted kernels run, never
    their operands, so every depth is bitwise-pinned against 0)."""
    Auto = "auto"
    Stream = "stream"
    Sharded = "sharded"

    @staticmethod
    def resolve(n: int, nt: int, nranks: int, dtype) -> "MethodOOC":
        """Auto resolution: the tuned/frozen ``ooc/shard_method``
        route, demoted to Stream when the panel count cannot give
        every rank its ``ooc/shard_min_panels`` share."""
        from ..tune.select import resolve as _resolve
        try:
            m = str2method("ooc", str(_resolve(
                "ooc", "shard_method", n=n, dtype=dtype)))
        except KeyError:
            m = MethodOOC.Stream   # newer cache vs older tree: the
            #                        frozen route, never an error
        if m is MethodOOC.Sharded:
            minp = int(_resolve("ooc", "shard_min_panels", n=n,
                                dtype=dtype))
            if nt < minp * max(int(nranks), 1):
                return MethodOOC.Stream
        return MethodOOC.Stream if m is MethodOOC.Auto else m

    @staticmethod
    def lookahead(n: int, dtype) -> int:
        """The sharded drivers' broadcast-pipeline depth: the tuned /
        frozen ``ooc/shard_lookahead`` row, clamped non-negative
        (class doc; a non-integer entry from a newer cache demotes to
        the frozen synchronous 0, never an error)."""
        from ..tune.select import resolve as _resolve
        try:
            return max(int(_resolve("ooc", "shard_lookahead", n=n,
                                    dtype=dtype)), 0)
        except (TypeError, ValueError):
            return 0


class MethodPrecision(enum.Enum):
    """Arithmetic-precision mode of the out-of-core streams
    (ISSUE 12):

      * ``Full``: every staged byte and every update runs in the
        input dtype — the PR 11 schedule bit-identically;
      * ``Mixed``: panels still FACTOR in the input dtype (the
        critical path keeps full precision), but trailing-matrix
        updates run in the lo pair dtype (refine.lo_dtype — bf16 for
        f32 input, the TPU MXU's native halved-byte contraction) and
        the PanelCache holds lo residents (demote on ``put``, promote
        on gather), so cache budget, H2D/D2H staging, and the sharded
        layer's broadcast payloads all pay half the bytes. Solves
        finish with iterative refinement (refine.host_ir) whose
        residual sentinel drives the ``mixed_to_full`` escalation
        through the resil guard funnel.

    ``Auto`` resolves through the tune cache (the ``ooc/precision``
    tunable; FROZEN default "f32"), so a COLD CACHE keeps the
    full-precision stream bit-identically — bf16 is an earned
    (measured, ``bench.py --ooc``/``--shard`` precision legs) or
    explicit decision, pinned by tests."""
    Auto = "auto"
    Full = "f32"
    Mixed = "bf16"

    @staticmethod
    def resolve(n: int, dtype) -> "MethodPrecision":
        """The tuned/frozen ``ooc/precision`` route (unknown values
        from a newer cache demote to the frozen Full, never an
        error)."""
        from ..tune.select import resolve as _resolve
        try:
            m = str2method("precision", str(_resolve(
                "ooc", "precision", n=n, dtype=dtype)))
        except KeyError:
            m = MethodPrecision.Full
        return MethodPrecision.Full if m is MethodPrecision.Auto \
            else m


class MethodBatchStrategy(enum.Enum):
    """Stacking strategy of the batched execution layer's coalescing
    queue (ISSUE 15):

      * ``Bucket``: the PR 5 pow2 shape ladder — every request rounds
        up a geometric bucket ladder with validity-masked padding, one
        vmapped dispatch per (op, bucket, nrhs, dtype). Bounded jit
        cache, but a lognormal size stream pays 30-60% of its cubic
        flops to padding (obs ``batch.padding_waste_flops``);
      * ``Ragged``: one dispatch over a RAGGED batch — requests stack
        to the max live size rounded to lane alignment (no pow2
        rounding; the coalescing key drops the bucket dimension, so
        previously-separate buckets merge into one dispatch) and the
        masked ragged Pallas kernels
        (ops/pallas_kernels.ragged_potrf/getrf/trsm) bound every
        element's work to its true extent via a per-element sizes
        vector. Fewer dispatches AND less padding — the Ragged Paged
        Attention play applied to dense factorizations.

    ``Auto`` resolves through the tune cache (the ``batch/strategy``
    tunable; FROZEN default "bucket"), so a COLD CACHE keeps the PR 5
    bucket routing bit-identically — ragged is an earned (bench
    ``--serve`` ragged leg on hardware) or explicit decision, pinned
    by tests."""
    Auto = "auto"
    Bucket = "bucket"
    Ragged = "ragged"

    @staticmethod
    def resolve(dtype=None) -> "MethodBatchStrategy":
        """The tuned/frozen ``batch/strategy`` route (unknown values
        from a newer cache demote to the frozen Bucket, never an
        error)."""
        from ..tune.select import resolve as _resolve
        try:
            m = str2method("batch", str(_resolve(
                "batch", "strategy", dtype=dtype)))
        except KeyError:
            m = MethodBatchStrategy.Bucket
        return MethodBatchStrategy.Bucket \
            if m is MethodBatchStrategy.Auto else m


class MethodLUPivot(enum.Enum):
    """Pivot discipline of the out-of-core LU stream (ISSUE 10):

      * ``Partial``: partial pivoting confined to the resident panel
        (the PR 4 ``getrf_ooc`` discipline) — since PR 47 the row
        swaps are applied on the chip at the time of use and a
        written panel is never rewritten (no cache entry retired),
        but its rows are final only after one repair at the end,
        which bars the checkpoint and the sharded layer;
      * ``Tournament``: CALU-style tournament pivoting
        (ca.tournament_pivot_rows) — the pivot permutation is
        finalized BEFORE the panel's factor column is written, factor
        panels are stored in ORIGINAL row order and never rewritten
        (zero revisit invalidations; the MRU residency cache finally
        works for LU), and the sharded 2D-block-cyclic stream
        (dist/shard_ooc.shard_getrf_ooc) becomes possible. Pivot
        growth is bounded like CALU's (2^(nb*depth) worst case vs
        partial's 2^(n-1); benign in practice) — the documented CALU
        trade.

    ``Auto`` resolves through the tune cache (the ``ooc/lu_pivot``
    tunable; FROZEN default "partial"), so with no tune entry a call
    that names no discipline takes the partial stream (pinned by
    tests). Both were read on a TPU v5e at n=32768 in panels of 4096
    on a matrix whose every panel swaps rows past itself: Partial
    5.4-5.5 s a warm ``gesv_ooc`` (PERF.md, PR 47; 15.1-15.2 s
    while the host moved the rows), Tournament 11.9-12.4 s (PR 46),
    once its chunk nomination compiled there
    (ca._chunk_pivot_rows). The default is ``Partial``: the
    benchmark cell ``stream-gesv`` runs whatever this resolves to,
    and its limits hold for either."""
    Auto = "auto"
    Partial = "partial"
    Tournament = "tournament"

    @staticmethod
    def resolve(n: int, dtype) -> "MethodLUPivot":
        """The tuned/frozen ``ooc/lu_pivot`` route (never an error on
        a newer cache vs an older tree — unknown values demote to the
        frozen Partial)."""
        from ..tune.select import resolve as _resolve
        try:
            m = str2method("lu_pivot", str(_resolve(
                "ooc", "lu_pivot", n=n, dtype=dtype)))
        except KeyError:
            m = MethodLUPivot.Partial
        return MethodLUPivot.Partial if m is MethodLUPivot.Auto else m


class MethodOwnership(enum.Enum):
    """Panel-ownership policy of the sharded OOC stream (ISSUE 19):

      * ``Static``: the pure 2D-block-cyclic ``CyclicSchedule``
        assignment — ownership is arithmetic on the panel index,
        fixed for the life of the stream;
      * ``Elastic``: throughput-driven re-ownership
        (dist/elastic.py) — per-host effective speeds (EWMA over
        phase-split-corrected ledger step walls) drive an
        epoch-boundary re-map of not-yet-factored panels away from
        stragglers, rebuilding the remaining subgraph under the new
        map. With uniform throughput the planner never fires, so the
        route stays bitwise vs Static.

    ``Auto`` resolves through the tune cache (the ``mesh/ownership``
    tunable; FROZEN default "static"), so a COLD CACHE keeps the
    static cyclic map bit-identically — elastic is an earned
    (measured, ``bench.py --elastic``) or explicit decision, pinned
    by tests."""
    Auto = "auto"
    Static = "static"
    Elastic = "elastic"

    @staticmethod
    def resolve(n: int, dtype) -> "MethodOwnership":
        """The tuned/frozen ``mesh/ownership`` route (unknown values
        from a newer cache demote to the frozen Static, never an
        error)."""
        from ..tune.select import resolve as _resolve
        try:
            m = str2method("ownership", str(_resolve(
                "mesh", "ownership", n=n, dtype=dtype)))
        except KeyError:
            m = MethodOwnership.Static
        return MethodOwnership.Static if m is MethodOwnership.Auto \
            else m


class MethodEig(enum.Enum):
    """Eigensolver backend: QR iteration vs divide & conquer."""
    Auto = "auto"
    QRIteration = "qr_iteration"
    DC = "dc"

    @staticmethod
    def select(n: int, want_vectors: bool) -> "MethodEig":
        return MethodEig.DC if want_vectors else MethodEig.QRIteration


class MethodSVD(enum.Enum):
    Auto = "auto"
    QRIteration = "qr_iteration"
    DC = "dc"


def str2method(family: str, s: str):
    fam = {
        "trsm": MethodTrsm, "gemm": MethodGemm, "hemm": MethodHemm,
        "cholqr": MethodCholQR, "gels": MethodGels, "lu": MethodLU,
        "factor": MethodFactor, "eig": MethodEig, "svd": MethodSVD,
        "lu_panel": MethodLUPanel, "ooc": MethodOOC,
        "lu_pivot": MethodLUPivot, "precision": MethodPrecision,
        "batch": MethodBatchStrategy, "ownership": MethodOwnership,
    }[family]
    for mem in fam:
        if mem.value.lower() == s.lower() or mem.name.lower() == s.lower():
            return mem
    raise KeyError(f"unknown {family} method {s!r}")
