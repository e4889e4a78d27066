"""OOC streaming engine v2 (shared by every linalg/ooc.py driver):
HBM panel-residency cache + double-buffered async transfer pipeline.

The beyond-HBM schedule (PERF.md Round-4c, n=65536 verified for all
three factorization families) was fully synchronous and residency-
blind: every column panel re-uploaded *every* earlier factor panel
(O(nt^2/2) panel uploads — 46 GB of H2D revisits against a 16 GB part
that could have held ~5 of the 8 panels), and H2D, compute, and D2H
strictly serialized on the Python thread. The reference manages tile
residency explicitly (MOSI per-tile copies on host + N devices,
BaseMatrix.hh) and overlaps the panel with the trailing update via
lookahead; BLASX (arXiv:1510.05041) shows the same two moves — an LRU
tile cache plus async transfer pipelines — recovering near-peak BLAS-3
over PCIe. This module is those two moves for the host<->HBM stream:

* ``PanelCache`` — an HBM-budget-aware device-resident cache of
  visiting panels. Entries are keyed by ``(buffer, epoch, panel
  index)``; ``invalidate(buf)`` bumps the buffer's epoch so
  getrf_ooc's host-side row-swap fixups retire already-cached L
  panels instead of serving stale rows (wrong-answer guard, pinned by
  tests). The two working panels (current visit + prefetched next)
  are pinned against eviction. Eviction policy is tunable
  (``ooc/cache_policy``): the shipped default is **mru** — a
  left-looking stream revisits panels 0..k-1 cyclically, the access
  pattern on which LRU famously degenerates to zero hits once the
  working set exceeds the budget (each panel is evicted right before
  its reuse), while evict-most-recent keeps a stable resident prefix
  and approximates Belady for cyclic scans. ``lru`` and ``fifo`` are
  selectable for measurement.
* ``StreamEngine`` — double-buffered async H2D prefetch (panel j+1's
  staging copy + ``device_put`` run on a transfer thread while the
  visit kernel for panel j executes; ``jax.device_put`` itself is
  async, so the worker only serializes the host-side staging memcpy)
  and a background D2H writer (panel k's writeback into the host
  factor overlaps panel k+1's visit stream — SLATE's lookahead mapped
  onto host<->HBM transfers). The writer fetches a panel in row
  chunks small enough that the arrays the runtime allocates for them
  come from heap pages the allocator has touched and keeps
  (``FETCH_CHUNK_BYTES``): in eighths of a panel each arrived in a
  fresh mapping, 2 GB of first touches a solve at n=32768 (PERF.md,
  PRs 36 and 37). Writeback futures are keyed like cache entries, so
  a later cache MISS that must re-read a panel from host memory first
  waits for that panel's writeback — never for the whole queue. At
  most ``WRITES_IN_FLIGHT`` writebacks are unfinished at
  once: queuing one more waits for the oldest, which is the engine's
  only backpressure from the device on the driver's main thread.
* ``StreamEngine.stash`` — the multi-shard extension (ISSUE 7): a
  DIRTY working panel (a trailing-update state the host copy does not
  yet reflect, as in the sharded right-looking schedules of
  dist/shard_ooc.py) held device-resident under the same budget.
  Unlike ``put`` entries (clean — the host has the truth and eviction
  just drops the reference), a stashed panel must SPILL on eviction:
  the cache's ``on_evict`` hook hands the victim back to the engine,
  which writes it to the caller-registered host view through the
  normal D2H writer; a later ``fetch`` of that key first waits that
  spill (the existing per-key writeback fence) and re-stages from the
  host view. Budget 0 degenerates to write-through — every stash is
  an immediate spill — which is exactly the uncached schedule.

Mixed-precision residency (ISSUE 12): under the ``ooc/precision``
bf16 mode the drivers demote factor panels to the lo dtype at every
staging boundary (``demote_host`` in the revisit loaders, so uploads
ship half the bytes; ``demote_dev`` before ``put``, so residents
charge half the budget — ~2x the panels fit at equal
``cache_budget_mb``) and promote back (``promote_dev``) only where
full precision re-enters (the sharded layer's host mirrors). Both
directions are counted (``ooc.cast_demote_bytes`` /
``ooc.cast_promote_bytes``) so bench can attribute exactly how much
of the H2D saving the casts give back. The engine itself stays
dtype-agnostic — ``resident_dtype`` declares the expectation for
budget math and stats, and the f32 mode passes None, leaving this
module's behavior bit-identical.

Host staging (PR 26): a panel that has to be made contiguous before
the runtime can take it (every column slice of the C-ordered operand
or factor) is packed into one of a small, process-wide ring of host
buffers (the class is core/staging.py's ``StageRing``, which the
mesh's placement uses too) that are reused from panel to panel,
engine to engine and call to call, never into a fresh array: on the
v5e host the first touch of freshly mapped pages, not the strided
read, was 94% of a streamed solve's staging time (PERF.md, PR 26).
The ring holds at
most ``prefetch_depth + 2`` slots of the deepest engine seen (3 at
the frozen depth of 1), each as large as the largest panel staged
through it and kept for the life of the process: 1.6 GB of host
memory for 537 MB panels, beside an 8.6 GB operand and factor. A slot
is recycled only when the transfer that read it is over (the span
``ooc::wait_ring`` is the wait), and on a backend whose device arrays
may alias host memory (the CPU) the put is made to copy.

Budget contract: ``cache_budget_bytes=0`` disables the cache entirely
and every fetch takes the exact upload path the pre-engine drivers
used — bit-identical to the uncached schedule (pinned by tests). The
frozen tunable default IS 0 (tune/cache.py), so cold start reproduces
today's behavior; real runs set a budget explicitly, via the tuning
cache, or with ``"auto"`` (device memory minus a working-set reserve
of ``RESERVE_PANELS`` full panels).

Observability: cache hits/misses/evictions/invalidations and
served/uploaded bytes are published as ``ooc.cache.*`` counters, and
prefetch/writeback overlap as ``ooc.prefetch.*``/``ooc.d2h.*``
counters plus per-transfer spans on the event bus (the worker-thread
spans are what make the overlap visible on the Perfetto timeline next
to the main-thread visit kernels). ``bench.py --ooc`` ships
``last_stats()`` into the BENCH extras.
"""

from __future__ import annotations

import collections
import concurrent.futures as cf
import functools
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from ..core.staging import (StageRing as _StageRing,
                            aliases_host as _aliases_host)
from ..core.tiles import ceil_div
from ..obs import events as obs_events
from ..obs import ledger as _ledger
from ..obs import metrics as obs_metrics
from ..resil import faults as _faults
from ..resil import guard as _guard

#: working-set reserve of the "auto" budget: two resident (m, w)
#: panels (S + visiting), one prefetched, one in writeback flight
RESERVE_PANELS = 4

#: headroom factor on the device's reported bytes_limit — the XLA
#: allocator needs slack for kernel temps beyond the working panels
AUTO_BUDGET_FRACTION = 0.9

#: writebacks that may be unfinished at once: the one the writer is
#: copying out and one queued behind it. Each holds its device panel,
#: and a driver's main thread waits for nothing else on the device, so
#: this is also what keeps the host from dispatching a whole
#: factorization ahead of the chip: with staging at memory speed
#: (PR 26) potrf_ooc at n=32768 had six of eight steps' temporaries
#: allocated at once, 13.6 GB of a 16 GB chip, where the slow pack had
#: held it to 6.5 GB
WRITES_IN_FLIGHT = 2

#: most bytes `_d2h` fetches as one numpy array. glibc hands out a
#: request over its mmap threshold as a fresh mapping and unmaps it
#: when freed; the threshold follows the largest such block freed, up
#: to 32 MiB and no further, so a chunk under that is served from heap
#: pages the allocator has touched and keeps, and one over it faults
#: every page in again (d2h_probe.py on the v5e host, PR 37: a
#: 470 MB panel in chunks of 64 and 32 MiB at 2.7 and 2.9 GB/s, of 24
#: and 16 MiB at 11.2 and 11.1, of 8 and 4 MiB at 8.9 and 5.5). Half
#: the ceiling: a block's header and an aligned request stay under it
FETCH_CHUNK_BYTES = 16 << 20

#: most recent finished engine's stats (bench.py --ooc extras); a
#: plain module slot, last-writer-wins — the bench runs one driver at
#: a time
_last_stats: Dict[str, Any] = {}


#: one ring for the process: posv_ooc builds two engines a solve, and
#: a ring per engine would first-touch its slots again each time
_ring = _StageRing("ooc")


def _h2d(x: np.ndarray) -> jax.Array:
    """Host-to-device copy via a contiguous staging buffer: jax's
    transfer of a non-contiguous numpy view (any column slice of a
    C-ordered matrix) marshals element-wise; one host-side memcpy buys
    the contiguous path. What the memcpy writes INTO decides its cost:
    on the v5e host a fresh ``np.ascontiguousarray`` of a 537 MB panel
    runs at 0.9 GB/s (every page of a new mapping faults in), the same
    copy into a reused, already touched buffer at 11 GB/s
    (benchmarks/tools/pack_probe.py, PERF.md PR 26). So a source that
    needs the copy is packed into a slot of the process-wide ring; a
    source that is already contiguous (the ``astype`` results of
    ``demote_host``, a right-hand side, a ``np.take`` result) passes
    through untouched. The hand-over does not wait for the transfer
    (0.5 ms for a panel the link then moves in 55 ms): the ring does,
    before it recycles the slot."""
    import jax.numpy as jnp
    on, nbytes = obs_events.enabled(), int(x.nbytes)
    if on:
        obs_metrics.inc("ooc.h2d_bytes", nbytes)
    with obs_events.span("ooc::h2d", cat="staging", bytes=nbytes):
        # the host-side copy against the hand-over to the runtime; the
        # transfer is not waited for (observing must not change the
        # program): `ooc::h2d_ready` below stays open on the obs-ready
        # thread until it is over
        if x.flags.c_contiguous:
            reused = None           # taken as it lies: in neither counter
            with obs_events.span("ooc::h2d_pack", cat="staging"):
                packed = np.ascontiguousarray(x)
            with obs_events.span("ooc::h2d_put", cat="staging"):
                arr = jnp.asarray(packed)
        else:
            slot, reused = _ring.acquire(nbytes)
            arr = None
            try:
                with obs_events.span("ooc::h2d_pack", cat="staging"):
                    packed = slot.buf[:nbytes].view(x.dtype) \
                        .reshape(x.shape)
                    np.copyto(packed, x)
                with obs_events.span("ooc::h2d_put", cat="staging"):
                    arr = jnp.array(packed) if _aliases_host() \
                        else jnp.asarray(packed)
            finally:
                _ring.release(slot, arr)
    if on:
        obs_events.watch_ready("ooc::h2d_ready", arr, bytes=nbytes)
        if reused:
            obs_metrics.inc("ooc.h2d_stage_reuse_bytes", nbytes)
        elif reused is not None:
            obs_metrics.inc("ooc.h2d_stage_fresh_bytes", nbytes)
    return arr


def _chunk_rows(m: int, nbytes: int, threads: int) -> int:
    """Rows `_d2h` fetches at a time of an (m, ...) block of `nbytes`:
    an eighth of it (one a thread), or ``FETCH_CHUNK_BYTES`` worth
    where an eighth is more."""
    return max(min(ceil_div(m, threads),
                   FETCH_CHUNK_BYTES * m // max(nbytes, 1)), 1)


def _d2h(x: jax.Array, out: Optional[np.ndarray] = None,
         threads: int = 8) -> np.ndarray:
    """Device-to-host copy of a big block, chunked over rows and
    issued from a thread pool, eight chunk reads at a time.

    ``out`` — a caller-provided preallocated slice (any writable
    ndarray view of x's shape) that chunks are written into directly,
    dropping the full extra host copy a concatenate would cost per
    panel writeback. Without it a fresh writable array is returned.

    Where a chunk's bytes land on the way decides the cost. Each
    ``np.asarray`` arrives in an array the runtime allocates and lets
    go again, and an allocation over the allocator's mmap threshold is
    a mapping of its own, every page of it touched for the first time
    at 0.65 s a GB on the v5e host: fetched in eighths, a 470 MB panel
    came over at 2.7 GB/s where the link does 10, 2 GB of first
    touches a solve at n=32768; in chunks of ``FETCH_CHUNK_BYTES``,
    which come from heap pages the allocator has touched and keeps,
    at 11 (benchmarks/tools/d2h_probe.py; PERF.md, PR 37). The slice
    is dropped as soon as it is copied: the runtime keeps the host
    copy of an array it has fetched for as long as the array lives."""
    m = x.shape[0]
    nbytes = _nbytes(x)
    if obs_events.enabled():
        obs_metrics.inc("ooc.d2h_bytes", nbytes)
    if out is None:
        out = np.empty(x.shape, np.dtype(x.dtype))
    # `ooc::d2h` counts what the process's resident set grows by under
    # it: `out` where nothing has written it yet, and whatever the
    # fetched chunks' own arrays map afresh and still hold when it
    # closes. Around the chunk threads and not on each, because the
    # count is the whole process's, and the writer runs these one at a
    # time
    if m < 2048:
        with obs_events.span("ooc::d2h", cat="staging",
                             resident="ooc.d2h_touched_bytes"):
            out[...] = np.asarray(x)
        return out
    step = _chunk_rows(m, nbytes, threads)

    def fetch(i):
        # per-chunk staging span: these run on POOL THREADS — the
        # shared bus (obs/events.py) is what makes them visible at
        # finish/export time (the old thread-local trace lost them)
        with obs_events.span("ooc::d2h_chunk", cat="staging"):
            out[i:i + step] = np.asarray(x[i:i + step])

    with obs_events.span("ooc::d2h", cat="staging",
                         resident="ooc.d2h_touched_bytes"):
        with cf.ThreadPoolExecutor(threads) as ex:
            list(ex.map(fetch, range(0, m, step)))
    return out


@functools.partial(jax.jit, static_argnames=("rows",))
def _suffix_rows(P: jax.Array, off, *, rows: int) -> jax.Array:
    """Serve rows [off:off+rows] of a cached full-height panel. The
    offset is traced (one compiled program per (panel shape, rows)
    pair — O(nt), the same count the visit kernels already compile),
    never a Python slice (which would compile per offset VALUE,
    O(nt^2) tiny programs over a whole stream)."""
    return jax.lax.dynamic_slice(P, (off, 0), (rows, P.shape[1]))


@functools.partial(jax.jit, static_argnames=("n",))
def _embed_rows(P: jax.Array, off, *, n: int) -> jax.Array:
    """Zero-embed a (rows, w) panel at row offset `off` of an (n, w)
    frame — how a lower-Cholesky factor panel (rows k0:, from its
    diagonal block down) becomes the full-height normal form every
    later visit slices from: potrf_ooc's just-factored panel on its
    way into the cache, and every factor panel an engine stages
    trimmed (``fetch(embed=)``; PR 34). Rows above the offset are
    exact zeros, which is what the lower factor holds there (the
    host buffer's strictly upper blocks are never written), so the
    frame is bit-identical to the full-height column it stands for,
    without those zeros being read on the host or sent over the
    link. The frame has the panel's dtype (bf16 under the mixed
    mode)."""
    import jax.numpy as jnp
    frame = jnp.zeros((n, P.shape[1]), P.dtype)
    return jax.lax.dynamic_update_slice(frame, P, (off, 0))


def _nbytes(arr) -> int:
    return int(np.dtype(arr.dtype).itemsize) * int(np.prod(arr.shape))


# -- mixed-precision residency casts (ISSUE 12) ---------------------------
#
# The bf16 streaming mode halves every staged/resident/broadcast byte
# by demoting factor panels to the lo dtype at the cache/staging
# boundary and promoting them back only where full precision is
# required (host factor mirrors, tau rows). Every panel-granular cast
# goes through these helpers so the byte volume the casts add back is
# directly attributable: ``ooc.cast_demote_bytes`` counts the
# full-precision bytes entering a demotion, ``ooc.cast_promote_bytes``
# the full-precision bytes a promotion produces. (Sub-panel promotes
# inside the mixed visit kernels — the w x w diagonal blocks the
# strip solves need in f32 — are fused into the jitted programs and
# deliberately uncounted: they never cross a staging boundary.)


@functools.partial(jax.jit, static_argnames=("dt",))
def _cast_panel(P: jax.Array, *, dt) -> jax.Array:
    return P.astype(dt)


def demote_dev(arr: jax.Array, dtype) -> jax.Array:
    """Demote a just-computed device panel to the resident lo dtype
    (the mixed ``put``/broadcast path)."""
    if obs_events.enabled():
        obs_metrics.inc("ooc.cast_demote_bytes", _nbytes(arr))
    return _cast_panel(arr, dt=np.dtype(dtype))


def demote_host(x: np.ndarray, dtype) -> np.ndarray:
    """Demote a host factor slice for staging — the mixed loaders
    wrap this around every revisit upload, halving its H2D bytes
    before _h2d ever sees them. The cast copies, so the result is
    contiguous (the _h2d fast path) regardless of the source
    stride."""
    x = np.asarray(x)
    if obs_events.enabled():
        obs_metrics.inc("ooc.cast_demote_bytes", int(x.nbytes))
    return x.astype(dtype)


def host_demoter(lo) -> Callable:
    """The staging-boundary demotion rule as ONE loader wrapper for
    every driver's revisit loaders and solve sweeps: the identity
    when `lo` is None (the full-precision path bit-identically),
    else demote_host into `lo`. A single definition so a future
    change to demotion (another counter, an f8 tier) lands at every
    staging site at once."""
    if lo is None:
        return lambda sl: sl
    return lambda sl: demote_host(sl, lo)


def promote_dev(arr: jax.Array, dtype) -> jax.Array:
    """Promote a lo-resident panel back to full precision (the
    sharded layer's host-mirror writes)."""
    out = _cast_panel(arr, dt=np.dtype(dtype))
    if obs_events.enabled():
        obs_metrics.inc("ooc.cast_promote_bytes", _nbytes(out))
    return out


def _guard_transfer(site: str, fn: Callable, **ctx):
    """Resilience wrapper for one host<->HBM transfer (resil/, ISSUE
    9). With no fault plan installed the success path is EXACTLY
    ``fn()`` — one module-attribute load plus a zero-cost try frame,
    no tune lookup — preserving the bit-identical/zero-dispatch off
    contract; a REAL transient failure (guard.TRANSIENT_TYPES) still
    engages the bounded retry, which is the production duty this
    wrapper exists for. With a plan, the injection point fires first
    (site ``h2d`` / ``d2h`` with the buf/idx context) and transient
    failures are re-attempted the same way; a ``nan`` corruption rule
    poisons the transferred payload (the non-finite sentinel's test
    vector)."""
    if _faults.active() is None:
        try:
            return fn()
        except Exception as e:
            if not _guard.is_transient(e):
                raise
            return _guard.retry_after_failure(fn, site, e, **ctx)

    def attempt():
        action = _faults.check(site, **ctx)
        out = fn()
        if action == "nan" and out is not None:
            if isinstance(out, np.ndarray):
                # d2h returns the caller's preallocated host VIEW —
                # poison it in place (a rebound copy would leave the
                # real factor clean and the corruption rule a no-op)
                out *= np.nan
            else:
                out = out * np.nan
        return out

    return _guard.retry(attempt, site, **ctx)


class PanelCache:
    """Budget-aware device-resident panel cache (module doc). Not a
    generic cache: keys are (buf, epoch, idx), values device arrays,
    and the budget is HBM bytes — eviction drops the cache's
    reference (the buffer itself dies when the last consumer's
    reference does, so evicting an in-flight panel is safe; pinning
    exists to keep the POLICY from discarding the two panels about to
    be reused)."""

    def __init__(self, budget_bytes: int, policy: str = "mru",
                 pins: int = 2, resident_dtype=None) -> None:
        self.budget = max(int(budget_bytes), 0)
        self.policy = policy if policy in ("lru", "mru", "fifo") \
            else "mru"
        #: dtype-aware residency (ISSUE 12): the dtype entries are
        #: expected to hold under the mixed-precision mode (None =
        #: the driver's compute dtype, the historical behavior). The
        #: cache itself stores whatever arrays it is handed — the
        #: drivers demote before `put` and in their loaders — but the
        #: declared resident dtype is what the budget math and the
        #: stats report, so a panel-count prediction at bf16
        #: residency is not 2x conservative (engine_for satellite).
        self.resident_dtype = None if resident_dtype is None \
            else np.dtype(resident_dtype)
        #: optional (key, arr) callback fired for every eviction,
        #: UNDER the cache lock — the hook must only record (the
        #: engine's spill hook appends to a list; the actual D2H is
        #: scheduled by the engine outside the lock). Dirty working
        #: panels (StreamEngine.stash) ride on this.
        self.on_evict: Optional[Callable] = None
        self._lock = threading.Lock()
        #: key -> (array, nbytes); order = recency (get moves to end)
        self._entries: "collections.OrderedDict[Tuple, Tuple]" = \
            collections.OrderedDict()
        self._epochs: Dict[str, int] = {}
        #: the working panels the POLICY must not discard: current
        #: visit + prefetched next (the historical 2), plus one more
        #: per lookahead slot when the sharded schedule keeps an
        #: in-flight panel live across a step boundary (ISSUE 11 —
        #: callers size this via StreamEngine/engine_for extra_pins)
        self._pins: "collections.deque[Tuple]" = \
            collections.deque(maxlen=max(int(pins), 2))
        self.resident_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0
        self.invalidated_bytes = 0
        self.served_bytes = 0
        self.uploaded_bytes = 0

    @property
    def enabled(self) -> bool:
        return self.budget > 0

    def key(self, buf: str, idx: int) -> Tuple:
        with self._lock:
            return (buf, self._epochs.get(buf, 0), idx)

    def get(self, key: Tuple, served_rows: Optional[int] = None):
        """The cached panel for `key` (recency-bumped + pinned), or
        None. `served_rows` scales the hit's byte credit when the
        consumer slices a row sub-view (the credit is bytes NOT
        re-sent over H2D, which is the view's size, not the
        entry's)."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            arr, nb = ent
            rows = int(arr.shape[0]) or 1
            self.served_bytes += nb if served_rows is None \
                else nb * min(int(served_rows), rows) // rows
            self._pins.append(key)
            return arr

    def put(self, key: Tuple, arr) -> bool:
        """Insert (evicting per policy to fit the budget; pinned keys
        and the new entry itself are never victims). False when the
        cache is off, the entry alone exceeds the budget, or only
        pinned entries could make room."""
        if not self.enabled:
            return False
        nb = _nbytes(arr)
        if nb > self.budget:
            return False
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return True
            while self.resident_bytes + nb > self.budget:
                victim = self._victim()
                if victim is None:
                    return False
                varr, vnb = self._entries.pop(victim)
                self.resident_bytes -= vnb
                self.evictions += 1
                if self.on_evict is not None:
                    self.on_evict(victim, varr)
            self._entries[key] = (arr, nb)
            self.resident_bytes += nb
            self._pins.append(key)
            return True

    def _victim(self) -> Optional[Tuple]:
        """Eviction choice under self._lock: lru = least recent, mru
        = most recent, fifo = oldest insertion (== lru order here
        since puts append and only gets re-order; kept distinct for
        measurement). Pinned keys are skipped."""
        pinned = set(self._pins)
        order = list(self._entries)
        if self.policy == "mru":
            order.reverse()
        elif self.policy == "fifo":
            pass          # insertion order IS the dict order pre-get
        for k in order:
            if k not in pinned:
                return k
        return None

    def take(self, key: Tuple):
        """Pop one entry and return its array (None when absent),
        WITHOUT counting an eviction/hit/miss or firing on_evict —
        the engine's shutdown spill of still-resident dirty panels
        reads through this."""
        with self._lock:
            ent = self._entries.pop(key, None)
            if ent is None:
                return None
            self.resident_bytes -= ent[1]
            return ent[0]

    def drop(self, key: Tuple) -> bool:
        """Remove one entry WITHOUT counting an eviction or firing
        on_evict — the caller supersedes the value (a dirty working
        panel being re-stashed after an update). No-op when absent."""
        return self.take(key) is not None

    def invalidate(self, buf: str) -> int:
        """Bump `buf`'s epoch and drop its entries: every cached
        panel of the buffer is stale (getrf's row-swap fixup rewrote
        the host rows under it). Returns the number dropped."""
        with self._lock:
            self._epochs[buf] = self._epochs.get(buf, 0) + 1
            stale = [k for k in self._entries if k[0] == buf]
            for k in stale:
                _, nb = self._entries.pop(k)
                self.resident_bytes -= nb
                # per-cause byte accounting (ISSUE 10 satellite):
                # every byte dropped here is a panel the stream must
                # re-upload — the cost the tournament-pivot LU path
                # exists to remove (it never calls invalidate)
                self.invalidated_bytes += nb
            self._pins = collections.deque(
                (k for k in self._pins if k[0] != buf),
                maxlen=self._pins.maxlen)
            if stale:
                self.invalidations += 1
            return len(stale)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            total = self.hits + self.misses
            return {
                "budget_bytes": self.budget,
                "policy": self.policy,
                "resident_dtype": None if self.resident_dtype is None
                else self.resident_dtype.name,
                "entries": len(self._entries),
                "resident_bytes": self.resident_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": self.hits / total if total else 0.0,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "invalidated_bytes": self.invalidated_bytes,
                "served_bytes": self.served_bytes,
                "uploaded_bytes": self.uploaded_bytes,
            }


def auto_budget_bytes(n: int, panel_cols: int, itemsize: int,
                      device=None) -> int:
    """Device memory minus the working-set reserve (RESERVE_PANELS
    full panels), with allocator headroom. 0 (cache off) when the
    backend does not report a limit — "auto" must never invent a
    budget the device cannot honor.

    `device` is the device the engine stages panels onto; the default
    is THIS PROCESS's first local device (never ``jax.devices()[0]``,
    which on a multi-process mesh is process 0's device — sizing
    another host's budget from it would be wrong whenever the mesh
    mixes part generations or per-host HBM carve-outs differ). The
    sharded OOC layer passes each host's staging device explicitly."""
    try:
        dev = device if device is not None else jax.local_devices()[0]
        stats = dev.memory_stats() or {}
        limit = int(stats.get("bytes_limit", 0))
    except Exception:
        limit = 0
    if limit <= 0:
        return 0
    reserve = RESERVE_PANELS * int(n) * int(panel_cols) * int(itemsize)
    return max(int(limit * AUTO_BUDGET_FRACTION) - reserve, 0)


class StreamEngine:
    """One per driver invocation (or shared across a composed driver
    like gels_ooc: factor panels cached by geqrf are served straight
    to the unmqr apply). See the module doc for the two layers."""

    def __init__(self, budget_bytes: int = 0, policy: str = "mru",
                 prefetch_depth: int = 1, pins: int = 2,
                 resident_dtype=None) -> None:
        self.cache = PanelCache(budget_bytes, policy, pins=pins,
                                resident_dtype=resident_dtype)
        self.prefetch_depth = max(int(prefetch_depth), 0)
        _ring.reserve(self.prefetch_depth + 2)
        self._h2d_pool = cf.ThreadPoolExecutor(
            1, thread_name_prefix="ooc-h2d") \
            if self.prefetch_depth > 0 else None
        self._d2h_pool = cf.ThreadPoolExecutor(
            1, thread_name_prefix="ooc-d2h")
        self._lock = threading.Lock()
        self._pending: Dict[Tuple, cf.Future] = {}
        self._writes: Dict[Tuple[str, int], list] = {}
        #: dirty working panels (stash): key -> (buf, idx, spill_view
        #: factory). Evicted dirty panels land in _evicted (under the
        #: cache lock, record-only) and are spilled by _drain_spills
        #: on the next engine call from the stashing thread.
        self._dirty: Dict[Tuple, Tuple] = {}
        self._evicted: list = []
        self.cache.on_evict = self._record_evicted
        self.spills = 0
        self._finished = False
        # overlap accounting (seconds)
        self.prefetch_issued = 0
        self.prefetch_upload_seconds = 0.0
        self.prefetch_wait_seconds = 0.0
        self.sync_upload_seconds = 0.0
        self.d2h_write_seconds = 0.0
        self.d2h_wait_seconds = 0.0
        self.writes_issued = 0

    # -- properties -------------------------------------------------

    @property
    def caching(self) -> bool:
        """Call sites switch loaders on this: cached mode wants an
        entry every later visit can slice from, the full-height
        normal form (staged whole, or, for a lower-Cholesky factor
        panel, from its diagonal block down and zero-embedded on the
        device: ``fetch(embed=)``); uncached mode wants exactly the
        rows the kernel consumes (the pre-engine upload,
        bit-identical by construction)."""
        return self.cache.enabled

    # -- H2D side ---------------------------------------------------

    def _wait_write(self, buf: str, idx: int) -> None:
        """Block until `buf[idx]`'s host writeback (if any) lands —
        a re-read of the host factor must see the final rows. The
        blocked wall is a cache stall on the flight-recorder ledger
        (a spilled/written panel re-read the step had to fence on);
        credit() no-ops off the recording thread, so the prefetch
        worker's fences never misattribute."""
        with self._lock:
            futs = list(self._writes.get((buf, idx), ()))
        if not futs:
            return
        t0 = time.perf_counter()
        with obs_events.span("ooc::wait_write", cat="staging",
                             buf=buf, idx=idx):
            for f in futs:
                f.result()
        _ledger.credit("cache", time.perf_counter() - t0)

    def _upload(self, buf: str, idx: int, loader: Callable,
                embed: Optional[Tuple[int, int]] = None) -> Any:
        """Stage what `loader` returns. With `embed=(off, n)` that is
        rows off: of a panel whose rows above are zeros (fetch doc):
        the full-height frame is made on the device, and the bytes
        the trim left on the host are counted beside the bytes
        sent."""
        self._wait_write(buf, idx)
        arr = _guard_transfer("h2d", lambda: _h2d(loader()),
                              buf=buf, idx=idx)
        # runs on BOTH the prefetch worker and the main thread —
        # take the cache lock like every other counter mutation
        with self.cache._lock:
            self.cache.uploaded_bytes += _nbytes(arr)
        off, n = embed or (0, 0)
        if off > 0:
            if obs_events.enabled():
                obs_metrics.inc("ooc.h2d_trimmed_bytes",
                                off * _nbytes(arr) // arr.shape[0])
            arr = _embed_rows(arr, off, n=n)
        return arr

    def prefetch(self, buf: str, idx: int, loader: Callable,
                 cache: bool = True,
                 embed: Optional[Tuple[int, int]] = None) -> None:
        """Queue `buf[idx]`'s upload on the transfer thread (no-op
        when already cached, already pending, or prefetch is off).
        The loader runs ON the worker — it must read host state that
        is stable until the matching fetch (drivers only prefetch
        within a fixup-free window; a stale pending entry is fenced
        by the epoch in its key). `embed` as in fetch: the pending
        entry is the embedded full-height panel."""
        if self._h2d_pool is None:
            return
        key = self.cache.key(buf, idx)
        with self._lock:
            if key in self._pending \
                    or len(self._pending) >= self.prefetch_depth:
                return
        if cache and self.cache.enabled:
            with self.cache._lock:
                if key in self.cache._entries:
                    return

        def task():
            t0 = time.perf_counter()
            with obs_events.span("ooc::prefetch", cat="staging",
                                 buf=buf, idx=idx):
                arr = self._upload(buf, idx, loader, embed)
            self.prefetch_upload_seconds += time.perf_counter() - t0
            return arr

        self.prefetch_issued += 1
        fut = self._h2d_pool.submit(task)
        with self._lock:
            self._pending[key] = fut

    def fetch(self, buf: str, idx: int, loader: Callable,
              view: Optional[Tuple[Any, int]] = None,
              cache: bool = True,
              embed: Optional[Tuple[int, int]] = None) -> Any:
        """The visiting panel `buf[idx]`: cache hit, pending prefetch,
        or synchronous upload — in that order. `view=(offset, rows)`
        slices the served full-height entry down to the rows the
        kernel consumes (potrf's shrinking visits, gels' R prefix);
        None serves the entry as-is. With the cache off the loader is
        expected to return the exact kernel input and `view` is
        ignored for uploads.

        `embed=(off, n)` says the loader returns a TRIMMED panel:
        rows off: of an (n, w) panel whose rows above `off` are exact
        zeros (a lower-Cholesky factor panel above its diagonal
        block). The upload zero-embeds it on the device
        (_embed_rows), so the cache, `view` and the caller see the
        same full-height array a whole-column upload gives, and
        `off * w * itemsize` bytes are neither read on the host nor
        sent (``ooc.h2d_trimmed_bytes``). `off == 0` is a plain
        upload. It is the caller's knowledge of its matrix, never a
        default: an LU panel carries U above its diagonal block."""
        key = self.cache.key(buf, idx)
        use_cache = cache and self.cache.enabled
        if use_cache:
            arr = self.cache.get(
                key, None if view is None else view[1])
            if arr is not None:
                return self._serve(arr, view)
        fut = None
        with self._lock:
            fut = self._pending.pop(key, None)
        if fut is not None:
            t0 = time.perf_counter()
            with obs_events.span("ooc::wait_stage", cat="staging",
                                 buf=buf, idx=idx, kind="prefetch"):
                arr = fut.result()
            dt = time.perf_counter() - t0
            self.prefetch_wait_seconds += dt
            _ledger.credit("stage", dt)
            if use_cache:
                self.cache.put(key, arr)
                self._drain_spills()
                return self._serve(arr, view)
            return arr       # cache-off loaders return the exact input
        t0 = time.perf_counter()
        # the sync upload is a ledger `stage` frame (self-time: the
        # writeback fence inside _upload charges `cache`, not stage)
        with _ledger.frame("stage"), \
                obs_events.span("ooc::wait_stage", cat="staging",
                                buf=buf, idx=idx, kind="sync"):
            arr = self._upload(buf, idx, loader, embed)
        self.sync_upload_seconds += time.perf_counter() - t0
        if use_cache:
            self.cache.put(key, arr)
            self._drain_spills()
            return self._serve(arr, view)
        return arr

    @staticmethod
    def _serve(arr, view: Optional[Tuple[Any, int]]):
        if view is None:
            return arr
        off, rows = view
        if off == 0 and rows == arr.shape[0]:
            return arr
        return _suffix_rows(arr, off, rows=int(rows))

    def put(self, buf: str, idx: int, arr) -> bool:
        """Insert a just-computed device panel (potrf's factored
        panel at full-height normal form) so later visits never
        re-upload it."""
        if not self.cache.enabled:
            return False
        ok = self.cache.put(self.cache.key(buf, idx), arr)
        self._drain_spills()
        return ok

    # -- dirty working panels (multi-shard extension, ISSUE 7) ------

    def _record_evicted(self, key: Tuple, arr) -> None:
        """PanelCache.on_evict hook: runs UNDER the cache lock, so it
        only records the victim (list append is atomic under the GIL);
        the spill itself is scheduled lock-free by _drain_spills."""
        self._evicted.append((key, arr))

    def _drain_spills(self) -> None:
        """Spill every evicted DIRTY panel to its registered host view
        via the background writer. Clean victims (plain cached reads)
        are just dropped, as before. Runs on the stashing thread —
        cache.put only happens there, so eviction records cannot race
        a concurrent drain."""
        while self._evicted:
            key, arr = self._evicted.pop()
            with self._lock:
                ent = self._dirty.pop(key, None)
            if ent is not None:
                buf, idx, view = ent
                self.spills += 1
                self.write(buf, idx, arr, view())

    def stash(self, buf: str, idx: int, arr,
              view: Callable[[], np.ndarray]) -> bool:
        """Hold a DIRTY working panel (`view()` returns the writable
        host slice its truth belongs in) device-resident under the
        budget. On eviction the panel spills through the D2H writer;
        a later fetch of the key waits that spill (the per-key
        writeback fence) before re-staging from the host view. With
        the cache off (budget 0) this is write-through — the panel is
        written back immediately, exactly the uncached schedule.
        Returns True when the panel stayed resident."""
        key = self.cache.key(buf, idx)
        if self.cache.enabled:
            self.cache.drop(key)           # superseded state, if any
            if self.cache.put(key, arr):
                with self._lock:
                    self._dirty[key] = (buf, idx, view)
                self._drain_spills()
                return True
        self._drain_spills()
        with self._lock:
            self._dirty.pop(key, None)
        self.write(buf, idx, arr, view())
        return False

    def discard(self, buf: str, idx: int) -> None:
        """Drop a stashed/cached panel whose lifetime ended (the
        caller holds or has explicitly written its final value) —
        frees the budget without a spill."""
        key = self.cache.key(buf, idx)
        with self._lock:
            self._dirty.pop(key, None)
        self.cache.drop(key)

    def invalidate(self, buf: str, cause: Optional[str] = None
                   ) -> int:
        """Epoch-bump `buf` (see PanelCache.invalidate) after first
        draining any in-flight prefetch of it — the worker may be
        mid-read of host rows the caller is about to rewrite.

        ``cause`` labels the per-cause counters
        ``ooc.<cause>_invalidations`` / ``ooc.<cause>_invalidation_
        bytes`` (ISSUE 10 satellite): getrf_ooc's partial-pivot
        row-swap fixup passes ``cause="lu"``, whose retired-panel
        bytes were previously folded invisibly into the generic
        eviction stats — bench now shows exactly the delta the
        tournament-pivot path removes (it never invalidates; its
        counter stays 0). Without a cause only the generic instant
        is published."""
        with self._lock:
            stale = [(k, f) for k, f in self._pending.items()
                     if k[0] == buf]
            for k, _ in stale:
                del self._pending[k]
        for _, f in stale:
            try:
                f.result()
            except Exception:
                pass
        b0 = self.cache.invalidated_bytes
        n = self.cache.invalidate(buf)
        if obs_events.enabled():
            dropped_bytes = self.cache.invalidated_bytes - b0
            if n and cause:
                obs_metrics.inc("ooc.%s_invalidations" % cause, n)
                obs_metrics.inc("ooc.%s_invalidation_bytes" % cause,
                                dropped_bytes)
            obs_events.instant("ooc::invalidate", cat="staging",
                               buf=buf, dropped=n,
                               bytes=dropped_bytes)
        return n

    # -- D2H side ---------------------------------------------------

    def write(self, buf: str, idx: int, dev, out_view: np.ndarray
              ) -> None:
        """Queue `dev`'s writeback into the preallocated host slice
        `out_view` on the writer thread: panel k's D2H overlaps panel
        k+1's visit stream. np.asarray on the worker blocks until the
        producing computation is done — exactly the sync the main
        thread no longer pays."""
        self._throttle_writes()

        def task():
            t0 = time.perf_counter()
            with obs_events.span("ooc::writeback", cat="staging",
                                 buf=buf, idx=idx):
                # idempotent host write: the retry wrapper may rerun
                # the whole D2H into the same preallocated view
                _guard_transfer("d2h",
                                lambda: _d2h(dev, out=out_view),
                                buf=buf, idx=idx)
            self.d2h_write_seconds += time.perf_counter() - t0

        self.writes_issued += 1
        fut = self._d2h_pool.submit(task)
        with self._lock:
            self._writes.setdefault((buf, idx), []).append(fut)

    def _throttle_writes(self) -> None:
        """Block the queuing thread until fewer than WRITES_IN_FLIGHT
        writebacks are unfinished (a failed one counts as finished:
        its error is raised where it is today, by the fence or the
        drain that reads its result)."""
        while True:
            with self._lock:
                unfinished = [f for fs in self._writes.values()
                              for f in fs if not f.done()]
            if len(unfinished) < WRITES_IN_FLIGHT:
                return
            t0 = time.perf_counter()
            with obs_events.span("ooc::wait_write", cat="staging",
                                 throttle=True):
                cf.wait(unfinished, return_when=cf.FIRST_COMPLETED)
            dt = time.perf_counter() - t0
            self.d2h_wait_seconds += dt
            _ledger.credit("cache", dt)

    def wait_writes(self) -> None:
        """Drain the writeback queue (drivers call this before
        returning or before host-side fixups that read the factor)."""
        while True:
            with self._lock:
                futs = [f for fs in self._writes.values() for f in fs]
                self._writes.clear()
            if not futs:
                return
            t0 = time.perf_counter()
            with obs_events.span("ooc::wait_write", cat="staging",
                                 n=len(futs)):
                for f in futs:
                    f.result()
            dt = time.perf_counter() - t0
            self.d2h_wait_seconds += dt
            _ledger.credit("cache", dt)

    # -- lifecycle --------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        s = self.cache.stats()
        up = self.prefetch_upload_seconds
        s.update({
            "prefetch_issued": self.prefetch_issued,
            "prefetch_upload_seconds": round(up, 6),
            "prefetch_wait_seconds":
                round(self.prefetch_wait_seconds, 6),
            "prefetch_overlap_fraction":
                round(max(0.0, 1.0 - self.prefetch_wait_seconds / up),
                      4) if up > 0 else 0.0,
            "sync_upload_seconds": round(self.sync_upload_seconds, 6),
            "spills": self.spills,
            "writes_issued": self.writes_issued,
            "d2h_write_seconds": round(self.d2h_write_seconds, 6),
            "d2h_wait_seconds": round(self.d2h_wait_seconds, 6),
            "d2h_overlap_fraction":
                round(max(0.0, 1.0 - self.d2h_wait_seconds
                          / self.d2h_write_seconds), 4)
                if self.d2h_write_seconds > 0 else 0.0,
        })
        return s

    def finish(self) -> Dict[str, Any]:
        """Drain both pipelines, publish the ooc.cache.* / overlap
        counters, remember the stats for bench extras, and shut the
        workers down. Idempotent."""
        global _last_stats
        if self._finished:
            return dict(_last_stats)
        self._finished = True
        self._drain_spills()
        # dirty stashed panels still cache-resident at shutdown spill
        # now: the stash contract is that the registered host view
        # ends up holding the truth whether or not eviction ever
        # fired (the shard drivers discard every stash they factor,
        # so this is a no-op for them — it guards direct engine users)
        with self._lock:
            leftover = list(self._dirty.items())
            self._dirty.clear()
        for key, (buf, idx, view) in leftover:
            arr = self.cache.take(key)
            if arr is not None:
                self.spills += 1
                self.write(buf, idx, arr, view())
        self.wait_writes()
        with self._lock:
            pending = list(self._pending.values())
            self._pending.clear()
        for f in pending:
            try:
                f.result()
            except Exception:
                pass
        if self._h2d_pool is not None:
            self._h2d_pool.shutdown(wait=True)
        self._d2h_pool.shutdown(wait=True)
        _ring.sweep()
        s = self.stats()
        if obs_events.enabled():
            obs_metrics.inc("ooc.cache.hits", s["hits"])
            obs_metrics.inc("ooc.cache.misses", s["misses"])
            obs_metrics.inc("ooc.cache.evictions", s["evictions"])
            obs_metrics.inc("ooc.cache.invalidations",
                            s["invalidations"])
            obs_metrics.inc("ooc.cache.served_bytes",
                            s["served_bytes"])
            obs_metrics.inc("ooc.prefetch.issued",
                            s["prefetch_issued"])
            obs_metrics.observe("ooc.prefetch.overlap_fraction",
                                s["prefetch_overlap_fraction"])
            obs_metrics.observe("ooc.d2h.overlap_fraction",
                                s["d2h_overlap_fraction"])
        _last_stats = s
        return s

    def __enter__(self) -> "StreamEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.finish()


def last_stats() -> Dict[str, Any]:
    """Stats of the most recently finished engine (bench --ooc)."""
    return dict(_last_stats)


#: one-shot flag for the unknown-dtype budget warning below (tests
#: reset it to re-trigger)
_warned_unknown_dtype = False


def engine_for(n: int, panel_cols: int, dtype,
               budget_bytes: Optional[Any] = None,
               device=None, extra_pins: int = 0,
               resident_dtype=None) -> StreamEngine:
    """Build a driver's engine with the tunable knobs resolved
    through tune/select (explicit argument > measured cache entry >
    frozen default — budget 0 / policy mru / prefetch depth 1, see
    tune/cache.FROZEN). `budget_bytes` accepts an int, "auto" (device
    memory minus the working-set reserve), or None (resolve the
    ``ooc/cache_budget_mb`` tunable, which itself may be "auto").
    `device` scopes an "auto" budget to the staging device (the
    per-process local device under a multi-process mesh — see
    auto_budget_bytes). `extra_pins` raises the cache's pinned-panel
    capacity above the default two (visiting + prefetched next) — the
    lookahead-overlapped sharded schedule (ISSUE 11) passes its depth
    so the panel being factored ahead cannot be evicted by its own
    step's trailing fetches. `resident_dtype` declares the
    mixed-precision residency dtype (ISSUE 12): the "auto" budget's
    working-set reserve is sized at the RESIDENT (post-demotion)
    itemsize — panel-count predictions against an f32 itemsize would
    be 2x conservative at bf16 residency — and the cache reports it
    in its stats. An unknown dtype (both None) warns ONCE and assumes
    f64, instead of the historical silent 8-byte fallback that made
    predictions 2-4x conservative for narrow dtypes."""
    from ..tune.select import resolve
    if resident_dtype is not None:
        itemsize = np.dtype(resident_dtype).itemsize
    elif dtype is not None:
        itemsize = np.dtype(dtype).itemsize
    else:
        global _warned_unknown_dtype
        if not _warned_unknown_dtype:
            _warned_unknown_dtype = True
            import warnings
            warnings.warn(
                "stream.engine_for: no dtype supplied — sizing the "
                "'auto' cache budget's working-set reserve at 8 "
                "bytes/element (f64); pass dtype/resident_dtype for "
                "exact panel-count predictions", stacklevel=2)
        itemsize = 8
    if budget_bytes is None:
        # no fallback argument: the shipped default must come from
        # the FROZEN table (select.resolve never consults it when a
        # fallback is supplied), so `bench --tune`-measured budgets
        # and the frozen 0 resolve through one path
        mb = resolve("ooc", "cache_budget_mb", n=n, dtype=dtype)
        budget_bytes = mb if isinstance(mb, str) \
            else int(float(mb) * (1 << 20))
    if isinstance(budget_bytes, str):
        if budget_bytes != "auto":
            raise ValueError("cache budget must be bytes or 'auto', "
                             "got %r" % (budget_bytes,))
        budget_bytes = auto_budget_bytes(n, panel_cols, itemsize,
                                         device=device)
    policy = str(resolve("ooc", "cache_policy", n=n, dtype=dtype))
    depth = int(resolve("ooc", "prefetch_depth", n=n, dtype=dtype))
    return StreamEngine(budget_bytes=int(budget_bytes), policy=policy,
                        prefetch_depth=depth,
                        pins=2 + max(int(extra_pins), 0),
                        resident_dtype=resident_dtype)
