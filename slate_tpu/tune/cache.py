"""Persistent tuning cache (ISSUE 1 tentpole, part 2).

Measured-best configurations are keyed by
``(op, backend_kind, device_kind, dtype, size_bucket)`` and stored as
versioned JSON under ``~/.cache/slate_tpu/`` (override the directory
with ``SLATE_TPU_TUNE_CACHE``; disable lookups entirely with
``SLATE_TPU_TUNE=0``). The file is loaded once per process and
memoized; a corrupt or version-mismatched file is treated as empty
(never fatal — tuning is advisory).

Cold-start contract: when no measured entry exists, selection falls
back to FROZEN — the read-only table of shipped defaults, which are
exactly the constants the drivers used before this subsystem existed
(core/options._DEFAULTS nb=256/ib=128/lookahead=1, eig.py
SPECTRAL_DC_MIN_N, spectral_dc.LEAF, ooc.py panel_cols, qr.py's
fused-vs-carry crossover). An empty cache therefore reproduces
today's routing bit-identically; it can never regress below it.

Keys bucket the size (power-of-two buckets, floor 256) so one probe
at n=4096 serves every nearby shape — the same shape-class idea XLA's
own autotuner uses for gemm tilings, and the TPU-vs-CPU block-size
divergence reported by arXiv:2112.09017 is exactly what the
backend_kind/device_kind key components capture.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, Optional

from . import stats

#: bump when the on-disk layout changes; mismatched files are ignored
SCHEMA_VERSION = 1

_FILE_NAME = "tune_cache_v%d.json" % SCHEMA_VERSION

#: read-only shipped defaults: (op, param) -> value. These mirror the
#: constants that were hard-coded across the drivers before the tune
#: subsystem (see module doc). select.resolve falls back here (or to
#: the caller's shape-dependent formula) when the cache has no
#: measured entry, so cold start == today's behavior.
FROZEN: Dict[tuple, Any] = {
    ("*", "nb"): 256,            # core/options._DEFAULTS BlockSize
    ("*", "ib"): 128,            # core/options._DEFAULTS InnerBlocking
    ("*", "lookahead"): 1,       # core/options._DEFAULTS Lookahead
    ("heev", "spectral_dc_min_n"): 2048,   # eig.SPECTRAL_DC_MIN_N
    ("heev", "dc_leaf"): 256,              # spectral_dc.LEAF
    ("geqrf", "fused_max_n"): 4096,        # qr.py measured crossover
    ("ooc", "panel_cols"): 8192,           # ooc.py streaming width
    # stream-engine knobs (ISSUE 4): budget 0 = panel cache OFF, the
    # pre-engine uncached schedule bit-identically (linalg/stream.py
    # budget contract); "auto" or an explicit MB count turns it on.
    # mru is the eviction policy a cyclic left-looking revisit wants
    # (LRU degenerates to zero hits once the factor outgrows the
    # budget); prefetch depth 1 = double-buffered H2D
    ("ooc", "cache_budget_mb"): 0,         # stream.PanelCache budget
    ("ooc", "cache_policy"): "mru",        # lru | mru | fifo
    ("ooc", "prefetch_depth"): 1,          # async H2D lookahead
    # sharded-OOC knobs (ISSUE 7): shard_method "stream" = even with a
    # grid supplied, the OOC drivers keep the single-device stream
    # path bit-identically (dist/shard_ooc.py is an earned or explicit
    # route — core/methods.MethodOOC); shard_fanin feeds the factor-
    # panel broadcast tree (dist/tree.py schedule, 2 = binary);
    # shard_min_panels is the per-rank panel floor below which a
    # measured "sharded" entry still demotes to the stream path (the
    # cyclic walk cannot balance fewer panels than ranks)
    ("ooc", "shard_method"): "stream",     # stream | sharded
    ("ooc", "shard_fanin"): 2,             # broadcast tree fan-in
    ("ooc", "shard_min_panels"): 2,        # panels per rank floor
    # sharded broadcast-pipeline depth (ISSUE 11): 0 = the
    # step-synchronous schedule, BIT-IDENTICAL to the pre-lookahead
    # drivers (every depth is bitwise-pinned against 0 — the
    # reordering changes only WHEN identical jitted kernels run, not
    # their operands — but 0 stays the shipped default until the TPU
    # hardware round measures the overlap win; depth 1 is the
    # earned/explicit setting, SLATE's lookahead parameter carried to
    # the mesh broadcast)
    ("ooc", "shard_lookahead"): 0,         # broadcast frames in flight
    # OOC-LU pivot discipline (ISSUE 10): "partial" is getrf_ooc's
    # own body (panel-confined partial pivoting, the row swaps
    # applied on the chip and one repair at the end, PR 47);
    # "tournament" is the CALU route (getrf_tntpiv_ooc /
    # shard_getrf_ooc) — rows final when written, checkpoint- and
    # sharding-capable. Read on the chip at n=32768 (PERF.md, PRs 47
    # and 46): 5.4-5.5 s against 11.9-12.4 s a solve
    # (core/methods.MethodLUPivot)
    ("ooc", "lu_pivot"): "partial",        # partial | tournament
    # OOC streaming precision (ISSUE 12): "f32" keeps every staged
    # byte and every trailing update in the input dtype — the PR 11
    # stream bit-identically on a cold cache; "bf16" is the
    # mixed-precision mode (f32 panel factors, bf16 trailing updates
    # + bf16 cache residency + bf16 broadcast frames, refinement-
    # guarded solves) — an earned (bench --ooc/--shard precision
    # legs) or explicit decision (core/methods.MethodPrecision)
    ("ooc", "precision"): "f32",           # f32 | bf16
    # elastic mesh ownership (ISSUE 19): "static" keeps the pure
    # 2D-block-cyclic CyclicSchedule assignment bit-identically on a
    # cold cache; "elastic" re-derives per-host effective throughput
    # from the ledger tails (EWMA over phase-split-corrected step
    # walls) and re-owns not-yet-factored panels away from stragglers
    # at epoch boundaries by rebuilding the remaining subgraph under
    # the new map (dist/elastic.py) — an earned (bench --elastic) or
    # explicit decision (core/methods.MethodOwnership). remap_every is
    # the segment length in panel steps between remap decisions,
    # remap_threshold the max/min host-speed ratio below which the
    # planner keeps the current map (uniform fleets never remap, so
    # elastic stays bitwise vs static), throughput_alpha the EWMA
    # smoothing weight on new step-wall samples
    ("mesh", "ownership"): "static",       # static | elastic
    ("mesh", "remap_every"): 4,            # panel steps per segment
    ("mesh", "remap_threshold"): 1.25,     # speed ratio to act on
    ("mesh", "throughput_alpha"): 0.4,     # EWMA weight, (0, 1]
    # dist/ subsystem knobs (ISSUE 2): the combine-tree fan-in of the
    # mesh TSQR (2 = the reference's binary ttqrt; larger = shorter
    # tree, fatter (g*w, w) combine QRs), the tall-skinny aspect above
    # which the grid geqrf takes the tree instead of the blocked
    # panel loop, and the distributed stedc leaf size
    ("tsqr", "tree_fanin"): 2,             # dist/tree.py schedule
    ("tsqr", "panel_aspect"): 4,           # qr.py grid TSQR gate
    ("stedc", "leaf"): 32,                 # stedc_solve leaf width
    # batch/ coalescing-queue knobs (ISSUE 5): flush a shape bucket at
    # max_batch occupants or after max_wait_us, whichever first — the
    # latency-vs-occupancy trade a serving tier re-probes per hardware
    # (the 2 ms window was sized against a dispatch floor that is not
    # measured on the current machine; chip_smoke.py prints today's)
    ("batch", "max_batch"): 64,            # queue.CoalescingQueue
    ("batch", "max_wait_us"): 2000,        # coalescing window
    # batch stacking strategy (ISSUE 15): "bucket" keeps the PR 5 pow2
    # ladder + validity-masked padding bit-identically on a cold cache;
    # "ragged" is the padding-tax-free route — one dispatch at the max
    # live size rounded to lane alignment, per-element sizes vector,
    # masked ragged Pallas kernels (ops/pallas_kernels.ragged_*) — an
    # earned (bench --serve ragged leg on hardware) or explicit
    # decision (core/methods.MethodBatchStrategy). batch/align is the
    # ladder/ceiling lane alignment: 8 is the CPU-era rung rounding
    # (cold routes unchanged); a TPU probe can earn 128/256-lane rungs
    ("batch", "strategy"): "bucket",       # bucket | ragged
    ("batch", "align"): 8,                 # bucket.ALIGN rung rounding
    ("ragged", "blk"): 32,                 # pk.RAGGED_BLK stripe width
    # serving-daemon knobs (ISSUE 16, serve/): cache_mb bounds the
    # fingerprint-keyed factor cache — FROZEN 0 = cache OFF, and the
    # daemon forwards every request unchanged to the coalescing queue
    # (the cold route is bitwise-identical to direct queue use,
    # pinned by tests); an earned MB budget or explicit argument
    # turns the cached factor + solve-only split path on. The
    # admission thresholds: per-tenant pending-request quota,
    # watchdog-ETA seconds above which lowest-priority requests shed
    # (obs/health.py `health.eta_seconds` gauge), and the oldest-
    # pending-age milliseconds above which degradable f64 requests
    # drop to f32 (serve/admission.py ladder)
    ("serve", "cache_mb"): 0,              # factor cache; 0 = off
    ("serve", "max_pending"): 4096,        # per-tenant quota default
    ("serve", "shed_eta_s"): 30,           # ETA gauge shed threshold
    ("serve", "max_queue_age_ms"): 500,    # degrade-precision gate
    # request-scoped telemetry (ISSUE 18, obs/reqtrace.py +
    # obs/series.py): "off" = Server.submit mints NO span, the RPC
    # header gains NO fields, queue tickets carry None, and the
    # series registry stays empty — the serve/queue cold routes are
    # bitwise and allocation-free vs PR 17 (pinned by tests).
    # serve/slo_ms is the per-tenant latency objective the SLO burn
    # window (series.note_slo) scores against; serve/slo_burn_pct is
    # the violation percentage above which the admission ladder
    # sheds lowest-priority / degrades degradable-f64 requests
    ("obs", "reqtrace"): "off",            # off | on (request tracing)
    ("serve", "metrics"): "off",           # off | on (series + SLO)
    ("serve", "slo_ms"): 500,              # latency objective
    ("serve", "slo_burn_pct"): 50,         # burn shed/degrade gate
    # Pallas kernel arbitration (ISSUE 6): every public kernel entry
    # in ops/pallas_kernels.py registers its tune op here
    # (KERNEL_REGISTRY; linted by tools/check_instrumented.py). The
    # method_* routes ('method_lu_panel', 'chain') are written only
    # by probes — a cold cache keeps the drivers' frozen chains
    # (native/fori, dense compose) bit-identically.
    # resil/ knobs (ISSUE 9): the bounded-retry budget around
    # transfer/collective faults (retries only engage ON failure, so
    # steady state is untouched), the exponential-backoff base, and
    # the checkpoint commit cadence — FROZEN 0 = checkpointing OFF
    # and bit-identical to the pre-resil drivers (resil/checkpoint.py
    # contract; bench --faults pins the 0-byte overhead)
    ("resil", "max_retries"): 2,           # guard.retry budget
    ("resil", "backoff_us"): 500,          # backoff base (*2^attempt)
    ("resil", "ckpt_every"): 0,            # panels per commit; 0 = off
    # flight-recorder knobs (ISSUE 14): "off" = the obs/ledger.py
    # step recorder appends NOTHING and the obs/health.py watchdog
    # starts NO monitor thread — every streaming driver bit-identical
    # to the pre-recorder stack (pinned by tests, single-engine + the
    # 2-process mesh). "on" is an earned (measured-overhead) or
    # explicit decision; obs.ledger.enable()/obs.health.enable()
    # override per process
    ("obs", "ledger"): "off",              # off | on (flight recorder)
    ("obs", "watchdog"): "off",            # off | on (stall monitor)
    ("lu_panel", "ib"): 32,                # lu_panel_rec base width
    ("lu_panel", "max_w"): 256,            # pk.LU_PANEL_MAX_W
    ("steqr2", "chain"): "dense",          # dense | pallas_rec
    ("steqr2", "chain_blk"): 128,          # pk.GIVENS_CHAIN_BLK
    ("bdsqr", "chain"): "dense",           # dense | pallas_rec
    ("qr_panel", "max_w"): 128,            # pk.QR_PANEL_MAX_W
    ("chol_panel", "fused_max"): 1024,     # pk.CHOL_FUSED_MAX
    ("trtri", "fused_max"): 512,           # pk.TRTRI_FUSED_MAX
}


def frozen_default(op: str, param: str, fallback=None):
    """Shipped default for (op, param): exact op entry, then the "*"
    row, then the caller's fallback."""
    if (op, param) in FROZEN:
        return FROZEN[(op, param)]
    if ("*", param) in FROZEN:
        return FROZEN[("*", param)]
    return fallback


def enabled() -> bool:
    """Master switch: SLATE_TPU_TUNE=0/off/false disables every cache
    lookup (selection then sees only explicit options and frozen
    defaults — bit-identical to the pre-tune code paths)."""
    return os.environ.get("SLATE_TPU_TUNE", "1").lower() \
        not in ("0", "off", "false", "no")


def cache_dir() -> str:
    env = os.environ.get("SLATE_TPU_TUNE_CACHE")
    if env:
        return env
    xdg = os.environ.get("XDG_CACHE_HOME")
    base = xdg if xdg else os.path.join(os.path.expanduser("~"), ".cache")
    return os.path.join(base, "slate_tpu")


def cache_path() -> str:
    return os.path.join(cache_dir(), _FILE_NAME)


def size_bucket(n: Optional[int]) -> int:
    """Power-of-two size class (floor 256): one measured entry serves
    every shape in its bucket. n=None (size-independent decisions)
    maps to bucket 0."""
    if n is None:
        return 0
    b = 256
    while b < n:
        b *= 2
    return b


def _backend_device() -> tuple:
    """(backend_kind, device_kind) of the ambient jax backend —
    distinct cache rows per hardware, so a CPU-tuned table never
    leaks onto a TPU run (and re-probing after a backend change is
    automatic: the new backend's keys start cold)."""
    try:
        import jax
        backend = jax.default_backend()
        device = jax.devices()[0].device_kind
    except Exception:                    # backend init failure: tuning
        backend, device = "none", "none"  # is advisory, never fatal
    # device_kind strings can contain spaces ("TPU v5 lite")
    return backend, device.replace(" ", "_").replace("|", "_")


def make_key(op: str, dtype, n: Optional[int]) -> str:
    import numpy as np
    backend, device = _backend_device()
    dt = np.dtype(dtype).name if dtype is not None else "any"
    return "|".join([op, backend, device, dt, str(size_bucket(n))])


class TuneCache:
    """The persistent store: entries[key] = {param: value, ...,
    "_meta": {...probe evidence...}}. Lazy single load per process;
    put() updates memory, save() writes the versioned JSON."""

    def __init__(self, path: Optional[str] = None) -> None:
        self._path = path
        self._lock = threading.Lock()
        self._entries: Optional[Dict[str, Dict[str, Any]]] = None

    @property
    def path(self) -> str:
        return self._path or cache_path()

    @staticmethod
    def _parse(path: str) -> Dict[str, Dict[str, Any]]:
        """Read + validate the versioned JSON; empty dict on missing,
        corrupt, or version-mismatched files (advisory cache, never
        fatal — re-probe repopulates; the next save() overwrites a
        bad file)."""
        try:
            with open(path) as f:
                raw = json.load(f)
            if isinstance(raw, dict) \
                    and raw.get("version") == SCHEMA_VERSION \
                    and isinstance(raw.get("entries"), dict):
                return {str(k): dict(v)
                        for k, v in raw["entries"].items()
                        if isinstance(v, dict)}
        except Exception:
            pass
        return {}

    def _load(self) -> Dict[str, Dict[str, Any]]:
        if self._entries is None:
            # slate-lint: exempt[SL301] every caller holds self._lock
            self._entries = self._parse(self.path)
        return self._entries

    def lookup(self, op: str, dtype, n: Optional[int]
               ) -> Optional[Dict[str, Any]]:
        """The measured entry for (op, backend, device, dtype,
        bucket), or None. Counts hits/misses in tune.stats."""
        with self._lock:
            e = self._load().get(make_key(op, dtype, n))
        stats.record_cache(e is not None)
        return dict(e) if e is not None else None

    def get_param(self, op: str, param: str, dtype, n: Optional[int]):
        e = self.lookup(op, dtype, n)
        if e is None:
            return None
        return e.get(param)

    def put(self, op: str, dtype, n: Optional[int],
            values: Dict[str, Any],
            meta: Optional[Dict[str, Any]] = None) -> None:
        key = make_key(op, dtype, n)
        with self._lock:
            entries = self._load()
            entry = dict(entries.get(key, {}))
            entry.update(values)
            if meta is not None:
                entry["_meta"] = meta
            entries[key] = entry

    def save(self) -> str:
        """Write the versioned JSON atomically (tmp + rename) and
        return the path. Read-merge-write: entries another process
        persisted since our load are kept (our in-memory values win
        per-key conflicts), so concurrent tuning runs don't silently
        drop each other's work."""
        with self._lock:
            entries = self._load()
            path = self.path
            merged = self._parse(path)
            merged.update(entries)
            self._entries = entries = merged
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp.%d" % os.getpid()
            with open(tmp, "w") as f:
                json.dump({"version": SCHEMA_VERSION,
                           "entries": entries}, f, indent=1,
                          sort_keys=True)
            os.replace(tmp, path)
        return path

    def entries(self) -> Dict[str, Dict[str, Any]]:
        """Copy of every loaded entry (the multihost share payload —
        dist/tuneshare.py serializes exactly this)."""
        with self._lock:
            return {k: dict(v) for k, v in self._load().items()}

    def merge(self, entries: Dict[str, Dict[str, Any]]) -> int:
        """BEST-ENTRY merge of another host's table (ROADMAP multihost
        tuning-share item). Per key:

          * missing locally -> adopt the incoming entry;
          * present on both sides -> the entry with the LOWER measured
            best probe time (min over ``_meta.results[*].seconds``)
            wins whole-entry — half-winners are not spliced, a probe's
            parameters are only consistent together;
          * an incoming entry WITHOUT probe evidence never replaces a
            local one (merge must not clobber measurements with
            hearsay).

        In-memory only (like put()); call save() to persist. Returns
        the number of keys adopted/replaced."""
        def best_s(e) -> float:
            try:
                return min(float(r["seconds"])
                           for r in e["_meta"]["results"]
                           if "seconds" in r)
            except Exception:
                return float("inf")

        changed = 0
        with self._lock:
            mine = self._load()
            for key, inc in (entries or {}).items():
                if not isinstance(inc, dict):
                    continue
                cur = mine.get(key)
                if cur is None or best_s(inc) < best_s(cur):
                    mine[key] = dict(inc)
                    changed += 1
        return changed

    def clear_memo(self) -> None:
        """Drop the in-process memo so the next access re-reads the
        file (tests repoint SLATE_TPU_TUNE_CACHE between cases)."""
        with self._lock:
            self._entries = None


_cache = TuneCache()


def get_cache() -> TuneCache:
    return _cache


def reset_cache() -> None:
    """Forget the memoized file contents AND the resolved path (the
    global cache re-reads cache_path() env resolution lazily)."""
    _cache._path = None
    _cache.clear_memo()
