"""Worker for the sharded-OOC multi-process tests (ISSUE 7): one of
two processes on the global 2x4 virtual-CPU mesh, exercising

  * dist/tuneshare wired into the multi-process startup path: process
    0 seeds a measured entry, share_tuning_table broadcasts it over
    the tree, process 1 must adopt it (the ROADMAP item this PR's
    mesh startup path unblocks);
  * shard_potrf_ooc / shard_geqrf_ooc / shard_getrf_ooc across the
    process boundary: results match the local single-engine stream
    (getrf: the tournament-pivot single engine — ISSUE 10), and the
    obs h2d counters prove each host staged ONLY its cyclic shard's
    panels (exactly — the ownership schedule makes prefetch exact);
  * streaming per-host obs snapshot DELTAS over the handshake
    (ISSUE 10 satellite): one incremental counters record per driver
    phase whose deltas sum to the final snapshot;
  * lookahead v2 (ISSUE 11): every driver re-run at depth 1 across
    the process boundary — bitwise vs its depth-0 factor, potrf
    staging exactly the depth-invariant schedule prediction, nt-1
    frames dispatched ahead, per-host broadcast-wait wall emitted;
  * mixed-precision streaming (ISSUE 12): the FROZEN ``ooc/precision``
    cold route is bitwise on the real mesh for all three drivers
    (default vs explicit "f32"), and the bf16 mode's broadcast
    frames carry exactly half the bytes across the process boundary;
  * per-host obs staging spans exported with the PR 5 tid namespace,
    so the parent can merge both hosts' Perfetto traces into one
    timeline.

Run as  python tests/shard_ooc_worker.py <pid> <port> <out_dir>
<seed_cache_dir>.  The parent pre-seeds `seed_cache_dir` with a
measured entry; process 0 points its tune cache there, so the
share-on-startup broadcast carries a REAL persisted table.
"""
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from slate_tpu.testing import multiproc as mp  # noqa: E402

pid, port = int(sys.argv[1]), sys.argv[2]
out_dir, seed_dir = sys.argv[3], sys.argv[4]
if pid == 0:
    # host 0 carries the probed table the rest of the mesh adopts
    os.environ["SLATE_TPU_TUNE_CACHE"] = seed_dir

# tuneshare wired INTO the startup path (ISSUE 7 satellite): host 0's
# persisted entries broadcast + best-entry merged before any driver
# resolves a knob
grid, adopted = mp.startup(pid, port, num_processes=2,
                           expect_devices=8, share_tuning=True)

import numpy as np  # noqa: E402

from slate_tpu import obs  # noqa: E402
from slate_tpu.dist import shard_ooc  # noqa: E402
from slate_tpu.linalg import ooc  # noqa: E402
from slate_tpu.obs import export, ledger, metrics  # noqa: E402
from slate_tpu.tune.cache import get_cache  # noqa: E402

mp.emit("tuneshare", proc=pid, adopted=adopted,
        value=get_cache().get_param("ooc", "shard_method",
                                    np.float32, 4096))

# -- sharded potrf/geqrf vs the local single-engine stream ----------------
obs.enable()
# flight recorder ON for the whole worker (ISSUE 14): every sharded
# step appends a per-host ledger record, the bitwise assertions below
# double as the enabled-state identity pin on a REAL mesh, and the
# obs_* handshake emits stream the per-host ledger tail to the parent
ledger.enable()
n, w = 160, 32
item = 4
rng = np.random.default_rng(0)
x = rng.standard_normal((n, n)).astype(np.float32)
a = x @ x.T / n + 4.0 * np.eye(n, dtype=np.float32)
g = x + 0.1 * n * np.eye(n, dtype=np.float32)

L0 = ooc.potrf_ooc(a, panel_cols=w, cache_budget_bytes=0)
single_h2d = int(metrics.snapshot()["counters"]["ooc.h2d_bytes"])
metrics.reset()

budget = 64 * n * w * item
L1 = shard_ooc.shard_potrf_ooc(a, grid, panel_cols=w,
                               cache_budget_bytes=budget)
c = metrics.snapshot()["counters"]
sched = shard_ooc.CyclicSchedule((n + w - 1) // w, grid)
expect = sched.staged_bytes({k: n - k * w for k in range(sched.nt)},
                            w, n - (sched.nt - 1) * w, item)
assert np.allclose(L0, L1, rtol=1e-5, atol=1e-5), \
    "proc %d: sharded potrf != stream" % pid
assert int(c["ooc.h2d_bytes"]) == expect, \
    "proc %d staged %d bytes, schedule predicts %d" \
    % (pid, c["ooc.h2d_bytes"], expect)
mp.emit("shard_potrf", proc=pid, h2d_bytes=int(c["ooc.h2d_bytes"]),
        expect_bytes=expect, single_h2d_bytes=single_h2d,
        bcast_panels=int(c["ooc.shard.bcast_panels"]),
        bitwise=bool(np.array_equal(L0, L1)),
        my_panels=sched.my_panels())

mp.emit_obs_delta("obs_potrf", proc=pid)   # streaming increment 1

qr0, tau0 = ooc.geqrf_ooc(g, panel_cols=w, cache_budget_bytes=0)
qr1, tau1 = shard_ooc.shard_geqrf_ooc(g, grid, panel_cols=w,
                                      cache_budget_bytes=budget)
assert np.allclose(qr0, qr1, rtol=1e-4, atol=1e-4)
assert np.allclose(tau0, tau1, rtol=1e-5, atol=1e-5)
mp.emit("shard_geqrf", proc=pid,
        bitwise=bool(np.array_equal(qr0, qr1)
                     and np.array_equal(tau0, tau1)))
mp.emit_obs_delta("obs_geqrf", proc=pid)   # streaming increment 2

# -- sharded tournament LU (ISSUE 10): bitwise vs the single-engine
# tournament stream at the same pivot mode, per-host staging exactly
# the FULL-HEIGHT schedule prediction, pivot payload row counted in
# the broadcast bytes
lp = g * (1.0 + np.arange(n, dtype=np.float32))[:, None]
lu0, piv0 = ooc.getrf_tntpiv_ooc(lp, panel_cols=w,
                                 cache_budget_bytes=0)
metrics.reset()
lu1, piv1 = shard_ooc.shard_getrf_ooc(lp, grid, panel_cols=w,
                                      cache_budget_bytes=budget)
c = metrics.snapshot()["counters"]
expect_lu = sched.staged_bytes({k: n for k in range(sched.nt)},
                               w, n - (sched.nt - 1) * w, item)
assert np.array_equal(lu0, lu1) and np.array_equal(piv0, piv1), \
    "proc %d: sharded getrf != tournament single engine" % pid
assert int(c["ooc.h2d_bytes"]) == expect_lu, \
    "proc %d staged %d bytes, LU schedule predicts %d" \
    % (pid, c["ooc.h2d_bytes"], expect_lu)
mp.emit("shard_getrf", proc=pid, h2d_bytes=int(c["ooc.h2d_bytes"]),
        expect_bytes=expect_lu,
        bcast_panels=int(c["ooc.shard.bcast_panels"]),
        bitwise=True, my_panels=sched.my_panels())
mp.emit_obs_delta("obs_getrf", proc=pid)   # streaming increment 3
mp.emit("obs_final", proc=pid,
        counters={k: float(v)
                  for k, v in metrics.snapshot()["counters"].items()})

# -- lookahead v2 (ISSUE 11): depth 1 on the REAL mesh — each driver
# bitwise vs its depth-0 / single-engine factor, potrf staging still
# EXACTLY the (depth-invariant) schedule prediction, nt-1 frames
# dispatched ahead, and the per-host broadcast-wait wall emitted so
# the slow tier records the mesh-scale overlap numbers
metrics.reset()
L2 = shard_ooc.shard_potrf_ooc(a, grid, panel_cols=w,
                               cache_budget_bytes=budget,
                               lookahead=1)
c = metrics.snapshot()["counters"]
expect_la = sched.staged_bytes(
    {k: n - k * w for k in range(sched.nt)}, w,
    n - (sched.nt - 1) * w, item, depth=1)
assert np.array_equal(np.asarray(L1), np.asarray(L2)), \
    "proc %d: depth-1 potrf != depth-0" % pid
assert int(c["ooc.h2d_bytes"]) == expect_la, \
    "proc %d depth-1 staged %d bytes, schedule predicts %d" \
    % (pid, c["ooc.h2d_bytes"], expect_la)
qr2, tau2 = shard_ooc.shard_geqrf_ooc(g, grid, panel_cols=w,
                                      cache_budget_bytes=budget,
                                      lookahead=1)
lu2, piv2 = shard_ooc.shard_getrf_ooc(lp, grid, panel_cols=w,
                                      cache_budget_bytes=budget,
                                      lookahead=1)
mp.emit("shard_lookahead", proc=pid,
        potrf_bitwise=True,
        potrf_h2d_exact=True,
        bcast_ahead=int(c["ooc.shard.bcast_ahead"]),
        bcast_wait_s=float(c["ooc.shard.bcast_wait_seconds"]),
        bcast_inflight_s=float(
            c["ooc.shard.bcast_inflight_seconds"]),
        geqrf_bitwise=bool(np.array_equal(np.asarray(qr1),
                                          np.asarray(qr2))
                           and np.array_equal(np.asarray(tau1),
                                              np.asarray(tau2))),
        getrf_bitwise=bool(np.array_equal(np.asarray(lu1),
                                          np.asarray(lu2))
                           and np.array_equal(np.asarray(piv1),
                                              np.asarray(piv2))))

# -- mixed-precision streaming (ISSUE 12): the frozen cold route is
# bitwise on the REAL mesh for all three drivers (default vs explicit
# precision="f32"), and the bf16 frames carry exactly half the
# broadcast bytes across the process boundary with a factor every
# host agrees on (the promote-mirror path)
Lp = shard_ooc.shard_potrf_ooc(a, grid, panel_cols=w,
                               cache_budget_bytes=budget,
                               precision="f32")
qrp, taup = shard_ooc.shard_geqrf_ooc(g, grid, panel_cols=w,
                                      cache_budget_bytes=budget,
                                      precision="f32")
lup, pivp = shard_ooc.shard_getrf_ooc(lp, grid, panel_cols=w,
                                      cache_budget_bytes=budget,
                                      precision="f32")
metrics.reset()
Lb = shard_ooc.shard_potrf_ooc(a, grid, panel_cols=w,
                               cache_budget_bytes=budget,
                               precision="bf16")
c = metrics.snapshot()["counters"]
assert np.allclose(np.asarray(L1), np.asarray(Lb), rtol=5e-2,
                   atol=5e-2), "proc %d: bf16 potrf far from f32" % pid
mp.emit("precision", proc=pid,
        potrf_bitwise=bool(np.array_equal(np.asarray(L1),
                                          np.asarray(Lp))),
        geqrf_bitwise=bool(np.array_equal(np.asarray(qr1),
                                          np.asarray(qrp))
                           and np.array_equal(np.asarray(tau1),
                                              np.asarray(taup))),
        getrf_bitwise=bool(np.array_equal(np.asarray(lu1),
                                          np.asarray(lup))
                           and np.array_equal(np.asarray(piv1),
                                              np.asarray(pivp))),
        bf16_bcast_bytes=int(c["ooc.shard.bcast_bytes"]),
        bf16_demote_bytes=int(c["ooc.cast_demote_bytes"]),
        bf16_promote_bytes=int(c["ooc.cast_promote_bytes"]))

# -- per-host Perfetto export (PR 5 tid namespace, auto host id) ----------
path = str(pathlib.Path(out_dir) / ("trace%d.json" % pid))
export.write_trace(path)
mp.emit("trace", proc=pid, path=path)
