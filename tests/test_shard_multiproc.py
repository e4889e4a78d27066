"""Sharded-OOC multi-process coverage (ISSUE 7 acceptance): a real
2-process x 4-virtual-CPU-device mesh running shard_potrf_ooc /
shard_geqrf_ooc through the promoted multiproc fixture, asserting

  * results allclose to the single-device stream engine on every
    host (the workers assert bitwise internally too);
  * each host staged ONLY its cyclic shard's panels — per-host obs
    ``ooc.h2d_bytes`` equals the ownership schedule's exact
    prediction, and the sum over hosts stays within the single-engine
    volume plus one broadcast panel per step;
  * dist/tuneshare rides the multi-process startup path (host 0's
    seeded entry adopted by host 1 — the ROADMAP item this PR's mesh
    startup unblocks);
  * both hosts' Perfetto traces merge into one timeline with
    disjoint per-host tid blocks (the PR 5 namespace);
  * the flight-recorder ledger tail (ISSUE 14) streams per-host
    per-step phase attribution over the handshake."""
import json
from pathlib import Path

import pytest

from slate_tpu.testing import multiproc as mp
from slate_tpu.tune import cache as tc

WORKER = Path(__file__).with_name("shard_ooc_worker.py")


@pytest.mark.slow
def test_two_process_shard_ooc(tmp_path):
    out_dir, seed_dir, empty_dir = (tmp_path / d
                                    for d in ("out", "seed", "empty"))
    for d in (out_dir, seed_dir, empty_dir):
        d.mkdir()
    # Host 0's pre-seeded "measured" table: workers are pinned to the
    # cpu platform by worker_env, so the row is the cpu/cpu key no
    # matter what backend the parent pytest process runs on.
    key = "|".join(["ooc", "cpu", "cpu", "float32", "4096"])
    entry = {"shard_method": "sharded",
             "_meta": {"results": [{"config": {"shard_method": "sharded"},
                                    "seconds": 1e-3}]}}
    (seed_dir / ("tune_cache_v%d.json" % tc.SCHEMA_VERSION)).write_text(
        json.dumps({"version": tc.SCHEMA_VERSION, "entries": {key: entry}}))
    # every worker starts from an EMPTY cache dir (worker 0 repoints to
    # seed_dir before init) so a developer's ~/.cache table can't leak
    # into the adoption assertions
    procs, outs = mp.launch(str(WORKER), num_processes=2,
                            extra_args=[str(out_dir), str(seed_dir)],
                            env={"SLATE_TPU_TUNE_CACHE": str(empty_dir)})
    mp.assert_success(procs, outs)
    recs = [mp.results(out) for out in outs]

    # tuneshare through startup: host 1 adopted host 0's entry
    assert recs[0]["tuneshare"]["adopted"] == 0
    assert recs[1]["tuneshare"]["adopted"] >= 1
    for r in recs:
        assert r["tuneshare"]["value"] == "sharded"

    # per-host staging: exact shard bytes, disjoint panel ownership,
    # and the summed volume bound of the acceptance criterion
    p0, p1 = recs[0]["shard_potrf"], recs[1]["shard_potrf"]
    assert not (set(p0["my_panels"]) & set(p1["my_panels"]))
    n, w, item = 160, 32, 4
    nt = (n + w - 1) // w
    assert set(p0["my_panels"]) | set(p1["my_panels"]) == set(range(nt))
    for r in (p0, p1):
        assert r["h2d_bytes"] == r["expect_bytes"]   # exact prefetch
        assert r["bcast_panels"] == nt
        assert r["bitwise"]      # cross-process transport is exact
    total = p0["h2d_bytes"] + p1["h2d_bytes"]
    assert total <= p0["single_h2d_bytes"] + nt * n * w * item
    for r in recs:
        assert r["shard_geqrf"]["bitwise"]

    # sharded tournament LU (ISSUE 10 acceptance): bitwise == the
    # single-engine getrf_tntpiv_ooc on every host, per-host staging
    # exactly the full-height schedule prediction, disjoint ownership
    g0, g1 = recs[0]["shard_getrf"], recs[1]["shard_getrf"]
    for r in (g0, g1):
        assert r["bitwise"]
        assert r["h2d_bytes"] == r["expect_bytes"]
        assert r["bcast_panels"] == nt
    assert not (set(g0["my_panels"]) & set(g1["my_panels"]))
    assert set(g0["my_panels"]) | set(g1["my_panels"]) \
        == set(range(nt))

    # lookahead v2 (ISSUE 11): depth 1 on the real mesh is bitwise
    # for all three drivers on every host, stages exactly the
    # depth-invariant schedule prediction, and dispatched nt-1
    # frames ahead (the workers assert the bitwise/exact pins
    # in-process; the emission records the per-host overlap walls)
    for r in recs:
        la = r["shard_lookahead"]
        assert la["potrf_bitwise"] and la["potrf_h2d_exact"]
        assert la["geqrf_bitwise"] and la["getrf_bitwise"]
        assert la["bcast_ahead"] == nt - 1
        assert la["bcast_inflight_s"] >= la["bcast_wait_s"] > 0

    # mixed-precision streaming (ISSUE 12): the frozen cold route is
    # bitwise on the real mesh (default vs explicit "f32" for all
    # three drivers), and the bf16 potrf's broadcast frames carried
    # exactly half the f32 frame bytes (n*n*2 — the workers assert
    # the bf16 factor's closeness in-process)
    for r in recs:
        pr = r["precision"]
        assert pr["potrf_bitwise"] and pr["geqrf_bitwise"] \
            and pr["getrf_bitwise"]
        assert pr["bf16_bcast_bytes"] == n * n * item // 2
        assert pr["bf16_demote_bytes"] > 0
        assert pr["bf16_promote_bytes"] > 0

    # streaming obs deltas over the handshake (ISSUE 10 satellite):
    # each host emitted one incremental counters record per phase,
    # and the post-reset increment reconstructs the final snapshot
    # exactly (deltas sum to the full counters)
    for r in recs:
        for tag in ("obs_potrf", "obs_geqrf", "obs_getrf"):
            assert r[tag]["counters"], "%s delta is empty" % tag
        assert r["obs_potrf"]["counters"]["ooc.h2d_bytes"] > 0
        final = r["obs_final"]["counters"]
        inc = r["obs_getrf"]["counters"]
        for key, val in final.items():
            assert inc.get(key, 0.0) == val, key

    # flight-recorder tail over the handshake (ISSUE 14 satellite):
    # each host's obs_potrf record carries the ledger step records
    # committed since the previous emit — per-host, per-step phase
    # attribution streaming while the run progresses (the elastic-
    # mesh item's throughput feed)
    owner_of = {k: (0 if k in p0["my_panels"] else 1)
                for k in range(nt)}
    for proc, r in enumerate(recs):
        led = r["obs_potrf"].get("ledger") or []
        srecs = [e for e in led if e["op"] == "shard_potrf_ooc"]
        assert {e["step"] for e in srecs} >= set(range(nt))
        mine = set(r["shard_potrf"]["my_panels"])
        for e in srecs:
            assert e["host"] == proc          # per-host attribution
            if e["step"] < nt:
                assert e["owner"] == owner_of[e["step"]]
                # the exhaustive phase split: phases sum to the wall
                assert abs(sum(e["phases"].values())
                           - e["wall_s"]) < 1e-3
                if e["step"] in mine:
                    # the owner's record carries the factor phase
                    assert e["phases"].get("factor", 0) > 0
        # the single-engine potrf records ride the same tail
        assert any(e["op"] == "potrf_ooc" for e in led)

    # merged Perfetto timeline: per-host tid blocks are disjoint and
    # each host's process metadata is present
    events = []
    for r in recs:
        with open(r["trace"]["path"]) as f:
            events.extend(json.load(f)["traceEvents"])
    stride = 100_000
    hosts = {e["tid"] // stride for e in events}
    assert hosts == {0, 1}
    names = {e["args"]["name"] for e in events
             if e.get("name") == "process_name"}
    assert {"host 0", "host 1"} <= names
    # both hosts contributed staging spans to the one timeline
    for h in (0, 1):
        assert any(e.get("cat") == "staging"
                   and e["tid"] // stride == h for e in events)
