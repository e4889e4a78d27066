"""What moving a host matrix onto a grid of chips costs on the host it
runs on, way by way (PR 28; run by hand on the chips, never in tier-1):

    python3 -m tools.place_probe [--n 49152] [--grid 2x2]
                                 [--chunk-mb 256,384] [--reps 2]

For a C-ordered f32 (n, n) matrix spread as P('p','q') it times

  (a) `jax.device_put(a, sharding)`: every chip's strided block handed
      to the runtime whole, which linearizes it into buffers of its
      own (what `parallel/sharding.place` did before PR 28);
  (b) contiguous chunks from two reused host buffers (touched, and
      sent once before the clock starts) to ONE chip, a block's worth
      of bytes: the link alone; and the same
      with each chunk first copied out of the strided block
      (`np.copyto` into the reused buffer), the pack of one chunk
      under the transfer of the one before;
  (c) both of (b) to every chip at once, a thread a chip: whether the
      links add;
  (d) `parallel/sharding.place` itself, the first call (which touches
      the ring's slots) and the later ones, with one sampled band of
      every shard compared with the host's.

Rates are GB/s (1e9) of bytes handed to the devices, wall to every
array ready. One JSON object on the last line, and the same in
`chiprun_out/place_probe.json`. The numbers answer PERF.md Open
question 10.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def host_matrix(n: int) -> np.ndarray:
    """(n, n) f32 with every page written and every entry a function
    of its place, made band by band (no second copy)."""
    a = np.empty((n, n), np.float32)
    band = max(1, (64 << 20) // (4 * n))
    for i in range(0, n, band):
        rows = min(band, n - i)
        ramp = np.arange(rows * n, dtype=np.float32).reshape(rows, n)
        np.add(ramp, np.float32(i), out=a[i:i + rows])
    return a


def rate(nbytes: int, seconds: float) -> float:
    return nbytes / seconds / 1e9


def send_chunks(block, dev, bufs, pack, out):
    """A block's worth of chunks to `dev` from the two (rows, width)
    buffers `bufs`; with `pack`, each chunk is first copied out of the
    strided `block`."""
    import jax
    m, rows = block.shape[0], bufs[0].shape[0]
    last = [None, None]
    arrs = []
    t0 = time.perf_counter()
    for k, r0 in enumerate(range(0, m, rows)):
        i = k % 2
        if last[i] is not None:
            last[i].block_until_ready()
        h = min(rows, m - r0)
        if pack:
            np.copyto(bufs[i][:h], block[r0:r0 + h])
        last[i] = jax.device_put(bufs[i][:h], dev)
        arrs.append(last[i])
    jax.block_until_ready(arrs)
    out[dev.id] = (time.perf_counter() - t0, sum(x.nbytes for x in arrs))


def chunked(blocks, bufs, pack):
    """`send_chunks` for each (block, device) at once, each device
    from its own two of `bufs`; the aggregate rate and the wall."""
    out = {}
    ts = [threading.Thread(target=send_chunks,
                           args=(b, d, bufs[d.id], pack, out))
          for b, d in blocks]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    nbytes = sum(v[1] for v in out.values())
    return {"gb_per_s": rate(nbytes, wall), "wall_s": wall,
            "bytes": nbytes}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=49152)
    ap.add_argument("--grid", default="2x2")
    ap.add_argument("--chunk-mb", default="256,384")
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args(argv)

    import jax
    import slate_tpu as st
    from slate_tpu.parallel import sharding as sh

    p, q = (int(x) for x in args.grid.split("x"))
    grid = st.make_grid(p, q, devices=jax.devices()[:p * q])
    t0 = time.perf_counter()
    a = host_matrix(args.n)
    res = {"device": jax.devices()[0].device_kind, "n": args.n,
           "grid": [p, q], "bytes": int(a.nbytes),
           "host_fill_s": time.perf_counter() - t0}
    sharding = sh.fitted_sharding(a.shape, grid)
    where = sharding.addressable_devices_indices_map(a.shape)
    blocks = [(a[idx], dev) for dev, idx in where.items()]
    res["block"] = list(blocks[0][0].shape)
    res["block_contiguous"] = bool(blocks[0][0].flags.c_contiguous)

    # (a) the whole array to device_put
    res["a_device_put"] = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(jax.device_put(a, sharding))
        dt = time.perf_counter() - t0
        nb = sum(s.data.nbytes for s in out.addressable_shards)
        res["a_device_put"].append({"gb_per_s": rate(nb, dt),
                                    "wall_s": dt, "bytes": nb})
        del out

    # (b), (c): chunks from reused buffers, one chip and all of them
    row_bytes = blocks[0][0].shape[1] * a.itemsize
    for mb in (int(x) for x in args.chunk_mb.split(",")):
        rows = max(8, (mb << 20) // row_bytes // 8 * 8)
        key = "chunk_%dmb" % mb
        res[key] = {"rows": rows, "chunk_bytes": rows * row_bytes}
        # two buffers a chip, made once and sent once untimed: a
        # buffer's first transfer also pays for its pages' first use
        # by the link (PR 28's first reading, which made them anew for
        # every pass, was 2.3-4.8 GB/s to one chip for that)
        bufs = {d.id: [np.ones((rows, b.shape[1]), a.dtype)
                       for _ in range(2)] for b, d in blocks}
        chunked(blocks, bufs, False)
        for name, bl in (("b_one_chip", blocks[:1]),
                         ("c_all_chips", blocks)):
            for pack in (False, True):
                res[key][name + ("_packed" if pack else "_link")] = [
                    chunked(bl, bufs, pack) for _ in range(args.reps)]
        del bufs

    # (d) the library's placement
    for mb in (int(x) for x in args.chunk_mb.split(",")):
        sh.STAGE_CHUNK_BYTES = mb << 20
        runs = []
        for _ in range(args.reps + 1):
            t0 = time.perf_counter()
            out = sh.place(a, grid)
            dt = time.perf_counter() - t0
            same = all(np.array_equal(np.asarray(s.data[-8:]),
                                      a[s.index][-8:])
                       for s in out.addressable_shards)
            runs.append({"gb_per_s": rate(a.nbytes, dt), "wall_s": dt,
                         "bitwise_sample": bool(same)})
            del out
        res["chunk_%dmb" % mb]["d_place"] = runs
        res["chunk_%dmb" % mb]["ring_slots"] = len(sh._ring._slots)
        res["chunk_%dmb" % mb]["ring_bytes"] = sum(
            s.buf.nbytes for s in sh._ring._slots)
    peak = [d.memory_stats() for d in jax.devices()[:p * q]]
    res["device_peak_bytes"] = [
        (m or {}).get("peak_bytes_in_use") for m in peak]

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "place_probe.json"),
              "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
