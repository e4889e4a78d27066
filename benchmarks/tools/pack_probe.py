#!/usr/bin/env python3
"""What staging one column panel costs on THIS host (builder's tool; a
host measurement, so it needs no chip, but the chip's host is the one
that counts):

    python benchmarks/tools/pack_probe.py [--n 32768] [--w 4096] \
        [--reps 4] [--threads 8] [--put]

Times, at the streamed cell's panel shape (a (n, w) column slice of a
C-ordered f32 (n, n) matrix), the ways `linalg/stream.py _h2d` could
make it contiguous: a fresh `np.ascontiguousarray` per panel, `np.copyto`
into one reused and already touched buffer, the same in row chunks on
a thread pool (into a reused and into a fresh buffer), and the two ways
`linalg/ooc.py potrf_ooc` could allocate the factor (`np.zeros_like`,
which fills, against `np.zeros`, which maps untouched pages, and what
the first read and the first write of those pages then cost). `--put`
(needs the chip) also times `jnp.asarray` of a reused staging buffer:
the hand-over, and the wait until the device array is ready.

Prints one JSON line per way: seconds per repetition and GB/s. PERF.md
section 6 keeps the chip host's output.
"""

import argparse
import concurrent.futures as cf
import json
import os
import time

import numpy as np


def say(**kv):
    print(json.dumps(kv), flush=True)


def timed(fn, reps):
    out = []
    for k in range(reps):
        t0 = time.perf_counter()
        fn(k)
        out.append(time.perf_counter() - t0)
    return out


def report(way, secs, nbytes, **kv):
    say(way=way, seconds=[round(s, 4) for s in secs],
        gb_per_s=[round(nbytes / s / 1e9, 2) for s in secs], **kv)


def chunked_copy(dst, src, pool, threads):
    m = src.shape[0]
    step = -(-m // threads)
    list(pool.map(lambda i: np.copyto(dst[i:i + step], src[i:i + step]),
                  range(0, m, step)))


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--n", type=int, default=32768)
    p.add_argument("--w", type=int, default=4096)
    p.add_argument("--reps", type=int, default=4)
    p.add_argument("--threads", type=int, default=8)
    p.add_argument("--put", action="store_true")
    args = p.parse_args()
    n, w, reps = args.n, args.w, args.reps
    nt = n // w
    say(host_cores=os.cpu_count(), n=n, w=w, threads=args.threads,
        panel_mb=round(n * w * 4 / 1e6, 1))

    t0 = time.perf_counter()
    a = np.empty((n, n), np.float32)
    a[...] = 1.0
    say(way="fill the (n, n) operand, first touch",
        seconds=round(time.perf_counter() - t0, 3),
        gb_per_s=round(a.nbytes / (time.perf_counter() - t0) / 1e9, 2))

    def panel(k):
        k = (k + 1) % nt
        return a[:, k * w:(k + 1) * w]

    nb = n * w * 4
    report("np.ascontiguousarray(panel), a fresh buffer each time",
           timed(lambda k: np.ascontiguousarray(panel(k)), reps), nb)
    buf = np.empty((n, w), np.float32)
    report("np.copyto(buf, panel), buf fresh at the first repetition "
           "and reused after", timed(lambda k: np.copyto(buf, panel(k)),
                                     reps), nb)
    for threads in sorted({2, 4, args.threads}):
        with cf.ThreadPoolExecutor(threads) as pool:
            report("reused buf, row chunks on a pool",
                   timed(lambda k: chunked_copy(buf, panel(k), pool,
                                                threads), reps),
                   nb, threads=threads)
    with cf.ThreadPoolExecutor(args.threads) as pool:
        report("a fresh np.empty each time, row chunks on a pool",
               timed(lambda k: chunked_copy(
                   np.empty((n, w), np.float32), panel(k), pool,
                   args.threads), reps), nb, threads=args.threads)

    t0 = time.perf_counter()
    z = np.zeros_like(a)
    t1 = time.perf_counter()
    say(way="np.zeros_like(a)", seconds=round(t1 - t0, 4),
        gb_per_s=round(a.nbytes / (t1 - t0) / 1e9, 2))
    del z
    t0 = time.perf_counter()
    z = np.zeros(a.shape, a.dtype)
    t1 = time.perf_counter()
    say(way="np.zeros(a.shape, a.dtype)", seconds=round(t1 - t0, 6))
    # what a full-height read of a factor panel pays for the rows above
    # its diagonal block, which nothing ever wrote: a read fault a page
    report("np.copyto(buf, panel of the np.zeros buffer), its pages "
           "untouched", timed(lambda k: np.copyto(
               buf, z[:, (nt - 1 - k) * w:(nt - k) * w]), min(reps, nt // 2)),
           nb)
    report("the same panels read again",
           timed(lambda k: np.copyto(
               buf, z[:, (nt - 1 - k) * w:(nt - k) * w]), min(reps, nt // 2)),
           nb)
    # what the writer then pays: the first touch of one panel's lower
    # part, on a pool as `_d2h` writes it
    src = np.ascontiguousarray(panel(0))
    with cf.ThreadPoolExecutor(args.threads) as pool:
        report("first write into np.zeros pages, row chunks on a pool",
               timed(lambda k: chunked_copy(z[:, k * w:(k + 1) * w], src,
                                            pool, args.threads),
                     min(reps, nt // 2)), nb, threads=args.threads)
        report("the same pages written again",
               timed(lambda k: chunked_copy(z[:, k * w:(k + 1) * w], src,
                                            pool, args.threads),
                     min(reps, nt // 2)), nb, threads=args.threads)
    del z, src

    if args.put:
        import jax
        import jax.numpy as jnp
        say(device=str(jax.devices()[0]))
        fresh = np.ascontiguousarray(panel(0))
        t0 = time.perf_counter()
        d = jnp.asarray(fresh)
        t1 = time.perf_counter()
        d.block_until_ready()
        say(way="jnp.asarray(a fresh np.ascontiguousarray): the hand-over, "
            "and until ready", seconds=[round(t1 - t0, 4),
                                        round(time.perf_counter() - t0, 4)])
        del d, fresh
        hand, ready = [], []
        for k in range(reps + 1):
            np.copyto(buf, panel(k))
            t0 = time.perf_counter()
            d = jnp.asarray(buf)
            t1 = time.perf_counter()
            d.block_until_ready()
            t2 = time.perf_counter()
            hand.append(t1 - t0)
            ready.append(t2 - t0)
            del d
        report("jnp.asarray(reused buf): the hand-over (first "
               "repetition dropped)", hand[1:], nb)
        report("jnp.asarray(reused buf): until the array is ready",
               ready[1:], nb)


if __name__ == "__main__":
    main()
