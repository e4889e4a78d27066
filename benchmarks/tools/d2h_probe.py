#!/usr/bin/env python3
"""What writing one factor panel back costs on THIS host and link
(builder's tool; needs the chip for a number that counts, runs on the
CPU backend at a toy size to find faults):

    python benchmarks/tools/d2h_probe.py [--n 32768] [--w 4096] \
        [--reps 4] [--threads 8] [--k0 0 4096 ...] \
        [--ways fetch library pinned shapes raw] \
        [--chunk-mib 4 8 16 24 32 64] [--sweep-k0 0 4096]

The other direction of `pack_probe.py`. At the streamed cell's shapes
(a device panel of f32 (n - k0, w) for each k0, written into
`out[k0:, k0:k0 + w]` of a touched C-ordered (n, n) host buffer, as
`linalg/ooc.py potrf_ooc` writes its factor back) it times the ways
`linalg/stream.py _d2h` could bring the bytes over:

  fetch         device row slices, `np.asarray` of each on a pool
                thread, each landing in an array the runtime
                allocates, copied into `out` from there. `rows/8`:
                eight slices whatever their size (what `_d2h` did up
                to PR 36); `<= N MiB`: slices of at most that many
                bytes (`--chunk-mib`, at the heights `--sweep-k0`), on
                `--threads` threads and, at 16 MiB, on 4 and 12
  library       `stream._d2h` as it stands in this tree (since PR 37:
                ONE program cuts the panel into row chunks of at most
                `stream.FETCH_CHUNK_BYTES`, and a pool made at every
                call, as the writer's is, fetches them)
  pinned        `jax.device_put` of the whole panel to the device's
                `pinned_host` memory, `block_until_ready`, the numpy
                view `_single_device_array_to_np_array_did_copy()`
                gives (and whether it copied), then `np.copyto` into
                `out` in row chunks on the pool; `pinned/2`, `/4`: the
                same in 2 and 4 row pieces put together, each
                scattered as soon as it is ready
  shapes        fetch and library on the right-hand side's shape
                (n, nrhs) and on a bf16 panel
  raw           (last, because a wrong address kills the process; only
                where the pinned view copied) the view made from
                `unsafe_buffer_pointer()` instead of numpy's own

and for each: seconds (until ready, making the view and scattering,
where a way has such parts), the resident set's growth while the bytes
came over (`obs.events._resident_bytes`, what `span(resident=)` reads)
and what of it is still held afterwards, and whether `out` then equals
the panel bit for bit. Every repetition gets a device array of its
own, made outside the timed part: the runtime keeps the host copy of
an array it has fetched once, and a second `np.asarray` of the same
array is a memcpy. The first repetition of each way is printed and
left out of `steady_gb_per_s`.

Prints one JSON line per way and height; with `--out` also into that
file. PERF.md section 6 keeps the chip's output (PR 37).
"""

import argparse
import concurrent.futures as cf
import ctypes
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))

WAYS = ("fetch", "library", "pinned", "shapes", "raw")
_sink = None


def say(**kv):
    line = json.dumps(kv)
    print(line, flush=True)
    if _sink is not None:
        _sink.write(line + "\n")
        _sink.flush()


def nbytes(a):
    return int(np.dtype(a.dtype).itemsize * np.prod(a.shape))


def bounds(m, step):
    return [(i, min(i + step, m)) for i in range(0, m, step)]


def scatter(out, view, pool, threads):
    """`view` into `out` in row chunks on the pool."""
    list(pool.map(lambda b: np.copyto(out[b[0]:b[1]], view[b[0]:b[1]]),
                  bounds(view.shape[0], -(-view.shape[0] // threads))))


def fetch(chunk_bytes=None):
    """Row slices of at most `chunk_bytes` (None: an eighth of the
    rows, what `_d2h` did up to PR 36), each through `np.asarray`."""
    def run(x, out, pool, threads):
        m = x.shape[0]
        step = -(-m // 8) if chunk_bytes is None \
            else max(1, chunk_bytes * m // nbytes(x))

        def one(b):
            out[b[0]:b[1]] = np.asarray(x[b[0]:b[1]])
        t0 = time.perf_counter()
        list(pool.map(one, bounds(m, step)))
        return {"fetch_s": time.perf_counter() - t0,
                "chunks": -(-m // step)}, None
    return run


def library(x, out, pool, threads):
    from slate_tpu.linalg import stream
    t0 = time.perf_counter()
    stream._d2h(x, out=out)
    return {"fetch_s": time.perf_counter() - t0}, None


def raw_view(y):
    """A numpy view over the address the runtime gives for `y`; the
    caller keeps `y` alive while it reads."""
    buf = (ctypes.c_char * nbytes(y)).from_address(y.unsafe_buffer_pointer())
    return np.frombuffer(buf, np.dtype(y.dtype)).reshape(y.shape)


def pinned(pieces, raw=False):
    def run(x, out, pool, threads):
        import jax
        from jax.sharding import SingleDeviceSharding
        dev, = x.devices()
        host = SingleDeviceSharding(dev, memory_kind="pinned_host")
        rows = bounds(x.shape[0], -(-x.shape[0] // pieces))
        t0 = time.perf_counter()
        if pieces == 1:
            ys = [jax.device_put(x, host)]
        else:
            ys = jax.device_put([x[i:j] for i, j in rows], host)
        put_s = time.perf_counter() - t0
        ready_s = view_s = scatter_s = 0.0
        copied = []
        for y, (i, j) in zip(ys, rows):
            t1 = time.perf_counter()
            y.block_until_ready()
            t2 = time.perf_counter()
            if raw:
                view, did = raw_view(y), False
            else:
                view, did = y._single_device_array_to_np_array_did_copy()
            t3 = time.perf_counter()
            scatter(out[i:j], view, pool, threads)
            t4 = time.perf_counter()
            ready_s, view_s, scatter_s = (ready_s + t2 - t1, view_s + t3 - t2,
                                          scatter_s + t4 - t3)
            copied.append(bool(did))
            del view
        return ({"put_s": put_s, "ready_s": ready_s, "view_s": view_s,
                 "scatter_s": scatter_s, "did_copy": any(copied)}, ys)
    return run


def measure(way, fn, x, out, ref, pool, args, **kv):
    """`args.reps` repetitions of one way, each on a copy of `x` that
    nothing has fetched yet; one line. Returns whether a view copied."""
    import jax.numpy as jnp
    from slate_tpu.obs.events import _resident_bytes
    size = nbytes(x)
    rows = []
    for _ in range(args.reps):
        out[...] = -1           # touched pages; a stale answer shows
        xr = jnp.copy(x).block_until_ready()
        r0 = _resident_bytes() or 0
        t0 = time.perf_counter()
        try:
            parts, held = fn(xr, out, pool, args.threads)
        except Exception as e:      # a route this runtime does not have
            say(way=way, error="%s: %s" % (type(e).__name__, str(e)[:300]),
                **kv)
            return None
        total = time.perf_counter() - t0
        r1 = _resident_bytes() or 0
        for y in held or ():
            y.delete()
        del held, xr
        r2 = _resident_bytes() or 0
        rows.append(dict(parts, total_s=total,
                         grew_mb=(r1 - r0) / 1e6, kept_mb=(r2 - r0) / 1e6,
                         equal=bool(np.array_equal(out, ref))))
    steady = rows[1:] or rows
    rate = size / statistics.median(r["total_s"] for r in steady) / 1e9
    lists = [k for k in rows[0] if k.endswith("_s") or k.endswith("_mb")]
    say(way=way, mb=round(size / 1e6, 1), steady_gb_per_s=round(rate, 2),
        equal=all(r["equal"] for r in rows),
        steady_grew_share=round(max(r["grew_mb"] for r in steady)
                                * 1e6 / size, 4),
        **{k: rows[-1][k] for k in ("did_copy", "chunks") if k in rows[-1]},
        **{k: [round(r[k], 4 if k.endswith("_s") else 1) for r in rows]
           for k in lists}, **kv)
    return rows[-1].get("did_copy")


def panel(key, shape, dtype):
    import jax
    import jax.numpy as jnp
    x = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32)
                .astype(dtype))(key)
    return x.block_until_ready()


def main():
    global _sink
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--n", type=int, default=32768)
    p.add_argument("--w", type=int, default=4096)
    p.add_argument("--nrhs", type=int, default=8)
    p.add_argument("--reps", type=int, default=4)
    p.add_argument("--threads", type=int, default=8)
    p.add_argument("--k0", type=int, nargs="*", default=None)
    p.add_argument("--ways", nargs="*", default=list(WAYS), choices=WAYS)
    p.add_argument("--chunk-mib", type=int, nargs="*",
                   default=[4, 8, 16, 24, 32, 64])
    p.add_argument("--sweep-k0", type=int, nargs="*", default=None)
    p.add_argument("--out", default=None)
    args = p.parse_args()
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        _sink = open(args.out, "w")
    import jax
    import jax.numpy as jnp
    n, w = args.n, args.w
    dev = jax.devices()[0]
    say(device=str(dev), device_kind=dev.device_kind,
        memories=[m.kind for m in dev.addressable_memories()],
        default_memory=dev.default_memory().kind, host_cores=os.cpu_count(),
        n=n, w=w, threads=args.threads, jax=jax.__version__)

    t0 = time.perf_counter()
    host = np.zeros((n, n), np.float32)
    host.fill(0.0)
    ref = np.empty((n, w), np.float32)
    ref.fill(0.0)
    say(way="fill the (n, n) factor and a (n, w) reference, first touch",
        seconds=round(time.perf_counter() - t0, 3))

    key = jax.random.PRNGKey(37)
    heights = args.k0 if args.k0 is not None else list(range(0, n, w))
    sweep = heights[:2] if args.sweep_k0 is None else args.sweep_k0
    view_copied = False

    def f32_panel(k0):
        x = panel(jax.random.fold_in(key, k0), (n - k0, w), jnp.float32)
        np.copyto(ref[:n - k0], np.asarray(x))
        return x, host[k0:, k0:k0 + w], ref[:n - k0]

    with cf.ThreadPoolExecutor(args.threads) as pool:
        for k0 in heights:
            x, out, want = f32_panel(k0)
            if "fetch" in args.ways:
                measure("fetch rows/8", fetch(), x, out, want, pool, args,
                        k0=k0)
                for mib in args.chunk_mib if k0 in sweep else ():
                    measure("fetch <= %d MiB" % mib, fetch(mib << 20), x,
                            out, want, pool, args, k0=k0)
                for threads in (4, 12) if k0 in sweep else ():
                    with cf.ThreadPoolExecutor(threads) as other:
                        measure("fetch <= 16 MiB", fetch(16 << 20), x, out,
                                want, other, args, k0=k0,
                                pool_threads=threads)
            if "library" in args.ways:
                measure("library", library, x, out, want, pool, args, k0=k0)
            if "pinned" in args.ways:
                if k0 == heights[0]:
                    y = jax.device_put(x, jax.sharding.SingleDeviceSharding(
                        dev, memory_kind="pinned_host"))
                    say(pinned_format=str(getattr(y, "format", None)),
                        device_format=str(getattr(x, "format", None)))
                    y.delete()
                for pieces in (1, 2, 4):
                    view_copied |= bool(measure(
                        "pinned/%d" % pieces, pinned(pieces), x, out, want,
                        pool, args, k0=k0))
            del x
        if "shapes" in args.ways:
            # the right-hand side (narrow, contiguous destination), and
            # a panel in the mixed mode's resident dtype
            both = [("fetch rows/8", fetch()), ("library", library)]
            x = panel(key, (n, args.nrhs), jnp.float32)
            rhs, want = np.zeros((n, args.nrhs), np.float32), np.asarray(x)
            for way, fn in both:
                measure(way, fn, x, rhs, want, pool, args, shape="rhs")
            k0 = heights[min(1, len(heights) - 1)]
            x = panel(key, (n - k0, w), jnp.bfloat16)
            want = np.asarray(x)
            lo = np.zeros((n, w), want.dtype)
            for way, fn in both:
                measure(way, fn, x, lo[k0:], want, pool, args, k0=k0,
                        dtype="bfloat16")
            del x, lo
        if "raw" in args.ways and view_copied:
            for k0 in heights[:2]:
                x, out, want = f32_panel(k0)
                say(way="pinned-raw", k0=k0, about_to="read the address")
                measure("pinned-raw", pinned(1, raw=True), x, out, want,
                        pool, args, k0=k0)
                del x
    say(done=True)


if __name__ == "__main__":
    main()
