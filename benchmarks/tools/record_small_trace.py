#!/usr/bin/env python3
"""Record the small xplane that benchmarks/tests keeps (run once on the
chip): three launches of one program with a loop in it and two of
another, 20 ms of idle between launches. Writes
chiprun_out/small.xplane.pb and prints what lib/reduce_trace.describe
sees in it."""

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import reduce_trace
    from benchmarks.lib.tracer import Tracer

    @jax.jit
    def looped(x):
        return jax.lax.fori_loop(0, 4, lambda i, y: jnp.tanh(y @ y), x)

    @jax.jit
    def plain(x):
        return (x @ x).sum()

    x = jnp.ones((512, 512), jnp.float32)
    looped(x).block_until_ready()
    plain(x).block_until_ready()
    tr = Tracer(os.path.join(ROOT, ".bench_trace"))
    tr.start()
    for i in range(3):
        looped(x).block_until_ready()
        time.sleep(0.02)
        if i < 2:
            plain(x).block_until_ready()
            time.sleep(0.02)
    tr.stop()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    shutil.copy(tr.xplane(), os.path.join(out, "small.xplane.pb"))
    print(json.dumps({"bytes": os.path.getsize(tr.xplane()),
                      "window_s": tr.t1 - tr.t0,
                      "reduced": tr.reduce()}))
    for row in reduce_trace.describe(tr.xplane()):
        print(json.dumps(row))


if __name__ == "__main__":
    main()
