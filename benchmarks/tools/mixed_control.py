#!/usr/bin/env python3
"""The three controls of a kind-`mixed` configuration's tolerance
(tools/control.py knows kinds `solve` and `serve` and could not be
edited). One JSON line per reading: the numbers `check()` compares.

    python benchmarks/tools/mixed_control.py --config <name> \
        --seeds 1 2 3 --control a b c [--n 2048] [--sound]

Control (a), host arithmetic only, needs no chip: the plain reference
(lib/plainref_mixed.py) on the inputs the cell makes from the same
seeds, factored once a seed, then refined with its residual in f32
(the reference itself), at `high` (three bfloat16 passes) and at one
bfloat16 pass: a residual below f32 stalls the refinement over limit
(a). Control (b), on the chip: the program with `MaxIterations` 0 and
no fallback, the bf16 answer alone: over limit (a) by orders. Control
(c), on the chip: the program on an ill-conditioned matrix (HPL's own:
the same uniform entries with no raised diagonal): the refinement
does not converge, the host falls back to the f32 solve, and the
answer is graded FAILED by limit (b), `fallbacks`. `--sound`: the
program as the cell calls it, for the range beside them. `--n` reads
at another size of the same law.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import gen, plainref, plainref_mixed, refcheck  # noqa: E402,E501
from benchmarks.run import load_json, load_module                # noqa: E402


def say(**kv):
    print(json.dumps(kv, default=float), flush=True)


def ill_conditioned(r, n):
    """HPL's own matrix, the f32 deployment's (hpl-gesv): the same
    uniform entries with NO raised diagonal. cond_2 grows like n (1e5
    at 16384) and partial pivoting's growth like n^(2/3): far past
    what a bf16 factor can precondition (its contraction needs
    cond(A) 2^-8 well under 1)."""
    return r.random((n, n), dtype=np.float32) - np.float32(0.5)


def program(cfg, a, b, opts=None):
    """(x on the host, iters, seconds) of st.gesv_mixed on the chip."""
    import jax
    import slate_tpu as st
    a_dev, b_dev = jax.device_put(a), jax.device_put(b)
    t0 = time.perf_counter()
    _, X, iters = st.gesv_mixed(st.Matrix(a_dev, mb=cfg["mb"]),
                                st.Matrix(b_dev, mb=cfg["mb"]), opts)
    jax.block_until_ready(X.data)
    return X.to_numpy(), int(iters), time.perf_counter() - t0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control", nargs="+", choices="abc", default=[])
    p.add_argument("--sound", action="store_true")
    p.add_argument("--n", type=int)
    args = p.parse_args(argv)
    cfg = load_json(os.path.join(ROOT, "benchmarks", "configs",
                                 args.config + ".json"))
    kind = load_module("kinds", cfg["kind"])
    n = args.n or cfg["n"]
    tol = cfg["tolerance"]
    if args.sound or set(args.control) & {"b", "c"}:
        from benchmarks.lib import cache
        from slate_tpu.core.options import Option
        from slate_tpu.resil import guard
        cache.place()       # the cell's programs, where a run left them

    def grade(label, seed, a, b, x, iters, seconds, **more):
        resid = refcheck.hpl_resid_blocked(a, x, b, n)
        nums = {"scaled_residual_max": resid,
                "fallbacks": float(iters < 0),
                "refine_sweeps_max": float(kind.sweeps_of(iters))}
        say(config=cfg["name"], n=n, seed=seed, control=label, **nums,
            over=[k for k, v in nums.items() if not v <= tol[k]],
            seconds=seconds, **more)

    for seed in args.seeds:
        a, b = kind.hplmxp_system(gen.rng(seed, "solve"), n)
        if args.sound:
            for again in (0, 1):
                grade("sound", seed, a, b, *program(cfg, a, b), call=again)
        if "a" in args.control:
            t0 = time.perf_counter()
            lu, piv = plainref_mixed.lu_bf16(a)
            t_lu = time.perf_counter() - t0
            for label, mm in (("a:f32", plainref.matmul_f32),
                              ("a:bf16x3", plainref.matmul_bf16x3),
                              ("a:bf16", plainref_mixed.matmul_bf16)):
                t0 = time.perf_counter()
                x, it, ok = plainref_mixed.refine(a, b, lu, piv, mm)
                grade(label, seed, a, b, x, it if ok else -it - 1,
                      time.perf_counter() - t0, converged=ok,
                      factor_seconds=t_lu)
        if "b" in args.control:
            grade("b:no_refinement", seed, a, b, *program(
                cfg, a, b, {Option.MaxIterations: 0,
                            Option.UseFallbackSolver: False}))
        if "c" in args.control:
            ill = ill_conditioned(gen.rng(seed, "ill"), n)
            before = guard.counts().get("resil.fallback.mixed_to_full", 0)
            x, iters, secs = program(cfg, ill, b)
            grade("c:ill_conditioned", seed, ill, b, x, iters, secs,
                  mixed_to_full=guard.counts().get(
                      "resil.fallback.mixed_to_full", 0) - before)


if __name__ == "__main__":
    sys.exit(main())
