"""Tests of the benchmark's own yardstick, on the CPU, by hand:

    python -m pytest benchmarks/tests -q

(outside tier-1's tests/). The trace reduction on a small xplane
recorded on the chip, the operation counts against hand counts, the
seeded schedule, every workload's files found by name, run.py
--rehearse end to end for both kinds, the lower-precision control
failing the tolerances, and a run with the timed path broken
underneath coming out not correct.
"""

import json
import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run                     # noqa: E402
from benchmarks.lib import (gen, loadgen, opcount, peaks,   # noqa: E402
                            plainref, reduce_trace, refcheck)

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


# -- the trace reduction ------------------------------------------------------

def test_union_and_self_times_by_hand():
    total, merged = reduce_trace.union_ns([(0, 10), (5, 20), (30, 40)])
    assert total == 30 and merged == [[0, 20], [30, 40]]
    # a loop of 100 ns holding two bodies of 30 ns: self time 40 ns
    st = reduce_trace.self_times([(0, 100, "while"), (10, 40, "dot"),
                                  (50, 80, "dot"), (200, 210, "copy")])
    assert st == pytest.approx({"while": 40e-9, "dot": 60e-9,
                                "copy": 10e-9})


def test_reduce_planes_by_hand():
    lines = {reduce_trace.MODULES: [(0, 100, "jit_a"), (300, 400, "jit_b")],
             reduce_trace.OPS: [(0, 100, "while"), (10, 40, "dot"),
                                (300, 400, "fusion")]}
    red = reduce_trace.reduce_planes(
        [("/device:TPU:0", lines), ("/host:CPU", {"python": [(0, 9, "x")]})],
        window_s=1e-6)
    assert red["module_launches"] == 2 and red["planes"] == 1
    assert red["busy_s"] == pytest.approx(200e-9)
    assert red["idle_gaps"] == [["before jit_b", pytest.approx(200e-9)]]
    assert dict(map(tuple, red["device_ops"]))["while"] == \
        pytest.approx(70e-9)
    assert reduce_trace.reduce_planes([("/host:CPU", {})], 1.0) is None


def test_reduce_recorded_xplane():
    """tools/record_small_trace.py on the chip: three launches of a
    program with a loop and two of a plain one, 20 ms sleeps between."""
    path = os.path.join(HERE, "data", "small.xplane.pb")
    red = reduce_trace.reduce_file(path, window_s=0.2)
    assert red["planes"] == 1 and red["module_launches"] == 5
    assert 0.0 < red["busy_s"] < 0.1            # idle through the sleeps
    names = [g[0] for g in red["idle_gaps"]]
    assert any("looped" in n for n in names) \
        and any("plain" in n for n in names)
    run = {"trace": red, "records": {"slice_solves": 1}}
    idle = bench_run.load_module("layer_metrics", "idle_share.solve")
    assert 50.0 < idle.compute(run) < 100.0
    assert bench_run.load_module("layer_metrics", "launches_per_solve") \
        .compute(run) == 5


# -- counts, peaks, generators ------------------------------------------------

def test_opcount_by_hand():
    assert opcount.gesv(3, 2) == (2 * 27 / 3 + 2 * 9 * 2, 4.0 * (9 + 12))
    assert opcount.posv(3, 2) == (27 / 3 + 2 * 9 * 2, 4.0 * (9 + 12))
    f, b = opcount.gesv(16384, 64)
    least, bound = opcount.roofline_seconds(f, b, peaks.peak("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(0.015058, rel=1e-3)
    with pytest.raises(KeyError):
        peaks.peak("TPU v9")


def test_seeded_inputs_repeat_and_keep_their_multiset():
    big = 3_000_000_019                         # over 2**31, as the driver's
    d1 = loadgen.schedule(gen.rng(big, "arrivals"), 400, 100.0)
    d2 = loadgen.schedule(gen.rng(big, "arrivals"), 400, 100.0)
    d3 = loadgen.schedule(gen.rng(big + 1, "arrivals"), 400, 100.0)
    assert np.array_equal(d1, d2) and not np.array_equal(d1, d3)
    assert d1[-1] == pytest.approx(d3[-1]) and 3.8 < d1[-1] < 4.2
    assert np.allclose(np.sort(np.diff(d1, prepend=0)),
                       np.sort(np.diff(d3, prepend=0)))
    sizes = gen.uniform_sizes(300, 64, 1024)
    assert sizes == sorted(sizes) and sizes[0] == 65 and sizes[-1] == 1022
    assert sum(sizes) / 300 == pytest.approx(544, abs=1)
    assert sum(n > 512 for n in sizes) == 160        # 53% in the top bucket
    a = gen.spd_gram(gen.rng(big, "x"), 96)
    assert np.array_equal(a, a.T) and np.linalg.eigvalsh(
        a.astype(np.float64))[0] > 0.5
    assert np.abs(a - np.diag(np.diag(a))).sum(axis=1).max() > a.max()


def test_longest_stall_by_hand():
    """Five requests; nothing completes from 1.0 s to 4.0 s while two
    are open; a quiet second with nothing open is not a stall."""
    class Loop:
        due = [0.0, 0.9, 2.0, 6.0, 6.1]
        sent = [0.0, 0.9, 2.0, 6.0, 6.1]
        finished = [1.0, 4.0, 4.1, 6.05, None]
        clock = [(0.5, 0.10), (0.99, 0.20), (4.02, 0.45), (5.0, 0.50)]
        collections = [(1.5, 0.25), (5.0, 1.0)]
    st = loadgen.longest_stall(Loop)
    assert st["gap_s"] == pytest.approx(3.0) and st["at_s"] == 1.0
    assert st["open"] == 2 and st["open_at_end"] == 2
    assert st["sender_late_s"] == 0.0 and st["gc_s"] == 0.25
    assert st["cpu_s"] == pytest.approx(0.25)


def test_program_control_lowers_highest_products():
    import importlib.util
    import jax
    import jax.numpy as jnp
    from jax._src.lax import lax as _lax
    spec = importlib.util.spec_from_file_location(
        "control", os.path.join(ROOT, "benchmarks", "tools", "control.py"))
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    hi = jax.lax.Precision.HIGHEST
    x = jnp.ones((8, 8), jnp.float32)

    def prods(a):
        return (jnp.matmul(a, a, precision=hi)
                + jnp.einsum("ij,jk->ik", a, a, precision=hi)
                + jax.vmap(lambda r: jnp.dot(r, a, precision=hi))(a))
    # another shape the second time: jnp's own jitted products
    # remember what they traced (the tool lowers a fresh process)
    assert str(jax.make_jaxpr(prods)(x)).count("Precision.HIGHEST,") == 3
    try:
        control.lower_program_products()
        text = str(jax.make_jaxpr(prods)(jnp.ones((16, 16), jnp.float32)))
    finally:
        del _lax.dot_general_p.bind
    assert "HIGHEST" not in text and text.count("Precision.HIGH,") == 3


# -- data-driven: every name finds its file -----------------------------------

def test_every_workload_finds_its_files():
    for w in BENCH["workloads"]:
        cell, cfg, mix = bench_run.resolve(BENCH, w["name"], False)
        kind = bench_run.load_module("kinds", cfg["kind"])
        assert callable(kind.setup)
        for key in ("source", "reduced", "assumed", "tolerance", "rehearsal"):
            assert key in cfg, (w["name"], key)
        assert cfg["tolerance"]["reason"]
        assert isinstance(mix, dict)
    for m in BENCH["per_layer"]:
        assert callable(bench_run.load_module(
            "layer_metrics", m["name"]).compute)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])


def test_run_py_holds_no_cell_name():
    src = open(os.path.join(ROOT, "benchmarks", "run.py")).read()
    for w in BENCH["workloads"]:
        assert w["name"] not in src and w["config"] not in src


# -- run.py end to end on the CPU (--rehearse is never a number) --------------

def _rehearse(workload, trace, seconds="1.5"):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", "3000000019", "--seconds",
         seconds, "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_prints_the_contract_line(workload):
    last = _rehearse(workload, 0)
    assert set(last) == CONTRACT_KEYS
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    want = {m["name"] for m in bench_run.wanted(BENCH["end_to_end"],
                                                workload)}
    assert set(last["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in last["metrics"].values())
    traced = _rehearse(workload, 1)
    assert set(traced) - {"breakdown"} == CONTRACT_KEYS
    names = {m["name"] for m in bench_run.wanted(BENCH["per_layer"],
                                                 workload)}
    assert set(traced["metrics"]) <= names      # no device plane on a CPU


def test_no_tpu_no_result(capsys):
    rc = bench_run.main(["--workload", BENCH["workloads"][0]["name"],
                         "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0 and capsys.readouterr().out.strip() == ""


# -- the tolerance's control, and a broken timed path -------------------------

def _serve_cfg():
    return bench_run.resolve(BENCH, "serve-steady", False)[1]


def test_control_lower_precision_fails_the_served_tolerance():
    """The plain reference in the program's place, products at `high`
    (three bfloat16 passes), on problems of the cell's own sizes: its
    gesv residual breaks the configuration's own limit; in f32 it
    holds it."""
    cfg = _serve_cfg()
    lim = cfg["tolerance"]["scaled_residual_max.gesv"]
    r = gen.rng(5, "pool")
    worst = {"f32": 0.0, "bf16x3": 0.0}
    for n in (96, 192, 640):
        a, b = gen.general(r, n), gen.rhs(r, n, cfg["nrhs"])
        for label, mm in (("f32", plainref.matmul_f32),
                          ("bf16x3", plainref.matmul_bf16x3)):
            res = refcheck.hpl_resid(a.astype(np.float64),
                                     plainref.lu_solve(a, b, mm),
                                     b.astype(np.float64), n)
            worst[label] = max(worst[label], res)
    assert worst["f32"] <= lim < worst["bf16x3"]


@pytest.mark.parametrize("routine,solver", [("gesv", plainref.lu_solve),
                                            ("posv", plainref.chol_solve)])
def test_control_separates_at_test_size(routine, solver):
    """At a size a test holds the limits of the large cells do not
    apply (the scaled residual falls with n); what carries over is
    that `high` products read several times the f32 reading."""
    r = gen.rng(9, "solve")
    n = 768
    a = gen.general(r, n) if routine == "gesv" else gen.spd_gram(r, n)
    b = gen.rhs(r, n, 8)
    rows = refcheck.factor_sample(n, gen.rng(9, "sample"))
    got = {}
    for label, mm in (("f32", plainref.matmul_f32),
                      ("bf16x3", plainref.matmul_bf16x3)):
        if routine == "gesv":
            got[label] = refcheck.hpl_resid_blocked(a, solver(a, b, mm), b,
                                                    n, rows=256)
        else:
            fac = []
            solver(a, b, mm, factor=fac)
            got[label] = refcheck.factor_resid(a[np.ix_(rows, rows)],
                                               fac[0][rows], rows)
    assert got["bf16x3"] > 3.0 * got["f32"]


@pytest.mark.parametrize("workload,patch", [
    ("incore-gesv", "gesv"), ("stream-posv", "posv_ooc"),
    ("serve-steady", "serve")])
def test_broken_timed_path_is_not_correct(workload, patch, monkeypatch,
                                          capsys):
    """Everything of a run but the look for a chip, with each answer
    scaled by 1.01 where it is produced: `correct` comes out false."""
    import slate_tpu as st
    from slate_tpu.batch import queue as bq
    from slate_tpu.linalg import ooc

    def nudge(x):
        return np.asarray(x) * np.float32(1.01)

    if patch == "gesv":
        real = st.gesv

        def broken(A, B, *a, **k):
            F, X = real(A, B, *a, **k)
            import dataclasses
            return F, dataclasses.replace(X, data=X.data * 1.01)
        monkeypatch.setattr(st, "gesv", broken)
    elif patch == "posv_ooc":
        real = ooc.posv_ooc

        def broken(*a, **k):
            L, X = real(*a, **k)
            return L, nudge(X)
        monkeypatch.setattr(ooc, "posv_ooc", broken)
    else:
        real = bq._crop

        def broken(op, outs, m, n, nrhs):
            return nudge(real(op, outs, m, n, nrhs))
        monkeypatch.setattr(bq, "_crop", broken)
    rc = bench_run.main(["--workload", workload, "--seed", "77",
                         "--seconds", "1", "--trace", "0", "--rehearse"])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and last["correct"] is False
