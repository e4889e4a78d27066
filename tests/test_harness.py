"""Tester CLI, simplified API, trace, printing tests."""

import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import Side, TiledMatrix, Uplo


def test_tester_cli_quick(capsys):
    from slate_tpu.testing import tester
    rc = tester.main(["gemm", "potrf", "--dim", "64", "--type", "s,d",
                      "--nb", "32"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "All tests passed" in out
    assert "gemm" in out and "potrf" in out


def test_simplified_api(rng):
    from slate_tpu.api import simplified as s
    n = 32
    a = rng.standard_normal((n, n))
    spd = a @ a.T + n * np.eye(n)
    A = st.HermitianMatrix(Uplo.Lower, spd, mb=8)
    b = rng.standard_normal((n, 2))
    L, X = s.chol_solve(A, TiledMatrix.from_dense(b, 8))
    np.testing.assert_allclose(spd @ X.to_numpy(), b, rtol=1e-8)
    F, X2 = s.lu_solve(st.Matrix(a, mb=8), TiledMatrix.from_dense(b, 8))
    np.testing.assert_allclose(a @ X2.to_numpy(), b, rtol=1e-8)
    w = s.eig_vals(A)
    assert np.all(np.asarray(w) > 0)


def test_timers_and_trace(host_plane):
    from slate_tpu import obs
    from slate_tpu.utils import Timers, trace
    t = Timers()
    with t.phase("posv::potrf"):
        pass
    assert "posv::potrf" in t.values
    obs.clear()
    trace.on()

    def body():
        with trace.block("gemm"):
            pass
        with trace.block("potrf"):
            pass

    try:
        seen = host_plane(body, ["gemm", "potrf"])
    finally:
        trace.off()
    # the blocks are in the bus and on the profiler's timeline
    assert [e.name for e in obs.bus_events(cat="trace")] \
        == ["gemm", "potrf"]
    assert sorted(e[2] for e in seen) == ["gemm", "potrf"]
    obs.clear()


def test_print_matrix(rng, capsys):
    a = rng.standard_normal((30, 30))
    st.print_matrix("A", st.Matrix(a, mb=8))
    out = capsys.readouterr().out
    assert "A = [" in out and "..." in out
    small = rng.standard_normal((3, 3))
    s = st.utils.sprint_matrix("S", st.Matrix(small, mb=8))
    assert "..." not in s


def test_driver_phase_timers(rng):
    """Option.Timers: drivers record named phase wall times (reference
    timers["heev::he2hb"] map, heev.cc:108)."""
    import numpy as np
    import slate_tpu as st
    from slate_tpu.core.options import Option
    from slate_tpu.utils import Timers
    n = 32
    x = rng.standard_normal((n, n))
    spd = x @ x.T + n * np.eye(n)
    b = rng.standard_normal((n, 2))
    tm = Timers()
    st.posv(st.HermitianMatrix(st.Uplo.Lower, spd, mb=8),
            st.TiledMatrix.from_dense(b, 8), {Option.Timers: tm})
    assert tm["posv::potrf"] > 0 and tm["posv::potrs"] > 0
    st.gesv(st.Matrix(x + n * np.eye(n), mb=8),
            st.TiledMatrix.from_dense(b, 8), {Option.Timers: tm})
    assert "gesv::getrf" in tm.values and "gesv::getrs" in tm.values


def test_print_verbosity_levels(rng):
    """Reference print.cc verbosity ladder (enums.hh:79-84): 0 none,
    1 metadata, 2 corners, 3 tile corners, 4 full."""
    import slate_tpu as st
    from slate_tpu.core.options import Option
    from slate_tpu.utils.printing import sprint_matrix

    a = rng.standard_normal((12, 12))
    A = st.Matrix(a, mb=4)
    assert sprint_matrix("A", A, verbose=0) == ""
    meta = sprint_matrix("A", A, verbose=1)
    assert "12x12" in meta and "tiles 4x4" in meta
    corners = sprint_matrix("A", A, verbose=2, edgeitems=2)
    assert "..." in corners
    tiles = sprint_matrix("A", A, verbose=3)
    assert "tile row 2" in tiles
    full = sprint_matrix("A", A, verbose=4)
    assert full.count("\n") >= 12 and "..." not in full
    # options-driven configuration (Option.Print* keys)
    via_opts = sprint_matrix("A", A, opts={Option.PrintVerbose: 4})
    assert via_opts == full


def test_condest_early_exit(rng):
    """norm1est stops on convergence (repeated index / no increase)
    and still lands within the usual factor-of-n bound."""
    import slate_tpu as st
    from slate_tpu import Norm, TiledMatrix

    n = 40
    a = rng.standard_normal((n, n)) + 4 * np.eye(n)
    F = st.getrf(TiledMatrix.from_dense(a, 8))
    anorm = st.norm(Norm.One, TiledMatrix.from_dense(a, 8))
    rcond = float(st.gecondest(Norm.One, F, anorm))
    true = 1.0 / (np.linalg.norm(a, 1)
                  * np.linalg.norm(np.linalg.inv(a), 1))
    assert 0.1 * true <= rcond <= 10 * true


def test_print_tile_corners_crop_padding(rng):
    """verbose=3 must show logical tile corners, never padding zeros
    (review regression)."""
    import slate_tpu as st
    from slate_tpu.utils.printing import sprint_matrix

    a = np.arange(100.0).reshape(10, 10)
    out = sprint_matrix("A", st.Matrix(a, mb=4), verbose=3)
    assert "99.0000" in out            # true bottom-right corner
    assert "tile row 2" in out


@pytest.mark.parametrize("placed", [None, "/nonexistent/placed_cache"])
def test_compile_cache_placement(placed):
    """utils/compile_cache.enable(): with JAX_COMPILATION_CACHE_DIR set
    nothing is configured in code (JAX reads the variable); otherwise
    the cache is placed at the fixed <checkout>/.jax_cache. Run in a
    child pinned to the CPU: the helper is for the mains and must
    never place a cache inside the pytest process."""
    import os
    import pathlib
    import subprocess
    import sys
    root = pathlib.Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = placed
    code = ("import jax; from slate_tpu.utils import compile_cache; "
            "print(compile_cache.enable()); "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-400:]
    want = placed or str(root / ".jax_cache")
    assert out.stdout.split() == [want, want]
