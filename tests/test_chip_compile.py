"""Compile-only checks against a DESCRIBED TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a topology that is
described, not attached (guide on-chip-measurement §2, rehearsal 3).
This catches what interpret mode cannot: Mosaic alignment refusals and
scoped-VMEM overruns of the Pallas kernels, and HBM overruns of the
streamed update program. Nothing runs — a pass here is not a chip run.

`_on_tpu()` sees the CPU under pytest, so the jitted ``_*_pallas(...,
interp=False)`` functions are lowered directly with ShapeDtypeStructs
placed on the described device. The topology is described only inside
a fixture: one process at a time may load libtpu, so nothing may touch
it at import or collection time, and these cases stay in ONE file.
"""

import functools
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: one v5e chip's HBM (bytes); memory_analysis must fit under it
V5E_HBM = 16 << 30


#: the four described chips, for the programs of a grid (set by the
#: fixture, which alone may describe the topology)
_DESCRIBED = {}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no libtpu / lock held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to a persistent
    # cache but cannot be read back without the chip: keep it off
    from jax.experimental.compilation_cache import compilation_cache
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # conftest turns x64 on for the CPU tier; the chip runs without
    # it, and so do these compiles (the Mosaic lowering recurses
    # without end on x64 weak-typed scalars)
    jax.config.update("jax_enable_x64", False)
    _DESCRIBED["devices"] = topo.devices
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])
    compilation_cache.reset_cache()


def _compile(fn, dev, *shapes, kernel=True, limit_s=60.0, **static):
    """Lower `fn` on the described device and compile; returns the
    compiled program. Fails when the compile takes more than `limit_s`
    processor seconds of this process, or when a `kernel` program
    holds no Mosaic custom call. Processor seconds, not the wall: the
    compile is this worker's only work meanwhile, and alone its wall
    is its processor time (26.3 against 25.9 s for the widest LU
    panel, sandbox, PR 39), but beside five other workers the same
    compile took 61-69 s of wall and failed a limit of 60 that a slow
    compile, not a busy machine, is meant to fail."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=dev) for s, d in shapes]
    t0 = time.process_time()
    compiled = fn.lower(*args, **static).compile()
    took = time.process_time() - t0
    assert took < limit_s, \
        f"compile took {took:.1f} processor seconds (limit {limit_s})"
    assert not kernel or "tpu_custom_call" in compiled.as_text()
    return compiled


def _as_on_the_chip(monkeypatch):
    """The routing asks `jax.default_backend()` and the kernels' gates
    `pallas_kernels._on_tpu()`, which both see the CPU here: steer
    them to the chip's answers, so that the program compiled is the
    one the chip runs."""
    from slate_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)


#: the serve phase of chip_smoke.py drives these shapes
_B, _N, _K = 8, 1024, 4


def _ragged(name, **kw):
    from slate_tpu.ops import pallas_kernels as pk
    return jax.jit(functools.partial(getattr(pk, name), interp=False,
                                     **kw))


@pytest.mark.parametrize("kernel", ["potrf", "getrf"])
def test_ragged_factor_kernels_compile(one_chip, kernel):
    from slate_tpu.ops import pallas_kernels as pk
    kw = {"blk": pk.RAGGED_BLK} if kernel == "potrf" \
        else {"ib": pk.RAGGED_BLK}
    fn = _ragged("_ragged_%s_pallas" % kernel, B=_B, N=_N, **kw)
    _compile(fn, one_chip, ((_B,), jnp.int32),
             ((_B, _N, _N), jnp.float32))


@pytest.mark.parametrize("upper,trans,unit", [
    (False, False, False),      # posv forward sweep
    (False, True, False),       # posv backward sweep (L^T)
    (False, False, True),       # gesv unit-lower sweep
    (True, False, False),       # gesv upper sweep
])
def test_ragged_trsm_compiles(one_chip, upper, trans, unit):
    from slate_tpu.ops import pallas_kernels as pk
    fn = _ragged("_ragged_trsm_pallas", B=_B, N=_N, K=_K,
                 blk=pk.RAGGED_BLK, upper=upper, trans=trans, unit=unit)
    _compile(fn, one_chip, ((_B,), jnp.int32),
             ((_B, _N, _N), jnp.float32), ((_B, _N, _K), jnp.float32))


def test_ragged_ceiling_off_the_lane_tile_compiles(one_chip):
    """A ragged ceiling is a multiple of lcm(align, blk) = 32, not of
    the 128-lane tile: the kernels pad to the tile themselves."""
    from slate_tpu.ops import pallas_kernels as pk
    n = 928
    assert n % pk.RAGGED_BLK == 0 and n % 128
    fn = _ragged("_ragged_potrf_pallas", B=_B, N=n, blk=pk.RAGGED_BLK)
    _compile(fn, one_chip, ((_B,), jnp.int32),
             ((_B, n, n), jnp.float32))


def test_lu_panel_bf16_compiles(one_chip):
    """The cold route for bf16 LU panels (linalg/lu.py _lu_panel)."""
    from slate_tpu.ops import pallas_kernels as pk
    m, w = 2048, 128
    _compile(pk._lu_panel_pallas, one_chip, ((m, w), jnp.bfloat16),
             m=m, w=w, interp=False)


@pytest.mark.parametrize("m,w", [
    (2048, 256), (4096, 128), (4096, 32)])
def test_lu_panel_rec_compiles_at_its_gate(one_chip, m, w):
    """The gate and the compiler agree: the largest single-dispatch
    shapes `_rec_shape_reason` admits (element budget at the widest
    panel, and the height cap at a full and a sub-lane-tile width)
    compile in under a minute."""
    from slate_tpu.ops import pallas_kernels as pk
    assert w <= pk.LU_REC_MAX_W and m <= pk.LU_REC_MAX_M
    assert m * w <= pk.LU_REC_MAX_ELEMS
    assert pk._rec_shape_reason(m, w, jnp.float32) is None
    _compile(pk._lu_panel_rec_pallas, one_chip, ((m, w), jnp.float32),
             m=m, w=w, ib=pk.LU_REC_IB, interp=False)


@pytest.mark.parametrize("ib", [128, 64])
@pytest.mark.parametrize("m", [65536, 49152, 36864, 32768, 16384, 9216])
def test_lu_block_columns_compiles_at_the_cells_heights(one_chip, m, ib,
                                                        monkeypatch):
    """The VMEM-resident column recurrence of `lu.lu_panel_blocked`
    (PR 50) at the block heights the cells run: `grid-gesv`'s first
    two stages, `stream-gesv`'s two tall rungs, `incore-gesv-mixed`'s
    first and last tall panel; at the base block the route picks on
    the chip (128: a (136, m) buffer, 26.7 MB at the tallest) and at
    the XLA loop's 64 (72 sublanes, 14.2 MB). Either is over the
    scoped default, so the kernel asks for its own limit. And at the
    gate itself: 65536 rows at 128 is `LU_COLS_MAX_BYTES`, the
    tallest block the route hands the kernel (`grid-gesv`'s source
    size before its one reduction). Seconds each (2.6 s the tallest,
    sandbox, PR 50)."""
    from slate_tpu.linalg import lu
    from slate_tpu.ops import pallas_kernels as pk
    _as_on_the_chip(monkeypatch)
    assert pk._cols_rows(ib) * m * 4 <= pk.LU_COLS_MAX_BYTES
    assert lu._column_kernel(128, 2 * 65536, jnp.float32) == "xla"
    assert lu._blocked_ib(512, m, jnp.float32) == 128
    assert lu._column_kernel(ib, m, jnp.float32) == "vmem"
    _compile(pk._lu_block_columns_pallas, one_chip,
             ((pk._cols_rows(ib), m), jnp.float32), ((), jnp.int32),
             ib=ib, m=m, interp=False)


@pytest.mark.parametrize("m", [49152, 65536])
def test_blocked_panel_is_not_moved_around_the_column_kernel(one_chip, m,
                                                            monkeypatch):
    """`grid-gesv`'s tallest panel, (49152, 512) at the base block of
    128: the compiler keeps the panel the block loop carries in VMEM
    (100.7 MB of 128 MiB) and the column kernel asks for its block
    beside it (26.7 MB). With more room than it needs (16 MiB, PR 50's
    first form) the panel was moved out and back at every block, two
    100 MB copies: the only whole-panel moves are the program's first
    and last. At the gate's 65536 rows the panel (134 MB) is no longer
    kept in VMEM and the program still compiles around the 35.7 MB
    block (2.2 s, no whole-panel move, sandbox, PR 50)."""
    from slate_tpu.linalg import lu
    _as_on_the_chip(monkeypatch)
    w = 512
    ib = lu._blocked_ib(w, m, jnp.float32)
    text = _compile(jax.jit(lambda a: lu.lu_panel_blocked(a, ib)), one_chip,
                    ((m, w), jnp.float32)).as_text()
    moves = [line for line in text.splitlines()
             if " copy-start(" in line and "= (f32[%d,%d]" % (m, w) in line]
    assert len(moves) <= 2, len(moves)


def test_givens_apply_compiles(one_chip):
    from slate_tpu.ops import pallas_kernels as pk
    rows = n = 1024
    blk = pk.GIVENS_CHAIN_BLK
    assert pk._chain_shape_ok(rows, n, blk)
    rb = pk._chain_rb(rows, n, blk)
    _compile(pk._givens_apply_pallas, one_chip,
             ((rows, n), jnp.float32),
             ((n // blk, 2 * blk, 2 * blk), jnp.float32),
             rows=rows, n=n, blk=blk, rb=rb, interp=False)


def test_stream_update_fits_the_device(one_chip):
    """The streamed Cholesky's visit kernel at chip_smoke.py's panel
    shape: a (32768, 4096) target updated by a 4096-wide factor
    panel, arguments + output + temporaries under one chip's HBM."""
    from slate_tpu.linalg import ooc
    n, w = 32768, 4096
    compiled = _compile(ooc._panel_apply, one_chip,
                        ((n, w), jnp.float32), ((n, w), jnp.float32),
                        w=w, kernel=False)
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert 0 < total < V5E_HBM, total


def test_getrf_carry_finish_compiles_at_the_cells_shape(one_chip):
    """The carry form's finish (PR 30) at incore-gesv's shape, n=8192
    in 8 panels of 1024: eight panel gathers, the assembly and its
    strip overlays as ONE program, whose temporaries stay a small
    part of the matrix (the overlays update in place; issued one at a
    time each copied the whole 256 MiB)."""
    from slate_tpu.linalg import lu
    n, nb = 8192, 1024
    nt = n // nb

    def shapes(f):
        return [jax.ShapeDtypeStruct(*f(k), sharding=one_chip)
                for k in range(nt)]

    compiled = lu._carry_finish.lower(
        shapes(lambda k: ((n - k * nb, nb), jnp.float32)),
        shapes(lambda k: ((n - k * nb,), jnp.int32)),
        shapes(lambda k: ((nb, n - (k + 1) * nb), jnp.float32))[:-1],
        shapes(lambda k: ((nb,), jnp.int32)),
        nb=nb, kmax=n, M=n, N=n).compile()
    ma = compiled.memory_analysis()
    assert ma.output_size_in_bytes >= n * n * 4
    assert ma.temp_size_in_bytes < n * n * 4 // 8, ma.temp_size_in_bytes


@pytest.mark.parametrize("step", ["put", "leaf", "sign", "basis"])
def test_heev_steps_compile_at_the_cells_workspaces(one_chip, step):
    """A step of the spectral divide and conquer's agenda (PR 33) at
    incore-heev's workspaces, n=8192, in the ladder's second bucket.
    `dc_put` and `dc_leaf` take the donated (2n, n) and (n, 2n)
    workspaces and must write them in place: a conditional over them
    made the compiler copy one, 0.54 GB a launch. `dc_sign` holds ONE
    Cholesky form and `dc_basis` ONE QR (the parent held two of each):
    every bucket's programs stay a small part of what the compile
    cache takes an entry."""
    from slate_tpu.linalg import spectral_dc as dc
    n, B, leaf = 8192, 384, 256
    one = jnp.float32

    def S(*shape, dtype=one):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ws = (S(2 * n, n), S(n, 2 * n), S(2, dtype=jnp.int32))
    sq, flags = S(B, B), S(4, dtype=jnp.int32)
    if step == "put":
        compiled = dc._programs(B)["put"].lower(
            *ws, sq, sq, flags).compile()
    elif step == "leaf":
        compiled = dc._programs(leaf)["leaf"].lower(*ws, B=leaf).compile()
    elif step == "sign":
        compiled = dc._programs(B)["sign"].lower(
            sq, S(dtype=jnp.int32), S(dtype=jnp.bool_),
            S(dtype=jnp.bool_), None).compile()
    else:
        compiled = dc._programs(B)["basis"].lower(
            sq, sq, S(dtype=jnp.int32), flags).compile()
    ma = compiled.memory_analysis()
    if step in ("put", "leaf"):
        assert ma.output_size_in_bytes >= 2 * n * 2 * n * 4
        assert ma.temp_size_in_bytes < n * n * 4 // 8, ma.temp_size_in_bytes
    else:
        # 4.7 and 4.3 MB of code (sandbox, PR 33); two copies of the
        # Cholesky form or of the QR are 8 and more
        assert ma.generated_code_size_in_bytes < 6 << 20, \
            ma.generated_code_size_in_bytes


@pytest.mark.parametrize("program", ["polar", "form", "compose"])
def test_svd_programs_compile_at_the_cells_size(one_chip, program):
    """The three programs `st.svd` adds in front of and behind the
    eigensolver's agenda at incore-svd's size, n=8192 (PR 39). The
    polar is NOT a program of the SVD's own: it is `dc_sign` at the
    root's bucket, the executable `incore-heev` compiled, with its
    traced `general` flag set; it still holds ONE Cholesky form (a
    second copy of the Gram product, the factorization and the two
    solves under the flag would double its 41 MB cache entry) and fits
    the chip beside the solve's other arrays. The form and the compose
    are one product each, and the compose writes U and Vh into the
    donated workspaces."""
    import importlib
    from slate_tpu.linalg import spectral_dc as dc
    svd = importlib.import_module("slate_tpu.linalg.svd")
    n = 8192
    sq, flag = ((n, n), jnp.float32), ((), jnp.bool_)
    if program == "polar":
        # 343 s beside another compile (sandbox, PR 39)
        ma = _compile(dc._programs(n)["sign"], one_chip, sq,
                      ((), jnp.int32), flag, flag, ((), jnp.int32),
                      kernel=False, limit_s=1500.0).memory_analysis()
        # 304.0 MB of code and 2.887 GB of temporaries, the parent's
        # numbers to the megabyte; two Cholesky forms are 600 and more
        assert ma.generated_code_size_in_bytes < 340e6, \
            ma.generated_code_size_in_bytes
        assert ma.temp_size_in_bytes < 3.2e9, ma.temp_size_in_bytes
        # A, U_p and H, the eigensolver's two workspaces, this program
        assert (3 * n * n * 4 + 2 * 2 * n * n * 4 + ma.temp_size_in_bytes
                + ma.generated_code_size_in_bytes) < V5E_HBM
        return
    if program == "form":
        compiled = _compile(svd._svd_form, one_chip, sq, sq, kernel=False)
    else:
        compiled = _compile(svd._svd_compose_both, one_chip,
                            ((n,), jnp.float32), sq, sq, kernel=False)
    ma = compiled.memory_analysis()
    # 1.2 MB of code each (sandbox, PR 39); the two together are under
    # 2 MiB of compile cache
    assert ma.generated_code_size_in_bytes < 4 << 20, \
        ma.generated_code_size_in_bytes
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total <= 6 * n * n * 4 + (1 << 20), total
    if program == "compose":
        # U and Vh take the donated buffers: no third n x n output
        assert ma.alias_size_in_bytes >= 2 * n * n * 4, \
            ma.alias_size_in_bytes


@pytest.mark.parametrize("program", ["panel", "solve0", "sweeps", "update"])
def test_mixed_programs_compile_at_the_cells_size(one_chip, program,
                                                  monkeypatch):
    """The programs PR 42 adds for `incore-gesv-mixed`, n=16384 with
    a bf16 factor in panels of 1024: the tall panel by
    `lu_panel_blocked` (XLA's LU refuses 16384 rows), the first lo
    solve and the refinement (XLA's TriangularSolve on the whole
    factor kept this compiler over five minutes for one of them;
    `refine.tri_sweep` is O(1) in n), the first step's one-pass
    update. Each in seconds, its temporaries a small part of a chip."""
    from slate_tpu.core.methods import MethodLUPanel
    from slate_tpu.linalg import lu, refine
    _as_on_the_chip(monkeypatch)
    n, nb = 16384, 1024
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    fac = ((n, n), bf), ((n,), i32)

    def solve0(lu_data, perm, b):
        return refine._ir_solve0(lu._lo_getrs, (512, None),
                                 (lu_data, perm), b)

    def sweeps(lu_data, perm, a, b, x):
        return refine._ir_sweeps(lu._lo_getrs, (512, None),
                                 (lu_data, perm), a, b, x, 30)

    fn, shapes, static = {
        "panel": (lu._carry_panel_lo, [((n, n), bf)],
                  {"w": nb, "route": MethodLUPanel.Blocked}),
        "solve0": (jax.jit(solve0), [*fac, ((n, 1), f32)], {}),
        "sweeps": (jax.jit(sweeps), [*fac, ((n, n), f32), ((n, 1), f32),
                                     ((n, 1), f32)], {}),
        "update": (lu._carry_update, [((n, nb), bf), ((n, n - nb), bf)], {}),
    }[program]
    compiled = _compile(fn, one_chip, *shapes, kernel=program == "panel",
                        limit_s=60.0, **static)
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 1 << 30, ma.temp_size_in_bytes
    if program == "update":
        # the trailing matrix is written back in bf16
        assert "bf16[15360,15360]" in compiled.as_text()
        assert ma.output_size_in_bytes < 2 * n * n + (1 << 20)


#: `_lu_panel_factor`'s temporaries at the cell's (32768, 4096) panel,
#: by rung (sandbox, compiled for a described v5e, PR 48: 926, 451
#: and 165 MB; the fori form it replaced held 0.83 GB at every k;
#: PR 50, with the column kernel and its base block of 128: 977, 449
#: and 165 MB, under the same limits)
_RUNG_TEMPS = {32768: 1_100 << 20, 16384: 500 << 20, 8192: 200 << 20}


@pytest.mark.parametrize("height", sorted(_RUNG_TEMPS, reverse=True))
def test_stream_lu_panel_compiles_at_each_rung(one_chip, height,
                                               monkeypatch):
    """The partial stream's panel factor of `stream-gesv` at its whole
    (32768, 4096) panel, one program a rung of `ooc._lu_panel_height`'s
    ladder (PR 48): above the native LU's height the carry form with
    `lu_panel_blocked` panels (XLA's LU refuses those rows; since PR
    50 their column recurrence is the Mosaic call), at 8192 rows the
    native LU. The routing asks the backend, which is the CPU here:
    `_as_on_the_chip`. Each compiles in a quarter of a minute of wall (66-181 processor
    seconds), and its temporaries are held to what was read."""
    from slate_tpu.linalg import ooc
    _as_on_the_chip(monkeypatch)
    n, w = 32768, 4096
    assert ooc._lu_panel_height(n, height, jnp.float32) == height
    assert ooc._lu_panel_height(n, height + 1, jnp.float32) == \
        min(2 * height, n)
    compiled = _compile(ooc._lu_panel_factor, one_chip,
                        ((n, w), jnp.float32), ((), jnp.int32),
                        kernel=False, limit_s=400.0, nb=1024, height=height)
    ma = compiled.memory_analysis()
    print("rung %d: %d bytes of temporaries, %d of code" % (
        height, ma.temp_size_in_bytes, ma.generated_code_size_in_bytes))
    assert ma.temp_size_in_bytes < _RUNG_TEMPS[height], \
        ma.temp_size_in_bytes
    assert ma.output_size_in_bytes < n * w * 4 + (1 << 20)
    text = compiled.as_text()
    assert ("LuDecompositionBlock" in text) == (height == 8192)
    assert ("tpu_custom_call" in text) == (height > 8192)


@pytest.mark.parametrize("program", ["chunks", "rows", "col"])
def test_stream_lu_programs_compile_at_the_cells_height(one_chip, program,
                                                        monkeypatch):
    """The streamed LU of `stream-gesv` (PR 46) at its 32768 rows: the
    tournament's chunk nomination at the stream's four chunks
    of 8192 rows, which the batched native LU cannot take
    (`ca._chunk_pivot_rows` runs them one at a time there). The
    routing asks `jax.default_backend()`, which sees the CPU here: it
    is steered to the chip's answer. And the two programs through
    which the partial stream moves rows on the chip (PR 47) at the
    cell's whole panel, (32768, 4096): the row gather and the
    factored panel put together as one column; neither keeps a
    second panel of temporaries beside its result. (The partial
    stream's panel factor: the test above.)"""
    from slate_tpu.linalg import ca, ooc
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, w = 32768, 1024
    f32, i32 = jnp.float32, jnp.int32
    fn, shapes, static = {
        "chunks": (jax.jit(ca._chunk_pivot_rows),
                   [((4, n // 4, w), f32)], {}),
        "rows": (ooc._lu_rows, [((n, 4 * w), f32), ((n,), i32)], {}),
        "col": (ooc._lu_col, [((n, 4 * w), f32), ((n, 4 * w), f32),
                              ((), i32)], {}),
    }[program]
    # the chunk nomination's four native LUs compile on several
    # threads at once: 10-12 s of wall and 51.7-61.4 processor seconds
    # alone (sandbox, PR 50: 61.4 and 57.4 on PR 49's tree, 51.7, 57.0
    # and 61.3 on PR 50's, the same program), so a limit of 60 failed
    # by the draw; twice the reading still fails a slow compile
    compiled = _compile(fn, one_chip, *shapes, kernel=False,
                        limit_s=120.0 if program == "chunks" else 60.0,
                        **static)
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 1 << 30, ma.temp_size_in_bytes
    if program in ("rows", "col"):
        assert ma.output_size_in_bytes == n * 4 * w * 4
        assert ma.temp_size_in_bytes <= n * 4 * w * 4
    else:
        # the same nomination, batched, is what the compiler refuses
        batched = jax.jit(lambda b: jax.vmap(jax.lax.linalg.lu)(b)[2])
        with pytest.raises(Exception, match="vmem"):
            _compile(batched, one_chip, *shapes, kernel=False)


#: bytes a chip may hold of the grid LU's programs at the cell's size,
#: arguments, outputs and temporaries together (ISSUE 49: under 12 GB;
#: compiled for the described v5e 2x2, PR 49: the factor 8.27 GB, 2.42
#: in, 2.42 out, 3.44 of temporaries; the solve 2.42 GB, its factor)
_GRID_LU_BYTES = {"factor": 9 << 30, "solve": 3 << 30}


@pytest.mark.parametrize("program", ["factor", "solve"])
def test_grid_lu_programs_compile_at_the_cells_size(one_chip, program,
                                                    monkeypatch):
    """The two programs of `st.gesv` under `Option.Grid` on the cell
    `grid-gesv` (PR 49), compiled for the described v5e 2x2 at
    n=49152, nb=512, nrhs=64: `getrf`'s staged scan form (panels by
    `lu_panel_blocked` at 49152, 36864, 24576 and 12288 rows, the row
    exchange, the stage moves) and `getrs`'s permutation and two
    sweeps. Each fits a chip with room, and no value in either is more
    of the matrix than a chip's block (the form before PR 49 asks
    18.0 GB of the chip's 15.75 and is refused in 8 s). The routing
    asks `jax.default_backend()`, which sees the CPU here: it is
    steered to the chip's answer."""
    import dataclasses
    import re
    import slate_tpu as st
    from jax.sharding import NamedSharding, PartitionSpec as P
    from slate_tpu.linalg import lu
    _as_on_the_chip(monkeypatch)
    n, nb, nrhs = 49152, 512, 64
    grid = st.make_grid(2, 2, devices=_DESCRIBED["devices"])
    on = NamedSharding(grid.mesh, P("p", "q"))
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=on)
    t0 = time.process_time()
    if program == "factor":
        lowered = lu._grid_getrf_programs(grid)[1].lower(a, nb, 1, nb, n, n)
    else:
        LU = dataclasses.replace(
            st.Matrix(jnp.zeros((nb, nb), jnp.float32), mb=nb),
            data=a, m=n, n=n)
        B = dataclasses.replace(LU, n=nrhs, data=jax.ShapeDtypeStruct(
            (n, nrhs), jnp.float32, sharding=on))
        perm = jax.ShapeDtypeStruct((n,), jnp.int32,
                                    sharding=NamedSharding(grid.mesh, P()))
        lowered = lu._grid_getrs_program(grid).lower(LU, perm, B)
    compiled = lowered.compile()
    took = time.process_time() - t0
    assert took < 400.0, took
    ma = compiled.memory_analysis()
    held = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print("%s: %d bytes a chip (%d of temporaries), %d of code, %.0f "
          "processor seconds" % (program, held, ma.temp_size_in_bytes,
                                 ma.generated_code_size_in_bytes, took))
    assert held < _GRID_LU_BYTES[program], held
    text = compiled.as_text()
    assert not [m for m in re.findall(r"f32\[(\d+),(\d+)\]", text)
                if int(m[0]) * int(m[1]) > n * n // 4]
    if program == "factor":
        assert "LuDecompositionBlock" not in text   # too tall for it
        assert "tpu_custom_call" in text    # the panels' column kernel
        assert "f32[%d,%d]" % (2 * nb, n // 2) in text  # the exchange
