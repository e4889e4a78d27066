"""The VMEM-resident column recurrence of `lu.lu_panel_blocked`
(PR 50, ops/pallas_kernels.lu_block_columns) against the XLA `column`
loop it replaces on the chip: the same swap targets (ties included),
the same positions row, values to f32 rounding, on adversarial blocks;
and `lu_panel_blocked` whole, with the kernel forced on, against
`lu_panel_fori`'s pivots. The kernel runs through the Pallas
interpreter here (pallas_kernels.pallas_interpret); that it compiles
for a v5e at the cells' heights is tests/test_chip_compile.py's."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from slate_tpu.linalg import lu
from slate_tpu.ops import pallas_kernels as pk

from test_pallas_rec import _panel_cases

#: (ib, m): the base block's width and the panel's height; the panel
#: is 4 ib wide, so j0 = ib and j0 = w - ib are inner and last blocks
SHAPES = [(64, 1024), (32, 640), (16, 384)]
FAMILIES = ["random", "ties", "zero_column", "last_row", "ordered", "nan"]


def _block(family, rng, ib, m, j0):
    """An (ib + 1, m) block as `lu_panel_blocked` hands it over: row
    jj the panel's column j0 + jj, m along the lanes, the lanes left
    of j0 finished rows that no search may pick; the last row a
    permutation standing for the row positions."""
    if family == "random":
        blk = rng.standard_normal((ib, m))
    elif family == "ties":
        # every live magnitude one of two values, so each search sees
        # ties by the hundred and the lowest index has to win
        blk = rng.choice([-1.0, -0.5, 0.5, 1.0], size=(ib, m))
    elif family == "zero_column":
        blk = rng.standard_normal((ib, m))
        blk[ib // 2, j0:] = 0.0     # the guard on a zero pivot
        blk[0, j0:] = 0.0
    elif family == "nan":
        # a NaN in the first chunk, one in a later chunk, one left of
        # j0 that no search may see, and a row of nothing else: the
        # search takes the first it may see, as jnp.argmax does, and
        # its answer is always a lane of the block
        blk = rng.standard_normal((ib, m))
        blk[0, [j0 + 5, m - 3]] = np.nan
        blk[1, m - 7] = np.nan
        blk[2, max(j0 - 1, 0)] = np.nan
        blk[ib - 1, :] = np.nan
    elif family == "last_row":
        # column jj's pivot waits in row m - 1 - jj: the first is the
        # last lane of the last chunk
        blk = rng.integers(-8, 9, (ib, m)) / 16.0
        blk[np.arange(ib), m - 1 - np.arange(ib)] = 64.0
    else:
        # already in order: the diagonal dominates, p == j throughout
        blk = rng.integers(-8, 9, (ib, m)) / 16.0
        blk[np.arange(ib), j0 + np.arange(ib)] = 64.0
    pos = rng.permutation(m)
    return jnp.asarray(np.concatenate([blk, pos[None]]).astype(np.float32))


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("where", ["first", "second", "last"])
@pytest.mark.parametrize("ib,m", SHAPES)
def test_kernel_matches_the_xla_column_loop(rng, ib, m, where, family):
    j0 = {"first": 0, "second": ib, "last": 4 * ib - ib}[where]
    tb = _block(family, rng, ib, m, j0)
    ref, pv_ref = jax.jit(lu._block_columns_xla, static_argnums=2)(
        tb, jnp.int32(j0), ib)
    out, pv = pk.lu_block_columns(tb, jnp.int32(j0), ib)
    assert out.shape == tb.shape and pv.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(pv_ref))
    out, ref = np.asarray(out), np.asarray(ref)
    if family == "nan":
        # the same pivots; the loop's every pass multiplies the whole
        # block, finished rows and positions too, by a multiplier row
        # with NaNs in it (0 * NaN), where the kernel leaves them be:
        # its positions stay a permutation, its NaNs are among the
        # loop's, and the rest agrees
        np.testing.assert_array_equal(np.sort(out[ib]), np.arange(m))
        assert not (np.isnan(out) & ~np.isnan(ref)).any()
        out = np.where(np.isnan(ref), ref, out)
    else:
        np.testing.assert_array_equal(out[ib], ref[ib])
    np.testing.assert_allclose(out[:ib], ref[:ib], rtol=1e-5, atol=1e-5)
    pv = np.asarray(pv)
    assert (pv >= j0 + np.arange(ib)).all() and (pv < m).all()
    if family == "ordered":
        np.testing.assert_array_equal(pv, j0 + np.arange(ib))
    if family == "last_row":
        assert pv[0] == m - 1


@pytest.fixture
def kernel_forced_on(monkeypatch):
    """`lu._block_columns` asks the kernel's routing gate, which says
    'platform' off the chip: drop that one answer, so the shape alone
    decides as it does there, and the interpreter runs the kernel."""
    calls = []
    gate = pk.lu_block_columns_reject_reason

    def reason(ib, m, dtype, platform=True):
        calls.append((ib, m))
        return gate(ib, m, dtype, platform=False)

    monkeypatch.setattr(pk, "lu_block_columns_reject_reason", reason)
    return calls


@pytest.mark.parametrize("kind", ["antidiag", "boundary", "randperm",
                                  "ties", "zerocol"])
def test_blocked_panel_on_the_kernel_keeps_foris_pivots(
        rng, kernel_forced_on, kind):
    m, w, ib = 384, 64, 16
    a = _panel_cases(rng, m, w, ib)[kind]
    blocked = jax.jit(lu.lu_panel_blocked, static_argnums=1)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda x: lu.lu_panel_blocked(x, ib))(a))
    packed, piv, perm = blocked(a, ib)
    assert kernel_forced_on and set(kernel_forced_on) == {(ib, m)}
    ref, piv_ref = lu.lu_panel_fori(a)
    np.testing.assert_array_equal(np.asarray(piv), np.asarray(piv_ref))
    np.testing.assert_array_equal(
        np.asarray(perm), np.asarray(lu._compose_swaps(piv_ref, m)))
    np.testing.assert_allclose(np.asarray(packed), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_blocked_panel_on_the_kernel_reconstructs(rng, kernel_forced_on):
    """A general panel: A[perm] = L U to f32 accuracy, and the same
    pivots as the XLA loop gives the same panel."""
    m, w, ib = 640, 128, 32
    a = jnp.asarray(rng.standard_normal((m, w)).astype(np.float32))
    packed, piv, perm = jax.jit(lu.lu_panel_blocked, static_argnums=1)(a, ib)
    L = np.tril(np.asarray(packed, np.float64), -1) + np.eye(m, w)
    U = np.triu(np.asarray(packed, np.float64)[:w])
    np.testing.assert_allclose(L @ U, np.asarray(a)[np.asarray(perm)],
                               atol=2e-5)
    assert np.abs(np.tril(np.asarray(packed), -1)).max() <= 1.0
    _, piv_ref = lu.lu_panel_fori(a)
    np.testing.assert_array_equal(np.asarray(piv), np.asarray(piv_ref))


@pytest.mark.parametrize("ib,m,dtype,reason", [
    (64, 49152, "float32", None), (64, 9216, "float32", None),
    (8, 128, "float32", None), (64, 1000, "float32", "align"),
    (20, 1024, "float32", "align"), (64, 1024, "float64", "dtype"),
    (64, 1024, "bfloat16", "dtype"), (64, 1 << 19, "float32", "height"),
])
def test_gate_reads_the_shape_alone(monkeypatch, ib, m, dtype, reason):
    assert pk.lu_block_columns_reject_reason(
        ib, m, dtype, platform=False) == reason
    assert pk.lu_block_columns_reject_reason(ib, m, dtype) == "platform"
    assert lu._column_kernel(ib, m, dtype) == "xla"
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    assert pk.lu_block_columns_reject_reason(ib, m, dtype) == reason
    assert lu._column_kernel(ib, m, dtype) == \
        ("vmem" if reason is None else "xla")
    if reason is not None:
        with pytest.raises(ValueError, match=reason):
            pk.lu_block_columns(jax.ShapeDtypeStruct((ib + 1, m), dtype), 0, ib)


def test_base_block_is_twice_as_wide_under_the_kernel(monkeypatch):
    """`_blocked_ib` reads the platform and the shape: the XLA loop's
    widths as they were, and a base block of 128 where the VMEM kernel
    takes the panel's blocks that wide."""
    f32 = jnp.float32
    assert [lu._blocked_ib(w) for w in (1024, 192, 96, 48, 24, 100)] == \
        [64, 64, 32, 16, 8, 0]
    assert lu._blocked_ib(1024, 16384, f32) == 64       # off the chip
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    assert lu._blocked_ib(1024, 16384, f32) == 128
    assert lu._blocked_ib(512, 49152, f32) == 128
    assert lu._blocked_ib(192, 16384, f32) == 64        # 128 divides not
    assert lu._blocked_ib(1024, 16400, f32) == 64       # off the lane tile
    assert lu._blocked_ib(1024, 16384, jnp.float64) == 64
    assert lu._blocked_ib(96, 16384, f32) == 32
    assert lu._blocked_ib(1024) == 64                   # no panel given


def test_route_note_names_the_column_kernel(monkeypatch):
    """The route notes of `getrf` and of the streamed LU say which
    column recurrence a blocked panel runs; other panel routes carry
    no such key."""
    from slate_tpu.core.methods import MethodLUPanel
    note = lu._panel_note(16384, 512, jnp.float32, MethodLUPanel.Blocked)
    assert note == {"panel": "blocked", "panel_columns": "xla"}
    assert lu._panel_note(4096, 512, jnp.float32,
                          MethodLUPanel.Native) == {"panel": "native"}
    assert lu._panel_note(4096, 512, jnp.float32) == {"panel": "native"}
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    note = lu._panel_note(16384, 512, jnp.float32, MethodLUPanel.Blocked)
    assert note == {"panel": "blocked", "panel_columns": "vmem"}
    assert lu._panel_note(16400, 512, jnp.float32, MethodLUPanel.Blocked) \
        == {"panel": "blocked", "panel_columns": "xla"}


def test_chip_fallback_to_the_xla_loop_is_surfaced_once(rng, monkeypatch):
    """A TPU run whose block the kernel refuses keeps the XLA loop and
    says why, once a shape; off the chip the loop is the route and
    nothing is said."""
    from slate_tpu import obs
    ib, m = 16, 200              # off the lane tile
    tb = _block("random", rng, ib, m, 0)
    lu._COLUMNS_FALLBACK_SEEN.clear()
    obs.enable(beacon=False)
    try:
        obs.clear()
        lu._block_columns(tb, jnp.int32(0), ib)
        assert not [e for e in obs.bus_events()
                    if e.name == "pallas.lu_block_columns.reject"]
        monkeypatch.setattr(pk, "_on_tpu", lambda: True)
        out, pv = lu._block_columns(tb, jnp.int32(0), ib)
        lu._block_columns(tb, jnp.int32(0), ib)
        evs = [e for e in obs.bus_events()
               if e.name == "pallas.lu_block_columns.reject"]
        assert len(evs) == 1
        assert evs[0].args["reason"] == "align" and evs[0].args["m"] == m
    finally:
        obs.disable()
        obs.clear()
        lu._COLUMNS_FALLBACK_SEEN.clear()
    ref, pv_ref = lu._block_columns_xla(tb, jnp.int32(0), ib)
    np.testing.assert_array_equal(np.asarray(pv), np.asarray(pv_ref))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
