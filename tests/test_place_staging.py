"""The mesh's placement of a host array (PR 28): each device's block
travels as row chunks through a ring of reused host staging buffers
(`parallel/sharding.place`, `core/staging.StageRing`). On a 2x2 grid
of the virtual devices, with the chunk size set small enough that a
96 x 96 block is several chunks: the result against `device_put`'s bit
for bit, what passes through uncopied, what the counters count, that a
recycled slot leaves the earlier array alone, the ring's bound, the
spans on the profiler's clock, and the benchmark's metric file."""

import os

import jax
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.core.staging import StageRing
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import metrics as obs_metrics
from slate_tpu.parallel import sharding as sh

from benchmarks import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))

#: a 96 x 96 f32 block is 36,864 bytes: four chunks of 24 rows
CHUNK = 8192


@pytest.fixture(scope="module")
def grid():
    return st.make_grid(2, 2, devices=jax.devices()[:4])


@pytest.fixture
def bus():
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()
    yield
    obs.disable()
    obs_events.clear()
    obs_metrics.reset()


@pytest.fixture
def ring(monkeypatch):
    """A ring of this test's own, and chunks of `CHUNK` bytes."""
    r = StageRing("grid")
    monkeypatch.setattr(sh, "_ring", r)
    monkeypatch.setattr(sh, "STAGE_CHUNK_BYTES", CHUNK)
    return r


def counters():
    return obs.snapshot()["metrics"]["counters"]


def fill(ring, nbytes, slots=8):
    """The ring at its bound of `slots` slots, each of `nbytes` at
    least: how many a placement makes is the threads' luck (a slot
    whose transfer is over is taken before a new one is made)."""
    ring.reserve(slots)
    held = [ring.acquire(nbytes)[0] for _ in range(slots)]
    for slot in held:
        ring.release(slot, None)
    assert len(ring._slots) == slots


def same_placement(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.sharding.is_equivalent_to(want.sharding, want.ndim)
    mine = {s.device: s for s in got.addressable_shards}
    for s in want.addressable_shards:
        assert mine[s.device].index == s.index
        assert np.asarray(mine[s.device].data).tobytes() == \
            np.asarray(s.data).tobytes()


# -- bitwise what device_put gives -----------------------------------------

@pytest.mark.parametrize("shape,pad", [
    ((192, 192), None),             # strided blocks, four chunks each
    ((192, 192), (200, 208)),       # and padded on the mesh
    ((192, 64), None),              # a block under one chunk: one chunk
    ((100, 60), (104, 64)),         # chunk height not dividing the block
    ((192, 5), None),               # 'q' dropped: row blocks, sent twice
    ((7, 192), (8, 192)),           # 'p' dropped: column blocks
    ((1, 192), None),               # one row: contiguous as it lies
    ((192,), None),                 # a vector
    ((), None),                     # a scalar
], ids=str)
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.complex64])
def test_chunked_placement_is_bitwise_device_puts(grid, ring, rng, shape,
                                                  pad, dtype):
    a = rng.standard_normal(shape).astype(dtype)
    if np.iscomplexobj(a):
        a = a + 1j * rng.standard_normal(shape).astype(np.float32)
    want = jax.device_put(a, sh.fitted_sharding(a.shape, grid))
    if pad is not None:
        want = sh._pad_program(grid)(want, pad)
    same_placement(sh.place(a, grid, pad), want)
    # and again through slots the first call touched
    same_placement(sh.place(a, grid, pad), want)


def test_strided_block_is_several_chunks(grid, ring, bus, rng):
    a = rng.standard_normal((192, 192)).astype(np.float32)
    obs.enable()
    sh.place(a, grid)
    packs = [e for e in obs.bus_events(cat="shard")
             if e.name == "grid::pack"]
    assert len(packs) == 4 * 4
    assert {e.args["bytes"] for e in packs} == {24 * 96 * 4}
    assert sum(e.args["bytes"] for e in packs) == a.nbytes
    assert {e.args["device"] for e in packs} == \
        {d.id for d in jax.devices()[:4]}


@pytest.mark.parametrize("block,itemsize,rows,chunks", [
    ((24576, 24576), 4, 2736, 9),   # the cell's: 269 MB chunks
    ((24576, 32), 4, 24576, 1),     # its right-hand side's
    ((96, 96), 4, 96, 1),           # every tier-1 matrix elsewhere
    ((32768, 32768), 8, 1024, 32),
])
def test_chunk_height_follows_from_the_blocks_bytes(block, itemsize, rows,
                                                    chunks):
    class Block:
        shape = block
        nbytes = block[0] * block[1] * itemsize
    got = sh._chunk_rows(Block)
    assert got == rows and got % 8 == 0
    assert -(-block[0] // got) == chunks
    assert got * block[1] * itemsize <= sh.STAGE_CHUNK_BYTES + \
        8 * block[1] * itemsize


# -- what is copied, what is counted ---------------------------------------

def test_contiguous_blocks_pass_through_uncopied(ring, bus, rng):
    col = st.make_grid(4, 1, devices=jax.devices()[:4])
    a = rng.standard_normal((192, 192)).astype(np.float32)
    obs.enable()
    out = sh.place(a, col)
    same_placement(out, jax.device_put(a, sh.fitted_sharding(a.shape, col)))
    c = counters()
    assert c["grid.h2d_bytes"] == a.nbytes
    assert "grid.stage_reuse_bytes" not in c
    assert "grid.stage_fresh_bytes" not in c
    assert ring._slots == []
    names = [e.name for e in obs.bus_events(cat="shard")]
    assert names == ["grid::put"] * 4


def test_first_placement_is_fresh_and_the_second_reuse(grid, ring, bus, rng):
    a = rng.standard_normal((192, 192)).astype(np.float32)
    b = rng.standard_normal((192, 192)).astype(np.float32)
    obs.enable()
    sh.place(a, grid)
    c1 = counters()
    assert c1["grid.h2d_bytes"] == a.nbytes
    # a slot's first buffer is fresh, whatever is packed into it later
    assert c1["grid.stage_fresh_bytes"] == \
        sum(s.buf.nbytes for s in ring._slots) > 0
    assert c1["grid.stage_fresh_bytes"] + \
        c1.get("grid.stage_reuse_bytes", 0) == a.nbytes
    fill(ring, 24 * 96 * 4)
    sh.place(b, grid)
    c2 = counters()
    assert c2["grid.h2d_bytes"] == a.nbytes + b.nbytes
    assert c2["grid.stage_fresh_bytes"] == c1["grid.stage_fresh_bytes"]
    assert c2["grid.stage_reuse_bytes"] == \
        c1.get("grid.stage_reuse_bytes", 0) + b.nbytes
    # a larger chunk regrows the slot it lands in: fresh again
    wide = rng.standard_normal((192, 384)).astype(np.float32)
    sh.place(wide, grid)
    assert counters()["grid.stage_fresh_bytes"] > \
        c2["grid.stage_fresh_bytes"]
    assert counters()["grid.h2d_bytes"] == \
        a.nbytes + b.nbytes + wide.nbytes


def test_a_block_sent_twice_is_counted_twice(grid, ring, bus, rng):
    b = rng.standard_normal((192, 5)).astype(np.float32)
    obs.enable()
    sh.place(b, grid)
    c = counters()
    assert c["grid.h2d_bytes"] == 2 * b.nbytes      # 'q' replicates
    assert "grid.stage_fresh_bytes" not in c        # row blocks


def test_device_array_goes_as_before(grid, ring, bus, rng):
    a = rng.standard_normal((192, 192)).astype(np.float32)
    want = jax.device_put(a, sh.fitted_sharding(a.shape, grid))
    obs.enable()
    same_placement(sh.place(jax.numpy.asarray(a), grid), want)
    assert ring._slots == []
    assert [e.name for e in obs.bus_events(cat="staging")
            + obs.bus_events(cat="shard")] == ["grid::place"]
    assert not any(k.startswith("grid.") for k in counters())


# -- the ring under the placement ------------------------------------------

def _aligned(slot, nbytes):
    # the CPU backend aliases only a well aligned buffer: give the
    # slot one
    raw = np.empty(nbytes + 4096, np.uint8)
    off = -raw.ctypes.data % 4096
    slot.buf = raw[off:off + nbytes]


@pytest.mark.parametrize("guarded", [True, False])
def test_recycled_slot_leaves_the_earlier_matrix_intact(
        grid, ring, rng, monkeypatch, guarded):
    """Two matrices placed back to back through the same slots, one
    chunk a block so that the arrays made from the slots ARE the
    shards: the first survives only because the put copies on a
    backend that may alias host memory (this one); with the guard
    forced off the second placement rewrites the first."""
    if not guarded:
        monkeypatch.setattr(sh, "aliases_host", lambda dev: False)
    monkeypatch.setattr(sh, "STAGE_CHUNK_BYTES", 1 << 20)
    a = rng.standard_normal((192, 192)).astype(np.float32)
    b = rng.standard_normal((192, 192)).astype(np.float32)
    fill(ring, a.nbytes // 4)
    for slot in ring._slots:
        _aligned(slot, a.nbytes // 4)
    first = sh.place(a, grid)
    second = sh.place(b, grid)
    third = sh.place(a + 1, grid)
    assert len(ring._slots) == 8
    assert np.array_equal(np.asarray(third), a + 1)
    intact = np.array_equal(np.asarray(first), a) \
        and np.array_equal(np.asarray(second), b)
    assert intact == guarded


def test_ring_is_bounded_over_many_placements(grid, ring, rng):
    mats = [rng.standard_normal((192, 192)).astype(np.float32)
            for _ in range(3)]
    for rep in range(12):
        a = mats[rep % 3]
        assert np.array_equal(np.asarray(sh.place(a, grid)), a)
    assert 1 <= len(ring._slots) <= 2 * 4       # two a device
    assert not any(s.busy or s.last is not None for s in ring._slots)
    assert all(s.buf.nbytes == 24 * 96 * 4 for s in ring._slots)
    # one ring class in the package: the stream engine's is this one
    from slate_tpu.linalg import stream
    assert type(stream._ring) is type(ring) is StageRing
    assert stream._ring is not sh._ring


def test_a_failed_pack_frees_its_slot_and_raises(grid, ring, rng,
                                                 monkeypatch):
    a = rng.standard_normal((192, 192)).astype(np.float32)

    def refuse(dst, src):
        raise MemoryError("no copy today")

    monkeypatch.setattr(np, "copyto", refuse)
    with pytest.raises(MemoryError):
        sh.place(a, grid)
    monkeypatch.undo()
    assert not any(s.busy for s in sh._ring._slots)


# -- observability ----------------------------------------------------------

def test_pack_and_put_reach_the_host_plane_inside_place(grid, ring, bus,
                                                        host_plane, rng):
    a = rng.standard_normal((192, 192)).astype(np.float32)
    sh.place(a, grid)                       # compiled before the session
    obs.enable()
    names = ["grid::place", "matrix::h2d", "grid::pack", "grid::put"]
    seen = host_plane(lambda: sh.place(a, grid), names)
    by_name = {}
    for ev in seen:
        by_name.setdefault(ev[2], []).append(ev)
    assert set(by_name) == set(names)
    (plc,), (h2d,) = by_name["grid::place"], by_name["matrix::h2d"]
    assert len(by_name["grid::pack"]) == len(by_name["grid::put"]) == 16
    assert plc[0] <= h2d[0] <= h2d[1] <= plc[1]
    for ev in by_name["grid::pack"] + by_name["grid::put"]:
        assert h2d[0] <= ev[0] <= ev[1] <= h2d[1], ev
    assert sum(int(ev[3]["bytes"]) for ev in by_name["grid::pack"]) \
        == a.nbytes
    assert {int(ev[3]["device"]) for ev in by_name["grid::put"]} == \
        {d.id for d in jax.devices()[:4]}


def test_staging_sites_are_one_branch_when_off(grid, ring, bus, rng,
                                               monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(obs_events, "_annotation", Counting)
    a = rng.standard_normal((192, 192)).astype(np.float32)
    assert np.array_equal(np.asarray(sh.place(a, grid)), a)
    assert made == [] and obs.bus_events() == []
    assert counters() == {}


# -- the benchmark's metric -------------------------------------------------

def test_stage_reuse_share_is_declared_and_found():
    m = [x for x in BENCH["per_layer"]
         if x["name"] == "grid.stage_reuse_share"]
    assert m == [{"name": "grid.stage_reuse_share", "unit": "%",
                  "better": "higher", "source": "program_counter",
                  "layer": "mesh", "moves": "stream_solve_s",
                  "workloads": ["grid-posv"]}]
    # appended after PR 27's grid metrics; later cells append after it
    at = BENCH["per_layer"].index(m[0])
    assert BENCH["per_layer"][at - 1]["name"] == "grid.idle_upload_share"
    assert callable(bench_run.load_module(
        "layer_metrics", "grid.stage_reuse_share").compute)


@pytest.mark.parametrize("counted,want", [
    ({"grid.stage_reuse_bytes": 300, "grid.stage_fresh_bytes": 100}, 75.0),
    ({"grid.stage_reuse_bytes": 7}, 100.0),
    ({"grid.stage_fresh_bytes": 7}, 0.0),
    ({}, None),                                 # the parent: no ring
    ({"grid.h2d_bytes": 9, "ooc.h2d_stage_reuse_bytes": 5}, None),
])
def test_stage_reuse_share_by_hand(counted, want):
    compute = bench_run.load_module(
        "layer_metrics", "grid.stage_reuse_share").compute
    assert compute({"counters": counted}) == want


def test_stage_reuse_share_from_a_placement(grid, ring, bus, rng):
    compute = bench_run.load_module(
        "layer_metrics", "grid.stage_reuse_share").compute
    a = rng.standard_normal((192, 192)).astype(np.float32)
    obs.enable()
    sh.place(jax.numpy.asarray(a), grid)
    assert compute({"counters": counters()}) is None
    sh.place(a, grid)
    first = compute({"counters": counters()})
    fill(ring, 24 * 96 * 4)
    obs_metrics.reset()
    sh.place(a, grid)
    assert first < 100.0 and compute({"counters": counters()}) == 100.0
