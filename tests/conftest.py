"""Test config: run on CPU backend with 8 virtual devices so sharding /
multi-chip paths are exercised without TPU hardware (the reference's
analogue: 4-rank mpirun on one node, SURVEY.md §4)."""

import os
import tempfile

# isolate the autotuning cache: tests must never read the developer's
# real tuning table (a tuned entry would silently change the
# blocking/routing the numeric tests were written against) — override
# unconditionally, since an exported SLATE_TPU_TUNE_CACHE from bench
# runs must not leak in either; cleaned up at interpreter exit
_tune_cache_tmp = tempfile.TemporaryDirectory(
    prefix="slate_tpu_tune_test_")
os.environ["SLATE_TPU_TUNE_CACHE"] = _tune_cache_tmp.name

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# jax may be preloaded with an accelerator platform; force the CPU —
# the backend is initialized lazily so this still takes effect.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


#: tests measured >= ~4 s on the 1-core CPU CI box (2026-07-31 full
#: run: 233 tests, 19 min). Everything else forms the `-m quick` tier
#: (reference analogue: test/run_tests.py --quick/--small). Keep this
#: list in sync when adding heavy tests: `pytest --durations=30`.
SLOW_TESTS = {
    "test_band.py::test_band_flop_win",
    "test_band.py::test_hb2st_complex",
    "test_band.py::test_hb2st_driver_band_path",
    "test_band.py::test_tb2bd_band_windowed",
    "test_c_api.py::test_c_program_end_to_end",
    "test_ca.py::test_gesv_calu_route",
    "test_ca.py::test_getrf_tntpiv_factors",
    "test_ca.py::test_getrf_tntpiv_scan_path_stays_calu",
    "test_ca.py::test_getrf_tntpiv_bracket_runs_when_chunked",
    "test_chol.py::test_cholesky_scan_matches_blocked",
    "test_chol.py::test_pbsv",
    "test_chol.py::test_potrf_tiled_matches_fused",
    "test_distributed.py::test_gels_on_mesh",
    "test_distributed.py::test_geqrf_flop_balance",
    "test_distributed.py::test_gesv_on_mesh",
    "test_distributed.py::test_getrf_flop_balance",
    "test_distributed.py::test_getrf_nopiv_on_mesh",
    "test_distributed.py::test_posv_on_mesh",
    "test_distributed.py::test_potrf_cyclic_input",
    "test_distributed.py::test_potrf_flop_balance",
    "test_distributed.py::test_trsm_on_mesh",
    "test_dist.py::test_tree_allreduce_matches_psum",
    "test_dist.py::test_tsqr_mesh",
    "test_dist.py::test_tsqr_qt_solves_lstsq",
    "test_dist.py::test_geqrf_grid_tall_skinny_takes_tree",
    "test_dist.py::test_steqr2_dist_bitwise_matches_single",
    "test_dist.py::test_stedc_dist_matches_single_device",
    "test_dist.py::test_heev_dc_on_mesh",
    "test_dist.py::test_steqr2_separated_spectrum_medium",
    "test_eig_svd.py::test_bdsqr_qr_iteration",
    "test_eig_svd.py::test_ge2tb_scan_matches_unrolled",
    "test_eig_svd.py::test_gecondest",
    "test_eig_svd.py::test_he2hb_scan_matches_unrolled",
    "test_eig_svd.py::test_heev_method_qriteration",
    "test_eig_svd.py::test_hegst_blocked_matches_dense",
    "test_eig_svd.py::test_hegv",
    "test_eig_svd.py::test_hetrf_blocked_structure",
    "test_eig_svd.py::test_hetrf_scan_matches_blocked",
    "test_eig_svd.py::test_staged_svd",
    "test_eig_svd.py::test_steqr2_qr_iteration",
    "test_eig_svd.py::test_steqr2_routes_qr_iteration",
    "test_eig_svd.py::test_stage2_tpu_guard_warns",
    "test_eig_svd.py::test_svd_method_qriteration",
    "test_eig_svd.py::test_sytrf_blocked_complex_symmetric",
    "test_eig_svd.py::test_two_stage_pipeline",
    "test_elastic_multiproc.py::test_two_process_uniform_elastic_bitwise",
    "test_elastic_multiproc.py::test_two_process_straggler_remap_bitwise",
    "test_elastic_multiproc.py::test_two_process_kill_shrink_resume",
    "test_harness.py::test_condest_early_exit",
    "test_harness.py::test_tester_cli_quick",
    "test_info.py::test_hetrf_info",
    "test_lu.py::test_gesv_rbt",
    "test_lu.py::test_getrf_carry_rectangular",
    "test_lu.py::test_getrf_lookahead_pipelined_matches_plain",
    "test_lu.py::test_lu_scan_matches_unrolled",
    "test_matgen.py::test_all_kinds_materialize",
    "test_multihost.py::test_two_process_global_mesh_posv",
    "test_obs.py::test_heev_dc_mesh_report_shows_collectives",
    "test_obs.py::test_hlo_collectives_match_tree_schedule",
    "test_ooc.py::test_getrf_ooc_matches_incore_pivots",
    "test_qr.py::test_geqrf_blocksize_option",
    "test_qr.py::test_geqrf_complex",
    "test_qr.py::test_geqrf_fused_packed",
    "test_qr.py::test_unmqr_scan_matches_unrolled",
    "test_stedc.py::test_merge_decoupled_above_leaf",
    "test_stedc.py::test_secular_negative_rho",
    "test_chol.py::test_potrf_lookahead_pipelined_matches_plain",
    "test_qr.py::test_gelqf_unmlq",
    "test_qr.py::test_unmqr_right",
    "test_stedc.py::test_rotation_matrix_matches_column_loop",
    "test_stedc.py::test_secular_phase_direct",
    "test_stedc.py::test_stedc_solve",
    "test_stedc.py::test_stedc_solve_padded_driver",
    "test_stedc.py::test_stedc_solve_scale_invariant",
    "test_stedc.py::test_stedc_with_backtransform",
    "test_tune.py::test_eigh_dc_propagates_polar_convergence",
    "test_batch.py::test_tuneshare_broadcast_on_mesh",
    "test_shard_multiproc.py::test_two_process_shard_ooc",
    "test_shard_ooc.py::test_shard_geqrf_rectangular_shapes",
    "test_resil.py::test_rbt_sentinel_escalates_to_getrf",
    "test_resil_multiproc.py::test_two_process_kill_resume",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "quick: fast subset, < ~2 min total on 1 CPU core "
        "(run with -m quick; reference run_tests.py --quick tier)")
    config.addinivalue_line(
        "markers", "slow: excluded from the quick tier")


def pytest_collection_modifyitems(config, items):
    seen = set()
    for item in items:
        base = item.nodeid.split("/")[-1].split("[")[0]
        if base in SLOW_TESTS:
            seen.add(base)
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.quick)
    # drift guard: a renamed/removed test must not silently leave a
    # stale entry here (its successor would join the quick tier and
    # blow the ~2 min budget with no signal). A warning, not an
    # error: partial collections (--ignore, file subsets) legitimately
    # miss entries.
    if len(items) > 100:
        stale = SLOW_TESTS - seen
        if stale:
            import warnings
            warnings.warn(
                "conftest.SLOW_TESTS entries match no collected test "
                f"(renamed/removed, or a partial collection?): "
                f"{sorted(stale)}")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def host_plane(tmp_path):
    """`host_plane(body, names)`: run `body()` under a profiler session
    with the benchmark's own options (benchmarks/lib/tracer.py) and
    return the events named in `names` that the xplane's host planes
    hold, as [(start ns, end ns, name, {argument: value})]: where a
    bus span must be seen on the profiler's clock."""
    def run(body, names):
        from benchmarks.lib import hostspans, reduce_trace
        from benchmarks.lib.tracer import Tracer
        tr = Tracer(str(tmp_path / "trace"))
        tr.start()
        try:
            body()
        finally:
            tr.stop()
        return hostspans.host_events(reduce_trace.load(tr.xplane()),
                                     set(names))
    return run


@pytest.fixture
def dispatches_under(tmp_path):
    """`dispatches_under(body, names)`: run `body()` under a profiler
    session and return {span name: [the jitted functions dispatched
    while that span was open, in order], one list a span, in start
    order}. The profiler's host plane has a `PjitFunction(name)` event
    for each dispatch of a jitted function and, with the bus on, each
    bus span on the same clock: how a test counts the compiled
    programs a step holds once nothing is left to trace."""
    def run(body, names):
        from benchmarks.lib import reduce_trace
        from benchmarks.lib.tracer import Tracer
        tr = Tracer(str(tmp_path / "trace"))
        tr.start()
        try:
            body()
        finally:
            tr.stop()
        spans, calls = [], []
        for plane in reduce_trace.load(tr.xplane()).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    ev = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name in names:
                        spans.append(ev)
                    elif e.name.startswith("PjitFunction("):
                        calls.append(ev[:2] + (e.name[13:-1],))
        # the runtime marks a dispatch twice, one event inside the other
        calls.sort()
        calls = [c for prev, c in zip([(0, 0, "")] + calls, calls)
                 if prev[1] < c[1]]
        held = {n: [] for n in names}
        for s0, s1, name in sorted(spans):
            held[name].append([c for t, _, c in calls if s0 <= t < s1])
        return held
    return run


@pytest.fixture(scope="session")
def grid8():
    import slate_tpu as st
    return st.make_grid(2, 4)
