"""The streaming-aggregate grid backend vs the full-series ``_summarise``.

Acceptance contract of the O(N)-memory refactor:

* aggregate-mode sums (cost / processed / dropped), the per-bin max, the
  end-of-scan queue and the SLO percentages are BIT-IDENTICAL to the
  series-path summaries (the twice-compensated carry triples recombine to
  numpy's f64 sums exactly), across all five registered policies and both
  backends (XLA switch-scan and Pallas interpret);
* the histogram median and drop-rate SLO stats agree within histogram-bin
  / boundary tolerance of the numpy sort/cumsum path;
* aggregate mode never materializes a [N, T] series — asserted on the
  returned pytree shapes for every backend, chunked dispatch included;
* chunked megabatch dispatch (``lax.map`` over scenario blocks, load
  matrix + index map) returns the same numbers as the unchunked call.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.simulate import (GridSummary, _grid_agg_dispatch,  # noqa: E402
                                 _grid_scan_agg, simulate_grid)
from repro.core.slo import SLO  # noqa: E402
from repro.core.traffic import HOURS_PER_YEAR, TrafficModel  # noqa: E402
from repro.core.twin import (AGG_DIM, AGG_HIST_W, CARRY_DIM,  # noqa: E402
                             QuickscalingTwin, SimpleTwin, make_twin,
                             policy_onehot, registry_version)
from repro.core.whatif import run_grid, run_scenarios, Scenario  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.policy_scan import policy_grid_agg  # noqa: E402

SLO_4H = SLO(limit_s=4 * 3600, met_fraction=0.95)

#: one twin per registered policy — parity must hold for every branch
ALL_POLICY_TWINS = [
    SimpleTwin("fifo", 1.9512, 0.0082, 0.15),
    QuickscalingTwin("quick", 1.9512, 0.0082, 0.15),
    make_twin("auto", "autoscale", max_rps=0.5, usd_per_hour=0.002,
              base_latency_s=0.1, max_instances=32, scale_up_hours=3),
    make_twin("shed", "shed", max_rps=1.0, usd_per_hour=0.0082,
              base_latency_s=0.15, queue_cap_hours=2),
    make_twin("batch", "batch_window", max_rps=6.15, usd_per_hour=0.0703,
              base_latency_s=0.06, window_hours=6),
]
TRAFFICS = [TrafficModel.honda_default("nom"),
            TrafficModel.honda_default("high", G=1.5)]

#: the histogram median's representative is a bucket center, so it sits
#: within one log-spaced bucket of the true (sort/cumsum) median
MEDIAN_RATIO_TOL = 10.0 ** AGG_HIST_W * (1 + 1e-6)


def _series_vs_aggregate(series, aggs):
    assert len(series) == len(aggs)
    for s, a in zip(series, aggs):
        assert isinstance(a, GridSummary)
        assert s.name == a.name and s.twin == a.twin
        yield s, a


def _assert_scalar_parity(series, aggs, exact=True):
    close = (np.testing.assert_array_equal if exact else
             lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-6))
    for s, a in _series_vs_aggregate(series, aggs):
        close(a.total_cost_usd, s.total_cost_usd)
        close(a.backlog_s, s.backlog_s)
        close(a.backlog_cost_usd, s.backlog_cost_usd)
        close(a.max_throughput_rph, s.max_throughput_rph)
        close(a.mean_throughput_rph, s.mean_throughput_rph)
        close(a.dropped_records, s.dropped_records)
        close(a.processed_records, np.float64(s.processed).sum())
        close(a.arrived_records, np.float64(s.load).sum())
        close(a.queue_end, s.queue[-1])
        np.testing.assert_allclose(a.mean_latency_s, s.mean_latency_s,
                                   rtol=1e-5)
        ratio = a.median_latency_s / max(s.median_latency_s, 1e-12)
        assert 1.0 / MEDIAN_RATIO_TOL <= ratio <= MEDIAN_RATIO_TOL, \
            (s.name, a.median_latency_s, s.median_latency_s)


# ---------------------------------------------------------------------------
# aggregate-vs-series parity: all five policies, both backends
# ---------------------------------------------------------------------------

def test_aggregate_bit_identical_to_series_xla_all_policies():
    series = run_grid(ALL_POLICY_TWINS, TRAFFICS, slo=SLO_4H,
                      return_series=True)
    aggs = run_grid(ALL_POLICY_TWINS, TRAFFICS, slo=SLO_4H)
    _assert_scalar_parity(series, aggs, exact=True)
    for s, a in _series_vs_aggregate(series, aggs):
        # pct_* go through the identical f64 ratio, so exact too
        assert a.pct_latency_met == s.pct_latency_met
        assert a.pct_hours_met == s.pct_hours_met
        assert a.slo_met == s.slo_met


def test_aggregate_matches_series_under_pallas_mode():
    series = run_grid(ALL_POLICY_TWINS, TRAFFICS, slo=SLO_4H,
                      return_series=True)
    with ops.pallas_mode():
        aggs = run_grid(ALL_POLICY_TWINS, TRAFFICS, slo=SLO_4H)
    # the Pallas lane blend performs the same additions; empirically it
    # matches the switch-scan bit for bit, but only 1e-6 is contractual
    _assert_scalar_parity(series, aggs, exact=False)
    for s, a in _series_vs_aggregate(series, aggs):
        assert a.slo_met == s.slo_met


def test_aggregate_without_slo_defaults_like_series():
    series = run_grid(ALL_POLICY_TWINS, TRAFFICS[:1], return_series=True)
    aggs = run_grid(ALL_POLICY_TWINS, TRAFFICS[:1])
    for s, a in _series_vs_aggregate(series, aggs):
        assert a.slo_met is None and s.slo_met is None
        assert a.pct_latency_met == 100.0 and a.pct_hours_met == 100.0


def test_drop_rate_slo_aggregate_parity():
    slo = SLO.for_drop_rate(max_fraction=0.01, met_fraction=0.9)
    twins = [make_twin(f"shed{h}", "shed", max_rps=0.8, usd_per_hour=0.008,
                       base_latency_s=0.15, queue_cap_hours=h)
             for h in (0.5, 2.0, 8.0)]
    series = run_grid(twins, TRAFFICS, slo=slo, return_series=True)
    aggs = run_grid(twins, TRAFFICS, slo=slo)
    for s, a in _series_vs_aggregate(series, aggs):
        # the ok-mass is summed exactly, but the f32 in-carry drop
        # fraction can flip bins sitting exactly on the limit — allow a
        # whisker while requiring the decision pattern to match
        np.testing.assert_allclose(a.pct_latency_met, s.pct_latency_met,
                                   atol=0.05)
        np.testing.assert_allclose(a.pct_hours_met, s.pct_hours_met,
                                   atol=0.05)
        assert a.slo_met == s.slo_met
        np.testing.assert_array_equal(a.dropped_records, s.dropped_records)


def test_storage_costs_via_load_matrix_index_map():
    cm_twins = [SimpleTwin("a", 2.0, 0.01, 0.1),
                SimpleTwin("b", 4.0, 0.02, 0.1)]
    from repro.core.cost import CostModel
    cm = CostModel()
    series = run_grid(cm_twins, TRAFFICS, cost_model=cm, record_mb=0.001,
                      return_series=True)
    aggs = run_grid(cm_twins, TRAFFICS, cost_model=cm, record_mb=0.001)
    for s, a in _series_vs_aggregate(series, aggs):
        np.testing.assert_allclose(a.network_cost_usd, s.network_cost_usd,
                                   rtol=1e-12)
        np.testing.assert_allclose(a.storage_cost_usd, s.storage_cost_usd,
                                   rtol=1e-12)
        np.testing.assert_allclose(a.grand_total_usd, s.grand_total_usd,
                                   rtol=1e-12)


# ---------------------------------------------------------------------------
# O(N) memory: no [N, T] output ever exists in aggregate mode
# ---------------------------------------------------------------------------

def _grid_arrays(n):
    twins = [ALL_POLICY_TWINS[i % len(ALL_POLICY_TWINS)] for i in range(n)]
    matrix = np.stack([tr.hourly_loads() for tr in TRAFFICS]).astype(
        np.float32)
    index = np.arange(n, dtype=np.int32) % len(TRAFFICS)
    params = np.stack([tw.padded_params() for tw in twins])
    idx = np.asarray([tw.policy_index for tw in twins], np.int32)
    return twins, matrix, index, params, idx


def test_aggregate_pytree_has_no_series_axis():
    n = 12
    _, matrix, index, params, idx = _grid_arrays(n)
    loads = jnp.asarray(matrix[index])
    t_bins = loads.shape[1]

    def assert_o_n(outs):
        for leaf in jax.tree_util.tree_leaves(outs):
            assert leaf.shape[0] in (n, -(-n // 8) * 8)   # N or lane pad
            assert t_bins not in leaf.shape, leaf.shape
            assert leaf.ndim <= 2 and leaf.size <= n * 8 * AGG_DIM

    # every aggregate backend's result contract is O(N): the XLA path
    # (device-resident histogram), the jnp lane oracle, the Pallas
    # kernel, and the chunked lax.map dispatch
    assert_o_n(_grid_scan_agg(loads, jnp.asarray(params),
                              jnp.asarray(idx), registry_version(),
                              1.0, float("inf"), 0))
    assert_o_n(ref.policy_grid_agg(loads, jnp.asarray(params),
                                   jnp.asarray(policy_onehot(idx)), 1.0))
    assert_o_n(policy_grid_agg(loads, jnp.asarray(params),
                               jnp.asarray(policy_onehot(idx)), 1.0))
    carry_end, agg = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                        float("inf"), 0, scenario_block=5)
    assert carry_end.shape == (n, CARRY_DIM) and agg.shape == (n, AGG_DIM)


def test_grid_summary_rows_carry_no_series():
    aggs = run_grid(ALL_POLICY_TWINS, TRAFFICS, slo=SLO_4H)
    for a in aggs:
        arrays = [v for v in vars(a).values() if isinstance(v, np.ndarray)]
        assert all(v.size <= AGG_DIM for v in arrays)   # histogram only


# ---------------------------------------------------------------------------
# chunked megabatch dispatch == unchunked
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [5, 8, 24])
def test_chunked_dispatch_matches_unchunked(block):
    # n=24: block 5 exercises tail padding, 8 even blocks, 24 one block
    n = 24
    twins, matrix, index, params, idx = _grid_arrays(n)
    base_c, base_a = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                        float(SLO_4H.limit_s), 0, None)
    chunk_c, chunk_a = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                          float(SLO_4H.limit_s), 0, block)
    np.testing.assert_array_equal(chunk_c, base_c)
    np.testing.assert_array_equal(chunk_a, base_a)


def test_chunked_dispatch_matches_under_pallas():
    n = 13
    twins, matrix, index, params, idx = _grid_arrays(n)
    with ops.pallas_mode():
        base_c, base_a = _grid_agg_dispatch(matrix, index, params, idx,
                                            1.0, float("inf"), 0, None)
        chunk_c, chunk_a = _grid_agg_dispatch(matrix, index, params, idx,
                                              1.0, float("inf"), 0, 4)
    np.testing.assert_allclose(chunk_c, base_c, rtol=1e-6)
    np.testing.assert_allclose(chunk_a, base_a, rtol=1e-6)


def test_simulate_grid_chunked_end_to_end():
    n = 10
    twins, matrix, index, _, _ = _grid_arrays(n)
    base = simulate_grid(twins, load_matrix=matrix, load_index=index,
                         slo=SLO_4H, return_series=False)
    chunked = simulate_grid(twins, load_matrix=matrix, load_index=index,
                            slo=SLO_4H, return_series=False,
                            scenario_block=3)
    for b, c in zip(base, chunked):
        assert b.total_cost_usd == c.total_cost_usd
        assert b.median_latency_s == c.median_latency_s
        assert b.slo_met == c.slo_met


# ---------------------------------------------------------------------------
# load matrix + index map plumbing
# ---------------------------------------------------------------------------

def test_run_grid_series_and_matrix_paths_agree():
    # the matrix/index grid must equal the old stacked-loads grid
    series = run_grid(ALL_POLICY_TWINS, TRAFFICS, slo=SLO_4H,
                      return_series=True)
    k = 0
    for tr in TRAFFICS:
        loads = tr.hourly_loads().astype(np.float32)[None]
        for tw in ALL_POLICY_TWINS:
            solo = simulate_grid([tw], loads, slo=SLO_4H)[0]
            assert series[k].total_cost_usd == solo.total_cost_usd
            np.testing.assert_array_equal(series[k].processed,
                                          solo.processed)
            k += 1


def test_run_scenarios_aggregate_default_and_dedup():
    tr = TRAFFICS[0]
    scens = [Scenario("s1", ALL_POLICY_TWINS[0], tr),
             Scenario("s2", ALL_POLICY_TWINS[1], tr),
             Scenario("s3", ALL_POLICY_TWINS[0], TRAFFICS[1])]
    aggs = run_scenarios(scens, slo=SLO_4H)
    assert [a.name for a in aggs] == ["s1", "s2", "s3"]
    assert all(isinstance(a, GridSummary) for a in aggs)
    series = run_scenarios(scens, slo=SLO_4H, return_series=True)
    for s, a in zip(series, aggs):
        assert s.total_cost_usd == a.total_cost_usd


def test_simulate_grid_matrix_input_validation():
    tw = SimpleTwin("s", 1.0, 0.01, 0.1)
    year = np.ones((1, HOURS_PER_YEAR), np.float32)
    with pytest.raises(ValueError, match="exactly one"):
        simulate_grid([tw], year, load_matrix=year,
                      load_index=np.zeros(1, np.int32))
    with pytest.raises(ValueError, match="exactly one"):
        simulate_grid([tw])
    with pytest.raises(ValueError, match="load_index"):
        simulate_grid([tw], load_matrix=year)
    with pytest.raises(ValueError, match="out of range"):
        simulate_grid([tw], load_matrix=year,
                      load_index=np.asarray([3], np.int32))
    with pytest.raises(ValueError, match="twins"):
        simulate_grid([tw, tw], load_matrix=year,
                      load_index=np.zeros(1, np.int32))
    for bad_block in (0, -4096):
        with pytest.raises(ValueError, match="scenario_block"):
            simulate_grid([tw], year, return_series=False,
                          scenario_block=bad_block)
    # series mode can't honor a chunked memory bound — loud, not silent
    with pytest.raises(ValueError, match="scenario_block"):
        simulate_grid([tw], year, return_series=True, scenario_block=8)
