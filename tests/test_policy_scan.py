"""The fused Pallas scenario-grid backend vs the XLA ``lax.switch`` anchor.

Acceptance contract of the lane-vectorized refactor:

* the pure-jnp lane oracle (``kernels.ref.policy_grid_scan``) and the
  Pallas kernel (interpret mode on CPU) match the XLA backend within
  1e-5 relative on ALL FIVE output series for a mixed-policy 64-scenario
  year grid;
* ``simulate_grid`` routes through whichever backend the ``pallas_mode``
  switch selects, end to end, with identical summaries;
* the default XLA hourly full-year path stays bit-identical (the seed
  parity tests in test_twin_policies.py cover that side untouched).
"""
import contextlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.simulate import (_grid_scan, _grid_scan_xla,  # noqa: E402
                                 SimulationResult, simulate_grid,
                                 simulate_year)
from repro.core.traffic import HOURS_PER_YEAR, TrafficModel  # noqa: E402
from repro.core.twin import (QuickscalingTwin, SimpleTwin,  # noqa: E402
                             lane_branches, make_twin, policy_branches,
                             policy_names, policy_onehot, registry_version)
from repro.core.whatif import run_grid  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.policy_scan import policy_grid_scan  # noqa: E402

SERIES = ("processed", "queue", "latency", "cost", "dropped")


def _mixed_grid(n: int):
    """n scenarios cycling through every registered policy x traffic."""
    base = [
        SimpleTwin("fifo", 1.9512, 0.0082, 0.15),
        QuickscalingTwin("quick", 1.9512, 0.0082, 0.15),
        make_twin("auto", "autoscale", max_rps=0.5, usd_per_hour=0.002,
                  base_latency_s=0.1, max_instances=32, scale_up_hours=3),
        make_twin("shed", "shed", max_rps=1.0, usd_per_hour=0.0082,
                  base_latency_s=0.15, queue_cap_hours=2),
        make_twin("batch", "batch_window", max_rps=6.15, usd_per_hour=0.0703,
                  base_latency_s=0.06, window_hours=6),
    ]
    gs = np.linspace(1.0, 1.7, -(-n // len(base)))
    twins, loads = [], []
    for g in gs:
        hl = TrafficModel.honda_default(f"g{g:.2f}", R=3.5,
                                        G=float(g)).hourly_loads()
        for tw in base:
            twins.append(tw)
            loads.append(hl)
    twins, loads = twins[:n], loads[:n]
    params = np.stack([tw.padded_params() for tw in twins])
    idx = np.asarray([tw.policy_index for tw in twins], np.int32)
    return twins, np.stack(loads).astype(np.float32), params, idx


def _xla(loads, params, idx, dt=1.0):
    return _grid_scan_xla(jnp.asarray(loads), jnp.asarray(params),
                          jnp.asarray(idx), registry_version(), dt)


def _assert_series_close(outs_a, outs_b, rtol=1e-5):
    for name, a, b in zip(SERIES, outs_a, outs_b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        denom = np.maximum(np.abs(b), 1e-6 * max(np.abs(b).max(), 1.0))
        rel = np.abs(a - b) / denom
        assert rel.max() <= rtol, (name, rel.max())


# ---------------------------------------------------------------------------
# the acceptance grid: 64 mixed-policy scenarios over the full year
# ---------------------------------------------------------------------------

def test_ref_lane_oracle_matches_xla_switch_64():
    _, loads, params, idx = _mixed_grid(64)
    q_end, outs_x = _xla(loads, params, idx)
    carry_end, outs_r = ref.policy_grid_scan(
        jnp.asarray(loads), jnp.asarray(params),
        jnp.asarray(policy_onehot(idx)), 1.0)
    _assert_series_close(outs_r, outs_x)
    np.testing.assert_allclose(np.asarray(carry_end[:, 0]),
                               np.asarray(q_end), rtol=1e-5)


def test_pallas_kernel_matches_xla_switch_64():
    _, loads, params, idx = _mixed_grid(64)
    q_end, outs_x = _xla(loads, params, idx)
    carry_end, outs_p = policy_grid_scan(
        jnp.asarray(loads), jnp.asarray(params),
        jnp.asarray(policy_onehot(idx)), 1.0)
    _assert_series_close(outs_p, outs_x)
    np.testing.assert_allclose(np.asarray(carry_end[:, 0]),
                               np.asarray(q_end), rtol=1e-5)


def test_pallas_kernel_scenario_padding_and_lane_blocking():
    """N not a lane multiple + lanes < N both hit the padding/grid paths."""
    _, loads, params, idx = _mixed_grid(13)
    q_end, outs_x = _xla(loads, params, idx)
    for lanes in (8, 128):
        carry_end, outs_p = policy_grid_scan(
            jnp.asarray(loads), jnp.asarray(params),
            jnp.asarray(policy_onehot(idx)), 1.0, lanes=lanes)
        _assert_series_close(outs_p, outs_x)
        np.testing.assert_allclose(np.asarray(carry_end[:, 0]),
                                   np.asarray(q_end), rtol=1e-5)


def test_pallas_kernel_short_horizon_subhour_bins():
    """A horizon the default chunk doesn't divide falls back cleanly, at a
    calibration-style sub-hour bin width."""
    rng = np.random.default_rng(0)
    loads = rng.uniform(0.0, 500.0, (5, 97)).astype(np.float32)
    twins = _mixed_grid(5)[0]
    params = np.stack([tw.padded_params() for tw in twins])
    idx = np.asarray([tw.policy_index for tw in twins], np.int32)
    q_end, outs_x = _xla(loads, params, idx, dt=1.0 / 60.0)
    carry_end, outs_p = policy_grid_scan(
        jnp.asarray(loads), jnp.asarray(params),
        jnp.asarray(policy_onehot(idx)), 1.0 / 60.0)
    _assert_series_close(outs_p, outs_x)


# ---------------------------------------------------------------------------
# backend selection end to end
# ---------------------------------------------------------------------------

def test_grid_scan_selects_pallas_backend():
    _, loads, params, idx = _mixed_grid(10)
    args = (jnp.asarray(loads), jnp.asarray(params), jnp.asarray(idx),
            registry_version(), 1.0)
    q_x, outs_x = _grid_scan(*args)
    assert not ops.pallas_enabled()
    with ops.pallas_mode():
        q_p, outs_p = _grid_scan(*args)
    _assert_series_close(outs_p, outs_x)
    np.testing.assert_allclose(np.asarray(q_p), np.asarray(q_x), rtol=1e-5)


def test_simulate_grid_end_to_end_under_pallas_mode():
    twins, loads, _, _ = _mixed_grid(5)
    sims_x = simulate_grid(twins, loads)
    with ops.pallas_mode():
        sims_p = simulate_grid(twins, loads)
    for sx, sp in zip(sims_x, sims_p):
        assert sp.total_cost_usd == pytest.approx(sx.total_cost_usd,
                                                  rel=1e-5)
        assert sp.mean_latency_s == pytest.approx(sx.mean_latency_s,
                                                  rel=1e-5)
        assert sp.dropped_records == pytest.approx(sx.dropped_records,
                                                   rel=1e-5, abs=1e-3)
        np.testing.assert_allclose(sp.processed, sx.processed, rtol=1e-5)


def test_run_grid_under_pallas_mode_mixed_policies():
    twins = _mixed_grid(5)[0]
    traffics = [TrafficModel.honda_default("nom"),
                TrafficModel.honda_default("high", G=1.5)]
    rows_x = [(s.name, s.total_cost_usd) for s in run_grid(twins, traffics)]
    with ops.pallas_mode():
        rows_p = [(s.name, s.total_cost_usd)
                  for s in run_grid(twins, traffics)]
    for (nx, cx), (np_, cp) in zip(rows_x, rows_p):
        assert nx == np_
        assert cp == pytest.approx(cx, rel=1e-5)


def test_uniform_policy_index_lane_path_matches_blend():
    """The calibration route: a uniform-policy lane block selected by a
    (traced) scalar index runs one lax.switch branch and matches both the
    masked blend and the XLA anchor."""
    twins, loads, params, _ = _mixed_grid(5)
    for tw in twins:
        n = loads.shape[0]
        p_block = np.tile(tw.padded_params(), (n, 1))
        idx = np.full(n, tw.policy_index, np.int32)
        q_end, outs_x = _xla(loads, p_block, idx)
        ce_u, outs_u = ops.policy_scan(
            jnp.asarray(loads), jnp.asarray(p_block),
            policy_index=jnp.int32(tw.policy_index), differentiable=True)
        ce_b, outs_b = ops.policy_scan(
            jnp.asarray(loads), jnp.asarray(p_block),
            jnp.asarray(policy_onehot(idx)), differentiable=True)
        _assert_series_close(outs_u, outs_x)
        _assert_series_close(outs_u, outs_b, rtol=1e-6)
    # the ambiguity is rejected before backend dispatch — identically on
    # the ref path and under the Pallas switch (no silent zero grids)
    for enable_pallas in (False, True):
        ctx = ops.pallas_mode() if enable_pallas else \
            contextlib.nullcontext()
        with ctx:
            with pytest.raises(ValueError, match="exactly one"):
                ops.policy_scan(jnp.asarray(loads), jnp.asarray(p_block))
            with pytest.raises(ValueError, match="exactly one"):
                ops.policy_scan(jnp.asarray(loads), jnp.asarray(p_block),
                                jnp.asarray(policy_onehot(idx)),
                                policy_index=jnp.int32(0))
    with pytest.raises(ValueError, match="exactly one"):
        ref.policy_grid_scan(jnp.asarray(loads), jnp.asarray(p_block))


# ---------------------------------------------------------------------------
# registry: both step forms exist and the onehot selector is sound
# ---------------------------------------------------------------------------

def test_every_policy_has_both_step_forms():
    assert len(lane_branches()) == len(policy_branches()) \
        == len(policy_names())
    assert all(callable(f) for f in lane_branches())


def test_policy_onehot_rows():
    idx = np.asarray([0, 3, 1], np.int32)
    oh = policy_onehot(idx)
    assert oh.shape == (3, len(policy_names()))
    np.testing.assert_array_equal(oh.sum(axis=1), 1.0)
    np.testing.assert_array_equal(np.argmax(oh, axis=1), idx)


# ---------------------------------------------------------------------------
# satellites: input validation survives ``python -O``; dropped default
# matches the horizon
# ---------------------------------------------------------------------------

def test_simulate_grid_input_checks_raise_value_error():
    tw = SimpleTwin("s", 1.0, 0.01, 0.1)
    year = np.ones(HOURS_PER_YEAR, np.float32)
    with pytest.raises(ValueError, match=r"\[N, T\]"):
        simulate_grid([tw], year)                       # 1-D, not a grid
    with pytest.raises(ValueError, match="twins"):
        simulate_grid([tw, tw], year[None])             # count mismatch
    with pytest.raises(ValueError, match="year"):
        simulate_year(tw, np.ones(100, np.float32))     # short horizon
    # the checks are real raises, not ``assert`` statements stripped by -O
    import inspect

    from repro.core import simulate as S
    src = inspect.getsource(S.simulate_grid) + inspect.getsource(
        S.simulate_year)
    assert "assert " not in src.replace("assert_", "")


def test_simulation_result_dropped_defaults_to_horizon():
    h = np.zeros(HOURS_PER_YEAR)
    sim = SimulationResult(
        name="x", twin=SimpleTwin("s", 1.0, 0.01, 0.1), load=h,
        processed=h, queue=h, latency_s=h, cost_usd=h, total_cost_usd=0.0,
        backlog_s=0.0, backlog_cost_usd=0.0, mean_throughput_rph=0.0,
        max_throughput_rph=0.0, median_latency_s=0.0, mean_latency_s=0.0,
        pct_latency_met=100.0, pct_hours_met=100.0, slo_met=None)
    assert sim.dropped.shape == h.shape
    # elementwise use against the other hourly series must be well-formed
    assert (sim.processed + sim.dropped).shape == h.shape
    with pytest.raises(ValueError, match="dropped"):
        SimulationResult(
            name="x", twin=SimpleTwin("s", 1.0, 0.01, 0.1), load=h,
            processed=h, queue=h, latency_s=h, cost_usd=h,
            total_cost_usd=0.0, backlog_s=0.0, backlog_cost_usd=0.0,
            mean_throughput_rph=0.0, max_throughput_rph=0.0,
            median_latency_s=0.0, mean_latency_s=0.0, pct_latency_met=100.0,
            pct_hours_met=100.0, slo_met=None, dropped=np.zeros(7))
