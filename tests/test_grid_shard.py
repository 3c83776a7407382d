"""The sharded million-scenario grid engine vs the one-device anchors.

Acceptance contract of the scenario-axis sharding refactor:

* ``devices=D`` dispatch is BIT-IDENTICAL to both the single-device
  chunked engine and the unchunked anchor — including a tail where N is
  divisible by neither the block size nor the device count, across all
  five registered policies and both backends (XLA and Pallas interpret);
* the ``shard_map`` round step runs the same policy-uniform aggregate
  scan per shard that the one-device engine runs (unit-checked on a
  1-device mesh, so this holds in every environment);
* ``_agg_block_plan`` produces policy-uniform blocks that cover each
  scenario exactly once, in stable per-policy order;
* ``agg_auto_block`` derives the streamed block size from the horizon
  length, dtype, and staged-panel count against the ~150 MB budget —
  the device-resident XLA path (``panels=0``) budgets its [B, chunk]
  transients + aggregate rows, not a [B, T] panel it no longer stages;
* replication fall-backs in ``distributed.sharding`` warn once, loudly.

Multi-device cases need ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
exported before the first jax import (the CI multi-device job does);
without it they skip rather than sharding a 1-device mesh.
"""
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.simulate import (AGG_AUTO_BLOCK,  # noqa: E402
                                 AGG_BLOCK_BUDGET_BYTES, _agg_block_plan,
                                 _agg_scan_uniform, _grid_agg_dispatch,
                                 _sharded_agg_fn, agg_auto_block,
                                 simulate_grid)
from repro.core.slo import SLO  # noqa: E402
from repro.core.traffic import HOURS_PER_YEAR, TrafficModel  # noqa: E402
from repro.core.twin import (AGG_DIM, CARRY_DIM, PARAM_DIM,  # noqa: E402
                             QuickscalingTwin, SimpleTwin, make_twin,
                             registry_version)
from repro.core.whatif import run_grid  # noqa: E402
from repro.distributed import sharding  # noqa: E402
from repro.kernels import ops  # noqa: E402

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "before the first jax import")

SLO_4H = SLO(limit_s=4 * 3600, met_fraction=0.95)

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

#: one-month horizon keeps the parity matrix fast; the engine treats the
#: horizon as opaque, so parity here is parity on the year
T_MONTH = 744


def _grid_arrays(n, t_bins=T_MONTH):
    twins = [ALL_POLICY_TWINS[i % len(ALL_POLICY_TWINS)] for i in range(n)]
    matrix = np.stack([tr.hourly_loads()[:t_bins] for tr in TRAFFICS]) \
        .astype(np.float32)
    index = np.arange(n, dtype=np.int32) % len(TRAFFICS)
    params = np.stack([tw.padded_params() for tw in twins])
    idx = np.asarray([tw.policy_index for tw in twins], np.int32)
    return twins, matrix, index, params, idx


# ---------------------------------------------------------------------------
# block-size budget: derived from horizon length + dtype
# ---------------------------------------------------------------------------

def test_agg_auto_block_derives_from_horizon_and_budget():
    from repro.core.simulate import _agg_time_chunk

    # device-resident default (panels=0): the per-row working set is the
    # scan pipeline's [B, chunk] transients (6 buffers) + the AGG_DIM
    # aggregate row, NOT a [B, T] panel — year blocks grow past the old
    # panel-bound 4480
    block = agg_auto_block(HOURS_PER_YEAR)
    assert block == AGG_AUTO_BLOCK
    assert block % 128 == 0
    per_row = (6 * _agg_time_chunk(HOURS_PER_YEAR) + 4 * AGG_DIM) * 4
    assert block * per_row <= AGG_BLOCK_BUDGET_BYTES
    assert (block + 128) * per_row > AGG_BLOCK_BUDGET_BYTES
    assert block > agg_auto_block(HOURS_PER_YEAR, panels=1)

    # panel-staging backends (Pallas) declare their panel count; one
    # benign [B, T] panel fits the budget tight, and a chaos grid's
    # three panels (loads_t + caps_t + fmask_t) shrink the block ~3x —
    # the historical under-budgeting bug was counting only one
    p1 = agg_auto_block(HOURS_PER_YEAR, panels=1)
    assert p1 % 128 == 0
    assert p1 * HOURS_PER_YEAR * 4 <= AGG_BLOCK_BUDGET_BYTES
    assert (p1 + 128) * HOURS_PER_YEAR * 4 > AGG_BLOCK_BUDGET_BYTES
    p3 = agg_auto_block(HOURS_PER_YEAR, panels=3)
    assert p3 * HOURS_PER_YEAR * 4 * 3 <= AGG_BLOCK_BUDGET_BYTES
    assert (p3 + 128) * HOURS_PER_YEAR * 4 * 3 > AGG_BLOCK_BUDGET_BYTES

    # wider dtypes halve the panel block; shorter horizons grow it
    assert agg_auto_block(HOURS_PER_YEAR, dtype_bytes=8,
                          panels=1) <= p1 // 2 + 128
    assert agg_auto_block(HOURS_PER_YEAR // 4, panels=1) >= 4 * p1 - 512
    # clamps: calibration-length horizons cap at 65536 lanes, pathological
    # horizons never chunk below one lane group
    assert agg_auto_block(1, panels=1) == 65536
    assert agg_auto_block(10 ** 9, panels=1) == 128
    # panel-free blocks stop scaling with the horizon once the time
    # chunking caps the transient width — a pathological horizon still
    # streams thousands of scenarios per block instead of 128
    assert agg_auto_block(10 ** 9) == agg_auto_block(10 ** 6)
    assert 128 <= agg_auto_block(1) <= 65536


# ---------------------------------------------------------------------------
# policy-uniform block plan
# ---------------------------------------------------------------------------

def test_agg_block_plan_covers_each_scenario_once_policy_uniform():
    rng = np.random.default_rng(7)
    idx = rng.integers(0, 5, size=23).astype(np.int32)
    positions, block_policy = _agg_block_plan(idx, block=5)
    assert positions.shape[1] == 5
    assert positions.shape[0] == len(block_policy)
    flat = positions.reshape(-1)
    valid = flat[flat >= 0]
    # exactly-once cover
    np.testing.assert_array_equal(np.sort(valid), np.arange(23))
    for b in range(positions.shape[0]):
        row = positions[b][positions[b] >= 0]
        # every block is single-policy and matches its label
        assert row.size > 0
        np.testing.assert_array_equal(idx[row], block_policy[b])
    # stable: within one policy, scenarios keep grid order
    for p in np.unique(idx):
        mine = valid[idx[valid] == p]
        np.testing.assert_array_equal(mine, np.where(idx == p)[0])


def test_agg_block_plan_empty_grid():
    positions, block_policy = _agg_block_plan(np.zeros(0, np.int32), 4)
    assert positions.shape == (0, 4) and block_policy.size == 0


# ---------------------------------------------------------------------------
# the shard_map round step: unit parity on a 1-device mesh (any env)
# ---------------------------------------------------------------------------

def test_sharded_round_step_matches_uniform_scan_one_device():
    block = 8
    _, matrix, index, params, _ = _grid_arrays(block)
    lidx = index.astype(np.int32)
    p_block = np.tile(ALL_POLICY_TWINS[0].padded_params(),
                      (block, 1)).astype(np.float32)
    fn = _sharded_agg_fn(1, registry_version(), 1.0, float("inf"), 0,
                         "xla", block)
    # the round step keeps the histogram in-body and traces f64; every
    # call site enters under enable_x64 (see _run_blocks_sharded)
    with jax.enable_x64(True):
        carry, agg = fn(jnp.asarray(matrix), jnp.asarray(lidx[None]),
                        jnp.asarray(p_block[None]),
                        jnp.asarray([0], np.int32))
        ref_c, ref_a = _agg_scan_uniform(
            jnp.asarray(matrix), jnp.asarray(lidx), jnp.asarray(p_block),
            0, 1.0, float("inf"), 0)
    assert np.asarray(agg).shape == (1, block, AGG_DIM)  # no [B, T] output
    np.testing.assert_array_equal(np.asarray(carry[0]), np.asarray(ref_c))
    np.testing.assert_array_equal(np.asarray(agg[0]), np.asarray(ref_a))


def test_mesh_round_has_a_stable_program_name():
    """The round step is a jit of a named function, so a profiler trace
    names its program ``jit__mesh_agg_round`` on any mesh."""
    block = 8
    _, matrix, index, _, _ = _grid_arrays(block)
    fn = _sharded_agg_fn(1, registry_version(), 1.0, float("inf"), 0,
                         "xla", block)
    with jax.enable_x64(True):
        low = fn.lower(jnp.asarray(matrix),
                       jnp.zeros((1, block), jnp.int32),
                       jnp.zeros((1, block, PARAM_DIM), jnp.float32),
                       jnp.zeros((1,), jnp.int32))
    assert "module @jit__mesh_agg_round" in low.as_text()


# ---------------------------------------------------------------------------
# sharded dispatch == chunked == unchunked, bit for bit (4-device mesh)
# ---------------------------------------------------------------------------

@needs4
def test_sharded_dispatch_bit_identical_xla_all_policies():
    # n=23 is divisible by neither block=5 nor devices=4: per-policy tail
    # pads AND a dummy-block round both execute
    n = 23
    _, matrix, index, params, idx = _grid_arrays(n)
    base_c, base_a = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                        float(SLO_4H.limit_s), 0, None)
    chunk_c, chunk_a = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                          float(SLO_4H.limit_s), 0, 5)
    shard_c, shard_a = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                          float(SLO_4H.limit_s), 0, 5,
                                          devices=4)
    np.testing.assert_array_equal(chunk_c, base_c)
    np.testing.assert_array_equal(chunk_a, base_a)
    np.testing.assert_array_equal(shard_c, base_c)
    np.testing.assert_array_equal(shard_a, base_a)
    assert shard_c.shape == (n, CARRY_DIM) and shard_a.shape == (n, AGG_DIM)


@needs4
def test_sharded_dispatch_bit_identical_pallas():
    n = 23
    _, matrix, index, params, idx = _grid_arrays(n)
    with ops.pallas_mode():
        chunk_c, chunk_a = _grid_agg_dispatch(matrix, index, params, idx,
                                              1.0, float("inf"), 0, 5)
        shard_c, shard_a = _grid_agg_dispatch(matrix, index, params, idx,
                                              1.0, float("inf"), 0, 5,
                                              devices=4)
    np.testing.assert_array_equal(shard_c, chunk_c)
    np.testing.assert_array_equal(shard_a, chunk_a)


@needs4
def test_sharded_dispatch_uneven_rounds_devices_2():
    # 3 policy blocks over 2 devices: one dummy pad block, two rounds
    n = 11
    _, matrix, index, params, idx = _grid_arrays(n)
    base_c, base_a = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                        float("inf"), 0, None)
    shard_c, shard_a = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                          float("inf"), 0, 4, devices=2)
    np.testing.assert_array_equal(shard_c, base_c)
    np.testing.assert_array_equal(shard_a, base_a)


@needs4
def test_simulate_grid_devices_end_to_end():
    n = 10
    twins, matrix, index, _, _ = _grid_arrays(n, t_bins=HOURS_PER_YEAR)
    base = simulate_grid(twins, load_matrix=matrix, load_index=index,
                         slo=SLO_4H, return_series=False)
    shard = simulate_grid(twins, load_matrix=matrix, load_index=index,
                          slo=SLO_4H, return_series=False,
                          scenario_block=4, devices=4)
    for b, s in zip(base, shard):
        assert b.total_cost_usd == s.total_cost_usd
        assert b.median_latency_s == s.median_latency_s
        assert b.pct_latency_met == s.pct_latency_met
        assert b.slo_met == s.slo_met


@needs4
def test_run_grid_devices_passthrough():
    base = run_grid(ALL_POLICY_TWINS, TRAFFICS, slo=SLO_4H)
    shard = run_grid(ALL_POLICY_TWINS, TRAFFICS, slo=SLO_4H,
                     scenario_block=4, devices=4)
    for b, s in zip(base, shard):
        assert b.name == s.name
        assert b.total_cost_usd == s.total_cost_usd


# ---------------------------------------------------------------------------
# devices= validation: loud, before any dispatch
# ---------------------------------------------------------------------------

def test_simulate_grid_devices_validation():
    tw = SimpleTwin("s", 1.0, 0.01, 0.1)
    year = np.ones((1, HOURS_PER_YEAR), np.float32)
    with pytest.raises(ValueError, match="streaming-aggregate"):
        simulate_grid([tw], year, return_series=True, devices=1)
    with pytest.raises(ValueError, match="devices"):
        simulate_grid([tw], year, return_series=False, devices=0)
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        simulate_grid([tw], year, return_series=False,
                      devices=jax.device_count() + 1)


# ---------------------------------------------------------------------------
# replication fall-backs warn once, naming axis and sizes
# ---------------------------------------------------------------------------

def test_replication_fallback_warns_once_per_site():
    sharding._REPLICATION_WARNED.clear()
    with pytest.warns(RuntimeWarning, match="replication"):
        sharding._warn_replicated("test(x)", "scenario", 23, 4)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sharding._warn_replicated("test(x)", "scenario", 23, 4)
    assert not caught                      # identical fall-back: silent
    with pytest.warns(RuntimeWarning, match="mesh axis 'scenario'"):
        sharding._warn_replicated("test(x)", "scenario", 25, 4)


@needs4
def test_constrain_indivisible_dim_warns_and_replicates():
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("d",))
    sharding._REPLICATION_WARNED.clear()
    sharding.set_activation_mesh(mesh, {"batch": "d"})
    try:
        x = jnp.zeros((6, 3))              # 6 % 4 != 0 -> replicate + warn
        with pytest.warns(RuntimeWarning, match="NO parallelism"):
            y = sharding.constrain(x, "batch", None)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    finally:
        sharding.set_activation_mesh(None)


# ---------------------------------------------------------------------------
# scenario-minor staging: loads_t= operands equal loads= on both kernels
# ---------------------------------------------------------------------------

def test_kernel_loads_t_staging_matches_loads():
    from repro.core.twin import policy_onehot
    from repro.kernels.policy_scan import policy_grid_agg, policy_grid_scan
    n = 13
    _, matrix, index, params, idx = _grid_arrays(n, t_bins=97)
    loads = matrix[index]
    onehot = policy_onehot(idx)
    a = policy_grid_agg(loads, params, onehot, 1.0)
    b = policy_grid_agg(None, params, onehot, 1.0,
                        loads_t=np.ascontiguousarray(loads.T))
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    sa = policy_grid_scan(loads, params, onehot, 1.0)
    sb = policy_grid_scan(None, params, onehot, 1.0,
                          loads_t=np.ascontiguousarray(loads.T))
    for x, y in zip(jax.tree_util.tree_leaves(sa),
                    jax.tree_util.tree_leaves(sb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for f in (policy_grid_scan, policy_grid_agg):
        with pytest.raises(ValueError, match="exactly one"):
            f(None, params, onehot, 1.0)
        with pytest.raises(ValueError, match="exactly one"):
            f(loads, params, onehot, 1.0, loads_t=loads.T)
