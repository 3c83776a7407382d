"""Device-resident histogram parity: the grid engines vs the host oracle.

The streaming-aggregate grid accumulates its quarter-octave latency
histogram ON DEVICE — an exact f64 ``segment_sum`` per time chunk on the
XLA path, compensated in-kernel triples on Pallas — and
``np_latency_histogram`` survives only as the parity oracle. These tests
pin the acceptance contract of that change:

* the histogram block of every engine's aggregate rows is BIT-IDENTICAL
  to ``np_latency_histogram`` over the series path's latency panel, for
  all five registered policies, on XLA and Pallas (interpret), through
  the chunked block driver, on a ``devices=4`` mesh, and on a chaos grid
  (``faults=``);
* no [B, T]-shaped intermediate exists anywhere in the XLA driver's
  computation (checked on the traced jaxpr, not just the output pytree)
  and the sharded round step returns O(B) aggregates only;
* bitwise-duplicate scenario rows — benign fault futures, tiled
  tournament grids — are simulated ONCE and their summary rows
  replicated (the dispatch-level ``_dedup_rows`` pass).

Mesh cases need ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
before the first jax import (the CI multi-device job exports it);
without it they skip.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import faults  # noqa: E402
from repro.core import simulate  # noqa: E402
from repro.core.simulate import (_agg_scan_uniform,  # noqa: E402
                                 _agg_scan_uniform_fault, _grid_agg_dispatch,
                                 _grid_scan, _grid_scan_fault_xla,
                                 _sharded_agg_fn, simulate_grid)
from repro.core.traffic import TrafficModel  # noqa: E402
from repro.core.twin import (AGG_DIM, AGG_SCALARS,  # noqa: E402
                             CARRY_DIM, QuickscalingTwin, SimpleTwin,
                             make_twin, np_latency_histogram,
                             registry_version)
from repro.kernels import ops  # noqa: E402

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "before the first jax import")

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

#: one-month horizon keeps the matrix fast; the engine treats the horizon
#: as opaque, so parity here is parity on the year
T_MONTH = 744

CHAOS = faults.FaultSchedule(
    specs=(faults.outage(rate_per_year=40),
           faults.disconnect(disconnect_frac=(0.2, 0.5))),
    n_futures=5, seed=3)


def _grid_arrays(n, t_bins=T_MONTH):
    twins = [ALL_POLICY_TWINS[i % len(ALL_POLICY_TWINS)] for i in range(n)]
    matrix = np.stack([tr.hourly_loads()[:t_bins] for tr in TRAFFICS]) \
        .astype(np.float32)
    index = np.arange(n, dtype=np.int32) % len(TRAFFICS)
    params = np.stack([tw.padded_params() for tw in twins])
    idx = np.asarray([tw.policy_index for tw in twins], np.int32)
    return twins, matrix, index, params, idx


def _oracle_hist(matrix, index, params, idx):
    """Host-oracle histogram: bin the SERIES path's latency panel with
    ``np_latency_histogram`` — exactly what the old engine shipped."""
    loads = matrix[index]
    _, (_, _, lat, _, _) = _grid_scan(
        jnp.asarray(loads), jnp.asarray(params), jnp.asarray(idx),
        registry_version(), 1.0)
    return np_latency_histogram(np.asarray(lat), loads)


# ---------------------------------------------------------------------------
# bit-parity vs the host oracle: all five policies, every engine
# ---------------------------------------------------------------------------

def test_device_hist_bit_identical_xla_all_policies():
    n = 10      # two scenarios per registered policy
    _, matrix, index, params, idx = _grid_arrays(n)
    oracle = _oracle_hist(matrix, index, params, idx)
    # unchunked and chunked drivers — the chunked one exercises the
    # donated block engine and the O(B·BINS) accumulator scatter
    for block in (None, 4):
        _, agg = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                    float("inf"), 0, block)
        np.testing.assert_array_equal(
            agg[:, AGG_SCALARS:].astype(np.float32), oracle)


def test_device_hist_bit_identical_pallas_all_policies():
    n = 10
    _, matrix, index, params, idx = _grid_arrays(n)
    oracle = _oracle_hist(matrix, index, params, idx)
    with ops.pallas_mode():
        for block in (None, 4):
            _, agg = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                        float("inf"), 0, block)
            np.testing.assert_array_equal(
                agg[:, AGG_SCALARS:].astype(np.float32), oracle)


@needs4
def test_device_hist_bit_identical_devices_4():
    n = 10
    _, matrix, index, params, idx = _grid_arrays(n)
    oracle = _oracle_hist(matrix, index, params, idx)
    _, agg = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                float("inf"), 0, 4, devices=4)
    np.testing.assert_array_equal(
        agg[:, AGG_SCALARS:].astype(np.float32), oracle)
    with ops.pallas_mode():
        _, agg_p = _grid_agg_dispatch(matrix, index, params, idx, 1.0,
                                      float("inf"), 0, 4, devices=4)
    np.testing.assert_array_equal(
        agg_p[:, AGG_SCALARS:].astype(np.float32), oracle)


def test_device_hist_bit_identical_chaos_grid():
    n = 6
    _, matrix, index, params, idx = _grid_arrays(n)
    sampled = faults.sample_futures(CHAOS, T_MONTH, 1.0)
    fg = faults.expand_grid(sampled, matrix, index)
    nf = fg.n_futures
    params_f = np.repeat(params, nf, axis=0)
    idx_f = np.repeat(idx, nf)
    fault = (fg.cap, fg.fmask, fg.fault_index)

    # chaos oracle: the fault SERIES path's latency panel, host-binned
    # weighted by the (load-fault-perturbed) arrive series
    loads = fg.load_matrix[fg.load_index]
    caps = fg.cap[fg.fault_index]
    _, (_, _, lat, _, _) = _grid_scan_fault_xla(
        jnp.asarray(loads), jnp.asarray(caps), jnp.asarray(params_f),
        jnp.asarray(idx_f), registry_version(), 1.0)
    oracle = np_latency_histogram(np.asarray(lat), loads)

    for block in (None, 4):
        _, agg = _grid_agg_dispatch(fg.load_matrix, fg.load_index, params_f,
                                    idx_f, 1.0, float("inf"), 0, block,
                                    fault=fault)
        np.testing.assert_array_equal(
            agg[:, AGG_SCALARS:].astype(np.float32), oracle)
    with ops.pallas_mode():
        _, agg_p = _grid_agg_dispatch(fg.load_matrix, fg.load_index,
                                      params_f, idx_f, 1.0, float("inf"),
                                      0, 4, fault=fault)
    np.testing.assert_array_equal(
        agg_p[:, AGG_SCALARS:].astype(np.float32), oracle)


# ---------------------------------------------------------------------------
# no [B, T] intermediate anywhere in the device-resident XLA driver
# ---------------------------------------------------------------------------

def _collect_shapes(jaxpr, out):
    """Every intermediate/output aval shape in the jaxpr, recursively."""
    from jax._src import core as jcore
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v.aval, "shape"):
                out.add(tuple(v.aval.shape))
        for p in eqn.params.values():
            cj = getattr(p, "jaxpr", None)
            if isinstance(p, jcore.ClosedJaxpr):
                _collect_shapes(p.jaxpr, out)
            elif cj is not None:
                _collect_shapes(cj, out)
    return out


def test_no_bt_intermediate_in_xla_driver():
    # T must exceed the 1024-bin time-chunk cap, else one chunk IS the
    # horizon; 2048 gives two 1024-bin chunks
    t_bins, k, b = 2048, 3, 7
    matrix = jnp.ones((k, t_bins), jnp.float32)
    lidx = jnp.zeros((b,), jnp.int32)
    params = jnp.ones((b, 6), jnp.float32)
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda m, li, p: _agg_scan_uniform(m, li, p, 0, 1.0,
                                               float("inf"), 0))(
            matrix, lidx, params)
        shapes = _collect_shapes(jaxpr.jaxpr, set())
        assert (b, t_bins) not in shapes, "a [B, T] panel is staged"
        jaxpr_f = jax.make_jaxpr(
            lambda m, li, c, f, fi, p: _agg_scan_uniform_fault(
                m, li, c, f, fi, p, 0, 1.0, float("inf"), 0))(
            matrix, lidx, jnp.ones((4, t_bins), jnp.float32),
            jnp.zeros((4, t_bins), jnp.float32), jnp.zeros((b,), jnp.int32),
            params)
        shapes_f = _collect_shapes(jaxpr_f.jaxpr, set())
        assert (b, t_bins) not in shapes_f, "a [B, T] fault panel is staged"


def test_sharded_round_step_outputs_are_o_n():
    block = 8
    _, matrix, index, params, _ = _grid_arrays(block)
    p_block = np.tile(ALL_POLICY_TWINS[0].padded_params(),
                      (block, 1)).astype(np.float32)
    fn = _sharded_agg_fn(1, registry_version(), 1.0, float("inf"), 0,
                         "xla", block)
    with jax.enable_x64(True):
        out = fn(jnp.asarray(matrix), jnp.asarray(index[None]),
                 jnp.asarray(p_block[None]), jnp.asarray([0], np.int32))
    shapes = [tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(out)]
    assert shapes == [(1, block, CARRY_DIM), (1, block, AGG_DIM)]
    assert all(T_MONTH not in s for s in shapes)


# ---------------------------------------------------------------------------
# duplicate-scenario dedup: one scan per distinct scenario, replicated rows
# ---------------------------------------------------------------------------

def test_benign_futures_simulated_once(monkeypatch):
    n = 4
    twins, matrix, index, params, idx = _grid_arrays(n)
    # a sparse schedule leaves several futures event-free (benign); their
    # (cap, fmask) rows are bitwise identical, so _dedup_rows inside the
    # dispatch collapses them to one simulated row per base scenario
    sparse = faults.FaultSchedule(
        specs=(faults.outage(rate_per_year=2),), n_futures=8, seed=1)
    sampled = faults.sample_futures(sparse, T_MONTH, 1.0)
    benign = faults.benign_futures(sampled)
    assert benign.sum() > 1, "seed must produce >1 benign futures"

    calls = []
    real_scan = simulate._grid_scan_agg

    def spy(loads, *args, **kw):
        calls.append(int(loads.shape[0]))
        return real_scan(loads, *args, **kw)

    monkeypatch.setattr(simulate, "_grid_scan_agg", spy)
    rows = simulate_grid(twins, load_matrix=matrix, load_index=index,
                         return_series=False, bin_hours=1.0, faults=sampled)
    nf = sampled.n_futures
    expected = n * (nf - int(benign.sum()) + 1)
    assert calls == [expected]          # one scan over the deduped rows
    assert len(rows) == n * nf          # ...but every row reported

    # replicated rows are bit-identical to a dedup-disabled dispatch
    monkeypatch.setattr(simulate, "_grid_scan_agg", real_scan)
    monkeypatch.setattr(simulate, "_dedup_rows", lambda *a, **kw: None)
    fg = faults.expand_grid(sampled, matrix, index)
    carry_full, agg_full = _grid_agg_dispatch(
        fg.load_matrix, fg.load_index, np.repeat(params, nf, axis=0),
        np.repeat(idx, nf), 1.0, float("inf"), 0, None,
        fault=(fg.cap, fg.fmask, fg.fault_index))
    from repro.core.simulate import _summarise_aggregates
    full = _summarise_aggregates(
        [f"{tw.name}/f{f}" for tw in twins for f in range(nf)],
        [tw for tw in twins for _ in range(nf)], carry_full[:, 0],
        agg_full, None, None, 0.0, 1.0, T_MONTH, fg.load_matrix,
        fg.load_index)
    for got, want in zip(rows, full):
        for k, u in vars(got).items():
            v = vars(want)[k]
            if isinstance(u, np.ndarray):
                np.testing.assert_array_equal(u, v)
            elif isinstance(u, float) and np.isnan(u):
                assert np.isnan(v)
            else:
                assert u == v, (k, u, v)


def test_tiled_tournament_deduped_and_replicated(monkeypatch):
    """A grid that re-runs identical (load, params, policy) rows — the
    tournament-baseline shape — is simulated once per distinct scenario
    and replicated bit-identically, with no fault grid in play."""
    n = 6
    _, matrix, index, params, idx = _grid_arrays(n)
    reps = 4
    index_t = np.tile(index, reps)
    params_t = np.tile(params, (reps, 1))
    idx_t = np.tile(idx, reps)

    calls = []
    real_scan = simulate._grid_scan_agg

    def spy(loads, *args, **kw):
        calls.append(int(loads.shape[0]))
        return real_scan(loads, *args, **kw)

    monkeypatch.setattr(simulate, "_grid_scan_agg", spy)
    carry, agg = _grid_agg_dispatch(matrix, index_t, params_t, idx_t,
                                    1.0, float("inf"), 0, None)
    assert calls == [n]                 # 4x-tiled grid -> n distinct scans
    assert carry.shape[0] == n * reps and agg.shape[0] == n * reps
    for r in range(1, reps):
        np.testing.assert_array_equal(agg[r * n:(r + 1) * n], agg[:n])
        np.testing.assert_array_equal(carry[r * n:(r + 1) * n], carry[:n])

    # and the replica block equals a dedup-disabled run of the base grid
    monkeypatch.setattr(simulate, "_grid_scan_agg", real_scan)
    monkeypatch.setattr(simulate, "_dedup_rows", lambda *a, **kw: None)
    carry_base, agg_base = _grid_agg_dispatch(matrix, index, params, idx,
                                              1.0, float("inf"), 0, None)
    np.testing.assert_array_equal(agg[:n], agg_base)
    np.testing.assert_array_equal(carry[:n], carry_base)
