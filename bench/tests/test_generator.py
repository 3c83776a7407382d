"""The traffic generator on the CPU: the committed sweep's requests drawn
as the formula below draws them, load rows at any bin width that divides
the hour, a shared load pool, the inputs it refuses, and a check whose
blocks follow the horizon."""
import json
import os

import numpy as np
import pytest

from bench import check, generator, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
SEED = 2**31 + 4242
ROWS = 40


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CFG = _json("configs", "telemetry-year.json")
MIX = dict(_json("traffic", "year-mixed.json"), rows=ROWS)
SPEC = CFG["load_uncertainty"]


class Recorder:
    """The system's entry points as the generator calls them, recording
    the sweep's twins and keyword arguments instead of running it."""

    def __init__(self):
        self.twins = self.kwargs = None

    def slo(self, spec):
        return spec

    def twin(self, name, policy, params):
        return policy, np.asarray(params)

    def simulate_grid(self, twins, **kwargs):
        self.twins, self.kwargs = twins, kwargs


def _sweep(cfg=CFG, mix=MIX, seed=SEED):
    api = Recorder()
    return generator.SweepTraffic(cfg, mix, seed, api), api


# --- the formula the committed sweep cells were measured with ------------

def formula_loads(seed, n):
    g = np.linspace(SPEC["growth"][0], SPEC["growth"][1], SPEC["curves"])
    base = reference.honda_loads([SPEC["base_rps"]] * len(g), g)[
        :, :CFG["horizon_bins"]].astype(np.float32)
    rng = generator.rng_for(seed, generator.SETUP)
    out = rng.standard_normal((n, CFG["horizon_bins"]), dtype=np.float32)
    out *= np.float32(SPEC["noise_sigma"])
    np.exp(out, out=out)
    out *= base[np.arange(n) % len(g)]
    out *= rng.uniform(*SPEC["scale"], (n, 1)).astype(np.float32)
    return out


def formula_request(seed, stream, index, n):
    """(load index, each row's policy, each row's parameters)."""
    rng = generator.rng_for(seed, stream, index)
    perm = rng.permutation(n).astype(np.int32)
    policies, space = CFG["policies"], CFG["sweep_space"]
    policy, params = [None] * n, [None] * n
    for j, name in enumerate(policies):
        rows = range(j, n, len(policies))
        cap = rng.uniform(*space["capacity"], len(rows))
        cols = []
        for key in reference.POLICY_PARAMS[name]:
            v = space[name][key]
            if isinstance(v, dict):
                cols.append(v["per_capacity"] * cap)
            elif isinstance(v, list):
                cols.append(rng.uniform(v[0], v[1], len(rows)))
            else:
                cols.append(np.full(len(rows), float(v)))
        for k, i in enumerate(rows):
            policy[i], params[i] = name, np.stack(cols, axis=1)[k]
    return perm, policy, params


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


@pytest.mark.parametrize("seed", [SEED, 7])
@pytest.mark.parametrize("request_of", [
    (generator.SETUP,), (generator.WARMUP, 0), (generator.WINDOW, 0)])
def test_committed_sweep_requests_are_the_formula_bit_for_bit(request_of,
                                                              seed):
    traffic, api = _sweep(seed=seed)
    if request_of == (generator.SETUP,):
        assert _same_bits(traffic.loads, formula_loads(seed, ROWS))
        return
    req = traffic.make(*request_of)
    req.call()
    perm, policy, params = formula_request(seed, *request_of, ROWS)
    assert _same_bits(api.kwargs["load_index"], perm)
    assert api.kwargs["load_matrix"] is traffic.loads
    assert [p for p, _ in api.twins] == policy
    for (_, got), want in zip(api.twins, params):
        assert _same_bits(got, want)
    assert req.work == ROWS


@pytest.mark.parametrize("bin_hours,t_bins", [
    (1.0, 48), (0.25, 48 * 4), (1.0 / 60, 48 * 60), (1.0 / 60, 47 * 60 + 1)])
def test_each_hour_spreads_its_records_over_its_bins(bin_hours, t_bins):
    spec = dict(SPEC, noise_sigma=0.0, scale=[1.0, 1.0])
    n = 20
    rows = generator.load_rows(n, t_bins, bin_hours, spec,
                               generator.rng_for(SEED, generator.SETUP))
    per_hour = round(1 / bin_hours)
    assert rows.shape == (n, t_bins) and rows.dtype == np.float32
    g = np.linspace(spec["growth"][0], spec["growth"][1], spec["curves"])
    hourly = reference.honda_loads([spec["base_rps"]] * len(g), g)[
        np.arange(n) % len(g)]
    whole = t_bins // per_hour
    sums = rows[:, :whole * per_hour].astype(np.float64).reshape(
        n, whole, per_hour).sum(axis=2)
    np.testing.assert_allclose(sums, hourly[:, :whole], rtol=1e-6)
    np.testing.assert_allclose(
        rows[:, whole * per_hour:].astype(np.float64),
        np.repeat(hourly[:, whole:whole + 1] * bin_hours,
                  t_bins - whole * per_hour, axis=1), rtol=1e-6)


def _load_rows(**kw):
    args = dict(n=4, t_bins=48, bin_hours=1.0, spec=SPEC,
                rng=generator.rng_for(SEED, generator.SETUP))
    args.update(kw)
    return generator.load_rows(**args)


def _traffic_with(**mix):
    return _sweep(mix=dict(MIX, **mix))


@pytest.mark.parametrize("make", [
    lambda: _load_rows(bin_hours=0.7),
    lambda: _load_rows(bin_hours=2.0),
    lambda: _load_rows(bin_hours=1.0 / 7 + 1e-9),
    lambda: _load_rows(bin_hours=0.0),
    lambda: _load_rows(t_bins=reference.HOURS_PER_YEAR + 1),
    lambda: _load_rows(t_bins=reference.HOURS_PER_YEAR * 60 + 1,
                       bin_hours=1.0 / 60),
    lambda: _traffic_with(load_pool=0),
    lambda: _traffic_with(load_pool=ROWS + 1),
    lambda: generator.WhatifTraffic(dict(CFG, bin_hours=0.25),
                                    _json("traffic",
                                          "analyst-closed-loop.json"),
                                    SEED, Recorder()),
], ids=["bin-0.7h", "bin-2h", "bin-not-a-divisor", "bin-0", "hour-8737",
        "minute-past-the-year", "pool-0",
        "pool-over-rows", "whatif-sub-hour"])
def test_refused_with_a_value_error(make):
    with pytest.raises(ValueError):
        make()


@pytest.mark.parametrize("pool", [1, 7, ROWS])
def test_a_shared_load_pool(pool):
    traffic, api = _sweep(mix=dict(MIX, load_pool=pool))
    assert traffic.loads.shape == (pool, CFG["horizon_bins"])
    assert _same_bits(traffic.loads, formula_loads(SEED, pool))
    req = traffic.make(generator.WINDOW, 0)
    req.call()
    perm, _, _ = formula_request(SEED, generator.WINDOW, 0, ROWS)
    assert _same_bits(api.kwargs["load_index"], perm % pool)
    uses = np.bincount(api.kwargs["load_index"], minlength=pool)
    assert uses.max() - uses.min() <= 1
    rows = traffic.check_rows(req, np.arange(ROWS))
    for i in range(ROWS):
        assert _same_bits(rows.loads[i], traffic.loads[perm[i] % pool])


def _tiny_rows(n=23, t_bins=96):
    cfg = dict(CFG, horizon_bins=t_bins)
    traffic, _ = _sweep(cfg=cfg, mix=dict(MIX, rows=n))
    req = traffic.make(generator.WINDOW, 0)
    return cfg, traffic.check_rows(req, np.arange(n))


@pytest.mark.parametrize("block", [1, 3, 8])
def test_reference_blocks_give_the_gaps_of_blocks_of_2048(block):
    cfg, rows = _tiny_rows()
    whole = check.reference_rows(rows, cfg, block=check.BLOCK_ROWS)
    cut = check.reference_rows(rows, cfg, block=block)
    assert set(whole) == set(cut)
    for k in whole:
        assert _same_bits(whole[k], cut[k]), k
    g = check.gaps(cut, whole, cfg["horizon_bins"])
    assert g == {"value_gap": 0.0, "hours_gap": 0.0, "pct_gap": 0.0,
                 "hist_gap": 0.0}


@pytest.mark.parametrize("t_bins,rows", [
    (96, 2048), (reference.HOURS_PER_YEAR, 2048),
    (reference.HOURS_PER_YEAR * 4, 512), (reference.HOURS_PER_YEAR * 60, 34),
    (10**9, 1)])
def test_reference_block_follows_the_horizon(t_bins, rows):
    assert check.block_rows(t_bins) == rows
