"""The one traffic generator: a configuration and a traffic mix, both data,
and a seed in; the requests of a run out.

A mix names its ``request`` kind and the sizes of each request:

* ``"sweep"`` — a planning sweep: one ``simulate_grid(...,
  return_series=False)`` call over ``rows`` scenario rows, each row its
  own seeded load year (the configuration's ``load_uncertainty``) and its
  own twin drawn from ``sweep_space``, the policies cycling in the
  configuration's order; with ``futures`` > 1 every base row plays that
  many fault futures of the configuration's ``faults`` schedule, and
  ``devices`` shards the sweep over a scenario mesh. The load rows are
  made once, at set-up, at the configuration's ``bin_hours``: one per
  base row, or with ``load_pool`` K a pool of K rows that the base rows
  share, each pool row played by about rows / K of them. Each request
  pairs them with new twins (and new futures) in a new order, so no two
  requests are alike and every one has the same sizes.
* ``"whatif"`` — an analyst's what-if query: ``whatif.run_grid`` over the
  configuration's paper ``variants`` x ``traffic_cases`` Honda traffic
  cases whose R and G are drawn from the mix's ranges, then
  ``table2_rows``.

Every random draw comes from ``numpy.random.SeedSequence([seed, stream,
index, ...])``: the same seed gives the same requests.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from bench import reference

#: streams of one seed's draws
SETUP, WARMUP, WINDOW, CHECK = 0, 1, 2, 3


def rng_for(seed: int, *keys: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % 2**63] + [int(k) for k in keys]))


# ---------------------------------------------------------------------------
# seeded data (the load-uncertainty Monte Carlo and the twin sweep space)
# ---------------------------------------------------------------------------

def bins_per_hour(bin_hours: float) -> int:
    """Bins in an hour, or ``ValueError`` where ``bin_hours`` does not
    divide the hour."""
    per_hour = round(1.0 / bin_hours) if bin_hours > 0 else 0
    if per_hour < 1 or abs(per_hour * bin_hours - 1.0) > 1e-12:
        raise ValueError(f"bin_hours {bin_hours!r} does not divide the "
                         f"hour")
    return per_hour


def load_rows(n: int, t_bins: int, bin_hours: float, spec: Dict,
              rng: np.random.Generator) -> np.ndarray:
    """[n, t_bins] float32 records per bin, rows all distinct: the
    ``curves`` Honda growth curves (R ``base_rps``, G spread over
    ``growth``) over the hours the horizon covers, each hour spread evenly
    over its bins, each row scaled by its own factor drawn from ``scale``
    and by per-bin log-normal noise of ``noise_sigma``. ``ValueError`` where
    ``bin_hours`` does not divide the hour or the horizon is longer than
    the Honda year."""
    per_hour = bins_per_hour(bin_hours)
    hours = -(-t_bins // per_hour)
    if hours > reference.HOURS_PER_YEAR:
        raise ValueError(f"{t_bins} bins of {bin_hours!r} h are longer "
                         f"than the {reference.HOURS_PER_YEAR}-hour year")
    g = np.linspace(spec["growth"][0], spec["growth"][1], spec["curves"])
    base = reference.honda_loads([spec["base_rps"]] * len(g), g)[:, :hours]
    if per_hour > 1:
        base = np.repeat(base * bin_hours, per_hour, axis=1)[:, :t_bins]
    base = base.astype(np.float32)
    out = rng.standard_normal((n, t_bins), dtype=np.float32)
    out *= np.float32(spec["noise_sigma"])
    np.exp(out, out=out)
    # out *= base[arange(n) % len(g)], without an [n, t_bins] copy
    for c in range(len(g)):
        out[c::len(g)] *= base[c]
    out *= rng.uniform(*spec["scale"], (n, 1)).astype(np.float32)
    return out


def twin_params(n: int, policies: List[str], space: Dict,
                rng: np.random.Generator) -> List[np.ndarray]:
    """Per policy, the [n_p, n_params] float64 parameters of the rows that
    play it (row i plays ``policies[i % len(policies)]``). Each policy's
    entry in ``space`` lists its parameters in the wind tunnel's layout:
    a ``[lo, hi]`` range is drawn uniformly, ``capacity`` scales the
    entries marked ``"per_capacity"``, a single number is fixed."""
    out = []
    for j, policy in enumerate(policies):
        n_p = len(range(j, n, len(policies)))
        sp = space[policy]
        cap = rng.uniform(*space["capacity"], n_p)
        cols = []
        for name in reference.POLICY_PARAMS[policy]:
            v = sp[name]
            if isinstance(v, dict):          # {"per_capacity": base value}
                cols.append(v["per_capacity"] * cap)
            elif isinstance(v, list):
                cols.append(rng.uniform(v[0], v[1], n_p))
            else:
                cols.append(np.full(n_p, float(v)))
        out.append(np.stack(cols, axis=1))
    return out


def fault_futures(schedule: Dict, t_bins: int, bin_hours: float,
                  seed: int, *keys: int) -> List[Dict]:
    """Sample ``schedule["futures"]`` fault futures over the horizon.

    Per (spec, future) event counts are Poisson with mean ``rate_per_year``
    over the horizon's share of a 8736-hour year; each window starts
    uniformly and lasts a uniform draw of ``duration_hours``. An outage
    zeroes capacity; a disconnect strips a ``disconnect_frac`` of the
    load for the window and replays the stripped mass uniformly over
    ``flood_hours`` right after it. Each future is a dict of ``cap``,
    ``mask`` (float32 [T]), ``load_mult`` (float64 [T]), ``replay`` (a
    list of (removed, profile) float64 [T] pairs) and ``events``."""
    years = t_bins * bin_hours / reference.HOURS_PER_YEAR
    futures = []
    for f in range(schedule["futures"]):
        cap = np.ones(t_bins)
        mask = np.zeros(t_bins, np.float32)
        load_mult = np.ones(t_bins)
        replay, events = [], []
        for s, spec in enumerate(schedule["specs"]):
            rng = rng_for(seed, *keys, s, f)
            for _ in range(int(rng.poisson(spec["rate_per_year"] * years))):
                start_h = rng.uniform(0.0, t_bins * bin_hours)
                dur_h = rng.uniform(*spec["duration_hours"])
                start = min(int(start_h // bin_hours), t_bins - 1)
                end = min(t_bins, start + max(1, math.ceil(dur_h
                                                           / bin_hours)))
                ev = {"spec": spec["name"], "kind": spec["kind"],
                      "start": start, "end": end}
                mask[start:end] = 1.0
                if spec["kind"] == "outage":
                    cap[start:end] = 0.0
                elif spec["kind"] == "disconnect":
                    frac = rng.uniform(*spec["disconnect_frac"])
                    removed = np.zeros(t_bins)
                    removed[start:end] = load_mult[start:end] * frac
                    load_mult[start:end] *= 1.0 - frac
                    n_flood = max(1, math.ceil(spec["flood_hours"]
                                               / bin_hours))
                    fl0 = min(end, t_bins - 1)
                    fl1 = min(t_bins, fl0 + n_flood)
                    profile = np.zeros(t_bins)
                    profile[fl0:fl1] = 1.0 / (fl1 - fl0)
                    mask[fl0:fl1] = 1.0
                    replay.append((removed, profile))
                    ev["flood_end"] = fl1
                else:
                    raise ValueError(f"no generator for fault kind "
                                     f"{spec['kind']!r}")
                events.append(ev)
        futures.append({"cap": cap.astype(np.float32), "mask": mask,
                        "load_mult": load_mult, "replay": replay,
                        "events": events})
    return futures


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

@dataclass
class Rows:
    """Scenario rows to hand the reference: for each row its policy,
    float64 parameters, float32 load row and optional fault future."""
    policy: List[str] = field(default_factory=list)
    params: List[np.ndarray] = field(default_factory=list)
    loads: List[np.ndarray] = field(default_factory=list)
    future: List[Optional[Dict]] = field(default_factory=list)


@dataclass
class Request:
    """One request of a run: ``call()`` is the timed path; ``answer``
    holds what it returned."""
    kind: str
    index: int
    call: Any
    work: float                    # scenario-years the request simulates
    rows: int
    inputs: Any = None             # what the reference needs of it
    answer: Any = None
    start: float = 0.0
    end: float = 0.0


class SweepTraffic:
    """``"sweep"`` requests (see the module docstring)."""

    def __init__(self, cfg: Dict, mix: Dict, seed: int, api):
        self.cfg, self.mix, self.seed, self.api = cfg, mix, seed, api
        self.t_bins = cfg["horizon_bins"]
        self.bin_hours = cfg["bin_hours"]
        self.policies = cfg["policies"]
        self.futures = int(mix.get("futures", 1))
        if self.futures > 1 and not cfg.get("faults"):
            raise ValueError("a mix with futures needs a configuration "
                             "with a fault schedule")
        if mix["rows"] % self.futures:
            raise ValueError("rows must be a multiple of futures")
        self.n_base = mix["rows"] // self.futures
        self.pool = int(mix.get("load_pool", self.n_base))
        if not 1 <= self.pool <= self.n_base:
            raise ValueError(f"load_pool {self.pool} is not in 1.."
                             f"{self.n_base} (rows / futures)")
        self.loads = load_rows(self.pool, self.t_bins, self.bin_hours,
                               cfg["load_uncertainty"],
                               rng_for(seed, SETUP))
        self.slo = api.slo(cfg["slo"])

    def make(self, stream: int, index: int) -> Request:
        rng = rng_for(self.seed, stream, index)
        n, p = self.n_base, len(self.policies)
        perm = (rng.permutation(n) % self.pool).astype(np.int32)
        params = twin_params(n, self.policies, self.cfg["sweep_space"], rng)
        twins = [None] * n
        for j, policy in enumerate(self.policies):
            for k, i in enumerate(range(j, n, p)):
                twins[i] = self.api.twin(f"{policy}{i}", policy,
                                         params[j][k])
        futures = None
        if self.futures > 1:
            sched = dict(self.cfg["faults"], futures=self.futures)
            futures = fault_futures(sched, self.t_bins, self.bin_hours,
                                    self.seed, stream, index)
        kwargs = dict(load_matrix=self.loads, load_index=perm, slo=self.slo,
                      bin_hours=self.bin_hours, return_series=False)
        if self.mix.get("devices", 1) > 1:
            kwargs["devices"] = self.mix["devices"]
        if "scenario_block" in self.mix:
            kwargs["scenario_block"] = self.mix["scenario_block"]
        if futures is not None:
            kwargs["faults"] = self.api.sampled_faults(
                futures, self.t_bins, self.bin_hours, index)
        api = self.api
        return Request("sweep", index,
                       lambda: api.simulate_grid(twins, **kwargs),
                       work=self.mix["rows"] * self.t_bins * self.bin_hours
                       / reference.HOURS_PER_YEAR, rows=self.mix["rows"],
                       inputs=(perm, params, futures))

    def check_rows(self, req: Request, pick: np.ndarray) -> Rows:
        """The reference's inputs for the answer rows ``pick`` of ``req``
        (grid order: base row major, future minor)."""
        perm, params, futures = req.inputs
        p = len(self.policies)
        out = Rows()
        for r in pick:
            i, f = divmod(int(r), self.futures)
            out.policy.append(self.policies[i % p])
            out.params.append(params[i % p][i // p])
            out.loads.append(self.loads[perm[i]])
            out.future.append(futures[f] if futures is not None else None)
        return out

    def answers(self, req: Request) -> List:
        return req.answer


class WhatifTraffic:
    """``"whatif"`` requests (see the module docstring)."""

    def __init__(self, cfg: Dict, mix: Dict, seed: int, api):
        self.cfg, self.mix, self.seed, self.api = cfg, mix, seed, api
        if cfg["bin_hours"] != 1.0:
            raise ValueError("a what-if query plays the Honda model's "
                             "hourly year: bin_hours must be 1")
        self.t_bins = cfg["horizon_bins"]
        self.names = list(cfg["variants"])
        self.variants = [cfg["variants"][k] for k in self.names]
        self.twins = [api.twin(k, v["policy"], np.array(
            [v[n] for n in reference.POLICY_PARAMS[v["policy"]]]))
            for k, v in zip(self.names, self.variants)]
        self.slo = api.slo(cfg["slo"])

    def make(self, stream: int, index: int) -> Request:
        rng = rng_for(self.seed, stream, index)
        k = self.mix["traffic_cases"]
        r = rng.uniform(*self.mix["R"], k)
        g = rng.uniform(*self.mix["G"], k)
        api, twins, slo = self.api, self.twins, self.slo

        def call():
            traffics = [api.traffic(f"case{j}", float(r[j]), float(g[j]))
                        for j in range(k)]
            sims = api.run_grid(twins, traffics, slo)
            return sims, api.table2_rows(sims)

        return Request("whatif", index, call,
                       work=k * len(twins) * self.t_bins
                       / reference.HOURS_PER_YEAR, rows=k * len(twins),
                       inputs=(r, g))

    def check_rows(self, req: Request, pick: np.ndarray) -> Rows:
        r, g = req.inputs
        v = len(self.variants)
        loads = reference.honda_loads(r, g).astype(np.float32)
        out = Rows()
        for row in pick:
            case, j = divmod(int(row), v)
            var = self.variants[j]
            out.policy.append(var["policy"])
            out.params.append(np.array(
                [var[n] for n in reference.POLICY_PARAMS[var["policy"]]]))
            out.loads.append(loads[case])
            out.future.append(None)
        return out

    def answers(self, req: Request) -> List:
        return req.answer[0]


KINDS = {"sweep": SweepTraffic, "whatif": WhatifTraffic}


def traffic(cfg: Dict, mix: Dict, seed: int, api):
    try:
        kind = KINDS[mix["request"]]
    except KeyError:
        raise ValueError(f"unknown request kind {mix.get('request')!r}; "
                         f"known: {sorted(KINDS)}") from None
    return kind(cfg, mix, seed, api)
