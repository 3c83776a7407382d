"""The system under test, as the benchmark calls it.

Every call into the wind tunnel (``repro``) goes through ``System``: the
entry points users call (``simulate_grid``, ``whatif.run_grid``,
``whatif.table2_rows``) and the constructors of their inputs. A test can
hand the harness a ``System`` whose timed path is broken and see the
comparison refuse it.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


class System:
    def __init__(self):
        from repro.core.simulate import simulate_grid
        from repro.core.slo import SLO
        from repro.core.traffic import TrafficModel
        from repro.core.twin import Twin
        from repro.core.whatif import run_grid, table2_rows
        from repro.faults import sampler
        self._simulate_grid, self._run_grid = simulate_grid, run_grid
        self._table2_rows = table2_rows
        self._SLO, self._Twin, self._TM = SLO, Twin, TrafficModel
        self._sampler = sampler

    # the timed entry points ---------------------------------------------
    def simulate_grid(self, twins, **kwargs) -> List:
        return self._simulate_grid(twins, **kwargs)

    def run_grid(self, twins, traffics, slo) -> List:
        return self._run_grid(twins, traffics, slo=slo)

    def table2_rows(self, sims) -> List[Dict]:
        return self._table2_rows(sims)

    def traffic(self, name: str, r: float, g: float):
        return self._TM.honda_default(name, R=r, G=g)

    # inputs ---------------------------------------------------------------
    def twin(self, name: str, policy: str, params: np.ndarray):
        return self._Twin(name=name, policy=policy,
                          params=tuple(float(v) for v in params))

    def slo(self, spec: Dict):
        return self._SLO(metric=spec["metric"], limit_s=spec["limit_s"],
                         met_fraction=spec["met_fraction"])

    def sampled_faults(self, futures: Sequence[Dict], t_bins: int,
                       bin_hours: float, seed: int):
        s = self._sampler
        return s.SampledFaults(
            cap=np.stack([f["cap"] for f in futures]),
            mask=np.stack([f["mask"] for f in futures]),
            load_mult=np.stack([f["load_mult"] for f in futures]),
            replay=tuple(tuple(s.ReplayTerm(removed=r, profile=p)
                               for r, p in f["replay"]) for f in futures),
            events=tuple(tuple(f["events"]) for f in futures),
            n_futures=len(futures), t_bins=t_bins,
            bin_hours=float(bin_hours), seed=int(seed))
