"""What decides ``correct``: the answers of the timed path against the
plain reference (``bench/reference.py``), number by number.

Four numbers are compared, each the widest gap over the rows checked:

* ``value_gap`` — the largest relative gap of a Table II value (cost,
  backlog, mean and peak throughput, mean latency, records dropped,
  processed and arrived, end-of-year queue, the share of records within
  the SLO; and ``slo_met``, a flip counting 1), each taken against the
  larger of the reference's value and a floor of its unit (``FLOORS``);
  Table II rows as printed count their rounding as no gap;
* ``hours_gap`` — the largest gap, in bins, of a count of bins: bins
  within the SLO, bins in a fault window, bins within the SLO in one;
* ``pct_gap`` — the largest gap, in histogram buckets, of the median, p95
  or p99 latency;
* ``hist_gap`` — the largest share of a row's load whose latency lies in
  another histogram bucket than the reference's.

The limits live in ``bench/limits.json``; ``PERF.md`` gives the readings
each was set from.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench import reference

NUMBERS = ("value_gap", "hours_gap", "pct_gap", "hist_gap")
#: what a number reads where answers are missing
MISSING = 1e9
#: rows the reference plays at once over the hourly year; ``block_rows``
#: scales it to the horizon, so a block's host memory stays the same
BLOCK_ROWS = 2048

#: (reference key, floor) of each compared value; the floor ``"load"`` is
#: the row's mean load per bin
FLOORS = {
    "total_cost_usd": 0.01, "backlog_s": 1.0,
    "mean_throughput_rph": "load", "max_throughput_rph": "load",
    "mean_latency_s": 1e-3, "dropped_records": "load",
    "processed_records": "load", "arrived_records": "load",
    "queue_end": "load", "pct_latency_met": 100.0,
}
#: Table II columns: (reference key, printed decimals)
TABLE = {
    "cost_usd": ("total_cost_usd", 2), "latency_mean_s": ("mean_latency_s", 2),
    "latency_backlog_s": ("backlog_s", 2),
    "thruput_mean_rph": ("mean_throughput_rph", 2),
    "thruput_max_rph": ("max_throughput_rph", 2),
    "dropped": ("dropped_records", 1),
    "pct_latency_met": ("pct_latency_met", 2),
}
TABLE_QUANTILES = {"latency_median_s": "bucket_p50",
                   "latency_p95_s": "bucket_p95",
                   "latency_p99_s": "bucket_p99"}


def load_limits(bench_dir: str) -> Dict[str, float]:
    with open(os.path.join(bench_dir, "limits.json")) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def block_rows(t_bins: int) -> int:
    """Rows the reference plays at once over ``t_bins`` bins: as many
    scenario-bins as ``BLOCK_ROWS`` rows of the hourly year, at least
    one row."""
    return max(1, min(BLOCK_ROWS, BLOCK_ROWS * reference.HOURS_PER_YEAR
                      // int(t_bins)))


def reference_rows(rows, cfg: Dict, dtype=np.float32,
                   block: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Reference Table II values of ``rows`` (a ``generator.Rows``), in
    their order, computed per policy and fault layout, ``block`` rows at
    a time (``block_rows`` of the horizon where None). A row's numbers do
    not depend on the block it is played in."""
    n = len(rows.policy)
    if block is None:
        block = block_rows(cfg["horizon_bins"])
    out: Dict[str, np.ndarray] = {}
    groups: Dict[tuple, List[int]] = {}
    for i in range(n):
        groups.setdefault((rows.policy[i], rows.future[i] is not None),
                          []).append(i)
    for (policy, faulted), members in groups.items():
        for at in range(0, len(members), block):
            _reference_block(rows, members[at:at + block], policy,
                             faulted, cfg, dtype, out, n)
    return out


def _reference_block(rows, idx, policy, faulted, cfg, dtype, out, n):
    t_bins, bin_hours, slo = cfg["horizon_bins"], cfg["bin_hours"], cfg["slo"]
    params = np.stack([rows.params[i] for i in idx])
    caps = fmask = None
    if faulted:
        loads = np.stack([reference.faulted_loads(rows.loads[i],
                                                  rows.future[i])
                          for i in idx])
        caps = np.stack([rows.future[i]["cap"] for i in idx])
        fmask = np.stack([rows.future[i]["mask"] for i in idx])
    else:
        loads = np.stack([rows.loads[i] for i in idx])
    stats = reference.simulate(policy, loads, params, slo["limit_s"], caps,
                               fmask, dtype, bin_hours)
    summ = reference.summarise(stats, params[:, 0], params[:, 1],
                               slo["met_fraction"], t_bins, bin_hours)
    for k, v in summ.items():
        if k not in out:
            out[k] = np.zeros((n,) + np.shape(v)[1:], np.asarray(v).dtype)
        out[k][idx] = v


def bucket_of(centers) -> np.ndarray:
    """Histogram bucket of each bucket-centre latency."""
    c = np.asarray(centers, np.float64)
    return np.rint(np.log2(np.maximum(c, 1e-300)) * 4.0
                   - reference.HIST_MIN_EXP * 4.0 - 0.5).astype(np.int64)


def summary_arrays(sims: Sequence, t_bins: int, bin_hours: float
                   ) -> Dict[str, np.ndarray]:
    """The compared values of ``GridSummary`` rows, as arrays keyed like
    ``reference.summarise``."""
    get = lambda f: np.array([float(getattr(s, f)) for s in sims])  # noqa
    out = {k: get(k) for k in FLOORS}
    out["slo_met"] = np.array([bool(s.slo_met) for s in sims])
    out["ok_bins"] = np.rint(get("pct_hours_met") * t_bins / 100.0)
    flt = np.rint(get("fault_hours") / bin_hours)
    out["fault_bins"] = flt
    out["fault_ok_bins"] = np.where(
        flt > 0, np.rint(get("pct_hours_met_in_fault") * flt / 100.0), 0.0)
    for q, f in (("bucket_p50", "median_latency_s"),
                 ("bucket_p95", "p95_latency_s"),
                 ("bucket_p99", "p99_latency_s")):
        out[q] = bucket_of(get(f))
    out["latency_hist"] = np.stack([np.asarray(s.latency_hist, np.float64)
                                    for s in sims])
    return out


def _floor(ref: Dict[str, np.ndarray], key: str, t_bins: int):
    f = FLOORS[key]
    return ref["arrived_records"] / t_bins if f == "load" else f


def gaps(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
         t_bins: int, notes: Optional[Dict[str, str]] = None
         ) -> Dict[str, float]:
    """The four compared numbers of ``got`` against ``ref``; ``notes``
    gets where the widest value gap lies: (value, row, got, reference)."""
    value = 0.0
    for key in FLOORS:
        den = np.maximum(np.abs(ref[key]), _floor(ref, key, t_bins))
        rel = np.abs(got[key] - ref[key]) / den
        if len(rel) and rel.max() > value:
            i = int(np.argmax(rel))
            value = float(rel[i])
            if notes is not None:
                notes["value_gap"] = (key, i, float(got[key][i]),
                                      float(ref[key][i]))
    if np.any(got["slo_met"] != ref["slo_met"]):
        value = max(value, 1.0)
    hours = max(float(np.max(np.abs(got[k] - ref[k]), initial=0.0))
                for k in ("ok_bins", "fault_bins", "fault_ok_bins"))
    pct = max(float(np.max(np.abs(got[k] - ref[k]), initial=0))
              for k in ("bucket_p50", "bucket_p95", "bucket_p99"))
    h_ref = ref["latency_hist"]
    share = (np.abs(got["latency_hist"] - h_ref).sum(axis=1)
             / np.maximum(h_ref.sum(axis=1), 1e-300))
    return {"value_gap": value, "hours_gap": hours, "pct_gap": pct,
            "hist_gap": float(np.max(share, initial=0.0))}


def table_gaps(table: Sequence[Dict], ref: Dict[str, np.ndarray],
               t_bins: int) -> Dict[str, float]:
    """Table II rows as printed against the reference: a value's gap
    beyond its rounding (into ``value_gap``), and the distance in buckets
    from the reference's bucket to the nearest bucket whose centre prints
    as the row shows (into ``pct_gap``)."""
    value = pct = 0.0
    printed: Dict[float, List[int]] = {}
    for j, c in enumerate(reference.HIST_CENTERS):
        printed.setdefault(round(float(c), 2), []).append(j)
    for i, row in enumerate(table):
        for col, (key, nd) in TABLE.items():
            r = float(ref[key][i])
            den = max(abs(r), float(np.broadcast_to(
                _floor(ref, key, t_bins), ref[key].shape)[i]))
            value = max(value, max(0.0, abs(row[col] - r)
                                   - 0.5 * 10.0 ** -nd * (1 + 1e-9)) / den)
        if bool(row["slo_met"]) != bool(ref["slo_met"][i]):
            value = max(value, 1.0)
        for col, key in TABLE_QUANTILES.items():
            b = int(ref[key][i])
            pct = max(pct, min((abs(j - b) for j in printed.get(row[col], ())),
                               default=reference.HIST_BINS))
    return {"value_gap": value, "pct_gap": float(pct)}


def worst(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: max(a.get(k, 0.0), b.get(k, 0.0)) for k in set(a) | set(b)}


def check_requests(traffic, requests: Sequence, cfg: Dict, seed: int,
                   mix: Dict, dtype=np.float32, control: bool = False,
                   notes: Optional[Dict[str, str]] = None
                   ) -> Dict[str, float]:
    """Compare a sample of the answers of ``requests`` with the reference:
    ``check_requests`` of them (every one where the mix sets none) and
    ``check_rows`` rows of each (every row where it sets none), both drawn
    from the seed. With ``control``, the reference computed in ``dtype``
    takes the program's place and no answer of the program is read.
    ``notes`` gets where the widest value gap lies."""
    from bench.generator import CHECK, rng_for
    t_bins, bin_hours = cfg["horizon_bins"], cfg["bin_hours"]
    out = {k: 0.0 for k in NUMBERS}
    picks, rows_all = [], None
    if "check_requests" in mix and len(requests) > mix["check_requests"]:
        chosen = rng_for(seed, CHECK).choice(
            len(requests), mix["check_requests"], replace=False)
        requests = [requests[i] for i in sorted(chosen)]
    for req in requests:
        n = req.rows
        k = mix.get("check_rows", "all")
        pick = (np.arange(n) if k == "all" else np.sort(
            rng_for(seed, CHECK, req.index).choice(n, min(int(k), n),
                                                   replace=False)))
        rows = traffic.check_rows(req, pick)
        picks.append((req, pick, len(rows.policy)))
        if rows_all is None:
            rows_all = rows
        else:
            for f in ("policy", "params", "loads", "future"):
                getattr(rows_all, f).extend(getattr(rows, f))
    if rows_all is None:
        return out
    ref = reference_rows(rows_all, cfg)
    if control:
        got_all = reference_rows(rows_all, cfg, dtype)
        return gaps(got_all, ref, t_bins)
    at = 0
    for req, pick, m in picks:
        sl = {k: v[at:at + m] for k, v in ref.items()}
        at += m
        sims = traffic.answers(req)
        if len(sims) != req.rows:
            return {k: MISSING for k in NUMBERS}
        got = summary_arrays([sims[i] for i in pick], t_bins, bin_hours)
        here: Dict = {}
        g = gaps(got, sl, t_bins, here)
        if notes is not None and "value_gap" in here \
                and g["value_gap"] > out["value_gap"]:
            key, i, a, b = here["value_gap"]
            notes["value_gap"] = (f"{key} of row {int(pick[i])} of request "
                                  f"{req.index} ({rows_all.policy[at - m + i]}"
                                  f"): {a!r} against {b!r}")
        out = worst(out, g)
        if req.kind == "whatif":
            table = req.answer[1]
            if len(table) != req.rows:
                return {k: MISSING for k in NUMBERS}
            out = worst(out, table_gaps([table[i] for i in pick], sl,
                                        t_bins))
    return out
