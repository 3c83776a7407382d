"""Plain reference of the wind tunnel's semantics, for deciding ``correct``.

Independent of the code under test: it imports nothing of ``repro`` and
takes only the inputs the benchmark generated (load rows, twin
parameters, fault futures, traffic cases). It plays each scenario bin by
bin in numpy, vectorised over the scenarios of one policy, in the
precision the configuration states (float32 per-bin arithmetic), and
summarises every scenario the way the wind tunnel documents its
``GridSummary`` rows:

* sums over the year (processed, cost, dropped, latency x load, load,
  load in SLO-ok bins) accumulated in float64 from the float32 terms;
* the end-of-year backlog priced at the twin's hourly rate;
* a load-weighted latency histogram over quarter-octave buckets keyed by
  the float32 exponent and the top two mantissa bits, bucket 0 at
  2**-10 s, and the median / p95 / p99 read as the centre of the first
  bucket whose cumulative load crosses the quantile;
* SLO-ok bin counts in and outside fault windows.

``dtype=ml_dtypes.bfloat16`` runs the same arithmetic one precision
lower: the control that the comparison has to fail.
"""
from __future__ import annotations

import numpy as np

HOURS_PER_YEAR = 8736
DAYS_PER_YEAR = 364
MONTH_DAYS = (31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 30)
START_DOW = 3

HIST_BINS = 152
HIST_MIN_EXP = -10
HIST_CENTERS = np.power(2.0, HIST_MIN_EXP + (np.arange(HIST_BINS) + 0.5)
                        / 4.0)

#: parameter layout of each policy (the wind tunnel's flat vector)
POLICY_PARAMS = {
    "fifo": ("max_rps", "usd_per_hour", "base_latency_s"),
    "quickscale": ("max_rps", "usd_per_hour", "base_latency_s"),
    "autoscale": ("max_rps", "usd_per_hour", "base_latency_s",
                  "min_instances", "max_instances", "scale_up_hours"),
    "shed": ("max_rps", "usd_per_hour", "base_latency_s",
             "queue_cap_hours"),
    "batch_window": ("max_rps", "usd_per_hour", "base_latency_s",
                     "window_hours", "idle_cost_fraction"),
}


# ---------------------------------------------------------------------------
# the Honda telemetry traffic model (arXiv 2504.10692, Sec. V-G)
# ---------------------------------------------------------------------------

M_MONTH = np.array([0.84, 0.86, 0.92, 0.98, 1.04, 1.09, 1.12, 1.14,
                    1.08, 1.00, 0.92, 0.87])
PIN_FRI20, PIN_WED06 = 2.26, 0.04
TARGET_MEAN_RPH = 5035.8
HOUR_CURVE = np.array([
    0.30, 0.18, 0.10, 0.07, 0.05, 0.045, 0.05, 0.30,
    0.70, 0.95, 1.05, 1.10, 1.15, 1.10, 1.05, 1.10,
    1.25, 1.50, 1.75, 1.95, 2.05, 1.55, 0.95, 0.55])
DOW_SCALE = np.array([0.97, 0.99, 1.01, 1.03, 1.10, 1.05, 0.85])


def _hour_index():
    hours = np.arange(HOURS_PER_YEAR)
    day = hours // 24
    how = ((START_DOW + day) % 7) * 24 + hours % 24
    month = np.searchsorted(np.cumsum(MONTH_DAYS), np.arange(DAYS_PER_YEAR),
                            side="right")
    return day, how, month[day]


def _honda_rows(r, g, h_rel):
    day, how, month = _hour_index()
    growth = 1.0 + day[None, :] * (np.asarray(g)[:, None] - 1.0) / 365.0
    return ((np.asarray(r)[:, None] * 3600.0) * growth * h_rel[how][None, :]
            * M_MONTH[month][None, :])


def _honda_h_rel() -> np.ndarray:
    base = np.outer(DOW_SCALE, HOUR_CURVE).reshape(168)
    h = base / base.mean()
    fri20, wed06 = 4 * 24 + 20, 2 * 24 + 6
    free = np.ones(168, bool)
    free[[fri20, wed06]] = False
    for _ in range(4):
        h[fri20], h[wed06] = PIN_FRI20, PIN_WED06
        h[free] *= (168 - PIN_FRI20 - PIN_WED06) / h[free].sum()
    return h


def honda_loads(r, g) -> np.ndarray:
    """[len(r), 8736] float64 records per hour: R records/s at the start
    of the year, growth G over the year, the hour-of-week and month
    factors calibrated to Table II's mean load of 5035.8 records/h at
    R = 3.5."""
    h = _honda_h_rel()
    rows = []
    for ri, gi in zip(np.atleast_1d(r), np.atleast_1d(g)):
        ri, gi = float(ri), float(gi)
        alpha = (TARGET_MEAN_RPH * (ri / 3.5)
                 / _honda_rows([ri], [1.0], h)[0].mean())
        rows.append(_honda_rows([ri], [gi], h * alpha)[0])
    return np.stack(rows)


# ---------------------------------------------------------------------------
# fault futures
# ---------------------------------------------------------------------------

def faulted_loads(row: np.ndarray, future) -> np.ndarray:
    """One base load row [T] under one fault future: the row times the
    future's load multiplier, then each disconnect's removed mass
    replayed over its flood bins, in float64, cast to the row's type."""
    row64 = np.asarray(row, np.float64)
    out = row64 * future["load_mult"]
    for removed, profile in future["replay"]:
        mass = float(row64 @ removed)
        if mass != 0.0:
            out = out + mass * profile
    return out.astype(np.asarray(row).dtype)


# ---------------------------------------------------------------------------
# policy steps over [S] scenarios of one policy
# ---------------------------------------------------------------------------

def _step(policy, q, s, arrive, p, dt, c):
    """One bin of ``policy``: returns (queue, state, processed, latency,
    cost, dropped). ``c`` casts Python constants to the working
    precision."""
    max_rps, usd, base = p[0], p[1], p[2]
    tiny = c(1e-9)
    zero = np.zeros_like(arrive)
    if policy == "fifo" or policy == "shed":
        cap_hour = max_rps * c(3600.0)
        cap_bin = cap_hour * dt
        avail = q + arrive
        processed = np.minimum(avail, cap_bin)
        backlog = avail - processed
        if policy == "shed":
            dropped = np.maximum(backlog - p[3] * cap_hour, c(0.0))
        else:
            dropped = zero
        new_q = backlog - dropped
        latency = base + c(0.5) * (q + new_q) / np.maximum(max_rps, tiny)
        return new_q, s, processed, latency, usd * dt, dropped
    if policy == "quickscale":
        cap_bin = max_rps * c(3600.0) * dt
        inst = np.maximum(np.ceil(arrive / np.maximum(cap_bin, tiny)),
                          c(1.0))
        new_q = q * c(0.0)
        return new_q, s, arrive, base + zero, usd * inst * dt, zero
    if policy == "autoscale":
        min_i, max_i, delay = p[3], p[4], p[5]
        cap1 = max_rps * c(3600.0) * dt
        prev = np.clip(s, min_i, max_i)
        avail = q + arrive
        target = np.clip(np.ceil(avail / np.maximum(cap1, tiny)), min_i,
                         max_i)
        booting = prev + (target - prev) * dt / np.maximum(delay, dt)
        inst = np.where(target > prev, booting, target)
        processed = np.minimum(avail, inst * cap1)
        new_q = avail - processed
        latency = (base + c(0.5) * (q + new_q)
                   / np.maximum(inst * max_rps, tiny))
        return new_q, inst, processed, latency, usd * inst * dt, zero
    if policy == "batch_window":
        window, idle = p[3], p[4]
        cap_hour = max_rps * c(3600.0)
        timer = s + dt
        flush = timer >= window
        avail = q + arrive
        processed = np.where(flush, np.minimum(avail, cap_hour * window),
                             c(0.0))
        new_acc = avail - processed
        latency = (base + c(0.5) * window * c(3600.0)
                   + new_acc / np.maximum(max_rps, tiny))
        cost = usd * idle * dt + usd * processed / np.maximum(cap_hour, tiny)
        return (new_acc, np.where(flush, c(0.0), timer), processed, latency,
                cost, zero)
    raise ValueError(f"no reference for policy {policy!r}")


def hist_bucket(latency: np.ndarray) -> np.ndarray:
    """Quarter-octave bucket of each latency: (octave above 2**-10) * 4 +
    the top two bits of the mantissa, clipped to the histogram."""
    lat = np.maximum(np.asarray(latency, np.float32),
                     np.float32(2.0 ** HIST_MIN_EXP))
    mant, exp = np.frexp(lat)              # lat = mant * 2**exp, mant in [.5, 1)
    quarter = np.floor((mant * 2.0 - 1.0) * 4.0).astype(np.int64)
    return np.clip((exp.astype(np.int64) - 1 - HIST_MIN_EXP) * 4 + quarter,
                   0, HIST_BINS - 1)


def simulate(policy: str, loads: np.ndarray, params: np.ndarray,
             slo_limit_s: float, caps=None, fmask=None, dtype=np.float32,
             bin_hours: float = 1.0) -> dict:
    """Play S scenarios of one policy over T bins and summarise them.

    loads [S, T] records per bin; params [S, n_params] float64 (the
    policy's layout, ``POLICY_PARAMS``); caps / fmask [S, T] capacity
    multiplier and in-fault indicator of each scenario's fault future.
    Returns float64 arrays of [S] statistics and the [S, 152] histogram.
    """
    c = lambda v: dtype(v)  # noqa: E731
    loads_w = np.asarray(loads, np.float32).astype(dtype)
    s_n, t_n = loads_w.shape
    p = [np.asarray(params[:, j], np.float32).astype(dtype)
         for j in range(params.shape[1])]
    dt = c(bin_hours)
    limit = c(slo_limit_s)
    q = np.zeros(s_n, dtype)
    state = np.zeros(s_n, dtype)
    fq = np.zeros(s_n, dtype)
    series = {k: np.empty((s_n, t_n), dtype) for k in
              ("processed", "latency", "cost", "dropped")}
    for t in range(t_n):
        arrive = loads_w[:, t]
        if caps is None:
            a_eff, p_eff = arrive, p
        else:
            capmul = np.asarray(caps[:, t], np.float32).astype(dtype)
            avail = fq + arrive
            a_eff = (capmul > c(0.0)).astype(dtype) * avail
            fq = avail - a_eff
            p_eff = [p[0] * capmul] + p[1:]
        q, state, proc, lat, cost, drop = _step(
            policy, q, state, a_eff, p_eff, dt, c)
        if caps is not None:
            lat = lat + fq / np.maximum(p[0], c(1e-9))
        series["processed"][:, t] = proc
        series["latency"][:, t] = lat
        series["cost"][:, t] = cost
        series["dropped"][:, t] = drop
    q_end = q.astype(np.float64) + (fq.astype(np.float64)
                                    if caps is not None else 0.0)
    lat = series["latency"]
    ok = lat <= limit
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    out = {
        "processed": f64(series["processed"]).sum(axis=1),
        "cost": f64(series["cost"]).sum(axis=1),
        "dropped": f64(series["dropped"]).sum(axis=1),
        "latw": f64(lat * loads_w).sum(axis=1),
        "load": f64(loads_w).sum(axis=1),
        "okw": f64(loads_w * ok.astype(dtype)).sum(axis=1),
        "okh": ok.sum(axis=1).astype(np.float64),
        "maxp": f64(series["processed"]).max(axis=1),
        "q_end": q_end,
    }
    if fmask is not None:
        fm = np.asarray(fmask) > 0
        out["flth"] = fm.sum(axis=1).astype(np.float64)
        out["fokh"] = (fm & ok).sum(axis=1).astype(np.float64)
    else:
        out["flth"] = out["fokh"] = np.zeros(s_n)
    buckets = hist_bucket(lat.astype(np.float32))
    w = f64(loads_w)
    out["hist"] = np.stack([np.bincount(buckets[i], weights=w[i],
                                        minlength=HIST_BINS)
                            for i in range(s_n)])
    return out


def summarise(stats: dict, max_rps: np.ndarray, usd_per_hour: np.ndarray,
              slo_met_fraction: float, t_bins: int,
              bin_hours: float = 1.0) -> dict:
    """[S] Table II values of each scenario from ``simulate``'s stats;
    ``max_rps`` / ``usd_per_hour`` are the twins' float64 parameters."""
    backlog_s = stats["q_end"] / np.maximum(max_rps, 1e-9)
    load = np.maximum(stats["load"], 1e-9)
    cdf = np.cumsum(stats["hist"], axis=1)
    quant = {q: np.argmax(cdf >= q * cdf[:, -1:], axis=1)
             for q in (0.5, 0.95, 0.99)}
    pct_rec = stats["okw"] / np.maximum(stats["load"], 1e-12) * 100.0
    flth = stats["flth"]
    return {
        "total_cost_usd": stats["cost"] + backlog_s / 3600.0 * usd_per_hour,
        "backlog_s": backlog_s,
        "mean_throughput_rph": stats["processed"] / t_bins / bin_hours,
        "max_throughput_rph": stats["maxp"] / bin_hours,
        "mean_latency_s": stats["latw"] / load,
        "dropped_records": stats["dropped"],
        "processed_records": stats["processed"],
        "arrived_records": stats["load"],
        "queue_end": stats["q_end"],
        "pct_latency_met": pct_rec,
        "slo_met": pct_rec >= slo_met_fraction * 100.0,
        "ok_bins": stats["okh"],
        "fault_bins": flth,
        "fault_ok_bins": stats["fokh"],
        "bucket_p50": quant[0.5],
        "bucket_p95": quant[0.95],
        "bucket_p99": quant[0.99],
        "latency_hist": stats["hist"],
    }

