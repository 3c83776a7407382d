"""What the per-layer metric readers (``bench/metrics/<name>.py``) share:
the run's context and the reductions of its traced requests.

A reader is a module with ``read(ctx) -> float | None``. It returns None
where the run gives it nothing to read (no traced request of its kind,
no device operation, no span), and the harness then leaves the metric
out of the result line.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import trace_reduce as tr

#: the wind tunnel's scan programs, by the name the profiler gives their
#: runs (``jit_<function>``): the block steps of the block engine, the
#: unchunked small-grid engine, and the scenario mesh's round step
SCAN_PROGRAMS = (
    "jit__agg_block_step_xla", "jit__agg_block_step_pallas",
    "jit__grid_scan_agg_xla", "jit__grid_scan_agg_fault_xla",
    "jit__policy_agg", "jit__policy_agg_fault", "jit__mesh_agg_round",
)


@dataclass
class Traced:
    """The traced part of a run, on the trace's clock (ns)."""
    devices: List[Dict]                        # device planes that ran
    requests: List[Tuple[str, float, float]]   # (kind, start, end)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    obs_spans: List[Any] = field(default_factory=list)   # repro.obs
    counters: Dict[str, float] = field(default_factory=dict)

    def window_ns(self) -> float:
        """The traced requests' total duration: the window the device
        shares are taken over (the benchmark's own work between requests
        is left out)."""
        return sum(e - s for _, s, e in self.requests)

    def busy_ns(self, plane: Dict) -> float:
        return sum(tr.busy_ns(plane, s, e) for _, s, e in self.requests)

    def busy(self) -> List[np.ndarray]:
        if not hasattr(self, "_busy"):
            self._busy = [tr.busy(p) for p in self.devices]
        return self._busy


@dataclass
class Context:
    cell: Dict
    cfg: Dict
    mix: Dict
    chips: int
    requests: List[Any]
    setup_s: float
    window_s: float
    peaks: Optional[Dict] = None
    traced: Optional[Traced] = None


def done(ctx: Context, kind: str) -> List[Any]:
    return [r for r in ctx.requests if r.kind == kind
            and r.answer is not None]


def latency_percentile(ctx: Context, kind: str, q: float
                       ) -> Optional[float]:
    """The ``q``-th percentile (linear between order statistics) of the
    latencies of all the window's answered requests of ``kind``."""
    lat = [r.end - r.start for r in done(ctx, kind)]
    return float(np.percentile(lat, q)) if lat else None


def traced(ctx: Context, kind: str) -> List[Tuple[float, float]]:
    if ctx.traced is None or not ctx.traced.devices:
        return []
    return [(s, e) for k, s, e in ctx.traced.requests if k == kind]


def frontend_s(ctx: Context, kind: str) -> Optional[float]:
    """Mean host time per request before its first and after its last
    device operation (on any chip)."""
    vals = []
    for lo, hi in traced(ctx, kind):
        iv = tr.merge(x for m in ctx.traced.busy()
                      for x in tr.clip(m, lo, hi))
        if len(iv):
            vals.append((iv[0, 0] - lo) + (hi - iv[-1, 1]))
    return float(np.mean(vals)) * 1e-9 if vals else None


def block_gap_s(ctx: Context, kind: str) -> Optional[float]:
    """Mean device idle time per request between its first and last
    device operation, averaged over the chips."""
    vals = []
    for lo, hi in traced(ctx, kind):
        per_chip = []
        for m in ctx.traced.busy():
            iv = tr.clip(m, lo, hi)
            if len(iv):
                span = iv[-1, 1] - iv[0, 0]
                per_chip.append(span - (iv[:, 1] - iv[:, 0]).sum())
        if per_chip:
            vals.append(np.mean(per_chip))
    return float(np.mean(vals)) * 1e-9 if vals else None


def idle_pct(ctx: Context, kind: str) -> Optional[float]:
    """Share of the traced requests' time in which a chip ran no
    operation, averaged over the chips."""
    if not traced(ctx, kind):
        return None
    t = ctx.traced
    busy = np.mean([t.busy_ns(p) for p in t.devices])
    return 100.0 * (1.0 - busy / t.window_ns())


def scan_device_s(ctx: Context, kind: str) -> Optional[float]:
    """Mean device time per request of the scan programs, per chip."""
    vals = []
    for lo, hi in traced(ctx, kind):
        per_chip = [sum(t for name, t in tr.program_ns(p, lo, hi).items()
                        if name in SCAN_PROGRAMS)
                    for p in ctx.traced.devices]
        vals.append(np.mean(per_chip))
    return float(np.mean(vals)) * 1e-9 if vals and max(vals) > 0 else None


def pad_pct(ctx: Context) -> Optional[float]:
    """Share of the scenario slots the block engine ran that held no
    scenario: slots from the ``grid.block`` spans' ``size`` and the
    ``grid.round`` spans' ``scenarios`` (the mesh's dummy blocks
    included), scenarios from the ``grid.simulate`` spans' ``n`` less
    what dedup removed; from the requests ``repro.obs`` recorded."""
    if ctx.traced is None:
        return None
    spans = ctx.traced.obs_spans
    slots = (sum(s.attrs.get("size", 0) for s in spans
                 if s.name == "grid.block")
             + sum(s.attrs.get("scenarios", 0) for s in spans
                   if s.name == "grid.round"))
    if not slots:
        return None
    rows = sum(s.attrs.get("n", 0) for s in spans
               if s.name == "grid.simulate")
    c = ctx.traced.counters
    rows -= c.get("grid.dedup.total", 0.0) - c.get("grid.dedup.kept", 0.0)
    return 100.0 * (slots - rows) / slots


def breakdown(t: Traced, top: int = 10) -> Dict[str, List]:
    """The programs that took the most device time in the traced requests
    (seconds per chip; ``(scan names matched none)`` sums the programs no
    name of ``SCAN_PROGRAMS`` matched), and the longest idle gaps of the
    first chip inside them, each named by the request's kind and the host
    span open in it, or where none is, its place among the request's
    device operations."""
    totals: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for kind, lo, hi in t.requests:
        for p in t.devices:
            for name, ns in tr.program_ns(p, lo, hi).items():
                totals[name] = totals.get(name, 0.0) + ns / len(t.devices)
        if t.devices:
            gaps += [(f"{kind}: {n}", s) for n, s in
                     tr.named_gaps(t.busy()[0], lo, hi, t.spans, top)]
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    ops = [[n, v * 1e-9] for n, v in ranked[:top - 1]]
    other = sum(v for n, v in totals.items() if n not in SCAN_PROGRAMS)
    ops.append(["(scan names matched none)", other * 1e-9])
    gaps = sorted(gaps, key=lambda g: -g[1])[:top]
    return {"device_ops": ops, "idle_gaps": [[n, s] for n, s in gaps]}


def least_time_s(ops: float, nbytes: float, peaks: Dict, chips: int
                 ) -> Tuple[float, str]:
    """The least time ``chips`` chips need for ``ops`` operations and
    ``nbytes`` bytes of memory traffic, and which bound binds."""
    t_ops = ops / (peaks["flops_per_s"] * chips)
    t_mem = nbytes / (peaks["hbm_bytes_per_s"] * chips)
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")

