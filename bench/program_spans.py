#!/usr/bin/env python3
"""The program's own ``repro.obs`` spans and counters, read for the
per-layer metrics, and laid on a traced run's profile to name its idle
gaps.

The harness's traced run records one request with ``repro.obs`` on,
outside the profile (``Traced.obs_spans``, ``Traced.counters``). The span
metrics read that request: ``prep_s.*`` (host work before the scan),
``upload_s.sweep`` (the host-to-device copies), ``summary_s.*`` (host
work after it) and ``h2d_mb.sweep`` (bytes copied per grid).

The harness profiles its requests with ``repro.obs`` off, so its trace
holds none of these spans and ``breakdown`` names idle gaps by their
place alone. This module's command runs a cell with a tracer whose
profiled requests also record ``repro.obs`` spans, each into a recorder
of its own that is then dropped: since an enabled span opens a profiler
annotation, the spans land on the profile's host plane, on the device's
clock. It prints the harness's result line, whose ``breakdown`` then
names each idle gap by the program span open over it, and one more line
with ``idle_unnamed_pct.<kind>``, the share of the chip's idle time in
the traced requests that no program span covers::

    python3 bench/program_spans.py --workload <name> --seed <n> \\
        --seconds <s>
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
from typing import Dict, Optional, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import trace_reduce as tr  # noqa: E402

#: the program's spans, by name prefix (``traffic.honda_default`` builds
#: a query's traffic cases)
PROGRAM_SPANS = ("grid.", "whatif.", "traffic.")
#: the grid's root span: it covers the whole dispatch and names no work
ROOT_SPAN = "grid.simulate"
#: host work before the scan: traffic loads, twin rows, dedup, block plan
PREP_SPANS = ("whatif.loads", "grid.params", "grid.dedup", "grid.plan")
#: the host-to-device copies of a grid
UPLOAD_SPANS = ("grid.upload",)
#: host work after the scan: scatter, ``GridSummary`` rows, Table II rows
SUMMARY_SPANS = ("grid.scatter", "grid.summarise", "whatif.table2")


def _observed(ctx, kind: str):
    """The spans ``repro.obs`` recorded of the harness's one observed
    request, in a run whose traced requests are of ``kind``."""
    t = ctx.traced
    if t is None or not any(k == kind for k, _, _ in t.requests):
        return []
    return t.obs_spans


def _length(merged: np.ndarray) -> float:
    return float((merged[:, 1] - merged[:, 0]).sum()) if len(merged) else 0.0


def span_s(ctx, kind: str, names: Tuple[str, ...]) -> Optional[float]:
    """Host seconds of the observed request inside the spans ``names``
    (the union of their intervals); None where it has no such span."""
    merged = tr.merge((s.start, s.end) for s in _observed(ctx, kind)
                      if s.name in names)
    return _length(merged) if len(merged) else None


def h2d_mb(ctx, kind: str) -> Optional[float]:
    """Megabytes a grid of the observed request copies from host to
    device: the ``grid.h2d_bytes`` counter per ``grid.simulate`` span."""
    grids = sum(s.name == ROOT_SPAN for s in _observed(ctx, kind))
    nbytes = ctx.traced.counters.get("grid.h2d_bytes") if grids else None
    return nbytes / grids * 1e-6 if nbytes is not None else None


def idle_unnamed_pct(traced, kind: str) -> Optional[float]:
    """Share of the chips' idle time in the traced requests of ``kind``
    that no program span in ``traced.spans`` covers, the root
    ``grid.simulate`` left out; mean over the chips. None where the
    trace has no such request, no device plane or no program span."""
    reqs = [(lo, hi) for k, lo, hi in traced.requests if k == kind]
    if not reqs or not traced.devices or not traced.spans:
        return None
    named = tr.merge((s, e) for n, s, e in traced.spans if n != ROOT_SPAN)
    shares = []
    for m in traced.busy():
        gaps = [g for lo, hi in reqs for g in tr.idle_gaps(m, lo, hi)]
        idle = sum(e - s for s, e in gaps)
        covered = sum(_length(tr.clip(named, s, e)) for s, e in gaps)
        if idle > 0:
            shares.append(100.0 * (1.0 - covered / idle))
    return float(np.mean(shares)) if shares else 0.0


@contextlib.contextmanager
def _recorded(annotation):
    from repro import obs
    with annotation, obs.capture(clear=False, recorder=obs.Recorder()):
        yield


def span_tracer():
    """``harness.Tracer`` with its profiled requests recorded by
    ``repro.obs``, and ``reduce`` keeping their spans as
    ``Traced.spans``; the last reduction is kept as ``traced``."""
    from bench import harness

    class SpanTracer(harness.Tracer):
        traced = None

        def request(self, kind: str, window_over: bool):
            ctx = super().request(kind, window_over)
            return _recorded(ctx) if self.active else ctx

        def reduce(self):
            spans = [(n, s, s + d) for n, s, d in
                     tr.host_spans(tr.from_xplane(self.dir), PROGRAM_SPANS)]
            traced = super().reduce()
            traced.spans = spans
            SpanTracer.traced = traced
            return traced

    return SpanTracer


def unnamed(traced) -> Dict[str, float]:
    """``idle_unnamed_pct.<kind>`` of each kind of request traced, where
    it reads."""
    out = {}
    for kind in sorted({k for k, _, _ in traced.requests}):
        v = idle_unnamed_pct(traced, kind)
        if v is not None:
            out[f"idle_unnamed_pct.{kind}"] = v
    return out


def run(workload: str, seed: int, seconds: float, t_start: float,
        out=sys.stdout, **kw):
    """One traced run of ``workload`` through ``harness.run`` (``kw``
    passes on) with the program's spans on the profile; prints the
    result line, then the line of ``unnamed``. Returns the result and
    the reduced trace."""
    from unittest import mock
    from bench import harness
    tracer = span_tracer()
    with mock.patch.object(harness, "Tracer", tracer):
        res = harness.run(workload, seed, seconds, True, t_start, out=out,
                          **kw)
    print(json.dumps(unnamed(tracer.traced)), file=out, flush=True)
    return res, tracer.traced


def main(argv=None) -> int:
    import argparse
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        run(args.workload, args.seed, args.seconds, t_start)
    except (harness.DeviceError, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
