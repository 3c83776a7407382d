"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The profiler writes an XSpace (``*.xplane.pb``). ``from_xplane`` turns it
into plain data: planes, each with lines of ``[name, start_ns,
duration_ns]`` events, times relative to the start of the trace. Every
reduction below works on that plain form, so a small recorded or
synthesised trace (``bench/tests/data``) tests it on the CPU.

On a TPU each chip is a plane ``/device:TPU:<i>`` with the lines ``XLA
Ops`` (every operation the chip ran, nested ones included) and ``XLA
Modules`` (one event per program run, named ``jit_<function>(<hash>)``).
Host spans that the benchmark opens with ``jax.profiler.TraceAnnotation``
lie on the host plane ``/host:CPU``, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"

Event = Tuple[str, float, float]        # (name, start_ns, duration_ns)


def from_xplane(trace_dir: str) -> Dict:
    """Read the newest ``*.xplane.pb`` under ``trace_dir`` into plain
    data: ``{"planes": [{"name", "lines": [{"name", "events"}]}]}``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    planes = []
    for pl in data.planes:
        if not (pl.name.startswith(DEVICE_PREFIX)
                or pl.name.startswith(HOST_PREFIX)):
            continue
        lines = []
        for ln in pl.lines:
            if pl.name.startswith(DEVICE_PREFIX) and ln.name not in (
                    OPS_LINE, MODULES_LINE):
                continue
            lines.append({"name": ln.name, "events": [
                (e.name, e.start_ns, e.duration_ns) for e in ln.events]})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def _line(plane: Dict, name: str) -> List[Event]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def device_planes(trace: Dict) -> List[Dict]:
    """The planes of devices that ran something, in name order."""
    return sorted((p for p in trace["planes"]
                   if p["name"].startswith(DEVICE_PREFIX)
                   and (_line(p, OPS_LINE) or _line(p, MODULES_LINE))),
                  key=lambda p: p["name"])


def host_spans(trace: Dict, prefix: str = "") -> List[Event]:
    """Host events whose name starts with ``prefix``, by start time."""
    out = [e for p in trace["planes"] if p["name"].startswith(HOST_PREFIX)
           for ln in p["lines"] for e in ln["events"]
           if e[0].startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


def merge(intervals: Iterable[Tuple[float, float]]) -> np.ndarray:
    """Union of [start, end) intervals as a sorted [M, 2] array."""
    iv = np.asarray(list(intervals), np.float64).reshape(-1, 2)
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # a new run starts where an interval begins after every earlier end
    first = np.flatnonzero(np.concatenate([[True], iv[1:, 0] > ends[:-1]]))
    last = np.concatenate([first[1:] - 1, [len(iv) - 1]])
    return np.stack([iv[first, 0], ends[last]], axis=1)


def clip(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Merged intervals cut to [lo, hi]; empty pieces dropped."""
    if not len(merged):
        return merged
    out = np.stack([np.maximum(merged[:, 0], lo),
                    np.minimum(merged[:, 1], hi)], axis=1)
    return out[out[:, 1] > out[:, 0]]


def busy(plane: Dict) -> np.ndarray:
    """Merged intervals in which an operation ran on this device (the
    ``XLA Ops`` line; the ``XLA Modules`` line where no op was
    recorded)."""
    events = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
    return merge((s, s + d) for _, s, d in events)


def busy_ns(plane: Dict, lo: float, hi: float) -> float:
    iv = clip(busy(plane), lo, hi)
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def idle_gaps(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """[G, 2] idle intervals of [lo, hi] between busy intervals."""
    iv = clip(merged, lo, hi)
    edges = np.concatenate([[lo], iv.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def program_name(module_event_name: str) -> str:
    """``jit__agg_block_step_xla(1830...)`` -> ``jit__agg_block_step_xla``."""
    return re.sub(r"\(\d+\)$", "", module_event_name)


def program_ns(plane: Dict, lo: float, hi: float) -> Dict[str, float]:
    """Device time of each program (by name) inside [lo, hi]."""
    out: Dict[str, float] = {}
    for name, s, d in _line(plane, MODULES_LINE):
        t = min(s + d, hi) - max(s, lo)
        if t > 0:
            key = program_name(name)
            out[key] = out.get(key, 0.0) + t
    return out


def innermost(spans: Sequence[Tuple[str, float, float]], t: float
              ) -> Optional[str]:
    """Name of the latest-opened span that is open at time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else None


def gap_place(gap: np.ndarray, lo: float, hi: float) -> str:
    """Where an idle gap of [lo, hi] lies among its device operations."""
    first, last = gap[0] <= lo, gap[1] >= hi
    if first and last:
        return "no device op"
    return ("before the first op" if first else
            "after the last op" if last else "between ops")


def named_gaps(merged: np.ndarray, lo: float, hi: float,
               spans: Sequence[Tuple[str, float, float]], top: int = 10
               ) -> List[Tuple[str, float]]:
    """The ``top`` longest idle gaps in [lo, hi], longest first, each
    named by the host span open at its middle, or by its place
    (``gap_place``) when none is."""
    gaps = idle_gaps(merged, lo, hi)
    order = np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")[:top]
    return [(innermost(spans, float(gaps[i].mean()))
             or gap_place(gaps[i], lo, hi),
             float(gaps[i, 1] - gaps[i, 0]) * 1e-9) for i in order]
