"""One run of one cell: set-up, the measured window, the check, the line.

Everything a cell is made of is found by name: its entry in
``BENCHMARK.json``, its configuration ``bench/configs/<config>.json``, its
traffic mix ``bench/traffic/<traffic>.json`` and one reader
``bench/metrics/<metric>.py`` per metric it reports. No code here names a
cell, a configuration, a mix or a metric.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Callable, Dict, List, Optional, TextIO

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class DeviceError(RuntimeError):
    """The machine lacks what the cell asks for."""


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(spec: Dict, workload: str) -> Dict:
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in spec['workloads']]}")


def cell_metrics(spec: Dict, workload: str, trace: bool) -> List[Dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    ones: each metric whose ``workloads`` names the cell, or that has
    none."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def reader(bench_dir: str, name: str) -> Callable:
    """``read`` of ``<bench_dir>/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_):
        if event == COMPILE_EVENT:
            self.n += 1

    def _event(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            self.n += 1


def check_devices(chips: int, peaks: Dict):
    """The chips the cell asks for, or ``DeviceError``: a TPU, at least
    ``chips`` of them, and a kind ``bench/peaks.json`` knows."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise DeviceError(f"the cell needs {chips} TPU chip(s); JAX sees "
                          f"{len(devs)} {devs[0].platform} device(s)")
    if kind not in peaks:
        raise DeviceError(f"no peaks for device kind {kind!r} in "
                          f"bench/peaks.json")
    return devs[:chips]


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Tracer:
    """The traced run's two phases. The profiler covers the window's first
    ``limit`` requests, each in a ``bench.<kind>`` span, with the
    program's ``repro.obs`` off, so the device readings are of the path
    the untraced window times. Then ``repro.obs`` records the next
    request, unprofiled, for the readings taken from its spans and
    counters: its ``grid.block`` spans block on their block, which would
    serialise the block engine under the profiler."""

    def __init__(self, limit: int):
        from repro import obs
        self.limit = limit
        self.profiled = 0
        self.observed = False
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.active = False
        self.recorder = obs.Recorder()

    def request(self, kind: str, window_over: bool):
        """The context to run the window's next request in."""
        import jax
        from repro import obs
        if self.profiled < self.limit and not window_over:
            if not self.active:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.dir, profiler_options=opts)
                self.active = True
            self.profiled += 1
            return jax.profiler.TraceAnnotation(f"bench.{kind}")
        self.stop()
        if not self.observed:
            self.observed = True
            return obs.capture(clear=False, recorder=self.recorder)
        return contextlib.nullcontext()

    def stop(self):
        import jax
        if self.active:
            jax.profiler.stop_trace()
            self.active = False

    def reduce(self):
        from bench import layers, trace_reduce as tr
        data = tr.from_xplane(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        requests = [(n[len("bench."):], s, s + d)
                    for n, s, d in tr.host_spans(data, "bench.")]
        counters = {}
        for (name, _), v in self.recorder.counters.items():
            counters[name] = counters.get(name, 0.0) + v
        return layers.Traced(devices=tr.device_planes(data),
                             requests=requests,
                             obs_spans=list(self.recorder.find()),
                             counters=counters)


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, system_factory=None, require_tpu: bool = True,
        out: TextIO = sys.stdout, err: TextIO = sys.stderr,
        root: str = ROOT) -> Dict:
    """Run one cell of the benchmark at ``root`` and print its result
    line; returns the result. ``require_tpu=False`` (tests only) skips
    the look for a chip."""
    from bench import check, generator, layers
    spec = load_json(root, "BENCHMARK.json")
    bench_dir = os.path.join(root, spec["paths"][0])
    cell = find_cell(spec, workload)
    cfg = load_json(bench_dir, "configs", f"{cell['config']}.json")
    mix = load_json(bench_dir, "traffic", f"{cell['traffic']}.json")
    peaks_all = load_json(bench_dir, "peaks.json")["devices"]
    metrics = cell_metrics(spec, workload, trace)
    readers = {m["name"]: reader(bench_dir, m["name"]) for m in metrics}

    import jax
    if require_tpu:
        devices = check_devices(cell["chips"], peaks_all)
    else:
        devices = jax.devices()[:cell["chips"]]
    if mix.get("devices", 1) > len(devices):
        raise DeviceError(f"the mix shards over {mix['devices']} devices; "
                          f"the cell has {len(devices)}")
    dev = devices[0]
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()

    if system_factory is None:
        from bench.system import System as system_factory
    traffic = generator.traffic(cfg, mix, seed, system_factory())
    warm = traffic.make(generator.WARMUP, 0)
    warm.answer = warm.call()
    setup_s = time.perf_counter() - t_start
    print(json.dumps({"setup_s": setup_s, "compile_cache": cache_dir,
                      "platform": dev.platform, "device_kind":
                      dev.device_kind, "device_count": len(devices)}),
          file=err, flush=True)

    tracer = (Tracer(int(mix.get("trace_requests", 1 << 30))) if trace
              else None)
    requests, failed = [], 0
    compiles0 = compiles.n
    t0 = time.perf_counter()
    window_over = False
    while not window_over or (tracer is not None and not tracer.observed):
        req = traffic.make(generator.WINDOW, len(requests))
        ctx = (tracer.request(req.kind, window_over) if tracer is not None
               else contextlib.nullcontext())
        with ctx:
            req.start = time.perf_counter()
            try:
                req.answer = req.call()
            except Exception:                   # noqa: BLE001
                failed += 1
                traceback.print_exc(file=err)
            req.end = time.perf_counter()
        requests.append(req)
        window_over = window_over or req.end - t0 >= seconds
    if tracer is not None:
        tracer.stop()
    window_s = time.perf_counter() - t0
    in_window = compiles.n - compiles0
    mem = memory_peak(devices)
    print(json.dumps({"requests": len(requests), "window_s": window_s,
                      "compiles_in_window": in_window}), file=err,
          flush=True)

    traced = tracer.reduce() if tracer is not None else None
    ctx = layers.Context(cell=cell, cfg=cfg, mix=mix, chips=len(devices),
                         requests=requests, setup_s=setup_s,
                         window_s=window_s,
                         peaks=peaks_all.get(dev.device_kind),
                         traced=traced)
    result_metrics = {}
    for m in metrics:
        v = readers[m["name"]](ctx)
        if v is not None:
            result_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    answered = [r for r in requests if r.answer is not None]
    notes: Dict[str, str] = {}
    gaps = check.check_requests(traffic, answered, cfg, seed, mix,
                                notes=notes)
    limits = check.load_limits(bench_dir)
    checks = {k: {"value": gaps[k], "limit": limits[k]}
              for k in check.NUMBERS}
    correct = (failed == 0 and bool(answered)
               and all(c["value"] <= c["limit"] for c in checks.values()))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": len(requests),
              "failed": failed, "metrics": result_metrics, "device": device,
              "compiles_in_window": in_window}
    if traced is not None and traced.devices and traced.requests:
        device["busy_s"] = float(np.mean([traced.busy_ns(p)
                                          for p in traced.devices])) * 1e-9
        device["window_s"] = traced.window_ns() * 1e-9
        result["breakdown"] = layers.breakdown(traced)
    result["checks"] = checks
    for k, note in notes.items():
        print(f"widest {k}: {note}", file=err)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result

