"""``repro.obs`` — run-telemetry for the wind tunnel itself.

PlantD's pitch is instrumenting pipelines; this package instruments the
*reproduction*: spans at every dispatch boundary, counters for the
runtime decisions that used to vanish into warn-once messages, per-
dispatch compile/execute/peak-memory profiling, and exporters that
round-trip straight back into the tool (OTel span dicts ->
``ObservedTrace.from_otel_spans`` -> refit).

Off by default: export ``REPRO_OBS=1``, or::

    from repro import obs
    with obs.capture() as rec:
        simulate_grid(..., return_series=False)
    print(obs.render(rec))                       # console report
    spans = obs.to_otel_spans(rec)               # feeds from_otel_spans
    text = obs.prometheus_exposition(rows)       # scrape-able exposition
    obs.append_jsonl("obs.jsonl", retention_s=600)   # rolling collect

Disabled overhead is one module-attribute check per call site — the
instrumentation never sits inside jitted code, so the simulated numbers
are bit-identical either way. Enabled, every ``span`` also opens a
``jax.profiler.TraceAnnotation`` of its name, so under a running
profiler the spans land on the trace's host plane, on the device
planes' clock: a device idle gap is then named by the span open over it.

The grid engine's spans: ``grid.simulate`` (root), ``grid.params``
(twin parameter rows), ``grid.dedup``, ``grid.plan`` (block plan and
staging), ``grid.upload`` (host-to-device copies; with telemetry on it
waits for them, before any device work is queued), ``grid.block`` per
block (the host's dispatch of the block: it does not wait for the
device) or ``grid.round`` per mesh round (which does), ``grid.scan``
(the small-grid dispatch), ``grid.drain`` (the wait for and copy of the
results), ``grid.scatter``, ``grid.summarise``; ``whatif.run_grid``
adds ``whatif.loads``, ``table2_rows`` ``whatif.table2`` and
``TrafficModel.honda_default`` ``traffic.honda_default``. The counter
``grid.h2d_bytes`` counts the bytes a grid copies to the device.

See ``record`` (spans/counters/ring buffer), ``profile``
(compile-vs-execute dispatch profiling via ``jax.stages``), ``export``
(OTel / Prometheus / JSONL) and ``report`` (the ``make obs-report``
console summary).
"""
from repro.obs.export import (append_jsonl, prometheus_exposition,
                              read_jsonl, to_otel_spans)
from repro.obs.profile import (DispatchProfile, jit_cache_grew,
                               jit_cache_size, profile_dispatch)
from repro.obs.record import (ObsSpan, Recorder, capture, count, counters,
                              disable, enable, enabled, event, gauge,
                              get_recorder, instrument, set_recorder,
                              span, timed)
from repro.obs.report import render, summarize

__all__ = [
    "DispatchProfile", "ObsSpan", "Recorder", "append_jsonl", "capture",
    "count", "counters", "disable", "enable", "enabled", "event",
    "gauge", "get_recorder", "instrument", "jit_cache_grew",
    "jit_cache_size", "profile_dispatch", "prometheus_exposition",
    "read_jsonl", "render", "set_recorder", "span", "summarize", "timed",
    "to_otel_spans",
]
