"""Run-telemetry layer (``repro.obs``): the wind tunnel observing itself.

Acceptance contract of the observability PR:

* **Off by default, invisible when off.** The disabled path records
  nothing — no spans, no counters — and ``obs.span`` returns a shared
  null context manager (no per-call allocation). Enabling telemetry
  changes no computed number: grid results are bit-identical with obs
  on and off.
* **Spans nest and carry attributes.** ``parent_id`` links children to
  the enclosing span while it is still open; ``obs.timed`` records
  unconditionally (the explicit call is the opt-in) and exposes the
  measured wall time.
* **Bounded retention.** The ring drops oldest beyond ``capacity``;
  ``retention_s`` ages spans out by time against an injectable clock,
  and the JSONL collect file prunes itself the same way.
* **The engines emit.** ``simulate_grid`` aggregate runs produce
  ``grid.simulate``/``grid.block`` spans plus dedup counters;
  ``devices=4`` sharded runs produce per-round ``grid.round`` spans
  with device/block attrs; ``search()``/``fit()`` produce kernel spans;
  warn-once messages double as counters (visible even after Python's
  warning dedup silences the repeat).
* **The golden round-trip.** An instrumented experiment's stage spans
  export as OTel-style dicts (``to_otel_spans``) that feed straight
  back into ``ObservedTrace.from_otel_spans`` and support a refit —
  the twin calibrates from the tool's own telemetry.

Multi-device cases need ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
exported before the first jax import (the CI obs-suite job does);
without it they skip rather than sharding a 1-device mesh.
"""
import dataclasses
import json
import time
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro import obs  # noqa: E402
from repro.core.simulate import simulate_grid  # noqa: E402
from repro.core.slo import SLO  # noqa: E402
from repro.core.traffic import TrafficModel  # noqa: E402
from repro.core.twin import SimpleTwin, make_twin  # noqa: E402
from repro.obs.export import (append_jsonl, prometheus_exposition,  # noqa: E402
                              read_jsonl, to_otel_spans)
from repro.obs.record import _NULL, Recorder  # noqa: E402

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "before the first jax import")

SLO_4H = SLO(limit_s=4 * 3600, met_fraction=0.95)


@pytest.fixture(autouse=True)
def _obs_clean_slate():
    """Every test starts disabled with an empty global recorder and
    leaves the module state the way it found it."""
    was_enabled = obs.enabled()
    obs.disable()
    obs.get_recorder().clear()
    yield
    if was_enabled:
        obs.enable()
    else:
        obs.disable()
    obs.get_recorder().clear()


def _small_grid(n=16, distinct=4, t_bins=168):
    """n scenarios over `distinct` twin configs x 2 traffics — the
    dedup pass collapses the grid `n / distinct`-fold. ``t_bins`` trims
    the year to a week, so pass ``bin_hours=1.0`` to ``simulate_grid``."""
    twins = [SimpleTwin(f"tw{i % distinct}", 1.5 + 0.3 * (i % distinct),
                        0.01, 0.15) for i in range(n)]
    matrix = np.stack(
        [TrafficModel.honda_default("a", G=1.2).hourly_loads()[:t_bins],
         TrafficModel.honda_default("b", G=1.5).hourly_loads()[:t_bins]],
    ).astype(np.float32)
    index = (np.arange(n, dtype=np.int32) % distinct) % 2
    return twins, matrix, index


# ---------------------------------------------------------------------------
# off by default: no recording, no allocation, no numeric effect
# ---------------------------------------------------------------------------

def test_disabled_by_default_records_nothing():
    rec = obs.get_recorder()
    assert not obs.enabled()

    with obs.span("should.not.record", n=1):
        pass
    obs.count("should.not.count", 5)
    obs.gauge("should.not.gauge", 1.0)
    obs.event("should.not.event")

    twins, matrix, index = _small_grid()
    simulate_grid(twins, slo=SLO_4H, bin_hours=1.0, return_series=False,
                  load_matrix=matrix, load_index=index)

    assert len(rec.spans) == 0
    assert rec.counters == {} and rec.gauges == {}


def test_disabled_span_is_shared_null():
    # the disabled fast path hands every call site the SAME null span —
    # no per-call allocation — and its attrs dict accepts writes
    s1 = obs.span("a", x=1)
    s2 = obs.span("b")
    assert s1 is _NULL and s2 is _NULL
    with obs.span("c") as sp:
        sp.attrs["compiled"] = 1.0     # the block-engine write pattern


def test_enabling_does_not_change_grid_numbers():
    twins, matrix, index = _small_grid()
    base = simulate_grid(twins, slo=SLO_4H, bin_hours=1.0, return_series=False,
                         load_matrix=matrix, load_index=index)
    with obs.capture():
        instrumented = simulate_grid(twins, slo=SLO_4H, bin_hours=1.0,
                                     return_series=False,
                                     load_matrix=matrix,
                                     load_index=index)
    for a, b in zip(base, instrumented):
        assert a.mean_latency_s == b.mean_latency_s
        assert a.grand_total_usd == b.grand_total_usd
        assert a.pct_latency_met == b.pct_latency_met


# ---------------------------------------------------------------------------
# spans: nesting, attrs, decorator, timed
# ---------------------------------------------------------------------------

def test_span_nesting_links_parent_ids():
    with obs.capture() as rec:
        with obs.span("outer", layer=0) as outer:
            with obs.span("inner", layer=1):
                time.sleep(0.002)
    outer_sp, = rec.find(name="outer")
    inner_sp, = rec.find(name="inner")
    assert outer_sp.parent_id is None
    assert inner_sp.parent_id == outer_sp.span_id
    assert inner_sp.attrs["layer"] == 1
    assert outer_sp.duration >= inner_sp.duration > 0


def test_span_attrs_mutable_until_exit():
    with obs.capture() as rec:
        with obs.span("block", size=8) as sp:
            sp.attrs["compiled"] = 1.0
    sp, = rec.find(name="block")
    assert sp.attrs == {"size": 8, "compiled": 1.0}


def test_instrument_decorator_names_and_gates():
    @obs.instrument(name="custom.op", kind="unit")
    def work(x):
        return x + 1

    assert work.__obs_name__ == "custom.op"
    assert work(1) == 2                      # disabled: plain call
    assert len(obs.get_recorder().spans) == 0
    with obs.capture() as rec:
        assert work(2) == 3
    sp, = rec.find(name="custom.op")
    assert sp.attrs["kind"] == "unit"


def test_timed_always_records_and_exposes_elapsed():
    assert not obs.enabled()
    with obs.timed("bench.thing", n=4) as tm:
        time.sleep(0.002)
    assert tm.elapsed >= 0.002
    sp, = obs.get_recorder().find(name="bench.thing")
    assert sp.attrs["n"] == 4
    assert sp.duration == pytest.approx(tm.elapsed)


def test_capture_restores_state_and_injected_recorder():
    global_rec = obs.get_recorder()
    mine = Recorder()
    with obs.capture(recorder=mine) as rec:
        assert rec is mine
        assert obs.enabled()
        with obs.span("inside"):
            pass
    assert not obs.enabled()
    assert obs.get_recorder() is global_rec
    assert len(mine.find(name="inside")) == 1
    assert len(global_rec.spans) == 0


# ---------------------------------------------------------------------------
# counters / gauges
# ---------------------------------------------------------------------------

def test_labeled_counters_accumulate_and_flatten():
    with obs.capture() as rec:
        obs.count("grid.blocks", 3, backend="xla", devices=1)
        obs.count("grid.blocks", 2, backend="xla", devices=1)
        obs.count("grid.blocks", 5, backend="pallas", devices=1)
        obs.gauge("grid.block_size", 4480)
        flat = obs.counters()
    assert rec.counter_total("grid.blocks") == 10
    assert flat["grid.blocks{backend=xla,devices=1}"] == 5
    assert flat["grid.blocks{backend=pallas,devices=1}"] == 5


# ---------------------------------------------------------------------------
# bounded retention: capacity ring + time window
# ---------------------------------------------------------------------------

def test_ring_capacity_drops_oldest():
    rec = Recorder(capacity=4)
    for i in range(10):
        rec.add_span(f"s{i}", float(i), float(i) + 0.5)
    names = [s.name for s in rec.find()]
    assert names == ["s6", "s7", "s8", "s9"]


def test_retention_prunes_by_injected_clock():
    t = {"now": 100.0}
    rec = Recorder(retention_s=10.0, clock=lambda: t["now"])
    rec.add_span("old", 80.0, 85.0)
    rec.add_span("fresh", 95.0, 99.0)
    # the next add prunes lazily: cutoff = 100 - 10 = 90 drops "old"
    rec.add_span("new", 99.0, 100.0)
    assert [s.name for s in rec.find()] == ["fresh", "new"]
    t["now"] = 120.0
    assert rec.prune() == 2
    assert rec.find() == []


# ---------------------------------------------------------------------------
# the engines emit: grid spans + dedup counters, sharded per-round spans
# ---------------------------------------------------------------------------

def test_grid_emits_spans_and_dedup_counters():
    twins, matrix, index = _small_grid(n=16, distinct=4)
    with obs.capture() as rec:
        # scenario_block=2 forces the blocked engine on the 4 kept
        # (deduped) scenarios: 2 blocks, each its own span
        rows = simulate_grid(twins, slo=SLO_4H, bin_hours=1.0,
                             return_series=False, scenario_block=2,
                             load_matrix=matrix, load_index=index)
    assert len(rows) == 16
    top, = rec.find(name="grid.simulate")
    assert top.attrs["n"] == 16 and top.attrs["mode"] == "agg"
    blocks = rec.find(name="grid.block")
    assert len(blocks) == 2, "blocked run must emit per-block spans"
    for sp in blocks:
        assert sp.parent_id == top.span_id
        assert sp.attrs["backend"] in ("xla", "pallas")
        assert sp.attrs["compiled"] in (0.0, 1.0)
        assert sp.attrs["size"] == 2
    # 16 scenarios over 4 distinct configs: dedup collapses 4x
    assert rec.counter_total("grid.dedup.total") == 16
    assert rec.counter_total("grid.dedup.kept") == 4
    assert rec.counter_total("grid.scenarios") == 16
    assert rec.counter_total("grid.blocks") == 2
    assert ("grid.block_size", ()) in rec.gauges


def test_series_mode_emits_simulate_span():
    twins, matrix, index = _small_grid(n=4, distinct=4)
    with obs.capture() as rec:
        sims = simulate_grid(twins, slo=SLO_4H, bin_hours=1.0, return_series=True,
                             load_matrix=matrix, load_index=index)
    assert len(sims) == 4
    top, = rec.find(name="grid.simulate")
    assert top.attrs["mode"] == "series"
    assert top.attrs["faulted"] is False


@needs4
def test_sharded_grid_emits_per_round_spans():
    d, block, n = 4, 8, 64                  # 2 rounds of d*block = 32
    twins, matrix, index = _small_grid(n=n, distinct=n)
    with obs.capture() as rec:
        rows = simulate_grid(twins, slo=SLO_4H, bin_hours=1.0, return_series=False,
                             load_matrix=matrix, load_index=index,
                             scenario_block=block, devices=d)
    assert len(rows) == n
    rounds = rec.find(name="grid.round")
    assert len(rounds) == 2
    for i, sp in enumerate(rounds):
        assert sp.attrs["round"] == i
        assert sp.attrs["devices"] == d
        assert sp.attrs["block"] == block
        assert sp.attrs["scenarios"] == d * block
        assert sp.attrs["compiled"] in (0.0, 1.0)
    # the first dispatch of a fresh shape traces; later rounds reuse it
    assert rounds[1].attrs["compiled"] == 0.0
    flat = obs.counters()                    # capture() left the spans +
    key = f"grid.blocks{{backend=xla,devices={d}}}"   # counters in place
    assert flat[key] == n // block


# ---------------------------------------------------------------------------
# spans on the profiler's clock: annotations, no sync in the block loop
# ---------------------------------------------------------------------------

class _Annotations:
    """Stand-in for ``jax.profiler.TraceAnnotation``: logs each open and
    close and keeps the stack of open annotations."""

    def __init__(self):
        self.log, self.stack = [], []
        outer = self

        class Stub:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                outer.log.append(self.name)
                outer.stack.append(self.name)
                return self

            def __exit__(self, *exc):
                outer.log.append("/" + outer.stack.pop())
                return False

        self.cls = Stub


@pytest.fixture()
def annotations(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann.cls)
    return ann


def test_span_opens_a_trace_annotation_only_when_on(annotations):
    with obs.span("off"):
        pass
    obs.instrument(name="off.deco")(lambda: None)()
    assert annotations.log == []
    with obs.capture():
        with obs.span("outer"):
            with obs.span("inner"):
                pass
        obs.instrument(name="deco")(lambda: None)()
    assert annotations.log == ["outer", "inner", "/inner", "/outer",
                               "deco", "/deco"]


def test_block_loop_never_syncs_inside_grid_block(annotations, monkeypatch):
    """With obs on, the block engine waits for the device only in
    ``grid.upload`` (its own copies, nothing queued yet) and at the drain,
    never inside a ``grid.block`` span."""
    real = jax.block_until_ready
    synced_in = []

    def spy(x):
        synced_in.append(tuple(annotations.stack))
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", spy)
    twins, matrix, index = _small_grid(n=8, distinct=8)
    with obs.capture() as rec:
        simulate_grid(twins, slo=SLO_4H, bin_hours=1.0, return_series=False,
                      scenario_block=2, load_matrix=matrix,
                      load_index=index)
    assert annotations.log.count("grid.block") == 4
    assert len(rec.find(name="grid.block")) == 4
    assert not [s for s in synced_in if "grid.block" in s]
    assert [s[-1] for s in synced_in] == ["grid.upload"]


#: the host-side spans of one grid, by engine
_SMALL_SPANS = ("grid.params", "grid.simulate", "grid.dedup", "grid.plan",
                "grid.upload", "grid.scan", "grid.drain", "grid.summarise")
_BLOCK_SPANS = ("grid.params", "grid.simulate", "grid.dedup", "grid.plan",
                "grid.upload", "grid.drain", "grid.scatter",
                "grid.summarise")


@pytest.mark.parametrize("scenario_block,names,absent", [
    (None, _SMALL_SPANS, ("grid.scatter", "grid.block")),
    (2, _BLOCK_SPANS, ("grid.scan",))])
def test_grid_spans_once_per_grid_and_bits_unchanged(scenario_block, names,
                                                     absent):
    """Each host-side span appears once per grid of distinct rows, on the
    small-grid engine and the block engine, the bytes copied to the
    device are counted, and the results are bit-identical with obs on
    and off."""
    from repro.core.twin import PARAM_DIM
    twins, matrix, index = _small_grid(n=8, distinct=8)
    kw = dict(slo=SLO_4H, bin_hours=1.0, return_series=False,
              scenario_block=scenario_block, load_matrix=matrix,
              load_index=index)
    base = simulate_grid(twins, **kw)
    with obs.capture() as rec:
        instrumented = simulate_grid(twins, **kw)
    for a, b in zip(base, instrumented):
        for f in dataclasses.fields(a):
            np.testing.assert_array_equal(getattr(a, f.name),
                                          getattr(b, f.name))
    for name in names:
        assert len(rec.find(name=name)) == 1, name
    for name in absent:
        assert not rec.find(name=name), name
    if scenario_block is None:      # the gathered [8, T] rows, in one go
        h2d = 8 * (matrix.shape[1] * 4 + PARAM_DIM * 4 + 4)
    else:                           # the matrix, then 4 blocks of 2 rows
        h2d = matrix.nbytes + 4 * (2 * 4 + 2 * PARAM_DIM * 4 + 4)
    assert rec.counter_total("grid.h2d_bytes") == h2d


def test_whatif_query_spans():
    from repro.core.whatif import run_grid, table2_rows
    twins = [SimpleTwin("a", 1.9512, 0.0082, 0.15),
             SimpleTwin("b", 6.15, 0.0703, 0.06)]
    with obs.capture() as rec:
        traffics = [TrafficModel.honda_default("x", G=1.2),
                    TrafficModel.honda_default("y", G=1.5)]
        rows = table2_rows(run_grid(twins, traffics, slo=SLO_4H))
    assert len(rows) == 4
    assert len(rec.find(name="traffic.honda_default")) == 2
    for name in ("whatif.loads", "whatif.table2", "grid.simulate",
                 "grid.summarise"):
        assert len(rec.find(name=name)) == 1, name


# ---------------------------------------------------------------------------
# search / fit spans + warn events as counters
# ---------------------------------------------------------------------------

def test_search_emits_kernel_span_and_infeasible_event():
    from repro.search import SearchInfeasibleWarning, search, search_space

    base = make_twin("tiny", "shed", max_rps=0.5, usd_per_hour=0.0082,
                     base_latency_s=0.9, queue_cap_hours=1.0)
    sp = search_space(base, ("queue_cap_hours",))
    loads = TrafficModel.honda_default("w").hourly_loads()[:168]
    slo = SLO(limit_s=1.0, met_fraction=0.99)
    with obs.capture() as rec:
        with pytest.warns(SearchInfeasibleWarning):
            res = search(sp, loads=loads, bin_hours=1.0, slo=slo,
                         restarts=4, steps=30, seed=0)
    assert not res.feasible
    kernel = rec.find(name="search.kernel")
    assert kernel and kernel[0].attrs["restarts"] == 4
    assert rec.counter_total("warn.search_infeasible") == 1
    assert rec.counter_total("search.restarts") >= 4
    flat = obs.counters()
    assert flat["search.objective_choice{policy=shed,stream=False}"] >= 1


def test_fit_emits_span_and_pinned_warn_events():
    from repro.calibrate import ObservedTrace, fit
    from repro.core.loadpattern import LoadPattern

    truth = SimpleTwin("t", 2.0, 0.05, 0.2)
    tr = ObservedTrace.from_loadpattern(
        LoadPattern.steady("steady", 1800.0, 3.0), truth, bin_s=300.0)
    giant = SimpleTwin("g", 2000.0, 0.05, 0.2)    # box tops out at 1e3
    with obs.capture() as rec:
        with pytest.warns(UserWarning):
            fit(tr, "fifo", restarts=2, steps=5, seed=0, init=giant)
    span_, = rec.find(name="calibrate.fit")
    assert span_.attrs["policy"] == "fifo"
    assert span_.attrs["restarts"] == 2
    assert rec.counter_total("warn.fit_warm_start_outside") == 1
    assert rec.counter_total("warn.fit_pinned") == 1
    assert rec.counter_total("calibrate.fits") == 1


def test_replication_fallback_counts_every_event():
    from repro.distributed import sharding

    with obs.capture() as rec:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            # same site twice: Python's warn-once dedup fires the
            # RuntimeWarning only the first time, but the obs counter
            # must see BOTH fallbacks
            sharding._warn_replicated("obs-test(x)", "scenario", 23, 4)
            sharding._warn_replicated("obs-test(x)", "scenario", 23, 4)
    flat = obs.counters()
    key = "warn.replication_fallback{axis=scenario,where=obs-test(x)}"
    assert flat[key] == 2


def test_faults_expand_grid_counts():
    from repro import faults

    sched = faults.FaultSchedule(
        specs=(faults.outage(rate_per_year=6, duration_hours=(1, 4)),),
        n_futures=2, seed=0)
    twins, matrix, index = _small_grid(n=4, distinct=4)
    sampled = faults.sample_futures(sched, matrix.shape[1])
    with obs.capture() as rec:
        grid = faults.expand_grid(sampled, matrix, index)
    assert grid.load_index.shape[0] == 8
    assert rec.counter_total("faults.futures") == 2
    assert rec.counter_total("faults.rows") == 8
    assert rec.find(name="faults.expand_grid")


# ---------------------------------------------------------------------------
# exporters: Prometheus exposition, JSONL retention, dispatch profiles
# ---------------------------------------------------------------------------

def test_prometheus_exposition_shape():
    twins, matrix, index = _small_grid(n=4, distinct=4)
    with obs.capture() as rec:
        rows = simulate_grid(twins, slo=SLO_4H, bin_hours=1.0, return_series=False,
                             load_matrix=matrix, load_index=index)
        text = prometheus_exposition(rows, recorder=rec)
    lines = text.strip().split("\n")
    families = [ln.split()[2] for ln in lines if ln.startswith("# TYPE")]
    for fam in ("plantd_latency_seconds", "plantd_latency_mean_seconds",
                "plantd_message_count",
                "plantd_target_compliance_percent", "plantd_cost_usd",
                "plantd_throughput_rph", "plantd_obs_events_total",
                "plantd_obs_span_count"):
        assert fam in families, fam
    # every sample line parses: name{labels} float
    samples = [ln for ln in lines if not ln.startswith("#")]
    assert len(samples) > 20
    for ln in samples:
        metric, val = ln.rsplit(" ", 1)
        float(val)
        assert metric[0].isalpha()
    # each scenario appears at 3 quantiles
    q_lines = [ln for ln in samples
               if ln.startswith("plantd_latency_seconds{")]
    assert len(q_lines) == 3 * len(rows)
    assert 'quantile="0.95"' in text
    # the engine's own counters ride along as obs events
    assert 'event="grid.scenarios"' in text


def test_jsonl_append_prunes_by_retention(tmp_path):
    path = str(tmp_path / "collect.jsonl")
    rec = Recorder()
    t0 = rec.mono0
    rec.add_span("tick.a", t0 + 0.0, t0 + 1.0)
    rec.count("events", 2)
    n = append_jsonl(path, rec, retention_s=3600.0,
                     now=rec.wall0 + 10.0)
    assert n == 2                            # one span + one snapshot
    assert len(rec.spans) == 0               # clear=True drained the ring

    # a second tick an hour later: the first span ages out of the window
    rec.add_span("tick.b", t0 + 3599.0, t0 + 3600.0)
    rec.count("events", 3)
    append_jsonl(path, rec, retention_s=1800.0,
                 now=rec.wall0 + 3601.0)
    data = read_jsonl(path)
    assert [d["name"] for d in data["spans"]] == ["tick.b"]
    # counters are cumulative; the latest snapshot wins
    assert data["counters"][-1]["values"]["events"] == 5.0
    # every line is valid JSON with a type tag
    with open(path) as f:
        for ln in f:
            assert json.loads(ln)["type"] in ("span", "counters")


def test_profile_dispatch_splits_compile_and_execute():
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return (x @ x.T).sum()

    x = jnp.ones((256, 256), jnp.float32)
    out, prof = obs.profile_dispatch("unit.matmul", f, x, reps=2,
                                     size=256)
    assert float(out) == pytest.approx(256.0 ** 3)
    assert prof.compile_s > 0 and prof.execute_s > 0
    assert prof.reps == 2
    row = prof.row()
    assert row["name"] == "unit.matmul" and row["size"] == 256.0
    assert "compile_s" in row and "execute_s" in row
    rec = obs.get_recorder()
    sp, = rec.find(name="dispatch.unit.matmul")
    assert sp.attrs["compile_s"] == prof.compile_s
    assert rec.profiles[-1] is prof
    # CPU XLA exposes the compiled program's memory analysis
    if prof.peak_temp_bytes is not None:
        assert prof.peak_temp_bytes >= 0
        assert "peak_temp_mb" in row


def test_jit_cache_growth_detection():
    import jax.numpy as jnp

    @jax.jit
    def g(x):
        return x * 2

    g._clear_cache() if hasattr(g, "_clear_cache") else None
    before = obs.jit_cache_size(g)
    g(jnp.ones((3,)))
    assert obs.jit_cache_grew(g, before)
    mid = obs.jit_cache_size(g)
    g(jnp.ones((3,)))                        # cache hit: no growth
    assert not obs.jit_cache_grew(g, mid)


# ---------------------------------------------------------------------------
# the golden round-trip: instrumented experiment -> OTel export ->
# from_otel_spans -> refit
# ---------------------------------------------------------------------------

def test_otel_export_roundtrip_refits_twin():
    from repro.calibrate import ObservedTrace, fit
    from repro.core.datagen import DataGenerator
    from repro.core.experiment import Experiment
    from repro.core.loadpattern import LoadPattern
    from repro.core.pipeline import Pipeline, PipelineStage, Resources
    from repro.core.schema import FieldSpec, Schema

    def work(batch):
        time.sleep(0.004)
        return batch

    pipe = Pipeline("rt", [PipelineStage("only_stage", work)],
                    resources=Resources(vcpus=1, ram_gb=1))
    schema = Schema("one", (FieldSpec("x", "float"),))
    ds = DataGenerator(0).generate(schema, 100)
    load = LoadPattern.steady("rt-load", duration_s=1.2, rate=60)

    with obs.capture() as rec:
        res = Experiment("rt", pipe, load, ds, drain_timeout_s=30).run()
    assert res.drained

    # the pipeline's stage spans were mirrored into obs with records
    spans = to_otel_spans(rec, prefix="stage.")
    assert spans, "instrumented experiment produced no stage spans"
    for d in spans:
        assert d["status"] == "OK"
        assert d["records"] >= 1
        assert d["end"] >= d["start"]
        # unix epoch, not monotonic: the wall anchor placed them
        assert d["start"] > 1e9

    trace = ObservedTrace.from_otel_spans(spans, bin_seconds=0.25,
                                          name="obs-roundtrip")
    assert trace.num_bins >= 2
    assert float(np.sum(trace.arrivals)) == pytest.approx(
        sum(d["records"] for d in spans))

    result = fit(trace, "fifo", restarts=2, steps=30, seed=0)
    assert np.isfinite(result.loss)
    assert result.twin.max_rps > 0


def test_report_renders_spans_counters_and_profiles():
    from repro.obs.report import render, summarize

    with obs.capture() as rec:
        with obs.span("demo.outer", records=8):
            time.sleep(0.002)
        obs.count("demo.events", 3, kind="x")
        obs.gauge("demo.level", 7.0)
        stats = summarize(rec)
        text = render(rec)
    assert stats["demo.outer"]["count"] == 1
    assert stats["demo.outer"]["records"] == 8.0
    assert "demo.outer" in text
    assert "demo.events{kind=x}" in text
    assert "demo.level" in text


def test_report_from_jsonl_file(tmp_path):
    from repro.obs.report import _report_file

    path = str(tmp_path / "obs.jsonl")
    rec = Recorder()
    rec.add_span("tick", rec.mono0, rec.mono0 + 0.5, {"records": 4})
    rec.count("ticks", 2)
    append_jsonl(path, rec)
    text = _report_file(path)
    assert "tick" in text and "ticks" in text
