"""The readers of the program's own spans (``bench/program_spans.py``) on
a small synthesised trace (``data/trace_spans.json``, times in ns) of one
chip: a sweep [0, 1000) with every grid span, a what-if query [2000,
2500) and a second sweep [3000, 3400) with ``grid.params`` alone. The
chip is idle in the first sweep over [0, 320), [500, 550) and [780,
1000), in the query over [2000, 2180) and [2380, 2500), in the second
sweep over [3000, 3100) and [3300, 3400). The span readers read the one
request ``repro.obs`` records: the first sweep or the query."""
import json
import os

import pytest

from bench import harness, layers, program_spans as ps
from bench import trace_reduce as tr
from bench.tests import tiny
from repro.obs import ObsSpan

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_spans.json")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ["prep_s.sweep", "prep_s.whatif", "upload_s.sweep",
         "summary_s.sweep", "summary_s.whatif", "h2d_mb.sweep"]


def _ctx(traced):
    return layers.Context(cell={}, cfg={}, mix={}, chips=1, requests=[],
                          setup_s=1.0, window_s=1.0, traced=traced)


@pytest.fixture()
def traced():
    """The trace as ``program_spans.span_tracer`` reduces it."""
    with open(DATA) as f:
        trace = json.load(f)
    return layers.Traced(
        devices=tr.device_planes(trace),
        requests=[(n[len("bench."):], s, s + d)
                  for n, s, d in tr.host_spans(trace, "bench.")],
        spans=[(n, s, s + d)
               for n, s, d in tr.host_spans(trace, ps.PROGRAM_SPANS)],
        counters={"grid.h2d_bytes": 3e6})


def _observe(traced, lo, hi):
    """``traced`` with the program spans in [lo, hi) as the request
    ``repro.obs`` recorded (seconds)."""
    traced.obs_spans = [ObsSpan(n, s * 1e-9, e * 1e-9)
                        for n, s, e in traced.spans if lo <= s < hi]
    return traced


@pytest.mark.parametrize("name,value", [
    # grid.params 50 + grid.dedup 20 + grid.plan 50
    ("prep_s.sweep", 120e-9),
    # whatif.loads 90 + grid.params 20 + grid.dedup 5 + grid.plan 10
    ("prep_s.whatif", 125e-9),
    ("upload_s.sweep", 100e-9),
    # grid.scatter 50 + grid.summarise 130
    ("summary_s.sweep", 180e-9),
    # grid.summarise 40 + whatif.table2 40
    ("summary_s.whatif", 80e-9),
    # 3e6 bytes over the sweep's one grid.simulate span
    ("h2d_mb.sweep", 3.0),
])
def test_reader_reads_its_hand_computed_value(traced, name, value):
    lo, hi = (2000, 2500) if name.endswith("whatif") else (0, 1000)
    ctx = _ctx(_observe(traced, lo, hi))
    assert harness.reader(BENCH, name)(ctx) == pytest.approx(value)


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_nothing_without_program_spans(traced, name):
    """A program that records only the root and block spans and counts
    no bytes, as one without these spans does, gives each reader nothing
    to read: it returns None and does not raise."""
    traced.obs_spans = [ObsSpan("grid.simulate", 0.0, 1.0),
                        ObsSpan("grid.block", 0.1, 0.2, {"size": 16})]
    traced.counters = {}
    assert harness.reader(BENCH, name)(_ctx(traced)) is None
    assert harness.reader(BENCH, name)(_ctx(None)) is None


@pytest.mark.parametrize("kind,value", [
    # idle 590 + 200 ns; no span but the root covers 10 + 20 + 10 + 50
    # of the first sweep's first gap, 10 + 10 of its last, and 10 + 50
    # + 100 of the second sweep
    ("sweep", 100.0 * 270 / 790),
    # idle 300 ns, 40 of it in no span but the root
    ("whatif", 100.0 * 40 / 300),
])
def test_idle_unnamed_pct_hand_computed(traced, kind, value):
    assert ps.idle_unnamed_pct(traced, kind) == pytest.approx(value)
    traced.spans = []
    assert ps.idle_unnamed_pct(traced, kind) is None


def test_breakdown_names_gaps_by_the_span_open_over_them(traced):
    gaps = layers.breakdown(traced)["idle_gaps"]
    assert [n for n, _ in gaps[:4]] == [
        "sweep: grid.upload", "sweep: grid.summarise",
        "whatif: whatif.loads", "whatif: whatif.table2"]
    assert [s for _, s in gaps[:4]] == pytest.approx(
        [320e-9, 220e-9, 180e-9, 120e-9])
    named = dict((n, s) for n, s in gaps)
    assert named["sweep: grid.drain"] == pytest.approx(50e-9)
    assert named["sweep: before the first op"] == pytest.approx(100e-9)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell,names", [
    ("t-sweep", ["prep_s.sweep", "upload_s.sweep", "summary_s.sweep",
                 "h2d_mb.sweep"]),
    ("t-whatif", ["prep_s.whatif", "summary_s.whatif"])])
def test_traced_run_reads_the_spans_of_the_observed_request(root, cell,
                                                             names):
    """A tiny traced run reads each span metric from the request
    ``repro.obs`` records. A tiny sweep copies its 70 x 336 load matrix
    and five blocks of 16 rows' index, parameters and policy."""
    from repro.core.twin import PARAM_DIM
    res, _, _ = tiny.run(root, cell, trace=True)
    assert res["correct"] is True
    for name in names:
        assert res["metrics"][name]["value"] > 0, name
    if cell == "t-sweep":
        assert res["metrics"]["h2d_mb.sweep"]["value"] == pytest.approx(
            (70 * 336 * 4 + 5 * (16 * 4 + 16 * PARAM_DIM * 4 + 4)) * 1e-6)


def test_span_run_lays_the_program_spans_on_the_profile(root):
    """``program_spans.run`` profiles with ``repro.obs`` on: the grid's
    spans lie on the trace inside the traced sweeps, the harness's own
    ``Tracer`` is back in place afterwards, and the answers stay correct
    (on the CPU no device plane is recorded, so no idle share reads)."""
    import io
    import time
    tracer = harness.Tracer
    out = io.StringIO()
    res, traced = ps.run("t-sweep", tiny.SEED, 0.5, time.perf_counter(),
                         out=out, err=io.StringIO(), require_tpu=False,
                         root=root)
    assert harness.Tracer is tracer
    assert res["correct"] is True
    assert json.loads(out.getvalue().splitlines()[-1]) == ps.unnamed(traced)
    reqs = [(lo, hi) for k, lo, hi in traced.requests if k == "sweep"]
    names = [n for n, s, e in traced.spans
             if any(lo <= s and e <= hi for lo, hi in reqs)]
    for name in ("grid.simulate", "grid.params", "grid.dedup", "grid.plan",
                 "grid.upload", "grid.block", "grid.drain", "grid.scatter",
                 "grid.summarise"):
        assert name in names, name
    assert names.count("grid.simulate") == len(reqs)
