"""The reduction from a profiler trace to busy time, idle share, time per
program and named idle gaps, on a small synthesised trace."""
import json
import os

import numpy as np
import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


@pytest.fixture()
def trace():
    with open(DATA) as f:
        return json.load(f)


def test_busy_union_merges_overlaps_and_nesting(trace):
    chip0 = tr.device_planes(trace)[0]
    np.testing.assert_array_equal(tr.busy(chip0), [[0, 150], [300, 400]])
    assert tr.busy_ns(chip0, 0, 500) == 250
    assert tr.busy_ns(chip0, 100, 350) == 100


def test_idle_share_and_device_planes(trace):
    chips = tr.device_planes(trace)
    assert [p["name"] for p in chips] == ["/device:TPU:0", "/device:TPU:1"]
    busy = np.mean([tr.busy_ns(p, 0, 500) for p in chips])
    assert 1.0 - busy / 500 == pytest.approx(0.25)


def test_time_per_program_by_jit_name(trace):
    chip0 = tr.device_planes(trace)[0]
    assert tr.program_ns(chip0, 0, 500) == {"jit__agg_block_step_xla": 150,
                                            "jit_other": 100}
    assert tr.program_ns(chip0, 100, 350) == {"jit__agg_block_step_xla": 50,
                                              "jit_other": 50}
    assert tr.program_name("jit__agg_block_step_xla(1830)") == \
        "jit__agg_block_step_xla"


def test_gaps_named_by_the_innermost_open_host_span(trace):
    chip0 = tr.device_planes(trace)[0]
    spans = [(n, s, s + d) for n, s, d in tr.host_spans(trace, "")]
    gaps = tr.named_gaps(tr.busy(chip0), 0, 500, spans)
    assert [n for n, _ in gaps] == ["inner", "bench.sweep"]
    assert [s for _, s in gaps] == pytest.approx([150e-9, 100e-9])
    assert [n for n, _, _ in tr.host_spans(trace, "bench.")] == [
        "bench.anchor", "bench.sweep"]


@pytest.mark.parametrize("gap,place", [
    ((0, 40), "before the first op"), ((40, 60), "between ops"),
    ((60, 100), "after the last op"), ((0, 100), "no device op")])
def test_gap_place_names_a_gap_by_where_it_lies(gap, place):
    assert tr.gap_place(np.array(gap, np.float64), 0, 100) == place


def test_gaps_with_no_host_span_named_by_place():
    busy = np.array([[10.0, 40.0], [60.0, 90.0]])
    assert [n for n, _ in tr.named_gaps(busy, 0, 100, [])] == [
        "between ops", "before the first op", "after the last op"]


def test_merge_of_nothing_and_clip():
    assert tr.merge([]).shape == (0, 2)
    np.testing.assert_array_equal(
        tr.clip(np.array([[0.0, 10.0], [20.0, 30.0]]), 5, 25),
        [[5, 10], [20, 25]])
    np.testing.assert_array_equal(
        tr.idle_gaps(np.array([[0.0, 10.0]]), 0, 20), [[10, 20]])


def test_layer_readers_on_the_trace(trace):
    """The per-layer readers over one traced sweep [0, 500) on two chips:
    chip 0 busy 250 ns (a scan program 150, another 100), chip 1 busy
    throughout (the scan program)."""
    import os
    from bench import harness, layers
    t = layers.Traced(devices=tr.device_planes(trace),
                      requests=[("sweep", 0.0, 500.0)],
                      spans=[(n, s, s + d) for n, s, d in
                             tr.host_spans(trace, "")
                             if not n.startswith("bench.")])
    ctx = layers.Context(cell={}, cfg={}, mix={}, chips=2, requests=[],
                         setup_s=1.0, window_s=1.0, traced=t)
    bench = os.path.dirname(os.path.dirname(__file__))
    read = lambda name: harness.reader(bench, name)(ctx)  # noqa: E731
    assert read("idle_pct.sweep") == pytest.approx(25.0)
    assert read("frontend_s.sweep") == pytest.approx(0.0)
    assert read("block_gap_s.sweep") == pytest.approx(75e-9)
    assert read("scan_device_s.sweep") == pytest.approx(325e-9)
    assert read("idle_pct.whatif") is None
    b = layers.breakdown(t)
    assert [n for n, _ in b["device_ops"]] == [
        "jit__agg_block_step_xla", "jit_other", "(scan names matched none)"]
    assert [s for _, s in b["device_ops"]] == pytest.approx(
        [325e-9, 50e-9, 50e-9])
    assert [n for n, _ in b["idle_gaps"]] == [
        "sweep: inner", "sweep: after the last op"]


def test_the_mesh_round_is_read_as_scan():
    """A four-chip sweep whose device time is the scenario mesh's round
    step: each chip runs ``jit__mesh_agg_round`` for 300 of its 400 ns and
    a copy for 50; ``scan_device_s`` counts the rounds alone."""
    from bench import layers
    planes = [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": [
            [f"jit__mesh_agg_round({90 + i})", 0, 200],
            [f"jit__mesh_agg_round({90 + i})", 250, 100],
            ["jit_convert_element_type(3)", 360, 50]]}]}
        for i in range(4)]
    t = layers.Traced(devices=tr.device_planes({"planes": planes}),
                      requests=[("sweep", 0.0, 400.0)])
    ctx = layers.Context(cell={}, cfg={}, mix={}, chips=4, requests=[],
                         setup_s=1.0, window_s=1.0, traced=t)
    assert "jit__mesh_agg_round" in layers.SCAN_PROGRAMS
    assert layers.scan_device_s(ctx, "sweep") == pytest.approx(300e-9)
    ops = dict(layers.breakdown(t)["device_ops"])
    assert ops["(scan names matched none)"] == pytest.approx(40e-9)
