"""The end-to-end arithmetic: a rate over every completed sweep of the
window, and percentiles over every query, not medians of chunks."""
import os

import numpy as np
import pytest

from bench import generator, harness, layers

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _ctx(requests):
    return layers.Context(cell={}, cfg={}, mix={}, chips=1,
                          requests=requests, setup_s=12.5, window_s=51.0)


def _req(kind, start, end, work=0.0, answer=True):
    r = generator.Request(kind, 0, None, work=work, rows=1)
    r.start, r.end, r.answer = start, end, ([] if answer else None)
    return r


def test_rate_over_all_completed_sweeps():
    reqs = [_req("sweep", 0.0, 26.0, 32768.0), _req("sweep", 26.0, 51.0,
                                                    32768.0),
            _req("sweep", 51.0, 52.0, 32768.0, answer=False)]
    rate = harness.reader(BENCH, "scenario_years_per_s")(_ctx(reqs))
    assert rate == pytest.approx(2 * 32768.0 / 51.0)


def test_percentiles_over_all_queries():
    rng = np.random.default_rng(0)
    lat = rng.lognormal(-3.0, 0.3, 1000)
    reqs, t = [], 0.0
    for x in lat:
        reqs.append(_req("whatif", t, t + x))
        t += x
    ctx = _ctx(reqs)
    p50 = harness.reader(BENCH, "whatif_p50_s")(ctx)
    p90 = harness.reader(BENCH, "whatif_p90_s")(ctx)
    assert p50 == pytest.approx(np.percentile(lat, 50))
    assert p90 == pytest.approx(np.percentile(lat, 90))
    chunks = np.median([np.percentile(c, 90) for c in lat.reshape(10, -1)])
    assert p90 != chunks


def test_setup_and_nothing_to_read():
    ctx = _ctx([])
    assert harness.reader(BENCH, "setup_s")(ctx) == 12.5
    for name in ("scenario_years_per_s", "whatif_p90_s", "idle_pct.sweep",
                 "frontend_s.whatif", "pad_pct.sweep",
                 "scan_roofline_pct.sweep"):
        assert harness.reader(BENCH, name)(ctx) is None
