"""A tiny copy of the benchmark for CPU tests: the real configurations,
mixes and metric readers, cut to a few rows and, for sweeps, two weeks
of bins."""
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2**31 + 77

#: a fault schedule for the tests alone, dense enough that two weeks of
#: bins hold outages and disconnects with their floods
FAULTS = {"specs": [
    {"kind": "outage", "name": "outage", "rate_per_year": 156.0,
     "duration_hours": [1.0, 4.0]},
    {"kind": "disconnect", "name": "disconnect", "rate_per_year": 312.0,
     "duration_hours": [0.5, 2.0], "disconnect_frac": [0.2, 0.5],
     "flood_hours": 1.0}]}


def make_root(tmp, chips_mesh: int = 1) -> str:
    """A benchmark root under ``tmp`` with the cells ``t-sweep``,
    ``t-chaos`` (the year under ``FAULTS``), ``t-whatif`` and ``t-mesh``
    (a ``chips_mesh``-device sweep), each checking every answer."""
    root = os.path.join(str(tmp), "root")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = os.path.join(root, "bench")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

    def cfg(src, name, **kw):
        c = json.load(open(os.path.join(b, "configs", f"{src}.json")))
        c.update(kw)
        json.dump(c, open(os.path.join(b, "configs", f"{name}.json"), "w"))
        return c

    cfg("telemetry-year", "t-year", horizon_bins=336)
    cfg("telemetry-year", "t-paper")
    cfg("telemetry-year", "t-chaos", horizon_bins=336, faults=FAULTS)
    mixes = {
        "t-mixed": dict(request="sweep", rows=70, futures=1,
                        scenario_block=16),
        "t-futures": dict(request="sweep", rows=40, futures=4,
                          scenario_block=16),
        "t-mesh": dict(request="sweep", rows=70, futures=1,
                       scenario_block=16, devices=chips_mesh),
        "t-analyst": dict(request="whatif", traffic_cases=2, R=[2.0, 6.0],
                          G=[1.0, 1.7]),
    }
    for name, m in mixes.items():
        json.dump(m, open(os.path.join(b, "traffic", f"{name}.json"), "w"))
    cells = [("t-sweep", "t-year", "t-mixed", 1),
             ("t-chaos", "t-chaos", "t-futures", 1),
             ("t-whatif", "t-paper", "t-analyst", 1),
             ("t-mesh", "t-year", "t-mesh", chips_mesh)]
    spec["workloads"] = [dict(name=n, config=c, traffic=t, chips=k, why=n)
                         for n, c, t, k in cells]
    kinds = {"sweep": ["t-sweep", "t-chaos", "t-mesh"],
             "whatif": ["t-whatif"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = "whatif" if any("whatif" in w for w in m["workloads"]) \
                else "sweep"
            m["workloads"] = kinds[kind]
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


def run(root, workload, system_factory=None, seconds=0.5, trace=False,
        seed=SEED):
    """One tiny run through the harness, the look for a chip skipped;
    returns (result, stdout text, stderr text)."""
    import io
    import time
    from bench import harness
    out, err = io.StringIO(), io.StringIO()
    res = harness.run(workload, seed, seconds, trace, time.perf_counter(),
                      system_factory=system_factory, require_tpu=False,
                      out=out, err=err, root=root)
    return res, out.getvalue(), err.getvalue()

