"""A whole run through the harness at a tiny size on the CPU (the look for
a chip skipped): the form of the last line, a cell added by files and
entries alone, and the refusal of a machine without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import tiny

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "checks"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["t-sweep", "t-chaos", "t-whatif"])
def test_last_line_form_and_correct(root, cell):
    res, out, err = tiny.run(root, cell)
    last = json.loads(out.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(last) and list(last)[-1] == "checks"
    assert last["correct"] is True, last["checks"]
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for name, m in last["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0, name
    assert "setup_s" in last["metrics"] and len(last["metrics"]) >= 2
    assert last["compiles_in_window"] == 0
    lines = err.strip().splitlines()
    assert all(line.startswith("check ") and " limit " in line
               for line in lines[-4:])


#: (configuration changes, mix) of each cell added by files alone: two
#: policies of the two-week year; two days of one-minute bins (2,880)
#: with a pool of 6 load rows that 30 rows share
ADDED_CELLS = {
    "lean": (dict(policies=["fifo", "shed"]),
             dict(request="sweep", rows=24, futures=1, scenario_block=8)),
    "minute-pool": (dict(horizon_bins=2880, bin_hours=1.0 / 60),
                    dict(request="sweep", rows=30, load_pool=6,
                         scenario_block=8, check_requests=2)),
}


@pytest.mark.parametrize("case", sorted(ADDED_CELLS))
def test_a_cell_added_by_files_and_entries_alone(root, tmp_path, case):
    """A new configuration, mix and per-layer metric: three files and
    three entries, no code changed."""
    import shutil
    changes, mix = ADDED_CELLS[case]
    new = str(tmp_path / "root")
    shutil.copytree(root, new)
    b = os.path.join(new, "bench")
    cfg = json.load(open(os.path.join(b, "configs", "t-year.json")))
    cfg.update(changes, name=f"t-{case}")
    json.dump(cfg, open(os.path.join(b, "configs", f"t-{case}.json"), "w"))
    json.dump(mix, open(os.path.join(b, "traffic", f"t-{case}.json"), "w"))
    with open(os.path.join(b, "metrics", "rows_per_sweep.py"), "w") as f:
        f.write("from bench import layers\n\n\n"
                "def read(ctx):\n"
                "    return float(sum(r.rows for r in "
                "layers.done(ctx, 'sweep')))\n")
    spec = json.load(open(os.path.join(new, "BENCHMARK.json")))
    spec["configs"].append(dict(name=f"t-{case}", source="test",
                                file=f"bench/configs/t-{case}.json",
                                reduced=[], why="test"))
    spec["workloads"].append(dict(name=f"t-{case}", config=f"t-{case}",
                                  traffic=f"t-{case}", chips=1, why="test"))
    spec["end_to_end"].append(dict(name="rows_per_sweep", unit="rows",
                                   better="higher", bound=0.01,
                                   source="host_clock",
                                   workloads=[f"t-{case}"]))
    json.dump(spec, open(os.path.join(new, "BENCHMARK.json"), "w"))
    res, _, _ = tiny.run(new, f"t-{case}")
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["rows_per_sweep"]["value"] >= mix["rows"]
    assert res["checks"]["hours_gap"]["value"] == 0.0
    assert res["checks"]["pct_gap"]["value"] == 0.0


def _bench_run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sweep-year-mixed",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_the_cpu():
    p = _bench_run(tiny.ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    import shutil
    shutil.copytree(os.path.join(tiny.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    p = _bench_run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_traced_run_reads_the_program_spans(root):
    """With ``--trace 1`` the block engine's spans give the padding share:
    70 rows, 14 per policy, in 5 blocks of 16 slots."""
    res, out, _ = tiny.run(root, "t-sweep", trace=True)
    assert res["correct"] is True
    assert res["metrics"]["pad_pct.sweep"]["value"] == pytest.approx(12.5)
    assert "scenario_years_per_s" not in res["metrics"]


def test_traced_run_profiles_with_obs_off_then_observes(root):
    """The profiled requests run with ``repro.obs`` off, as the untraced
    window does; one request after them runs with it on, for the span
    readings alone."""
    from repro import obs
    from bench.system import System
    seen = []

    class Watch(System):
        def simulate_grid(self, twins, **kw):
            seen.append(obs.enabled())
            return super().simulate_grid(twins, **kw)

    res, _, _ = tiny.run(root, "t-sweep", system_factory=Watch, trace=True)
    assert res["correct"] is True
    assert seen[-1] is True and not any(seen[:-1]) and len(seen) >= 3
    assert res["metrics"]["pad_pct.sweep"]["value"] == pytest.approx(12.5)
