"""``correct`` comes out false when the timed path is broken underneath a
run (the look for a chip skipped, everything else as in a run), once for
each fault a cell can have, and for the control: the reference one
precision lower in the program's place."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench.system import System
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


class Stale(System):
    """A step that returns its state unchanged: every request after the
    first gets the answer of the request before it."""

    def __init__(self):
        super().__init__()
        self.last = None

    def simulate_grid(self, twins, **kw):
        ans, self.last = self.last, super().simulate_grid(twins, **kw)
        return ans if ans is not None else self.last

    def run_grid(self, twins, traffics, slo):
        ans, self.last = self.last, super().run_grid(twins, traffics, slo)
        return ans if ans is not None else self.last


class HalfBatch(System):
    """Half of the batch left out: the second half of the rows comes back
    as copies of the first half's."""

    def simulate_grid(self, twins, **kw):
        rows = super().simulate_grid(twins, **kw)
        half = len(rows) // 2
        return rows[:half] + rows[:len(rows) - half]

    def run_grid(self, twins, traffics, slo):
        rows = super().run_grid(twins, traffics, slo)
        half = len(rows) // 2
        return rows[:half] + rows[:len(rows) - half]


class Altered(System):
    """One answer altered where it is produced: the first row's cost
    (and, for a query, the first Table II row's) a quarter off."""

    def simulate_grid(self, twins, **kw):
        rows = super().simulate_grid(twins, **kw)
        rows[0].total_cost_usd *= 1.25
        return rows

    def table2_rows(self, sims):
        table = super().table2_rows(sims)
        table[0]["cost_usd"] = round(table[0]["cost_usd"] * 1.25, 2)
        return table


@pytest.mark.parametrize("cell", ["t-sweep", "t-chaos", "t-whatif"])
@pytest.mark.parametrize("broken", [Stale, HalfBatch, Altered],
                         ids=lambda c: c.__name__)
def test_broken_path_is_not_correct(root, cell, broken):
    res, _, _ = tiny.run(root, cell, system_factory=broken, seconds=0.3)
    assert res["attempted"] >= 2 or broken is not Stale
    assert res["correct"] is False, res["checks"]


def test_control_fails_every_cell(root):
    from bench import check, control
    limits = check.load_limits(os.path.join(root, "bench"))
    for cell in ("t-sweep", "t-chaos", "t-whatif"):
        got = control.readings(cell, tiny.SEED, 2, root=root)
        assert any(got[k] > limits[k] for k in check.NUMBERS), (cell, got)


MESH = textwrap.dedent("""
    import json, os, sys, time
    sys.path[:0] = [{src!r}, {root!r}]
    from bench.tests import tiny
    from repro.core import simulate
    root = tiny.make_root({tmp!r}, chips_mesh=4)
    out = {{"sound": tiny.run(root, "t-mesh")[0]["correct"]}}
    orig = simulate._sharded_agg_fn

    def no_exchange(*a, **k):
        f = orig(*a, **k)
        def g(*args):
            carry, agg = f(*args)
            return carry.at[1:].set(0.0), agg.at[1:].set(0.0)
        return g

    simulate._sharded_agg_fn = no_exchange
    out["no_exchange"] = tiny.run(root, "t-mesh")[0]["correct"]
    print(json.dumps(out))
""")


def test_mesh_without_the_exchange_is_not_correct(tmp_path):
    """Four virtual CPU devices: the sound mesh sweep is correct; with the
    rows of every shard but the first left out of the round's result,
    it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH.format(src=os.path.join(tiny.ROOT, "src"), root=tiny.ROOT,
                       tmp=str(tmp_path))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "no_exchange": False}
