"""Rehearsal of ``chip_smoke.py`` on the CPU.

Every phase function of the chip smoke runs here at a tiny size (short
horizons, tens of scenarios, the Pallas kernels in the interpreter), so a
wrong path, argument or check fails without a chip. ``main()`` itself
must refuse the CPU: it exits non-zero and never prints the ``"ok"``
line, and so does the script alone in a directory without the repo.
"""
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

pytest.importorskip("jax")

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _phase_lines(out: str):
    return [json.loads(line) for line in out.splitlines()
            if line.startswith("{")]


def test_paper_grid_phase(smoke, capsys):
    info = smoke.phase_paper_grid()
    assert info["rows"] == 6 and info["max_cost_err_pct"] < 5.0
    (line,) = _phase_lines(capsys.readouterr().out)
    assert line["phase"] == "paper_grid" and line["compile_s"] >= 0.0


def test_year_grid_phase(smoke, capsys):
    info = smoke.phase_year_grid(n=40, t_bins=744, n_check=12, seed=1)
    assert info["rows"] == 40 and info["checked_rows"] == 12
    assert info["policies"] == 5
    # on the CPU the kernel runs in the interpreter, never as Mosaic
    assert info["interpret"] and not info["mosaic_kernel"]
    assert info["max_diff_xla_vs_pallas"]["total_cost_usd"]["rel"] <= 1e-6
    names = [ln["phase"] for ln in _phase_lines(capsys.readouterr().out)]
    assert names == ["year_grid.xla", "year_grid.pallas", "year_grid.check"]


def test_distinct_grid_has_no_duplicate_rows(smoke):
    loads = smoke.distinct_loads(64, 48, seed=0)
    assert len(set(map(bytes, loads))) == 64
    twins = smoke.distinct_twins(64, seed=0)
    assert len({tw.params for tw in twins}) == 64
    assert len({tw.policy for tw in twins}) == 5


def test_chaos_grid_phase(smoke):
    info = smoke.phase_chaos_grid(n_base=6, n_futures=4, t_bins=744,
                                  n_check_base=3, seed=1)
    assert info["rows"] == 24 and info["checked_rows"] == 12
    assert info["faulted_rows"] > 0


def test_search_phase(smoke):
    info = smoke.phase_search(restarts=4, n_traffics=2, n_futures=4,
                              t_bins=744, steps=10, quantile=0.75)
    assert info["lanes"] == 32
    assert info["achieved_quantile"] >= 0.75
    assert info["cost_usd"] <= info["base_cost_usd"]


def test_fit_phase(smoke):
    info = smoke.phase_fit()
    assert info["max_rel_err"] < 0.05


def test_mesh_phases_on_one_device(smoke):
    grid = smoke.phase_year_grid_mesh(n=12, t_bins=744, devices=1)
    assert grid["devices"] == 1
    search = smoke.phase_search_mesh(restarts=2, n_traffics=1, n_futures=2,
                                     t_bins=744, steps=5, quantile=0.5,
                                     devices=1)
    assert search["lanes"] == 4


def test_main_refuses_the_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert smoke.main(["--four-chips"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_compile_cache_dir(monkeypatch, tmp_path):
    import jax

    from repro.compile_cache import DEFAULT_DIR, enable_compile_cache
    assert DEFAULT_DIR == ROOT / ".jax_cache"
    prev = jax.config.jax_compilation_cache_dir
    try:
        # a directory the environment names is JAX's own to read
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
