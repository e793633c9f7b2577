"""The harness: discovery by name, the refusal off a TPU, the plan check
and the peak table."""
from __future__ import annotations

import cells  # first: it puts bench and src on the path

import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import types

import jax
import pytest

from bench import harness, peaks, run

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_every_cell_finds_its_files():
    spec = run.load_spec()
    for entry in spec["workloads"]:
        cell = run.make_cell(spec, entry["name"], 1)
        assert callable(cell.ref.generate)
        driver = run.driver_module(cell.traffic["driver"])
        for fn in ("setup", "window", "check"):
            assert callable(getattr(driver, fn))
        for m in run.cell_metrics(spec, entry["name"], trace=True):
            assert callable(run.reader_module(m["name"]).read)
        names = {m["name"] for m in run.cell_metrics(spec, entry["name"],
                                                      trace=False)}
        assert "setup_s" in names and len(names) >= 2


def test_every_per_layer_metric_names_a_reader_and_cells_that_exist():
    spec = run.load_spec()
    cell_names = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert callable(run.reader_module(m["name"]).read), m["name"]
        assert m["moves"] in e2e, m["name"]
        for w in m.get("workloads", []):
            assert w in cell_names, (m["name"], w)
            # The cell reports the end-to-end metric this one moves.
            assert w in e2e[m["moves"]].get("workloads", [w]), (m["name"], w)


def test_every_config_file_is_listed_with_its_cuts():
    spec = run.load_spec()
    for c in spec["configs"]:
        sizes = json.loads((ROOT / c["file"]).read_text())
        assert sizes["name"] == c["name"]
        assert sorted(sizes["reduced"]) == sorted(c["reduced"])


def test_a_fixture_added_by_name_is_discovered(tmp_path):
    """A configuration, a mix (with its driver) and a per-layer metric
    added as files, and entries naming them, run without an edit to any
    file the benchmark already has."""
    for sub in ("configs", "traffic", "drivers", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "toy.json").write_text(
        json.dumps({"name": "toy", "n": 3, "settings": {"scale": 2}}))
    (tmp_path / "configs" / "toy.py").write_text(
        "def generate(config, seed):\n"
        "    return list(range(config['n']))\n")
    (tmp_path / "traffic" / "steady.json").write_text(
        json.dumps({"driver": "count", "limits": {"sum": 6}}))
    (tmp_path / "drivers" / "count.py").write_text(
        "def setup(cell):\n"
        "    return {'data': cell.ref.generate(cell.config, cell.seed),\n"
        "            'scale': cell.settings['scale']}\n"
        "def window(ctx, seconds):\n"
        "    return {'attempted': 1, 'failed': 0,\n"
        "            'metrics': {'count_s': 0.5}, 'readers': {'x': 7}}\n"
        "def check(ctx, win):\n"
        "    return {'sum': {'value': float(sum(ctx['data']) * ctx['scale']\n"
        "                                 / 2), 'limit': 6.0}}\n")
    (tmp_path / "metrics" / "widget.py").write_text(
        "def read(red, ctx):\n"
        "    return ctx['x'] * 2\n")
    spec = {"workloads": [{"name": "toy-steady", "config": "toy",
                           "traffic": "steady", "chips": 1}],
            "end_to_end": [{"name": "count_s", "unit": "s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "widget.toy", "unit": "1",
                           "moves": "count_s", "layer": "toy"}]}
    cell = run.make_cell(spec, "toy-steady", 5, bench=tmp_path,
                         log=lambda m: None)
    line = run.run_cell(cell, 0.1, False, spec=spec, devices=jax.devices(),
                        counter=run.CompileCounter().install(),
                        t_start=0.0, bench=tmp_path)
    assert line["correct"] is True
    assert line["metrics"]["count_s"]["value"] == 0.5
    assert set(line["metrics"]) == {"count_s", "setup_s"}
    assert list(line)[-1] == "checks"
    assert run.reader_module("widget.toy", tmp_path).read(
        None, {"x": 7}) == 14
    assert [m["name"] for m in run.cell_metrics(spec, "toy-steady",
                                                trace=True)] == ["widget.toy"]


def test_refuses_without_a_tpu():
    with pytest.raises(SystemExit, match="no TPU"):
        run.tpu_devices(1)


def test_command_off_a_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_KERNELS", None)    # set by tests/conftest.py
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-oneshot",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def _plan(**kw):
    base = dict(backend="single", strategy="exact_gram", rank=None,
                window=None, reasons=("R1: exact gram fits",))
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.mark.parametrize("plan", [
    _plan(reasons=("R1: EXCEEDS the budget; degrading to hierarchical",)),
    _plan(backend="hierarchical"),
    _plan(rank=16),
    _plan(strategy="streaming", window=1),
])
def test_plan_check_refuses_another_plan(plan):
    want = {"backend": "single", "strategy": "exact_gram", "rank": None}
    if plan.strategy == "streaming":
        want = {"backend": "single", "strategy": "streaming", "rank": None,
                "min_window": 2}
    with pytest.raises(RuntimeError, match="not the one the cell states"):
        harness.require_plan(plan, want, "cell")


def test_plan_check_passes_the_stated_plan():
    got = harness.require_plan(_plan(), {"backend": "single",
                                         "strategy": "exact_gram",
                                         "rank": None}, "cell")
    assert got["degraded"] is False


def test_a_run_with_a_degraded_plan_fails(monkeypatch):
    from repro.core import api

    real = api.svd

    def degraded(a, config=None, **kw):
        res = real(a, config, **kw)
        return dataclasses.replace(res, plan=dataclasses.replace(
            res.plan, backend="hierarchical"))

    monkeypatch.setattr(api, "svd", degraded)
    with pytest.raises(RuntimeError, match="not the one the cell states"):
        cells.run_tiny(cells.tiny_cell("paper-oneshot"))


def test_peaks_table_and_unknown_device():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_roofline_share_takes_the_binding_bound():
    # 197e9 operations take 1 ms at the peak; 819e6 bytes take 1 ms too.
    share, bound = peaks.roofline_share(197e9, 409.5e6, 2e-3, "TPU v5 lite")
    assert share == pytest.approx(50.0) and bound == "compute"
    share, bound = peaks.roofline_share(1e6, 819e6, 4e-3, "TPU v5 lite")
    assert share == pytest.approx(25.0) and bound == "memory"
