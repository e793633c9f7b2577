"""The stream's per-layer readers on a module table saved from a chip
trace: ``data/ml25m-ingest.modules.json.gz`` holds the traced window (one
replay of 8 batches) of one ``--trace 1`` run of ``ml25m-ingest`` on a
TPU v5 lite, reduced to device 0's busy intervals and module executions
(the raw trace is 4.7 MB compressed), and the operations and bytes the
run's gram reader logged for the replay."""
from __future__ import annotations

import cells  # first: it puts bench and src on the path

import gzip
import json
import math
import pathlib

import numpy as np
import pytest

from bench import run, trace

TABLE = (pathlib.Path(__file__).resolve().parent / "data"
         / "ml25m-ingest.modules.json.gz")
INGEST = [m for m in run.load_spec()["per_layer"]
          if "ml25m-ingest" in m.get("workloads", [])]
# What the traced run printed for each metric (seed 2147485002).
PRINTED = {"device_idle.ingest": 27.08913579468647,
           "gram_roofline.ingest": 0.0030008661040907803,
           "batch_eigh_ms.ingest": 132.138182625,
           "merge_scan_ms.ingest": 774.469703125,
           "u_fold_ms.ingest": 1.3216183750000001}


@pytest.fixture(scope="module")
def table():
    with gzip.open(TABLE, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def red(table):
    return trace.Reduced(
        window=tuple(table["window_ns"]),
        ops=[[("busy", s, e) for s, e in table["busy_ns"]]],
        modules=[[tuple(m) for m in table["modules"]]], host=[])


def _read(metric, red, table, monkeypatch):
    """``metric``'s reader in the traced run's context; the gram's work is
    the one the run logged, spread over stand-ins for the replay's
    batches."""
    flops, nbytes = table["gram_work"]
    n = table["batches"]
    reader = run.reader_module(metric)
    if hasattr(reader, "batch_work"):
        monkeypatch.setattr(reader, "batch_work",
                            lambda batch: (flops / n, nbytes / n))
    return reader.read(red, {"kind": table["kind"], "log": lambda msg: None,
                             "batches": n, "replay": [None] * n})


def test_the_table_is_one_replay(red, table):
    runs = {}
    for name, _, _ in red.modules[0]:
        runs[name] = runs.get(name, 0) + 1
    # One gram and one eigh program a batch; the scan in windows.
    assert runs["jit__unknown"] == runs["jit_merge_grams_eigh"] == 8
    assert 1 <= runs["jit_run"] <= 8
    assert 0 < red.busy_s() < red.window_s()


@pytest.mark.parametrize("metric", [m["name"] for m in INGEST])
def test_each_ingest_reader_reads_the_recorded_replay(metric, red, table,
                                                      monkeypatch):
    value = _read(metric, red, table, monkeypatch)
    assert value is not None and math.isfinite(value) and value > 0
    unit = next(m["unit"] for m in INGEST if m["name"] == metric)
    if unit == "%":
        assert value <= 100
    assert value == pytest.approx(PRINTED[metric], rel=1e-5)


def test_lead_numbers_cut_at_the_last_drop_of_a_tenth():
    ml25m = run.config_files("ml25m")[1]
    rng = np.random.default_rng(3)
    u_r = np.linalg.qr(rng.normal(size=(40, 5)))[0]
    v_r = np.linalg.qr(rng.normal(size=(30, 5)))[0]
    s_r = np.array([10.0, 9.5, 5.0, 4.9, 4.85])   # drops .05 .47 .02 .01
    # Another state that differs from the reference only past the lead.
    u, v = u_r.copy(), v_r.copy()
    u[:, 2:] = np.linalg.qr(rng.normal(size=(40, 3)))[0]
    got = ml25m.lead_numbers(u, s_r, v, (u_r, s_r, v_r))
    assert got["lead_rank"] == 2 and got["lead"] < 1e-12
    assert ml25m.state_numbers(u, s_r, v, (u_r, s_r, v_r))["state"] > 0.1
    s = s_r.copy()
    s[0] *= 1 + 1e-4
    lead = ml25m.lead_numbers(u_r, s, v_r, (u_r, s_r, v_r))["lead"]
    assert lead == pytest.approx(10 * 1e-4 / np.hypot(10, 9.5), rel=1e-6)
    flat = np.array([5.0, 4.9, 4.85, 4.8, 4.75])
    assert ml25m.lead_numbers(u_r, flat, v_r, (u_r, flat, v_r))[
        "lead_rank"] == 5


def test_the_ingest_entries_are_the_recorded_ones():
    assert sorted(m["name"] for m in INGEST) == sorted(PRINTED)
    assert {m["moves"] for m in INGEST} == {"ingest_rows_per_s"}
