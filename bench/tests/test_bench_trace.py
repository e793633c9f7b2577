"""The reduction from a profiler trace to metrics, on a recorded trace:
``data/ml25m-serve.xplane.pb.gz`` is the traced window (3 s, 60 waves at
20 waves/s) of one ``--trace 1`` run of ``ml25m-serve`` on a TPU v5 lite,
and on hand-made intervals."""
from __future__ import annotations

import cells  # first: it puts bench and src on the path

import gzip
import pathlib

import numpy as np
import pytest

from bench import peaks, run, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "ml25m-serve.xplane.pb.gz"
KIND = "TPU v5 lite"


@pytest.fixture(scope="module")
def red():
    return trace.reduce_file(TRACE)


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData

    with gzip.open(TRACE, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def _ctx(**kw):
    base = {"kind": KIND, "log": lambda msg: None, "waves": 60, "batch": 64,
            "items": 62423, "rank": 32, "k_top": 10}
    base.update(kw)
    return base


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 12), (20, 25), (24, 30), (40, 41)]
    assert trace.union_ns(iv) == 12 + 10 + 1
    assert trace.gaps(iv, 0, 50) == [(12, 20), (30, 40), (41, 50)]
    assert trace.gaps(iv, 2, 22) == [(12, 20)]


def test_window_is_the_annotation(red):
    assert red.window_s() == pytest.approx(2.991144303, abs=1e-9)
    assert red.devices == 1


def test_busy_is_the_union_of_op_intervals(red, raw):
    """Against a 1 us raster of every op interval inside the window."""
    lo, hi = red.window
    dev = [p for p in raw.planes if p.name == "/device:TPU:0"][0]
    ops = [ln for ln in dev.lines if ln.name == "XLA Ops"][0]
    grid = np.zeros(int((hi - lo) / 1e3) + 1, bool)
    for ev in ops.events:
        s = max(float(ev.start_ns), lo)
        e = min(float(ev.start_ns) + float(ev.duration_ns), hi)
        if e > s:
            grid[int((s - lo) / 1e3):int(np.ceil((e - lo) / 1e3))] = True
    n_ops = sum(1 for _ in ops.events)
    assert red.busy_s() == pytest.approx(grid.sum() * 1e-6,
                                         abs=2 * n_ops * 1e-6)
    assert 0 < red.busy_s() < red.window_s()


def test_module_time_sums_the_program_executions(red, raw):
    dev = [p for p in raw.planes if p.name == "/device:TPU:0"][0]
    mods = [ln for ln in dev.lines if ln.name == "XLA Modules"][0]
    want = sum(float(ev.duration_ns) for ev in mods.events
               if ev.name.startswith("jit_topk_score(")) * 1e-9
    got = red.module_s(lambda n: n == "jit_topk_score")
    assert got == pytest.approx(want, rel=1e-12)
    assert sum(1 for ev in mods.events
               if ev.name.startswith("jit_topk_score(")) == 60


def test_readers_read_the_recorded_trace(red):
    idle = run.reader_module("device_idle.serve").read(red, _ctx())
    assert idle == pytest.approx(
        100 * (1 - red.busy_s() / red.window_s()))
    share = run.reader_module("topk_roofline.serve").read(red, _ctx())
    per_wave = red.op_s(lambda n: n.startswith("%topk_score")) / 60
    assert 0 < per_wave < 1e-3
    assert share == pytest.approx(100 * 8003456 / 819e9 / per_wave)


def test_a_name_that_is_not_found_reads_nothing(red, monkeypatch):
    for metric in ("solve_device_ms.oneshot", "batch_eigh_ms.ingest",
                   "merge_scan_ms.ingest", "u_fold_ms.ingest"):
        assert run.reader_module(metric).read(
            red, _ctx(solves=3, batches=8)) is None
    mod = run.reader_module("topk_roofline.serve")
    monkeypatch.setattr(mod, "OPS", ("%no_such_kernel",))
    assert mod.read(red, _ctx()) is None
    assert run.reader_module("gen_late_p95_ms.serve").read(
        red, _ctx(late_ms=[])) is None


def test_roofline_arithmetic_by_hand():
    topk = run.reader_module("topk_roofline.serve")
    flops, nbytes = topk.wave_work(64, 62423, 32, 10)
    assert flops == 2 * 64 * 62423 * 32 == 255684608
    assert nbytes == 4 * 62423 * 32 + 4 * 64 * 32 + 8 * 64 * 10 == 8003456
    share, bound = peaks.roofline_share(flops, nbytes, 1e-4, KIND)
    assert bound == "memory"
    assert share == pytest.approx(100 * (8003456 / 819e9) / 1e-4)

    gram = run.reader_module("gram_roofline.ingest")
    batch = (np.array([0, 1, 0, 2, 3, 1]), np.array([0, 0, 1, 2, 2, 2]),
             np.ones(6, np.float32), (4, 3))
    # Column degrees 2, 1, 3: 2 * (4 + 1 + 9) operations; 8 bytes per
    # entry and the 4 x 4 float32 gram.
    assert gram.batch_work(batch) == (28.0, 8.0 * 6 + 4.0 * 16)
