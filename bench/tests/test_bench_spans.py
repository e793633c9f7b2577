"""The front door's host-span readers on a recorded trace:
``data/paper-oneshot.xplane.pb.gz`` is the traced window of one
``--trace 1 --seconds 0.1`` run of ``paper-oneshot`` on a TPU v5 lite
(SOLVES solves, each under the benchmark's ``solve`` annotation, the
program's ``svd.*`` spans nested in it)."""
from __future__ import annotations

import cells  # first: it puts bench and src on the path

import dataclasses
import gzip
import pathlib

import pytest

from bench import run, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "paper-oneshot.xplane.pb.gz"
SOLVES = 2
READERS = {"convert_ms.oneshot": "svd.convert",
           "dispatch_ms.oneshot": "svd.solve",
           "diagnostics_ms.oneshot": "svd.diagnostics"}
FRONT_DOOR = ("svd.plan", "svd.convert", "svd.solve", "svd.wait",
              "svd.diagnostics")


@pytest.fixture(scope="module")
def red():
    return trace.reduce_file(TRACE)


@pytest.fixture(scope="module")
def host():
    """Every host-plane event of the raw trace: (name, start, end)."""
    from jax.profiler import ProfileData

    with gzip.open(TRACE, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    return [(ev.name, float(ev.start_ns),
             float(ev.start_ns) + float(ev.duration_ns))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_the_span_time_per_solve(red, host, metric):
    """By hand: the span's events inside the window, over the solves."""
    lo, hi = red.window
    inside = [(s, e) for n, s, e in host
              if n == READERS[metric] and lo <= s and e <= hi]
    assert len(inside) == SOLVES
    want = sum(e - s for s, e in inside) * 1e-6 / SOLVES
    got = run.reader_module(metric).read(red, {"solves": SOLVES})
    assert got == pytest.approx(want, rel=1e-12)
    assert 0 < got < 1e3 * red.window_s() / SOLVES


def test_front_door_spans_follow_each_other_in_each_solve(red, host):
    lo, hi = red.window
    solves = [(s, e) for n, s, e in host
              if n == "solve" and lo <= s and e <= hi]
    assert len(solves) == SOLVES
    for s0, e0 in solves:
        spans = []
        for name in FRONT_DOOR:
            (ev,) = [(s, e) for n, s, e in host
                     if n == name and s0 <= s and e <= e0]
            spans.append(ev)
        assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))


def test_program_spans_name_the_idle_time(red):
    """The idle gaps fall under the program's spans: at least 90 % of
    the device's idle time, and the longest gaps are named by them, not
    by the whole-call annotation."""
    idle = trace.gaps([(s, e) for _, s, e in red.ops[0]], *red.window)
    spans = [(s, e) for n, s, e in red.host if n.startswith("svd.")]
    covered = sum(trace.union_ns([(max(s, gs), min(e, ge))
                                  for s, e in spans if e > gs and s < ge])
                  for gs, ge in idle)
    assert covered >= 0.9 * sum(e - s for s, e in idle)
    names = [name for name, _ in red.breakdown()["idle_gaps"]]
    assert names and not {"solve", "host idle"} & set(names)


def test_a_missing_span_reads_nothing(red, monkeypatch):
    for metric in READERS:
        mod = run.reader_module(metric)
        monkeypatch.setattr(mod, "SPAN", "svd.no_such_span")
        assert mod.read(red, {"solves": SOLVES}) is None
    # a trace of another cell holds no front-door span
    serve = trace.reduce_file(DATA / "ml25m-serve.xplane.pb.gz")
    for metric in READERS:
        assert run.reader_module(metric).read(serve,
                                              {"solves": 3}) is None


def test_a_trace_without_a_device_reads_nothing(red):
    host_only = dataclasses.replace(red, ops=[], modules=[])
    for metric in READERS:
        assert run.reader_module(metric).read(host_only,
                                              {"solves": SOLVES}) is None
