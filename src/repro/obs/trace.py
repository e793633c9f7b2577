"""Structured span tracing with a fixed-capacity event ring buffer.

``span("ingest.window", bucket=..., batches=...)`` is a context manager
that records one complete trace event — name, start, duration, thread,
nesting depth, and small key=value args — into a process-local ring
buffer.  The buffer is bounded (``obs.enable(ring_capacity=...)``) with
a DROP-OLDEST overflow policy: a long-lived stream keeps the most
recent window of events and counts what it shed (``dropped()``), so
tracing can stay on for days without growing.

Recording discipline:

* everything is gated on :func:`repro.obs.gate.enabled` — a disabled
  span is one boolean check and an empty ``yield``;
* spans never record while jax is tracing
  (``repro.compat.trace_state_clean()``): a span inside a scanned/jitted
  step body would otherwise log trace-time, not run-time.  This makes
  ``span`` safe to place in code that runs both eagerly and under jit
  (e.g. ``hierarchy.merge_svd``);
* durations come from the obs clock (one timebase for every event).

Export is Chrome/Perfetto trace-event JSON (:func:`chrome_trace` /
:func:`write_chrome_trace`): load the file at https://ui.perfetto.dev
or chrome://tracing.  ``scripts/ranky_trace.py`` is the CLI front end.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import threading
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple

from repro.obs import clock, gate


@dataclasses.dataclass(frozen=True)
class TraceEvent:
    """One recorded span (ph="X") or instant marker (ph="i")."""

    name: str
    ph: str                      # "X" complete span | "i" instant
    ts_us: float                 # start, obs-clock microseconds
    dur_us: float                # 0.0 for instants
    tid: int
    depth: int                   # span nesting depth on its thread
    args: Tuple[Tuple[str, object], ...]


class TraceBuffer:
    """Bounded event ring: append is O(1), overflow drops the OLDEST
    event and bumps the dropped counter (tested overflow policy)."""

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"ring capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self._dropped = 0
        self._lock = threading.Lock()

    def append(self, event: TraceEvent) -> None:
        with self._lock:
            if len(self._ring) == self.capacity:
                self._dropped += 1
            self._ring.append(event)

    def events(self) -> List[TraceEvent]:
        """Snapshot, oldest first (append order == span-exit order)."""
        with self._lock:
            return list(self._ring)

    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._dropped = 0


_BUFFER = TraceBuffer(gate.ring_capacity())
_TLS = threading.local()


def buffer() -> TraceBuffer:
    return _BUFFER


def set_capacity(capacity: int) -> None:
    """Swap in a fresh ring of the given capacity (drops history)."""
    global _BUFFER
    _BUFFER = TraceBuffer(capacity)


def events() -> List[TraceEvent]:
    return _BUFFER.events()


def dropped() -> int:
    return _BUFFER.dropped()


def clear() -> None:
    _BUFFER.clear()


def _depth_stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def _recording() -> bool:
    if not gate.enabled():
        return False
    from repro.compat import trace_state_clean
    return trace_state_clean()


def _norm_args(kw: Dict[str, object]) -> Tuple[Tuple[str, object], ...]:
    return tuple(sorted((k, v) for k, v in kw.items()))


@contextlib.contextmanager
def span(name: str, **args):
    """Record one complete span around the ``with`` body.  No-op when
    obs is disabled or jax is mid-trace."""
    if not _recording():
        yield
        return
    stack = _depth_stack()
    depth = len(stack)
    stack.append(name)
    t0 = clock.now_us()
    try:
        yield
    finally:
        dur = clock.now_us() - t0
        stack.pop()
        _BUFFER.append(TraceEvent(
            name=name, ph="X", ts_us=t0, dur_us=dur,
            tid=threading.get_ident(), depth=depth, args=_norm_args(args)))


def event(name: str, **args) -> None:
    """Record one instant marker."""
    if not _recording():
        return
    _BUFFER.append(TraceEvent(
        name=name, ph="i", ts_us=clock.now_us(), dur_us=0.0,
        tid=threading.get_ident(), depth=len(_depth_stack()),
        args=_norm_args(args)))


def add_complete(name: str, ts_us: float, dur_us: float, **args) -> None:
    """Record a span whose start/duration the caller measured itself
    (for sites that learn the span's attributes only after it ends,
    e.g. the window driver's compile-vs-execute flag)."""
    if not gate.enabled():
        return
    _BUFFER.append(TraceEvent(
        name=name, ph="X", ts_us=ts_us, dur_us=dur_us,
        tid=threading.get_ident(), depth=len(_depth_stack()),
        args=_norm_args(args)))


# ---------------------------------------------------------------------------
# Summaries + Chrome/Perfetto export
# ---------------------------------------------------------------------------

def span_summary(
    evs: Optional[Iterable[TraceEvent]] = None,
) -> Tuple[Tuple[str, int, float], ...]:
    """((name, count, total_us), ...) sorted by descending total time —
    the compact per-call digest ``Diagnostics.span_summary`` carries."""
    agg: Dict[str, List[float]] = {}
    for ev in (events() if evs is None else evs):
        if ev.ph != "X":
            continue
        cell = agg.setdefault(ev.name, [0, 0.0])
        cell[0] += 1
        cell[1] += ev.dur_us
    return tuple(sorted(
        ((name, int(c), float(t)) for name, (c, t) in agg.items()),
        key=lambda row: -row[2]))


def chrome_trace(evs: Optional[Iterable[TraceEvent]] = None, *,
                 process_name: str = "ranky") -> dict:
    """The ring's contents as a Chrome trace-event JSON object
    (Perfetto/chrome://tracing both load it)."""
    out = [{
        "name": "process_name", "ph": "M", "pid": 1, "tid": 0,
        "args": {"name": process_name},
    }]
    for ev in (events() if evs is None else evs):
        rec = {
            "name": ev.name,
            "ph": ev.ph,
            "ts": ev.ts_us,
            "pid": 1,
            "tid": ev.tid,
            "cat": ev.name.split(".", 1)[0],
            "args": dict(ev.args, depth=ev.depth),
        }
        if ev.ph == "X":
            rec["dur"] = ev.dur_us
        else:
            rec["s"] = "t"
        out.append(rec)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, *, process_name: str = "ranky") -> int:
    """Dump the ring to ``path`` as trace-event JSON; returns the event
    count written."""
    doc = chrome_trace(process_name=process_name)
    with open(path, "w") as f:
        json.dump(doc, f)
    return len(doc["traceEvents"]) - 1   # minus the process_name meta


def validate_chrome_trace(doc: dict) -> None:
    """Assert ``doc`` is schema-valid trace-event JSON (the shape
    ``scripts/check_bench_json.py --check-obs`` gates CI artifacts on).
    Raises AssertionError with the offending record otherwise."""
    assert isinstance(doc, dict) and "traceEvents" in doc, \
        f"trace JSON must be an object with a traceEvents list, got " \
        f"{type(doc)}"
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs, "traceEvents is empty"
    for rec in evs:
        for field in ("name", "ph", "pid", "tid"):
            assert field in rec, f"trace event lacks {field!r}: {rec!r}"
        if rec["ph"] == "X":
            assert "ts" in rec and "dur" in rec and rec["dur"] >= 0, \
                f"complete event needs ts + non-negative dur: {rec!r}"
        elif rec["ph"] == "i":
            assert "ts" in rec, f"instant event needs ts: {rec!r}"
