"""Structured span tracing: the profiler's host trace and an event ring.

``span("ingest.window", bucket=..., batches=...)`` is the program's one
span API, a context manager around the work it names.  A span has two
recorders, each switched on by its own condition:

* **the profiler** — while a JAX profiler session runs
  (``jax.profiler.start_trace`` / ``trace``), the span enters a
  ``jax.profiler.TraceAnnotation`` of the same name, whatever the obs
  gate says.  Its args become the event's stats, and it shares the
  profiler's clock with the device ops, so an idle gap of the device
  falls inside the program span that was open on the host;
* **the ring** — while ``obs.enable()`` is on, the span records one
  complete trace event (name, start, duration, thread, nesting depth,
  args) into a process-local ring buffer on the obs clock.  The buffer
  is bounded (``obs.enable(ring_capacity=...)``) with a DROP-OLDEST
  overflow policy: a long-lived stream keeps the most recent window of
  events and counts what it shed (``dropped()``), so tracing can stay on
  for days without growing.  ``appended()`` counts every event ever
  appended, so a caller reads the events of one call with ``since``.

With neither on, a span costs the gate check and the profiler's
``is_enabled`` check: no device dispatch, no jit trace.  Spans never
record while jax is tracing (``repro.compat.trace_state_clean()``): a
span inside a scanned/jitted step body would otherwise log trace-time,
not run-time.  This makes ``span`` safe to place in code that runs both
eagerly and under jit (e.g. ``hierarchy.merge_svd``).

The ``with`` statement binds the span's args dict: keys added to it
inside the body (a flag learnt only at the end, such as the window
driver's compile-vs-execute flag) land on the ring event and, through
``TraceAnnotation.set_metadata``, on the profiler's event.

Export of the ring is Chrome/Perfetto trace-event JSON
(:func:`chrome_trace` / :func:`write_chrome_trace`): load the file at
https://ui.perfetto.dev or chrome://tracing.  ``scripts/ranky_trace.py``
is the CLI front end.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import threading
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.compat import trace_state_clean
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
        self._appended = 0
        self._lock = threading.Lock()

    def append(self, event: TraceEvent) -> None:
        with self._lock:
            if len(self._ring) == self.capacity:
                self._dropped += 1
            self._ring.append(event)
            self._appended += 1

    def events(self) -> List[TraceEvent]:
        """Snapshot, oldest first (append order == span-exit order)."""
        with self._lock:
            return list(self._ring)

    def appended(self) -> int:
        """Events appended since the ring was made or cleared, dropped
        ones included: it only grows, where ``len`` stops at capacity."""
        with self._lock:
            return self._appended

    def since(self, count: int) -> List[TraceEvent]:
        """The events appended after ``appended()`` read ``count``, oldest
        first, as far as the ring still holds them; copies only those."""
        with self._lock:
            n = min(max(0, self._appended - count), len(self._ring))
            out = list(itertools.islice(reversed(self._ring), n))
        out.reverse()
        return out

    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._dropped = 0
            self._appended = 0


_BUFFER = TraceBuffer(gate.ring_capacity())
_GATE = gate._STATE
_TLS = threading.local()


def buffer() -> TraceBuffer:
    return _BUFFER


def set_capacity(capacity: int) -> None:
    """Swap in a fresh ring of the given capacity (drops history)."""
    global _BUFFER
    _BUFFER = TraceBuffer(capacity)


def events() -> List[TraceEvent]:
    return _BUFFER.events()


def appended() -> int:
    return _BUFFER.appended()


def since(count: int) -> List[TraceEvent]:
    return _BUFFER.since(count)


def dropped() -> int:
    return _BUFFER.dropped()


def clear() -> None:
    _BUFFER.clear()


def _depth_stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def _norm_args(kw: Dict[str, object]) -> Tuple[Tuple[str, object], ...]:
    return tuple(sorted((k, v) for k, v in kw.items()))


class span:
    """Record one complete span around the ``with`` body (module
    docstring): on the profiler's trace while a session runs, in the
    ring while obs is enabled, nowhere while jax traces.  ``as`` binds
    the args dict; keys added in the body are recorded at the end."""

    __slots__ = ("name", "args", "_ring", "_me", "_keys", "_t0", "_depth")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self._ring = False
        self._me = None

    def __enter__(self) -> Dict[str, object]:
        ring = _GATE["enabled"]          # gate.enabled(), without the call
        profiled = TraceAnnotation.is_enabled()
        if not (ring or profiled) or not trace_state_clean():
            return self.args
        if profiled:
            self._keys = frozenset(self.args)
            self._me = TraceAnnotation(self.name, **self.args)
            self._me.__enter__()
        if ring:
            self._ring = True
            stack = _depth_stack()
            self._depth = len(stack)
            stack.append(self.name)
            self._t0 = clock.now_us()
        return self.args

    def __exit__(self, et, ev, tb) -> None:
        if self._ring:
            dur = clock.now_us() - self._t0
            _depth_stack().pop()
            _BUFFER.append(TraceEvent(
                name=self.name, ph="X", ts_us=self._t0, dur_us=dur,
                tid=threading.get_ident(), depth=self._depth,
                args=_norm_args(self.args)))
        if self._me is not None:
            late = {k: v for k, v in self.args.items()
                    if k not in self._keys}
            if late:
                self._me.set_metadata(**late)
            self._me.__exit__(et, ev, tb)


def event(name: str, **args) -> None:
    """Record one instant marker (a zero-length span on the profiler's
    trace), under the same conditions as :class:`span`."""
    ring = _GATE["enabled"]
    profiled = TraceAnnotation.is_enabled()
    if not (ring or profiled) or not trace_state_clean():
        return
    if profiled:
        with TraceAnnotation(name, **args):
            pass
    if ring:
        _BUFFER.append(TraceEvent(
            name=name, ph="i", ts_us=clock.now_us(), dur_us=0.0,
            tid=threading.get_ident(), depth=len(_depth_stack()),
            args=_norm_args(args)))


def add_complete(name: str, ts_us: float, dur_us: float, **args) -> None:
    """Record a ring span whose start/duration the caller measured
    itself: the supervisor's ``recover.*`` steps only.  It never reaches
    the profiler's trace; every other site uses :class:`span`."""
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
