"""The obs clock and the compile-time probe.

Every timestamp in ``src/repro/{stream,serve,core}`` routes through
this module (ranky-lint rule RL108 flags direct ``time.time()`` /
``time.perf_counter()`` there) so spans, metrics and Diagnostics wall
times all share ONE monotonic timebase and traces stay coherent.

The compile probe splits a call's wall time into compile vs run:
``jax.monitoring`` emits duration events for every jaxpr trace, MLIR
lowering and backend compile; :func:`install_compile_probe` registers a
process-global listener that accumulates them, and
``compile_seconds()`` deltas around a call attribute its first-call
tracing/compilation cost (``Diagnostics.compile_time_s``) separately
from the steady-state execution (``run_time_s``).
"""
from __future__ import annotations

import threading
import time

_EPOCH = time.perf_counter()


def now() -> float:
    """Monotonic seconds since the obs epoch (process start-ish)."""
    return time.perf_counter() - _EPOCH


def now_us() -> float:
    """Monotonic microseconds — the trace-event timebase."""
    return (time.perf_counter() - _EPOCH) * 1e6


def wall() -> float:
    """Wall-clock unix seconds (snapshot age / staleness only — never
    used for durations)."""
    return time.time()


# ---------------------------------------------------------------------------
# Compile-time probe (jax.monitoring duration events)
# ---------------------------------------------------------------------------

_COMPILE = {"secs": 0.0, "installed": False, "spans": []}
_COMPILE_LOCK = threading.Lock()
_COMPILE_EVENT_PREFIX = "/jax/core/compile/"
# Recent counted (start, end) spans; an event reaches the listener when
# it ends, so only recent spans can lie inside a new one.  The slack
# absorbs the listener's own latency in placing a span's start.
_MAX_SPANS = 256
_NEST_SLACK_S = 1e-3


def _on_event_duration(event: str, secs: float, **_kw) -> None:
    """Count each compile event once: tracing a jitted function traces
    the jitted functions it calls, and each reports its own event,
    nested inside the outer one — those seconds are already counted."""
    if not event.startswith(_COMPILE_EVENT_PREFIX):
        return
    end = now()
    start = end - secs
    with _COMPILE_LOCK:
        spans = _COMPILE["spans"]
        inner = [sp for sp in spans
                 if sp[0] >= start - _NEST_SLACK_S and sp[1] <= end]
        _COMPILE["secs"] += secs - sum(e - s for s, e in inner)
        spans[:] = [sp for sp in spans if sp not in inner][-_MAX_SPANS:]
        spans.append((start, end))


def install_compile_probe() -> bool:
    """Idempotently register the jax.monitoring listener.  Returns True
    when the probe is live (False when this jax build has no monitoring
    API — callers then report compile_time_s = 0.0)."""
    if _COMPILE["installed"]:
        return True
    try:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)
    except Exception:   # pragma: no cover - depends on the jax build
        return False
    _COMPILE["installed"] = True
    return True


def compile_seconds() -> float:
    """Cumulative seconds this process spent tracing/lowering/compiling
    since the probe was installed.  Delta it around a call."""
    return _COMPILE["secs"]
