"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The JAX profiler writes one plane per device (``/device:TPU:<i>``) with
a line of XLA modules (one event per program execution) and a line of
XLA ops (one event per operation), and a host plane (``/host:CPU``)
whose lines carry the host threads' events, among them the benchmark's
own ``jax.profiler.TraceAnnotation`` spans (``window``, ``solve``,
``replay``, ``wave``, ...).  :func:`reduce_file` keeps, for the span of
the ``window`` annotation only:

* each device's op intervals and module intervals (name, start, end);
* the host events (name, start, end), for attributing idle gaps.

Times are nanoseconds on the profiler's common clock.  Metric readers
(``bench/metrics/*.py``) ask a :class:`Reduced` for busy time, module
time and op time by name; a name that matches nothing reads 0 seconds
and the reader then returns nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Callable, Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

WINDOW_SPAN = "window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Reduced:
    """One traced window: per-device ops and modules, and host events."""

    window: Tuple[float, float]
    ops: List[List[Interval]]        # per device
    modules: List[List[Interval]]    # per device
    host: List[Interval]

    @property
    def devices(self) -> int:
        return max(1, len(self.ops))

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        return sum(union_ns([(s, e) for _, s, e in dev])
                   for dev in self.ops) * 1e-9 / self.devices

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def module_s(self, match: Callable[[str], bool]) -> float:
        """Seconds of the programs whose name ``match`` accepts, summed
        over their executions and averaged over the devices."""
        return sum(e - s for dev in self.modules for n, s, e in dev
                   if match(_short(n))) * 1e-9 / self.devices

    def op_s(self, match: Callable[[str], bool]) -> float:
        """Seconds of the ops whose instruction name (``%topk_score.1``)
        ``match`` accepts; ops nested in another op count once."""
        return sum(union_ns([(s, e) for n, s, e in dev if match(op_name(n))])
                   for dev in self.ops) * 1e-9 / self.devices

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device ops that took most time (``module/op``, seconds,
        averaged over devices) and the longest idle gaps of the first
        device, each named by the innermost host event that covers its
        middle."""
        per_op: Dict[str, float] = {}
        for d, dev in enumerate(self.ops):
            mods = sorted(self.modules[d], key=lambda iv: iv[1])
            starts = [s for _, s, _ in mods]
            for n, s, e in dev:
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][0] if i >= 0 and mods[i][2] >= e else "?"
                key = f"{_short(mod)}/{op_name(n)}"
                per_op[key] = per_op.get(key, 0.0) + (e - s) * 1e-9
        ops = sorted(([k, v / self.devices] for k, v in per_op.items()),
                     key=lambda kv: -kv[1])[:top]
        idle = []
        if self.ops:
            longest = sorted(gaps([(s, e) for _, s, e in self.ops[0]],
                                  *self.window),
                             key=lambda g: g[0] - g[1])[:top]
            idle = [[self.host_at((s + e) / 2), (e - s) * 1e-9]
                    for s, e in longest]
        return {"device_ops": ops, "idle_gaps": idle}

    def host_at(self, t: float) -> str:
        """Name of the shortest host event that covers time ``t``."""
        best, best_len = "host idle", None
        for n, s, e in self.host:
            if s <= t < e and n != WINDOW_SPAN and (
                    best_len is None or e - s < best_len):
                best, best_len = n, e - s
        return best


def _short(module: str) -> str:
    """``jit_run(12)`` -> ``jit_run``: module events carry a run id."""
    return re.sub(r"\(\d+\)$", "", module)


def op_name(op: str) -> str:
    """``%fusion.3 = f32[8] fusion(...), kind=...`` -> ``%fusion.3``: op
    events carry the whole HLO instruction."""
    return op.split(" = ", 1)[0]


def _clip(ev, lo: float, hi: float):
    s = float(ev.start_ns)
    e = s + float(ev.duration_ns)
    if e <= lo or s >= hi:
        return None
    return (ev.name, max(s, lo), min(e, hi))


def reduce_profile(data) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` to its ``window`` span."""
    host_events = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                host_events.append(ev)
    spans = [ev for ev in host_events if ev.name == WINDOW_SPAN]
    if not spans:
        raise ValueError("the trace holds no 'window' annotation")
    w = max(spans, key=lambda ev: ev.duration_ns)
    lo, hi = float(w.start_ns), float(w.start_ns) + float(w.duration_ns)

    ops, modules = [], []
    for plane in data.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        dev_ops, dev_mods = [], []
        for line in plane.lines:
            if line.name == OP_LINE:
                target = dev_ops
            elif line.name == MODULE_LINE:
                target = dev_mods
            else:
                continue
            for ev in line.events:
                iv = _clip(ev, lo, hi)
                if iv is not None:
                    target.append(iv)
        ops.append(dev_ops)
        modules.append(dev_mods)
    host = [iv for iv in (_clip(ev, lo, hi) for ev in host_events)
            if iv is not None]
    return Reduced(window=(lo, hi), ops=ops, modules=modules, host=host)


def reduce_file(path) -> Reduced:
    """Reduce the ``.xplane.pb`` (or ``.xplane.pb.gz``) at ``path``."""
    import gzip

    from jax.profiler import ProfileData

    path = str(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return reduce_profile(ProfileData.from_serialized_xspace(f.read()))
    return reduce_profile(ProfileData.from_file(path))
