"""Pieces every driver shares: the clock, the sample of answers kept
for the check, the plan check, and the host spans."""
from __future__ import annotations

import time
from typing import Any

import numpy as np

# The markers a degraded plan leaves in its reasons (planner.py).
_DEGRADED = ("degrad", "NO ", "EXCEEDS")


def now() -> float:
    return time.perf_counter()


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Reservoir:
    """Keeps one of the answers offered, drawn uniformly from the seed:
    the check compares an answer of the window without holding them all."""

    def __init__(self, seed: int):
        self._rng = np.random.default_rng([seed, 7])
        self.item: Any = None
        self._seen = 0

    def offer(self, item: Any) -> None:
        self._seen += 1
        if self._rng.integers(0, self._seen) == 0:
            self.item = item


def plan_fields(plan) -> dict:
    return {"backend": plan.backend, "strategy": plan.strategy,
            "rank": plan.rank, "window": plan.window,
            "degraded": any(m in r for r in plan.reasons for m in _DEGRADED)}


def require_plan(plan, want: dict, where: str) -> dict:
    """Raise unless ``plan`` is the one the cell states: every key of
    ``want`` (``backend``, ``strategy``, ``rank``; ``min_window``) holds
    and the plan is not degraded."""
    got = plan_fields(plan)
    bad = [k for k, v in want.items() if k != "min_window" and got[k] != v]
    if "min_window" in want and (got["window"] or 0) < want["min_window"]:
        bad.append("window")
    if got["degraded"]:
        bad.append("degraded")
    if bad:
        raise RuntimeError(f"{where}: the plan {got} is not the one the "
                           f"cell states {want} ({', '.join(bad)})")
    return got


def checks(numbers: dict, limits: dict) -> dict:
    """Each number compared beside its limit, in the order of ``limits``;
    a number that is not finite is printed as a string and fails."""
    out = {}
    for name, limit in limits.items():
        v = float(numbers[name])
        out[name] = {"value": v if np.isfinite(v) else str(v),
                     "limit": float(limit)}
    return out


def passed(check: dict) -> bool:
    v = check["value"]
    return isinstance(v, float) and v <= check["limit"]
