"""StreamSupervisor: mid-stream recovery for the streaming engines.

The ``shard_map`` streaming backend assumes a fixed healthy mesh for
the life of a stream — one lost or slow device kills a week-long
ingest.  The supervisor turns that into a recoverable event::

    sup = StreamSupervisor(config, ckpt_dir, state=svd_init(n, config))
    state = sup.run(batches)          # survives kills / stragglers
    sup.events                        # what happened, machine-readable

It wraps ``api.svd_stream`` in commit-sized chunks
(``SolveConfig.checkpoint_every`` batches per chunk), checkpoints after
every successful chunk, and on a fault:

1. **drain** — flush the async checkpoint writer; the last committed
   batch is the resume point (``obs`` span ``recover.drain``).
2. **re-plan** — drop the dead device from the healthy pool, pick the
   new layout with ``elastic.plan_stream_mesh`` (1-D ``STREAM_AXIS``
   grid when enough survive, honest single-host degrade otherwise) and
   price it with planner rule R8 — the recovery event carries the R8
   reasons, so a degrade is explained, not silent (``recover.replan``).
3. **restore** — ``Checkpointer.restore(reshard=False)`` + an explicit
   ``reshard_for_restore`` against the surviving pool
   (``stream.state.set_stream_devices``), so the state lands sharded
   over the survivors or gathered on one of them (``recover.restore``).
4. **resume** — replay the uncommitted batches.  The PRNG chain keys on
   ``batches_seen`` (batch b always draws ``fold_in(root, b)``), so the
   resumed stream is bit-identical to an uninterrupted run of the same
   batch sequence — the chaos tests assert bitwise equality.

Transient faults (a dropped collective) skip the restore: the
in-flight chunk's partial work is discarded and the chunk replays from
the supervisor's committed state, bounded by ``SolveConfig.max_retries``
with ``retry_backoff_s * 2**attempt`` exponential backoff.

**Straggler detection** rides on ``repro.obs`` instead of ad-hoc
timing: each chunk's ingest span duration, fanned by per-slot skew
factors (the injector's delay seam here; per-host span rings on a real
multi-host deployment) and scaled by the worst plan-vs-measured drift
ratio, feeds ``StragglerMonitor.observe_window``.  A flagged slot with
``backup_ingest=True`` gets **backup-shard duplicate-ingest**: an idle
healthy device outside the mesh shadows the slow slot's shard, and the
chunk completes at the backup's (median) speed — accounted in
``backup_saved_s`` (on forced-host CPU simulation every slot shares one
physical clock, so the saving is accounting, not wall time — the
POLICY, which slots evict vs shadow, is the real thing under test).  A
slot whose RAW time stays flagged for ``patience`` consecutive windows
under ``policy="evict"`` is evicted through the same recovery path as a
kill.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from repro import obs
from repro.checkpoint.ckpt import Checkpointer
from repro.core import planner
from repro.core.planner import ASpec
from repro.ft import elastic
from repro.ft.inject import CollectiveDropError, DeviceLostError
from repro.ft.straggler import StragglerConfig, StragglerMonitor
from repro.obs import clock
from repro.stream import state as stream_state


class NoSurvivorsError(RuntimeError):
    """Every device in the pool is dead — nothing to recover onto."""


@dataclasses.dataclass(frozen=True)
class RecoveryEvent:
    """One machine-readable recovery record (the CI chaos job uploads
    the list as an artifact; ``benchmarks/recovery.py`` gates it)."""

    kind: str                 # "device_lost" | "straggler_evict" |
    #                           "collective_retry"
    batch: int                # global batch index where the fault surfaced
    device: Optional[int]     # pool index of the lost/evicted device
    survivors: int            # healthy pool size after the event
    backend_before: str       # "shard_map" | "single"
    backend_after: str
    resumed_from_batch: int   # batches_seen at the resume point
    retries: int              # attempts consumed (transient faults)
    wall_s: float             # recovery wall time (drain..resume-ready)
    r8_peak_bytes: int        # post-shrink peak the R8 plan prices
    reasons: Tuple[str, ...]  # the R8 plan's reasons (degrade explained)

    def to_json(self) -> Dict:
        d = dataclasses.asdict(self)
        d["reasons"] = list(self.reasons)
        return d


class StreamSupervisor:
    """Wrap a streaming solve with fault recovery (module docstring).

    ``config`` is a streaming ``SolveConfig`` (``truncate_rank`` set;
    ``checkpoint_every`` / ``max_retries`` / ``retry_backoff_s`` are
    the recovery knobs).  ``state`` seeds the stream (``api.svd_init``
    result or a checkpoint restore).  ``devices`` is the device pool
    (default: all local devices); ``injector`` an optional
    ``ft.inject.FaultInjector``.  The supervisor owns the stream-device
    registry (``stream.state.set_stream_devices``) between ``run``
    calls — use it as a context manager (or call :meth:`close`) to
    reset the registry.
    """

    def __init__(self, config, checkpoint_dir: str, *, state,
                 devices: Optional[Sequence] = None,
                 straggler: Optional[StragglerConfig] = None,
                 injector=None, backup_ingest: bool = True, keep: int = 3):
        if config.truncate_rank is None:
            raise ValueError(
                "StreamSupervisor needs a streaming SolveConfig "
                "(truncate_rank=k)")
        self.config = config
        self.state = state
        self.pool: List = list(devices) if devices is not None \
            else list(jax.devices())
        if not self.pool:
            raise ValueError("StreamSupervisor needs a non-empty "
                             "device pool")
        self.healthy: List[int] = list(range(len(self.pool)))
        self.injector = injector
        self.backup_ingest = backup_ingest
        self.straggler_cfg = straggler or StragglerConfig()
        self.ckpt = Checkpointer(checkpoint_dir, keep=keep)
        self.events: List[RecoveryEvent] = []
        self.backup_saved_s = 0.0
        self._base = int(state.batches_seen)
        self._state0 = stream_state.gather_state(
            state, device=self.pool[self.healthy[0]])
        self._monitor: Optional[StragglerMonitor] = None
        self._apply_placement()

    # -- device pool / placement -----------------------------------------

    def _healthy_devices(self) -> List:
        return [self.pool[i] for i in self.healthy]

    def _active_plan(self) -> elastic.ElasticPlan:
        return elastic.plan_stream_mesh(len(self.healthy),
                                        self.state.num_blocks)

    def _apply_placement(self, reset_monitor: bool = False) -> None:
        """Point the stream-device registry at the active slice of the
        healthy pool: exactly ``num_blocks`` devices when the 1-D mesh
        fits (so planner rule R5d picks shard_map), exactly one when
        degraded to single-host."""
        if not self.healthy:
            raise NoSurvivorsError(
                "no surviving devices in the supervisor's pool")
        plan = self._active_plan()
        active = self._healthy_devices()[:plan.shape[0]]
        stream_state.set_stream_devices(active)
        slots = len(active)
        if (reset_monitor or self._monitor is None
                or self._monitor.num_hosts != slots):
            # Fresh EWMAs after ANY recovery, even at unchanged slot
            # count: slot s now maps to a different pool device, and
            # inheriting the evicted straggler's flag streak would get
            # a healthy survivor evicted on the next window.
            self._monitor = StragglerMonitor(self.straggler_cfg, slots)

    @property
    def backend(self) -> str:
        """What the active placement runs: "shard_map" when one device
        per column block is registered, else "single"."""
        return ("shard_map"
                if stream_state.stream_device_count()
                == self.state.num_blocks
                and self.state.num_blocks > 1 else "single")

    def close(self) -> None:
        """Reset the stream-device registry and flush the checkpointer."""
        self.ckpt.wait()
        stream_state.set_stream_devices(None)

    def __enter__(self) -> "StreamSupervisor":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- obs-fed straggler observation ------------------------------------

    def _observe_window(self, dur_s: float, batch: int) -> Dict[str, list]:
        """Feed one chunk's span timing + drift into the monitor and
        apply the backup-shard mitigation policy.  Returns the verdict
        (the caller handles ``evict``)."""
        slots = self._monitor.num_hosts
        factors = [
            self.injector.delay_factor(self.healthy[s], batch)
            if self.injector is not None else 1.0
            for s in range(slots)]
        ratios = obs.drift_ratios()
        drift = max((r for k, r in ratios.items()
                     if k.startswith("R5") or k.startswith("R6")),
                    default=None)
        verdict = self._monitor.observe_window(dur_s, factors, drift=drift)
        for slot in verdict["flagged"]:
            if self.backup_ingest and slot not in verdict["evict"]:
                # Backup-shard duplicate-ingest: shadow the flagged
                # slot's shard on an idle healthy device; the chunk
                # completes at healthy speed, so the straggler costs
                # duplicate work, not wall time.
                saved = dur_s * max(0.0, factors[slot] - 1.0)
                self.backup_saved_s += saved
        return verdict

    # -- recovery ----------------------------------------------------------

    def _recovery_plan(self, m_hint: int):
        spec = ASpec(m=max(1, m_hint), n=self.state.n,
                     nnz=max(1, m_hint) * self.state.n,
                     num_blocks=self.state.num_blocks, kind="stream")
        return planner.make_recovery_plan(spec, self.config,
                                          survivors=len(self.healthy))

    def _recover(self, kind: str, batch: int, device: Optional[int],
                 m_hint: int, retries: int = 0) -> None:
        """The four-step recovery path (drain / re-plan / restore /
        resume-ready); appends the RecoveryEvent."""
        t0 = clock.now()
        backend_before = self.backend
        t_us = clock.now_us()
        self.ckpt.wait()                          # drain
        obs.trace.add_complete("recover.drain", t_us,
                               clock.now_us() - t_us, kind=kind)

        if device is not None and device in self.healthy:
            self.healthy.remove(device)
        if not self.healthy:
            raise NoSurvivorsError(
                f"device {device} was the last healthy device")

        t_us = clock.now_us()
        rplan = self._recovery_plan(m_hint)       # re-plan (R8)
        self._apply_placement(reset_monitor=True)
        obs.trace.add_complete(
            "recover.replan", t_us, clock.now_us() - t_us,
            survivors=len(self.healthy), backend=rplan.backend,
            r8_peak_bytes=rplan.peak_bytes)

        t_us = clock.now_us()
        step = self.ckpt.latest_step()            # restore
        if step is not None:
            restored, _meta = self.ckpt.restore(step, reshard=False)
        else:
            # Fault before the first commit: rewind to the initial
            # state (kept gathered host-side at construction).
            restored = self._state0
        restored = restored.reshard_for_restore()
        if stream_state.stream_device_count() == 1:
            restored = stream_state.gather_state(restored)
        self.state = restored
        obs.trace.add_complete(
            "recover.restore", t_us, clock.now_us() - t_us,
            resumed_from_batch=int(restored.batches_seen))

        wall = clock.now() - t0
        event = RecoveryEvent(
            kind=kind, batch=batch, device=device,
            survivors=len(self.healthy),
            backend_before=backend_before, backend_after=rplan.backend,
            resumed_from_batch=int(restored.batches_seen),
            retries=retries, wall_s=wall,
            r8_peak_bytes=rplan.peak_bytes, reasons=rplan.reasons)
        self.events.append(event)

    # -- the supervised stream loop ---------------------------------------

    def run(self, batches: Sequence):
        """Ingest every batch, surviving faults; returns the final
        state.  ``batches`` must be a re-indexable sequence — recovery
        replays the batches after the last commit (a generator cannot
        rewind; spool it first)."""
        from repro.core import api

        batches = list(batches)
        every = self.config.checkpoint_every or 1
        i = int(self.state.batches_seen) - self._base
        if i < 0:
            raise ValueError(
                f"state.batches_seen={self.state.batches_seen} is behind "
                f"the supervisor's base {self._base}")
        attempt = 0
        while i < len(batches):
            chunk = batches[i:i + every]
            lo = self._base + i
            hi = lo + len(chunk)
            if self.injector is not None:
                self.injector.begin_batches(lo, hi)
            t0 = clock.now()
            try:
                result = api.svd_stream(chunk, self.config,
                                        state=self.state)
            except CollectiveDropError as e:
                attempt += 1
                if attempt > self.config.max_retries:
                    # Bounded retry exhausted: escalate to the full
                    # device-loss path (re-plan + restore) — the
                    # honest interpretation of a collective that will
                    # not come back.
                    self._recover("collective_escalate", e.batch, None,
                                  self._m_hint(chunk), retries=attempt)
                    i = int(self.state.batches_seen) - self._base
                    attempt = 0
                    continue
                self.events.append(RecoveryEvent(
                    kind="collective_retry", batch=e.batch, device=None,
                    survivors=len(self.healthy),
                    backend_before=self.backend,
                    backend_after=self.backend,
                    resumed_from_batch=int(self.state.batches_seen),
                    retries=attempt, wall_s=clock.now() - t0,
                    r8_peak_bytes=0, reasons=(
                        f"transient collective drop at batch {e.batch}; "
                        f"replaying the uncommitted chunk (attempt "
                        f"{attempt}/{self.config.max_retries}) — the "
                        f"PRNG chain keys on batches_seen, so the retry "
                        f"is bit-identical",)))
                if self.config.retry_backoff_s:
                    time.sleep(self.config.retry_backoff_s
                               * (2 ** (attempt - 1)))
                continue
            except DeviceLostError as e:
                self._recover("device_lost", e.batch, e.device,
                              self._m_hint(chunk))
                i = int(self.state.batches_seen) - self._base
                attempt = 0
                continue
            attempt = 0
            self.state = result.state
            i += len(chunk)
            self.ckpt.save(int(self.state.batches_seen), self.state,
                           blocking=False)
            verdict = self._observe_window(clock.now() - t0, hi - 1)
            if verdict["evict"]:
                # Evict the slowest flagged slot at this (just
                # committed) boundary; remaining evictees get caught on
                # later windows against the re-meshed monitor.
                slot = verdict["evict"][0]
                self._recover("straggler_evict", hi - 1,
                              self.healthy[slot], self._m_hint(chunk))
                i = int(self.state.batches_seen) - self._base
        self.ckpt.wait()
        return self.state

    @staticmethod
    def _m_hint(chunk) -> int:
        try:
            return int(stream_state.delta_shape(chunk[0])[0])
        except Exception:
            return 1

    def events_json(self) -> List[Dict]:
        return [e.to_json() for e in self.events]

    def write_events(self, path: str, **extra) -> None:
        """The CI artifact: recovery events + pool summary as JSON."""
        doc = dict(events=self.events_json(),
                   healthy=len(self.healthy), pool=len(self.pool),
                   backend=self.backend,
                   backup_saved_s=self.backup_saved_s, **extra)
        with open(path, "w") as f:
            json.dump(doc, f, indent=2)
