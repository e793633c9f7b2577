#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Everything that belongs to one of them
is found by its name:

* ``bench/configs/<config>.json`` — the deployment's sizes, source,
  cuts, guarantees and front-door settings; ``bench/configs/<config>.py``
  beside it makes the data from the seed and holds the plain float64
  reference;
* ``bench/traffic/<mix>.json`` — the mix's parameters; its ``driver``
  names the general generator in ``bench/drivers/<driver>.py`` that runs
  it (set-up, warm-up, the timed window, the correctness check, and the
  control that ``bench/readings.py`` and the tests put in its place);
* ``bench/metrics/<metric>.py`` — the reader of one per-layer metric,
  found by the part of its name before the first dot
  (``device_idle.ingest`` -> ``device_idle.py``).

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (read from a profiler trace of the window) with the
device's busy and window seconds and a breakdown.  Both check what the
timed path produced against the configuration's reference, print each
number compared beside its limit as the last lines on stderr, and end
stdout with one JSON line.  Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import os
import time


def _process_start() -> float:
    """``time.monotonic()`` at the moment this process started."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.monotonic() - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return time.monotonic()


T_START = _process_start()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def load_module(path: pathlib.Path):
    """Import the Python file at ``path`` (names may hold '-' and '.')."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    mod_name = "bench_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Discovery by name
# ---------------------------------------------------------------------------

def load_spec(root: pathlib.Path = ROOT) -> dict:
    return read_json(root / "BENCHMARK.json")


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json; "
                     f"have {[c['name'] for c in spec['workloads']]}")


def config_files(name: str, bench: pathlib.Path = BENCH):
    """(sizes dict, module with ``generate`` and the reference)."""
    sizes = read_json(bench / "configs" / f"{name}.json")
    return sizes, load_module(bench / "configs" / f"{name}.py")


def traffic_file(name: str, bench: pathlib.Path = BENCH) -> dict:
    return read_json(bench / "traffic" / f"{name}.json")


def driver_module(kind: str, bench: pathlib.Path = BENCH):
    return load_module(bench / "drivers" / f"{kind}.py")


def reader_module(metric: str, bench: pathlib.Path = BENCH):
    """The reader of a per-layer metric: the file named by the part of
    the name before its first dot (``device_idle.ingest`` ->
    ``metrics/device_idle.py``)."""
    return load_module(bench / "metrics" / f"{metric.split('.', 1)[0]}.py")


def cell_metrics(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` prints: with ``trace`` the per-layer
    metrics listed for it (or, without a ``workloads`` key, those whose
    end-to-end metric the cell reports), else its end-to-end metrics."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


@dataclasses.dataclass
class Cell:
    """Everything a driver needs to run one cell."""

    name: str
    seed: int
    chips: int
    config: dict            # the configuration's JSON
    ref: Any                # the configuration's module (data, reference)
    traffic: dict           # the mix's JSON
    settings: dict          # front-door settings: config's, then the mix's
    log: Callable[[str], None]


def make_cell(spec: dict, name: str, seed: int,
              bench: pathlib.Path = BENCH, log=None) -> Cell:
    entry = find_cell(spec, name)
    sizes, ref = config_files(entry["config"], bench)
    traffic = traffic_file(entry["traffic"], bench)
    settings = dict(sizes.get("settings", {}))
    settings.update(traffic.get("settings", {}))
    return Cell(name=name, seed=seed, chips=int(entry["chips"]),
                config=sizes, ref=ref, traffic=traffic, settings=settings,
                log=log or _log)


def use_checkout_paths() -> None:
    """Import ``bench`` from the checkout's root and the program from
    ``src``; drop the script's own directory from the path, where
    ``trace.py`` would shadow the standard library's module."""
    sys.path[:] = [p for p in sys.path
                   if pathlib.Path(p or ".").resolve() != BENCH]
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Compile events, counted by the benchmark's own listener
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts JAX's compile requests and persistent-cache hits and misses.

    ``backend`` counts every request for an executable (a jit cache miss),
    whether the persistent cache then serves it or XLA compiles it."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counts = {"backend": 0, "trace": 0, "cache_hits": 0,
                       "cache_misses": 0, "compile_s": 0.0}

    def install(self) -> "CompileCounter":
        import jax.monitoring as mon

        def on_duration(event: str, secs: float, **_kw):
            with self._lock:
                if event == "/jax/core/compile/backend_compile_duration":
                    self.counts["backend"] += 1
                    self.counts["compile_s"] += secs
                elif event == "/jax/core/compile/jaxpr_trace_duration":
                    self.counts["trace"] += 1

        def on_event(event: str, **_kw):
            with self._lock:
                if event == "/jax/compilation_cache/cache_hits":
                    self.counts["cache_hits"] += 1
                elif event == "/jax/compilation_cache/cache_misses":
                    self.counts["cache_misses"] += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        return self

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self.counts)

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------

def tpu_devices(chips: int):
    """The TPU devices of this process; SystemExit without a TPU or with
    fewer than ``chips`` of them."""
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(f"no TPU: JAX's default backend is "
                         f"{jax.default_backend()!r}")
    devs = jax.devices()
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s) but JAX sees "
                         f"{len(devs)}")
    return devs


def device_line(devs) -> dict:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def enable_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    every program written, no size cap: after a cell's first run in a
    checkout, set-up reads every program back."""
    import jax
    from repro import compile_cache

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return compile_cache.enable()


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, seconds: float, trace: bool, *,
             spec: dict, devices, counter: CompileCounter,
             t_start: float, bench: pathlib.Path = BENCH,
             keep_trace: Optional[str] = None) -> dict:
    """Set up, run the window (traced or not), read the metrics and check
    the result; returns the result line as a dict (``checks`` last)."""
    import jax

    from bench import harness
    from bench import trace as btrace

    driver = driver_module(cell.traffic["driver"], bench)
    wanted = cell_metrics(spec, cell.name, trace)

    ctx = driver.setup(cell)
    c_setup = counter.snapshot()
    if trace:
        seconds = min(seconds, float(cell.traffic.get("trace_seconds",
                                                      seconds)))
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    t_window = time.monotonic()
    setup_s = t_window - t_start
    try:
        with jax.profiler.TraceAnnotation("window"):
            win = driver.window(ctx, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    c_window = CompileCounter.delta(counter.snapshot(), c_setup)
    cell.log("compile events: " + json.dumps({
        "setup": c_setup, "window": c_window}))
    dev = device_line(devices)

    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        paths = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(paths[-1], pathlib.Path(keep_trace) /
                        f"{cell.name}.{cell.seed}.xplane.pb")
        red = btrace.reduce_file(paths[-1])
        shutil.rmtree(tdir, ignore_errors=True)
        dev["busy_s"] = red.busy_s()
        dev["window_s"] = red.window_s()
        rctx = dict(win["readers"], kind=dev["kind"], log=cell.log)
        for m in wanted:
            value = reader_module(m["name"], bench).read(red, rctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        breakdown = red.breakdown()
    else:
        for m in wanted:
            value = (setup_s if m["name"] == "setup_s"
                     else win["metrics"].get(m["name"]))
            if value is None:
                raise RuntimeError(f"the {cell.traffic['driver']} driver "
                                   f"reports no {m['name']}")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    t_ref = time.monotonic()
    with jax.profiler.TraceAnnotation("reference"):
        checks = driver.check(ctx, win)
    cell.log(f"reference: {time.monotonic() - t_ref!r} s")
    correct = all(harness.passed(c) for c in checks.values())
    line = {"correct": bool(correct), "attempted": int(win["attempted"]),
            "failed": int(win["failed"]), "metrics": metrics,
            "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the raw trace into this directory")
    args = ap.parse_args(argv)

    mode = os.environ.get("REPRO_KERNELS")
    if mode not in (None, "", "pallas"):
        raise SystemExit(f"REPRO_KERNELS={mode!r}: the benchmark runs the "
                         f"compiled kernels only (unset it)")
    use_checkout_paths()
    spec = load_spec()
    cell = make_cell(spec, args.workload, args.seed)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # libtpu logs under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))

    devices = tpu_devices(cell.chips)
    counter = CompileCounter().install()
    from repro import compile_cache

    cache = enable_cache()
    line = run_cell(cell, args.seconds, bool(args.trace), spec=spec,
                    devices=devices, counter=counter, t_start=T_START,
                    keep_trace=args.keep_trace)
    cell.log("compile cache: " + json.dumps(compile_cache.usage(cache)))
    for name, c in line["checks"].items():
        cell.log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
