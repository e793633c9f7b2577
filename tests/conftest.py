import contextlib
import os
import subprocess
import sys
import textwrap

import pytest

# Tests exercise kernels explicitly with interpret=True; everything else
# (models, integration) uses the pure-jnp reference path so CPU tests are
# fast and the device count stays 1 (the 512-device env var is dryrun-only).
os.environ.setdefault("REPRO_KERNELS", "ref")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pytest_configure(config):
    # The 8-device subprocess suites carry @pytest.mark.timeout caps.
    # pytest-timeout (requirements-dev.txt) enforces them in CI; when
    # the plugin is absent locally the marker must still be registered
    # or strict-marker runs reject the suite.
    if not config.pluginmanager.hasplugin("timeout"):
        config.addinivalue_line(
            "markers",
            "timeout(seconds): per-test hard timeout, enforced by "
            "pytest-timeout when installed (no-op without it)")


@contextlib.contextmanager
def jaxpr_traces():
    """Count the jaxpr traces JAX makes inside the block: the
    ``/jax/core/compile/jaxpr_trace_duration`` events that
    ``bench/run.py``'s ``CompileCounter`` counts.  Yields a one-item list
    holding the count."""
    import jax.monitoring as mon

    seen = [0]

    def on(event, secs, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            seen[0] += 1

    mon.register_event_duration_secs_listener(on)
    try:
        yield seen
    finally:
        mon.unregister_event_duration_listener(on)


def run_forced_devices(body: str, devices: int = 8) -> str:
    """Run a snippet in a subprocess with ``devices`` forced host
    devices.  jax pins the device count at first initialization, so
    multi-device tests (test_distributed / test_api / test_streaming)
    all use this one mechanism instead of in-process meshes."""
    code = textwrap.dedent(body)
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               REPRO_KERNELS="ref",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


MEMORY_SLACK = 1.3   # XLA scratch/alignment overhead atop the closed
                     # form's dominant terms (measured ratios on CPU sit
                     # at 1.02-1.20; a regression like an accidental
                     # densify or an untruncated merge blows well past)


def measured_bytes(jitted_fn, args, *, component: str = "temp"):
    """Compile ``jitted_fn`` for ``args`` and return its measured peak
    bytes: ``temp`` = XLA temporaries only (what planner rules R5/R5d
    price — intermediates, not I/O), ``total`` = temps + arguments +
    outputs - aliased (what R6 prices — the whole dispatch is resident).
    Returns None when the backend exposes no memory analysis."""
    stats = jitted_fn.lower(*args).compile().memory_analysis()
    if stats is None:                                 # pragma: no cover
        return None
    temp = int(stats.temp_size_in_bytes)
    if component == "temp":
        return temp
    return (temp + int(stats.argument_size_in_bytes)
            + int(stats.output_size_in_bytes)
            - int(stats.alias_size_in_bytes))


class MemoryChecker:
    """Falkon-style memory assertion: the *measured* compiled peak of a
    jitted callable must stay within a planner closed form (times
    :data:`MEMORY_SLACK`).  Keeps the R5/R5d/R6 byte formulas honest —
    if the engine allocates something the planner does not price, the
    budget check that users rely on is fiction."""

    slack = MEMORY_SLACK

    def __call__(self, jitted_fn, args, budget_bytes, *, label: str = "",
                 component: str = "temp", slack: float = None):
        measured = measured_bytes(jitted_fn, args, component=component)
        if measured is None:                          # pragma: no cover
            pytest.skip("backend exposes no compiled memory analysis")
        self.check_value(measured, budget_bytes,
                         label=f"{label} ({component})", slack=slack)
        return measured

    def check_value(self, measured: int, budget_bytes: int, *,
                    label: str = "", slack: float = None):
        allowed = int(budget_bytes * (self.slack if slack is None
                                      else slack))
        assert measured <= allowed, (
            f"{label or 'callable'}: measured peak {measured}B exceeds "
            f"closed form {budget_bytes}B (x{slack or self.slack} slack "
            f"= {allowed}B) — the planner is under-pricing this path")


@pytest.fixture
def memory_checker():
    return MemoryChecker()
