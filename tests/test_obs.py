"""The observability layer (repro.obs): span nesting/ordering and the
ring buffer's drop-oldest overflow policy, spans on the profiler's host
trace (the front door's svd.* spans with their args, obs on or off),
the off path that touches no recorder, the named scopes in the solve
program's lowered text, the Prometheus/JSON metric
exporters (golden output), the plan-vs-measured drift monitor (fires a
one-shot DriftWarning on an under-priced plan, stays silent for
R5/R6/R7 at reference shapes), the disabled-mode contract (zero extra
jit traces, zero extra window dispatches, bit-identical factors, empty
ring/registry), Diagnostics' compile/run wall-time split, ServeHandle
metrics, and the 8-device shard_map run whose R5d drift gauges record
PER-DEVICE peaks against the per-device closed form."""
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import planner, ranky, sparse
from repro.core.api import (ASpec, ServeTopKConfig, SolveConfig,
                            serve_init, serve_topk, svd, svd_init,
                            svd_stream, svd_update)
from repro.stream import window as sw

from conftest import run_forced_devices

N, D, K = 96, 4, 12
CFG = SolveConfig(method="none", truncate_rank=K, num_blocks=D)


@pytest.fixture
def obs_on():
    """Enabled + clean obs state; always restores the module-global
    disabled default so the rest of the suite runs untouched."""
    obs.enable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _batches(num, m=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((m, N)).astype(np.float32)
            for _ in range(num)]


# ---------------------------------------------------------------------------
# spans + ring buffer
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering(obs_on):
    with obs.span("a.outer", stage=1):
        with obs.span("a.inner"):
            pass
        obs.event("a.mark", hit=True)
    evs = obs.trace.events()
    # append order == exit order: inner closes first, outer last
    assert [e.name for e in evs] == ["a.inner", "a.mark", "a.outer"]
    inner, mark, outer = evs
    assert (outer.ph, inner.ph, mark.ph) == ("X", "X", "i")
    assert outer.depth == 0 and inner.depth == 1 and mark.depth == 1
    # the inner span is contained in the outer one on the obs timebase
    assert outer.ts_us <= inner.ts_us
    assert inner.ts_us + inner.dur_us <= outer.ts_us + outer.dur_us
    assert outer.args == (("stage", 1),)
    summary = obs.span_summary(evs)
    assert [row[0] for row in summary] == ["a.outer", "a.inner"]
    assert summary[0][1] == 1 and summary[0][2] >= summary[1][2]


def test_span_records_nothing_while_jax_traces(obs_on):
    def f(x):
        with obs.span("traced.body"):
            return x * 2
    jax.jit(f)(jnp.ones((4,)))
    assert [e.name for e in obs.trace.events()] == []


def test_ring_overflow_drops_oldest(obs_on):
    try:
        obs.trace.set_capacity(4)
        for i in range(10):
            obs.event("ring.tick", i=i)
        evs = obs.trace.events()
        assert len(evs) == 4
        # drop-OLDEST: the survivors are the most recent four
        assert [dict(e.args)["i"] for e in evs] == [6, 7, 8, 9]
        assert obs.trace.dropped() == 6
        # the appended count keeps growing past capacity; since() reads
        # what the ring still holds of the events after a count
        assert obs.trace.appended() == 10
        assert [dict(e.args)["i"] for e in obs.trace.since(8)] == [8, 9]
        assert obs.trace.since(0) == evs
        assert obs.trace.since(10) == []
        obs.trace.clear()
        assert obs.trace.events() == [] and obs.trace.dropped() == 0
        assert obs.trace.appended() == 0
    finally:
        obs.trace.set_capacity(obs.gate.ring_capacity())


def test_ring_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        obs.trace.TraceBuffer(0)


def test_chrome_trace_schema_roundtrip(obs_on):
    with obs.span("ingest.window", bucket="('dense', 8)"):
        obs.event("snapshot.publish", version=1)
    doc = obs.chrome_trace()
    obs.validate_chrome_trace(doc)
    recs = doc["traceEvents"]
    assert recs[0]["ph"] == "M"      # process_name metadata
    cats = {r.get("cat") for r in recs[1:]}
    assert cats == {"ingest", "snapshot"}


def _coo(m=24, n=64, nnz=120, seed=0):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, m, nnz).astype(np.int32)
    cols = rng.integers(0, n, nnz).astype(np.int32)
    vals = rng.uniform(0.5, 2.0, nnz).astype(np.float32)
    return sparse.COOMatrix(rows=rows, cols=cols, vals=vals, shape=(m, n))


def _host_events(tmp_path, body):
    """Run ``body`` under a profiler session; the host plane's events as
    (name, start_ns, end_ns, stats dict)."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


FRONT_DOOR = ("svd.plan", "svd.convert", "svd.solve", "svd.wait",
              "svd.diagnostics")


def test_front_door_spans_reach_the_profiler_with_obs_off(tmp_path):
    """obs off, a profiler session on: one svd call leaves its five spans
    on the host plane, one after the other inside the caller's
    annotation, with their counts as stats; the ring stays empty."""
    assert not obs.enabled()
    obs.reset()
    coo = _coo()
    cfg = SolveConfig(num_blocks=4, method="neighbor_random",
                      want_right=True)
    svd(coo, cfg)                       # compile outside the session
    got = {}

    def call():
        with jax.profiler.TraceAnnotation("caller"):
            got["res"] = svd(coo, cfg)

    evs = _host_events(tmp_path, call)
    assert obs.trace.events() == []
    (outer,) = [e for e in evs if e[0] == "caller"]
    spans = []
    for name in FRONT_DOOR:
        (ev,) = [e for e in evs if e[0] == name]
        assert outer[1] <= ev[1] <= ev[2] <= outer[2], name
        spans.append(ev)
    for before, after in zip(spans, spans[1:]):
        assert before[2] <= after[1], (before[0], after[0])
    stats = {e[0]: e[3] for e in spans}
    ell = sparse.block_ell_from_coo(coo, 4)
    assert stats["svd.convert"] == {"nnz": coo.nnz,
                                    "ell_slots": int(ell.col_vals.size)}
    diag = got["res"].diagnostics
    assert stats["svd.diagnostics"] == {
        "lonely_rows": diag.lonely_rows,
        "repaired_rows": diag.repaired_rows}
    assert stats["svd.solve"]["backend"] == "single"


def test_span_args_added_in_the_body_reach_the_profiler(tmp_path):
    def body():
        with obs.span("late.flag", batches=3) as args:
            args["compiled"] = True
        obs.event("mark.here", v=2)

    evs = {e[0]: e[3] for e in _host_events(tmp_path, body)}
    assert evs["late.flag"] == {"batches": 3, "compiled": 1}
    assert evs["mark.here"] == {"v": 2}


def test_span_off_path_touches_no_recorder(monkeypatch):
    """obs off, no profiler session: a span and an event ask the gate and
    the profiler's is_enabled, and nothing else — no annotation, no
    trace-state query, no jax trace or compile, no ring write."""
    from repro.obs import clock

    class NoProfiler:
        @staticmethod
        def is_enabled():
            return False

        def __init__(self, *a, **k):
            raise AssertionError("annotation made with no session")

    def no_query():
        raise AssertionError("trace state queried on the off path")

    assert not obs.enabled()
    obs.reset()
    monkeypatch.setattr(obs.trace, "TraceAnnotation", NoProfiler)
    monkeypatch.setattr(obs.trace, "trace_state_clean", no_query)
    clock.install_compile_probe()
    c0 = clock.compile_seconds()
    with obs.span("off.span", a=1) as args:
        args["b"] = 2
    obs.event("off.event")
    assert clock.compile_seconds() == c0
    assert obs.trace.events() == [] and obs.trace.appended() == 0


def test_solve_single_lowered_text_carries_the_scopes():
    ell = sparse.block_ell_from_coo(_coo(), 4)
    text = ranky.solve_single.lower(
        ell, num_blocks=4, method="neighbor_random", merge_mode="gram",
        want_right=True).as_text(debug_info=True)
    for scope in ("ranky.repair", "ranky.gram", "ranky.eigh",
                  "ranky.right"):
        assert scope in text, scope


def test_ingest_window_span_carries_the_compile_flag(obs_on):
    sw.clear_caches()
    for seed in (7, 8):
        svd_stream(iter(_batches(6, seed=seed)), CFG)
    flags = [dict(e.args)["compiled"] for e in obs.trace.events()
             if e.name == "ingest.window"]
    assert flags[0] is True and flags[-1] is False


def test_call_digest_survives_a_full_ring(obs_on):
    """The ring is full (drop-oldest) before the call: the call's own
    spans still make its Diagnostics.span_summary."""
    try:
        obs.trace.set_capacity(16)
        for i in range(40):
            obs.event("ring.fill", i=i)
        assert obs.trace.dropped() == 24
        rng = np.random.default_rng(0)
        a = rng.standard_normal((16, 32)).astype(np.float32)
        summary = svd(a, SolveConfig(num_blocks=2)).diagnostics.span_summary
        assert {row[0] for row in summary} == {
            "svd.plan", "svd.convert", "svd.solve", "svd.wait"}
    finally:
        obs.trace.set_capacity(obs.gate.ring_capacity())


# ---------------------------------------------------------------------------
# metrics registry + exporters
# ---------------------------------------------------------------------------

def test_export_text_golden(obs_on):
    obs.counter_add("ingest_rows_total", 3)
    obs.gauge_set("snapshot_version", 2)
    for v in (100.0, 200.0, 300.0):
        obs.histogram_observe("serve_latency_us", v)
    assert obs.export_text() == (
        "# TYPE ingest_rows_total counter\n"
        "ingest_rows_total 3\n"
        "# TYPE snapshot_version gauge\n"
        "snapshot_version 2\n"
        "# TYPE serve_latency_us summary\n"
        'serve_latency_us{quantile="0.5"} 200\n'
        'serve_latency_us{quantile="0.9"} 300\n'
        'serve_latency_us{quantile="0.99"} 300\n'
        "serve_latency_us_sum 600\n"
        "serve_latency_us_count 3\n")


def test_export_json_and_labels(obs_on):
    obs.counter_add("planner_plans_total", labels={"rule": "R6"})
    obs.counter_add("planner_plans_total", labels={"rule": "R6"})
    obs.gauge_set("drift_ratio", 1.02, labels={"rule": "R7",
                                               "site": "dense"})
    doc = obs.export_json()
    assert doc["counters"] == {'planner_plans_total{rule="R6"}': 2}
    assert doc["gauges"] == {
        'drift_ratio{rule="R7",site="dense"}': 1.02}
    assert doc["histograms"] == {}
    reg = obs.registry()
    assert reg.counter_value("planner_plans_total",
                             {"rule": "R6"}) == 2
    assert reg.gauge_value("drift_ratio",
                           {"site": "dense", "rule": "R7"}) == 1.02


def test_histogram_reservoir_is_sliding_window(obs_on):
    h = obs.metrics.Histogram(capacity=4)
    for v in (1.0, 2.0, 3.0, 4.0, 100.0, 100.0, 100.0, 100.0):
        h.observe(v)
    assert h.count == 8                      # lifetime count survives
    assert h.quantile(0.5) == 100.0          # quantiles track the window


def test_disabled_wrappers_do_not_touch_registry():
    assert not obs.enabled()
    obs.reset()
    obs.counter_add("ghost_total")
    obs.gauge_set("ghost_gauge", 1.0)
    obs.histogram_observe("ghost_hist", 1.0)
    assert obs.record_drift("R6", 10, 1) is None
    doc = obs.export_json()
    assert (doc["counters"], doc["gauges"], doc["histograms"]) \
        == ({}, {}, {})


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------

def test_drift_warns_once_on_underpriced_plan(obs_on):
    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    with pytest.warns(obs.DriftWarning, match="under-pricing"):
        ratio = obs.observe_compiled("R6", lambda: f, (x,), 8,
                                     component="total", label="test")
    assert ratio is not None and ratio > obs.gate.drift_factor()
    assert obs.drift_ratios()["R6/test"] == ratio
    reg = obs.registry()
    assert reg.gauge_value("drift_ratio",
                           {"rule": "R6", "site": "test"}) == ratio
    # shape-memoized AND one-shot: the same site/shape neither
    # re-measures nor re-warns
    with warnings.catch_warnings():
        warnings.simplefilter("error", obs.DriftWarning)
        again = obs.observe_compiled("R6", lambda: f, (x,), 8,
                                     component="total", label="test")
    assert again == ratio


def test_drift_record_sets_all_three_gauges(obs_on):
    ratio = obs.record_drift("R5", 120, 100, label="single")
    assert ratio == pytest.approx(1.2)
    reg = obs.registry()
    lab = {"rule": "R5", "site": "single"}
    assert reg.gauge_value("drift_measured_bytes", lab) == 120
    assert reg.gauge_value("drift_estimated_bytes", lab) == 100
    assert reg.gauge_value("drift_ratio", lab) == pytest.approx(1.2)
    # ratios() keeps the WORST ratio per key
    obs.record_drift("R5", 110, 100, label="single")
    assert obs.drift_ratios()["R5/single"] == pytest.approx(1.2)


def test_drift_silent_on_pipeline_at_reference_shapes(obs_on):
    """The acceptance-criterion run: svd_stream + serve_topk with
    observe on records R5, R6 and R7 drift ratios, all at or below the
    configured threshold — no DriftWarning at the shapes we ship."""
    rng = np.random.default_rng(3)
    cfg = SolveConfig(method="none", truncate_rank=K, num_blocks=D,
                      observe=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error", obs.DriftWarning)
        res = svd_stream(iter(_batches(5)), cfg)
        handle = serve_init(res.state,
                            ServeTopKConfig(batch_size=8, k_top=5,
                                            use_kernel=False))
        serve_topk(handle, jnp.asarray(
            rng.standard_normal((8, K)).astype(np.float32)))
    ratios = obs.drift_ratios()
    for rule in ("R5", "R6", "R7"):
        keys = [k for k in ratios if k.split("/")[0] == rule]
        assert keys, f"{rule} drift never recorded: {ratios}"
        for k in keys:
            assert ratios[k] <= obs.gate.drift_factor(), (k, ratios)
    # the digest rides on Diagnostics when observe=True
    assert res.diagnostics.drift_ratios is not None
    assert any(k.startswith("R6") for k in res.diagnostics.drift_ratios)
    assert res.diagnostics.span_summary is not None
    assert {row[0] for row in res.diagnostics.span_summary} >= \
        {"ingest.window"}
    # ServeHandle.metrics() surfaces the serve-side view
    m = handle.metrics()
    assert m["snapshot_version"] == 0     # no commit yet
    assert m["serve_requests_total"] == 1.0
    assert m["serve_queries_total"] == 8.0
    assert m["serve_latency_us_p99"] > 0
    assert all(k.split("/")[0] == "R7" for k in m["drift_ratios"])


# ---------------------------------------------------------------------------
# disabled mode: the zero-cost contract
# ---------------------------------------------------------------------------

def test_disabled_mode_zero_dispatch_and_bit_identical():
    """observe=off vs on from identical fresh cache state: the SAME
    number of window dispatches and jit traces, bit-identical factors —
    and the off run leaves the ring and registry empty."""
    assert not obs.enabled()
    obs.reset()
    batches = _batches(6, seed=42)

    sw.clear_caches()
    sw.reset_dispatch_counts()
    res_off = svd_stream(iter(batches), CFG)
    off_counts = dict(sw.dispatch_counts())
    off_traces = sw.trace_count()
    assert obs.trace.events() == []
    doc = obs.export_json()
    assert (doc["counters"], doc["gauges"], doc["histograms"]) \
        == ({}, {}, {})
    assert obs.drift_ratios() == {}

    obs.enable()
    try:
        obs.reset()
        sw.clear_caches()
        sw.reset_dispatch_counts()
        res_on = svd_stream(iter(batches), CFG)
        on_counts = dict(sw.dispatch_counts())
        on_traces = sw.trace_count()
        assert obs.trace.events(), "observe=on recorded nothing"
    finally:
        obs.disable()
        obs.reset()

    assert off_counts == on_counts
    assert off_traces == on_traces
    for f in ("u", "s", "v"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res_off.state, f)),
            np.asarray(getattr(res_on.state, f)), err_msg=f)


def test_disabled_serve_topk_uses_untouched_path():
    assert not obs.enabled()
    obs.reset()
    state = svd_stream(iter(_batches(3, seed=5)), CFG).state
    handle = serve_init(state, ServeTopKConfig(batch_size=4, k_top=3,
                                               use_kernel=False))
    q = jnp.asarray(np.random.default_rng(1)
                    .standard_normal((4, K)).astype(np.float32))
    serve_topk(handle, q)
    assert obs.trace.events() == []
    assert obs.drift_ratios() == {}
    # metrics() still answers (buffer-derived health needs no obs)
    m = handle.metrics()
    assert m["snapshot_version"] == 0
    assert m["snapshot_age_s"] >= 0
    assert "serve_requests_total" not in m


# ---------------------------------------------------------------------------
# Diagnostics wall-time split
# ---------------------------------------------------------------------------

def test_diagnostics_compile_run_split():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((32, 48)).astype(np.float32)
    cfg = SolveConfig(num_blocks=2)
    d1 = svd(a, cfg).diagnostics
    assert d1.wall_time_s == pytest.approx(
        d1.compile_time_s + d1.run_time_s)
    assert d1.compile_time_s >= 0 and d1.run_time_s >= 0
    # warm call: same shapes, no new trace -> compile share ~ 0
    d2 = svd(a, cfg).diagnostics
    assert d2.compile_time_s <= d1.wall_time_s
    assert d2.run_time_s > 0
    # off by default: no obs payloads on Diagnostics
    assert d1.drift_ratios is None and d1.span_summary is None


def test_compile_probe_counts_nested_traces_once():
    # Tracing a jit traces the jits it calls; each reports its own
    # trace event, nested inside the caller's.  Counted once, the
    # compile seconds of a cold call stay within its wall time.
    from repro.obs import clock

    def body(x):
        for i in range(300):          # a trace that takes a while
            x = x * 1.0001 + i
        return x

    fn = jax.jit(body)
    for _ in range(6):                # six nested jit levels
        fn = jax.jit(lambda x, f=fn: f(x) + 1.0)
    clock.install_compile_probe()
    c0, t0 = clock.compile_seconds(), clock.now()
    jax.block_until_ready(fn(jnp.ones((8,), jnp.float32)))
    wall = clock.now() - t0
    assert 0 < clock.compile_seconds() - c0 <= wall


# ---------------------------------------------------------------------------
# 8-device shard_map: per-device drift gauges
# ---------------------------------------------------------------------------

@pytest.mark.timeout(840)
def test_shard_map_r5d_drift_is_per_device_subprocess():
    """R5d drift on the 8-device shard_map ingest: memory_analysis
    reports PER-DEVICE peaks and the sharded stream plan prices
    per-device bytes, so the recorded ratio sits under the threshold —
    a whole-mesh measurement would read ~8x and trip the warning."""
    out = run_forced_devices("""
        import warnings
        import numpy as np, jax
        from repro import obs
        from repro.core.api import SolveConfig, svd_init, svd_update
        assert jax.device_count() == 8
        obs.enable()
        d, n, m_b, k = 8, 4096, 32, 16
        cfg = SolveConfig(truncate_rank=k, oversample=8, num_blocks=d,
                          stream_backend="shard_map")
        rng = np.random.default_rng(0)
        state = svd_init(n, cfg)
        with warnings.catch_warnings():
            warnings.simplefilter("error", obs.DriftWarning)
            for _ in range(2):
                batch = rng.standard_normal((m_b, n)).astype(np.float32)
                state = svd_update(state, batch, cfg).state
        ratios = obs.drift_ratios()
        assert "R5d/shard_map" in ratios, ratios
        lab = {"rule": "R5d", "site": "shard_map"}
        reg = obs.registry()
        meas = reg.gauge_value("drift_measured_bytes", lab)
        est = reg.gauge_value("drift_estimated_bytes", lab)
        assert meas is not None and est is not None
        assert ratios["R5d/shard_map"] == meas / est
        assert meas <= est * obs.gate.drift_factor(), (meas, est)
        print("OK", round(ratios["R5d/shard_map"], 3))
    """)
    assert "OK" in out
