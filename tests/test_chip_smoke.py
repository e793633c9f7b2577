"""chip_smoke.py's phases on the CPU at a small size, against the same
float64 reference checks the chip run applies — plus the refusals that
keep the script from passing anywhere but on a TPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke as cs  # noqa: E402
from conftest import REPO, run_forced_devices  # noqa: E402
from repro.core import api, planner  # noqa: E402

D = 8
STREAM = dict(items=4096, draws_per_row=48, batch_rows=128, batches=8,
              groups=8, seed=25)
RANK = 8
# What this small stream reaches on the CPU (float32 XLA CPU, measured
# once at this size and seed) — its truncation error by design.
STREAM_CPU = {"sv_rel": 0.009277063356082632, "v_sin": 0.049054128811818434}


@pytest.fixture(scope="module")
def paper_small():
    return cs.paper_matrix(rows=64, cols=4096, density=5e-3, seed=2020)


@pytest.fixture(scope="module")
def stream_out():
    batches = cs.rating_rows(num_blocks=D, **STREAM)
    cfg = api.SolveConfig(method="neighbor_random", num_blocks=D,
                          truncate_rank=RANK, use_kernel=True,
                          stream_backend="single")
    return cs.stream_phase(batches, cfg)


@pytest.mark.parametrize("use_kernel", [True, False])
def test_oneshot_phase_meets_f32_bounds(paper_small, use_kernel,
                                        monkeypatch):
    # The kernel run executes the real sparse_gram body (interpreted).
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    cfg = api.SolveConfig(method="neighbor_random", num_blocks=D,
                          want_right=True, use_kernel=use_kernel)
    out = cs.oneshot_phase(paper_small, cfg)
    cs.check("oneshot", out, cs.ONESHOT_TOL, backend="single",
             strategy="exact_gram")
    assert out["repaired_rows"] > 0      # the rank problem is exercised


def test_rating_rows_shape_and_no_lonely_rows():
    batches = cs.rating_rows(num_blocks=D, **STREAM)
    assert len(batches) == STREAM["batches"]
    w = -(-STREAM["items"] // D)
    for b in batches:
        assert b.shape == (STREAM["batch_rows"], STREAM["items"])
        hit = set(zip(b.rows.tolist(), (b.cols // w).tolist()))
        assert len(hit) == STREAM["batch_rows"] * D


def test_stream_phase_within_cpu_truncation_bound(stream_out):
    bounds = {k: v + cs.STREAM_SLACK for k, v in STREAM_CPU.items()}
    cs.check("stream", stream_out, bounds, backend="single",
             strategy="streaming")
    assert stream_out["plan"]["rank"] is None       # exact batch gram
    assert stream_out["plan"]["window"] > 1         # scan window on
    assert stream_out["repaired_rows"] == 0


@pytest.mark.parametrize("quantize", [False, True])
def test_serve_phase_tie_aware_topk(stream_out, quantize):
    cfg = api.ServeTopKConfig(batch_size=16, k_top=5, quantize=quantize,
                              use_kernel=True, serve_backend="single")
    out = cs.serve_phase(stream_out["result"].state, cfg, waves=2, seed=7)
    cs.check("serve", out, {"score_over_tol": 1.0}, backend="single",
             strategy="serve_fused")
    assert out["bitwise_vs_ref"]


def test_degraded_plan_fails_the_check():
    # shard_map requested on one device: R5d degrades to single-host.
    cfg = api.SolveConfig(truncate_rank=RANK, num_blocks=D,
                          stream_backend="shard_map")
    spec = planner.ASpec(m=128, n=4096, nnz=1000, num_blocks=D,
                         kind="stream")
    plan = planner.make_stream_plan(spec, cfg, device_count=1)
    out = {"plan": cs._plan_line(plan), "errors": {}}
    assert out["plan"]["degraded"]
    with pytest.raises(AssertionError, match="not the requested"):
        cs.check("stream", out, {}, backend="shard_map",
                 strategy="streaming")


def test_error_past_bound_fails_the_check():
    out = {"plan": {"backend": "single", "strategy": "exact_gram",
                    "degraded": False},
           "errors": {"gram": 2 * cs.ONESHOT_TOL["gram"]}}
    with pytest.raises(AssertionError, match="gram error"):
        cs.check("oneshot", out, {"gram": cs.ONESHOT_TOL["gram"]},
                 backend="single", strategy="exact_gram")


@pytest.mark.parametrize("kernels", [None, "interpret", "ref"])
def test_main_refuses_without_tpu_or_compiled_kernels(monkeypatch, capsys,
                                                      kernels):
    if kernels is None:
        monkeypatch.delenv("REPRO_KERNELS", raising=False)
    else:
        monkeypatch.setenv("REPRO_KERNELS", kernels)
    with pytest.raises(SystemExit) as e:
        cs.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_four_chip_phases_on_four_host_devices():
    # The --chips 4 path (shard_map one-shot, stream and ranker beside
    # the single-device engine) on four forced CPU devices.
    out = run_forced_devices(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke as cs
        cs.four_chips(
            cs.paper_matrix(rows=64, cols=4096, density=5e-3, seed=2020),
            cs.rating_rows(num_blocks=4, **{dict(STREAM, batches=4)!r}),
            rank={RANK}, serve=dict(batch=16, waves=2, k_top=5, seed=7))
    """, devices=4)
    for line in ("oneshot shard_map vs single", "stream shard_map vs single",
                 "serve shard_map vs single", '"v_devices": 4'):
        assert line in out, out


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_lands_in_one_place(tmp_path, from_env):
    # JAX_COMPILATION_CACHE_DIR wins when set; otherwise .jax_cache/ in
    # the checkout.  A fresh process, so no other test's JAX config leaks.
    import subprocess

    from repro import compile_cache

    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro import compile_cache\n"
        "path = compile_cache.enable()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()\n"
        "print(path, compile_cache.usage(path)['entries'])\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=300, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    path, entries = out.stdout.split()
    want = tmp_path if from_env else compile_cache.CHECKOUT_CACHE_DIR
    assert path == str(want) and int(entries) >= 1
