"""The unified front door (repro.core.api): SolveConfig validation
matrix, planner decisions against hand-computed byte estimates,
bit-identical parity between svd() and the legacy driver shims for
dense/COO/BlockEll inputs across backends, the documented key=None
determinism shared by every driver, and the new want_right capability
on the single-host and hierarchical drivers."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core as core
from repro.core import sparse, ranky, planner
from repro.core.api import (SolveConfig, SVDResult, as_block_input,
                            default_key, describe, plan, svd)
from repro.core.hierarchy import hierarchical_ranky_svd
from repro.core.planner import ASpec, PlanError
from conftest import jaxpr_traces
from repro.core.ranky import ranky_svd


def _coo(m=24, n=1024, density=0.01, seed=0):
    return sparse.ensure_full_row_rank(
        sparse.random_bipartite(m, n, density, seed=seed, weighted=True),
        seed=seed)


def _bitwise(x, y):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# SolveConfig validation matrix: every invalid combination raises with a
# message naming BOTH offending fields.
# ---------------------------------------------------------------------------

CROSS_FIELD_CASES = [
    # (kwargs, (field_a, field_b))
    (dict(undetermined_tail=True), ("undetermined_tail", "merge_mode")),
    (dict(undetermined_tail=True, merge_mode="gram"),
     ("undetermined_tail", "merge_mode")),
    (dict(undetermined_tail=True, merge_mode="proxy", rank=4),
     ("undetermined_tail", "rank")),
    (dict(undetermined_tail=True, merge_mode="proxy", backend="shard_map"),
     ("undetermined_tail", "backend")),
    (dict(undetermined_tail=True, merge_mode="proxy",
          backend="hierarchical"), ("undetermined_tail", "backend")),
    (dict(sketch=True, backend="single"), ("sketch", "backend")),
    (dict(sketch=True, backend="shard_map"), ("sketch", "backend")),
    (dict(two_level=True), ("two_level", "backend")),
    (dict(two_level=True, backend="single"), ("two_level", "backend")),
    (dict(two_level=True, backend="hierarchical"), ("two_level", "backend")),
    (dict(local_mode="svd", backend="hierarchical"),
     ("local_mode", "backend")),
    (dict(local_mode="svd", rank=3), ("local_mode", "rank")),
    (dict(local_mode="svd", use_kernel=True), ("local_mode", "use_kernel")),
]


@pytest.mark.parametrize("kwargs,fields", CROSS_FIELD_CASES)
def test_invalid_cross_field_config_names_both_fields(kwargs, fields):
    with pytest.raises(ValueError) as exc:
        SolveConfig(**kwargs)
    msg = str(exc.value)
    for f in fields:
        assert f in msg, (f, msg)


@pytest.mark.parametrize("kwargs,field", [
    (dict(method="bogus"), "method"),
    (dict(backend="bogus"), "backend"),
    (dict(local_mode="bogus"), "local_mode"),
    (dict(merge_mode="bogus"), "merge_mode"),
    (dict(rank=0), "rank"),
    (dict(oversample=-1), "oversample"),
    (dict(power_iters=-1), "power_iters"),
    (dict(num_blocks=0), "num_blocks"),
    (dict(fanout=1), "fanout"),
    (dict(memory_budget_bytes=0), "memory_budget_bytes"),
])
def test_invalid_single_field_config(kwargs, field):
    with pytest.raises(ValueError, match=field):
        SolveConfig(**kwargs)


def test_valid_legacy_default_configs_construct():
    # The exact configs the three legacy shims build from their defaults.
    SolveConfig(backend="single", merge_mode="proxy", num_blocks=8)
    SolveConfig(backend="hierarchical", num_blocks=8)
    SolveConfig(backend="shard_map")
    SolveConfig()  # the documented front-door default


# ---------------------------------------------------------------------------
# Planner: byte estimates pinned to the documented closed forms, and the
# auto rules pinned on hand-built specs.
# ---------------------------------------------------------------------------

SPEC = ASpec(m=512, n=4096, nnz=10_000, num_blocks=8)


def test_planner_byte_estimates_hand_computed():
    assert planner.exact_bytes(SPEC) == 4 * 8 * 512 * 512  # 8_388_608
    assert planner.shard_map_bytes(SPEC, "gram") == 4 * 512 * 512
    assert planner.shard_map_bytes(SPEC, "proxy") == 4 * 8 * 512 * 512
    # L = min(6 + 8, 512) = 14, W = ceil(4096 / 8) = 512
    assert planner.sketch_bytes(SPEC, rank=6, oversample=8) == \
        4 * (8 * 14 * 512 + 2 * 512 * 14)  # 286_720
    assert planner.hierarchical_bytes(SPEC, rank=6) == 4 * 8 * 512 * 6
    assert planner.hierarchical_bytes(SPEC, rank=None) == 4 * 8 * 512 * 512


def test_planner_auto_exact_when_it_fits():
    p = planner.make_plan(SPEC, SolveConfig(), device_count=1)
    assert (p.backend, p.strategy) == ("single", "exact_gram")
    assert p.estimated_peak_bytes == planner.exact_bytes(SPEC)


def test_planner_auto_rank_truncates_exact_when_small():
    p = planner.make_plan(SPEC, SolveConfig(rank=6), device_count=1)
    assert p.strategy == "exact_gram"
    assert p.truncate_to == 6 and p.rank is None


def test_planner_auto_rank_sketches_when_gram_exceeds_budget():
    cfg = SolveConfig(rank=6, memory_budget_bytes=1 << 20)  # 1 MiB < 8 MiB
    p = planner.make_plan(SPEC, cfg, device_count=1)
    assert (p.backend, p.strategy) == ("single", "randomized")
    assert any("exceeds the budget" in r for r in p.reasons)
    assert p.estimates["exact_gram"] == 8 * 512 * 512 * 4
    assert p.estimates["randomized"] == 286_720


def test_planner_auto_rank_sketches_in_tall_row_regime():
    # M > EXACT_TRUNC_MAX_M: sketch even though the default budget fits.
    tall = ASpec(m=32_768, n=4096, nnz=100_000, num_blocks=8)
    p = planner.make_plan(tall, SolveConfig(rank=16), device_count=1)
    assert p.strategy == "randomized"
    assert any("exceeds the budget" in r for r in p.reasons)  # 32 GiB gram


def test_planner_auto_exact_infeasible_raises_with_estimates():
    cfg = SolveConfig(memory_budget_bytes=1 << 20)
    with pytest.raises(PlanError) as exc:
        planner.make_plan(SPEC, cfg, device_count=1)
    msg = str(exc.value)
    assert "rank=k" in msg and "8,388,608" in msg


def test_planner_auto_shard_map_when_devices_match():
    p = planner.make_plan(SPEC, SolveConfig(), device_count=8)
    assert p.backend == "shard_map"
    assert p.estimates["shard_map"] == 4 * 512 * 512


def test_planner_auto_undetermined_tail_pins_single_proxy():
    cfg = SolveConfig(undetermined_tail=True, merge_mode="proxy")
    p = planner.make_plan(SPEC, cfg, device_count=8)
    assert (p.backend, p.strategy) == ("single", "exact_proxy")


def test_planner_auto_sketch_flag_picks_hierarchical():
    p = planner.make_plan(SPEC, SolveConfig(sketch=True, rank=6),
                          device_count=1)
    assert (p.backend, p.strategy) == ("hierarchical", "hierarchical")
    assert p.sketch_leaves


def test_planner_explicit_backend_echoed():
    p = planner.make_plan(SPEC, SolveConfig(backend="hierarchical",
                                            rank=6), device_count=1)
    assert (p.backend, p.strategy) == ("hierarchical", "hierarchical")
    assert "explicitly" in p.reasons[0]
    assert "hierarchical" in p.explain()


def test_plan_accepts_spec_or_matrix():
    p1 = plan(SPEC, SolveConfig(rank=6))
    coo = _coo()
    p2 = plan(coo, SolveConfig(rank=6, num_blocks=8))
    assert p1.strategy in ("exact_gram", "randomized")
    assert p2.spec.m == coo.shape[0] and p2.spec.nnz == coo.nnz


# ---------------------------------------------------------------------------
# Input adapter
# ---------------------------------------------------------------------------

def test_describe_all_representations():
    coo = _coo()
    dense = coo.todense()
    ell = sparse.block_ell_from_coo(coo, 8)
    for a, kind in ((dense, "dense"), (coo, "coo"), (ell, "ell")):
        spec = describe(a, 8)
        assert (spec.m, spec.n, spec.kind) == (24, 1024, kind)
        assert spec.nnz == coo.nnz


def test_as_block_input_normalizes_each_kind():
    coo = _coo()
    out = as_block_input(coo, 8)
    assert isinstance(out, sparse.BlockEll) and out.num_blocks == 8
    out_d = as_block_input(coo, 8, needs_dense=True)
    assert isinstance(out_d, jnp.ndarray) and out_d.shape[1] % 8 == 0
    a = np.ones((4, 10), np.float32)  # indivisible: padded, not rejected
    padded = as_block_input(a, 8)
    assert padded.shape == (4, 16)
    ell = sparse.block_ell_from_coo(coo, 8)
    assert as_block_input(ell, 8) is ell
    with pytest.raises(ValueError, match="num_blocks"):
        as_block_input(ell, 4)
    with pytest.raises(ValueError, match="gram-native"):
        as_block_input(ell, 8, needs_dense=True)


# ---------------------------------------------------------------------------
# Parity: svd() reproduces each legacy driver bit-identically (the shims
# and the front door share one engine per backend).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("merge_mode", ["proxy", "gram"])
def test_parity_single_backend_all_representations(merge_mode):
    coo = _coo()
    a = sparse.pad_to_block_multiple(coo.todense(), 8)
    ell = sparse.block_ell_from_coo(coo, 8)
    key = jax.random.PRNGKey(7)
    kw = dict(num_blocks=8, method="neighbor_random", merge_mode=merge_mode,
              key=key)
    cfg = SolveConfig(backend="single", **kw)
    for legacy_in, api_in in ((jnp.asarray(a), a), (ell, ell), (ell, coo)):
        u0, s0 = ranky_svd(legacy_in, **kw)
        res = svd(api_in, cfg)
        _bitwise(res.u, u0)
        _bitwise(res.s, s0)


def test_parity_single_backend_randomized():
    coo = _coo()
    ell = sparse.block_ell_from_coo(coo, 8)
    kw = dict(num_blocks=8, method="random", rank=6, oversample=32,
              power_iters=4, key=jax.random.PRNGKey(3))
    u0, s0 = ranky_svd(ell, **kw)
    res = svd(ell, SolveConfig(backend="single", **kw))
    _bitwise(res.u, u0)
    _bitwise(res.s, s0)


def test_parity_hierarchical_backend():
    coo = _coo()
    a = sparse.pad_to_block_multiple(coo.todense(), 8)
    ell = sparse.block_ell_from_coo(coo, 8)
    key = jax.random.PRNGKey(5)
    for sketch in (False, True):
        kw = dict(num_blocks=8, fanout=2, rank=6, method="random",
                  sketch=sketch, oversample=32, power_iters=4, key=key)
        cfg = SolveConfig(backend="hierarchical", **kw)
        for legacy_in, api_in in ((jnp.asarray(a), a), (ell, ell),
                                  (ell, coo)):
            u0, s0 = hierarchical_ranky_svd(legacy_in, **kw)
            res = svd(api_in, cfg)
            _bitwise(res.u, u0)
            _bitwise(res.s, s0)


from conftest import run_forced_devices as run_py  # noqa: E402


def test_parity_shard_map_backend_8_devices():
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import sparse
        from repro.core.api import SolveConfig, svd
        from repro.core.distributed import distributed_ranky_svd
        coo = sparse.ensure_full_row_rank(
            sparse.random_bipartite(16, 2048, 0.004, seed=3), seed=3)
        a = sparse.pad_to_block_multiple(coo.todense(), 8)
        ell = sparse.block_ell_from_coo(coo, 8)
        mesh = jax.make_mesh((8,), ("model",))
        key = jax.random.PRNGKey(11)
        kw = dict(method="neighbor_random", merge_mode="gram",
                  want_right=True, key=key)
        cfg = SolveConfig(backend="shard_map", **kw)
        for legacy_in, api_in in ((jnp.asarray(a), a), (ell, ell),
                                  (ell, coo)):
            u0, s0, v0 = distributed_ranky_svd(
                legacy_in, mesh, block_axes=("model",), **kw)
            res = svd(api_in, cfg, mesh=mesh, block_axes=("model",))
            np.testing.assert_array_equal(np.asarray(res.u), np.asarray(u0))
            np.testing.assert_array_equal(np.asarray(res.s), np.asarray(s0))
            # api trims V back to the original N columns
            np.testing.assert_array_equal(
                np.asarray(res.v), np.asarray(v0)[:coo.shape[1]])
            assert res.plan.backend == "shard_map"
        # auto + small rank on a mesh: exact-then-truncate runs the
        # EXACT shard_map engine (not the sketch) and slices top-k.
        res = svd(ell, SolveConfig(method="none", merge_mode="gram",
                                   rank=6, key=key), mesh=mesh)
        assert res.plan.backend == "shard_map"
        assert res.plan.truncate_to == 6 and res.plan.rank is None
        u0, s0 = distributed_ranky_svd(ell, mesh, block_axes=("model",),
                                       method="none", merge_mode="gram",
                                       key=key)
        np.testing.assert_array_equal(np.asarray(res.s),
                                      np.asarray(s0)[:6])
        print("OK")
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# The acceptance case: auto picks the randomized plan for a tall solve
# whose gram stack exceeds the budget, and the result explains why.
# ---------------------------------------------------------------------------

def test_auto_backend_sketches_tall_case_and_explains():
    # Tall-ish: M=512, D=8 -> exact gram stack 8*512^2*4 = 8 MiB > the
    # 1 MiB budget, while the sketch (L=38, W=256) needs only
    # 4*(8*38*256 + 2*512*38) = 466,944 B and fits.
    coo = _coo(m=512, n=2048, density=0.01, seed=2)
    ell = sparse.block_ell_from_coo(coo, 8)
    cfg = SolveConfig(method="random", rank=6, oversample=32, power_iters=4,
                      memory_budget_bytes=1 << 20)
    res = svd(ell, cfg)
    assert res.plan.strategy == "randomized"
    assert res.plan.estimates["exact_gram"] == 8_388_608
    assert res.plan.estimates["randomized"] == 466_944
    assert any("exceeds the budget" in r for r in res.plan.reasons)
    assert res.diagnostics.strategy == "randomized"
    assert res.diagnostics.estimated_peak_bytes == res.plan.peak_bytes
    if res.plan.backend == "single":
        assert res.plan.peak_bytes == res.plan.estimates["randomized"]
        # ... and the result matches the explicitly-requested sketch
        # bitwise.
        u0, s0 = ranky_svd(ell, num_blocks=8, method="random", rank=6,
                           oversample=32, power_iters=4)
    else:
        # One device per column block available (e.g. the CI's 8 forced
        # host devices): auto runs the SAME sketch under shard_map and
        # the peak is the smaller per-device form.
        assert res.plan.backend == "shard_map"
        assert res.plan.peak_bytes < res.plan.estimates["randomized"]
        s0 = svd(ell, dataclasses.replace(cfg, backend="shard_map")).s
    _bitwise(res.s, s0)


def test_planner_auto_rank_prefers_exact_when_sketch_does_not_fit():
    # Extremely fat blocks (W = 4_194_304/8 = 524_288) make the D*L*W
    # sketch term (638,779,392 B at L=38) outgrow even an M=4096 gram
    # stack (536,870,912 B).  With a budget between the two, the
    # planner must notice and solve exactly + truncate.
    spec = ASpec(m=4096, n=4_194_304, nnz=100_000, num_blocks=8)
    cfg = SolveConfig(rank=6, oversample=32, method="random",
                      memory_budget_bytes=550_000_000)
    assert planner.sketch_bytes(spec, 6, 32) == 638_779_392
    assert planner.exact_bytes(spec) == 536_870_912
    p = planner.make_plan(spec, cfg, device_count=1)
    assert p.strategy == "exact_gram" and p.truncate_to == 6
    assert any("sketch estimate" in r for r in p.reasons)


def test_planner_auto_rank_degrades_honestly_when_nothing_fits():
    spec = ASpec(m=4096, n=4_194_304, nnz=100_000, num_blocks=8)
    cfg = SolveConfig(rank=6, oversample=32, method="random",
                      memory_budget_bytes=100_000_000)  # < gram < sketch
    p = planner.make_plan(spec, cfg, device_count=1)
    assert p.strategy == "exact_gram" and p.truncate_to == 6
    assert any("NO strategy fits" in r for r in p.reasons)


def test_plan_peak_bytes_is_per_device_for_shard_map():
    spec = ASpec(m=16_384, n=65_536, nnz=100_000, num_blocks=8)
    p = planner.make_plan(spec, SolveConfig(), device_count=8)
    assert p.backend == "shard_map"
    # per-device psum buffer, NOT the 8 GiB single-host gram stack
    assert p.estimated_peak_bytes == 4 * 16_384 * 16_384
    assert p.estimated_peak_bytes <= p.budget


def test_result_diagnostics_and_unpacking():
    coo = _coo()
    ell = sparse.block_ell_from_coo(coo, 8)
    res = svd(ell, SolveConfig(backend="single", method="neighbor_random",
                               num_blocks=8, merge_mode="gram"))
    assert isinstance(res, SVDResult)
    assert len(res.diagnostics.lonely_rows_per_block) == 8
    assert res.diagnostics.lonely_rows == \
        sum(res.diagnostics.lonely_rows_per_block)
    # neighbor_random repairs every lonely row
    assert res.diagnostics.repaired_rows == res.diagnostics.lonely_rows
    assert res.diagnostics.wall_time_s > 0
    u, s = res
    _bitwise(u, res.u)
    _bitwise(s, res.s)


@pytest.mark.parametrize("as_ell", [False, True], ids=["coo", "ell"])
def test_svd_lonely_counts_match_host_count_without_retrace(as_ell):
    """api.svd of a COO (or of its BlockEll) counts lonely (block, row)
    pairs as a host bincount over the COO does, repairs each of them
    under neighbor_random, and a second call with the same shapes runs no
    jaxpr trace (every program, the count's included, is reused)."""
    coo = _coo(m=32, n=1024, density=0.01, seed=3)
    d, (m, n) = 8, coo.shape
    a = sparse.block_ell_from_coo(coo, d) if as_ell else coo
    cfg = SolveConfig(backend="single", method="neighbor_random",
                      num_blocks=d, merge_mode="gram")
    first = svd(a, cfg)
    blk = coo.cols // sparse.block_width(n, d)
    hits = np.bincount(blk * m + coo.rows, weights=coo.vals != 0,
                       minlength=d * m).reshape(d, m)
    want = tuple(int(x) for x in (hits == 0).sum(axis=1))
    assert sum(c > 0 for c in want) >= 2
    with jaxpr_traces() as traces:
        res = svd(a, cfg)
    assert traces[0] == 0
    for diag in (first.diagnostics, res.diagnostics):
        assert diag.lonely_rows_per_block == want
        assert diag.lonely_rows == sum(want)
        assert diag.repaired_rows == diag.lonely_rows


def test_diagnostics_neighbor_counts_partial_repairs():
    coo = _coo(seed=5)
    ell = sparse.block_ell_from_coo(coo, 8)
    res = svd(ell, SolveConfig(backend="single", method="neighbor",
                               num_blocks=8, merge_mode="gram"))
    rep = ranky.split_and_repair(ell, 8, "neighbor", default_key())
    assert res.diagnostics.repaired_rows == \
        int(np.asarray(rep.repair_mask).sum())
    assert res.diagnostics.repaired_rows <= res.diagnostics.lonely_rows


# ---------------------------------------------------------------------------
# key=None determinism: one documented default key across all drivers
# ---------------------------------------------------------------------------

def test_default_key_is_documented_prngkey_zero():
    _bitwise(default_key(), jax.random.PRNGKey(0))
    assert ranky.DEFAULT_SEED == 0


def test_key_none_matches_default_key_across_drivers():
    coo = _coo()
    a = jnp.asarray(sparse.pad_to_block_multiple(coo.todense(), 8))
    mesh = jax.make_mesh((jax.device_count(),), ("blocks",))
    a1 = jnp.asarray(sparse.pad_to_block_multiple(
        coo.todense(), jax.device_count()))
    drivers = [
        lambda k: ranky_svd(a, num_blocks=8, method="random",
                            merge_mode="gram", key=k),
        lambda k: hierarchical_ranky_svd(a, num_blocks=8, fanout=2,
                                         method="random", key=k),
        lambda k: core.distributed_ranky_svd(
            a1, mesh, block_axes=("blocks",), method="random",
            merge_mode="gram", key=k),
        lambda k: tuple(svd(a, SolveConfig(
            backend="single", num_blocks=8, method="random",
            merge_mode="gram", key=k))),
        lambda k: ranky_svd(a, num_blocks=8, method="random",
                            merge_mode="gram", rank=6, key=k),
    ]
    for fn in drivers:
        got_none = fn(None)
        got_default = fn(default_key())
        got_zero = fn(jax.random.PRNGKey(0))
        for x, y, z in zip(got_none, got_default, got_zero):
            _bitwise(x, y)
            _bitwise(x, z)


# ---------------------------------------------------------------------------
# want_right on the previously left-only drivers (capability matrix now
# rectangular)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("container", ["dense", "ell"])
@pytest.mark.parametrize("merge_mode", ["proxy", "gram"])
def test_ranky_svd_want_right_reconstructs(container, merge_mode):
    coo = _coo(seed=3)
    a = sparse.pad_to_block_multiple(coo.todense(), 8)
    inp = (jnp.asarray(a) if container == "dense"
           else sparse.block_ell_from_coo(coo, 8))
    u, s, v = ranky_svd(inp, num_blocks=8, method="none",
                        merge_mode=merge_mode, want_right=True)
    recon = np.asarray(u) * np.asarray(s) @ np.asarray(v).T
    assert np.abs(recon - a).max() < 5e-3


@pytest.mark.parametrize("container", ["dense", "ell"])
def test_hierarchical_want_right_reconstructs(container):
    coo = _coo(seed=4)
    a = sparse.pad_to_block_multiple(coo.todense(), 8)
    inp = (jnp.asarray(a) if container == "dense"
           else sparse.block_ell_from_coo(coo, 8))
    u, s, v = hierarchical_ranky_svd(inp, num_blocks=8, fanout=2,
                                     method="none", want_right=True)
    recon = np.asarray(u) * np.asarray(s) @ np.asarray(v).T
    assert np.abs(recon - a).max() < 5e-3


def test_hierarchical_truncated_want_right_quasi_optimal():
    rng = np.random.default_rng(0)
    lo = (rng.standard_normal((16, 4)) @ rng.standard_normal((4, 512))) \
        .astype(np.float32)
    a = sparse.pad_to_block_multiple(lo, 8)
    u, s, v = hierarchical_ranky_svd(jnp.asarray(a), num_blocks=8,
                                     fanout=2, rank=6, method="none",
                                     want_right=True)
    recon = np.asarray(u) * np.asarray(s) @ np.asarray(v).T
    assert np.abs(recon - a).max() < 1e-2  # rank(A)=4 <= 6: exact


def test_ranky_svd_want_right_randomized_path():
    coo = _coo(seed=6)
    ell = sparse.block_ell_from_coo(coo, 8)
    a = sparse.pad_to_block_multiple(coo.todense(), 8)
    u, s, v = ranky_svd(ell, num_blocks=8, method="none", rank=6,
                        oversample=32, power_iters=4, want_right=True)
    s_full = np.linalg.svd(a, compute_uv=False)
    recon = np.asarray(u) * np.asarray(s) @ np.asarray(v).T
    assert np.linalg.norm(a - recon, 2) <= s_full[6] * 1.02


# ---------------------------------------------------------------------------
# Public surface
# ---------------------------------------------------------------------------

def test_core_all_exports_resolve():
    for name in core.__all__:
        assert hasattr(core, name), name
    for name in ("hierarchical_ranky_svd", "randomized", "SolveConfig",
                 "SVDResult", "plan", "api", "default_key"):
        assert name in core.__all__, name
    # repro.core.svd stays the local-SVD-primitives MODULE (the solver
    # function is repro.core.api.svd) — pinned because rebinding it
    # breaks `from repro.core import svd as lsvd` everywhere.
    assert hasattr(core.svd, "local_svd_exact")
    assert callable(core.api.svd)


def test_mesh_with_non_shard_map_backend_rejected():
    coo = _coo()
    mesh = jax.make_mesh((jax.device_count(),), ("blocks",))
    with pytest.raises(ValueError, match="backend"):
        svd(coo, SolveConfig(backend="single", num_blocks=8), mesh=mesh)


def test_rank_exceeding_m_rejected():
    coo = _coo()
    with pytest.raises(ValueError, match="rank"):
        svd(coo, SolveConfig(backend="single", num_blocks=8, rank=25))


# ---------------------------------------------------------------------------
# Falkon-style measured-memory checks for the ONE-SHOT R1-R4 engines:
# the compiled executable's actual peak must stay within strategy bytes
# + solve_repair_bytes (the split-and-repair transient these
# measurements surfaced — and the economy proxy-merge SVD they forced).
# Lowered from avals: no data materialized.
# ---------------------------------------------------------------------------

def _solve_single_temp_bytes(**engine_kw):
    aval = jax.ShapeDtypeStruct((SPEC.m, SPEC.n), jnp.float32)
    stats = ranky.solve_single.lower(
        aval, num_blocks=SPEC.num_blocks,
        **engine_kw).compile().memory_analysis()
    if stats is None:                                 # pragma: no cover
        pytest.skip("backend exposes no compiled memory analysis")
    return int(stats.temp_size_in_bytes)


def test_r4_exact_gram_measured_peak(memory_checker):
    """R4 single-host exact: the (D, M, M) gram stack plus the
    split-and-repair transient (measured ratio ~1.00002 on CPU)."""
    measured = _solve_single_temp_bytes(merge_mode="gram")
    memory_checker.check_value(
        measured,
        planner.exact_bytes(SPEC) + planner.solve_repair_bytes(SPEC),
        label="R4 exact_gram one-shot temp")


def test_r1_proxy_measured_peak_stays_economy(memory_checker):
    """R1 single/proxy (undetermined_tail's home): same budget as the
    gram merge.  Regression for the economy proxy-merge SVD — with
    full_matrices=True the merge allocated a discarded (D*M, D*M)
    right-vector buffer that measured 3x this budget."""
    measured = _solve_single_temp_bytes(
        merge_mode="proxy", local_mode="gram", undetermined_tail=True)
    memory_checker.check_value(
        measured,
        planner.exact_bytes(SPEC) + planner.solve_repair_bytes(SPEC),
        label="R1 exact_proxy one-shot temp")


def test_r3_randomized_measured_peak(memory_checker):
    """R3 sketch: the sketch working set + the repair transient + the
    repaired (D, M, W) block stack that stays live as the sketch's
    input (the term the gram paths fold into their own stack)."""
    measured = _solve_single_temp_bytes(rank=6)
    blocks_live = planner.BYTES_F32 * SPEC.m * SPEC.num_blocks * SPEC.width
    memory_checker.check_value(
        measured,
        planner.sketch_bytes(SPEC, 6, 8)
        + planner.solve_repair_bytes(SPEC) + blocks_live,
        label="R3 randomized one-shot temp")


def test_r4_shard_map_measured_peak_subprocess(memory_checker):
    """R4 distributed exact: per-device peak = one (M, M) psum gram
    plus the per-device repair transient (8 forced host devices)."""
    out = run_py("""
        from functools import partial
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.compat import shard_map_nocheck as shard_map
        from repro.core import distributed as dist, planner
        from repro.core.planner import ASpec

        m, n, d = 512, 4096, 8
        spec = ASpec(m=m, n=n, nnz=m * n, num_blocks=d)
        mesh = jax.make_mesh((d,), ("model",))
        fn = partial(dist._svd_shard_fn, axes=("model",),
                     method="neighbor_random", local_mode="gram",
                     merge_mode="gram", hierarchical=False,
                     use_kernel=False, want_right=False, rank=None,
                     oversample=8, power_iters=2)
        sharded = jax.jit(shard_map(fn, mesh=mesh,
                                    in_specs=(P(None, "model"), P()),
                                    out_specs=(P(), P())))
        key = jax.random.PRNGKey(0)
        args = (jax.ShapeDtypeStruct(
                    (m, n), jnp.float32,
                    sharding=NamedSharding(mesh, P(None, "model"))),
                jax.ShapeDtypeStruct(
                    key.shape, key.dtype,
                    sharding=NamedSharding(mesh, P())))
        stats = sharded.lower(*args).compile().memory_analysis()
        budget = (planner.shard_map_bytes(spec, "gram")
                  + planner.stream_repair_bytes_per_device(spec))
        print("MEASURED", int(stats.temp_size_in_bytes), budget)
    """)
    measured, budget = (int(x) for x in out.split("MEASURED")[1].split())
    memory_checker.check_value(measured, budget,
                               label="R4 shard_map per-device temp")


# ---------------------------------------------------------------------------
# Planner rule R7: serving bytes pinned to hand-computed closed forms,
# and the decision/degrade narration
# ---------------------------------------------------------------------------

def test_r7_byte_estimates_hand_computed():
    from repro.core.api import ServeTopKConfig
    assert planner.serve_factor_bytes(4096, 16) == 4 * 4096 * 16
    assert planner.serve_factor_bytes(4096, 16, quantized=True) == \
        4096 * 16 + 4 * 4096
    # B=32, k=16, k_top=10, block_n=512:
    #   queries 32*16, score tile 32*512, running pair 2*32*10,
    #   merge candidates 2*32*(10+512)
    assert planner.serve_fused_bytes(32, 16, 10, 512) == \
        4 * 32 * (16 + 512 + 2 * 10 + 2 * (10 + 512))
    assert planner.serve_fallback_bytes(32, 16, 4096, 10) == \
        4 * 32 * (16 + 4096 + 2 * 10)
    # Fused total is N-independent in everything but the factors
    one_m = planner.serving_bytes(1_000_000, 16, 32, 10)
    assert one_m == planner.serve_factor_bytes(1_000_000, 16) + \
        planner.serve_fused_bytes(32, 16, 10, 512)
    # Sharded per-device: (W, k) slice + working set + (B, D*k_top)
    # all-gathered candidate pair
    per_dev = planner.serving_bytes(4096, 16, 32, 10, num_blocks=8,
                                    per_device=True)
    assert per_dev == planner.serve_factor_bytes(512, 16) + \
        planner.serve_fused_bytes(32, 16, 10, 512) + 2 * 4 * 32 * 8 * 10


def test_r7_plan_auto_degrades_to_single_on_device_mismatch():
    from repro.core.api import ServeTopKConfig
    cfg = ServeTopKConfig(num_blocks=8, serve_backend="shard_map")
    p = planner.make_serve_plan(4096, 16, cfg, device_count=1)
    assert p.backend == "single" and p.strategy == "serve_fused"
    assert any("degrading to the single-device ranker" in r
               for r in p.reasons)
    assert p.peak_bytes == planner.serving_bytes(
        4096, 16, cfg.batch_size, cfg.k_top, num_blocks=8)


def test_r7_plan_fallback_strategy_and_over_budget_reason():
    from repro.core.api import ServeTopKConfig
    cfg = ServeTopKConfig(num_blocks=1, use_kernel=False,
                          serve_backend="single",
                          memory_budget_bytes=1 << 20)
    p = planner.make_serve_plan(1_000_000, 16, cfg, device_count=1)
    assert p.strategy == "serve_fallback"
    assert p.peak_bytes == planner.serving_bytes(
        1_000_000, 16, cfg.batch_size, cfg.k_top, fused=False)
    assert any("EXCEEDS budget" in r for r in p.reasons)
    assert any("quantize=True" in r for r in p.reasons)


def test_r7_plan_sharded_quantized_per_device_peak():
    from repro.core.api import ServeTopKConfig
    cfg = ServeTopKConfig(num_blocks=8, quantize=True,
                          serve_backend="auto")
    p = planner.make_serve_plan(4096, 16, cfg, device_count=8)
    assert p.backend == "shard_map" and p.strategy == "serve_fused"
    assert p.peak_bytes == p.estimates["serve_fused_per_device"]
    assert p.peak_bytes == planner.serving_bytes(
        4096, 16, cfg.batch_size, cfg.k_top, num_blocks=8,
        quantized=True, per_device=True)
    assert any("all-gathers" in r for r in p.reasons)
