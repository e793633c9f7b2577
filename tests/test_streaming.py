"""The streaming SVD subsystem (repro.stream + the api.svd_update /
svd_stream front door): config validation, the R5/R5d planner rules
pinned against hand-computed byte estimates, pytree registration,
equivalence of streaming over B batches with a one-shot svd() on the
concatenated matrix (singular values AND the U subspace) for
dense/COO/BlockEll deltas, the rank-problem streaming edition (a
rank-deficient batch that requires repair before the truncated
factorization), history decay, bit-identical checkpoint
save -> restore -> svd_update resume, the shard_map ingest engine
(stream_backend="shard_map": sharded-v merge matching the single-host
result, exercised in-process when 8 devices are forced and via a
subprocess otherwise), and checkpoint portability across device counts
(save sharded on 8, restore on 1, and vice versa)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint.ckpt import Checkpointer, tree_signature
from repro.core import planner, ranky, sparse
from repro.core import svd as lsvd
from repro.core.api import (ASpec, SolveConfig, plan_update, svd, svd_init,
                            svd_stream, svd_update)
from repro.stream import StreamingSVDState, init_state

RANK = 24

from conftest import run_forced_devices

eight_devices = pytest.mark.skipif(
    jax.device_count() != 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the CI streaming leg forces it; the subprocess twin covers "
           "single-device runs)")


def _spectrum_matrix(m=32, n=96, seed=0):
    """Dense (m, n) float32 matrix with a known, well-separated
    spectrum — the U-subspace comparisons need clean gaps."""
    rng = np.random.default_rng(seed)
    u0, _ = np.linalg.qr(rng.standard_normal((m, m)))
    v0, _ = np.linalg.qr(rng.standard_normal((n, m)))
    svals = np.geomspace(20.0, 0.5, m)
    return ((u0 * svals) @ v0.T).astype(np.float32)


def _dense_to_coo(a: np.ndarray) -> sparse.COOMatrix:
    r, c = np.nonzero(a)
    return sparse.COOMatrix(rows=r.astype(np.int32), cols=c.astype(np.int32),
                            vals=a[r, c].astype(np.float32), shape=a.shape)


def _row_batches(a: np.ndarray, num_batches: int, kind: str, d: int):
    """Split a dense matrix row-wise into num_batches deltas of the
    requested representation."""
    mb = a.shape[0] // num_batches
    out = []
    for i in range(num_batches):
        rows = a[i * mb:(i + 1) * mb]
        if kind == "dense":
            out.append(rows)
        else:
            coo = _dense_to_coo(rows)
            out.append(coo if kind == "coo"
                       else sparse.block_ell_from_coo(coo, d))
    return out


def _sparse_coo(m=24, n=256, density=0.02, seed=3):
    return sparse.ensure_full_row_rank(
        sparse.random_bipartite(m, n, density, seed=seed, weighted=True),
        seed=seed)


def _coo_row_slice(coo: sparse.COOMatrix, lo: int, hi: int,
                   n: int) -> sparse.COOMatrix:
    sel = (coo.rows >= lo) & (coo.rows < hi)
    return sparse.COOMatrix(rows=(coo.rows[sel] - lo).astype(np.int32),
                            cols=coo.cols[sel], vals=coo.vals[sel],
                            shape=(hi - lo, n))


# ---------------------------------------------------------------------------
# SolveConfig: the new streaming knobs validate like every other knob
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs,field", [
    (dict(truncate_rank=0), "truncate_rank"),
    (dict(truncate_rank=-3), "truncate_rank"),
    (dict(history_decay=0.0), "history_decay"),
    (dict(history_decay=1.5), "history_decay"),
    (dict(history_decay=-0.1), "history_decay"),
])
def test_invalid_streaming_single_field_config(kwargs, field):
    with pytest.raises(ValueError, match=field):
        SolveConfig(**kwargs)


@pytest.mark.parametrize("kwargs,fields", [
    (dict(truncate_rank=8, undetermined_tail=True, merge_mode="proxy"),
     ("truncate_rank", "undetermined_tail")),
    (dict(history_decay=0.9), ("history_decay", "truncate_rank")),
])
def test_invalid_streaming_cross_field_config(kwargs, fields):
    with pytest.raises(ValueError) as exc:
        SolveConfig(**kwargs)
    for f in fields:
        assert f in str(exc.value), (f, str(exc.value))


def test_svd_update_requires_truncate_rank_and_single_backend():
    state = init_state(64, num_blocks=4)
    with pytest.raises(ValueError, match="truncate_rank"):
        svd_update(state, np.ones((2, 64), np.float32), SolveConfig())
    with pytest.raises(ValueError, match="backend"):
        svd_update(state, np.ones((2, 64), np.float32),
                   SolveConfig(truncate_rank=4, backend="shard_map"))
    with pytest.raises(TypeError, match="StreamingSVDState"):
        svd_update(np.ones((2, 2)), np.ones((2, 64), np.float32),
                   SolveConfig(truncate_rank=4))
    # local_mode/merge_mode never apply to the streaming path — the
    # plan must not misreport a mode that never ran.
    with pytest.raises(ValueError, match="local_mode"):
        svd_update(state, np.ones((2, 64), np.float32),
                   SolveConfig(truncate_rank=4, local_mode="svd"))
    with pytest.raises(ValueError, match="merge_mode"):
        svd_update(state, np.ones((2, 64), np.float32),
                   SolveConfig(truncate_rank=4, merge_mode="proxy"))


def test_delta_universe_mismatches_rejected():
    cfg = SolveConfig(truncate_rank=4, num_blocks=4)
    state = svd_init(64, cfg)
    with pytest.raises(ValueError, match="universe"):
        svd_update(state, np.ones((2, 32), np.float32), cfg)
    wrong_d = sparse.block_ell_from_coo(
        _dense_to_coo(np.ones((2, 64), np.float32)), 8)
    with pytest.raises(ValueError, match="num_blocks"):
        svd_update(state, wrong_d, cfg)
    with pytest.raises(ValueError, match="num_blocks"):
        svd_update(state, np.ones((2, 64), np.float32),
                   SolveConfig(truncate_rank=4, num_blocks=8))


# ---------------------------------------------------------------------------
# Planner rule R5: byte estimates pinned to the documented closed form
# ---------------------------------------------------------------------------

BATCH_SPEC = ASpec(m=64, n=4096, nnz=5_000, num_blocks=8)  # W = 512


def test_r5_byte_estimates_hand_computed():
    # l_b = min(16 + 8, 64) = 24; N_pad = 8 * 512 = 4096
    assert planner.stream_panel_width(16, 8, 64) == 24
    assert planner.stream_panel_width(16, 8, 10) == 10
    # merge: 4 * 2 * 4096 * (16 + 24) = 1_310_720
    assert planner.stream_merge_bytes(BATCH_SPEC, 16, 8) == 1_310_720
    # repair transient: 4 * 2 * 64 * 4096 = 2_097_152
    assert planner.stream_repair_bytes(BATCH_SPEC) == 2_097_152
    # exact batch term: 4 * 8 * 64 * 64 = 131_072
    assert planner.streaming_bytes(BATCH_SPEC, 16, 8, exact=True) == \
        131_072 + 2_097_152 + 1_310_720
    # sketch batch term at the rank the engine actually runs (r_b = l_b
    # = 24, internal width L = min(24 + 8, 64) = 32):
    # 4 * (8*32*512 + 2*64*32) = 540_672
    assert planner.streaming_bytes(BATCH_SPEC, 16, 8, exact=False) == \
        540_672 + 2_097_152 + 1_310_720
    # explicitly forced batch rank 12: L = min(12 + 8, 64) = 20, merge
    # panel (N_pad, 16 + 12): 4*(8*20*512 + 2*64*20) + 4*2*4096*28
    assert planner.streaming_bytes(BATCH_SPEC, 16, 8, exact=False,
                                   batch_rank=12) == \
        4 * (8 * 20 * 512 + 2 * 64 * 20) + 2_097_152 + 4 * 2 * 4096 * 28


def test_r5_peak_independent_of_rows_seen():
    # Same batch spec -> same estimate, no matter how much was ingested:
    # the closed form has no rows-seen term at all (that is the point).
    cfg = SolveConfig(truncate_rank=16)
    p = planner.make_stream_plan(BATCH_SPEC, cfg)
    assert p.strategy == "streaming"
    assert p.backend == "single"
    assert p.rank is None  # exact batch factorization fits comfortably
    assert p.peak_bytes == 131_072 + 2_097_152 + 1_310_720
    assert "independent of rows already ingested" in " ".join(p.reasons)


def test_r5_tall_batch_picks_sketch():
    tall = ASpec(m=1_000_000, n=4096, nnz=10_000_000, num_blocks=8)
    p = planner.make_stream_plan(tall, SolveConfig(truncate_rank=16))
    assert p.rank == planner.stream_panel_width(16, 8, 1_000_000)  # sketch
    assert p.estimates["stream_sketch"] == p.peak_bytes


def test_r5_explicit_rank_forces_sketch():
    p = planner.make_stream_plan(
        BATCH_SPEC, SolveConfig(truncate_rank=16, rank=12))
    assert p.rank == 12
    assert any("explicitly" in r for r in p.reasons)
    # The estimate tracks the forced rank, not the default l_b.
    assert p.peak_bytes == planner.streaming_bytes(
        BATCH_SPEC, 16, 8, exact=False, batch_rank=12)


def test_oneshot_svd_rejects_streaming_knobs():
    a = _spectrum_matrix(m=16, n=96)
    with pytest.raises(ValueError, match="truncate_rank"):
        svd(a, SolveConfig(truncate_rank=8, num_blocks=4))
    from repro.core.api import plan
    with pytest.raises(ValueError, match="truncate_rank"):
        plan(ASpec(m=16, n=96, nnz=100, num_blocks=4),
             SolveConfig(truncate_rank=8))


def test_r5_degrades_honestly_when_nothing_fits():
    p = planner.make_stream_plan(
        BATCH_SPEC, SolveConfig(truncate_rank=16, memory_budget_bytes=1))
    assert p.rank is None  # exact is the cheaper of the two here
    assert any("NO batch factorization fits" in r for r in p.reasons)


def test_plan_update_from_spec_and_from_delta():
    cfg = SolveConfig(truncate_rank=16)
    p = plan_update(BATCH_SPEC, cfg)
    assert p.strategy == "streaming"
    state = svd_init(64, SolveConfig(truncate_rank=4, num_blocks=4))
    p2 = plan_update(np.ones((8, 64), np.float32),
                     SolveConfig(truncate_rank=4), state=state)
    assert p2.spec.m == 8 and p2.spec.num_blocks == 4
    with pytest.raises(ValueError, match="state"):
        plan_update(np.ones((8, 64), np.float32),
                    SolveConfig(truncate_rank=4))


# ---------------------------------------------------------------------------
# Pytree registration (BlockEll + StreamingSVDState)
# ---------------------------------------------------------------------------

def test_block_ell_is_a_registered_pytree():
    ell = sparse.block_ell_from_coo(_sparse_coo(), 4)
    leaves, treedef = jax.tree.flatten(ell)
    assert len(leaves) == 3  # col_ids, col_rows, col_vals
    back = jax.tree.unflatten(treedef, leaves)
    assert isinstance(back, sparse.BlockEll)
    assert (back.m, back.width, back.n) == (ell.m, ell.width, ell.n)
    doubled = jax.tree.map(lambda x: x * 2, ell)
    np.testing.assert_array_equal(np.asarray(doubled.col_vals),
                                  np.asarray(ell.col_vals) * 2)


def test_streaming_state_is_a_registered_pytree():
    cfg = SolveConfig(method="none", truncate_rank=8, num_blocks=4)
    state = svd_update(svd_init(96, cfg),
                       _spectrum_matrix()[:8], cfg).state
    leaves, treedef = jax.tree.flatten(state)
    assert len(leaves) == 4  # u, s, v, key
    back = jax.tree.unflatten(treedef, leaves)
    assert isinstance(back, StreamingSVDState)
    assert back.rows_seen == state.rows_seen == 8
    assert back.batches_seen == 1 and back.n == 96


# ---------------------------------------------------------------------------
# Equivalence: streaming over B batches == one-shot svd() on the
# concatenation, for all three delta representations
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "coo", "ell"])
def test_stream_matches_oneshot_spectrum_matrix(kind):
    """4 batches of a known-spectrum matrix: singular values within
    1e-3 relative (acceptance bar; actual ~1e-6) and the top-U subspace
    aligned with the one-shot solve."""
    d, b = 4, 4
    a = _spectrum_matrix(m=32, n=96)
    cfg = SolveConfig(method="none", truncate_rank=RANK, oversample=8,
                      num_blocks=d)
    res = svd_stream(_row_batches(a, b, kind, d), cfg)
    state = res.state
    assert state.rows_seen == 32 and state.batches_seen == b
    assert state.rank == RANK

    oracle = svd(a, SolveConfig(method="none", num_blocks=d,
                                backend="single", merge_mode="gram"))
    s_true = np.asarray(oracle.s)[:RANK]
    assert np.abs(np.asarray(res.s) - s_true).max() <= 1e-3 * s_true[0]

    # U subspace: principal angles between the streamed and one-shot
    # top-j left subspaces (j where the constructed spectrum has gaps).
    j = 8
    c = np.linalg.svd(np.asarray(res.u)[:, :j].T @ np.asarray(oracle.u)[:, :j],
                      compute_uv=False)
    assert c.min() > 1.0 - 1e-4, f"subspace angle too wide: cos={c.min()}"


@pytest.mark.parametrize("kind", ["dense", "coo", "ell"])
def test_stream_matches_oneshot_sparse_bipartite(kind):
    """Paper-shaped sparse data, 4 batches, full retained rank: the
    stream reproduces the one-shot spectrum of the concatenation."""
    d, b, n = 4, 4, 256
    coo = _sparse_coo(m=24, n=n)
    dense = coo.todense()
    batches = []
    for i in range(b):
        c = _coo_row_slice(coo, 6 * i, 6 * i + 6, n)
        batches.append(c.todense() if kind == "dense" else
                       c if kind == "coo" else
                       sparse.block_ell_from_coo(c, d))
    cfg = SolveConfig(method="none", truncate_rank=24, num_blocks=d)
    res = svd_stream(batches, cfg)
    s_true = np.linalg.svd(dense, compute_uv=False)
    assert np.abs(np.asarray(res.s) - s_true[:24]).max() <= 1e-3 * s_true[0]
    # Full reconstruction through the trimmed right vectors.
    resv = svd_stream(batches, cfg, **{})  # fresh stream
    state = resv.state
    recon = np.asarray(state.u) * np.asarray(state.s) @ \
        np.asarray(state.trimmed_v()).T
    assert np.abs(recon - dense).max() <= 1e-3 * s_true[0]


def test_svd_stream_equals_svd_update_loop():
    d = 4
    a = _spectrum_matrix(m=32, n=96, seed=5)
    cfg = SolveConfig(method="none", truncate_rank=16, num_blocks=d)
    batches = _row_batches(a, 4, "dense", d)
    res = svd_stream(batches, cfg)
    state = svd_init(96, cfg)
    for delta in batches:
        r = svd_update(state, delta, cfg)
        state = r.state
    np.testing.assert_array_equal(np.asarray(res.s), np.asarray(state.s))
    np.testing.assert_array_equal(np.asarray(res.u), np.asarray(state.u))
    # svd_stream's final diagnostics are cumulative over the stream.
    assert res.diagnostics.lonely_rows == state.lonely_rows_seen
    assert res.diagnostics.repaired_rows == state.repaired_rows_seen
    # ... but a RESUMED stream counts only its own batches.
    resumed = svd_stream(batches[2:], cfg,
                         state=svd_stream(batches[:2], cfg).state)
    assert resumed.diagnostics.lonely_rows == \
        state.lonely_rows_seen - svd_stream(batches[:2], cfg).state.lonely_rows_seen


def test_unkeyed_streams_are_deterministic():
    coo = _sparse_coo()
    cfg = SolveConfig(method="random", truncate_rank=12, num_blocks=4)
    batches = [_coo_row_slice(coo, 6 * i, 6 * i + 6, 256) for i in range(4)]
    s1 = svd_stream(batches, cfg).state
    s2 = svd_stream(batches, cfg).state
    for f in ("u", "s", "v"):
        np.testing.assert_array_equal(np.asarray(getattr(s1, f)),
                                      np.asarray(getattr(s2, f)))


def test_want_right_trims_to_original_columns():
    cfg = SolveConfig(method="none", truncate_rank=8, num_blocks=4,
                      want_right=True)
    a = _spectrum_matrix(m=16, n=90)  # 90 pads to 92 (W = 23)
    res = svd_stream(_row_batches(a, 2, "dense", 4), cfg)
    assert res.v is not None and res.v.shape == (90, 8)
    assert res.state.v.shape == (92, 8)
    no_v = svd_stream(_row_batches(a, 2, "dense", 4),
                      SolveConfig(method="none", truncate_rank=8,
                                  num_blocks=4))
    assert no_v.v is None


def test_history_decay_matches_decayed_oneshot():
    """decay=0.5 over B batches == one-shot SVD of the concatenation
    with batch i scaled by 0.5^(B-1-i)."""
    d, b, decay = 4, 4, 0.5
    a = _spectrum_matrix(m=32, n=96, seed=7)
    cfg = SolveConfig(method="none", truncate_rank=32, oversample=8,
                      num_blocks=d, history_decay=decay)
    res = svd_stream(_row_batches(a, b, "dense", d), cfg)
    mb = 32 // b
    scaled = np.concatenate(
        [a[i * mb:(i + 1) * mb] * decay ** (b - 1 - i) for i in range(b)])
    s_true = np.linalg.svd(scaled, compute_uv=False)
    assert np.abs(np.asarray(res.s) - s_true).max() <= 1e-3 * s_true[0]


# ---------------------------------------------------------------------------
# The rank problem, streaming edition: a rank-deficient batch needs
# repair BEFORE the truncated factorization or the merge can never
# recover the lost components
# ---------------------------------------------------------------------------

def test_rank_deficient_batch_requires_repair():
    coo = sparse.ensure_full_row_rank(
        sparse.random_bipartite(16, 1024, 0.006, seed=11, weighted=True),
        seed=11)
    dead = np.isin(coo.rows, (2, 9, 13))
    coo = sparse.COOMatrix(rows=coo.rows[~dead], cols=coo.cols[~dead],
                           vals=coo.vals[~dead], shape=coo.shape)
    a = sparse.pad_to_block_multiple(coo.todense(), 8)
    assert all(np.linalg.matrix_rank(blk) < 16
               for blk in np.split(a, 8, axis=1))
    k = 15  # > rank(A) = 13: the tail only exists after repair

    # rank=k forces the randomized BATCH factorization — the truncated
    # path whose recovery depends on repair (exact grams would mask it).
    base = dict(truncate_rank=k, rank=k, oversample=32, power_iters=4,
                num_blocks=8)
    res_none = svd_stream([coo], SolveConfig(method="none", **base))
    res_fix = svd_stream([coo], SolveConfig(method="neighbor_random",
                                            **base))
    assert res_fix.plan.rank == k  # the sketch really ran

    # The oracle factors what the stream actually factored: batch 0 is
    # repaired with fold_in(default_key(), 0) — the documented chain.
    ell = sparse.block_ell_from_coo(coo, 8)
    k0 = jax.random.fold_in(ranky.default_key(), 0)
    repaired = np.asarray(
        ranky.split_and_repair(ell, 8, "neighbor_random", k0).todense())
    s_true = np.linalg.svd(repaired, compute_uv=False)

    assert float(np.asarray(res_none.s)[-1]) < 1e-4 * s_true[0]
    assert s_true[k - 1] > 0.05 * s_true[0]  # genuinely nonzero
    np.testing.assert_allclose(np.asarray(res_fix.s), s_true[:k],
                               rtol=1e-3, atol=1e-3 * s_true[0])
    assert res_fix.diagnostics.repaired_rows > 0
    assert res_none.diagnostics.repaired_rows == 0

    # The repair side-band accumulates across the stream: a second
    # deficient batch adds its own lonely/repaired counts on top.
    after = svd_update(res_fix.state, coo,
                       SolveConfig(method="neighbor_random", **base))
    assert after.state.lonely_rows_seen == 2 * res_fix.state.lonely_rows_seen
    assert after.state.repaired_rows_seen == \
        res_fix.state.repaired_rows_seen + after.diagnostics.repaired_rows
    assert after.diagnostics.repaired_rows > 0


# ---------------------------------------------------------------------------
# Checkpointing: save -> restore -> svd_update continues bit-identically
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_resumes_bit_identically(tmp_path):
    coo = _sparse_coo()
    cfg = SolveConfig(method="random", truncate_rank=12, num_blocks=4)
    batches = [_coo_row_slice(coo, 6 * i, 6 * i + 6, 256) for i in range(4)]

    state = svd_init(256, cfg)
    for delta in batches[:2]:
        state = svd_update(state, delta, cfg).state

    ck = Checkpointer(str(tmp_path))
    ck.save(2, state, blocking=True)
    restored, meta = ck.restore(2)
    assert isinstance(restored, StreamingSVDState)
    assert meta["signature"] == tree_signature(state)
    assert (restored.n, restored.num_blocks) == (256, 4)
    assert (restored.rows_seen, restored.batches_seen) == (12, 2)
    assert (restored.lonely_rows_seen, restored.repaired_rows_seen) == \
        (state.lonely_rows_seen, state.repaired_rows_seen)
    for f in ("u", "s", "v", "key"):
        np.testing.assert_array_equal(np.asarray(getattr(restored, f)),
                                      np.asarray(getattr(state, f)))

    # Continue BOTH streams over the remaining batches: bit-identical.
    for delta in batches[2:]:
        state = svd_update(state, delta, cfg).state
        restored = svd_update(restored, delta, cfg).state
    for f in ("u", "s", "v"):
        np.testing.assert_array_equal(np.asarray(getattr(state, f)),
                                      np.asarray(getattr(restored, f)))


def test_checkpoint_roundtrip_block_ell_inside_plain_tree(tmp_path):
    """Registered pytree dataclasses round-trip inside ordinary dict
    trees (and plain trees still work unchanged)."""
    ell = sparse.block_ell_from_coo(_sparse_coo(), 4)
    tree = {"data": ell, "step_arrays": [np.arange(3.0), np.ones((2, 2))]}
    ck = Checkpointer(str(tmp_path))
    ck.save(0, tree, blocking=True)
    back, _ = ck.restore(0)
    assert isinstance(back["data"], sparse.BlockEll)
    assert (back["data"].m, back["data"].width, back["data"].n) == \
        (ell.m, ell.width, ell.n)
    np.testing.assert_array_equal(np.asarray(back["data"].col_vals),
                                  np.asarray(ell.col_vals))
    np.testing.assert_array_equal(np.asarray(back["step_arrays"]["0"]),
                                  np.arange(3.0))


def test_checkpoint_rejects_sequence_children_loudly(tmp_path):
    """A pytree dataclass whose child is a bare tuple would restore as a
    string-keyed dict; save refuses it instead of corrupting silently."""
    import dataclasses as dc

    @jax.tree_util.register_pytree_node_class
    @dc.dataclass(frozen=True)
    class BadChain:
        keys: tuple

        def tree_flatten(self):
            return ((self.keys,), ())

        @classmethod
        def tree_unflatten(cls, aux, children):
            return cls(*children)

    ck = Checkpointer(str(tmp_path))
    with pytest.raises(TypeError, match="tuple"):
        ck.save(0, {"bad": BadChain(keys=(np.ones(2), np.ones(2)))},
                blocking=True)
    # An empty-dict child emits no keys at all, so restore would
    # miscount the children — also rejected at save time.
    with pytest.raises(TypeError, match="empty dict"):
        ck.save(1, {"bad": BadChain(keys={})}, blocking=True)
    # Plain user dicts must not collide with the restore markers.
    with pytest.raises(ValueError, match="__type__"):
        ck.save(2, {"cfg": {"__type__": "v1"}}, blocking=True)


# ---------------------------------------------------------------------------
# Planner rule R5d: the shard_map streaming variant — per-device byte
# estimates pinned to the documented closed form, backend selection, and
# the honest degrade to single-host
# ---------------------------------------------------------------------------

def test_r5d_byte_estimates_hand_computed():
    # BATCH_SPEC: m=64, n=4096, D=8 -> W=512; k=16, p=8 -> l_b=24.
    # merge slice per device: 4 * 2 * 512 * (16 + 24) = 163_840
    assert planner.stream_merge_bytes_per_device(BATCH_SPEC, 16, 8) == \
        163_840
    # per-device repair transient: 4 * 2 * (64*512 + 64*64) = 294_912
    assert planner.stream_repair_bytes_per_device(BATCH_SPEC) == 294_912
    # exact batch term per device (local gram + psum buffer):
    # 4 * 64 * 64 = 16_384
    assert planner.streaming_bytes_per_device(BATCH_SPEC, 16, 8,
                                              exact=True) == \
        16_384 + 294_912 + 163_840
    # sketch per device at the rank the engine runs (r_b = l_b = 24,
    # internal width L = min(24 + 8, 64) = 32):
    # 4 * (32*512 + 2*64*32) = 81_920
    assert planner.streaming_bytes_per_device(BATCH_SPEC, 16, 8,
                                              exact=False) == \
        81_920 + 294_912 + 163_840
    # explicitly forced batch rank 12: L = min(12 + 8, 64) = 20 ->
    # 4*(20*512 + 2*64*20) = 51_200; merge 4*2*512*(16+12) = 114_688
    assert planner.streaming_bytes_per_device(
        BATCH_SPEC, 16, 8, exact=False, batch_rank=12) == \
        51_200 + 294_912 + 114_688


def test_r5d_backend_selection_and_honest_degrade():
    cfg = SolveConfig(truncate_rank=16, stream_backend="shard_map")
    p = planner.make_stream_plan(BATCH_SPEC, cfg, device_count=8)
    assert p.backend == "shard_map" and p.strategy == "streaming"
    assert p.rank is None  # exact batch factorization fits per device
    assert p.peak_bytes == 16_384 + 294_912 + 163_840
    assert p.estimates["stream_exact_per_device"] == p.peak_bytes
    assert "independent of rows already ingested" in " ".join(p.reasons)
    # shard_map requested but one-block-per-device impossible: degrade
    # honestly (R5d never raises), with the single-host R5 peak.
    p = planner.make_stream_plan(BATCH_SPEC, cfg, device_count=4)
    assert p.backend == "single"
    assert any("degrading honestly" in r for r in p.reasons)
    assert p.peak_bytes == 131_072 + 2_097_152 + 1_310_720
    # auto engages shard_map exactly when one device per block exists.
    p = planner.make_stream_plan(BATCH_SPEC, SolveConfig(truncate_rank=16),
                                 device_count=8)
    assert p.backend == "shard_map"
    p = planner.make_stream_plan(BATCH_SPEC, SolveConfig(truncate_rank=16),
                                 device_count=1)
    assert p.backend == "single"
    # explicit single stays single even with a matching device count.
    p = planner.make_stream_plan(
        BATCH_SPEC, SolveConfig(truncate_rank=16, stream_backend="single"),
        device_count=8)
    assert p.backend == "single"


def test_r5d_forced_rank_tracks_per_device_estimate():
    cfg = SolveConfig(truncate_rank=16, rank=12, stream_backend="shard_map")
    p = planner.make_stream_plan(BATCH_SPEC, cfg, device_count=8)
    assert p.backend == "shard_map" and p.rank == 12
    assert p.peak_bytes == planner.streaming_bytes_per_device(
        BATCH_SPEC, 16, 8, exact=False, batch_rank=12)
    assert any("explicitly" in r for r in p.reasons)


def test_stream_backend_config_validation():
    with pytest.raises(ValueError, match="stream_backend"):
        SolveConfig(truncate_rank=8, stream_backend="proxy")
    # stream_backend is a streaming knob: it needs truncate_rank.
    with pytest.raises(ValueError) as exc:
        SolveConfig(stream_backend="shard_map")
    assert "stream_backend" in str(exc.value)
    assert "truncate_rank" in str(exc.value)


# ---------------------------------------------------------------------------
# The shard_map ingest engine: sharded-v merge matches the single-host
# result (acceptance bar 1e-5; S ranked, U/V up to sign) for all three
# delta representations, including a rank-deficient batch that needs
# repair.  In-process when 8 host devices are forced (the CI streaming
# leg), via a subprocess twin otherwise.
# ---------------------------------------------------------------------------

def _assert_stream_results_match(r1, r2, j: int, tol: float):
    """r2 (sharded) vs r1 (single-host): singular values within tol
    (and ranked descending), leading-j U/V columns equal up to sign."""
    s1, s2 = np.asarray(r1.s), np.asarray(r2.s)
    assert np.abs(s1 - s2).max() <= tol * s1[0]
    assert np.all(np.diff(s2) <= 1e-6 * s1[0])  # ranked
    u1, u2 = np.asarray(r1.state.u), np.asarray(r2.state.u)
    v1, v2 = np.asarray(r1.state.v), np.asarray(r2.state.v)
    sign = np.sign((u1[:, :j] * u2[:, :j]).sum(axis=0))
    assert np.abs(u1[:, :j] - u2[:, :j] * sign).max() <= tol
    assert np.abs(v1[:, :j] - v2[:, :j] * sign).max() <= tol


@eight_devices
@pytest.mark.parametrize("kind", ["dense", "coo", "ell"])
def test_sharded_ingest_matches_single_host(kind):
    d, b = 8, 4
    a = _spectrum_matrix(m=32, n=96)
    base = dict(method="neighbor_random", truncate_rank=RANK, oversample=8,
                num_blocks=d)
    r1 = svd_stream(_row_batches(a, b, kind, d),
                    SolveConfig(stream_backend="single", **base))
    r2 = svd_stream(_row_batches(a, b, kind, d),
                    SolveConfig(stream_backend="shard_map", **base))
    assert r2.plan.backend == "shard_map"
    assert r1.plan.backend == "single"
    _assert_stream_results_match(r1, r2, j=8, tol=1e-5)
    # The repair side-band counters agree exactly (psum'd == summed).
    assert r2.state.lonely_rows_seen == r1.state.lonely_rows_seen
    assert r2.state.repaired_rows_seen == r1.state.repaired_rows_seen


@eight_devices
def test_sharded_rank_deficient_batch_repair_matches_single_host():
    """The rank problem, sharded edition: the per-device repair replays
    the single-host key chain bit-identically, so the forced-sketch
    factorization of a batch whose tail only exists after repair agrees
    across engines."""
    coo = sparse.ensure_full_row_rank(
        sparse.random_bipartite(16, 1024, 0.006, seed=11, weighted=True),
        seed=11)
    dead = np.isin(coo.rows, (2, 9, 13))
    coo = sparse.COOMatrix(rows=coo.rows[~dead], cols=coo.cols[~dead],
                           vals=coo.vals[~dead], shape=coo.shape)
    k = 15
    base = dict(method="neighbor_random", truncate_rank=k, rank=k,
                oversample=32, power_iters=4, num_blocks=8)
    r1 = svd_stream([coo], SolveConfig(stream_backend="single", **base))
    r2 = svd_stream([coo], SolveConfig(stream_backend="shard_map", **base))
    assert r2.plan.backend == "shard_map" and r2.plan.rank == k
    s1, s2 = np.asarray(r1.s), np.asarray(r2.s)
    assert np.abs(s1 - s2).max() <= 1e-5 * s1[0]
    assert float(s2[-1]) > 0.01 * s2[0]  # the repaired tail is real
    assert r2.diagnostics.repaired_rows == r1.diagnostics.repaired_rows > 0


@eight_devices
def test_sharded_history_decay_matches_single_host():
    d, b = 8, 4
    a = _spectrum_matrix(m=32, n=96, seed=7)
    base = dict(method="none", truncate_rank=32, oversample=8, num_blocks=d,
                history_decay=0.5)
    r1 = svd_stream(_row_batches(a, b, "dense", d),
                    SolveConfig(stream_backend="single", **base))
    r2 = svd_stream(_row_batches(a, b, "dense", d),
                    SolveConfig(stream_backend="shard_map", **base))
    assert r2.plan.backend == "shard_map"
    s1, s2 = np.asarray(r1.s), np.asarray(r2.s)
    assert np.abs(s1 - s2).max() <= 1e-5 * s1[0]


@pytest.mark.timeout(840)
def test_sharded_ingest_matches_single_host_subprocess():
    """Subprocess twin of the in-process sharded tests, so a
    single-device tier-1 run still exercises the shard_map engine on 8
    forced host devices (same mechanism as tests/test_distributed.py)."""
    if jax.device_count() == 8:
        pytest.skip("in-process sharded tests cover this directly")
    out = run_forced_devices("""
        import numpy as np, jax
        from repro.core import sparse
        from repro.core.api import SolveConfig, svd_stream
        assert jax.device_count() == 8
        rng = np.random.default_rng(0)
        u0, _ = np.linalg.qr(rng.standard_normal((32, 32)))
        v0, _ = np.linalg.qr(rng.standard_normal((96, 32)))
        a = ((u0 * np.geomspace(20.0, 0.5, 32)) @ v0.T).astype(np.float32)
        def batches(kind):
            out = []
            for i in range(4):
                rows = a[i * 8:(i + 1) * 8]
                if kind == "dense":
                    out.append(rows); continue
                r, c = np.nonzero(rows)
                coo = sparse.COOMatrix(
                    rows=r.astype(np.int32), cols=c.astype(np.int32),
                    vals=rows[r, c].astype(np.float32), shape=rows.shape)
                out.append(coo if kind == "coo"
                           else sparse.block_ell_from_coo(coo, 8))
            return out
        base = dict(method="neighbor_random", truncate_rank=24,
                    oversample=8, num_blocks=8)
        for kind in ("dense", "coo", "ell"):
            r1 = svd_stream(batches(kind),
                            SolveConfig(stream_backend="single", **base))
            r2 = svd_stream(batches(kind),
                            SolveConfig(stream_backend="shard_map", **base))
            assert r2.plan.backend == "shard_map"
            s1, s2 = np.asarray(r1.s), np.asarray(r2.s)
            assert np.abs(s1 - s2).max() <= 1e-5 * s1[0], kind
            u1 = np.asarray(r1.state.u)[:, :8]
            u2 = np.asarray(r2.state.u)[:, :8]
            sign = np.sign((u1 * u2).sum(axis=0))
            assert np.abs(u1 - u2 * sign).max() <= 1e-5, kind
        print("OK")
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Checkpoint portability across device counts: saves are gathered (the
# file never bakes in a mesh), restores re-shard onto the CURRENT
# device count, and the next svd_update is bit-identical
# ---------------------------------------------------------------------------

@eight_devices
def test_checkpoint_portability_sharded_roundtrip(tmp_path):
    """Save a SHARDED state, restore (re-shards onto the 8 devices),
    continue both sharded and gathered-single-host: bit-identical to
    continuing the never-checkpointed state the same way.  And the
    reverse direction: a single-host stream's checkpoint restores
    straight into the sharded engine."""
    from repro import stream

    rng = np.random.default_rng(3)
    a = rng.standard_normal((48, 128)).astype(np.float32)
    cfg_sh = SolveConfig(method="random", truncate_rank=12, num_blocks=8,
                         stream_backend="shard_map")
    cfg_si = SolveConfig(method="random", truncate_rank=12, num_blocks=8,
                         stream_backend="single")

    state = svd_init(128, cfg_sh)
    for i in range(3):
        state = svd_update(state, a[i * 12:(i + 1) * 12], cfg_sh).state
    ck = Checkpointer(str(tmp_path))
    ck.save(3, state, blocking=True)
    restored, _ = ck.restore(3)
    assert isinstance(restored, StreamingSVDState)
    for f in ("u", "s", "v", "key"):
        np.testing.assert_array_equal(np.asarray(getattr(restored, f)),
                                      np.asarray(getattr(state, f)))
    # Continue SHARDED on both: bit-identical.
    n1 = svd_update(state, a[36:48], cfg_sh).state
    n2 = svd_update(restored, a[36:48], cfg_sh).state
    for f in ("u", "s", "v"):
        np.testing.assert_array_equal(np.asarray(getattr(n1, f)),
                                      np.asarray(getattr(n2, f)))
    # "Restore on 1": gather both and continue single-host —
    # bit-identical again (the engine never sees the donor's layout).
    g1 = svd_update(stream.gather_state(state), a[36:48], cfg_si).state
    g2 = svd_update(stream.gather_state(restored), a[36:48], cfg_si).state
    for f in ("u", "s", "v"):
        np.testing.assert_array_equal(np.asarray(getattr(g1, f)),
                                      np.asarray(getattr(g2, f)))
    # Vice versa: a single-host stream's checkpoint feeds the sharded
    # engine bit-identically.
    st1 = svd_init(128, cfg_si)
    for i in range(2):
        st1 = svd_update(st1, a[i * 12:(i + 1) * 12], cfg_si).state
    ck.save(10, st1, blocking=True)
    rest1, _ = ck.restore(10)
    m1 = svd_update(st1, a[24:36], cfg_sh).state
    m2 = svd_update(rest1, a[24:36], cfg_sh).state
    for f in ("u", "s", "v"):
        np.testing.assert_array_equal(np.asarray(getattr(m1, f)),
                                      np.asarray(getattr(m2, f)))


@pytest.mark.timeout(840)
def test_checkpoint_saved_on_8_devices_restores_on_1(tmp_path):
    """True cross-device-count portability, two processes: an 8-device
    process streams SHARDED and saves; a 1-device process restores the
    same directory and continues single-host — bit-identical to the
    donor's own gathered single-host continuation (dumped as reference
    arrays next to the checkpoint)."""
    ckdir = str(tmp_path)
    common = """
        import numpy as np, jax
        from repro.checkpoint.ckpt import Checkpointer
        from repro.core.api import SolveConfig, svd_init, svd_update
        from repro import stream
        rng = np.random.default_rng(3)
        a = rng.standard_normal((48, 128)).astype(np.float32)
    """
    run_forced_devices(common + f"""
        assert jax.device_count() == 8
        cfg = SolveConfig(method="random", truncate_rank=12, num_blocks=8,
                          stream_backend="shard_map")
        state = svd_init(128, cfg)
        for i in range(3):
            state = svd_update(state, a[i*12:(i+1)*12], cfg).state
        ck = Checkpointer({ckdir!r})
        ck.save(3, state, blocking=True)
        nxt = svd_update(stream.gather_state(state), a[36:48],
                         SolveConfig(method="random", truncate_rank=12,
                                     num_blocks=8,
                                     stream_backend="single")).state
        np.savez({ckdir!r} + "/ref.npz", u=np.asarray(nxt.u),
                 s=np.asarray(nxt.s), v=np.asarray(nxt.v))
        print("SAVED")
    """)
    out = run_forced_devices(common + f"""
        assert jax.device_count() == 1
        ck = Checkpointer({ckdir!r})
        restored, _ = ck.restore(3)
        assert restored.num_blocks == 8 and restored.batches_seen == 3
        cfg = SolveConfig(method="random", truncate_rank=12, num_blocks=8,
                          stream_backend="single")
        nxt = svd_update(restored, a[36:48], cfg).state
        ref = np.load({ckdir!r} + "/ref.npz")
        for f in ("u", "s", "v"):
            np.testing.assert_array_equal(np.asarray(getattr(nxt, f)),
                                          ref[f])
        print("OK")
    """, devices=1)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Falkon-style measured-memory checks: the compiled executable's actual
# peak must stay within the planner's closed forms (keeps R5/R5d honest
# — these measurements are what surfaced the repair-transient term)
# ---------------------------------------------------------------------------

MEM_SPEC = ASpec(m=64, n=4096, nnz=64 * 4096, num_blocks=8, kind="stream")


def test_r5_measured_peak_within_closed_form(memory_checker):
    """R5: the single-host per-batch update's measured XLA temporaries
    (a T=1 scan window IS the per-batch loop — same compiled step) stay
    within ``streaming_bytes``.  Lowered from avals: no data needed."""
    from repro.stream import window as sw
    cfg = SolveConfig(truncate_rank=16, num_blocks=8)
    p = planner.make_window_plan(MEM_SPEC, cfg, device_count=1)
    assert p.backend == "single"
    r_b = (min(MEM_SPEC.m, 16 + cfg.oversample) if p.rank is None
           else p.rank)
    fn = sw._window_fn("dense", 8, MEM_SPEC.m, 512, 4096, r_b, 16,
                       p.rank, cfg.oversample, cfg.power_iters,
                       cfg.method, float(cfg.history_decay))
    key = jax.random.PRNGKey(0)
    f32, i32 = jnp.float32, jnp.int32
    scalar = jax.ShapeDtypeStruct((), i32)
    batch = jax.ShapeDtypeStruct((MEM_SPEC.m, 4096), f32)
    args = (key, jax.ShapeDtypeStruct((16,), f32),
            jax.ShapeDtypeStruct((4096, 16), f32), scalar, scalar, scalar,
            (jax.ShapeDtypeStruct((1, MEM_SPEC.m, 4096), f32),
             jax.ShapeDtypeStruct((1, MEM_SPEC.m, r_b), f32),
             jax.ShapeDtypeStruct((1,), i32)))
    budget = planner.streaming_bytes(MEM_SPEC, 16, cfg.oversample,
                                     exact=p.rank is None,
                                     batch_rank=p.rank)
    # The exact update runs as three programs — the batch gram, its eigh
    # and the merge scan — each within the closed form.
    assert p.rank is None
    gram = sw._gram_fn("dense", 8, MEM_SPEC.m, 512, 4096, cfg.method,
                       cfg.use_kernel)
    memory_checker(gram, (key, scalar, batch, scalar), budget,
                   label="R5 batch gram", component="temp")
    memory_checker(lsvd.merge_grams_eigh,
                   (jax.ShapeDtypeStruct((MEM_SPEC.m, MEM_SPEC.m), f32),),
                   budget, label="R5 batch eigh", component="temp")
    memory_checker(fn, args, budget, label="R5 svd_update (T=1 window)",
                   component="temp")


@pytest.mark.timeout(840)
def test_r5d_measured_peak_within_closed_form_subprocess(memory_checker):
    """R5d: the sharded ingest's per-device measured temporaries stay
    within ``streaming_bytes_per_device`` (8 forced host devices)."""
    out = run_forced_devices("""
        import importlib
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.api import ASpec, SolveConfig
        from repro.core import planner
        si = importlib.import_module("repro.stream.ingest")
        from repro.stream.state import STREAM_AXIS, stream_devices_key

        d, n, m_b, k, p_os = 8, 4096, 32, 16, 8
        spec = ASpec(m=m_b, n=n, nnz=m_b * n, num_blocks=d, kind="stream")
        cfg = SolveConfig(truncate_rank=k, oversample=p_os, num_blocks=d,
                          stream_backend="shard_map")
        plan = planner.make_stream_plan(spec, cfg, device_count=8)
        assert plan.backend == "shard_map"
        r_b = min(m_b, k + p_os) if plan.rank is None else plan.rank
        assert plan.rank is None
        mesh, fn = si._sharded_ingest_fn(
            stream_devices_key(), d, "dense", m_b, n // d, r_b, k,
            plan.rank, p_os, cfg.power_iters, cfg.method)
        _, gram = si._sharded_gram_fn(
            stream_devices_key(), d, "dense", m_b, n // d, cfg.method,
            cfg.use_kernel)
        key = jax.random.PRNGKey(0)
        def sds(shape, dtype, spec_):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, spec_))
        delta = sds((m_b, n), jnp.float32, P(None, STREAM_AXIS))
        keys = sds((d,) + key.shape, key.dtype, P(STREAM_AXIS))
        args = (delta, keys,
                sds(key.shape, key.dtype, P()),
                sds((n, k), jnp.float32, P(STREAM_AXIS, None)),
                sds((k,), jnp.float32, P()),
                sds((m_b, r_b), jnp.float32, P()))
        # The exact update's two sharded regions (the batch gram, then
        # panel and merge; the eigh between runs on one device): the
        # larger per-device peak.
        temps = [int(f.lower(*a).compile().memory_analysis()
                     .temp_size_in_bytes)
                 for f, a in ((gram, (delta, keys)), (fn, args))]
        budget = planner.streaming_bytes_per_device(
            spec, k, p_os, exact=plan.rank is None, batch_rank=plan.rank)
        print("MEASURED", max(temps), budget)
    """)
    measured, budget = (int(x) for x in
                        out.split("MEASURED")[1].split())
    memory_checker.check_value(measured, budget,
                               label="R5d sharded ingest per-device temp")
