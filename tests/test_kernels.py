"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels import blockgram as bg
from repro.kernels import flash_attention as fa
from repro.kernels import sketch_panel as sp
from repro.kernels import sparse_gram as sg
from repro.kernels import ssd_scan as ssd
from repro.kernels import ops

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return (3e-2, 1e-1) if dtype == jnp.bfloat16 else (2e-5, 1e-4)


# ---------------------------------------------------------------------------
# blockgram
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [8, 64, 128])
@pytest.mark.parametrize("n", [256, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blockgram_sweep(m, n, dtype):
    x = jax.random.normal(KEY, (m, n), dtype)
    got = bg.blockgram(x, block_n=256, interpret=True)
    want = ref.blockgram(x)
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rtol, atol=atol * n / 100)


def test_blockgram_ops_padding():
    # M not 8-aligned, N not block-aligned -> ops pads losslessly.
    x = jax.random.normal(KEY, (13, 300), jnp.float32)
    got = ops.blockgram(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.blockgram(x)),
                               rtol=1e-5, atol=1e-3)
    assert got.shape == (13, 13)


def test_blockgram_sparse_zeros():
    x = jnp.zeros((16, 512), jnp.float32)
    got = bg.blockgram(x, block_n=256, interpret=True)
    assert np.all(np.asarray(got) == 0)


# ---------------------------------------------------------------------------
# sparse_gram (padded-ELL gram; the sparse-native twin of blockgram)
# ---------------------------------------------------------------------------

def _random_ell(m, c, k, seed=0, zero_frac=0.3):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, m, size=(c, k)).astype(np.int32)
    vals = rng.standard_normal((c, k)).astype(np.float32)
    vals[rng.random((c, k)) < zero_frac] = 0.0  # padding slots
    return jnp.asarray(rows), jnp.asarray(vals)


@pytest.mark.parametrize("m", [8, 64, 128])
@pytest.mark.parametrize("c", [128, 512])
@pytest.mark.parametrize("k", [1, 8])
def test_sparse_gram_sweep(m, c, k):
    rows, vals = _random_ell(m, c, k)
    got = sg.sparse_gram(rows.T, vals.T, m, block_c=128, interpret=True)
    want = ref.sparse_gram(rows, vals, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-4)


def test_sparse_gram_ops_padding(monkeypatch):
    # M not 8-aligned, K not sublane-aligned, C not block-aligned -> ops
    # pads losslessly around the actual kernel (interpret mode).
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    rows, vals = _random_ell(13, 60, 3, seed=1)
    got = ops.sparse_gram(rows, vals, 13)
    want = ref.sparse_gram(rows, vals, 13)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-4)
    assert got.shape == (13, 13)


@pytest.mark.parametrize("m,block_m", [(256, 128), (384, 128), (512, 256)])
def test_sparse_gram_tiled_output(m, block_m):
    # (block_m, block_m) output tiles, each with its own accumulator over
    # the stored-column axis, reassemble the oracle's (M, M) gram.
    rows, vals = _random_ell(m, 256, 8, seed=3)
    got = sg.sparse_gram(rows.T, vals.T, m, block_m=block_m, block_c=128,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.sparse_gram(rows, vals, m)),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("m", [300, 520])
def test_sparse_gram_ops_tiles_unaligned_m(monkeypatch, m):
    # M past one tile: ops pads M to the 128-aligned tile grid and trims.
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    monkeypatch.setattr(ops, "MAX_GRAM_BLOCK", 128)
    monkeypatch.setattr(ops, "MAX_GRAM_TILE", 128)
    rows, vals = _random_ell(m, 200, 5, seed=4)
    got = ops.sparse_gram(rows, vals, m, block_c=128)
    assert got.shape == (m, m)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.sparse_gram(rows, vals, m)),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("m,want", [
    (13, (16, 16)), (544, (544, 544)), (640, (640, 640)),
    (700, (384, 768)), (2048, (512, 2048)), (2049, (512, 2560))])
def test_gram_tiles(m, want):
    # One block up to MAX_GRAM_BLOCK rows; past it the fewest tiles of at
    # most MAX_GRAM_TILE rows, each 128-aligned, so M pads by less than
    # 128 rows per tile.
    assert ops._gram_tiles(m) == want


def test_kernel_width_limits_raise(monkeypatch):
    # Past a kernel's VMEM limit ops raises, naming it, instead of
    # quietly running XLA.
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    slots = ops.MAX_ELL_SLOTS["sparse_gram"] + 1
    rows, vals = _random_ell(8, 128, slots)
    with pytest.raises(ValueError, match="MAX_ELL_SLOTS"):
        ops.sparse_gram(rows, vals, 8)
    x = jnp.zeros((ops.MAX_BLOCKGRAM_ROWS + 8, 128), jnp.float32)
    with pytest.raises(ValueError, match="MAX_BLOCKGRAM_ROWS"):
        ops.blockgram(x)


def test_sparse_gram_matches_dense_blockgram():
    """Container-built ELL gram == dense gram of the same block."""
    from repro.core import sparse as spr

    coo = spr.ensure_full_row_rank(
        spr.random_bipartite(24, 2000, 0.005, seed=2), seed=2)
    ell = spr.block_ell_from_coo(coo, 4)
    a = spr.pad_to_block_multiple(coo.todense(), 4)
    for d in range(4):
        got = ops.sparse_gram(jnp.asarray(ell.col_rows[d]),
                              jnp.asarray(ell.col_vals[d]), ell.m)
        blk = a[:, d * ell.width:(d + 1) * ell.width]
        np.testing.assert_allclose(np.asarray(got), blk @ blk.T,
                                   rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# sketch_panel (randomized range finder: Omega @ E over stored columns)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [128, 256])
@pytest.mark.parametrize("l", [8, 16])
@pytest.mark.parametrize("c", [128, 512])
@pytest.mark.parametrize("k", [1, 8])
def test_sketch_panel_sweep(m, l, c, k):
    rows, vals = _random_ell(m, c, k)
    omega = jax.random.normal(KEY, (l, m), jnp.float32)
    got = sp.sketch_panel(omega, rows.T, vals.T, block_c=128, block_m=128,
                          interpret=True)
    want = ref.sketch_panel(omega, rows, vals)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-4)


def test_sketch_panel_ops_padding(monkeypatch):
    # L not sublane-aligned, M not block-aligned, K/C unaligned -> ops
    # pads losslessly around the actual kernel (interpret mode).
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    rows, vals = _random_ell(13, 60, 3, seed=1)
    omega = jax.random.normal(KEY, (5, 13), jnp.float32)
    got = ops.sketch_panel(omega, rows, vals)
    want = ref.sketch_panel(omega, rows, vals)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-4)
    assert got.shape == (5, 60)


def test_sketch_panel_matches_dense_contraction():
    """Container-built ELL sketch == Omega @ dense block, per block."""
    from repro.core import sparse as spr

    coo = spr.ensure_full_row_rank(
        spr.random_bipartite(24, 2000, 0.005, seed=2), seed=2)
    ell = spr.block_ell_from_coo(coo, 4)
    a = spr.pad_to_block_multiple(coo.todense(), 4)
    omega = jax.random.normal(KEY, (6, 24), jnp.float32)
    for d in range(4):
        panel = ops.sketch_panel(omega, jnp.asarray(ell.col_rows[d]),
                                 jnp.asarray(ell.col_vals[d]))
        got = np.zeros((6, ell.width), np.float32)
        np.add.at(got, (slice(None), np.asarray(ell.col_ids[d])),
                  np.asarray(panel))
        blk = a[:, d * ell.width:(d + 1) * ell.width]
        np.testing.assert_allclose(got, np.asarray(omega) @ blk,
                                   rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "b,hq,hkv,sq,sk,d",
    [
        (2, 4, 2, 128, 128, 64),
        (1, 8, 1, 64, 64, 128),   # MQA
        (1, 4, 4, 256, 256, 32),  # MHA
        (2, 4, 2, 64, 192, 64),   # cross/right-aligned (sq < sk)
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, sq, sk, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, sk, d), dtype)
    got = fa.flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention(q, k, v)
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=rtol, atol=atol
    )


@pytest.mark.parametrize("window", [0, 96])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_variants(window, softcap, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
    got = fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=64, block_k=64, interpret=True,
    )
    want = ref.flash_attention(q, k, v, causal=causal, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-4)


def test_chunked_flash_matches_oracle():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4, 512, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 512, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 512, 64), jnp.float32)
    got = ref.chunked_flash_attention(q, k, v, block_k=128)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-4)


def test_flash_ops_unaligned_padding():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 100, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 100, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 100, 64), jnp.float32)
    got = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("bq,bk,s", [(64, 128, 150), (64, 128, 100),
                                     (128, 64, 100)])
def test_flash_ops_padding_blockq_ne_blockk(monkeypatch, bq, bk, s):
    """Regression: ops used to pad K and V by the QUERY pad pq instead of
    aligning to block_k — with block_q=64, block_k=128 and causal
    sq == sk == 150 the kernel either rejected the padded KV length or,
    padded unequally, shifted the right-alignment and mis-masked real
    rows.  Both Q and KV must land on one common length aligned to both
    block sizes.  Interpret mode so the actual kernel body runs."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, s, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, s, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, s, 64), jnp.float32)
    got = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    want = ref.flash_attention(q, k, v)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "b,l,h,g,p,n,chunk",
    [
        (2, 128, 4, 2, 32, 16, 64),
        (1, 256, 2, 2, 64, 32, 128),
        (1, 64, 4, 1, 16, 8, 32),   # MVA-style shared B/C
        (1, 128, 8, 8, 64, 64, 64),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(b, l, h, g, p, n, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, l, h, p), dtype)
    dt = (jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.1).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = (jax.random.normal(ks[3], (b, l, g, n)) / np.sqrt(n)).astype(dtype)
    cm = (jax.random.normal(ks[4], (b, l, g, n)) / np.sqrt(n)).astype(dtype)
    y, hf = ssd.ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    yr, hr = ref.ssd_scan(x, dt, a, bm, cm, return_state=True)
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32), rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr), rtol=rtol, atol=atol)


def test_ssd_state_decays():
    # With strongly negative A and long sequence the state forgets the past:
    # final state ~ function of the recent tokens only.
    b, l, h, g, p, n = 1, 128, 2, 1, 16, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jnp.ones((b, l, h)) * 2.0
    a = jnp.full((h,), -10.0)
    bm = jax.random.normal(ks[3], (b, l, g, n))
    cm = jax.random.normal(ks[4], (b, l, g, n))
    _, hf = ssd.ssd_scan(x, dt, a, bm, cm, chunk=64, interpret=True)
    x2 = x.at[:, : l // 2].set(jax.random.normal(ks[2], (b, l // 2, h, p)))
    _, hf2 = ssd.ssd_scan(x2, dt, a, bm, cm, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hf2), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# topk_score (fused score + running top-k)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "b,k,n,k_top,block_n",
    [
        (3, 5, 700, 10, 256),    # ragged last tile
        (8, 16, 512, 4, 512),    # single tile
        (1, 3, 130, 7, 512),     # n < block_n, unaligned everything
        (5, 16, 1024, 16, 128),  # k_top == block_n grid stress
    ],
)
def test_topk_score_sweep_bitwise(b, k, n, k_top, block_n, monkeypatch):
    """The fused kernel is BIT-identical to the oracle — values AND
    indices (same tie rule: descending values, ties to lowest index)."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    ks = jax.random.split(KEY, 2)
    qs = jax.random.normal(ks[0], (b, k))
    v = jax.random.normal(ks[1], (n, k))
    got_v, got_i = ops.topk_score(qs, v, k_top, block_n=block_n)
    want_v, want_i = ref.topk_score(qs, v, k_top)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_topk_score_ties_resolve_to_lowest_index(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    qs = jax.random.normal(KEY, (4, 8))
    base = jax.random.normal(jax.random.fold_in(KEY, 1), (75, 8))
    v = jnp.concatenate([base, base, base])  # every score a 3-way tie
    got_v, got_i = ops.topk_score(qs, v, 9, block_n=128)
    want_v, want_i = ref.topk_score(qs, v, 9)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_topk_score_scale_offset_valid_n(monkeypatch):
    """The sharded per-device call shape: per-item scales folded into
    the contraction, a global index offset, and a ragged valid width
    masking the padded tail to -inf."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    ks = jax.random.split(KEY, 3)
    qs = jax.random.normal(ks[0], (5, 12))
    v = jax.random.normal(ks[1], (640, 12))
    scale = jnp.exp(jax.random.normal(ks[2], (640,)) * 0.3)
    got = ops.topk_score(qs, v, 11, scale=scale, valid_n=613,
                         index_offset=1000, block_n=256)
    want = ref.topk_score(qs, v, 11, scale=scale, valid_n=613,
                          index_offset=1000)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    # masked tail never surfaces: all ids in [offset, offset + valid)
    ids = np.asarray(got[1])
    assert ids.min() >= 1000 and ids.max() < 1000 + 613


def test_topk_score_int8_factors(monkeypatch):
    """int8 factor rows + per-item dequant scales (the quantized
    serving path) stay bit-identical to the oracle fed the same
    operands."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    from repro.serve import kvquant
    ks = jax.random.split(KEY, 2)
    qs = jax.random.normal(ks[0], (4, 8))
    v = jax.random.normal(ks[1], (300, 8)) * 2.0
    v_q, v_scale = kvquant.quantize(v, axis=-1)
    got = ops.topk_score(qs, v_q, 6, scale=v_scale[:, 0],
                         valid_n=300, block_n=128)
    want = ref.topk_score(qs, v_q, 6, scale=v_scale[:, 0], valid_n=300)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_topk_score_ref_mode_dispatch():
    """conftest pins REPRO_KERNELS=ref: the dispatch must route to the
    oracle without padding artifacts."""
    qs = jax.random.normal(KEY, (2, 4))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (50, 4))
    got_v, got_i = ops.topk_score(qs, v, 5)
    want_v, want_i = ref.topk_score(qs, v, 5)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
