"""Sparse-native execution path: container round-trips, the one
block-splitting convention, sparse checkers vs the dense oracles, exact
grams of repaired blocks, and (U, S) parity of the sparse pipeline with
the dense pipeline / numpy truth."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import ranky, sparse
from repro.core import svd as lsvd
from repro.core.hierarchy import hierarchical_ranky_svd
from conftest import jaxpr_traces

KEY = jax.random.PRNGKey(0)


def _coo(m=16, n=517, density=0.004, seed=5):
    return sparse.ensure_full_row_rank(
        sparse.random_bipartite(m, n, density, seed=seed), seed=seed)


def _dense_blocks(a: np.ndarray, num_blocks: int) -> np.ndarray:
    m, n = a.shape
    return np.transpose(a.reshape(m, num_blocks, n // num_blocks), (1, 0, 2))


# ---------------------------------------------------------------------------
# Container + block-splitting convention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_blocks", [1, 3, 8])
def test_block_ell_roundtrip(num_blocks):
    coo = _coo()
    ell = sparse.block_ell_from_coo(coo, num_blocks)
    want = sparse.pad_to_block_multiple(coo.todense(), num_blocks)
    np.testing.assert_array_equal(np.asarray(ell.todense()), want)
    assert ell.padded_shape == want.shape


def test_coo_duplicates_dense_and_sparse_agree():
    """Regression: duplicate (row, col) triples used to diverge —
    COOMatrix.todense assigned (last write wins) while the BlockEll
    consumers scatter-ADD, so the sparse and dense paths factored
    DIFFERENT matrices.  Both now accumulate (block_ell_from_coo
    coalesces duplicates by summing) and must factor the same matrix."""
    coo = sparse.COOMatrix(
        rows=np.asarray([0, 0, 1, 0, 2, 2], np.int32),
        cols=np.asarray([1, 1, 5, 1, 9, 9], np.int32),
        vals=np.asarray([1.0, 2.0, 3.0, 0.5, 1.0, 1.0], np.float32),
        shape=(3, 12))
    dense = coo.todense()
    assert dense[0, 1] == 3.5 and dense[2, 9] == 2.0  # summed, not last
    for num_blocks in (1, 3):
        ell = sparse.block_ell_from_coo(coo, num_blocks)
        want = sparse.pad_to_block_multiple(dense, num_blocks)
        np.testing.assert_array_equal(np.asarray(ell.todense()), want)
    # and the two pipelines factor the same matrix
    ell = sparse.block_ell_from_coo(coo, 3)
    s_true = np.linalg.svd(sparse.pad_to_block_multiple(dense, 3),
                           compute_uv=False)
    _, s = ranky.ranky_svd(ell, num_blocks=3, method="none",
                           merge_mode="gram")
    np.testing.assert_allclose(np.asarray(s), s_true, rtol=1e-4, atol=1e-4)


def test_block_bounds_host_device_agree():
    """The one splitting convention: host block_col_bounds slices exactly
    the device blocks (pad_to_block_multiple + equal reshape), with only
    trailing zero-padding in the final device block."""
    n, num_blocks = 37, 5  # non-divisible on purpose
    rng = np.random.default_rng(0)
    a = (rng.random((4, n)) < 0.3).astype(np.float32)
    padded = sparse.pad_to_block_multiple(a, num_blocks)
    w = padded.shape[1] // num_blocks
    assert w == sparse.block_width(n, num_blocks)
    widths = []
    for d in range(num_blocks):
        lo, hi = sparse.block_col_bounds(n, num_blocks, d)
        widths.append(hi - lo)
        dev_blk = padded[:, d * w:(d + 1) * w]
        np.testing.assert_array_equal(dev_blk[:, : hi - lo], a[:, lo:hi])
        assert (dev_blk[:, hi - lo:] == 0).all()
    assert sum(widths) == n
    # split_blocks follows the same bounds
    split = sparse.split_blocks(a, num_blocks)
    assert [b.shape[1] for b in split] == widths


# ---------------------------------------------------------------------------
# Sparse-native detection / adjacency / repair vs the dense oracles
# ---------------------------------------------------------------------------

def test_sparse_lonely_and_adjacency_match_dense():
    coo = _coo()
    num_blocks = 8
    a = sparse.pad_to_block_multiple(coo.todense(), num_blocks)
    ell = sparse.block_ell_from_coo(coo, num_blocks)
    blocks = _dense_blocks(a, num_blocks)
    for d in range(num_blocks):
        want = np.asarray(ranky.lonely_rows(jnp.asarray(blocks[d])))
        got = np.asarray(ranky.sparse_lonely_rows(
            ell.col_rows[d], ell.col_vals[d], ell.m))
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(ranky.row_adjacency_sparse(ell)),
        np.asarray(ranky.row_adjacency(jnp.asarray(a))))


@pytest.mark.parametrize("on_device", [False, True], ids=["numpy", "device"])
@pytest.mark.parametrize("num_blocks", [2, 8])
def test_lonely_counts_match_reference_without_retrace(num_blocks, on_device):
    """The per-block lonely counts of a BlockEll (numpy- or device-backed)
    equal the literal reference and the dense path; a second call with
    the same shapes reuses the compiled program (no jaxpr trace)."""
    coo = _coo()
    a = sparse.pad_to_block_multiple(coo.todense(), num_blocks)
    want = tuple(int(ranky.ref_lonely_rows(b).sum())
                 for b in _dense_blocks(a, num_blocks))
    assert sum(c > 0 for c in want) >= 2  # lonely rows in several blocks
    ell = sparse.block_ell_from_coo(coo, num_blocks)
    if on_device:
        ell = jax.device_put(ell)
        assert isinstance(ell.col_rows, jax.Array)
    assert ranky.lonely_rows_per_block(ell, num_blocks) == want
    assert ranky.lonely_rows_per_block(a, num_blocks) == want
    with jaxpr_traces() as traces:
        counts = ranky.lonely_counts(ell, num_blocks)
        again = ranky.lonely_rows_per_block(ell, num_blocks)
    assert traces[0] == 0
    assert counts.shape == (num_blocks,)
    assert tuple(int(x) for x in np.asarray(counts)) == again == want


@pytest.mark.parametrize("method", ["random", "neighbor", "neighbor_random"])
def test_sparse_repair_invariants(method):
    """Densified sparse repair obeys the dense-checker invariants: at
    most one new entry per row, value 1, only on lonely rows, and for
    neighbor entries only at neighbor-candidate columns."""
    coo = _coo(seed=9)
    num_blocks = 8
    a = sparse.pad_to_block_multiple(coo.todense(), num_blocks)
    ell = sparse.block_ell_from_coo(coo, num_blocks)
    rep = ranky.split_and_repair(ell, num_blocks, method, KEY)
    before = np.asarray(ell.todense_blocks())
    after = np.asarray(rep.todense_blocks())
    adj = np.asarray(ranky.row_adjacency(jnp.asarray(a)))
    total_new = 0
    for d in range(num_blocks):
        new = after[d] - before[d]
        lonely = ranky.ref_lonely_rows(before[d])
        rows, cols = np.nonzero(new)
        total_new += rows.size
        assert np.all(new[rows, cols] == 1.0)
        assert np.unique(rows).size == rows.size  # <= 1 repair per row
        assert lonely[rows].all()                 # only lonely rows
        if method in ("random", "neighbor_random"):
            assert not ranky.ref_lonely_rows(after[d]).any()
        if method == "neighbor":
            present = (before[d] != 0).astype(np.float32)
            cand = (adj.astype(np.float32) @ present) > 0
            assert cand[rows, cols].all()
    assert total_new > 0, "dataset must exhibit the rank problem"


def test_sparse_random_checker_bit_identical_to_dense():
    """The random checker draws the identical (M,)-shaped column sample,
    so sparse and dense repairs agree exactly for the same key."""
    coo = _coo()
    num_blocks = 8
    a = sparse.pad_to_block_multiple(coo.todense(), num_blocks)
    ell = sparse.block_ell_from_coo(coo, num_blocks)
    rep_sparse = ranky.split_and_repair(ell, num_blocks, "random", KEY)
    rep_dense = ranky.split_and_repair(jnp.asarray(a), num_blocks,
                                       "random", KEY)
    np.testing.assert_array_equal(
        np.asarray(rep_sparse.todense_blocks()), np.asarray(rep_dense))


# ---------------------------------------------------------------------------
# Exact grams (the E/R cross terms) and right vectors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", list(ranky.METHODS))
def test_sparse_gram_exact_for_repaired_blocks(method):
    coo = _coo(seed=3)
    num_blocks = 8
    ell = sparse.block_ell_from_coo(coo, num_blocks)
    rep = ranky.split_and_repair(ell, num_blocks, method, KEY)
    got = np.asarray(lsvd.gram_stack(rep))
    dense = np.asarray(rep.todense_blocks())
    want = np.einsum("dmn,dkn->dmk", dense, dense)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sparse_right_vectors_match_dense():
    coo = _coo(seed=3)
    num_blocks = 4
    ell = sparse.block_ell_from_coo(coo, num_blocks)
    rep = ranky.split_and_repair(ell, num_blocks, "neighbor_random", KEY)
    a_rep = np.asarray(rep.todense())
    u, s = lsvd.local_svd_exact(jnp.asarray(a_rep))
    for d in range(num_blocks):
        got = lsvd.sparse_right_vectors(
            ell.col_ids[d], ell.col_rows[d], ell.col_vals[d],
            rep.repair_cols[d], rep.repair_mask[d], ell.width, u, s)
        blk = jnp.asarray(a_rep[:, d * ell.width:(d + 1) * ell.width])
        want = lsvd.right_vectors(blk, u, s)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# (U, S) parity of the sparse pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", list(ranky.METHODS))
def test_sparse_ranky_svd_matches_repaired_truth(method):
    """Paper evaluation protocol on the sparse path: the pipeline result
    must equal the exact SVD of the (sparse-)repaired matrix."""
    coo = _coo(seed=5, n=512)
    num_blocks = 8
    ell = sparse.block_ell_from_coo(coo, num_blocks)
    key = jax.random.PRNGKey(3)
    repaired = np.asarray(
        ranky.split_and_repair(ell, num_blocks, method, key).todense())
    s_true = np.linalg.svd(repaired, compute_uv=False)
    u, s = ranky.ranky_svd(ell, num_blocks=num_blocks, method=method,
                           merge_mode="gram", key=key)
    np.testing.assert_allclose(np.asarray(s), s_true, rtol=2e-3, atol=2e-3)
    g = np.asarray(u).T @ np.asarray(u)
    np.testing.assert_allclose(g, np.eye(ell.m), atol=1e-3)


@pytest.mark.parametrize("merge_mode", ["proxy", "gram"])
def test_sparse_ranky_svd_matches_dense_path(merge_mode):
    """With method='none' the sparse and dense pipelines factor the same
    matrix — (U, S) must agree to numerical precision."""
    coo = _coo(n=1024, density=0.01)
    num_blocks = 4
    a = sparse.pad_to_block_multiple(coo.todense(), num_blocks)
    ell = sparse.block_ell_from_coo(coo, num_blocks)
    s_true = np.linalg.svd(a, compute_uv=False)[: ell.m]
    _, s = ranky.ranky_svd(ell, num_blocks=num_blocks, method="none",
                           merge_mode=merge_mode, local_mode="gram")
    np.testing.assert_allclose(np.asarray(s), s_true, rtol=1e-3, atol=1e-3)
    _, s_dense = ranky.ranky_svd(jnp.asarray(a), num_blocks=num_blocks,
                                 method="none", merge_mode=merge_mode,
                                 local_mode="gram")
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_dense),
                               rtol=1e-5, atol=1e-5)


def test_sparse_local_svd_mode_rejected():
    ell = sparse.block_ell_from_coo(_coo(), 8)
    with pytest.raises(ValueError, match="gram-native"):
        ranky.ranky_svd(ell, num_blocks=8, method="none",
                        merge_mode="proxy", local_mode="svd")


def test_sparse_hierarchical_matches_flat():
    coo = _coo(n=1024, density=0.01)
    a = sparse.pad_to_block_multiple(coo.todense(), 16)
    ell = sparse.block_ell_from_coo(coo, 16)
    s_true = np.linalg.svd(a, compute_uv=False)[: ell.m]
    _, s = hierarchical_ranky_svd(ell, num_blocks=16, fanout=4,
                                  method="none")
    np.testing.assert_allclose(np.asarray(s), s_true, rtol=1e-3, atol=1e-3)
