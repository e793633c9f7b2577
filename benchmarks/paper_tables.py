"""Paper Tables I-III: e_sigma / e_u of the distributed Ranky SVD vs the
exact SVD, for each checker method and block count.

Evaluation protocol (matches the paper): the checker repairs the input
matrix; ground truth is the full SVD of the REPAIRED matrix (the repair
is a preprocessing of the input, so both sides see the same matrix); the
distributed pipeline must recover it.  e_u aligns column signs first
(singular vectors are defined up to sign).

The paper's kariyer.net matrix is proprietary — we synthesize a matrix
with its published shape (539 x 170897) and a heavy-tailed bipartite
degree profile that exhibits the same rank problem (lonely rows under
column blocking).  Default runs use a 1/10-width version so the whole
table suite stays CPU-friendly; --full reproduces the exact shape.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import ranky, sparse

METHODS = {"table1": "random", "table2": "neighbor",
           "table3": "neighbor_random"}


def align_signs(u_hat: np.ndarray, u: np.ndarray) -> np.ndarray:
    """Flip u_hat column signs to match u."""
    signs = np.sign(np.sum(u_hat * u, axis=0))
    signs[signs == 0] = 1.0
    return u_hat * signs[None, :]


def repaired_matrix(a: np.ndarray, num_blocks: int, method: str,
                    key) -> np.ndarray:
    m, n = a.shape
    fixed = ranky.split_and_repair(jnp.asarray(a), num_blocks, method, key)
    return np.asarray(jnp.transpose(fixed, (1, 0, 2)).reshape(m, n),
                      np.float64)


def run_table(method: str, *, rows=539, cols=17_088, density=2e-3,
              blocks=(2, 3, 4, 8, 10, 16, 32), seed=2020,
              weighted=True, verbose=True):
    """One paper table.  Returns list of row dicts.

    The pipeline runs in float64 (the paper's C/MKL dgesvd is double
    precision; its 1e-13 errors are unreachable in f32).  ``weighted``
    edges keep the spectrum non-degenerate — binary adjacency matrices
    have repeated singular values whose individual vectors are defined
    only up to rotation, which would contaminate e_u with basis
    ambiguity rather than algorithmic error (see EXPERIMENTS.md).
    """
    coo =sparse.ensure_full_row_rank(
        sparse.random_bipartite(rows, cols, density, seed=seed,
                                weighted=weighted), seed=seed)
    a0 = coo.todense()
    out = []
    for d in blocks:
        a = sparse.pad_to_block_multiple(a0, d).astype(np.float64)
        key = jax.random.PRNGKey(seed + d)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            repaired = repaired_matrix(a, d, method, key)
            # exact truth on the repaired matrix (f64)
            u_true, s_true, _ = np.linalg.svd(repaired, full_matrices=False)
            # distributed pipeline (paper-faithful: block SVD + proxy SVD)
            u_hat, s_hat = ranky.ranky_svd(
                jnp.asarray(a), num_blocks=d, method=method,
                local_mode="svd", merge_mode="proxy", key=key)
            u_hat = np.asarray(u_hat, np.float64)[:, : s_true.shape[0]]
            s_hat = np.asarray(s_hat, np.float64)[: s_true.shape[0]]
        dt = time.perf_counter() - t0
        e_sigma = float(np.abs(s_hat - s_true).sum())
        e_u = float(np.abs(align_signs(u_hat, u_true) - u_true).sum())
        lonely = int(sum(
            (~(b != 0).any(axis=1)).sum()
            for b in sparse.split_blocks(a, d)))
        row = {"blocks": d, "block_size": f"{rows}x{a.shape[1] // d}",
               "e_sigma": e_sigma, "e_u": e_u, "lonely_rows": lonely,
               "seconds": dt}
        out.append(row)
        if verbose:
            print(f"  D={d:4d} {row['block_size']:>12s} "
                  f"e_sigma={e_sigma:.3e} e_u={e_u:.3e} "
                  f"lonely={lonely:5d} ({dt:.1f}s)", flush=True)
    return out


def main(full: bool = False):
    kw = {}
    if full:
        # exact paper shape + all 9 block counts (slow on one CPU core:
        # the f64 per-block SVDs at D=64/128 dominate)
        kw = {"cols": 170_897, "density": 5e-4,
              "blocks": (2, 3, 4, 8, 10, 16, 32, 64, 128)}
    results = {}
    for table, method in METHODS.items():
        print(f"{table} ({method}Checker):")
        results[table] = run_table(method, **kw)
    return results


if __name__ == "__main__":
    import sys
    main(full="--full" in sys.argv)
