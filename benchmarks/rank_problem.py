"""The paper's MOTIVATION, reproduced: why rank-deficient blocks break
the distributed SVD, and how each Ranky checker fixes it.

In exact arithmetic the one-level proxy merge is unconditionally exact,
so the failure the paper observes (Table II e_u ~ 0.1 .. 0.6 vs Table
I/III ~ 1e-10) comes from the implementation: a rank-deficient block's
dead singular directions are numerically UNDETERMINED, and the reference
C pipeline ships d panel columns per block regardless of actual block
rank.  We emulate exactly that (ranky_svd(undetermined_tail=True)) and
measure e_sigma / e_u per method:

  none              -> many dead columns -> e_u blows up   (the problem)
  random            -> all blocks full rank -> clean        (Table I)
  neighbor          -> *unreachable* lonely rows stay dead -> partial
                       failures, worse e_u than random      (Table II)
  neighbor_random   -> clean                                (Table III)
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.paper_tables import align_signs, repaired_matrix
from repro.core import ranky, sparse


def run(rows=539, cols=17_088, density=4e-4, blocks=(8, 32), seed=2021,
        verbose=True):
    out = []
    coo = sparse.ensure_full_row_rank(
        sparse.random_bipartite(rows, cols, density, seed=seed,
                                weighted=True), seed=seed)
    a0 = coo.todense()
    for d in blocks:
        a = sparse.pad_to_block_multiple(a0, d).astype(np.float64)
        for method in ("none", "random", "neighbor", "neighbor_random"):
            key = jax.random.PRNGKey(seed + d)
            t0 = time.perf_counter()
            with jax.enable_x64(True):
                repaired = repaired_matrix(a, d, method, key)
                u_true, s_true, _ = np.linalg.svd(repaired,
                                                  full_matrices=False)
                u_hat, s_hat = ranky.ranky_svd(
                    jnp.asarray(a), num_blocks=d, method=method,
                    local_mode="svd", merge_mode="proxy",
                    undetermined_tail=True, key=key)
                u_hat = np.asarray(u_hat, np.float64)
                s_hat = np.asarray(s_hat, np.float64)
            dt = time.perf_counter() - t0
            e_sigma = float(np.abs(s_hat - s_true).sum())
            e_u = float(np.abs(align_signs(u_hat, u_true) - u_true).sum())
            still_lonely = int(sum(
                ranky.ref_lonely_rows(b).sum()
                for b in sparse.split_blocks(repaired, d)))
            row = {"blocks": d, "method": method, "e_sigma": e_sigma,
                   "e_u": e_u, "unfixed_lonely": still_lonely,
                   "seconds": dt}
            out.append(row)
            if verbose:
                print(f"  D={d:3d} {method:16s} e_sigma={e_sigma:.3e} "
                      f"e_u={e_u:.3e} unfixed_lonely={still_lonely:5d}",
                      flush=True)
    return out


if __name__ == "__main__":
    run()
