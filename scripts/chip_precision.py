#!/usr/bin/env python3
"""How much accuracy the SVD path's contractions keep at each TPU matmul
precision, against chip_smoke.py's float32-level bounds.

    python3 scripts/chip_precision.py

For DEFAULT (one bfloat16 pass), HIGH (three) and HIGHEST (six) in turn,
with ``repro.precision.MATMUL`` set before anything is traced:

* **gram** — the paper matrix's repaired (M, M) gram, through the
  compiled ``sparse_gram`` kernel and through XLA.  Its eigenvalues,
  taken in float64 on the host, against the float64 SVD's s^2: the
  one-shot "gram" error, with the device eigh left out.
* **panel** — ``A^T U`` (the right-vector contraction) for the float64
  left vectors, normwise against float64: the contraction behind the
  one-shot "recon" error.
* **serve** — the fused top-k kernel's scores over random rank-32
  factors, as multiples of chip_smoke's per-score tolerance.

Prints one JSON line per precision and path; a path the compiler
refuses at that precision (Mosaic lowers no HIGH dot) prints the
refusal.  Needs a TPU.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import chip_smoke as cs  # noqa: E402
from repro import precision  # noqa: E402
from repro.core import api, ranky, sparse  # noqa: E402
from repro.core import svd as lsvd  # noqa: E402
from repro.kernels import ops  # noqa: E402

PRECISIONS = ("DEFAULT", "HIGH", "HIGHEST")


def main() -> int:
    if jax.default_backend() != "tpu":
        raise SystemExit(f"no TPU: JAX's default backend is "
                         f"{jax.default_backend()!r}")
    coo = cs.paper_matrix()
    cfg = api.SolveConfig(method="neighbor_random", num_blocks=8)
    a = cs.repaired_dense(coo, cfg, 8)
    u_ref, s_ref, _ = np.linalg.svd(a, full_matrices=False)
    panel_ref = a.T @ u_ref
    blocks = ranky.split_and_repair(sparse.block_ell_from_coo(coo, 8), 8,
                                    cfg.method, cfg.resolved_key())
    u32 = jnp.asarray(u_ref, jnp.float32)
    n_cols = coo.shape[1]

    rng = np.random.default_rng(7)
    v = rng.standard_normal((62_423, 32)).astype(np.float32)
    qs = (rng.standard_normal((64, 32))
          * np.geomspace(300, 30, 32)).astype(np.float32)
    scores_ref = qs.astype(np.float64) @ v.astype(np.float64).T
    tol = cs.SERVE_TOL * (np.abs(qs.astype(np.float64))
                          @ np.abs(v.astype(np.float64)).T).max(axis=1)

    def gram_error(use_kernel):
        g = jax.jit(lambda b: lsvd.gram_stack(
            b, use_kernel=use_kernel).sum(axis=0))(blocks)
        lam = np.linalg.eigvalsh(np.asarray(g, np.float64))[::-1]
        return {"error": float(np.max(np.abs(lam - s_ref ** 2))
                               / s_ref[0] ** 2),
                "bound": cs.ONESHOT_TOL["gram"]}

    def panel_error():
        p = jax.jit(lambda b, u: ranky.right_vectors_stack(
            b, u, jnp.ones((u.shape[1],), jnp.float32)))(blocks, u32)
        p = np.asarray(p, np.float64)[:n_cols]
        return {"error": float(np.linalg.norm(p - panel_ref)
                               / np.linalg.norm(panel_ref)),
                "bound": cs.ONESHOT_TOL["recon"]}

    def serve_error():
        vals, idx = jax.jit(lambda q, f: ops.topk_score(q, f, 10))(qs, v)
        got = np.asarray(vals, np.float64)
        want = np.take_along_axis(scores_ref, np.asarray(idx), axis=1)
        return {"score_over_tol": float((np.abs(got - want)
                                         / tol[:, None]).max()),
                "bound": 1.0}

    paths = {"gram kernel": lambda: gram_error(True),
             "gram xla": lambda: gram_error(False),
             "panel": panel_error, "serve kernel": serve_error}
    for name in PRECISIONS:
        precision.MATMUL = getattr(jax.lax.Precision, name)
        jax.clear_caches()
        for path, measure in paths.items():
            try:
                line = measure()
            except NotImplementedError as e:      # a lowering refuses it
                line = {"refused": str(e)}
            print(json.dumps({"precision": name, "path": path, **line}),
                  flush=True)
    print(json.dumps({"device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
