"""Matmul precision of the SVD path's float32 contractions, in one place.

On a TPU an f32 ``dot`` at DEFAULT precision is one bfloat16 pass, about
three significant digits per product.  The grams, sketches, panel and
merge products, the left-factor updates, the served scores and the
SVD-path Pallas kernels (with their ``kernels/ref.py`` oracles) pass
:data:`MATMUL` explicitly, so a result does not depend on the entry point
that reached it or on a caller's ``jax.default_matmul_precision``.
Products of 0/1 indicator matrices (row adjacency, neighbor candidates)
are exact in one bfloat16 pass and keep the default.  CPU results do not
depend on this setting.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MATMUL = jax.lax.Precision.HIGHEST


def mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a @ b`` at :data:`MATMUL` (read when traced)."""
    return jnp.matmul(a, b, precision=MATMUL)
