"""Products in the precision just below the one the configurations state.

The configurations state float32 contractions at ``HIGHEST``.  The next
precision down is ``HIGH``: each float32 operand is split into two
bfloat16 parts (``x = hi + lo``) and a product keeps three of the four
partial products, ``hi*hi + hi*lo + lo*hi``, accumulated in float32 —
what a TPU does for ``Precision.HIGH``.  Products of bfloat16 values are
exact in float32, so the emulation gives the same numbers on any
backend.  The controls of ``bench/tests`` put the plain reference,
computed this way, in the program's place.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp


def bf16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16, held in float32."""
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def split(x: np.ndarray):
    hi = bf16(x)
    return hi, bf16(np.asarray(x, np.float32) - hi)


def matmul_high(a, b) -> np.ndarray:
    """``a @ b`` at ``HIGH``: dense or scipy-sparse float operands."""
    def parts(x):
        if sp.issparse(x):
            x = x.tocsr().astype(np.float32)
            hi = x.copy()
            hi.data = bf16(x.data)
            lo = x.copy()
            lo.data = bf16(x.data - hi.data)
            return hi, lo
        return split(x)

    a_hi, a_lo = parts(a)
    b_hi, b_lo = parts(b)
    out = a_hi @ b_hi + a_hi @ b_lo + a_lo @ b_hi
    return np.asarray(out.toarray() if sp.issparse(out) else out, np.float32)
