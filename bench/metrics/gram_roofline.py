"""Share of its roofline that the stream's batch gram reaches, in %.

The least time for the gram's needed work over the device time of the
program that repairs and grams each batch (``stream/window.py``
``_gram_single``: ``core/svd.py`` ``gram_stack`` on XLA or
``kernels/sparse_gram.py``).  The needed work is the batch's own, so it
reads the same whichever path runs it:

* operations 2 * sum_c deg_c^2 over the batch's stored columns (each
  column adds deg_c^2 products to the gram);
* bytes 8 per stored entry (row index and value) plus 4 * m^2 for the
  (m, m) float32 gram written out.
"""

import numpy as np

from bench import peaks

# jax.jit of a functools.partial has no name of its own: the gram
# program is the only "_unknown" module of a replay.
MODULES = ("jit__unknown",)


def batch_work(batch):
    """(operations, bytes) the gram of one (rows, cols, vals, shape)
    batch needs."""
    _, cols, vals, (m, _) = batch
    deg = np.bincount(np.asarray(cols)[np.asarray(vals) != 0])
    return 2.0 * float(np.sum(deg.astype(np.float64) ** 2)), \
        8.0 * float(np.count_nonzero(vals)) + 4.0 * m * m


def read(red, ctx):
    t = red.module_s(lambda name: name in MODULES)
    replay, batches = ctx.get("replay"), ctx.get("batches")
    if t <= 0 or not replay or not batches:
        return None
    work = np.array([batch_work(b) for b in replay])
    flops, nbytes = work.sum(axis=0) * (batches / len(replay))
    share, bound = peaks.roofline_share(flops, nbytes, t, ctx["kind"])
    ctx["log"](f"gram_roofline: {flops:.6g} operations, {nbytes:.6g} bytes "
               f"in {t!r} s of device time; the {bound} bound binds")
    return share
