"""How late the load generator sent a wave that found the server idle,
95th percentile in ms, by the host's clock: a starved generator must not
read as a fast server."""

import numpy as np


def read(red, ctx):
    late = ctx.get("late_ms")
    if not late:
        return None
    return float(np.percentile(np.asarray(late), 95))
