"""Host time per solve, in ms, of the front door's conversion
(``core/api.py`` ``svd``, span ``svd.convert``): COO -> ``BlockEll`` and
its copy to the device.  The profiler's host events of that name in the
traced window, over the solves completed there.  A trace with no device
plane (not a chip's) reads nothing."""

SPAN = "svd.convert"


def read(red, ctx):
    if not red.ops:
        return None
    t = sum(e - s for name, s, e in red.host if name == SPAN)
    if t <= 0 or not ctx.get("solves"):
        return None
    return 1e-6 * t / ctx["solves"]
