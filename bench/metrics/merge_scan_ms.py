"""Device time of the scan-window program per batch, in ms: the jitted
``lax.scan`` of ``stream/window.py`` (its function is named ``run``)
that repairs, forms each batch's panel and merges it into the state
(``core/hierarchy.py`` ``merge_svd``)."""

MODULES = ("jit_run",)


def read(red, ctx):
    t = red.module_s(lambda name: name in MODULES)
    if t <= 0 or not ctx.get("batches"):
        return None
    return 1e3 * t / ctx["batches"]
