"""Device time of the fold into ``u`` per batch, in ms: after each scan
window, ``stream/window.py`` ``ingest_window`` runs eagerly, per batch,
``u @ uk[:k]``, ``u_b @ uk[k:]`` and their concatenation, and slices of
the stacked scan outputs."""

MODULES = ("jit_matmul", "jit_concatenate", "jit_dynamic_slice",
           "jit_squeeze")


def read(red, ctx):
    t = red.module_s(lambda name: name in MODULES)
    if t <= 0 or not ctx.get("batches"):
        return None
    return 1e3 * t / ctx["batches"]
