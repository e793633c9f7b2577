"""Device time of the batch factor's eigh program per batch, in ms: the
2,048-square ``core/svd.py`` ``merge_grams_eigh`` that
``stream/ingest.py`` ``batch_left_vectors`` runs once per batch."""

MODULES = ("jit_merge_grams_eigh",)


def read(red, ctx):
    t = red.module_s(lambda name: name in MODULES)
    if t <= 0 or not ctx.get("batches"):
        return None
    return 1e3 * t / ctx["batches"]
