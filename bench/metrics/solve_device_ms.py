"""Device time of the one-shot solve program per solve, in ms: the
executions of the jitted ``core/ranky.py`` ``solve_single`` in the
traced window over the solves completed there."""

MODULES = ("jit_solve_single",)


def read(red, ctx):
    t = red.module_s(lambda name: name in MODULES)
    if t <= 0 or not ctx.get("solves"):
        return None
    return 1e3 * t / ctx["solves"]
