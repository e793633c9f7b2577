"""Share of its roofline that the fused score + top-k kernel
(``kernels/topk_score.py``) reaches, in %.

The least time for one wave's needed work over the kernel's device time
per wave:

* operations 2 * B * n * k (the (B, k) x (k, n) score product);
* bytes 4 * n * k for the float32 factors, 4 * B * k for the queries and
  8 * B * k_top for the ids and scores returned.
"""

from bench import peaks

OPS = ("%topk_score",)


def wave_work(batch: int, items: int, rank: int, k_top: int):
    return (2.0 * batch * items * rank,
            4.0 * items * rank + 4.0 * batch * rank + 8.0 * batch * k_top)


def read(red, ctx):
    t = red.op_s(lambda name: name.startswith(OPS))
    if t <= 0 or not ctx.get("waves"):
        return None
    flops, nbytes = wave_work(ctx["batch"], ctx["items"], ctx["rank"],
                              ctx["k_top"])
    share, bound = peaks.roofline_share(flops, nbytes, t / ctx["waves"],
                                        ctx["kind"])
    ctx["log"](f"topk_roofline: {flops:.6g} operations, {nbytes:.6g} bytes "
               f"per wave in {t / ctx['waves']!r} s of kernel time; the "
               f"{bound} bound binds")
    return share
