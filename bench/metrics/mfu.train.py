"""Forward and backward operations per step (no recomputation) times the
steps finished in the window, over the window's time and the chip's
bf16 peak."""

from harness import work as W


def read(ctx):
    win = ctx.get("window")
    if not win or not win["steps"]:
        return None
    tr = ctx["traffic"]
    flops = W.train_flops_step(ctx["config"], tr["batch"], tr["seq"]) \
        * win["steps"]
    return 100.0 * flops / win["window_s"] / ctx["peak"]["flops_bf16"]
