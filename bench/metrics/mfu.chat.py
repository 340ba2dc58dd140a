"""Model operations of every prompt and output token the window
processed, over the window's time and the chip's bf16 peak."""

from harness import serve


def read(ctx):
    rec = ctx.get("record")
    if rec is None or not rec.steps:
        return None
    flops = serve.model_flops(ctx["config"], rec)
    return 100.0 * flops / ctx["window_s"] / ctx["peak"]["flops_bf16"]
