"""The GEMM kernels' share of their roofline in the traced steps: the
least time of every projection's forward and gradient products (from
the model's shapes) over the device time of every kernel whose name
holds "matmul" (forward, jvp and transpose kernels)."""

from harness import work as W


def read(ctx):
    red, win = ctx.get("trace"), ctx.get("window")
    if red is None or not win or not win["traced_steps"]:
        return None
    t = sum(s for k, s in red["kernel_s"].items() if "matmul" in k)
    if t <= 0:
        return None
    tr = ctx["traffic"]
    least = W.least_s(W.train_gemm_calls(ctx["config"], tr["batch"],
                                         tr["seq"]), ctx["peak"])
    return 100.0 * least * win["traced_steps"] / t
