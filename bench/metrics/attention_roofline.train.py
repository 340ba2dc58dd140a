"""The flash attention kernels' share of their roofline in the traced
steps: each layer's causal forward and its gradient (from the model's
shapes) over the device time of the flash_fwd and flash_bwd kernels."""

from harness import work as W


def read(ctx):
    red, win = ctx.get("trace"), ctx.get("window")
    if red is None or not win or not win["traced_steps"]:
        return None
    t = sum(s for k, s in red["kernel_s"].items()
            if k.startswith("flash") and "decode" not in k)
    if t <= 0:
        return None
    cfg, tr = ctx["config"], ctx["traffic"]
    per_layer = W.attention_prefill(cfg, tr["seq"], tr["batch"]).seconds(
        ctx["peak"]) + W.attention_backward(cfg, tr["seq"], tr["batch"]) \
        .seconds(ctx["peak"])
    least = per_layer * cfg["program"]["n_layers"] * win["traced_steps"]
    return 100.0 * least / t
