"""The decode attention kernel's share of its roofline in the traced
steps, as decode_attention_roofline.chat reads it, over the attention
layers of an interleaved model (not all its layers): per call the cached
keys and values of the active slots read once, over the device time of
flash_decode. Admission remainder steps are calls with one slot each."""

from harness import work as W, work_hybrid as H


def read(ctx):
    red, steps = ctx.get("trace"), ctx.get("traced_steps")
    if red is None or not steps:
        return None
    t = red["kernel_s"].get("flash_decode", 0.0)
    if t <= 0:
        return None
    cfg, peak = ctx["config"], ctx["peak"]
    least = 0.0
    for _, admitted, decoded in steps:
        if decoded:
            w = W.ZERO
            for c in decoded:
                w = w + W.attention_decode(cfg, c)
            least += w.seconds(peak)
        for p, lb in admitted:
            least += sum(W.attention_decode(cfg, i + 1).seconds(peak)
                         for i in range(lb, p))
    return 100.0 * least * H.layer_counts(cfg)[1] / t
