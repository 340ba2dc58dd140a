"""Median engine decode step in the window, as the engine times it:
the jitted step's dispatch through the host read of every slot's
logits."""

import statistics


def read(ctx):
    times = ctx.get("step_times")
    if not times:
        return None
    return 1000.0 * statistics.median(times)
