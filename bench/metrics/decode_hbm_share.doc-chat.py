"""The HBM floor of a decode step over its time: the median over the
window's decode steps of the least bytes each must move (the weights,
each active slot's recurrent state read and written and its cached K/V
read) at the chip's HBM rate, over the median engine decode step."""

import statistics

from harness import work_hybrid as H


def read(ctx):
    rec, times = ctx.get("record"), ctx.get("step_times")
    if rec is None or not times:
        return None
    floors = [H.decode_floor_bytes(ctx["config"], decoded)
              for _, _, decoded in rec.steps if decoded]
    if not floors:
        return None
    least = statistics.median(floors) / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / statistics.median(times)
