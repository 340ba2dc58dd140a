"""Engine admission time per admission, in the window: the engine's
own prefill_time counter (bucket prefill, one-token remainder steps,
slot copy, first-token read) over its admissions."""


def read(ctx):
    c = ctx.get("counters")
    if not c or not c["admissions"]:
        return None
    return 1000.0 * c["prefill_s"] / c["admissions"]
