"""`device_idle.sat`: percent of the traced answer's span in which no
operation ran on the chip (1 - busy / window, from the profiler trace)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
