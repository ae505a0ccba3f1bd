"""Device: percent of the profiled window in which no operation ran on the
device (1 - union of device-op intervals over the window)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0 or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
