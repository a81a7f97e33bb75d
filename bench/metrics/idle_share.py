"""Share of the window in which no operation ran on the device, from the
profiler trace (busy is the union of the device's op intervals)."""


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
