"""device_idle_share: 1 - (union of the device's operation intervals /
traced window), in %, mean over the cell's devices (profiler trace)."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s())
