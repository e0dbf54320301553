"""device_idle_share: share of the traced window in which no operation ran
on the device, 1 - busy / window, %."""


def read(rec):
    t = rec.trace
    if t is None or t.window_s <= 0 or not t.ops:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
