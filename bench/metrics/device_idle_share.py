"""``device_idle_share`` (%): the share of the traced window in which no
operation ran on the device, 100 * (1 - busy / window), from the device
trace. It moves ``requests_per_s``: host work between dispatches is idle
device time."""


def read(ctx) -> float | None:
    t = ctx.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
