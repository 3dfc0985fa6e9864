"""``window_compiles`` (compiles): XLA programs compiled or loaded from
the persistent cache inside the measured window, counted by JAX's
monitoring events. Set-up warms every shape the cell uses, so it reads
0; anything else is compilation paid inside ``requests_per_s``."""


def read(ctx) -> float | None:
    return float(ctx.window_compiles)
