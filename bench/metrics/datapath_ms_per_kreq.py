"""``datapath_ms_per_kreq`` (ms/kreq): device time of the batched
datapath scan (``core/simulator.py``: the two-level or single-level
batch programs) per 1,000 host requests of the traced window."""

PROGRAMS = (r"simulate_two_level_batch", r"simulate_single_level_batch")


def read(ctx) -> float | None:
    return ctx.ms_per_kreq(PROGRAMS)
