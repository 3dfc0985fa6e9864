"""``maintenance_ms_per_kreq`` (ms/kreq): device time of ETICA's fused
maintenance program (``kernels/maintenance``: popularity-table update,
queue sorts and the Pallas evict/promote/clean kernels) per 1,000 host
requests of the traced window."""

PROGRAMS = (r"_maintenance_impl",)


def read(ctx) -> float | None:
    return ctx.ms_per_kreq(PROGRAMS)
