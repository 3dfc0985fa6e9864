"""``sizing_ms_per_kreq`` (ms/kreq): device time of the reuse-distance
decompositions and sizing reductions (``core/reuse.py``,
``kernels/reuse_distance``, the Pallas ``count_between`` kernel
included) per 1,000 host requests of the traced window. In ETICA this is
the POD(RO) and POD(WBWO) sizing and the maintenance's TRD decompose."""

PROGRAMS = (r"_decompose_vmapped", r"_sizing_reduce_vmapped",
            r"count_between")


def read(ctx) -> float | None:
    return ctx.ms_per_kreq(PROGRAMS)
