"""Pallas kernels for ETICA's between-interval maintenance (paper §4.2).

The three maintenance scatters over stacked ``[V, S, W]`` cache states —
eviction (membership mask + dirty-flush count), promotion (in-order
queue drain into each set's lowest free way), and the background cleaner
(age-cutoff dirty flush) — tiled over ``(V, S)`` with the per-VM queue
streamed through SMEM, plus the fused per-interval
dispatch that chains popularity refresh, queue building, eviction,
promotion and cleaning into ONE jitted executable with no host
round-trips between stages (``ops.maintenance_interval``).
"""
from .ops import (clean, evict, promote, maintenance_interval,  # noqa: F401
                  serving_maintenance)
