"""Jitted wrapper: per-block popularity from a window's DistResult.

Host side maps block addresses to dense segment ids (np.unique), the
kernel does the fused exp + segment reduction; mirrors
``repro.core.popularity.{contributions, block_scores}``.
"""
from __future__ import annotations

import numpy as np

from .kernel import popularity


def block_popularity(addr, dist, served, cache_size, *,
                     interpret: bool | None = None):
    """Returns (unique_addrs, scores) for one maintenance window."""
    addr = np.asarray(addr)
    uniq, seg = np.unique(addr, return_inverse=True)
    scores = popularity(dist, served, seg.astype(np.int32),
                        num_blocks=int(uniq.size), cache_size=cache_size,
                        interpret=interpret)
    return uniq, np.asarray(scores)
