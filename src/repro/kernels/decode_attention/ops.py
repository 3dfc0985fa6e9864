"""Jitted wrapper for paged decode attention over the two-tier KV pool.

Used by `repro.kvcache`: the controller maintains the page table (which
pages are HBM-resident per the POD/popularity policy); this op consumes
it directly — no contiguous KV copy is ever materialized.
"""
from __future__ import annotations

from .kernel import paged_decode_attention


def decode_attention(q, kv_pool, page_table, lengths, *,
                     interpret: bool | None = None):
    """q: [B, H, D]; kv_pool: (k_pages, v_pages) [NP, PS, Hkv, D]."""
    k_pages, v_pages = kv_pool
    return paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                                  interpret=interpret)
