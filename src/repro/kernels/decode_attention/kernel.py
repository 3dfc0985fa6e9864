"""Pallas TPU kernel: paged flash-decode over the two-tier KV pool.

This is the ETICA-integrated serving hot spot (DESIGN.md §2): decode
reads KV *pages* whose HBM residency is decided by the POD/popularity
controller; the page table indirection is resolved with Pallas *scalar
prefetch* — the page_table (and per-sequence lengths) are prefetched to
SMEM, and the KV BlockSpec index_map dereferences them so each grid step
DMAs exactly the page it needs from the pool (no gather materialization,
the vLLM-on-TPU pattern).

Grid (B, n_pages), pages innermost. Each step DMAs one whole pool page
— every KV head, so the ``(Hkv, D)`` block dims equal the pool's — and
scores all query heads against it in one matmul: the page flattens to
``[PS*Hkv, D]`` rows (token-major) and a head mask keeps each query head
on its own KV head's rows. Online-softmax state for all ``H`` query heads
lives in VMEM scratch; output written on the final page step. Invalid
(beyond-length) tokens are masked in-tile. The compiled path needs
``Hkv`` to fill whole sublane tiles (a multiple of 8 for f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(page_table_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, ps: int, hkv: int, groups: int,
            n_pages: int, scale: float):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    h, d = q_ref.shape[1], q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32) * scale             # [H, D]
    k = k_ref[0].reshape(ps * hkv, d).astype(jnp.float32)  # [PS*Hkv, D]
    v = v_ref[0].reshape(ps * hkv, d).astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)  # [H, PS*Hkv]
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    tok = p * ps + col // hkv
    valid = (row // groups == col % hkv) & (tok < lengths_ref[b])
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    pexp = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode(q, k_pages, v_pages, page_table, lengths, *, interpret):
    b, h, d = q.shape
    np_, ps, hkv, _ = k_pages.shape
    n_pages = page_table.shape[1]
    groups = h // hkv
    scale = d ** -0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=[
            # q: all H query heads of sequence b (head-major: h = kv*G + g)
            pl.BlockSpec((1, h, d), lambda b_, p_, pt, ln: (b_, 0, 0)),
            # k/v: the whole pool page named by the page table
            pl.BlockSpec((1, ps, hkv, d),
                         lambda b_, p_, pt, ln: (pt[b_, p_], 0, 0, 0)),
            pl.BlockSpec((1, ps, hkv, d),
                         lambda b_, p_, pt, ln: (pt[b_, p_], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b_, p_, pt, ln: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, ps=ps, hkv=hkv, groups=groups,
                          n_pages=n_pages, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(page_table, lengths, q, k_pages, v_pages)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           interpret: bool | None = None):
    """q: [B, H, D]; k_pages/v_pages: [NP, PS, Hkv, D];
    page_table: [B, n_pages]; lengths: [B]. Returns [B, H, D]."""
    return _paged_decode(q, k_pages, v_pages, page_table, lengths,
                         interpret=resolve_interpret(interpret))
