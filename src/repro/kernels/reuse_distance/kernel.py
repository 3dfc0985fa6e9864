"""Pallas TPU kernel: tiled windowed distinct-count (stack distance).

PARDA-on-TPU (DESIGN.md §4): the O(N^2) pairwise predicate

    count[i] = sum_j [prev[i] < j < i] * touch[j] * [nt[j] >= i]

is tiled into (TI x TJ) blocks. Each grid step loads a TI-row column
strip of ``prev`` (``[TI, 1]``, rows on sublanes) and a TJ-wide row strip
of (touch, nt) (``[1, TJ]``, on lanes) into VMEM, evaluates the mask on
the VPU, and accumulates row sums into the ``[TI, 1]`` int32 output
block. The j grid dimension is innermost, so the output block (indexed
by i only) accumulates across j steps — the standard Pallas reduction
pattern; blocks entirely above the diagonal (every j >= every i) add
nothing and are skipped. VMEM footprint per step: TI*TJ mask + O(TI +
TJ) vectors; default 256 x 512 = 512KB of pred, well inside a v5e core's
16MB VMEM, with the mask dims multiples of the 8x128 VPU tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

DEFAULT_TI = 256
DEFAULT_TJ = 512


def _kernel(prev_ref, touch_ref, nt_ref, out_ref, *, ti: int, tj: int):
    i_blk = pl.program_id(0)
    j_blk = pl.program_id(1)

    @pl.when(j_blk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j_blk * tj < (i_blk + 1) * ti)   # some j < some i in the block
    def _accumulate():
        i_idx = i_blk * ti + jax.lax.broadcasted_iota(jnp.int32, (ti, tj), 0)
        j_idx = j_blk * tj + jax.lax.broadcasted_iota(jnp.int32, (ti, tj), 1)
        prev = prev_ref[...]               # [TI, 1]
        touch = touch_ref[...]             # [1, TJ] int32 (0/1)
        nt = nt_ref[...]                   # [1, TJ]
        m = (j_idx > prev) & (j_idx < i_idx) & (touch > 0) & (nt >= i_idx)
        out_ref[...] += jnp.sum(m.astype(jnp.int32), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("ti", "tj", "interpret"))
def _count_between(prev, touch, nt, *, ti, tj, interpret):
    n = prev.shape[0]
    ti = min(ti, max(8, 1 << (n - 1).bit_length()))
    tj = min(tj, max(128, 1 << (n - 1).bit_length()))
    n_pad = ((n + max(ti, tj) - 1) // max(ti, tj)) * max(ti, tj)
    pad = n_pad - n
    prev = jnp.pad(prev.astype(jnp.int32), (0, pad))
    touch = jnp.pad(touch.astype(jnp.int32), (0, pad))  # pad -> not touched
    nt = jnp.pad(nt.astype(jnp.int32), (0, pad), constant_values=-1)

    grid = (n_pad // ti, n_pad // tj)
    out = pl.pallas_call(
        functools.partial(_kernel, ti=ti, tj=tj),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ti, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, tj), lambda i, j: (0, j)),
            pl.BlockSpec((1, tj), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((ti, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        interpret=interpret,
    )(prev[:, None], touch[None, :], nt[None, :])
    return out[:n, 0]


def count_between(prev, touch, nt, *, ti: int = DEFAULT_TI,
                  tj: int = DEFAULT_TJ, interpret: bool | None = None):
    """count[i] = #{ j in (prev[i], i) : touch[j] and nt[j] >= i }.

    Inputs are 1-D int32 arrays of equal length; length is padded up to a
    tile multiple internally (padded j entries have touch = 0, padded i
    rows are discarded).
    """
    return _count_between(prev, touch, nt, ti=ti, tj=tj,
                          interpret=resolve_interpret(interpret))
