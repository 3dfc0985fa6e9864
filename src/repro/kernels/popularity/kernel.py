"""Pallas TPU kernel: fused Eq. 1 popularity scoring + segment reduction.

One pass over the access stream computes the per-access contribution
``exp(-dist/cacheSize)`` (VPU transcendental) and reduces it into
per-block scores without materializing the contribution vector in HBM.
The reduction is a tiled one-hot accumulation: for an access tile of TI
and a block-id tile of TB, ``acc[b] += sum_i contrib[i] * [seg[i] == b]``
— an outer-product-shaped reduction that maps onto the VPU (and the MXU
for f32 when TB = 128k lanes align).

Grid: (num_block_tiles, num_access_tiles); the access dimension is
innermost so each output tile accumulates across access tiles. Accesses
ride as ``[TI, 1]`` columns (sublanes), block ids as a ``[1, TB]`` row
(lanes); the cache size is an SMEM scalar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_TI = 1024
DEFAULT_TB = 512


def _kernel(cs_ref, dist_ref, served_ref, seg_ref, out_ref, *,
            ti: int, tb: int):
    b_blk = pl.program_id(0)
    i_blk = pl.program_id(1)

    @pl.when(i_blk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dist = dist_ref[...].astype(jnp.float32)       # [TI, 1]
    served = served_ref[...] > 0                   # [TI, 1]
    seg = seg_ref[...]                             # [TI, 1]
    cs = jnp.maximum(cs_ref[0], 1.0)

    contrib = jnp.where(served & (dist >= 0), jnp.exp(-dist / cs), 0.0)

    b_idx = b_blk * tb + jax.lax.broadcasted_iota(jnp.int32, (ti, tb), 1)
    onehot = (seg == b_idx).astype(jnp.float32)    # [TI, TB]
    out_ref[...] += jnp.sum(contrib * onehot, axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("num_blocks", "ti", "tb", "interpret"))
def _popularity(dist, served, seg, num_blocks, cache_size, *, ti, tb,
                interpret):
    n = dist.shape[0]
    ti = min(ti, max(8, 1 << (n - 1).bit_length()))
    n_pad = ((n + ti - 1) // ti) * ti
    tb = min(tb, max(128, 1 << (num_blocks - 1).bit_length()))
    nb_pad = ((num_blocks + tb - 1) // tb) * tb

    def col(x, fill):
        return jnp.pad(x, (0, n_pad - n), constant_values=fill)[:, None]

    dist = col(jnp.asarray(dist, jnp.int32), -1)
    served = col(jnp.asarray(served).astype(jnp.int32), 0)
    seg = col(jnp.asarray(seg, jnp.int32), nb_pad)  # out of every block tile
    cs = jnp.asarray(cache_size, jnp.float32).reshape(1)

    grid = (nb_pad // tb, n_pad // ti)
    out = pl.pallas_call(
        functools.partial(_kernel, ti=ti, tb=tb),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((ti, 1), lambda b, i: (i, 0)),
            pl.BlockSpec((ti, 1), lambda b, i: (i, 0)),
            pl.BlockSpec((ti, 1), lambda b, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, tb), lambda b, i: (0, b)),
        out_shape=jax.ShapeDtypeStruct((1, nb_pad), jnp.float32),
        interpret=interpret,
    )(cs, dist, served, seg)
    return out[0, :num_blocks]


def popularity(dist, served, seg, num_blocks: int, cache_size,
               *, ti: int = DEFAULT_TI, tb: int = DEFAULT_TB,
               interpret: bool | None = None):
    """Per-block popularity scores. seg[i] in [0, num_blocks)."""
    return _popularity(dist, served, seg, num_blocks, cache_size, ti=ti,
                       tb=tb, interpret=resolve_interpret(interpret))
