"""Pallas TPU kernel for the RG-LRU linear recurrence (Griffin /
RecurrentGemma's temporal-mixing core).

h_t = a_t * h_{t-1} + b_t over the sequence axis, per (batch, channel).

Grid (B, W/tile_w, S/tile_s) with the sequence dimension innermost and
sequential; the running state h lives in VMEM scratch across sequence
tiles.  Within a tile the recurrence is computed with a first-order scan
expressed as a log-depth prefix composition over rows (the recurrence is
associative: (a1,b1)∘(a2,b2) = (a1·a2, b1·a2 + b2)), which keeps the VPU
busy on [tile_s, tile_w] blocks instead of serializing row by row.

Channel tiles are 128-lane aligned; sequence tiles default to 256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lru_kernel(a_ref, b_ref, h_ref, carry, *, tile_s):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        carry[...] = jnp.zeros_like(carry)

    a = a_ref[0].astype(jnp.float32)          # [tile_s, tile_w]
    b = b_ref[0].astype(jnp.float32)

    # log-depth prefix composition over the tile's rows:
    # after the loop, a[t] = prod_{u<=t} a_u ; b[t] = h_t given h_{-1}=0
    k = 1
    while k < tile_s:
        a_sh = jnp.concatenate(
            [jnp.ones((k, a.shape[1]), jnp.float32), a[:-k]], axis=0)
        b_sh = jnp.concatenate(
            [jnp.zeros((k, b.shape[1]), jnp.float32), b[:-k]], axis=0)
        b = b + a * b_sh
        a = a * a_sh
        k *= 2

    h = b + a * carry[...]                    # carry: [1, tile_w]
    h_ref[0] = h.astype(h_ref.dtype)
    carry[...] = h[tile_s - 1:]


def lru_scan(a, b, *, tile_s: int = 256, tile_w: int = 128,
             interpret: bool = True):
    """a, b [B, S, W] -> h [B, S, W]."""
    bsz, s, w = a.shape
    tile_s = min(tile_s, s)
    tile_w = min(tile_w, w)
    assert s % tile_s == 0 and w % tile_w == 0, (s, w, tile_s, tile_w)
    grid = (bsz, w // tile_w, s // tile_s)
    kernel = functools.partial(_lru_kernel, tile_s=tile_s)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_s, tile_w),
                         lambda bb, wi, si: (bb, si, wi)),
            pl.BlockSpec((1, tile_s, tile_w),
                         lambda bb, wi, si: (bb, si, wi)),
        ],
        out_specs=pl.BlockSpec((1, tile_s, tile_w),
                               lambda bb, wi, si: (bb, si, wi)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), b.dtype),
        scratch_shapes=[pltpu.VMEM((1, tile_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
