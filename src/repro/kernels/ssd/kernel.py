"""Pallas TPU kernel for the SSD intra-chunk computation (Mamba-2).

The quadratic ('dual attention') part of the chunked SSD algorithm: per
(batch, chunk, head) tile, build the decay-masked score matrix on the
MXU, produce the intra-chunk outputs and the chunk's end-state
contribution. The O(S·N·P) inter-chunk recurrence stays in lax.scan
outside (it is tiny: one [N,P] GEMM per chunk).

Grid (B, K, H); blocks sized [chunk, N] / [chunk, P] live in VMEM —
chunk=256, N=128, P=64 uses ~0.4 MB/operand, MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(C_ref, B_ref, x_ref, dt_row_ref, dt_col_ref,
                      csum_row_ref, csum_col_ref, nr_row_ref, nr_col_ref,
                      y_ref, state_ref, *, chunk):
    # per-token vectors arrive twice: as [1, c] rows (the j side of the
    # [c, c] score matrix) and as [c, 1] columns (the i side)
    C_ = C_ref[...].astype(jnp.float32)           # [c, N]
    B_ = B_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)            # [c, P]
    dt_row = dt_row_ref[...].astype(jnp.float32)  # [1, c]
    dt_col = dt_col_ref[...].astype(jnp.float32)  # [c, 1]
    csum_col = csum_col_ref[...].astype(jnp.float32)
    nr_col = nr_col_ref[...]

    dec = jnp.exp(jnp.clip(csum_col - csum_row_ref[...].astype(jnp.float32),
                           -80.0, 0.0))
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    same = nr_col == nr_row_ref[...]
    dec = jnp.where(tri & same, dec, 0.0)

    scores = jax.lax.dot_general(C_, B_.T, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    w = scores * dec * dt_row
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)

    last = slice(chunk - 1, chunk)
    live = (nr_col == nr_col[last]).astype(jnp.float32)
    dec_end = jnp.exp(jnp.clip(csum_col[last] - csum_col, -80.0, 0.0)) \
        * live
    sB = B_ * (dec_end * dt_col)
    state = jax.lax.dot_general(sB.T, x, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    state_ref[...] = state.astype(state_ref.dtype)


def ssd_chunk(C_, B_, x, dt, csum, nr, *, interpret=True):
    """C_/B_ [Bt,K,c,H,N]; x [Bt,K,c,H,P]; dt/csum [Bt,K,c,H];
    nr [Bt,K,c] int32.  Returns (y [Bt,K,c,H,P], states [Bt,K,H,N,P])."""
    bt, k, c, h, n = C_.shape
    p = x.shape[-1]
    # layout: move head next to (b, k) so each grid step is one 2-D tile
    def mh(t):  # [Bt,K,c,H,...] -> [Bt*H, K, c, ...]
        t = jnp.moveaxis(t, 3, 1)
        return t.reshape((bt * h, t.shape[2], c) + t.shape[4:])
    Cm, Bm, xm, dtm, csm = mh(C_), mh(B_), mh(x), mh(dt), mh(csum)
    nrm = jnp.repeat(nr[:, None], h, axis=1).reshape(bt * h, k, c)

    def tile(rows, cols):
        return pl.BlockSpec((None, None, rows, cols),
                            lambda i, j: (i, j, 0, 0))

    def row(t):
        return t[..., None, :]

    def col(t):
        return t[..., None]

    kernel = functools.partial(_ssd_chunk_kernel, chunk=c)
    y, states = pl.pallas_call(
        kernel,
        grid=(bt * h, k),
        in_specs=[tile(c, n), tile(c, n), tile(c, p),
                  tile(1, c), tile(c, 1), tile(1, c), tile(c, 1),
                  tile(1, c), tile(c, 1)],
        out_specs=[tile(c, p), tile(n, p)],
        out_shape=[
            jax.ShapeDtypeStruct((bt * h, k, c, p), x.dtype),
            jax.ShapeDtypeStruct((bt * h, k, n, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(Cm, Bm, xm, row(dtm), col(dtm), row(csm), col(csm), row(nrm),
      col(nrm))

    def unh(t, tail):  # [Bt*H, K, ...] -> [Bt, K, ..., H, ...]
        t = t.reshape((bt, h, k) + tail)
        return jnp.moveaxis(t, 1, 3)
    y = unh(y, (c, p))                 # [Bt,K,c,H,P]
    states = unh(states, (n, p))       # [Bt,K,N,H->?]
    return y, jnp.moveaxis(states, 3, 2)   # [Bt,K,H,N,P]
