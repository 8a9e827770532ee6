"""Counting helpers for the reference modules (``bench/references/``),
which alone know their architecture and so which counts apply to it:
live attention pairs of packed rows, and the weights and CA-server call
work of grouped-query attention.  Nothing here reads the program's plan
or its grids.

Conventions:
  * a multiply-add is 2 FLOPs;
  * attention is counted over live pairs: query i and key j of the same
    document with position(j) <= position(i), and position(i) -
    position(j) < window where the layer has a window.  Forward per pair
    and q head: q.k and p.v, 4 x head_dim FLOPs.  The backward of
    attention recomputes p and forms dv, dp, dq and dk: 8 x head_dim per
    pair.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def live_pairs(segment_ids: np.ndarray, positions: np.ndarray,
               window: int = 0) -> int:
    """Same-document causal (query, key) pairs of packed rows: a document
    of n tokens has n(n+1)/2, or w(w+1)/2 + (n-w)w under a window of w
    (query i sees min(i+1, w) keys).  Documents are numbered from 1 and 0
    is padding; positions restart at 0 in every document."""
    seg = np.asarray(segment_ids).reshape(-1)
    docs = seg[seg > 0]
    if docs.size == 0:
        return 0
    _, n = np.unique(docs, return_counts=True)
    n = n.astype(np.int64)
    if window and window > 0:
        w = np.minimum(n, window)
        return int((w * (w + 1) // 2 + (n - w) * w).sum())
    return int((n * (n + 1) // 2).sum())


def gqa_params(d: int, hq: int, hkv: int, dh: int) -> int:
    """Weights of the q, k, v and o projections of one attention layer."""
    return d * hq * dh + 2 * d * hkv * dh + hq * dh * d


def gqa_call_work(segment_ids: np.ndarray, positions: np.ndarray, hq: int,
                  hkv: int, dh: int, *, backward: bool,
                  act_bytes: int = 2) -> Dict[str, float]:
    """Work of one CA-server kernel call of a grouped-query attention
    layer (``hq`` q and ``hkv`` kv heads of ``dh``) over the rows of one
    nano-batch, summed over the servers that share it.

    Forward: 4 x pairs x Hq x dh FLOPs; q, k, v read once, out written
    once in the activation type and the f32 log-sum-exp written once.
    Backward (dq and dk/dv passes together): 8 x pairs x Hq x dh FLOPs;
    q, k, v, out, dout read in the activation type and lse read in f32,
    dq, dk, dv written once."""
    seg = np.asarray(segment_ids)
    tokens = int((seg > 0).sum())
    pairs = live_pairs(seg, positions)
    q = tokens * hq * dh * act_bytes
    kv = tokens * hkv * dh * act_bytes
    lse = tokens * hq * 4
    if backward:
        flops = 8 * pairs * hq * dh
        nbytes = (q + 2 * kv + 2 * q + lse) + (q + 2 * kv)
    else:
        flops = 4 * pairs * hq * dh
        nbytes = (q + 2 * kv) + (q + lse)
    return {"flops": float(flops), "bytes": float(nbytes)}
