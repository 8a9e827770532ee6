"""Operations and bytes a training step needs, from the configuration and
the batch alone: nothing here reads the program's plan or its grids.

Conventions:
  * a multiply-add is 2 FLOPs;
  * the linear work of a token is 2 x (weights it multiplies through):
    the q/k/v/o projections, the gated MLP and the output head (the
    embedding lookup is a gather, 0 FLOPs);
  * attention is counted over live pairs: query i and key j of the same
    document with position(j) <= position(i).  Forward per pair and q
    head: q.k and p.v, 4 x head_dim FLOPs.  The backward of attention
    recomputes p and forms dv, dp, dq and dk: 8 x head_dim per pair;
  * a training step needs forward + backward = 3 x forward; work that
    rematerialisation repeats is not counted (MFU).
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def linear_params_per_layer(c: Dict) -> int:
    d, dh = c["hidden_size"], c["head_dim"]
    hq, hkv, f = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["intermediate_size"])
    attn = d * hq * dh + 2 * d * hkv * dh + hq * dh * d
    return attn + 3 * d * f


def linear_flops_per_token(c: Dict) -> int:
    """Forward FLOPs of the matrix multiplications of one token."""
    head = 2 * c["hidden_size"] * c["vocab_size"]
    return 2 * linear_params_per_layer(c) * c["num_hidden_layers"] + head


def live_pairs(segment_ids: np.ndarray, positions: np.ndarray) -> int:
    """Same-document causal (query, key) pairs of packed rows: a document
    of n tokens has n(n+1)/2.  Documents are numbered from 1 and 0 is
    padding; positions restart at 0 in every document."""
    seg = np.asarray(segment_ids).reshape(-1)
    docs = seg[seg > 0]
    if docs.size == 0:
        return 0
    _, n = np.unique(docs, return_counts=True)
    n = n.astype(np.int64)
    return int((n * (n + 1) // 2).sum())


def attention_fwd_flops(c: Dict, pairs: int) -> int:
    """Forward FLOPs of core attention over ``pairs`` live pairs, one
    layer."""
    return 4 * pairs * c["num_attention_heads"] * c["head_dim"]


def step_flops(c: Dict, batch: Dict[str, np.ndarray]) -> float:
    """FLOPs the forward and backward passes of one step need (no remat):
    every non-padding token goes through the linear layers."""
    tokens = int((np.asarray(batch["segment_ids"]) > 0).sum())
    pairs = live_pairs(batch["segment_ids"], batch["positions"])
    fwd = tokens * linear_flops_per_token(c) \
        + c["num_hidden_layers"] * attention_fwd_flops(c, pairs)
    return 3.0 * fwd


def ca_call_work(c: Dict, segment_ids: np.ndarray, positions: np.ndarray,
                 *, backward: bool, act_bytes: int = 2) -> Dict[str, float]:
    """Work of one CA-server kernel call over the rows of one nano-batch,
    one layer, summed over the servers that share it.

    Forward: 4 x pairs x Hq x dh FLOPs; q, k, v read once, out written
    once in the activation type and the f32 log-sum-exp written once.
    Backward (dq and dk/dv passes together): 8 x pairs x Hq x dh FLOPs;
    q, k, v, out, dout read in the activation type and lse read in f32,
    dq, dk, dv written once."""
    seg = np.asarray(segment_ids)
    tokens = int((seg > 0).sum())
    pairs = live_pairs(seg, positions)
    hq, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
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
