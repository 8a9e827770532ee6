"""Pallas TPU kernels for packed flash attention.

Forward kernels:

1. ``flash_fwd`` — packed-document self-attention over a chunk.  Grid
   (B, Hq, nq, nk) with the kv dimension innermost/sequential; online
   softmax accumulators live in VMEM scratch.  Causal block pruning skips
   (i, j) pairs above the diagonal; window pruning skips pairs entirely
   outside the sliding window.  Blocks are 128-aligned to the MXU —
   exactly the tile constraint the paper leans on (FA2's 128-token tile,
   §3.3 Fig. 5).  With ``return_lse`` the per-row log-sum-exp is written
   as a second output — the residual the backward kernels need.

2. ``ca_server_fwd`` — the attention-server kernel: a fused batch of
   CA-tasks (q-block, kv-prefix-range), where the kv range of each task is
   looked up through *scalar-prefetch* metadata (kv_start/kv_len), i.e.
   data-dependent BlockSpec index maps.  This is the TPU-native analogue
   of FA2 varlen batching that DistCA's attention servers rely on.

Backward kernels (flash-style, recompute-free: ``p`` is rebuilt from the
saved ``(out, lse)`` residuals instead of a second online-softmax pass):

3. ``flash_bwd`` — two grid passes.  dq iterates kv blocks innermost and
   accumulates one q-block's gradient in VMEM scratch; dk/dv iterates
   q blocks innermost and accumulates one kv-block's gradients.  Both
   reuse the forward's causal/window block pruning, so the backward
   touches exactly the forward's (i, j) pairs.

4. ``ca_server_bwd`` — the attention-server backward, honoring the same
   per-task ``kv_start``/``kv_len`` scalar-prefetch layout: dq walks each
   task's kv range; dk/dv inverts the mapping with a (kv-block, task)
   grid whose body is predicated on "task t's range covers block n", a
   scalar-prefetch condition — so servers run balanced bwd tasks in place
   (paper §4 ping-pong symmetry between fwd and bwd tasks).

GQA note: the dk/dv passes emit per-*query*-head gradients; the jnp
wrappers fold the repeat groups back onto kv heads.  That costs rep× the
final dk/dv footprint in f32 intermediates — accumulating the repeat
group in-kernel (q-heads folded into the sequential grid dim) is a
recorded §Perf follow-up; it changes memory, not semantics.

Layout: the entry functions keep the token-major ``[.., S, H, dh]``
arrays of their callers, and hand the kernels a heads-major copy
(``[.., H, S, dh]``).  Mosaic tiles the last two dims of every block by
(8, 128) unless a dim is taken whole, and only heads-major blocks end in
``(blk, dh)``.  Per-token vectors get a unit axis for the same reason:
q-side ones become ``[.., S, 1]`` columns and kv-side ones ``[.., 1, S]``
rows, which is also how the kernel bodies broadcast them against the
``[blk_q, blk_k]`` logits.  Row statistics (max, sum, lse, delta) are
``[blk_q, 1]`` columns throughout.

All kernels are validated in interpret mode against ref.py, and compiled
for v5e ahead of time in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 30
DEFAULT_BLOCK = 128


def _mxu_dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


LSE_DEAD = 2.0 ** 30   # lse of a fully-masked row: exp(x - LSE_DEAD) == 0


def _heads_major(x):
    """[.., S, H, dh] <-> [.., H, S, dh] (its own inverse)."""
    return jnp.swapaxes(x, -3, -2)


def _col(x):
    """[.., S] -> [.., S, 1]: a q-side per-token vector as a column."""
    return x[..., None]


def _row(x):
    """[.., S] -> [.., 1, S]: a kv-side per-token vector as a row."""
    return x[..., None, :]


def _fold_gqa(d_h, hkv, dtype):
    """Per-q-head [.., Hq, S, dh] grads -> token-major [.., S, Hkv, dh]."""
    *lead, hq, s, dh = d_h.shape
    d = d_h.reshape(tuple(lead) + (hkv, hq // hkv, s, dh)).sum(-3)
    return _heads_major(d).astype(dtype)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _capped_masked_logits(q, k, m, scale, softcap):
    """Scaled, softcapped, masked logits — shared by fwd and bwd bodies."""
    logits = _mxu_dot(q, k.T) * scale
    if softcap and softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    return jnp.where(m, logits, NEG_INF)


def _ds_from_p(p, dp, delta, logits, m, scale, softcap):
    """dL/d(q k^T): softmax bwd + softcap chain rule + scale."""
    ds = p * (dp - delta)
    if softcap and softcap > 0:
        sc = jnp.where(m, logits / softcap, 0.0)
        ds = ds * (1.0 - sc * sc)
    return ds * scale


def _softmax_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _softmax_update(logits, m, v, m_scr, l_scr, acc_scr):
    """One kv block of the online softmax; row stats are [blk_q, 1]."""
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    p = jnp.where(m, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + _mxu_dot(p.astype(v.dtype), v)
    m_scr[...] = m_new


def _softmax_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    l = l_scr[...]
    live = m_scr[...] > NEG_INF / 2
    out = acc_scr[...] / jnp.maximum(l, 1e-30)
    o_ref[...] = jnp.where(live, out, 0.0).astype(o_ref.dtype)
    if lse_ref is not None:
        lse = m_scr[...] + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[...] = jnp.where(live, lse, LSE_DEAD)


def _softmax_scratch(blk_q, dh):
    return [pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, dh), jnp.float32)]


# ----------------------------------------------------------- packed flash
def _flash_kernel(seg_q_ref, pos_q_ref, seg_k_ref, pos_k_ref,
                  q_ref, k_ref, v_ref, *rest,
                  scale, softcap, causal, window, sink, rate,
                  blk_q, blk_k, nk, save_lse=False):
    if save_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        (o_ref, m_scr, l_scr, acc_scr), lse_ref = rest, None
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    # mask-driven live-block pruning (chunk-order block indices; sound
    # for packed docs — see _flash_block_live)
    run = _flash_block_live(i, j, causal, window, sink, rate, blk_q, blk_k)

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)      # [blk_q, dh]
        k = k_ref[...].astype(jnp.float32)      # [blk_k, dh]
        v = v_ref[...].astype(jnp.float32)
        m = _flash_mask(seg_q_ref[...], pos_q_ref[...],
                        seg_k_ref[...], pos_k_ref[...], causal, window,
                        sink, rate, blk_q)
        logits = _capped_masked_logits(q, k, m, scale, softcap)
        _softmax_update(logits, m, v, m_scr, l_scr, acc_scr)

    @pl.when(j == nk - 1)
    def _finalize():
        _softmax_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _flash_specs(blk_q, blk_k, dh, rep, kv_major=False):
    """BlockSpecs of the packed-flash operands over the heads-major layout.

    Grid ids are (b, h, i, j) with i the q block and j the kv block, or
    (b, h, j, i) for the kv-major dk/dv pass (``kv_major``)."""
    def ids(f):
        if kv_major:
            return lambda b_, h, j, i: f(b_, h, i, j)
        return f
    return dict(
        seg_q=pl.BlockSpec((None, blk_q, 1), ids(lambda b_, h, i, j:
                                                  (b_, i, 0))),
        seg_k=pl.BlockSpec((None, 1, blk_k), ids(lambda b_, h, i, j:
                                                  (b_, 0, j))),
        q=pl.BlockSpec((None, None, blk_q, dh), ids(lambda b_, h, i, j:
                                                     (b_, h, i, 0))),
        kv=pl.BlockSpec((None, None, blk_k, dh), ids(lambda b_, h, i, j:
                                                      (b_, h // rep, j, 0))),
        kv_q_head=pl.BlockSpec((None, None, blk_k, dh),
                               ids(lambda b_, h, i, j: (b_, h, j, 0))),
        row=pl.BlockSpec((None, None, blk_q, 1), ids(lambda b_, h, i, j:
                                                      (b_, h, i, 0))),
    )


def flash_fwd(q, k, v, seg_q, pos_q, seg_kv, pos_kv, *, causal=True,
              window=0, sink=0, rate=1, softcap=0.0, scale=None,
              blk_q=DEFAULT_BLOCK, blk_k=DEFAULT_BLOCK, interpret=True,
              return_lse=False):
    """q [B, Sq, Hq, dh]; k/v [B, Skv, Hkv, dh]; seg/pos [B, S] int32.

    Returns out [B, Sq, Hq, dh] and, with ``return_lse``, lse
    [B, Hq, Sq] float32."""
    b, sq, hq, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, skv)
    assert sq % blk_q == 0 and skv % blk_k == 0, "pad seq to block size"
    if rate > 1:
        assert blk_q == blk_k, "dilated masks need square block tiles"
    nq, nk = sq // blk_q, skv // blk_k
    sp = _flash_specs(blk_q, blk_k, dh, rep)

    kernel = functools.partial(
        _flash_kernel, scale=scale, softcap=softcap, causal=causal,
        window=window, sink=sink, rate=rate, blk_q=blk_q, blk_k=blk_k,
        nk=nk, save_lse=return_lse)
    out_shape = jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype)
    out_specs = sp["q"]
    if return_lse:
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32))
        out_specs = (out_specs, sp["row"])
    res = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[sp["seg_q"], sp["seg_q"], sp["seg_k"], sp["seg_k"],
                  sp["q"], sp["kv"], sp["kv"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=_softmax_scratch(blk_q, dh),
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
    )(_col(seg_q), _col(pos_q), _row(seg_kv), _row(pos_kv),
      _heads_major(q), _heads_major(k), _heads_major(v))
    if return_lse:
        out, lse = res
        return _heads_major(out), lse[..., 0]
    return _heads_major(res)


# ---------------------------------------------------- packed flash bwd
def _flash_mask(sq, pq, sk, pk, causal, window, sink=0, rate=1, mblk=0):
    """Token-level mask: segments + causal + MaskSpec terms (DESIGN.md §12).

    q-side ``sq``/``pq`` are [blk_q, 1] columns, kv-side ``sk``/``pk``
    [1, blk_k] rows.  ``window``/``sink`` are the sliding family's
    parameters (sink tokens are the always-visible document head);
    ``rate``/``mblk`` the dilated family's block stride at granularity
    ``mblk``.  Positions are in-document, so sink and dilation are exact
    per document."""
    m = (sq == sk) & (sq > 0) & (sk > 0)
    if causal:
        m &= pq >= pk
    if window and window > 0:
        w = (pq - pk) < window
        if sink and sink > 0:
            w |= pk < sink
        m &= w
    if rate and rate > 1:
        m &= ((pq // mblk) - (pk // mblk)) % rate == 0
    return m


def _flash_block_live(i, j, causal, window, sink, rate, blk_q, blk_k):
    """Block-pruning predicate on chunk-order block indices.

    Sound for packed layouts (documents are block-aligned and contiguous,
    so the document offset cancels in ``i - j``).  When ``sink > 0`` the
    window prune is disabled — sink tokens live at in-document positions
    the global indices can't see — and the token mask alone enforces the
    window; causal pruning still bounds the work."""
    run = jnp.asarray(True)
    if causal:
        run = run & (j * blk_k < (i + 1) * blk_q)
    if window and window > 0 and not sink:
        run = run & ((j + 1) * blk_k - 1 >= i * blk_q - window)
    if rate and rate > 1:
        run = run & ((i - j) % rate == 0)
    return run


def _flash_bwd_dq_kernel(seg_q_ref, pos_q_ref, seg_k_ref, pos_k_ref,
                         q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, scale, softcap, causal,
                         window, sink, rate, blk_q, blk_k, nk):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = _flash_block_live(i, j, causal, window, sink, rate, blk_q, blk_k)

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        m = _flash_mask(seg_q_ref[...], pos_q_ref[...],
                        seg_k_ref[...], pos_k_ref[...], causal, window,
                        sink, rate, blk_q)
        logits = _capped_masked_logits(q, k, m, scale, softcap)
        p = jnp.where(m, jnp.exp(logits - lse_ref[...]), 0.0)
        dp = _mxu_dot(do, v.T)
        ds = _ds_from_p(p, dp, delta_ref[...], logits, m, scale, softcap)
        dq_scr[...] += _mxu_dot(ds, k)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(seg_q_ref, pos_q_ref, seg_k_ref, pos_k_ref,
                          q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *,
                          scale, softcap, causal, window, sink, rate,
                          blk_q, blk_k, nq):
    j = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = _flash_block_live(i, j, causal, window, sink, rate, blk_q, blk_k)

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        m = _flash_mask(seg_q_ref[...], pos_q_ref[...],
                        seg_k_ref[...], pos_k_ref[...], causal, window,
                        sink, rate, blk_q)
        logits = _capped_masked_logits(q, k, m, scale, softcap)
        p = jnp.where(m, jnp.exp(logits - lse_ref[...]), 0.0)
        dv_scr[...] += _mxu_dot(p.T, do)
        dp = _mxu_dot(do, v.T)
        ds = _ds_from_p(p, dp, delta_ref[...], logits, m, scale, softcap)
        dk_scr[...] += _mxu_dot(ds.T, q)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...]
        dv_ref[...] = dv_scr[...]


def flash_bwd(q, k, v, out, lse, do, seg_q, pos_q, seg_kv, pos_kv, *,
              causal=True, window=0, sink=0, rate=1, softcap=0.0,
              scale=None, blk_q=DEFAULT_BLOCK, blk_k=DEFAULT_BLOCK,
              interpret=True):
    """Hand-written backward for ``flash_fwd`` from saved (out, lse).

    Two passes over the same pruned (i, j) block pairs as the forward:
    a dq pass (kv innermost) and a dk/dv pass (q innermost).  Per-q-head
    dk/dv are folded back onto kv heads here (GQA)."""
    b, sq, hq, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, skv)
    assert sq % blk_q == 0 and skv % blk_k == 0, "pad seq to block size"
    if rate > 1:
        assert blk_q == blk_k, "dilated masks need square block tiles"
    nq, nk = sq // blk_q, skv // blk_k

    # delta_i = rowsum(do * out) — linear precompute shared by both passes
    delta = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                       out.astype(jnp.float32))
    args = (_col(seg_q), _col(pos_q), _row(seg_kv), _row(pos_kv),
            _heads_major(q), _heads_major(k), _heads_major(v),
            _heads_major(do), _col(lse), _col(delta))
    mask_kw = dict(scale=scale, softcap=softcap, causal=causal,
                   window=window, sink=sink, rate=rate, blk_q=blk_q,
                   blk_k=blk_k)
    semantics = _params("parallel", "parallel", "parallel", "arbitrary")

    sp = _flash_specs(blk_q, blk_k, dh, rep)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, nk=nk, **mask_kw),
        grid=(b, hq, nq, nk),
        in_specs=[sp["seg_q"], sp["seg_q"], sp["seg_k"], sp["seg_k"],
                  sp["q"], sp["kv"], sp["kv"], sp["q"], sp["row"],
                  sp["row"]],
        out_specs=sp["q"],
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, dh), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
    )(*args)

    # dk/dv pass: grid transposed, q-block dim innermost/sequential
    sp = _flash_specs(blk_q, blk_k, dh, rep, kv_major=True)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, nq=nq, **mask_kw),
        grid=(b, hq, nk, nq),
        in_specs=[sp["seg_q"], sp["seg_q"], sp["seg_k"], sp["seg_k"],
                  sp["q"], sp["kv"], sp["kv"], sp["q"], sp["row"],
                  sp["row"]],
        out_specs=(sp["kv_q_head"], sp["kv_q_head"]),
        out_shape=(jax.ShapeDtypeStruct((b, hq, skv, dh), jnp.float32),
                   jax.ShapeDtypeStruct((b, hq, skv, dh), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((blk_k, dh), jnp.float32),
                        pltpu.VMEM((blk_k, dh), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
    )(*args)
    return (_heads_major(dq), _fold_gqa(dk_h, hkv, k.dtype),
            _fold_gqa(dv_h, hkv, v.dtype))


# ---------------------------------------------------- ragged decode (serve)
def _ragged_decode_kernel(block_req_ref, kv_len_ref, qmin_ref,  # prefetch
                          q_pos_ref, q_ref, k_ref, v_ref,
                          o_ref, m_scr, l_scr, acc_scr, *,
                          scale, softcap, window, blk_q, blk_k, nk):
    """One (q-block, kv-block) step of the serving attention (DESIGN.md §8).

    Each q block belongs to exactly one request (``block_req``); its kv
    context is that request's cache rows ``[0, kv_len)`` where slot index
    == absolute position.  Online-softmax accumulators in VMEM scratch,
    kv blocks innermost/sequential — the decode/prefill analogue of
    ``_ca_server_kernel`` with the kv range looked up per request instead
    of per task."""
    i = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    req = block_req_ref[i]
    live = req >= 0
    kv_len = kv_len_ref[jnp.maximum(req, 0)]
    run = live & (j * blk_k < kv_len)
    if window and window > 0:
        # block j's last slot must be inside the oldest live row's window
        run = run & ((j + 1) * blk_k - 1 >= qmin_ref[i] - (window - 1))

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)       # [blk_q, dh]
        k = k_ref[...].astype(jnp.float32)       # [blk_k, dh]
        v = v_ref[...].astype(jnp.float32)
        pos = q_pos_ref[...]                     # [blk_q, 1]
        s_pos = j * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, blk_k), 1)
        m = (pos >= 0) & (s_pos < kv_len) & (pos >= s_pos)
        if window and window > 0:
            m &= (pos - s_pos) < window
        logits = _capped_masked_logits(q, k, m, scale, softcap)
        _softmax_update(logits, m, v, m_scr, l_scr, acc_scr)

    @pl.when(j == nk - 1)
    def _finalize():
        _softmax_finalize(o_ref, None, m_scr, l_scr, acc_scr)


def ragged_decode_fwd(q_blocks, k_cache, v_cache, block_req, kv_len, q_pos,
                      *, window=0, softcap=0.0, scale=None,
                      blk_k=DEFAULT_BLOCK, interpret=True):
    """Fused ragged-batch cache attention (serving hot loop, DESIGN.md §8).

    q_blocks [nq, blk_q, Hq, dh]   request-pure query blocks (blk_q = 1 for
                                   decode, 128 for chunked prefill)
    k_cache/v_cache [R, S, Hkv, dh] per-request cache, slot index == position
    block_req [nq] int32           request of each q block (-1 = dead block)
    kv_len   [R] int32             live slots per request (visibility bound)
    q_pos    [nq, blk_q] int32     absolute positions (-1 = padded row)

    ``block_req``/``kv_len`` and the per-block min position ride the
    scalar-prefetch channel so the kv BlockSpec index map and the
    per-request block pruning (kv_len upper bound + window lower bound)
    are data-dependent, exactly like ``ca_server_fwd``'s task ranges."""
    nq, blk_q, hq, dh = q_blocks.shape
    R, S, hkv, _ = k_cache.shape
    rep = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    assert S % blk_k == 0, "pad cache length to the kv block size"
    nk = S // blk_k

    qmin = jnp.min(jnp.where(q_pos >= 0, q_pos, jnp.int32(2 ** 31 - 1)),
                   axis=1).astype(jnp.int32)

    def q_index(i, h, j, br, kl, qm):
        return (i, h, 0, 0)

    def kv_index(i, h, j, br, kl, qm, r=rep):
        return (jnp.maximum(br[i], 0), h // r, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nq, hq, nk),
        in_specs=[
            pl.BlockSpec((None, blk_q, 1),
                         lambda i, h, j, br, kl, qm: (i, 0, 0)),
            pl.BlockSpec((None, None, blk_q, dh), q_index),
            pl.BlockSpec((None, None, blk_k, dh), kv_index),
            pl.BlockSpec((None, None, blk_k, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((None, None, blk_q, dh), q_index),
        scratch_shapes=_softmax_scratch(blk_q, dh),
    )
    out = pl.pallas_call(
        functools.partial(_ragged_decode_kernel, scale=scale,
                          softcap=softcap, window=window, blk_q=blk_q,
                          blk_k=blk_k, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nq, hq, blk_q, dh), q_blocks.dtype),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(block_req.astype(jnp.int32), kv_len.astype(jnp.int32), qmin,
      _col(q_pos), _heads_major(q_blocks), _heads_major(k_cache),
      _heads_major(v_cache))
    return _heads_major(out)


# ------------------------------------------------------- CA-server kernel
def _ca_mask(pq, pk, causal, window, sink=0, rate=1, mblk=0):
    """Token-level CA-task mask on in-document positions.

    ``pq`` is a [blk, 1] column, ``pk`` a [1, blk] row.  The scheduler
    guarantees each task's kv range is its own document's prefix, so
    segments are unneeded; sink/dilated terms (DESIGN.md §12) work
    directly on the in-document positions."""
    m = (pq >= 0) & (pk >= 0)
    if causal:
        m &= pq >= pk
    if window and window > 0:
        w = (pq - pk) < window
        if sink and sink > 0:
            w |= pk < sink
        m &= w
    if rate and rate > 1:
        m &= ((pq // mblk) - (pk // mblk)) % rate == 0
    return m


def _ca_live_mask(q_pos_ref, kv_pos_ref, causal, window, sink, rate, blk):
    """(mask, any_live) for the current (task, kv-block) pair, or
    ``(None, None)`` for the trivial dense-causal case.

    The mask-driven live-block predicate is computed from the *actual*
    position vectors (already resident for this grid cell), so it is
    exact for any caller — no reliance on the plan's prefix invariant —
    and skipping a dead block is a bit-exact no-op (its token mask is
    all-False, so the online-softmax carry would pass through
    unchanged).  ``mask.live_block_mask`` prices a conservative superset
    of these blocks (DESIGN.md §12)."""
    if not (window or sink or rate > 1):
        return None, None
    m = _ca_mask(q_pos_ref[...], kv_pos_ref[...], causal, window,
                 sink, rate, blk)
    return m, jnp.max(m.astype(jnp.int32)) > 0


def _ca_task_mask(mask, q_pos_ref, kv_pos_ref, causal, window, sink, rate,
                  blk):
    if mask is not None:
        return mask
    return _ca_mask(q_pos_ref[...], kv_pos_ref[...], causal, window, sink,
                    rate, blk)


def _ca_server_kernel(kv_start_ref, kv_len_ref,       # scalar prefetch
                      q_pos_ref, kv_pos_ref, q_ref, k_ref, v_ref, *rest,
                      scale, softcap, causal, window, sink, rate, blk,
                      jmax, save_lse=False):
    if save_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        (o_ref, m_scr, l_scr, acc_scr), lse_ref = rest, None
    t = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    mask, any_live = _ca_live_mask(q_pos_ref, kv_pos_ref, causal, window,
                                   sink, rate, blk)
    live = j < kv_len_ref[t]
    if mask is not None:
        live &= any_live

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        m = _ca_task_mask(mask, q_pos_ref, kv_pos_ref, causal, window,
                          sink, rate, blk)
        logits = _capped_masked_logits(q, k, m, scale, softcap)
        _softmax_update(logits, m, v, m_scr, l_scr, acc_scr)

    @pl.when(j == jmax - 1)
    def _finalize():
        _softmax_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _ca_specs(blk, dh, rep, n_kv_blocks):
    """BlockSpecs of the CA-server operands over the heads-major layout,
    for the (task, head, relative kv block) grid with the per-task kv
    ranges in scalar prefetch."""
    def kv_block(t, j, starts):
        return jnp.minimum(starts[t] + j, n_kv_blocks - 1)

    return dict(
        q_pos=pl.BlockSpec((None, blk, 1),
                           lambda t, h, j, st, ln: (t, 0, 0)),
        kv_pos=pl.BlockSpec((None, 1, blk),
                            lambda t, h, j, st, ln: (kv_block(t, j, st),
                                                     0, 0)),
        q=pl.BlockSpec((None, None, blk, dh),
                       lambda t, h, j, st, ln: (t, h, 0, 0)),
        kv=pl.BlockSpec((None, None, blk, dh),
                        lambda t, h, j, st, ln: (kv_block(t, j, st),
                                                 h // rep, 0, 0)),
        row=pl.BlockSpec((None, None, blk, 1),
                         lambda t, h, j, st, ln: (t, h, 0, 0)),
    )


def ca_server_fwd(q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos, *,
                  causal=True, window=0, sink=0, rate=1, softcap=0.0,
                  scale=None, jmax=None, interpret=True, return_lse=False):
    """Fused CA-task batch (see ref.ref_ca_server_attention for semantics).

    q_tasks [T,blk,Hq,dh]; k_buf/v_buf [N,blk,Hkv,dh]; kv_start/kv_len [T];
    q_pos [T,blk]; kv_pos [N,blk].  ``jmax`` bounds the kv blocks any task
    may touch (defaults to N).  window/sink/rate are the MaskSpec terms
    (DESIGN.md §12); kv blocks of a task's prefix that the mask leaves
    fully dead are skipped via ``_ca_live_mask``'s exact predicate.
    With ``return_lse`` the lse [T, Hq, blk] float32 comes second."""
    T, blk, hq, dh = q_tasks.shape
    N, _, hkv, _ = k_buf.shape
    rep = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    jmax = jmax or N
    sp = _ca_specs(blk, dh, rep, N)

    kernel = functools.partial(
        _ca_server_kernel, scale=scale, softcap=softcap, causal=causal,
        window=window, sink=sink, rate=rate, blk=blk, jmax=jmax,
        save_lse=return_lse)
    out_shape = jax.ShapeDtypeStruct((T, hq, blk, dh), q_tasks.dtype)
    out_specs = sp["q"]
    if return_lse:
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((T, hq, blk, 1), jnp.float32))
        out_specs = (out_specs, sp["row"])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, hq, jmax),
        in_specs=[sp["q_pos"], sp["kv_pos"], sp["q"], sp["kv"], sp["kv"]],
        out_specs=out_specs,
        scratch_shapes=_softmax_scratch(blk, dh),
    )
    res = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(kv_start, kv_len, _col(q_pos), _row(kv_pos), _heads_major(q_tasks),
      _heads_major(k_buf), _heads_major(v_buf))
    if return_lse:
        out, lse = res
        return _heads_major(out), lse[..., 0]
    return _heads_major(res)


# --------------------------------------------------- CA-server backward
def _ca_bwd_dq_kernel(kv_start_ref, kv_len_ref,       # scalar prefetch
                      q_pos_ref, kv_pos_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, dq_scr, *,
                      scale, softcap, causal, window, sink, rate, blk,
                      jmax):
    t = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    mask, any_live = _ca_live_mask(q_pos_ref, kv_pos_ref, causal, window,
                                   sink, rate, blk)
    live = j < kv_len_ref[t]
    if mask is not None:
        live &= any_live

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        m = _ca_task_mask(mask, q_pos_ref, kv_pos_ref, causal, window,
                          sink, rate, blk)
        logits = _capped_masked_logits(q, k, m, scale, softcap)
        p = jnp.where(m, jnp.exp(logits - lse_ref[...]), 0.0)
        dp = _mxu_dot(do, v.T)
        ds = _ds_from_p(p, dp, delta_ref[...], logits, m, scale, softcap)
        dq_scr[...] += _mxu_dot(ds, k)

    @pl.when(j == jmax - 1)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _ca_bwd_dkv_kernel(kv_start_ref, kv_len_ref,      # scalar prefetch
                       q_pos_ref, kv_pos_ref, q_ref, k_ref, v_ref, do_ref,
                       lse_ref, delta_ref, dk_ref, dv_ref,
                       dk_scr, dv_scr, *,
                       scale, softcap, causal, window, sink, rate, blk,
                       n_tasks):
    n = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # task t touches kv block n iff its prefix range covers it AND the
    # mask keeps any (q, kv) pair of the block live — untouched
    # (block, task) pairs skip the whole body (the bwd analogue of the
    # fwd's mask-driven live-block iteration)
    jrel = n - kv_start_ref[t]
    covers = (jrel >= 0) & (jrel < kv_len_ref[t])
    mask, any_live = _ca_live_mask(q_pos_ref, kv_pos_ref, causal, window,
                                   sink, rate, blk)
    if mask is not None:
        covers &= any_live

    @pl.when(covers)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        m = _ca_task_mask(mask, q_pos_ref, kv_pos_ref, causal, window,
                          sink, rate, blk)
        logits = _capped_masked_logits(q, k, m, scale, softcap)
        p = jnp.where(m, jnp.exp(logits - lse_ref[...]), 0.0)
        dv_scr[...] += _mxu_dot(p.T, do)
        dp = _mxu_dot(do, v.T)
        ds = _ds_from_p(p, dp, delta_ref[...], logits, m, scale, softcap)
        dk_scr[...] += _mxu_dot(ds.T, q)

    @pl.when(t == n_tasks - 1)
    def _finalize():
        dk_ref[...] = dk_scr[...]
        dv_ref[...] = dv_scr[...]


def ca_server_bwd(q_tasks, k_buf, v_buf, out, lse, do, kv_start, kv_len,
                  q_pos, kv_pos, *, causal=True, window=0, sink=0,
                  rate=1, softcap=0.0, scale=None, jmax=None,
                  interpret=True):
    """Hand-written backward for ``ca_server_fwd`` from saved (out, lse).

    dq walks each task's kv prefix range exactly like the forward (same
    scalar-prefetch index maps).  dk/dv inverts the task→kv-range mapping
    with an (kv-block, head, task) grid predicated on range coverage, so
    every kv block accumulates only the tasks whose prefix contains it."""
    T, blk, hq, dh = q_tasks.shape
    N, _, hkv, _ = k_buf.shape
    rep = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    jmax = jmax or N

    delta = jnp.einsum("tqhd,tqhd->thq", do.astype(jnp.float32),
                       out.astype(jnp.float32))
    args = (kv_start, kv_len, _col(q_pos), _row(kv_pos),
            _heads_major(q_tasks), _heads_major(k_buf), _heads_major(v_buf),
            _heads_major(do), _col(lse), _col(delta))
    mask_kw = dict(scale=scale, softcap=softcap, causal=causal,
                   window=window, sink=sink, rate=rate, blk=blk)
    semantics = _params("parallel", "parallel", "arbitrary")

    sp = _ca_specs(blk, dh, rep, N)
    dq = pl.pallas_call(
        functools.partial(_ca_bwd_dq_kernel, jmax=jmax, **mask_kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T, hq, jmax),
            in_specs=[sp["q_pos"], sp["kv_pos"], sp["q"], sp["kv"],
                      sp["kv"], sp["q"], sp["row"], sp["row"]],
            out_specs=sp["q"],
            scratch_shapes=[pltpu.VMEM((blk, dh), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, hq, blk, dh), q_tasks.dtype),
        compiler_params=semantics,
        interpret=interpret,
    )(*args)

    # dk/dv pass: grid (kv block n, head, task t), tasks innermost
    def task(n, h, t, st, ln):
        return (t, h, 0, 0)

    def kv_block(n, h, t, st, ln):
        return (n, h // rep, 0, 0)

    def kv_block_q_head(n, h, t, st, ln):
        return (n, h, 0, 0)

    dk_h, dv_h = pl.pallas_call(
        functools.partial(_ca_bwd_dkv_kernel, n_tasks=T, **mask_kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N, hq, T),
            in_specs=[
                pl.BlockSpec((None, blk, 1),
                             lambda n, h, t, st, ln: (t, 0, 0)),
                pl.BlockSpec((None, 1, blk),
                             lambda n, h, t, st, ln: (n, 0, 0)),
                pl.BlockSpec((None, None, blk, dh), task),
                pl.BlockSpec((None, None, blk, dh), kv_block),
                pl.BlockSpec((None, None, blk, dh), kv_block),
                pl.BlockSpec((None, None, blk, dh), task),
                pl.BlockSpec((None, None, blk, 1), task),
                pl.BlockSpec((None, None, blk, 1), task),
            ],
            out_specs=(pl.BlockSpec((None, None, blk, dh), kv_block_q_head),
                       pl.BlockSpec((None, None, blk, dh), kv_block_q_head)),
            scratch_shapes=[pltpu.VMEM((blk, dh), jnp.float32),
                            pltpu.VMEM((blk, dh), jnp.float32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((N, hq, blk, dh), jnp.float32),
                   jax.ShapeDtypeStruct((N, hq, blk, dh), jnp.float32)),
        compiler_params=semantics,
        interpret=interpret,
    )(*args)
    return (_heads_major(dq), _fold_gqa(dk_h, hkv, k_buf.dtype),
            _fold_gqa(dv_h, hkv, v_buf.dtype))
