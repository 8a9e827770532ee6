"""Jit'd wrappers for the packed-flash kernels with training-ready VJPs.

Forward runs the Pallas kernel (interpret=True on CPU, compiled on TPU)
and saves the flash residuals ``(out, lse)``.  Backward runs the
hand-written Pallas backward kernels (``kernel.flash_bwd`` /
``kernel.ca_server_bwd``) — recompute-free, rebuilding attention weights
from the saved log-sum-exp instead of re-deriving them via ``jax.vjp``
over a forward re-run.

The previous blockwise-jnp recompute backward is kept as an explicit
fallback: pass ``bwd_impl="xla"`` (or set ``REPRO_KERNEL_BWD=xla``) to
select it — e.g. on backends where even interpret-mode Pallas is
undesirable, or to A/B the two in ``benchmarks.kernel_throughput --bwd``.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core import attention as A
from repro.kernels.packed_flash import kernel as K


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve_bwd(bwd_impl) -> str:
    """"pallas" | "xla"; None defers to $REPRO_KERNEL_BWD (default pallas)."""
    impl = bwd_impl or os.environ.get("REPRO_KERNEL_BWD", "pallas")
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown kernel bwd impl {impl!r}")
    return impl


# ------------------------------------------------------------ packed flash
# ``sink``/``rate`` trail the original args (keeping positional callers
# valid): the unpacked static params of a non-causal MaskSpec
# (DESIGN.md §12) — sliding-sink tokens and dilated block stride.
@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def packed_flash_attention(q, k, v, seg_q, pos_q, seg_kv, pos_kv,
                           causal=True, window=0, softcap=0.0, scale=None,
                           bwd_impl=None, sink=0, rate=1):
    return K.flash_fwd(q, k, v, seg_q, pos_q, seg_kv, pos_kv, causal=causal,
                       window=window, sink=sink, rate=rate, softcap=softcap,
                       scale=scale, interpret=not _on_tpu())


def _pf_fwd(q, k, v, seg_q, pos_q, seg_kv, pos_kv, causal, window, softcap,
            scale, bwd_impl, sink, rate):
    out, lse = K.flash_fwd(q, k, v, seg_q, pos_q, seg_kv, pos_kv,
                           causal=causal, window=window, sink=sink,
                           rate=rate, softcap=softcap, scale=scale,
                           interpret=not _on_tpu(), return_lse=True)
    return out, (q, k, v, seg_q, pos_q, seg_kv, pos_kv, out, lse)


def _pf_bwd(causal, window, softcap, scale, bwd_impl, sink, rate, res, g):
    q, k, v, seg_q, pos_q, seg_kv, pos_kv, out, lse = res
    if _resolve_bwd(bwd_impl) == "pallas":
        dq, dk, dv = K.flash_bwd(q, k, v, out, lse, g, seg_q, pos_q,
                                 seg_kv, pos_kv, causal=causal,
                                 window=window, sink=sink, rate=rate,
                                 softcap=softcap, scale=scale,
                                 interpret=not _on_tpu())
        return dq, dk, dv, None, None, None, None
    f = lambda q_, k_, v_: A.xla_flash_attention(
        q_, k_, v_, seg_q, pos_q, seg_kv, pos_kv, causal=causal,
        window=window, sink=sink, rate=rate, softcap=softcap, scale=scale)
    _, vjp = jax.vjp(f, q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None, None, None


packed_flash_attention.defvjp(_pf_fwd, _pf_bwd)


# -------------------------------------------------------------- CA server
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14, 15))
def ca_server_attention(q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos,
                        kv_pos, causal=True, window=0, softcap=0.0,
                        scale=None, jmax=0, bwd_impl=None, sink=0, rate=1,
                        max_pairs=0):
    """Fused CA-task batch on an attention server (paper §4.1).

    ``jmax`` bounds the kv blocks any task may touch (0 -> all of k_buf);
    the scheduler's plan guarantees every ``kv_len`` fits under it.
    ``sink``/``rate`` carry a non-causal MaskSpec (DESIGN.md §12).
    ``max_pairs`` bounds the (task, kv block) pairs the ranges cover and
    sizes the Pallas pair grids (0 -> the dense sizes)."""
    return K.ca_server_fwd(q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos,
                           kv_pos, causal=causal, window=window, sink=sink,
                           rate=rate, softcap=softcap, scale=scale,
                           jmax=jmax or None, max_pairs=max_pairs or None,
                           interpret=not _on_tpu())


def _ca_fwd(q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos,
            causal, window, softcap, scale, jmax, bwd_impl, sink, rate,
            max_pairs):
    out, lse = K.ca_server_fwd(q_tasks, k_buf, v_buf, kv_start, kv_len,
                               q_pos, kv_pos, causal=causal, window=window,
                               sink=sink, rate=rate, softcap=softcap,
                               scale=scale, jmax=jmax or None,
                               max_pairs=max_pairs or None,
                               interpret=not _on_tpu(), return_lse=True)
    return out, (q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos,
                 out, lse)


def _ca_bwd(causal, window, softcap, scale, jmax, bwd_impl, sink, rate,
            max_pairs, res, g):
    q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos, out, lse = res
    if _resolve_bwd(bwd_impl) == "pallas":
        dq, dk, dv = K.ca_server_bwd(
            q_tasks, k_buf, v_buf, out, lse, g, kv_start, kv_len, q_pos,
            kv_pos, causal=causal, window=window, sink=sink, rate=rate,
            softcap=softcap, scale=scale, jmax=jmax or None,
            max_pairs=max_pairs or None, interpret=not _on_tpu())
        return dq, dk, dv, None, None, None, None
    if causal:
        # blockwise-jnp recompute fallback — the attention-server scan
        # path (dispatch._xla_server_bwd); mask params ride along
        from repro.core import dispatch as D
        f = lambda q_, k_, v_: D._xla_server(
            q_, k_, v_, kv_start, kv_len, q_pos, kv_pos,
            jmax or k_buf.shape[0], softcap, window, scale, sink, rate)
    else:
        from repro.core.mask import spec_from_params
        from repro.kernels.packed_flash import ref as R
        spec = spec_from_params(window, sink, rate)
        w = 0 if (spec is not None and spec.kind == "sliding") else window
        f = lambda q_, k_, v_: R.ref_ca_server_attention(
            q_, k_, v_, kv_start, kv_len, q_pos, kv_pos, causal=False,
            window=w, softcap=softcap, scale=scale, mask=spec)
    _, vjp = jax.vjp(f, q_tasks, k_buf, v_buf)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None, None, None, None


ca_server_attention.defvjp(_ca_fwd, _ca_bwd)


# --------------------------------------------- ring partials (DESIGN.md §13)
def _lse_dead(lse):
    """Rows whose partial saw no live kv (``kernel.LSE_DEAD`` marker)."""
    return lse >= K.LSE_DEAD / 2


def _merge_weights(lse_a, lse_b, lse):
    """Softmax merge weights, zeroed on dead partials.  When exactly one
    side is live its weight is ``exp(0) == 1.0`` exactly, so the merge
    below degenerates to a bitwise pass-through of the live side."""
    w_a = jnp.where(_lse_dead(lse_a), 0.0, jnp.exp(lse_a - lse))
    w_b = jnp.where(_lse_dead(lse_b), 0.0, jnp.exp(lse_b - lse))
    return w_a, w_b


def _broadcast_rows(w):
    """[..., hq, blk] row weights -> [..., blk, hq, 1] out broadcast."""
    return jnp.swapaxes(w, -1, -2)[..., None]


@jax.custom_vjp
def merge_softmax_partials(out_a, lse_a, out_b, lse_b):
    """Online-softmax merge of two finalized attention partials.

    ``out_*`` is ``[..., blk, hq, dh]`` (already normalized), ``lse_*``
    the matching ``[..., hq, blk]`` log-sum-exp over each partial's kv
    range; leading batch dims broadcast elementwise, so per-server
    ``[T, ...]`` and stacked-pool ``[D, T, ...]`` layouts merge with the
    identical FP ops (the ring dispatch / single-pool oracle bit-identity
    contract, DESIGN.md §13).  A dead partial (``kernel.LSE_DEAD``: no
    live kv in its range — a causal- or mask-dead ring pass) is a
    *bitwise* no-op: the result is selected, not blended, from the live
    side, the same discipline as ``dispatch.merge_recovered``.  Both
    outputs are differentiable, so merges chain across ring passes and
    gradients flow back into every partial."""
    out, lse = _merge_fwd(out_a, lse_a, out_b, lse_b)[0]
    return out, lse


def _merge_fwd(out_a, lse_a, out_b, lse_b):
    dead_a, dead_b = _lse_dead(lse_a), _lse_dead(lse_b)
    # neutralize dead sentinels before the max-stabilized logaddexp so a
    # dead side can never dominate the stabilizer
    la = jnp.where(dead_a, -K.LSE_DEAD, lse_a)
    lb = jnp.where(dead_b, -K.LSE_DEAD, lse_b)
    m = jnp.maximum(la, lb)
    lse_m = m + jnp.log(jnp.exp(la - m) + jnp.exp(lb - m))
    w_a, w_b = _merge_weights(lse_a, lse_b, lse_m)
    out_m = (_broadcast_rows(w_a) * out_a.astype(jnp.float32)
             + _broadcast_rows(w_b) * out_b.astype(jnp.float32)) \
        .astype(out_a.dtype)
    # bitwise select: a dead partial must not perturb the live side
    # (0.0*x + 1.0*y is not bitwise y when y holds -0.0)
    sel_b = _broadcast_rows(dead_b)
    sel_a = _broadcast_rows(dead_a)
    out = jnp.where(sel_b, out_a, jnp.where(sel_a, out_b, out_m))
    lse = jnp.where(dead_b, lse_a, jnp.where(dead_a, lse_b, lse_m))
    return (out, lse), (out_a, lse_a, out_b, lse_b, out, lse)


def _merge_bwd(res, g):
    out_a, lse_a, out_b, lse_b, out, lse = res
    g_out, g_lse = g
    gf = g_out.astype(jnp.float32)
    of = out.astype(jnp.float32)
    w_a, w_b = _merge_weights(lse_a, lse_b, lse)
    d_out_a = (_broadcast_rows(w_a) * gf).astype(out_a.dtype)
    d_out_b = (_broadcast_rows(w_b) * gf).astype(out_b.dtype)
    # d lse_i = w_i * (sum_dh g_out * (out_i - out) + g_lse): the weight
    # path (out shifts toward out_i as lse_i grows) plus the merged-lse
    # path (d lse / d lse_i == w_i)
    da = jnp.swapaxes(
        (gf * (out_a.astype(jnp.float32) - of)).sum(-1), -1, -2)
    db = jnp.swapaxes(
        (gf * (out_b.astype(jnp.float32) - of)).sum(-1), -1, -2)
    d_lse_a = w_a * (da + g_lse)
    d_lse_b = w_b * (db + g_lse)
    return d_out_a, d_lse_a, d_out_b, d_lse_b


merge_softmax_partials.defvjp(
    lambda oa, la, ob, lb: _merge_fwd(oa, la, ob, lb), _merge_bwd)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14))
def ca_partial_attention(q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos,
                         kv_pos, jmax=0, window=0, softcap=0.0,
                         scale=None, sink=0, rate=1, kernel="xla",
                         max_pairs=0):
    """One ring pass of a fused CA-task batch: attention over the pass's
    kv sub-range ``[kv_start, kv_start + kv_len)`` returning the
    finalized ``(out, lse)`` partial — both differentiable, so
    :func:`merge_softmax_partials` chains across passes with gradients
    intact.  ``kv_len == 0`` rows yield a dead partial (zero out,
    ``kernel.LSE_DEAD`` lse) that merges as a bitwise no-op.  ``kernel``
    picks the forward ("pallas" fused kernel / "xla" blockwise scan);
    backward always runs the blockwise recompute extended with the lse
    cotangent (``ds = p * (dp - delta + g_lse)``).  ``max_pairs`` sizes
    the Pallas pair list as in :func:`ca_server_attention`."""
    return _partial_fwd_impl(q_tasks, k_buf, v_buf, kv_start, kv_len,
                             q_pos, kv_pos, jmax, window, softcap, scale,
                             sink, rate, kernel, max_pairs)


def _partial_fwd_impl(q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos,
                      kv_pos, jmax, window, softcap, scale, sink, rate,
                      kernel, max_pairs):
    if kernel == "pallas":
        return K.ca_server_fwd(q_tasks, k_buf, v_buf, kv_start, kv_len,
                               q_pos, kv_pos, causal=True, window=window,
                               sink=sink, rate=rate, softcap=softcap,
                               scale=scale, jmax=jmax or None,
                               max_pairs=max_pairs or None,
                               interpret=not _on_tpu(), return_lse=True)
    from repro.core import dispatch as D
    return D._xla_server_fwd_impl(q_tasks, k_buf, v_buf, kv_start, kv_len,
                                  q_pos, kv_pos,
                                  jmax or k_buf.shape[-4], softcap,
                                  window, scale, sink, rate)


def _ca_partial_fwd(q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos,
                    kv_pos, jmax, window, softcap, scale, sink, rate,
                    kernel, max_pairs):
    out, lse = _partial_fwd_impl(q_tasks, k_buf, v_buf, kv_start, kv_len,
                                 q_pos, kv_pos, jmax, window, softcap,
                                 scale, sink, rate, kernel, max_pairs)
    return (out, lse), (q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos,
                        kv_pos, out, lse)


def _ca_partial_bwd(jmax, window, softcap, scale, sink, rate, kernel,
                    max_pairs, res, g):
    g_out, g_lse = g
    from repro.core import dispatch as D
    dq, dk, dv = D._xla_server_bwd_impl(
        res, g_out, g_lse, jmax=jmax or res[1].shape[-4], softcap=softcap,
        window=window, scale=scale, sink=sink, rate=rate)
    return dq, dk, dv, None, None, None, None


ca_partial_attention.defvjp(_ca_partial_fwd, _ca_partial_bwd)


# ---------------------------------------------------- ragged decode (serve)
def _resolve_decode(impl) -> str:
    """"pallas" | "xla"; None defers to $REPRO_KERNEL_DECODE (default
    pallas) — the serving mirror of ``_resolve_bwd``."""
    impl = impl or os.environ.get("REPRO_KERNEL_DECODE", "pallas")
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown kernel decode impl {impl!r}")
    return impl


def _xla_ragged_decode(q_blocks, k_cache, v_cache, block_req, kv_len, q_pos,
                       *, window=0, softcap=0.0, scale=None, blk_k=128):
    """Blockwise-jnp fallback for ``kernel.ragged_decode_fwd``: per q block
    gather that request's cache and run the same online-softmax recurrence
    in plain lax — memory O(S·blk) like the kernel, no [T, S] gather."""
    nq, blk_q, hq, dh = q_blocks.shape
    R, S, hkv, _ = k_cache.shape
    rep = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    blk_k = min(blk_k, S)
    assert S % blk_k == 0, "pad cache length to the kv block size"
    nk = S // blk_k

    outs = []
    for i in range(nq):        # static and small: T/128 (prefill) or B (decode)
        req = jnp.maximum(block_req[i], 0)
        qb = q_blocks[i].astype(jnp.float32)                  # [blk_q,hq,dh]
        pos = q_pos[i]
        kb = jnp.repeat(k_cache[req], rep, axis=1).astype(jnp.float32)
        vb = jnp.repeat(v_cache[req], rep, axis=1).astype(jnp.float32)
        kl = kv_len[req]

        def body(carry, j, qb=qb, pos=pos, kb=kb, vb=vb, kl=kl,
                 live_blk=block_req[i] >= 0):
            m_acc, l_acc, o_acc = carry
            k = jax.lax.dynamic_slice_in_dim(kb, j * blk_k, blk_k, 0)
            v = jax.lax.dynamic_slice_in_dim(vb, j * blk_k, blk_k, 0)
            s_pos = j * blk_k + jnp.arange(blk_k, dtype=jnp.int32)
            m = live_blk & (pos[:, None] >= 0) & (s_pos[None, :] < kl) \
                & (pos[:, None] >= s_pos[None, :])
            if window and window > 0:
                m &= (pos[:, None] - s_pos[None, :]) < window
            logits = jnp.einsum("qhd,khd->hqk", qb, k) * scale
            if softcap and softcap > 0:
                logits = jnp.tanh(logits / softcap) * softcap
            logits = jnp.where(m[None], logits, A.NEG_INF)
            m_new = jnp.maximum(m_acc, logits.max(axis=-1))
            p = jnp.where(m[None], jnp.exp(logits - m_new[..., None]), 0.0)
            corr = jnp.exp(m_acc - m_new)
            l_new = l_acc * corr + p.sum(axis=-1)
            contrib = (p[..., None] * v.transpose(1, 0, 2)[:, None]).sum(2)
            o_new = o_acc * corr[..., None] + contrib
            return (m_new, l_new, o_new), None

        carry0 = (jnp.full((hq, blk_q), A.NEG_INF, jnp.float32),
                  jnp.zeros((hq, blk_q), jnp.float32),
                  jnp.zeros((hq, blk_q, dh), jnp.float32))
        (m_acc, l_acc, o_acc), _ = jax.lax.scan(
            body, carry0, jnp.arange(nk, dtype=jnp.int32))
        live = m_acc > A.NEG_INF / 2
        out = o_acc / jnp.maximum(l_acc, 1e-30)[..., None]
        out = jnp.where(live[..., None], out, 0.0)
        outs.append(out.transpose(1, 0, 2).astype(q_blocks.dtype))
    return jnp.stack(outs)


def ragged_decode_attention(q, k_cache, v_cache, block_req, q_pos, kv_len,
                            *, window=0, softcap=0.0, scale=None,
                            impl=None):
    """Fused cache attention over a ragged request batch (DESIGN.md §8).

    The serving hot loop: every q block is request-pure and attends that
    request's cache prefix ``[0, kv_len)`` (slot index == position; the
    serving cache layout is non-ring), in one call for the whole batch —
    blk_q = 1 for decode steps, 128 for chunked prefill.

    q        [T, Hq, dh]  packed query tokens; T % len(block_req) == 0
    k_cache  [R, S, Hkv, dh]  (v_cache alike); S must be a 128 multiple
    block_req [nq] int32  request per q block (-1 = dead block)
    q_pos    [T] int32    absolute positions (-1 = padded row)
    kv_len   [R] int32    visibility bound per request

    Inference-only (no VJP).  ``impl`` mirrors the ``bwd_impl`` pattern:
    "pallas" runs ``kernel.ragged_decode_fwd`` (interpret off-TPU), "xla"
    the blockwise-jnp fallback; None defers to $REPRO_KERNEL_DECODE.
    """
    t, hq, dh = q.shape
    nq = block_req.shape[0]
    assert t % nq == 0, (t, nq)
    blk_q = t // nq
    qb = q.reshape(nq, blk_q, hq, dh)
    qp = q_pos.reshape(nq, blk_q)
    if _resolve_decode(impl) == "pallas":
        out = K.ragged_decode_fwd(qb, k_cache, v_cache, block_req, kv_len,
                                  qp, window=window, softcap=softcap,
                                  scale=scale, interpret=not _on_tpu())
    else:
        out = _xla_ragged_decode(qb, k_cache, v_cache, block_req, kv_len,
                                 qp, window=window, softcap=softcap,
                                 scale=scale)
    return out.reshape(t, hq, dh)
