"""CAD runtime: dispatch CA-tasks to the attention-server pool.

Dataflow per transformer layer (paper §4.1, Figure 2):

  local q/k/v blocks --gather--> per-destination send buffers
      --all_to_all--> attention servers (in-place: same devices)
      --fused CA kernel over the task batch--> outputs
      --all_to_all (transposed)--> home ranks --scatter--> local layout

Everything is linear except the CA kernel, so JAX transposes the backward
pass to the mirror-image communication automatically (the paper's
"backward reuses the schedule" property holds by construction).
DESIGN.md §1-§2 diagram the dataflow and the ping-pong overlap.

Two execution paths with identical math (shared helpers):
  * shard_map over the mesh's data axes with lax.all_to_all — the real
    distributed path (dry-run / TPU).
  * a "global simulation" on a single device where the exchange is a
    transpose on stacked [D, ...] arrays — used by tests & CPU examples;
    it IS the same per-rank code vmapped.

Ping-pong (paper §4.1): the layer's rows are split into two nano-batches
whose dispatch/compute phases are interleaved so XLA's async collectives
can overlap the A2A of one with the CA compute of the other.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.attention import NEG_INF, xla_flash_attention
from repro.core.mask import live_block_mask, live_kv_len, mask_params
from repro.core.plan import CADConfig, PingPongPlan, ca_pair_bound
from repro.obs import server_track
from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class CADContext:
    """Static CAD pool description + the (traced) plan for this step.

    ``plan`` is a :class:`repro.core.plan.StepPlan` (or
    :class:`PingPongPlan` when ping-pong is on).  Legacy dict plans and
    (ping, pong) tuples are still accepted for one release."""
    cfg: CADConfig
    plan: Any = None          # StepPlan | PingPongPlan | legacy dict/tuple
    kernel: str = "pallas"    # "pallas" | "xla" server implementation
    bwd: Any = None           # None (backend default) | "pallas" | "xla"
    jmax: int = 0             # max kv blocks any task touches (0 -> nkv)
    pingpong: bool = False
    mask: Any = None          # Optional[MaskSpec] — the step's task shape
                              # (DESIGN.md §12); None = dense causal

    def bind_plan(self, ctx, plan):
        new_cad = dataclasses.replace(self, plan=plan)
        return dataclasses.replace(ctx, cad=new_cad)


# ------------------------------------------------------------ helpers
def _to_blocks(x, blk):
    """[Bl, S, ...] -> [NB, blk, ...] (row-major token stream)."""
    bl, s = x.shape[:2]
    nb = bl * s // blk
    return x.reshape((nb, blk) + x.shape[2:])


def _gather_blocks(xb, idx, fill=0.0):
    """xb [NB, ...]; idx [...] with -1 padding -> gathered, pad = fill."""
    safe = jnp.maximum(idx, 0)
    out = xb[safe]
    mask = (idx >= 0).reshape(idx.shape + (1,) * (xb.ndim - 1))
    return jnp.where(mask, out, fill)


def _make_sends(qb, kb, vb, posb, plan):
    """Per-rank send buffers.  plan rows are this rank's (as src)."""
    q_send = _gather_blocks(qb, plan["q_send_idx"])      # [D, CQ, blk, H, dh]
    qpos_send = _gather_blocks(posb, plan["q_send_idx"], fill=-1)
    k_send = _gather_blocks(kb, plan["kv_send_idx"])     # [D, CKV, blk, Hk, dh]
    v_send = _gather_blocks(vb, plan["kv_send_idx"])
    kpos_send = _gather_blocks(posb, plan["kv_send_idx"], fill=-1)
    return q_send, qpos_send, k_send, v_send, kpos_send


def _server_tasks(qb, kb, vb, posb, recv, plan, cfg: CADConfig):
    """Assemble the fused CA-task batch on this server."""
    q_recv, qpos_recv, k_recv, v_recv, kpos_recv = recv
    d, cq, ckv = cfg.n_servers, cfg.cq, cfg.ckv
    # task list: home tasks then received tasks
    q_home = _gather_blocks(qb, plan["q_home_idx"])
    qpos_home = _gather_blocks(posb, plan["q_home_idx"], fill=-1)
    q_tasks = jnp.concatenate(
        [q_home, q_recv.reshape((d * cq,) + q_recv.shape[2:])], axis=0)
    qpos_tasks = jnp.concatenate(
        [qpos_home, qpos_recv.reshape(d * cq, -1)], axis=0)
    # dense kv buffer: concat(local blocks, received slots), then gather
    k_all = jnp.concatenate(
        [kb, k_recv.reshape((d * ckv,) + k_recv.shape[2:])], axis=0)
    v_all = jnp.concatenate(
        [vb, v_recv.reshape((d * ckv,) + v_recv.shape[2:])], axis=0)
    kpos_all = jnp.concatenate(
        [posb, kpos_recv.reshape(d * ckv, -1)], axis=0)
    k_buf = _gather_blocks(k_all, plan["kv_gather"])
    v_buf = _gather_blocks(v_all, plan["kv_gather"])
    kpos_buf = _gather_blocks(kpos_all, plan["kv_gather"], fill=-1)
    return q_tasks, qpos_tasks, k_buf, v_buf, kpos_buf


def _server_pair(qf, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos, j, *,
                 softcap, window, scale, rep, n, sink=0, rate=1):
    """logits/mask/value block for relative kv index j of every task.

    ``sink``/``rate`` are the unpacked MaskSpec params (DESIGN.md §12);
    both default to the pre-mask no-op so dense-causal traces stay
    byte-identical.  Positions are in-document, so the dilated stride
    operates on in-doc block indices at the task blk granularity."""
    idx = jnp.clip(kv_start + j, 0, n - 1)                  # [T]
    kj = k_buf[idx]                                         # [T, blk, Hkv, dh]
    vj = v_buf[idx]
    pkj = kv_pos[idx]                                       # [T, blk]
    if rep > 1:
        kj = jnp.repeat(kj, rep, axis=2)
        vj = jnp.repeat(vj, rep, axis=2)
    logits = jnp.einsum("tqhd,tkhd->thqk", qf,
                        kj.astype(jnp.float32)) * scale
    if softcap and softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    live = (j < kv_len)[:, None, None, None]
    msk = (q_pos[:, None, :, None] >= pkj[:, None, None, :]) \
        & (q_pos[:, None, :, None] >= 0) \
        & (pkj[:, None, None, :] >= 0) & live
    if window and window > 0:
        w = (q_pos[:, None, :, None] - pkj[:, None, None, :]) < window
        if sink and sink > 0:
            w = w | (pkj[:, None, None, :] < sink)
        msk &= w
    if rate and rate > 1:
        blk = qf.shape[1]
        msk &= ((q_pos[:, None, :, None] // blk)
                - (pkj[:, None, None, :] // blk)) % rate == 0
    return jnp.where(msk, logits, NEG_INF), msk, kj, vj, idx


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _xla_server(q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos,
                jmax, softcap, window, scale, sink=0, rate=1):
    out, _ = _xla_server_fwd_impl(q_tasks, k_buf, v_buf, kv_start, kv_len,
                                  q_pos, kv_pos, jmax, softcap, window,
                                  scale, sink, rate)
    return out


def _accum_init(T, hq, blk, dh):
    """Fresh running (m, l, acc) flash-accumulation carry."""
    return (jnp.full((T, hq, blk), NEG_INF, jnp.float32),
            jnp.zeros((T, hq, blk), jnp.float32),
            jnp.zeros((T, hq, blk, dh), jnp.float32))


def _accum_body(qf, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos, *,
                softcap, window, scale, rep, n, sink=0, rate=1):
    """One flash-accumulation scan step over relative kv-block index j.
    Shared — same closure, same op sequence — by the full serve scan and
    the chunked KV-streaming scans, which is what makes streamed output
    bit-identical to the unstreamed path (DESIGN.md §11): splitting a
    scan into chunked sub-scans with the carry threaded across chunks
    performs the identical FP operations in the identical order.
    Iterations past a task's kv_len are exact no-ops (masked logits are
    NEG_INF, so m/l/acc are multiplied by exp(0) == 1 and incremented
    by 0), which also covers a ragged final chunk."""
    def body(carry, j):
        m_acc, l_acc, acc = carry
        logits, msk, kj, vj, _ = _server_pair(
            qf, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos, j,
            softcap=softcap, window=window, scale=scale, rep=rep, n=n,
            sink=sink, rate=rate)
        m_new = jnp.maximum(m_acc, logits.max(-1))
        p = jnp.where(msk, jnp.exp(logits - m_new[..., None]), 0.0)
        corr = jnp.exp(m_acc - m_new)
        l_new = l_acc * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "thqk,tkhd->thqd", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc), None
    return body


def _accum_finalize(m_acc, l_acc, acc, dtype):
    """Normalize a finished flash carry into (out, lse)."""
    out = acc / jnp.maximum(l_acc, 1e-30)[..., None]
    live = m_acc > NEG_INF / 2
    out = jnp.where(live[..., None], out, 0.0)
    lse = jnp.where(live, m_acc + jnp.log(jnp.maximum(l_acc, 1e-30)),
                    jnp.float32(2.0 ** 30))
    return out.transpose(0, 2, 1, 3).astype(dtype), lse


def _xla_server_fwd_impl(q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos,
                         kv_pos, jmax, softcap, window, scale, sink=0,
                         rate=1):
    """Blockwise jnp attention-server (the compile/dry-run path): scan over
    relative kv-block index j, gathering each task's j-th context block."""
    T, blk, hq, dh = q_tasks.shape
    n = k_buf.shape[0]
    rep = hq // k_buf.shape[2]
    scale = scale if scale is not None else dh ** -0.5
    qf = q_tasks.astype(jnp.float32)
    body = _accum_body(qf, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos,
                       softcap=softcap, window=window, scale=scale,
                       rep=rep, n=n, sink=sink, rate=rate)
    carry, _ = jax.lax.scan(body, _accum_init(T, hq, blk, dh),
                            jnp.arange(jmax))
    return _accum_finalize(*carry, q_tasks.dtype)


def _xla_server_fwd(q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos,
                    jmax, softcap, window, scale, sink=0, rate=1):
    out, lse = _xla_server_fwd_impl(q_tasks, k_buf, v_buf, kv_start,
                                    kv_len, q_pos, kv_pos, jmax, softcap,
                                    window, scale, sink, rate)
    return out, (q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos,
                 out, lse)


def _xla_server_bwd(jmax, softcap, window, scale, sink, rate, res, g):
    """Flash-style recompute backward: nothing quadratic is saved."""
    return _xla_server_bwd_impl(res, g, None, jmax=jmax, softcap=softcap,
                                window=window, scale=scale, sink=sink,
                                rate=rate) + (None, None, None, None)


def _xla_server_bwd_impl(res, g, g_lse, *, jmax, softcap, window, scale,
                         sink, rate):
    """Blockwise recompute backward body, shared by the full serve's vjp
    and the ring partial op (``ops.ca_partial_attention``).  ``g_lse``
    is the cotangent of the partial's log-sum-exp output (None for the
    out-only full serve — the original expression is kept verbatim so
    pre-ring traces stay byte-identical); since ``d lse / d logits`` is
    the softmax itself, it joins the score gradient as
    ``ds = p * (dp - delta + g_lse)``.  Returns ``(dq, dk, dv)``."""
    q_tasks, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos, out, lse = res
    T, blk, hq, dh = q_tasks.shape
    n = k_buf.shape[0]
    hkv = k_buf.shape[2]
    rep = hq // hkv
    scale_v = scale if scale is not None else dh ** -0.5
    qf = q_tasks.astype(jnp.float32)
    gf = g.astype(jnp.float32)                              # [T,blk,hq,dh]
    of = out.astype(jnp.float32)
    delta = jnp.einsum("tqhd,tqhd->thq", gf, of)            # [T,hq,blk]

    dq0 = jnp.zeros((T, blk, hq, dh), jnp.float32)
    dk0 = jnp.zeros((n, blk, hkv, dh), jnp.float32)
    dv0 = jnp.zeros((n, blk, hkv, dh), jnp.float32)

    def body(carry, j):
        dq_acc, dk_acc, dv_acc = carry
        logits, msk, kj, vj, idx = _server_pair(
            qf, k_buf, v_buf, kv_start, kv_len, q_pos, kv_pos, j,
            softcap=softcap, window=window, scale=scale_v, rep=rep, n=n,
            sink=sink, rate=rate)
        p = jnp.where(msk, jnp.exp(logits - lse[..., None]), 0.0)
        dvj = jnp.einsum("thqk,tqhd->tkhd", p, gf)          # [T,blk,hq,dh]
        dp = jnp.einsum("tqhd,tkhd->thqk", gf, vj.astype(jnp.float32))
        if g_lse is None:
            ds = p * (dp - delta[..., None])
        else:
            ds = p * (dp - (delta - g_lse.astype(jnp.float32))[..., None])
        if softcap and softcap > 0:
            sc = jnp.where(msk, logits / softcap, 0.0)
            ds = ds * (1.0 - sc * sc)
        ds = ds * scale_v
        dq_acc = dq_acc + jnp.einsum("thqk,tkhd->tqhd", ds,
                                     kj.astype(jnp.float32))
        dkj = jnp.einsum("thqk,tqhd->tkhd", ds, qf)
        # fold GQA repeats, scatter-add into kv buffer rows
        dkj = dkj.reshape(T, blk, hkv, rep, dh).sum(3)
        dvj = dvj.reshape(T, blk, hkv, rep, dh).sum(3)
        live = (j < kv_len).astype(jnp.float32)[:, None, None, None]
        dk_acc = dk_acc.at[idx].add(dkj * live)
        dv_acc = dv_acc.at[idx].add(dvj * live)
        return (dq_acc, dk_acc, dv_acc), None

    (dq, dk, dv), _ = jax.lax.scan(body, (dq0, dk0, dv0),
                                   jnp.arange(jmax))
    return (dq.astype(q_tasks.dtype), dk.astype(k_buf.dtype),
            dv.astype(v_buf.dtype))


_xla_server.defvjp(_xla_server_fwd, _xla_server_bwd)


def _serve(q_tasks, qpos_tasks, k_buf, v_buf, kpos_buf, plan, cad,
           softcap, window, scale):
    jmax = cad.jmax or cad.cfg.nkv
    window, sink, rate = mask_params(cad.mask, window)
    if cad.kernel == "pallas":
        # pair lists sized by the pool's bound, which CADSession.plan
        # checks every plan against
        from repro.kernels.packed_flash.ops import ca_server_attention
        return ca_server_attention(
            q_tasks, k_buf, v_buf, plan["task_kv_start"],
            plan["task_kv_len"], qpos_tasks, kpos_buf,
            True, window, softcap, scale, jmax, cad.bwd, sink, rate,
            ca_pair_bound(cad.cfg, jmax))
    return _xla_server(q_tasks, k_buf, v_buf, plan["task_kv_start"],
                       plan["task_kv_len"], qpos_tasks, kpos_buf,
                       jmax, softcap, window, scale, sink, rate)


def _scatter_outputs(out_tasks, ret_recv, plan, cfg: CADConfig, nb, blk,
                     hq, dh, dtype):
    """Home-rank reassembly: home task slots + returned remote outputs."""
    out = jnp.zeros((nb, blk, hq, dh), jnp.float32)
    # home tasks: slot i corresponds to local block q_home_idx[i]
    idx_home = plan["q_home_idx"]
    safe = jnp.maximum(idx_home, 0)
    contrib = jnp.where((idx_home >= 0)[:, None, None, None],
                        out_tasks[:nb].astype(jnp.float32), 0.0)
    out = out.at[safe].add(contrib)
    # remote returns: ret_recv [D, CQ, blk, H, dh]; slot (s, c) is the
    # output of local block q_send_idx[s, c] (this rank's row as src)
    idx_rem = plan["q_send_idx"]                          # [D, CQ]
    safe_r = jnp.maximum(idx_rem, 0)
    contrib_r = jnp.where((idx_rem >= 0)[:, :, None, None, None],
                          ret_recv.astype(jnp.float32), 0.0)
    out = out.at[safe_r.reshape(-1)].add(
        contrib_r.reshape((-1,) + contrib_r.shape[2:]))
    return out.astype(dtype)


# ------------------------------------------------------- execution paths
def _rank_fn(q, k, v, pos, plan, cad, softcap, scale, axis_names):
    """Body run per rank inside shard_map.  q/k/v [Bl, S, H(l), dh]."""
    cfg = cad.cfg
    blk = cfg.blk
    qb = _to_blocks(q, blk)
    kb = _to_blocks(k, blk)
    vb = _to_blocks(v, blk)
    posb = _to_blocks(pos, blk)
    nb = qb.shape[0]

    sends = _make_sends(qb, kb, vb, posb, plan)
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_names,
                            split_axis=0, concat_axis=0)
    recv = tuple(a2a(s) for s in sends)
    q_tasks, qpos_tasks, k_buf, v_buf, kpos_buf = _server_tasks(
        qb, kb, vb, posb, recv, plan, cfg)
    out_tasks = _serve(q_tasks, qpos_tasks, k_buf, v_buf, kpos_buf, plan,
                       cad, softcap, 0, scale)
    ret_send = out_tasks[nb:].reshape((cfg.n_servers, cfg.cq)
                                      + out_tasks.shape[1:])
    ret_recv = a2a(ret_send)
    out = _scatter_outputs(out_tasks, ret_recv, plan, cfg, nb, blk,
                           q.shape[2], q.shape[3], q.dtype)
    return out.reshape(q.shape)


def _sim_exchange(x):
    """Global-simulation all_to_all: [D_src, D_dst, C, ...] ->
    [D_dst, D_src, C, ...]."""
    return jnp.swapaxes(x, 0, 1)


def _global_sim(q, k, v, pos, plan, cad, softcap, scale):
    """Single-device semantics-equivalent execution over stacked ranks.
    q [D*Bl, S, H, dh] with rank-major rows."""
    cfg = cad.cfg
    d = cfg.n_servers
    blk = cfg.blk

    def stack_ranks(x):
        return x.reshape((d, x.shape[0] // d) + x.shape[1:])

    qs, ks, vs, ps = map(stack_ranks, (q, k, v, pos))
    qb = jax.vmap(lambda t: _to_blocks(t, blk))(qs)
    kb = jax.vmap(lambda t: _to_blocks(t, blk))(ks)
    vb = jax.vmap(lambda t: _to_blocks(t, blk))(vs)
    posb = jax.vmap(lambda t: _to_blocks(t, blk))(ps)
    nb = qb.shape[1]

    sends = jax.vmap(_make_sends)(qb, kb, vb, posb, plan)
    recv = tuple(_sim_exchange(s) for s in sends)
    q_tasks, qpos_tasks, k_buf, v_buf, kpos_buf = jax.vmap(
        lambda a, b, c, dd, r, pr: _server_tasks(a, b, c, dd, r, pr, cfg)
    )(qb, kb, vb, posb, recv, plan)
    out_tasks = jax.vmap(
        lambda a, b, c, dd, e, pr: _serve(a, b, c, dd, e, pr, cad, softcap,
                                          0, scale)
    )(q_tasks, qpos_tasks, k_buf, v_buf, kpos_buf, plan)
    ret_send = out_tasks[:, nb:].reshape((d, d, cfg.cq)
                                         + out_tasks.shape[2:])
    ret_recv = _sim_exchange(ret_send)
    out = jax.vmap(
        lambda ot, rr, pr: _scatter_outputs(ot, rr, pr, cfg, nb, blk,
                                            q.shape[2], q.shape[3], q.dtype)
    )(out_tasks, ret_recv, plan)
    return out.reshape(q.shape)


# ----------------------------------------------------- calibration probes
def iter_plan_tasks(cfg: CADConfig, plan, mask=None) \
        -> "list[Tuple[int, int, int, int]]":
    """Host-side: the (server, task_slot, q_tokens, kv_tokens) list of
    every live CA task in a :class:`StepPlan` (or legacy dict plan).
    Every task is one q block against a (kv_len · blk)-token context —
    the shapes the runtime calibrator's grid cells are keyed by.  Task
    count comes from the plan arrays themselves, so nano-batch plans
    built from a re-sized ping-pong config iterate correctly.

    With a non-trivial ``mask`` (:class:`~repro.core.mask.MaskSpec`),
    ``kv_tokens`` is the task's *live* kv length — the blocks the masked
    kernel actually iterates (DESIGN.md §12) — so calibration grid cells
    key on work done rather than rectangle area."""
    kv_len = np.asarray(plan["task_kv_len"])
    d, n_tasks = kv_len.shape
    out = []
    for s in range(d):
        for slot in range(n_tasks):
            kvl = int(kv_len[s, slot])
            if kvl > 0:
                out.append((s, slot, cfg.blk,
                            live_kv_len(mask, kvl, cfg.blk)))
    return out


@functools.lru_cache(maxsize=16)
def _probe_serve_fn(cfg: CADConfig, kernel: str, bwd, jmax: int,
                    softcap: float = 0.0, scale=None, mask=None):
    """One jitted serve per pool geometry — probes recur every
    ``calibrate_every`` steps and must not pay a re-trace each time
    (jit caches per argument shape under the returned callable)."""
    cad = CADContext(cfg=cfg, kernel=kernel, bwd=bwd, jmax=jmax, mask=mask)
    return jax.jit(lambda qt, qp, kb_, vb_, kp, st, ln: _serve(
        qt, qp, kb_, vb_, kp,
        {"task_kv_start": st, "task_kv_len": ln}, cad, softcap, 0, scale))


def build_server_inputs(cad: CADContext, plan, q, k, v, pos):
    """Host-side decomposed dispatch: assemble every server's fused
    CA-task inputs for one plan, without the collective exchange.

    ``q``/``k``/``v`` are the stacked rank-major global layout
    (``[D*Bl, S, H(kv), dh]``, as fed to ``cad_attention``'s global
    simulation), ``pos`` is ``[D*Bl, S]`` with -1 marking padding.
    Returns ``(inputs, plans_r)``: per server *s*, ``inputs[s]`` is the
    ``(q_tasks, qpos_tasks, k_buf, v_buf, kpos_buf)`` tuple ``_serve``
    consumes and ``plans_r[s]`` its per-rank plan slice.

    This is the elastic runtime's execution substrate (DESIGN.md §9):
    because each server's task batch is materialized independently, a
    single server's serve can fail, be retried, or be speculatively
    re-executed without touching the others — the per-server
    decomposition the fused shard_map path cannot express.

    The per-server ``k_buf``/``v_buf`` returned here is also the unit
    chunked KV streaming consumes (DESIGN.md §11): when the config sets
    ``stream_chunk``, ``serve_task_batch`` reads the buffer one chunk
    of kv blocks at a time instead of scanning it whole, so a server
    whose budget cannot hold a task's full prefix still serves it."""
    cfg = cad.cfg
    d, blk = cfg.n_servers, cfg.blk
    plan_np = jax.tree.map(np.asarray, dict(plan.items()))

    def stack_ranks(x):
        return x.reshape((d, x.shape[0] // d) + x.shape[1:])

    qs, ks, vs, ps = map(stack_ranks, (q, k, v, pos))
    blocks, sends, plans_r = [], [], []
    for r in range(d):
        plan_r = jax.tree.map(lambda a, r=r: jnp.asarray(a[r]), plan_np)
        qb, kb, vb = (_to_blocks(x, blk) for x in (qs[r], ks[r], vs[r]))
        posb = _to_blocks(ps[r], blk)
        blocks.append((qb, kb, vb, posb))
        sends.append(_make_sends(qb, kb, vb, posb, plan_r))
        plans_r.append(plan_r)
    # stacked exchange: [D_src, D_dst, C, ...] -> [D_dst, D_src, C, ...]
    recv = tuple(jnp.swapaxes(jnp.stack([s[i] for s in sends]), 0, 1)
                 for i in range(len(sends[0])))
    inputs = []
    for s in range(d):
        qb, kb, vb, posb = blocks[s]
        recv_s = tuple(f[s] for f in recv)
        inputs.append(_server_tasks(qb, kb, vb, posb, recv_s, plans_r[s],
                                    cfg))
    return inputs, plans_r


@functools.lru_cache(maxsize=16)
def _stream_serve_fns(n_chunk: int, softcap: float, window: int, scale,
                      sink: int = 0, rate: int = 1):
    """Jitted (chunk_step, finalize) pair for chunked KV streaming —
    cached per chunk geometry like :func:`_probe_serve_fn` (jit then
    caches per input shape underneath).  ``sink``/``rate`` join the
    cache key: a masked chunk body is a different trace."""

    @jax.jit
    def chunk_step(carry, q_tasks, k_buf, v_buf, kv_start, kv_len,
                   q_pos, kv_pos, j0):
        dh = q_tasks.shape[3]
        n = k_buf.shape[0]
        body = _accum_body(
            q_tasks.astype(jnp.float32), k_buf, v_buf, kv_start, kv_len,
            q_pos, kv_pos, softcap=softcap, window=window,
            scale=scale if scale is not None else dh ** -0.5,
            rep=q_tasks.shape[2] // k_buf.shape[2], n=n,
            sink=sink, rate=rate)
        # scan length is padded to >= 2 with a masked no-op iteration
        # (j = n sits past every task's kv_len, so the carry passes
        # through bitwise unchanged): XLA unrolls a trip-count-1 loop
        # and re-fuses the body with its surroundings, which would cost
        # bit-identity with the unstreamed scan's loop body
        length = max(n_chunk, 2)
        idx = jnp.arange(length)
        js = jnp.where(idx < n_chunk, j0 + idx, jnp.int32(n))
        carry, _ = jax.lax.scan(body, carry, js)
        return carry

    @jax.jit
    def finalize(carry, q_tasks):
        return _accum_finalize(*carry, q_tasks.dtype)[0]

    return chunk_step, finalize


def stream_task_batch(cad: CADContext, inputs_s, plan_s, *,
                      chunk_blocks: Optional[int] = None,
                      softcap: float = 0.0, scale=None):
    """Chunked KV streaming serve for ONE server (DESIGN.md §11): the
    fused task batch consumes its kv range in fixed-size chunks of
    ``chunk_blocks`` kv blocks, carrying the running (m, l, acc) flash
    accumulation across chunks, then normalizes once.  The per-chunk
    scan reuses the unstreamed server's scan body verbatim, so the
    streamed output is bit-identical to ``serve_task_batch`` with
    streaming off — the same merge-math discipline as
    :func:`merge_recovered`'s bitwise select, applied to accumulation
    instead of selection.  On hardware each chunk's k/v blocks are
    fetched and discarded per chunk, bounding kv residency by one chunk
    (the planner's model for streamed docs); the host-side simulation
    materializes the full buffer but only ever *reads* one chunk per
    step.  The streamed path always runs the blockwise server — with
    ``kernel='pallas'`` the unstreamed fused kernel remains in charge
    whenever the task batch fits within one chunk."""
    cfg = cad.cfg
    chunk = int(chunk_blocks if chunk_blocks is not None
                else cfg.stream_chunk)
    if chunk <= 0:
        raise ValueError(
            f"stream_task_batch needs chunk_blocks > 0 kv blocks "
            f"(or CADConfig.stream_chunk set), got {chunk}")
    jmax = cad.jmax or cfg.nkv
    q_tasks, qpos, k_buf, v_buf, kpos = inputs_s
    T, blk, hq, dh = q_tasks.shape
    window, sink, rate = mask_params(cad.mask, 0)
    step, finalize = _stream_serve_fns(chunk, float(softcap), window,
                                       scale, sink, rate)
    carry = _accum_init(T, hq, blk, dh)
    kv_start = plan_s["task_kv_start"]
    kv_len = plan_s["task_kv_len"]
    for j0 in range(0, jmax, chunk):
        # the ragged tail runs a full chunk; iterations past jmax are
        # exact no-ops (see _accum_body), preserving bit-identity
        carry = step(carry, q_tasks, k_buf, v_buf, kv_start, kv_len,
                     qpos, kpos, jnp.int32(j0))
    return finalize(carry, q_tasks)


def serve_task_batch(cad: CADContext, inputs_s, plan_s, *,
                     softcap: float = 0.0, scale=None,
                     stream_chunk: Optional[int] = None):
    """Run ONE server's fused CA-task batch eagerly (compiled once per
    pool geometry) — the unit of work the elastic runtime dispatches,
    retries and speculates on.

    When chunked KV streaming is enabled (``cfg.stream_chunk`` > 0, or
    an explicit ``stream_chunk`` override) and the kv range spans more
    than one chunk, the batch is served through
    :func:`stream_task_batch` — so every caller (elastic executor
    primary serves, fabric serve backfill, recovery re-serves) inherits
    memory-bounded serving from the config with no code of its own."""
    chunk = cad.cfg.stream_chunk if stream_chunk is None \
        else int(stream_chunk)
    jmax = cad.jmax or cad.cfg.nkv
    if 0 < chunk < jmax:
        return stream_task_batch(cad, inputs_s, plan_s,
                                 chunk_blocks=chunk, softcap=softcap,
                                 scale=scale)
    q_tasks, qpos, k_buf, v_buf, kpos = inputs_s
    serve = _probe_serve_fn(cad.cfg, cad.kernel, cad.bwd, cad.jmax,
                            softcap, scale, cad.mask)
    return serve(q_tasks, qpos, k_buf, v_buf, kpos,
                 plan_s["task_kv_start"], plan_s["task_kv_len"])


def assemble_step_outputs(cfg: CADConfig, plan, out_tasks, q_shape,
                          dtype):
    """Host-side home-rank reassembly: the transposed return exchange +
    scatter of the distributed path, applied to per-server task outputs.

    ``out_tasks`` maps server -> its ``[T, blk, H, dh]`` fused-batch
    output; servers absent from the dict (failed / killed mid-step)
    contribute zeros, so their blocks can be recovered separately and
    merged with :func:`merge_recovered` — exactly-once by construction.
    Scatter arithmetic is identical to the fused path's
    ``_scatter_outputs``, so outputs are bit-identical to a fault-free
    execution of the same plan."""
    d, blk = cfg.n_servers, cfg.blk
    plan_np = jax.tree.map(np.asarray, dict(plan.items()))
    nb = plan_np["q_home_idx"].shape[1]
    cq = plan_np["q_send_idx"].shape[2]
    n_tasks = plan_np["task_kv_len"].shape[1]
    hq, dh = q_shape[-2], q_shape[-1]
    zeros = None
    outs = []
    for r in range(d):
        ot_r = out_tasks.get(r)
        if ot_r is None:
            if zeros is None:
                zeros = jnp.zeros((n_tasks, blk, hq, dh), dtype)
            ot_r = zeros
        ret_recv = jnp.stack([
            (out_tasks[s][nb + r * cq: nb + (r + 1) * cq]
             if s in out_tasks else
             jnp.zeros((cq, blk, hq, dh), dtype))
            for s in range(d)])
        plan_r = jax.tree.map(lambda a, r=r: jnp.asarray(a[r]), plan_np)
        out_r = _scatter_outputs(ot_r, ret_recv, plan_r, cfg, nb, blk,
                                 hq, dh, dtype)
        outs.append(out_r.reshape((q_shape[0] // d,) + q_shape[1:]))
    return jnp.concatenate(outs, axis=0)


def merge_recovered(cfg: CADConfig, base, recovered,
                    lost_blocks: np.ndarray):
    """Exactly-once merge of a recovery sub-plan's outputs into a step's
    base outputs: every q block's output is *selected* from exactly one
    execution (bitwise — no floating-point accumulation across the two),
    recovered blocks from ``recovered``, everything else from ``base``.
    ``lost_blocks`` is the boolean ``[D, NB]`` (or flat ``[D*NB]``) mask
    of blocks whose primary serve was lost."""
    d, blk = cfg.n_servers, cfg.blk
    lost = np.asarray(lost_blocks, bool).reshape(d, -1)
    tok = np.repeat(lost, blk, axis=1)           # [D, NB*blk] per-token
    mask = tok.reshape((base.shape[0], base.shape[1]))
    return jnp.where(jnp.asarray(mask)[..., None, None], recovered, base)


# ------------------------------------------- ring baseline (DESIGN.md §13)
def _plan_task_q_block(cfg: CADConfig, plan_np, server: int,
                       slot: int) -> Optional[int]:
    """Global q-block index of task ``slot`` on ``server`` (None for a
    dead slot) — the plan-array inverse ``iter_plan_tasks`` walks."""
    nb, cq = cfg.nb, cfg.cq
    if slot < nb:
        idx = int(plan_np["q_home_idx"][server, slot])
        return server * nb + idx if idx >= 0 else None
    src, c = divmod(slot - nb, cq)
    idx = int(plan_np["q_send_idx"][src, server, c])
    return src * nb + idx if idx >= 0 else None


def ring_pass_geometry(cfg: CADConfig, segment_ids: np.ndarray, plan, *,
                       n_passes: Optional[int] = None, mask=None) \
        -> List[Dict[str, Any]]:
    """Host-side ring pass construction (DESIGN.md §13): split every
    task's kv prefix into the P contiguous document shards of the
    DISTFLASHATTN schedule and emit one pseudo-plan per ring pass.

    At pass ``t`` a task whose q block sits in document shard ``i``
    consumes kv shard ``j = (i - t) % P``, i.e. blocks
    ``[j*L, (j+1)*L)`` of its document clipped to the causal prefix
    (``L = ring_shard_size``).  Dead pairs are skipped *exactly*:
    causal-dead shards (``j > i``) get ``kv_len 0``, and with a
    non-trivial ``mask`` the shard range is trimmed to its live columns
    (``live_block_mask``) — a fully mask-dead shard is dropped like a
    causal one.  Returns one dict per pass with the ``task_kv_start`` /
    ``task_kv_len`` ``[D, T]`` arrays the partial serve consumes and
    ``jmax`` (max live kv blocks across the pool; 0 marks a globally
    dead pass the executors skip entirely)."""
    from repro.core.scheduler import layout_from_segments, ring_shard_size
    docs, doc_of, bi_of = layout_from_segments(
        np.asarray(segment_ids).reshape(cfg.n_servers, -1), cfg.blk,
        cfg.n_servers)
    plan_np = jax.tree.map(np.asarray, dict(plan.items()))
    kv_start = plan_np["task_kv_start"]
    kv_len = plan_np["task_kv_len"]
    d, n_tasks = kv_len.shape
    P = int(n_passes) if n_passes else cfg.n_servers
    trivial = mask is None or mask.trivial
    lbm_cache: Dict[int, np.ndarray] = {}

    def lbm(n):
        if n not in lbm_cache:
            lbm_cache[n] = live_block_mask(mask, n, n, cfg.blk)
        return lbm_cache[n]

    starts = [kv_start.copy() for _ in range(P)]
    lens = [np.zeros_like(kv_len) for _ in range(P)]
    for s in range(d):
        for slot in range(n_tasks):
            if kv_len[s, slot] <= 0:
                continue
            g = _plan_task_q_block(cfg, plan_np, s, slot)
            bi = int(bi_of[g])
            n = docs[int(doc_of[g])].n_blocks
            L = ring_shard_size(n, P)
            i = bi // L
            row = None if trivial else lbm(n)[bi]
            for t in range(P):
                j = (i - t) % P
                lo, hi = j * L, min((j + 1) * L, bi + 1)
                if hi <= lo:
                    continue                      # causal-dead ring step
                if row is not None:
                    live = np.nonzero(row[lo:hi])[0]
                    if live.size == 0:
                        continue                  # mask-dead ring step
                    lo, hi = lo + int(live[0]), lo + int(live[-1]) + 1
                starts[t][s, slot] = kv_start[s, slot] + lo
                lens[t][s, slot] = hi - lo
    return [{"task_kv_start": starts[t], "task_kv_len": lens[t],
             "jmax": int(lens[t].max(initial=0))} for t in range(P)]


def _ring_serve_merge(cad: CADContext, inputs_s, pass_plans, server: int,
                      *, softcap: float = 0.0, scale=None):
    """ONE endpoint's ring execution: serve each live pass's kv window as
    a finalized ``(out, lse)`` partial and fold the passes together in
    pass order with ``merge_softmax_partials`` — dead per-task windows
    merge as bitwise no-ops, globally dead passes are never served."""
    from repro.kernels.packed_flash import ops as O
    q_tasks, qpos, k_buf, v_buf, kpos = inputs_s
    window, sink, rate = mask_params(cad.mask, 0)
    merged = None
    for t, pp in enumerate(pass_plans):
        if t > 0 and pp["jmax"] <= 0:
            continue                        # dead ring pass: skipped exactly
        o, l = O.ca_partial_attention(
            q_tasks, k_buf, v_buf,
            jnp.asarray(pp["task_kv_start"][server]),
            jnp.asarray(pp["task_kv_len"][server]), qpos, kpos,
            max(pp["jmax"], 1), window, softcap, scale, sink, rate,
            cad.kernel, ca_pair_bound(cad.cfg, cad.jmax or cad.cfg.nkv))
        merged = (o, l) if merged is None \
            else O.merge_softmax_partials(merged[0], merged[1], o, l)
    return merged[0]


def ring_attention(cad: CADContext, plan, segment_ids: np.ndarray,
                   q, k, v, pos, *, n_passes: Optional[int] = None,
                   softcap: float = 0.0, scale=None, pass_plans=None):
    """Decomposed ring-attention execution of one step (DESIGN.md §13):
    the DISTFLASHATTN baseline run through CAD's own dispatch substrate.
    Each endpoint serves its fused task batch one ring pass at a time —
    kv windows rotating through the P document shards — merging the
    per-pass ``(out, lse)`` partials online, then outputs are
    reassembled exactly like the standard serve.  Bit-identical
    (forward *and* vjp) to :func:`ring_global_sim`, the single-pool
    oracle running the same pass schedule through the fused vmapped
    orchestration."""
    cfg = cad.cfg
    if pass_plans is None:
        pass_plans = ring_pass_geometry(cfg, segment_ids, plan,
                                        n_passes=n_passes, mask=cad.mask)
    inputs, _plans_r = build_server_inputs(cad, plan, q, k, v, pos)
    outs = {s: _ring_serve_merge(cad, inputs[s], pass_plans, s,
                                 softcap=softcap, scale=scale)
            for s in range(cfg.n_servers)}
    return assemble_step_outputs(cfg, plan, outs, q.shape, q.dtype)


def ring_global_sim(q, k, v, pos, plan, cad: CADContext,
                    segment_ids: np.ndarray, *,
                    n_passes: Optional[int] = None,
                    softcap: float = 0.0, scale=None, pass_plans=None):
    """Single-pool oracle for the ring schedule: the same per-pass
    partial serves and lse merges as :func:`ring_attention`, executed
    through the fused vmapped single-device orchestration of
    :func:`_global_sim` — same ops in the same order, different
    orchestration, so the decomposed ring dispatch must match it
    bitwise (the PR 5 differential discipline applied to the ring)."""
    from repro.kernels.packed_flash import ops as O
    cfg = cad.cfg
    d = cfg.n_servers
    blk = cfg.blk
    if pass_plans is None:
        pass_plans = ring_pass_geometry(cfg, segment_ids, plan,
                                        n_passes=n_passes, mask=cad.mask)

    def stack_ranks(x):
        return x.reshape((d, x.shape[0] // d) + x.shape[1:])

    qs, ks, vs, ps = map(stack_ranks, (q, k, v, pos))
    qb = jax.vmap(lambda t: _to_blocks(t, blk))(qs)
    kb = jax.vmap(lambda t: _to_blocks(t, blk))(ks)
    vb = jax.vmap(lambda t: _to_blocks(t, blk))(vs)
    posb = jax.vmap(lambda t: _to_blocks(t, blk))(ps)
    nb = qb.shape[1]

    sends = jax.vmap(_make_sends)(qb, kb, vb, posb, plan)
    recv = tuple(_sim_exchange(s) for s in sends)
    q_tasks, qpos_tasks, k_buf, v_buf, kpos_buf = jax.vmap(
        lambda a, b, c, dd, r, pr: _server_tasks(a, b, c, dd, r, pr, cfg)
    )(qb, kb, vb, posb, recv, plan)

    window, sink, rate = mask_params(cad.mask, 0)
    bound = ca_pair_bound(cfg, cad.jmax or cfg.nkv)
    merged = None
    for t, pp in enumerate(pass_plans):
        if t > 0 and pp["jmax"] <= 0:
            continue                        # dead ring pass: skipped exactly
        o, l = jax.vmap(
            lambda qt, kbf, vbf, st, ln, qp, kp, jm=max(pp["jmax"], 1):
            O.ca_partial_attention(qt, kbf, vbf, st, ln, qp, kp, jm,
                                   window, softcap, scale, sink, rate,
                                   cad.kernel, bound)
        )(q_tasks, k_buf, v_buf, jnp.asarray(pp["task_kv_start"]),
          jnp.asarray(pp["task_kv_len"]), qpos_tasks, kpos_buf)
        merged = (o, l) if merged is None \
            else O.merge_softmax_partials(merged[0], merged[1], o, l)
    out_tasks = merged[0]

    ret_send = out_tasks[:, nb:].reshape((d, d, cfg.cq)
                                         + out_tasks.shape[2:])
    ret_recv = _sim_exchange(ret_send)
    out = jax.vmap(
        lambda ot, rr, pr: _scatter_outputs(ot, rr, pr, cfg, nb, blk,
                                            q.shape[2], q.shape[3], q.dtype)
    )(out_tasks, ret_recv, plan)
    return out.reshape(q.shape)


def probe_plan_times(cad: CADContext, plan, *, n_heads: int = 1,
                     head_dim: int = 8, n_kv_heads: Optional[int] = None,
                     dtype=jnp.float32, seed: int = 0,
                     repeats: int = 1, trace_label: str = "probe") \
        -> List[Tuple[int, List[Tuple[int, int]], float]]:
    """Time each server's fused CA-task batch for one plan, eagerly,
    with synthetic q/k/v — the per-task kernel-timing hook of the
    runtime calibration loop (DESIGN.md §3).

    Kernel time depends on shapes, not values, so random tensors give a
    faithful measurement; the compiled serve is warmed up once so every
    server's timing excludes compilation.  Returns one
    ``(server, [(q_tokens, kv_tokens), ...], seconds)`` entry per
    server, ready for ``GridCalibrator.observe_tasks``.

    Honesty note: the blockwise-XLA fallback server scans a jmax-padded
    kv range for every task, so off-TPU its per-task time is nearly
    flat in kv length; the Pallas kernel (block-pruned scalar-prefetch
    ranges) is where timings genuinely track task shapes."""
    cfg = cad.cfg
    d, nb, blk = cfg.n_servers, cfg.nb, cfg.blk
    s_len = nb * blk
    hkv = n_kv_heads or n_heads
    plan_np = jax.tree.map(np.asarray, dict(plan.items()))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (d, s_len, n_heads, head_dim), dtype)
    k = jax.random.normal(kk, (d, s_len, hkv, head_dim), dtype)
    v = jax.random.normal(kv, (d, s_len, hkv, head_dim), dtype)
    pos = jnp.broadcast_to(jnp.arange(s_len, dtype=jnp.int32)[None],
                           (d, s_len))

    inputs, plans_r = build_server_inputs(cad, plan_np, q, k, v, pos)
    serve = _probe_serve_fn(cfg, cad.kernel, cad.bwd, cad.jmax,
                            mask=cad.mask)

    by_server: Dict[int, List[Tuple[int, int]]] = {s: [] for s in range(d)}
    for s, _slot, qt, kvt in iter_plan_tasks(cfg, plan_np, mask=cad.mask):
        by_server[s].append((qt, kvt))

    results = []
    warm = False
    rec = obs_trace.get_recorder()
    for s in range(d):
        q_tasks, qpos, k_buf, v_buf, kpos = inputs[s]
        args = (q_tasks, qpos, k_buf, v_buf, kpos,
                plans_r[s]["task_kv_start"], plans_r[s]["task_kv_len"])
        if not warm:      # one compile for the shared shape
            jax.block_until_ready(serve(*args))
            warm = True
        # the probe span lands on the server's own gantt track
        # (``trace_label`` distinguishes ping-pong halves — §14)
        with rec.span(trace_label, server_track(s),
                      args={"repeats": max(1, repeats),
                            "n_tasks": len(by_server[s])}):
            t0 = time.perf_counter()
            for _ in range(max(1, repeats)):
                out = serve(*args)
            jax.block_until_ready(out)
            seconds = (time.perf_counter() - t0) / max(1, repeats)
        results.append((s, by_server[s], seconds))
    return results


# --------------------------------------------------------------- frontend
def cad_attention(q, k, v, seg_q, pos_q, seg_kv, pos_kv, *, ctx,
                  causal=True, window=0, softcap=0.0, scale=None,
                  mask=None):
    """Core-attention disaggregation entry point.

    Applies to causal full-attention layers (the quadratic-imbalance
    source).  Windowed/cross/non-causal layers fall back to the xla flash
    path: their compute is linear in tokens, so they do not create the
    imbalance CAD exists to fix (DESIGN.md §5).  A non-trivial ``mask``
    (:class:`~repro.core.mask.MaskSpec`) — sliding+sink or dilated —
    IS served through the plan path: the servers' kernels take the mask
    as static params and the plan is priced by live blocks (§12).  The
    spec must match the one the plan was built with; ``cad.mask`` (set
    by the session) is used when the call site passes none."""
    cad: Optional[CADContext] = getattr(ctx, "cad", None)
    if cad is not None and mask is not None and cad.mask != mask:
        cad = dataclasses.replace(cad, mask=mask)
    spec = cad.mask if cad is not None else mask
    if cad is None or cad.plan is None or not causal or window:
        w, sink, rate = mask_params(spec, window)
        return xla_flash_attention(q, k, v, seg_q, pos_q, seg_kv, pos_kv,
                                   causal=causal, window=w, sink=sink,
                                   rate=rate, blk=(cad.cfg.blk if cad
                                                   else 128),
                                   softcap=softcap, scale=scale)
    # padding tokens -> position -1 so the server kernels mask them
    pos = jnp.where(seg_q > 0, pos_q, -1)

    def run(qq, kk, vv, pp, plan):
        if ctx.mesh is None:
            return _global_sim(qq, kk, vv, pp, plan, cad, softcap, scale)
        rules = ctx.rules
        bspec = rules.batch
        # TP-shard the head dim inside the dispatch whenever it divides
        # the model axis (self_attn_apply pads/MHA-izes beforehand, so
        # this usually holds); otherwise heads replicate across TP ranks.
        msize = 1
        if ctx.mesh is not None and "model" in ctx.mesh.axis_names:
            msize = dict(zip(ctx.mesh.axis_names,
                             ctx.mesh.devices.shape))["model"]
        hspec = "model" if (msize > 1 and qq.shape[2] % msize == 0) \
            else rules.heads
        if hspec == "model" and kk.shape[2] != qq.shape[2] \
                and kk.shape[2] % msize != 0:
            # per-shard GQA breaks when q heads are TP-sharded but kv
            # heads don't divide the axis: MHA-ize kv so both shard
            # (comm cost noted in DESIGN.md §4)
            from repro.core.attention import _repeat_kv
            rep = qq.shape[2] // kk.shape[2]
            kk = _repeat_kv(kk, rep)
            vv = _repeat_kv(vv, rep)
        khspec = "model" if (msize > 1 and kk.shape[2] % msize == 0) \
            else rules.kv_heads
        axis_names = rules.cad_axis
        in_specs = (P(bspec, None, hspec, None),
                    P(bspec, None, khspec, None),
                    P(bspec, None, khspec, None),
                    P(bspec, None),
                    jax.tree.map(lambda _: P(bspec), plan))
        fn = functools.partial(_rank_fn, cad=cad, softcap=softcap,
                               scale=scale, axis_names=axis_names)

        def body(qq_, kk_, vv_, pp_, plan_):
            plan_ = jax.tree.map(lambda a: a[0], plan_)  # drop local D=1
            return fn(qq_, kk_, vv_, pp_, plan_)

        return jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=in_specs,
            out_specs=P(bspec, None, hspec, None),
            check_vma=False,
        )(qq, kk, vv, pp, plan)

    if cad.pingpong and isinstance(cad.plan, (tuple, list, PingPongPlan)):
        # nano-batch interleave: issue both dispatches; XLA overlaps the
        # A2A of one with the serve of the other (paper Fig. 7).  The
        # split is within each rank's rows (rank-major batch layout).
        d = cad.cfg.n_servers
        b = q.shape[0]
        rpr = b // d
        h = rpr // 2

        def nano(x, i):
            xs = x.reshape((d, rpr) + x.shape[1:])
            sel = xs[:, :h] if i == 0 else xs[:, h:]
            return sel.reshape((d * h,) + x.shape[1:])

        out0 = run(nano(q, 0), nano(k, 0), nano(v, 0), nano(pos, 0),
                   cad.plan[0])
        out1 = run(nano(q, 1), nano(k, 1), nano(v, 1), nano(pos, 1),
                   cad.plan[1])
        o = jnp.stack([out0.reshape((d, h) + q.shape[1:]),
                       out1.reshape((d, h) + q.shape[1:])], axis=1)
        return o.reshape(q.shape)
    plan = cad.plan[0] if isinstance(cad.plan, (tuple, list, PingPongPlan)) \
        else cad.plan
    return run(q, k, v, pos, plan)
