"""Layer implementations: norms, RoPE, GQA attention, (Mo)MLP, Mamba-2
SSD, RG-LRU, cross-attention.  Pure-functional: ``*_init`` builds a param
dict, ``*_apply`` consumes it.

Weight names follow the conventions consumed by
``repro.parallel.param_pspecs`` (wq/wk/wv/wo, w_gate/w_up/w_down,
experts_*, ...), so sharding specs are derived from the tree structure.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.attention import (_repeat_kv, core_attention,
                                  decode_attention)


# ----------------------------------------------------------------- helpers
def dense_init(key, fan_in, shape, dtype):
    return (jax.random.normal(key, shape) * (fan_in ** -0.5)).astype(dtype)


def norm_init(d, dtype, kind="rmsnorm"):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(p, x, kind="rmsnorm", eps=1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = out * p["scale"].astype(jnp.float32)
        if "bias" in p:
            out = out + p["bias"].astype(jnp.float32)
    else:
        ms = (xf ** 2).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def rope(x, positions, theta: float):
    """x [B,S,H,dh], positions [B,S] (within-document for packed data)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def sinusoidal_pos(positions, d, dtype):
    half = d // 2
    freqs = 10000.0 ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


def activation_fn(name):
    return {"silu": jax.nn.silu, "gelu": functools.partial(jax.nn.gelu, approximate=True),
            "relu2": lambda x: jnp.square(jax.nn.relu(x))}[name]


# --------------------------------------------------------------- attention
def attn_init(key, cfg, cross=False):
    d, dh = cfg.d_model, cfg.head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], d, (d, hq * dh), cfg.pdtype),
        "wk": dense_init(ks[1], d, (d, hkv * dh), cfg.pdtype),
        "wv": dense_init(ks[2], d, (d, hkv * dh), cfg.pdtype),
        "wo": dense_init(ks[3], hq * dh, (hq * dh, d), cfg.pdtype),
    }
    if cross:
        p["xwq"] = dense_init(ks[4], d, (d, hq * dh), cfg.pdtype)
        p["xwk"] = dense_init(ks[5], d, (d, hkv * dh), cfg.pdtype)
        p["xwv"] = dense_init(ks[6], d, (d, hkv * dh), cfg.pdtype)
        p["xwo"] = dense_init(ks[7], hq * dh, (hq * dh, d), cfg.pdtype)
        p["xgate"] = jnp.zeros((), cfg.pdtype)  # llama3.2-vision tanh gate
    return p


def qkv_proj(p, h, cfg, positions, prefix="w"):
    b, s, _ = h.shape
    dh = cfg.head_dim
    q = (h @ p[prefix + "q"]).reshape(b, s, cfg.n_heads, dh)
    k = (h @ p[prefix + "k"]).reshape(b, s, cfg.n_kv_heads, dh)
    v = (h @ p[prefix + "v"]).reshape(b, s, cfg.n_kv_heads, dh)
    if cfg.use_rope and positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _model_axis_size(ctx):
    mesh = getattr(ctx, "mesh", None)
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape))["model"]


def _pad_heads_for_tp(q, k, v, ctx):
    """When n_heads does not divide the model axis, core attention would be
    fully REPLICATED across TP ranks (a model-axis-x flops blowup).
    Instead: MHA-ize (repeat kv to q heads) and zero-pad heads up to the
    next model-axis multiple so CA stays TP-sharded (DESIGN.md §4).
    Returns (q, k, v, orig_heads, padded?)."""
    hq = q.shape[2]
    m = _model_axis_size(ctx)
    if m <= 1 or ctx.rules.heads is not None or hq % m == 0:
        return q, k, v, hq, False
    hkv = k.shape[2]
    if hkv != hq:
        k = _repeat_kv(k, hq // hkv)
        v = _repeat_kv(v, hq // hkv)
    target = ((hq + m - 1) // m) * m
    padw = [(0, 0), (0, 0), (0, target - hq), (0, 0)]
    return (jnp.pad(q, padw), jnp.pad(k, padw), jnp.pad(v, padw), hq, True)


def self_attn_apply(p, h, batch, cfg, ctx, *, causal=True, window=0):
    """h [B,S,D]; batch provides segment_ids/positions."""
    b, s, _ = h.shape
    seg, pos = batch["segment_ids"], batch["positions"]
    q, k, v = qkv_proj(p, h, cfg, pos if cfg.use_rope else None)
    q, k, v, hq_orig, padded = _pad_heads_for_tp(q, k, v, ctx)
    hspec = "heads" if not padded else "padded_heads"
    kspec = "kv_heads" if not padded else "padded_heads"
    q = ctx.cons(q, "batch", "seq", hspec, None)
    k = ctx.cons(k, "batch", "seq", kspec, None)
    v = ctx.cons(v, "batch", "seq", kspec, None)
    out = core_attention(q, k, v, seg, pos, seg, pos, causal=causal,
                         window=window, softcap=cfg.attn_logit_softcap,
                         ctx=ctx)
    # pin the CA output to the head sharding: without this GSPMD shards
    # the flash-scan accumulators on the sequence-block dim (to match the
    # residual's seq sharding) and every per-pair dynamic-slice becomes a
    # full all-gather (§Perf P7)
    out = ctx.cons(out, "batch", None, hspec, None)
    if padded:
        out = out[:, :, :hq_orig, :]
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return ctx.cons(out @ p["wo"], "batch", "residual_seq", None)


def cross_attn_apply(p, h, batch, cfg, ctx):
    """Cross-attention over encoder/vision memory [B,M,D]."""
    b, s, _ = h.shape
    dh = cfg.head_dim
    mem = batch["memory"]
    mem_mask = batch.get("memory_mask")
    q = (h @ p["xwq"]).reshape(b, s, cfg.n_heads, dh)
    k = (mem @ p["xwk"]).reshape(b, mem.shape[1], cfg.n_kv_heads, dh)
    v = (mem @ p["xwv"]).reshape(b, mem.shape[1], cfg.n_kv_heads, dh)
    seg_q = batch["segment_ids"]
    pos_q = batch["positions"]
    m = mem.shape[1]
    seg_kv = (jnp.ones((b, m), jnp.int32) if mem_mask is None
              else mem_mask.astype(jnp.int32))
    # cross attention: every query token may see every (valid) memory token
    # regardless of document id -> give kv the query's segment by using a
    # broadcast trick: all query segs attend seg 1; queries with seg 0 are
    # padding and masked by their own seg.
    seg_q_x = (seg_q > 0).astype(jnp.int32)
    pos_kv = jnp.zeros((b, m), jnp.int32)
    out = core_attention(q, k, v, seg_q_x, pos_q, seg_kv, pos_kv,
                         causal=False, window=0, softcap=0.0, ctx=ctx)
    out = out.reshape(b, s, cfg.n_heads * dh) @ p["xwo"]
    if "xgate" in p:
        out = jnp.tanh(p["xgate"].astype(jnp.float32)).astype(out.dtype) * out
    return ctx.cons(out, "batch", "residual_seq", None)


# --------------------------------------------------------------------- ffn
def ffn_init(key, cfg):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.gated_mlp:
        return {"w_gate": dense_init(ks[0], d, (d, f), cfg.pdtype),
                "w_up": dense_init(ks[1], d, (d, f), cfg.pdtype),
                "w_down": dense_init(ks[2], f, (f, d), cfg.pdtype)}
    return {"w_up": dense_init(ks[0], d, (d, f), cfg.pdtype),
            "w_down": dense_init(ks[1], f, (f, d), cfg.pdtype)}


def ffn_apply(p, h, cfg, ctx):
    act = activation_fn(cfg.activation)
    if "w_gate" in p:
        inner = act(h @ p["w_gate"]) * (h @ p["w_up"])
    else:
        inner = act(h @ p["w_up"])
    inner = ctx.cons(inner, "batch", "seq", "ffn")
    return ctx.cons(inner @ p["w_down"], "batch", "residual_seq", None)


# --------------------------------------------------------------------- moe
def moe_init(key, cfg):
    e = cfg.moe
    d, f = cfg.d_model, e.d_ff_expert
    ks = jax.random.split(key, 8)
    p = {"router": dense_init(ks[0], d, (d, e.n_experts), cfg.pdtype),
         "experts_gate": dense_init(ks[1], d, (e.n_experts, d, f), cfg.pdtype),
         "experts_up": dense_init(ks[2], d, (e.n_experts, d, f), cfg.pdtype),
         "experts_down": dense_init(ks[3], f, (e.n_experts, f, d), cfg.pdtype)}
    if e.n_shared_experts:
        fs = f * e.n_shared_experts
        p["w_gate"] = dense_init(ks[4], d, (d, fs), cfg.pdtype)
        p["w_up"] = dense_init(ks[5], d, (d, fs), cfg.pdtype)
        p["w_down"] = dense_init(ks[6], fs, (fs, d), cfg.pdtype)
    return p


def moe_apply(p, h, cfg, ctx, no_drop=False):
    """Capacity-based MoE with sort-based gather/scatter dispatch.

    Tokens pick top-k experts; each expert processes at most C tokens
    (C = tokens*top_k/E * capacity_factor).  Dispatch/return are gathers
    and scatter-adds (no one-hot einsums: a dense [T,E,C] dispatch tensor
    costs T·E·C·d matmul flops, which dwarfs the expert compute at
    E=128).  With ``expert_parallel`` the expert dim is sharded over
    "data" and GSPMD lowers the gather/scatter into all-to-alls;
    otherwise experts are replicated and dispatch is local.
    Returns (out, aux_losses).
    """
    e = cfg.moe
    b, s, d = h.shape
    act = activation_fn(cfg.activation)
    n_tok = b * s
    x = h.reshape(n_tok, d)

    logits = (x @ p["router"]).astype(jnp.float32)            # [T,E]
    probs = jax.nn.softmax(logits, -1)
    gate_vals, idx = jax.lax.top_k(probs, e.top_k)            # [T,k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    def route_compute(x_g, idx_g, gate_g, cap):
        """Route one token group through the (closed-over) experts.
        x_g [Tg, d]; idx_g/gate_g [Tg, k].  Returns out [Tg, d]."""
        tg = x_g.shape[0]
        tk = tg * e.top_k
        flat_e = idx_g.reshape(tk)
        order = jnp.argsort(flat_e, stable=True)              # [Tk]
        sorted_e = flat_e[order]
        grp_start = jnp.searchsorted(sorted_e, jnp.arange(e.n_experts),
                                     side="left")             # [E]
        rank_sorted = jnp.arange(tk) - grp_start[sorted_e]
        pos = jnp.zeros(tk, jnp.int32).at[order].set(
            rank_sorted.astype(jnp.int32))                    # [Tk]
        in_cap = pos < cap
        slot = flat_e * cap + pos                             # [Tk]
        token_id = jnp.repeat(jnp.arange(tg), e.top_k)
        safe_slot = jnp.where(in_cap, slot, e.n_experts * cap)
        token_of_slot = jnp.full(e.n_experts * cap + 1, -1, jnp.int32) \
            .at[safe_slot].set(token_id.astype(jnp.int32))[:-1]
        gate_of_slot = jnp.zeros(e.n_experts * cap + 1, h.dtype) \
            .at[safe_slot].set(gate_g.reshape(tk).astype(h.dtype))[:-1]

        live = (token_of_slot >= 0)
        xs = x_g[jnp.maximum(token_of_slot, 0)] \
            * live[:, None].astype(h.dtype)                   # [E*C, D]
        xs = xs.reshape(e.n_experts, cap, d)
        if e.expert_parallel:
            xs = ctx.cons(xs, "experts", None, None)
        gate = act(jnp.einsum("ecd,edf->ecf", xs, p["experts_gate"]))
        up = jnp.einsum("ecd,edf->ecf", xs, p["experts_up"])
        inner = gate * up
        if e.expert_parallel:
            inner = ctx.cons(inner, "experts", None, "ffn")
        ys = jnp.einsum("ecf,efd->ecd", inner, p["experts_down"])
        ys = (ys.reshape(e.n_experts * cap, d)
              * gate_of_slot[:, None].astype(ys.dtype))
        return jnp.zeros((tg, d), ys.dtype) \
            .at[jnp.maximum(token_of_slot, 0)] \
            .add(ys * live[:, None].astype(ys.dtype))

    # number of data shards (for group-local routing)
    n_groups = 1
    if not e.expert_parallel and ctx.mesh is not None \
            and ctx.rules.batch is not None:
        sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
        baxes = ctx.rules.batch
        baxes = baxes if isinstance(baxes, tuple) else (baxes,)
        for a in baxes:
            n_groups *= sizes.get(a, 1)
        if n_tok % n_groups:
            n_groups = 1

    if no_drop:
        cap = n_tok // n_groups  # decode: never drop a live request
    else:
        cap = max(1, int(n_tok / n_groups * e.top_k / e.n_experts
                         * e.capacity_factor))

    if n_groups > 1:
        # Replicated-expert path: route each data shard's tokens locally
        # (a data-dependent GLOBAL gather would force GSPMD to replicate
        # the whole expert computation — §Perf P8).  The leading group
        # dim is sharded over the data axes; everything stays shard-local.
        tg = n_tok // n_groups
        xg = ctx.cons(x.reshape(n_groups, tg, d), "batch", None, None)
        idxg = ctx.cons(idx.reshape(n_groups, tg, e.top_k),
                        "batch", None, None)
        gateg = ctx.cons(gate_vals.reshape(n_groups, tg, e.top_k),
                         "batch", None, None)
        out = jax.vmap(lambda a, b, c: route_compute(a, b, c, cap))(
            xg, idxg, gateg).reshape(n_tok, d)
    else:
        out = route_compute(x, idx, gate_vals, cap)

    if e.n_shared_experts and "w_gate" in p:
        out = out + ((act(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"])

    # aux losses (Switch-style load balance + router z-loss)
    me = probs.mean(0)                                         # [E]
    ce = jax.nn.one_hot(idx[:, 0], e.n_experts).mean(0)
    lb = e.n_experts * jnp.sum(me * ce) * e.load_balance_loss
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2) * e.router_z_loss
    return out.reshape(b, s, d).astype(h.dtype), \
        {"moe_lb": lb, "moe_z": z}


# --------------------------------------------------------------- mamba2 SSD
def ssd_init(key, cfg):
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    nh = d_in // s.head_dim
    conv_ch = d_in + 2 * s.n_groups * s.d_state
    ks = jax.random.split(key, 4)
    return {
        # fused input projection -> [z (d_in), x (d_in), B, C (G*N each), dt (nh)]
        "in_proj": dense_init(ks[0], d,
                              (d, 2 * d_in + 2 * s.n_groups * s.d_state + nh),
                              cfg.pdtype),
        "conv_w": dense_init(ks[1], s.conv_width,
                             (s.conv_width, conv_ch), cfg.pdtype),
        "conv_b": jnp.zeros((conv_ch,), cfg.pdtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(cfg.pdtype),
        "D_skip": jnp.ones((nh,), cfg.pdtype),
        "dt_bias": jnp.zeros((nh,), cfg.pdtype),
        "out_norm": norm_init(d_in, cfg.pdtype),
        "out_proj": dense_init(ks[2], d_in, (d_in, d), cfg.pdtype),
    }


def _causal_conv(x, w, b, state=None, first=None):
    """x [B,S,C]; w [W,C] depthwise causal conv.  Returns (y, new_state)
    where state is the last W-1 inputs (for decode).  ``first`` [B,S] marks
    document starts: taps reaching across a boundary are zeroed so packed
    documents do not leak into each other."""
    width = w.shape[0]
    s = x.shape[1]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state, x], axis=1)
    if first is not None:
        nr = jnp.cumsum(first.astype(jnp.int32), axis=1)       # [B,S]
        nrp = jnp.pad(nr, ((0, 0), (width - 1, 0)), constant_values=-1)
        terms = [xp[:, i:i + s, :] * w[i]
                 * (nrp[:, i:i + s] == nr)[..., None].astype(x.dtype)
                 for i in range(width)]
        ys = sum(terms)
    else:
        ys = sum(xp[:, i:i + s, :] * w[i] for i in range(width))
    new_state = xp[:, -(width - 1):, :] if width > 1 else None
    return jax.nn.silu(ys + b), new_state


def _ssd_split(p, h, cfg):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    gn = s.n_groups * s.d_state
    proj = h @ p["in_proj"]
    z = proj[..., :d_in]
    xbc = proj[..., d_in:d_in + d_in + 2 * gn]
    dt = proj[..., -nh:]
    return z, xbc, dt, d_in, nh, gn


def ssd_apply(p, h, batch, cfg, ctx):
    """Mamba-2 SSD block (chunked scan), packed-document aware: the decay
    is zeroed at document starts so state never crosses documents."""
    s = cfg.ssm
    b, S, _ = h.shape
    seg0 = batch["segment_ids"]
    first0 = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg0[:, 1:] != seg0[:, :-1]], axis=1)
    z, xbc, dt, d_in, nh, gn = _ssd_split(p, h, cfg)
    xbc, _ = _causal_conv(xbc, p["conv_w"], p["conv_b"], first=first0)
    x = xbc[..., :d_in].reshape(b, S, nh, s.head_dim)
    B_ = xbc[..., d_in:d_in + gn].reshape(b, S, s.n_groups, s.d_state)
    C_ = xbc[..., d_in + gn:].reshape(b, S, s.n_groups, s.d_state)

    A = -jnp.exp(p["A_log"].astype(jnp.float32))               # [nh] < 0
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))    # [B,S,nh]
    seg = batch["segment_ids"]
    first = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    log_a = dt * A                                              # [B,S,nh] <=0

    y = _ssd_chunked(x, dt, log_a, B_, C_, s.chunk_size, first, ctx=ctx)
    y = y + x * p["D_skip"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(b, S, d_in)
    y = norm_apply(p["out_norm"], y * jax.nn.silu(z))
    return ctx.cons(y @ p["out_proj"], "batch", "residual_seq", None)


def _ssd_chunked(x, dt, log_a, B_, C_, chunk, first=None, ctx=None):
    """Chunked SSD: y_t = C_t^T ( sum_{j<=t} prod_{i in (j,t]} a_i *
    dt_j B_j x_j^T ).  x [B,S,H,P]; B_/C_ [B,S,G,N]; log_a/dt [B,S,H];
    first [B,S] bool marks document starts (state resets).  Returns
    y [B,S,H,P].

    Document resets are NOT folded into log_a as -inf (the cumsum-difference
    trick would suffer catastrophic cancellation); instead the reset-count
    prefix sum gates which (j -> i) contributions are allowed."""
    b, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    rep = H // G
    chunk = min(chunk, S)
    assert S % chunk == 0, "seq must divide ssd chunk"
    nc = S // chunk
    if first is None:
        first = jnp.zeros((b, S), bool).at[:, 0].set(True)

    def r(t):  # [B,S,...] -> [B,nc,chunk,...]
        return t.reshape((b, nc, chunk) + t.shape[2:])

    xc, dtc, lac = r(x), r(dt), r(log_a)
    Bc = jnp.repeat(r(B_), rep, axis=3)          # [B,K,c,H,N]
    Cc = jnp.repeat(r(C_), rep, axis=3)
    # a_t at a reset position never multiplies anything that survives the
    # reset-count gates below, so zero its log contribution.
    lac = jnp.where(r(first)[..., None], 0.0, lac)
    nr = jnp.cumsum(r(first).astype(jnp.int32), axis=2)   # resets up to t

    csum = jnp.cumsum(lac, axis=2)               # [B,K,c,H]
    if getattr(ctx, "attn_impl", "") == "pallas":
        # Pallas intra-chunk kernel (kernels/ssd): MXU-tiled scores +
        # decay mask + end-state, one (batch, chunk, head) tile per step
        from repro.kernels.ssd.kernel import ssd_chunk
        y_intra, states = ssd_chunk(
            Cc.astype(jnp.float32), Bc.astype(jnp.float32),
            xc.astype(jnp.float32), dtc, csum, nr.astype(jnp.int32),
            interpret=jax.default_backend() != "tpu")
        y_intra = y_intra.astype(jnp.float32)
    else:
        # intra-chunk: contribution of input j to output i (j<=i) decays
        # by prod_{t in (j, i]} a_t = exp(csum_i - csum_j); weight dt_j;
        # allowed only when no reset occurred in (j, i] <=> nr_i == nr_j.
        li = csum[:, :, :, None, :]                  # i
        lj = csum[:, :, None, :, :]                  # j
        dec = jnp.exp(jnp.clip(li - lj, -80.0, 0.0))  # [B,K,i,j,H]
        iota = jnp.arange(chunk)
        tri = (iota[:, None] >= iota[None, :])[None, None, :, :, None]
        same_doc = (nr[:, :, :, None] == nr[:, :, None, :])[..., None]
        dec = jnp.where(tri & same_doc, dec, 0.0)
        scores = jnp.einsum("bkihn,bkjhn->bkijh", Cc.astype(jnp.float32),
                            Bc.astype(jnp.float32))
        w = scores * dec * dtc[:, :, None, :, :]
        y_intra = jnp.einsum("bkijh,bkjhp->bkihp", w,
                             xc.astype(jnp.float32))

        # chunk-final states: sum_j exp(csum_end - csum_j) dt_j B_j x_j^T
        # over inputs j with no reset after them (nr_j == nr_last).
        live_end = (nr == nr[:, :, -1:])[..., None]           # [B,K,c,1]
        dec_end = jnp.exp(jnp.clip(csum[:, :, -1:, :] - csum, -80.0, 0.0))
        dec_end = jnp.where(live_end, dec_end, 0.0)
        sB = Bc.astype(jnp.float32) * (dec_end * dtc)[..., None]
        states = jnp.einsum("bkjhn,bkjhp->bkhnp", sB,
                            xc.astype(jnp.float32))
    # carried decay is zero if the chunk contains any reset
    no_reset = (nr[:, :, -1] == 0)[..., None]             # [B,K,1]
    chunk_decay = jnp.exp(jnp.clip(csum[:, :, -1, :], -80.0, 0.0)) \
        * no_reset.astype(jnp.float32)                    # [B,K,H]

    def scan_fn(h_prev, inp):
        st, cd = inp                              # [B,H,N,P], [B,H]
        h_new = h_prev * cd[..., None, None] + st
        return h_new, h_prev

    h0 = jnp.zeros((b, H, N, P), jnp.float32)
    _, h_before = jax.lax.scan(
        scan_fn, h0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    h_before = h_before.transpose(1, 0, 2, 3, 4)  # [B,K,H,N,P] entering state
    # inter-chunk: y_i += C_i^T decay(start..i) h_before, gated on no reset
    # having occurred at or before i within this chunk.
    dec_in = jnp.exp(jnp.clip(csum, -80.0, 0.0)) \
        * (nr == 0).astype(jnp.float32)[..., None]
    y_inter = jnp.einsum("bkihn,bkhnp->bkihp",
                         Cc.astype(jnp.float32) * dec_in[..., None], h_before)
    y = (y_intra + y_inter).reshape(b, S, H, P)
    return y.astype(x.dtype)


# ------------------------------------------------------------------ rg-lru
def rglru_init(key, cfg):
    r = cfg.rglru
    d = cfg.d_model
    w = r.lru_width or d
    ks = jax.random.split(key, 6)
    # a initialised so that a = sigmoid(lru_a)^8 is in ~[0.9, 0.999]
    a_init = jnp.log(jnp.expm1(
        jnp.linspace(0.9, 0.999, w) ** (1 / 8.0)) + 1e-8)
    return {
        "w_x": dense_init(ks[0], d, (d, w), cfg.pdtype),       # recurrence in
        "w_gate_br": dense_init(ks[1], d, (d, w), cfg.pdtype),  # gelu branch
        "conv_w": dense_init(ks[2], r.conv_width, (r.conv_width, w), cfg.pdtype),
        "conv_b": jnp.zeros((w,), cfg.pdtype),
        "w_input_gate": dense_init(ks[3], w, (w, w), cfg.pdtype),
        "w_rec_gate": dense_init(ks[4], w, (w, w), cfg.pdtype),
        "lru_a": a_init.astype(cfg.pdtype),
        "w_out": dense_init(ks[5], w, (w, d), cfg.pdtype),
    }


_LRU_C = 8.0


def rglru_apply(p, h, batch, cfg, ctx):
    """Griffin RG-LRU temporal-mixing block with doc-boundary resets."""
    b, S, _ = h.shape
    seg = batch["segment_ids"]
    first = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    gate_br = jax.nn.gelu(h @ p["w_gate_br"])
    x = h @ p["w_x"]
    x, _ = _causal_conv(x, p["conv_w"], p["conv_b"], first=first)
    y = _rglru_scan(p, x, first, ctx=ctx)
    y = y * gate_br
    return ctx.cons(y @ p["w_out"], "batch", "residual_seq", None)


def _rglru_gates(p, x):
    rg = jax.nn.sigmoid(x @ p["w_rec_gate"]).astype(jnp.float32)
    ig = jax.nn.sigmoid(x @ p["w_input_gate"]).astype(jnp.float32)
    log_a0 = jax.nn.log_sigmoid(p["lru_a"].astype(jnp.float32))
    log_a = _LRU_C * rg * log_a0                       # [B,S,W] (<= 0)
    return log_a, ig


def _rglru_scan(p, x, first, ctx=None):
    """h_t = a_t h_{t-1} + sqrt(1-a_t^2) (i_t x_t), a_t=0 at doc starts.
    Parallelized with associative_scan; with attn_impl="pallas" the
    recurrence runs in the Pallas block-scan kernel (kernels/rglru)."""
    log_a, ig = _rglru_gates(p, x)
    log_a = jnp.where(first[..., None], -1e30, log_a)
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 0.0, 1.0))
    bterm = beta * ig * x.astype(jnp.float32)

    w = x.shape[-1]
    s = x.shape[1]
    if getattr(ctx, "attn_impl", "") == "pallas" and w % 128 == 0 \
            and s % 128 == 0:
        from repro.kernels.rglru.ops import lru_scan
        return lru_scan(a.astype(jnp.float32), bterm).astype(x.dtype)

    def combine(l, r_):
        al, bl = l
        ar, br = r_
        return al * ar, bl * ar + br

    _, h = jax.lax.associative_scan(combine, (a, bterm), axis=1)
    return h.astype(x.dtype)


def rglru_decode(p, x_t, h_prev, reset=None):
    """Single-step RG-LRU update: x_t [B,1,W] (post-conv), h_prev [B,W];
    reset [B] bool zeroes the decay (document start), matching the packed
    forward's segment-boundary convention."""
    log_a, ig = _rglru_gates(p, x_t)
    a = jnp.exp(log_a[:, 0])
    if reset is not None:
        a = jnp.where(reset[:, None], 0.0, a)
    beta = jnp.sqrt(jnp.clip(1.0 - a * a, 0.0, 1.0))
    h_new = a * h_prev + beta * ig[:, 0] * x_t[:, 0].astype(jnp.float32)
    return h_new


def ssd_decode(p, h_t, conv_state, ssm_state, cfg):
    """Single-token SSD step.  h_t [B,1,D]; conv_state [B,W-1,C];
    ssm_state [B,H,N,P] (f32).  Returns (out [B,1,D], conv_state, ssm_state)."""
    s = cfg.ssm
    b = h_t.shape[0]
    z, xbc, dt, d_in, nh, gn = _ssd_split(p, h_t, cfg)
    xbc, conv_state = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    x = xbc[:, 0, :d_in].reshape(b, nh, s.head_dim)
    B_ = jnp.repeat(xbc[:, 0, d_in:d_in + gn].reshape(b, s.n_groups, s.d_state),
                    nh // s.n_groups, axis=1)                    # [B,H,N]
    C_ = jnp.repeat(xbc[:, 0, d_in + gn:].reshape(b, s.n_groups, s.d_state),
                    nh // s.n_groups, axis=1)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))    # [B,H]
    a = jnp.exp(dtv * A)                                         # [B,H]
    upd = (dtv[..., None] * B_.astype(jnp.float32))[..., None] \
        * x.astype(jnp.float32)[:, :, None, :]                   # [B,H,N,P]
    ssm_state = ssm_state * a[..., None, None] + upd
    y = jnp.einsum("bhn,bhnp->bhp", C_.astype(jnp.float32), ssm_state)
    y = y + x.astype(jnp.float32) * p["D_skip"].astype(jnp.float32)[None, :, None]
    y = y.reshape(b, 1, d_in).astype(h_t.dtype)
    y = norm_apply(p["out_norm"], y * jax.nn.silu(z))
    return y @ p["out_proj"], conv_state, ssm_state
