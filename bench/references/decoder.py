"""Plain float32 reference of a dense decoder: RMSNorm, RoPE, grouped-
query attention, SwiGLU, tied or untied output head, next-token loss
within documents, and AdamW.

It reads the configuration file (Hugging Face key names) and imports
nothing of the program.  Attention is taken straight from segment ids
and positions: query i sees key j iff both lie in the same document
(segment > 0) and position(j) <= position(i).  Weights are drawn from
the run's seed by ``init``; ``precision`` rounds every matrix-multiply
operand to a lower type to make the control (see ``compare``).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 256


# ------------------------------------------------------------------ init
def round_to(x, dtype: str):
    """float32 ``x`` rounded to the values of ``dtype``.  A pair of
    converts would do the same, but XLA may drop such a pair as excess
    precision (it does on the TPU); ``reduce_precision`` it keeps."""
    e, m = {"bfloat16": (8, 7), "float32": (8, 23)}[dtype]
    return jax.lax.reduce_precision(x, exponent_bits=e, mantissa_bits=m)


def shapes(c: Dict) -> Dict[str, tuple]:
    d, dh, f, v, n = (c["hidden_size"], c["head_dim"],
                      c["intermediate_size"], c["vocab_size"],
                      c["num_hidden_layers"])
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    s = {"embed": (v, d), "final_norm": (d,),
         "norm1": (n, d), "wq": (n, d, hq * dh), "wk": (n, d, hkv * dh),
         "wv": (n, d, hkv * dh), "wo": (n, hq * dh, d), "norm2": (n, d),
         "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)}
    if not c["tie_word_embeddings"]:
        s["unembed"] = (v, d)
    return s


def fan_in(name: str, shape: tuple) -> Optional[int]:
    """Rows a weight multiplies through (None for a norm scale)."""
    if name in ("final_norm", "norm1", "norm2"):
        return None
    if name in ("embed", "unembed"):
        return shape[-1]
    return shape[-2]


def init(c: Dict, key) -> Dict[str, jnp.ndarray]:
    """float32 weights holding bfloat16 values: normal(0, fan_in^-0.5)
    per tensor from ``fold_in(key, index)``, norm scales 1."""
    out = {}
    for i, (name, shp) in enumerate(sorted(shapes(c).items())):
        fi = fan_in(name, shp)
        if fi is None:
            out[name] = jnp.ones(shp, jnp.float32)
        else:
            w = jax.random.normal(jax.random.fold_in(key, i), shp,
                                  jnp.float32) * (fi ** -0.5)
            out[name] = round_to(w, "bfloat16")
    return out


# --------------------------------------------------------------- forward
def _mm(rnd: Callable, a, b, spec: str):
    return jnp.einsum(spec, rnd(a), rnd(b), precision=HIGHEST)


def _rms(x, scale, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale


def _rope(x, pos, theta):
    """x [B,S,H,dh]: rotate the two halves of each head by
    pos * theta^(-2i/dh)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(rnd, q, k, v, seg, pos):
    """q [B,S,Hq,dh], k/v [B,S,Hkv,dh] -> [B,S,Hq,dh]; queries in chunks
    of Q_CHUNK so that the scores of one chunk are all that live."""
    b, s, hq, dh = q.shape
    rep = hq // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    n_chunk = s // Q_CHUNK if s % Q_CHUNK == 0 else 1
    cq = s // n_chunk

    @jax.checkpoint
    def chunk(args):
        qc, sc, pc = args                       # [B,cq,H,dh], [B,cq]
        logits = _mm(rnd, qc, k, "bqhd,bkhd->bhqk") * (dh ** -0.5)
        ok = (sc[:, None, :, None] == seg[:, None, None, :]) \
            & (sc[:, None, :, None] > 0) \
            & (pos[:, None, None, :] <= pc[:, None, :, None])
        logits = jnp.where(ok, logits, -jnp.inf)
        m = jnp.max(logits, axis=-1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(ok, jnp.exp(logits - m), 0.0)
        den = jnp.sum(p, axis=-1, keepdims=True)
        p = p / jnp.where(den > 0, den, 1.0)
        return _mm(rnd, p, v, "bhqk,bkhd->bqhd")

    qs = q.reshape(b, n_chunk, cq, hq, dh).swapaxes(0, 1)
    ss = seg.reshape(b, n_chunk, cq).swapaxes(0, 1)
    ps = pos.reshape(b, n_chunk, cq).swapaxes(0, 1)
    out = jax.lax.map(chunk, (qs, ss, ps))
    return out.swapaxes(0, 1).reshape(b, s, hq, dh)


def loss(c: Dict, w: Dict, batch: Dict, rnd: Callable = lambda x: x,
         alter: Optional[Callable] = None):
    """Mean next-token cross-entropy over label-bearing tokens.  ``alter``
    (a fault to plant, or None) rewrites each layer's attention output."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    hq, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    seg, pos = batch["segment_ids"], batch["positions"]
    b, s = seg.shape
    h = w["embed"][batch["tokens"]]
    layers = {k: w[k] for k in ("norm1", "wq", "wk", "wv", "wo", "norm2",
                                "w_gate", "w_up", "w_down")}

    @jax.checkpoint
    def layer(h, p):
        x = _rms(h, p["norm1"], eps)
        q = _rope(_mm(rnd, x, p["wq"], "bsd,de->bse").reshape(b, s, hq, dh),
                  pos, theta)
        k = _rope(_mm(rnd, x, p["wk"], "bsd,de->bse").reshape(b, s, hkv, dh),
                  pos, theta)
        v = _mm(rnd, x, p["wv"], "bsd,de->bse").reshape(b, s, hkv, dh)
        a = _attention(rnd, q, k, v, seg, pos)
        if alter is not None:
            a = alter(a)
        a = a.reshape(b, s, hq * dh)
        h = h + _mm(rnd, a, p["wo"], "bse,ed->bsd")

        @jax.checkpoint
        def mlp(hr):                      # one row's MLP at a time
            x = _rms(hr, p["norm2"], eps)
            g = _mm(rnd, x, p["w_gate"], "sd,df->sf")
            u = _mm(rnd, x, p["w_up"], "sd,df->sf")
            return hr + _mm(rnd, jax.nn.silu(g) * u, p["w_down"],
                            "sf,fd->sd")
        return jax.lax.map(mlp, h), None

    h, _ = jax.lax.scan(layer, h, layers)
    h = _rms(h, w["final_norm"], eps)
    head = w["embed"] if c["tie_word_embeddings"] else w["unembed"]
    lab = batch["labels"]
    valid = (lab >= 0) & (seg > 0)

    @jax.checkpoint
    def row_nll(args):                   # one row's logits at a time
        hr, lr, vr = args
        logits = _mm(rnd, hr, head, "sd,vd->sv")
        gold = jnp.take_along_axis(logits, jnp.where(vr, lr, 0)[:, None],
                                   axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * vr)

    nll = jax.lax.map(row_nll, (h, lab, valid))
    return nll.sum() / jnp.maximum(valid.sum(), 1)


# ------------------------------------------------------------- optimizer
DECAYED = ("embed", "unembed", "norm1", "wq", "wk", "wv", "wo", "norm2",
           "w_gate", "w_up", "w_down")


def lr_at(t: Dict, step):
    """Linear warm-up to peak, then cosine to a tenth of it over
    ``total_steps``; ``step`` counts updates from 1."""
    step = jnp.asarray(step, jnp.float32)
    warm = t["peak_lr"] * step / max(t["warmup"], 1)
    prog = jnp.clip((step - t["warmup"]) / max(t["total_steps"]
                                               - t["warmup"], 1), 0.0, 1.0)
    cos = t["peak_lr"] * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(step < t["warmup"], warm, cos)


def adamw_step(t: Dict, w, m, v, g, step: int):
    """One AdamW update; gradients clipped by their global norm first.
    Decay applies to every tensor but the final norm's scale (the
    configuration's stated rule).  Returns (w, m, v, clipped grads)."""
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, t["grad_clip"] / (gn + 1e-9))
    lr = lr_at(t, step)
    nw, nm, nv, gc = {}, {}, {}, {}
    for k in w:
        gk = g[k] * scale
        nm[k] = t["b1"] * m[k] + (1 - t["b1"]) * gk
        nv[k] = t["b2"] * v[k] + (1 - t["b2"]) * gk * gk
        mh = nm[k] / (1 - t["b1"] ** step)
        vh = nv[k] / (1 - t["b2"] ** step)
        delta = mh / (jnp.sqrt(vh) + t["eps"])
        if k in DECAYED:
            delta = delta + t["weight_decay"] * w[k]
        nw[k] = w[k] - lr * delta
        gc[k] = gk
    return nw, nm, nv, gc


def make_step(c: Dict, rnd: Callable = lambda x: x):
    """A jitted (w, m, v, batch, step) -> (w, m, v, loss, clipped grads)
    training step of the reference."""
    t = c["train"]

    @functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 1, 2))
    def step(w, m, v, batch, n):
        lval, g = jax.value_and_grad(lambda ww: loss(c, ww, batch, rnd))(w)
        w, m, v, gc = adamw_step(t, w, m, v, g, n)
        return w, m, v, lval, gc

    return step
