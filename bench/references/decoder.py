"""Plain float32 reference of a dense decoder: RMSNorm, RoPE, grouped-
query attention, SwiGLU, tied or untied output head, next-token loss
within documents, and AdamW.

It reads the configuration file (Hugging Face key names) and imports
nothing of the program.  Attention is taken straight from segment ids
and positions: query i sees key j iff both lie in the same document
(segment > 0) and position(j) <= position(i).  Weights are drawn from
the run's seed by ``init``; ``precision`` rounds every matrix-multiply
operand to a lower type to make the control (see ``compare``).

A configuration file names its reference module by ``reference``; the
module is the one place that knows the architecture.  What a reference
module provides, and who reads each item:

  ``program_fields(c)``  the program's ``ModelConfig`` fields the file
                         sets on its ``registry_arch`` entry, as plain
                         values (a dict for a nested config such as
                         ``moe``): ``harness/program.model_config``
  ``program_paths(c)``   reference tensor name -> path in the program's
                         parameter tree (an int is a layer-pattern slot):
                         ``harness/program`` ``to_program``,
                         ``from_program``, ``check_layout``
  ``shapes(c)``, ``init(c, key)``
                         the tensors and their seeded weights: the
                         program's initial parameters and the comparison
  ``loss(c, w, batch, rnd, alter)``, ``lr_at(t, step)``, ``DECAYED``,
  ``round_to(x, dtype)`` the reference's training steps:
                         ``harness/compare.reference_readings``
  ``step_flops(c, batch)``
                         forward and backward FLOPs of one step, no
                         remat: the ``step_mfu`` reader
  ``ca_call_work(c, segment_ids, positions, backward=)``
                         FLOPs and bytes of one CA-server call of one
                         layer: ``readings.Context.kernel_roofline``
                         (``ca_fwd_roofline``, ``ca_bwd_roofline``)
  ``ca_layers(c)``       layers a step sends to the CA servers: the same

``harness/flops.py`` holds counting helpers such modules share.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import flops

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 256


# --------------------------------------------------------------- program
def program_fields(c: Dict) -> Dict:
    """The ``ModelConfig`` fields of a dense GQA SwiGLU decoder."""
    return dict(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"],
        layer_pattern=("global",), activation="silu", gated_mlp=True,
        norm="rmsnorm", post_norms=False, scale_embed=False, qk_norm=False,
        use_rope=True, attn_logit_softcap=0.0, final_logit_softcap=0.0,
        moe=None, ssm=None, rglru=None, encoder=None,
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"])


# where each layer tensor lives in a pattern slot of the program's tree
SLOT_PATHS = {"norm1": ("norm1", "scale"), "wq": ("attn", "wq"),
              "wk": ("attn", "wk"), "wv": ("attn", "wv"),
              "wo": ("attn", "wo"), "norm2": ("norm2", "scale"),
              "w_gate": ("ffn", "w_gate"), "w_up": ("ffn", "w_up"),
              "w_down": ("ffn", "w_down")}
LAYER = tuple(SLOT_PATHS)


def program_paths(c: Dict) -> Dict[str, tuple]:
    """One pattern slot of global layers, stacked on a leading layer
    axis."""
    paths = {"embed": ("embed", "embed"), "unembed": ("unembed", "unembed"),
             "final_norm": ("final_norm", "scale")}
    paths.update({k: ("blocks", 0) + p for k, p in SLOT_PATHS.items()})
    return paths


# ------------------------------------------------------------------ work
def linear_params_per_layer(c: Dict) -> int:
    return flops.gqa_params(c["hidden_size"], c["num_attention_heads"],
                            c["num_key_value_heads"], c["head_dim"]) \
        + 3 * c["hidden_size"] * c["intermediate_size"]


def linear_flops_per_token(c: Dict) -> int:
    """Forward FLOPs of the matrix multiplications of one token: 2 x the
    weights it multiplies through (q/k/v/o, the gated MLP, the output
    head; the embedding lookup is a gather, 0 FLOPs)."""
    head = 2 * c["hidden_size"] * c["vocab_size"]
    return 2 * linear_params_per_layer(c) * c["num_hidden_layers"] + head


def attention_fwd_flops(c: Dict, pairs: int) -> int:
    """Forward FLOPs of core attention over ``pairs`` live pairs, one
    layer."""
    return 4 * pairs * c["num_attention_heads"] * c["head_dim"]


def step_flops(c: Dict, batch: Dict) -> float:
    """FLOPs the forward and backward passes of one step need: backward
    = 2 x forward, work that rematerialisation repeats not counted.
    Every non-padding token goes through the linear layers."""
    seg = np.asarray(batch["segment_ids"])
    tokens = int((seg > 0).sum())
    pairs = flops.live_pairs(seg, batch["positions"])
    fwd = tokens * linear_flops_per_token(c) \
        + c["num_hidden_layers"] * attention_fwd_flops(c, pairs)
    return 3.0 * fwd


def ca_call_work(c: Dict, segment_ids, positions, *,
                 backward: bool) -> Dict[str, float]:
    """Work of one CA-server call of one layer (``flops.gqa_call_work``)."""
    return flops.gqa_call_work(segment_ids, positions,
                               c["num_attention_heads"],
                               c["num_key_value_heads"], c["head_dim"],
                               backward=backward)


def ca_layers(c: Dict) -> int:
    """Every layer is causal full attention: all go to the CA servers."""
    return c["num_hidden_layers"]


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
    """The seed's weights of every tensor of ``shapes(c)``."""
    return draw(shapes(c), key)


def draw(sizes: Dict[str, tuple], key) -> Dict[str, jnp.ndarray]:
    """float32 weights holding bfloat16 values: normal(0, fan_in^-0.5)
    per tensor from ``fold_in(key, index)`` in name order, norm scales 1.
    The last dotted part of a name is its tensor's kind (``local.wq`` is
    a ``wq``)."""
    out = {}
    for i, (name, shp) in enumerate(sorted(sizes.items())):
        fi = fan_in(name.rsplit(".", 1)[-1], shp)
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


def _attention(rnd, q, k, v, seg, pos, window: int = 0):
    """q [B,S,Hq,dh], k/v [B,S,Hkv,dh] -> [B,S,Hq,dh]; queries in chunks
    of Q_CHUNK so that the scores of one chunk are all that live.  With
    a ``window``, query i also needs position(i) - position(j) < window."""
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
        if window:
            ok = ok & (pc[:, None, :, None] - pos[:, None, None, :] < window)
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


def block(c: Dict, p: Dict, h, seg, pos, rnd: Callable,
          alter: Optional[Callable] = None, window: int = 0):
    """One decoder layer on the residual ``h`` [B,S,D]: attention (under
    ``window`` where it is not 0) and the gated MLP, each after its
    RMSNorm.  ``alter`` (a fault to plant, or None) rewrites the
    attention output."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    hq, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    b, s = seg.shape
    x = _rms(h, p["norm1"], eps)
    q = _rope(_mm(rnd, x, p["wq"], "bsd,de->bse").reshape(b, s, hq, dh),
              pos, theta)
    k = _rope(_mm(rnd, x, p["wk"], "bsd,de->bse").reshape(b, s, hkv, dh),
              pos, theta)
    v = _mm(rnd, x, p["wv"], "bsd,de->bse").reshape(b, s, hkv, dh)
    a = _attention(rnd, q, k, v, seg, pos, window)
    if alter is not None:
        a = alter(a)
    a = a.reshape(b, s, hq * dh)
    h = h + _mm(rnd, a, p["wo"], "bse,ed->bsd")

    @jax.checkpoint
    def mlp(hr):                          # one row's MLP at a time
        x = _rms(hr, p["norm2"], eps)
        g = _mm(rnd, x, p["w_gate"], "sd,df->sf")
        u = _mm(rnd, x, p["w_up"], "sd,df->sf")
        return hr + _mm(rnd, jax.nn.silu(g) * u, p["w_down"],
                        "sf,fd->sd")
    return jax.lax.map(mlp, h)


def head_loss(c: Dict, w: Dict, h, batch: Dict, rnd: Callable):
    """Final norm, output head and the mean next-token cross-entropy over
    label-bearing tokens of the last residual ``h``."""
    h = _rms(h, w["final_norm"], c["rms_norm_eps"])
    head = w["embed"] if c["tie_word_embeddings"] else w["unembed"]
    lab = batch["labels"]
    valid = (lab >= 0) & (batch["segment_ids"] > 0)

    @jax.checkpoint
    def row_nll(args):                   # one row's logits at a time
        hr, lr, vr = args
        logits = _mm(rnd, hr, head, "sd,vd->sv")
        gold = jnp.take_along_axis(logits, jnp.where(vr, lr, 0)[:, None],
                                   axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * vr)

    nll = jax.lax.map(row_nll, (h, lab, valid))
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def loss(c: Dict, w: Dict, batch: Dict, rnd: Callable = lambda x: x,
         alter: Optional[Callable] = None):
    """Mean next-token cross-entropy over label-bearing tokens.  ``alter``
    (a fault to plant, or None) rewrites each layer's attention output."""
    seg, pos = batch["segment_ids"], batch["positions"]
    h = w["embed"][batch["tokens"]]

    @jax.checkpoint
    def layer(h, p):
        return block(c, p, h, seg, pos, rnd, alter), None

    h, _ = jax.lax.scan(layer, h, {k: w[k] for k in LAYER})
    return head_loss(c, w, h, batch, rnd)


# ------------------------------------------------------------- optimizer
# decay applies to every tensor but the final norm's scale (the
# configuration's stated rule; compare.reference_readings applies it)
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
