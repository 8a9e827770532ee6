"""Parallelism context: logical-axis sharding rules + helpers.

Logical activation/parameter dims are named; ``ShardingRules`` maps them to
mesh axes (or None).  The rule of thumb (DESIGN.md §4):

  batch   -> ("pod", "data")        tokens/batch dim
  seq     -> None  (long_500k decode: ("pod", "data") context-parallel)
  heads   -> "model"  iff n_heads   divisible by the model-axis size
  kv_heads-> "model"  iff n_kv_heads divisible, else replicated
  ffn     -> "model"
  dmodel  -> "data"   (FSDP; GSPMD all-gathers at use)
  vocab   -> "model"
  experts -> "data"   iff expert_parallel and divisible

Every constraint goes through ``ParallelContext.cons`` so single-device
smoke tests (mesh=None) run the identical code path with no-ops.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    batch: Any = None
    seq: Any = None
    # Megatron-SP analogue: the residual stream's sequence dim is sharded
    # over "model" between attention/FFN blocks; GSPMD inserts the
    # all-gather/reduce-scatter pairs.  Cuts activation memory by the TP
    # degree (decode contexts leave it None: S=1).
    residual_seq: Any = None
    heads: Any = None
    kv_heads: Any = None
    ffn: Any = None
    dmodel: Any = None
    vocab: Any = None
    experts: Any = None
    # axis used for CA head-padding when n_heads doesn't divide "model"
    padded_heads: Any = None
    # data-parallel axis name(s) used by the CAD dispatch shard_map
    cad_axis: Any = None

    def resolve(self, name: Optional[str]):
        if name is None:
            return None
        return getattr(self, name)


def make_rules(mesh: Optional[Mesh], cfg) -> ShardingRules:
    """Divisibility-aware rules for a ("data",), ("data","model") or
    ("pod","data","model") mesh."""
    if mesh is None:
        return ShardingRules()
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_n = axes.get("model", 1)
    data_axes = tuple(a for a in ("pod", "data") if a in axes)
    data_n = 1
    for a in data_axes:
        data_n *= axes[a]

    # a data-only mesh has no "model" axis to name
    model = "model" if "model" in axes else None

    def div(n, axis, size):
        return axis if (axis and n and n % size == 0) else None

    heads = div(getattr(cfg, "n_heads", 0), model, model_n)
    kv_heads = div(getattr(cfg, "n_kv_heads", 0), model, model_n)
    ffn = div(getattr(cfg, "d_ff", 0), model, model_n)
    dmodel = div(getattr(cfg, "d_model", 0), data_axes, data_n)
    vocab = div(getattr(cfg, "vocab_size", 0), model, model_n)
    experts = None
    if getattr(cfg, "moe", None) and cfg.moe.n_experts:
        if cfg.moe.expert_parallel and cfg.moe.n_experts % data_n == 0:
            experts = data_axes
        ffn = div(cfg.moe.d_ff_expert, model, model_n)
    return ShardingRules(
        batch=data_axes, seq=None,
        residual_seq="model" if model_n > 1 else None,
        heads=heads, kv_heads=kv_heads, ffn=ffn,
        dmodel=dmodel, vocab=vocab, experts=experts,
        padded_heads="model" if model_n > 1 else None,
        cad_axis=data_axes)


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """Static context threaded through model code.

    attn_impl: "ref" | "xla" | "pallas" | "cad"
    attn_bwd:  None (backend default) | "pallas" | "xla" — backward
               implementation for the Pallas kernel paths (the xla choice
               is the blockwise recompute fallback)
    decode_impl: None ($REPRO_KERNEL_DECODE / default pallas) | "pallas" |
               "xla" — the serving ragged-decode kernel selection
               (DESIGN.md §8), mirroring attn_bwd
    """
    mesh: Optional[Mesh] = None
    rules: ShardingRules = ShardingRules()
    attn_impl: str = "ref"
    attn_bwd: Optional[str] = None
    decode_impl: Optional[str] = None
    cad: Any = None          # CADContext (plan + pool config) when attn_impl=="cad"
    pingpong: bool = False
    remat: bool = True
    seq_shard: bool = False  # long_500k: shard the sequence dim (CP layout)

    def cons(self, x, *dims: Optional[str]):
        """with_sharding_constraint by logical dim names (None entries ok).
        Axes that do not evenly divide the dim are dropped (safety net for
        odd sizes like whisper's 1500-frame encoder)."""
        if self.mesh is None or self.mesh.empty:
            return x
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        resolved = []
        for i, d in enumerate(dims):
            ax = self.rules.resolve(d)
            if ax is not None:
                axs = ax if isinstance(ax, tuple) else (ax,)
                n = 1
                for a in axs:
                    n *= sizes.get(a, 1)
                if x.shape[i] % n:
                    ax = None
            resolved.append(ax)
        spec = P(*resolved)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def spec(self, *dims: Optional[str]) -> P:
        return P(*(self.rules.resolve(d) for d in dims))

    def sharding(self, *dims: Optional[str]):
        assert self.mesh is not None
        return NamedSharding(self.mesh, self.spec(*dims))


def param_pspecs(cfg, params, rules: ShardingRules,
                 mesh: Optional[Mesh] = None):
    """PartitionSpec tree for a param pytree, by leaf-path naming rules.

    Weight naming conventions (models/init):
      embed            (V, D)        -> (vocab, dmodel)
      wq/wo            (D, H*dh) / (H*dh, D)
      wk/wv            (D, Hkv*dh)
      w_gate/w_up      (D, F) ; w_down (F, D)
      experts_*        (E, D, F) / (E, F, D)
      scale/bias/lru_* 1-D or small -> replicated
    Stacked layer dim (leading, when ndim is one higher than the base
    weight) is always unsharded.
    """
    import jax.tree_util as jtu

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) \
        if mesh is not None else {}

    REPLICATED = {"scale", "bias", "lru_a", "conv_b", "conv_w", "A_log",
                  "D_skip", "dt_bias", "xgate", "enc_pos"}

    def leaf_spec(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        name = names[-1] if names else ""

        def wrap(*dims):
            dims = tuple(dims)
            # pad to leaf ndim with None on the left for the stacked dim
            extra = leaf.ndim - len(dims)
            dims = tuple([None] * extra) + dims
            # drop axes that don't divide the dim (safety net)
            fixed = []
            for i, ax in enumerate(dims):
                if ax is None:
                    fixed.append(None)
                    continue
                axs = ax if isinstance(ax, tuple) else (ax,)
                n = 1
                for a in axs:
                    n *= axis_sizes.get(a, 1)
                fixed.append(ax if leaf.shape[i] % n == 0 else None)
            return P(*fixed)

        if name in REPLICATED or leaf.ndim <= 1:
            return P(*([None] * leaf.ndim))

        if name in ("embed", "unembed"):
            return wrap(rules.vocab, rules.dmodel)
        if name in ("wq",):
            return wrap(rules.dmodel, rules.heads)
        if name in ("wk", "wv"):
            return wrap(rules.dmodel, rules.kv_heads)
        if name in ("wo",):
            return wrap(rules.heads, rules.dmodel)
        if name in ("w_gate", "w_up", "w_in"):
            return wrap(rules.dmodel, rules.ffn)
        if name in ("w_down", "w_out"):
            return wrap(rules.ffn, rules.dmodel)
        if name in ("experts_gate", "experts_up"):
            # expert-parallel: E over data; dmodel FSDP only when E isn't
            # (a mesh axis may appear once per spec)
            dm = None if rules.experts else rules.dmodel
            return wrap(rules.experts, dm, rules.ffn)
        if name in ("experts_down",):
            dm = None if rules.experts else rules.dmodel
            return wrap(rules.experts, rules.ffn, dm)
        if name in ("router",):
            return wrap(rules.dmodel, None)
        if name in ("in_proj", "xbc_proj"):   # ssm fused projections
            return wrap(rules.dmodel, None)
        if name in ("out_proj",):
            return wrap(None, rules.dmodel)
        if name in ("w_x", "w_gate_br"):      # rg-lru branches (D, W)
            return wrap(rules.dmodel, rules.ffn)
        if name in ("w_input_gate", "w_rec_gate"):   # (W, W)
            return wrap(rules.dmodel, rules.ffn)
        if name in ("w_out",):                # (W, D)
            return wrap(rules.ffn, rules.dmodel)
        if leaf.ndim >= 2:
            return wrap(*([None] * (leaf.ndim - 2)), rules.dmodel, None)
        return P()

    return jtu.tree_map_with_path(leaf_spec, params)


def head_pad(n_heads: int, mesh: Optional[Mesh]) -> int:
    """Heads padded up to a multiple of the model-axis size, used *inside*
    the CA module so CA stays TP-sharded when n_heads is not divisible
    (llama4 40->48, smollm 15->16, whisper 20->32 ... DESIGN.md §4)."""
    if mesh is None or "model" not in mesh.axis_names:
        return n_heads
    m = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    return ((n_heads + m - 1) // m) * m
