"""System-level end-to-end properties."""
import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cad import CADSession
from repro.configs import get_config
from repro.core.cost_model import CommModel
from repro.core.plan import CADConfig
from repro.data.pipeline import PipelineConfig, raw_batches
from repro.models import model as M
from repro.optim.adamw import AdamW
from repro.parallel import (ParallelContext, ShardingRules, make_rules,
                            param_pspecs)
from repro.train.step import make_train_step


def test_cad_training_grads_match_baseline():
    """One full train step with CAD (scheduler plan, dispatch, server
    kernels, flash backward) produces the same parameter update as the
    plain xla path — the whole-system correctness claim."""
    cfg = get_config("smollm-360m").reduced()
    pipe = PipelineConfig(distribution="pretrain", max_doc_len=256,
                          seq_len=256, global_batch=4, n_ranks=2,
                          vocab_size=cfg.vocab_size, seed=3)
    cadcfg = CADConfig.default(2, 2 * 256, max_doc_tokens=256)
    session = CADSession.from_legacy(
        cadcfg, comm=CommModel(n_heads=cfg.n_heads, head_dim=cfg.head_dim,
                               n_kv_heads=cfg.n_kv_heads))
    gen = session.attach_plans(raw_batches(pipe), prefetch=0)
    batch = next(gen)
    batch.pop("schedule_stats", None)

    params = M.init(jax.random.PRNGKey(0), cfg)
    opt = AdamW(lr=1e-2)

    from repro.core.dispatch import CADContext
    cad = CADContext(cfg=cadcfg, kernel="xla",
                     jmax=pipe.max_doc_len // cadcfg.blk)
    ctx_cad = ParallelContext(attn_impl="cad", cad=cad, remat=False)
    ctx_ref = ParallelContext(attn_impl="xla", remat=False)

    p1, _, m1 = make_train_step(cfg, ctx_cad, opt)(params, opt.init(params),
                                                   dict(batch))
    batch.pop("plan")
    p2, _, m2 = make_train_step(cfg, ctx_ref, opt)(params, opt.init(params),
                                                   batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert err < 5e-3, err


def test_make_rules_divisibility():
    """Sharding rules never propose a non-dividing axis (run on 4 fake
    devices would be nicer, but the rule logic is pure)."""
    class FakeMesh:
        axis_names = ("data", "model")

        class _D:
            shape = (16, 16)
        devices = _D()

    for arch in ("smollm-360m", "mistral-large-123b", "qwen2-moe-a2.7b"):
        cfg = get_config(arch)
        rules = make_rules(FakeMesh(), cfg)
        if cfg.n_heads % 16:
            assert rules.heads is None
        if cfg.n_kv_heads % 16:
            assert rules.kv_heads is None
        if cfg.d_ff and cfg.d_ff % 16 == 0 and not cfg.moe:
            assert rules.ffn == "model"


def test_make_rules_data_only_mesh():
    """A ("data",) mesh — one CAD rank per chip — has no "model" axis, so
    the rules name none; batch, dmodel (FSDP) and the CAD dispatch use the
    data axis."""
    class FakeMesh:
        axis_names = ("data",)

        class _D:
            shape = (4,)
        devices = _D()

    rules = make_rules(FakeMesh(), get_config("smollm-360m"))
    assert (rules.heads, rules.kv_heads, rules.ffn, rules.vocab) \
        == (None, None, None, None)
    assert rules.batch == rules.dmodel == rules.cad_axis == ("data",)


def test_compile_cache_placement(monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR is left to JAX; without it the cache
    goes to one fixed directory inside the checkout."""
    from repro.launch import compile_cache as CC
    updates = []
    monkeypatch.setattr(CC.jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    assert CC.enable_compile_cache() == str(tmp_path)
    assert updates == []
    monkeypatch.delenv(CC.ENV_VAR)
    placed = CC.enable_compile_cache()
    assert updates == [("jax_compilation_cache_dir", placed)]
    checkout = Path(__file__).resolve().parents[1]
    assert Path(placed) == checkout / ".jax_cache"


def test_param_pspecs_cover_all_leaves():
    """Every arch's param tree gets a valid spec for every leaf (specs
    match ndim, no axis repeated)."""
    import jax.tree_util as jtu
    for arch in ("gemma2-2b", "mamba2-370m", "recurrentgemma-9b",
                 "whisper-large-v3", "llama4-maverick-400b-a17b"):
        cfg = get_config(arch).reduced()
        shapes = jax.eval_shape(lambda c=cfg: M.init(jax.random.PRNGKey(0),
                                                     c))
        rules = ShardingRules(heads="model", kv_heads="model", ffn="model",
                              dmodel=("data",), vocab="model",
                              batch=("data",))
        specs = param_pspecs(cfg, shapes, rules)

        def check(path, leaf, spec):
            assert len(spec) <= leaf.ndim, (jtu.keystr(path), spec)
            flat = [a for s in spec if s is not None
                    for a in (s if isinstance(s, tuple) else (s,))]
            assert len(flat) == len(set(flat)), (jtu.keystr(path), spec)
        jtu.tree_map_with_path(check, shapes, specs)
