"""Trainer: the host loop that owns the data pipeline, the CAD attention
service (plans prefetched asynchronously one step ahead — the paper's
"scheduler prefetches the upcoming batch"), jit compilation,
checkpointing, and metrics."""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.cad import CADSession
from repro.checkpoint import ckpt
from repro.data.pipeline import PipelineConfig, raw_batches
from repro.models import model as M
from repro.optim.adamw import AdamW, AdamWState, cosine_schedule
from repro.parallel import ParallelContext, param_pspecs
from repro.train.step import make_train_step


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    peak_lr: float = 3e-4
    warmup: int = 20
    weight_decay: float = 0.1
    log_every: int = 10
    ckpt_every: int = 0
    ckpt_dir: str = "/tmp/repro_ckpt"
    seed: int = 0
    calibrate_every: int = 0      # probe + feed CA timings every N steps
                                  # (0 = off; needs a session calibrator)
    fault_schedule: str = ""      # FaultSchedule spec applied to the
                                  # session's ServerPool (one is attached
                                  # if missing): membership events take
                                  # effect at step granularity here —
                                  # a killed server is excluded from the
                                  # next plan; prefetched plans from the
                                  # dead epoch re-plan at pull
    speculate_pct: float = 0.0    # straggler-speculation percentile;
                                  # consumed by the task-level elastic
                                  # executor (benchmarks/examples) — the
                                  # fused jit path only records it


def _mesh_shardings(cfg, ctx: ParallelContext, params):
    """(params, optimizer state, batch) shardings on ``ctx.mesh``: params
    by the ``ctx.rules`` leaf rules (FSDP over the data axes), AdamW
    moments like their params, and every batch leaf — plan arrays
    included — split on its leading, rank-major dim."""
    mesh = ctx.mesh
    p_sh = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        param_pspecs(cfg, params, ctx.rules, mesh),
                        is_leaf=lambda x: isinstance(x, P))
    o_sh = AdamWState(step=NamedSharding(mesh, P()), mu=p_sh, nu=p_sh)
    return p_sh, o_sh, NamedSharding(mesh, P(ctx.rules.batch))


def train(cfg, pipe_cfg: PipelineConfig, train_cfg: TrainConfig,
          ctx: Optional[ParallelContext] = None, params=None,
          session: Optional[CADSession] = None) -> Dict[str, Any]:
    """Train ``cfg`` (a ModelConfig); returns final params + history.
    ``params``, when given, are consumed: the first step donates them.

    Pass ``session`` (a :class:`repro.cad.CADSession`) to train with the
    attention service: the session provides the ParallelContext and
    attaches prefetched plans to every batch.  Without a session the
    loop trains on raw packed batches with a plain (or caller-supplied)
    ``ctx``.  When the context carries a mesh, params, optimizer state
    and batches are placed on it, and the step keeps them there.

    The result holds the final ``params`` and ``opt_state``, the logged
    ``history`` and the jitted ``step_fn``."""
    faults = pool = None
    if session is not None:
        if train_cfg.fault_schedule:
            from repro.runtime import FaultSchedule, ServerPool
            faults = FaultSchedule.parse(train_cfg.fault_schedule)
            if session.pool is None:
                session = session.with_pool(ServerPool(
                    session.cfg.n_servers,
                    calibrator=session.calibrator))
            if train_cfg.speculate_pct > 0:
                print("note: --speculate-pct drives task-level "
                      "speculation in the elastic executor "
                      "(benchmarks/elastic_recovery.py); the fused "
                      "train step applies membership events only")
        pool = session.pool
        ctx = session.context()
        gen = session.attach_plans(raw_batches(pipe_cfg))
    else:
        ctx = ctx or ParallelContext(attn_impl="xla", remat=True)
        gen = raw_batches(pipe_cfg)
    key = jax.random.PRNGKey(train_cfg.seed)
    if params is None:
        params = M.init(key, cfg)
    opt = AdamW(lr=cosine_schedule(train_cfg.peak_lr, train_cfg.warmup,
                                   train_cfg.steps),
                weight_decay=train_cfg.weight_decay)
    # params and optimizer state are donated: each step's outputs reuse
    # their buffers, so the step never holds two copies of either
    step = make_train_step(cfg, ctx, opt)
    if ctx.mesh is None:
        opt_state = opt.init(params)
        step_fn = jax.jit(step, donate_argnums=(0, 1))
    else:
        p_sh, o_sh, b_sh = _mesh_shardings(cfg, ctx, params)
        params = jax.device_put(params, p_sh)
        opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)
        step_fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                          out_shardings=(p_sh, o_sh, None),
                          donate_argnums=(0, 1))

    calibrating = (session is not None
                   and session.calibrator is not None
                   and train_cfg.calibrate_every > 0)
    if session is not None and session.calibrator is not None \
            and train_cfg.ckpt_every:
        # calibration survives restarts: pick up the measured grid from
        # the newest checkpoint (no-op when none carries calibration)
        last = ckpt.latest_step(train_cfg.ckpt_dir)
        if last is not None and ckpt.restore_calibration(
                train_cfg.ckpt_dir, last, session.calibrator):
            print(f"restored calibration state from step {last}")
    history = []
    t0 = time.time()
    try:
        for step in range(train_cfg.steps):
            pool_events = []
            if faults is not None:
                # membership events land at step granularity on the
                # fused path: the planner is re-invoked against the
                # survivors and stale prefetched plans re-plan at pull
                # (kills apply before the step — the jitted path cannot
                # lose a server mid-flight; same shared semantics as
                # the elastic executor)
                pool_events = faults.apply_pre_step(pool, step) \
                    + faults.apply_failures(pool, step)
                if pool_events:
                    print(f"step {step:5d} pool: "
                          f"{', '.join(pool_events)} "
                          f"(epoch {pool.epoch})")
            batch = next(gen)
            stats = batch.pop("schedule_stats", None)
            plan = batch.get("plan") if calibrating else None
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if calibrating and plan is not None \
                    and step % train_cfg.calibrate_every == 0:
                # measure → fit: per-server kernel timings feed the
                # calibrator, so the (prefetched) plan for a later batch
                # is built from these measured costs (DESIGN.md §3)
                session.observe_probe(plan, seed=train_cfg.seed + step)
            if step % train_cfg.log_every == 0 \
                    or step == train_cfg.steps - 1:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step
                m["wall_s"] = time.time() - t0
                if stats:
                    m.update({f"sched_{k}": v for k, v in stats.items()})
                if pool_events:
                    m["pool_events"] = ";".join(pool_events)
                history.append(m)
                print(f"step {step:5d} loss {m['loss']:.4f} "
                      f"gnorm {m['grad_norm']:.3f} ({m['wall_s']:.1f}s)")
            if train_cfg.ckpt_every and step and \
                    step % train_cfg.ckpt_every == 0:
                ckpt.save(train_cfg.ckpt_dir, step, params, opt_state,
                          calibrator=None if session is None
                          else session.calibrator)
    finally:
        gen.close()      # stops the plan-prefetch worker, if any
    return {"params": params, "opt_state": opt_state, "history": history,
            "step_fn": step_fn}
