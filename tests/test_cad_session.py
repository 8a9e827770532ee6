"""CADSession API tests: policy-registry parity with the legacy dict-plan
path (bit-identical plans and global-sim outputs), ping-pong as a typed
PingPongPlan, PlanCapacityError diagnostics, and the async plan
prefetcher (ordering, queue bounds, shutdown, overlap)."""
import dataclasses
import itertools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cad import (CADConfig, CADSession, PingPongPlan,
                       PlanCapacityError, PlanPrefetcher, StepPlan,
                       available_policies, get_planner)
from repro.core import (CADContext, CommModel, cad_attention,
                        identity_plan, per_document_cp_plan,
                        plan_from_schedule, ref_attention, schedule)
from repro.core.dispatch import _global_sim
from repro.parallel import ParallelContext

BLK = 64


def random_layout(rng, d, s, blk=BLK, max_doc_blocks=4):
    segs = np.zeros((d, s), np.int32)
    poss = np.zeros((d, s), np.int32)
    sid = 1
    for r in range(d):
        t = 0
        while t < s:
            nbl = int(rng.integers(1, max_doc_blocks + 1))
            dl = min(nbl * blk, s - t)
            real = dl if rng.random() < 0.7 else max(1, dl - int(
                rng.integers(0, blk)))
            segs[r, t:t + real] = sid
            poss[r, t:t + real] = np.arange(real)
            sid += 1
            t += dl
    return segs, poss


def make_cfg(d, s, blk=BLK):
    nb = s // blk
    return CADConfig(n_servers=d, blk=blk, nb=nb, cq=nb, ckv=2 * nb,
                     nkv=4 * nb)


def legacy_dict_plan(policy, cfg, segs, comm, tolerance):
    """The pre-CADSession way of building each policy's plan, as a raw
    dict (the legacy plan format the dispatch still accepts)."""
    if policy == "identity":
        return identity_plan(cfg, segs).to_dict()
    if policy == "per_doc_cp":
        return per_document_cp_plan(cfg, segs).to_dict()
    sch = schedule(segs, blk=cfg.blk, n_servers=cfg.n_servers, comm=comm,
                   caps=cfg.caps(), tolerance=tolerance)
    return plan_from_schedule(cfg, sch).to_dict()


def test_all_policies_registered():
    assert set(available_policies()) >= {"identity", "per_doc_cp",
                                         "balanced"}


@pytest.mark.parametrize("policy", ["identity", "per_doc_cp", "balanced"])
def test_session_plan_parity_with_legacy(policy):
    """CADSession plans are bit-identical to the legacy path's, and the
    global-sim dispatch output is bit-identical too."""
    rng = np.random.default_rng(7)
    d, s, hq, hkv, dh = 2, 8 * BLK, 4, 2, 32
    segs, poss = random_layout(rng, d, s)
    cfg = make_cfg(d, s)
    comm = CommModel(hq, dh, hkv)
    session = CADSession(cfg=cfg, kernel="xla", plan_policy=policy,
                         tolerance=0.05, comm=comm, jmax=cfg.nkv)

    plan, stats = session.plan(segs)
    assert isinstance(plan, StepPlan)
    legacy = legacy_dict_plan(policy, cfg, segs, comm, 0.05)
    for k, v in legacy.items():
        np.testing.assert_array_equal(np.asarray(plan[k]), v, err_msg=k)

    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (d, s, hq, dh))
    k_ = jax.random.normal(ks[1], (d, s, hkv, dh))
    v_ = jax.random.normal(ks[2], (d, s, hkv, dh))
    posm = jnp.where(jnp.asarray(segs) > 0, jnp.asarray(poss), -1)

    cad_new = CADContext(cfg=cfg, kernel="xla", jmax=cfg.nkv)
    out_new = _global_sim(q, k_, v_, posm,
                          jax.tree.map(jnp.asarray, plan), cad_new, 0.0,
                          None)
    out_old = _global_sim(q, k_, v_, posm,
                          jax.tree.map(jnp.asarray, legacy), cad_new, 0.0,
                          None)
    np.testing.assert_array_equal(np.asarray(out_new), np.asarray(out_old))
    # and both match monolithic attention
    expected = ref_attention(q, k_, v_, jnp.asarray(segs),
                             jnp.asarray(poss), jnp.asarray(segs),
                             jnp.asarray(poss))
    np.testing.assert_allclose(np.asarray(out_new), np.asarray(expected),
                               atol=2e-5)


def test_session_pingpong_plan_parity():
    """Ping-pong sessions emit a typed PingPongPlan whose halves equal
    the legacy per-nano tuple plans; dispatch matches monolithic CA."""
    rng = np.random.default_rng(11)
    d, rpr, s, hq, hkv, dh = 2, 2, 4 * BLK, 2, 2, 32
    b = d * rpr
    segs_rows = np.zeros((b, s), np.int32)
    poss_rows = np.zeros((b, s), np.int32)
    sid = 1
    for r in range(b):
        t = 0
        while t < s:
            dl = min(int(rng.integers(1, 4)) * BLK, s - t)
            segs_rows[r, t:t + dl] = sid
            poss_rows[r, t:t + dl] = np.arange(dl)
            sid += 1
            t += dl
    nano_tokens = (rpr // 2) * s
    sub = CADConfig(n_servers=d, blk=BLK, nb=nano_tokens // BLK,
                    cq=nano_tokens // BLK, ckv=2 * nano_tokens // BLK,
                    nkv=4 * nano_tokens // BLK)
    comm = CommModel(hq, dh, hkv)
    session = CADSession(cfg=sub, kernel="xla", pingpong=True,
                         tolerance=0.05, plan_policy="balanced",
                         comm=comm, jmax=sub.nkv)
    # rank-major rows: rank r owns rows [r*rpr, (r+1)*rpr)
    segs_rank = segs_rows.reshape(d, rpr * s)
    plan, _ = session.plan(segs_rank)
    assert isinstance(plan, PingPongPlan)
    for i, half in enumerate(plan):
        seg_i = np.stack([segs_rows[r * rpr + i] for r in range(d)])
        sch = schedule(seg_i, blk=BLK, n_servers=d, comm=comm,
                       caps=sub.caps(), tolerance=0.05)
        legacy = plan_from_schedule(sub, sch)
        for key_ in legacy.keys():
            np.testing.assert_array_equal(np.asarray(half[key_]),
                                          np.asarray(legacy[key_]))

    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, hq, dh))
    k_ = jax.random.normal(ks[1], (b, s, hkv, dh))
    v_ = jax.random.normal(ks[2], (b, s, hkv, dh))
    seg, pos = jnp.asarray(segs_rows), jnp.asarray(poss_rows)
    ctx = session.context()
    ctx = ctx.cad.bind_plan(ctx, jax.tree.map(jnp.asarray, plan))
    out = cad_attention(q, k_, v_, seg, pos, seg, pos, ctx=ctx)
    expected = ref_attention(q, k_, v_, seg, pos, seg, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)


def test_legacy_fullsize_pingpong_cfg_resized():
    """A CADConfig sized for the full step with pingpong=True is re-sized
    to the nano-batch (the old pipeline behavior), not rejected."""
    d, s = 2, 8 * BLK
    cfg = make_cfg(d, s)                   # full-step geometry
    session = CADSession(cfg=cfg, pingpong=True, tolerance=0.05,
                         comm=CommModel(4, 32, 2))
    segs, _ = random_layout(np.random.default_rng(2), d, s)
    plan, _ = session.plan(segs)
    assert isinstance(plan, PingPongPlan)
    assert np.asarray(plan.ping.q_home_idx).shape == (d, (s // 2) // BLK)


def test_plan_capacity_error_reports_details():
    """CQ overflow raises a diagnostic error, not a bare assert."""
    rng = np.random.default_rng(0)
    d, s = 2, 8 * BLK
    segs = np.zeros((d, s), np.int32)
    # one long doc on rank 0 so head-tail CP must send many q blocks
    segs[0, :] = 1
    segs[1, : 2 * BLK] = 2
    nb = s // BLK
    tiny = CADConfig(n_servers=d, blk=BLK, nb=nb, cq=1, ckv=2 * nb,
                     nkv=4 * nb)
    with pytest.raises(PlanCapacityError) as ei:
        get_planner("per_doc_cp")(tiny, segs)
    e = ei.value
    assert e.capacity == "CQ"
    assert (e.src, e.dst) == (0, 1)
    assert e.needed > e.available == 1
    assert "CQ" in str(e) and "src=0" in str(e)


def test_for_pipeline_does_not_mutate_pipe_cfg():
    from repro.configs import get_config
    from repro.data.pipeline import PipelineConfig
    cfg = get_config("smollm-360m").reduced()
    pipe = PipelineConfig(seq_len=256, max_doc_len=256, global_batch=4,
                          n_ranks=2, vocab_size=cfg.vocab_size)
    before = dataclasses.asdict(pipe)
    session = CADSession.for_pipeline(cfg, pipe, plan_policy="balanced")
    assert dataclasses.asdict(pipe) == before
    assert session.cfg.n_servers == 2
    ctx = session.context()
    assert ctx.attn_impl == "cad" and ctx.cad.cfg is session.cfg


# ---------------------------------------------------------- prefetcher
def test_prefetcher_order_and_shutdown():
    items = list(range(20))
    pf = PlanPrefetcher(iter(items), lambda x: x * x, depth=3)
    out = list(pf)
    assert out == [x * x for x in items]
    assert not pf._thread.is_alive()
    pf.close()                               # idempotent


def test_prefetcher_bounded_lookahead():
    pulled = []

    def source():
        for i in itertools.count():
            pulled.append(i)
            yield i

    depth = 2
    pf = PlanPrefetcher(source(), lambda x: x, depth=depth)
    try:
        taken = []
        for _ in range(5):
            taken.append(next(pf))
            time.sleep(0.05)                 # let the worker run ahead
            # look-ahead never exceeds: consumed + queue depth + 1 in fn
            assert len(pulled) <= len(taken) + depth + 1, \
                (len(pulled), len(taken))
        assert taken == list(range(5))
    finally:
        pf.close()
    assert not pf._thread.is_alive()


def test_prefetcher_propagates_worker_exception():
    def bad(x):
        if x == 3:
            raise ValueError("boom at 3")
        return x

    pf = PlanPrefetcher(iter(range(10)), bad, depth=2)
    got = []
    with pytest.raises(ValueError, match="boom at 3"):
        for x in pf:
            got.append(x)
    assert got == [0, 1, 2]
    assert not pf._thread.is_alive()


def test_prefetch_overlaps_planning_with_compute():
    """The overlap claim: a multi-step loop with async prefetch completes
    no slower than with inline (synchronous) planning — the plan of step
    i+1 is built while step i 'computes'."""
    t_plan, t_step, steps = 0.03, 0.03, 6

    def plan_fn(x):
        time.sleep(t_plan)
        return x

    def run(depth):
        src = iter(range(steps))
        t0 = time.perf_counter()
        if depth == 0:
            for item in src:
                plan_fn(item)
                time.sleep(t_step)
        else:
            with PlanPrefetcher(src, plan_fn, depth=depth) as pf:
                for _ in pf:
                    time.sleep(t_step)
        return time.perf_counter() - t0

    sync_wall = run(0)
    async_wall = run(2)
    assert async_wall <= sync_wall, (async_wall, sync_wall)
    # and most of the planning time is actually hidden
    assert async_wall <= steps * t_step + 3 * t_plan, async_wall


def test_attach_plans_matches_synchronous_planning():
    """attach_plans(prefetch=2) yields the same plans, in order, as the
    synchronous path."""
    rng = np.random.default_rng(5)
    d, s = 2, 8 * BLK
    cfg = make_cfg(d, s)
    session = CADSession(cfg=cfg, plan_policy="balanced", tolerance=0.05,
                         comm=CommModel(4, 32, 2), jmax=cfg.nkv)

    def fake_batches(n):
        r = np.random.default_rng(9)
        for _ in range(n):
            segs, _ = random_layout(r, d, s)
            yield {"segment_ids": segs.reshape(d, s)}

    sync = [b["plan"] for b in
            session.attach_plans(fake_batches(5), prefetch=0)]
    pre = [b["plan"] for b in
           session.attach_plans(fake_batches(5), prefetch=2)]
    assert len(sync) == len(pre) == 5
    for a, b in zip(sync, pre):
        for ka, kb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(ka, kb)


def test_train_runs_through_session():
    """trainer.train with a CADSession: plans prefetched, loss finite."""
    from repro.configs import get_config
    from repro.data.pipeline import PipelineConfig
    from repro.train.trainer import TrainConfig, train
    cfg = get_config("smollm-360m").reduced()
    pipe = PipelineConfig(distribution="pretrain", max_doc_len=256,
                          seq_len=256, global_batch=4, n_ranks=2,
                          vocab_size=cfg.vocab_size, seed=3)
    session = CADSession.for_pipeline(cfg, pipe, plan_policy="balanced")
    res = train(cfg, pipe, TrainConfig(steps=2, peak_lr=1e-3, warmup=1,
                                       log_every=1), session=session)
    assert len(res["history"]) == 2
    assert np.isfinite(res["history"][-1]["loss"])
    assert "sched_comm_bytes" in res["history"][-1]
    # no stray prefetch workers left behind
    names = [t.name for t in threading.enumerate()]
    assert "cad-plan-prefetch" not in names


MESH_TRAIN_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import sys; sys.path.insert(0, 'src')
import numpy as np, jax
from jax.sharding import NamedSharding
from repro.cad import CADSession
from repro.configs import get_config
from repro.data.pipeline import PipelineConfig
from repro.launch.mesh import make_data_mesh
from repro.parallel import make_rules
from repro.train.trainer import TrainConfig, train

cfg = get_config('smollm-360m').reduced()
pipe = PipelineConfig(distribution='pretrain', max_doc_len=256, seq_len=256,
                      global_batch=8, n_ranks=4, vocab_size=cfg.vocab_size,
                      seed=3)
tc = TrainConfig(steps=2, peak_lr=1e-3, warmup=1, log_every=1)
mesh = make_data_mesh()
runs = {}
for name, m in (('sim', None), ('mesh', mesh)):
    session = CADSession.for_pipeline(
        cfg, pipe, kernel='xla', pingpong=True, prefetch=0, mesh=m,
        rules=None if m is None else make_rules(m, cfg))
    runs[name] = train(cfg, pipe, tc, session=session)
loss = {k: [h['loss'] for h in r['history']] for k, r in runs.items()}
np.testing.assert_allclose(loss['mesh'], loss['sim'], rtol=1e-4)
res = runs['mesh']
embed = res['params']['embed']['embed']
assert isinstance(embed.sharding, NamedSharding), embed.sharding
assert len(embed.sharding.device_set) == 4
assert embed.sharding.spec == jax.sharding.PartitionSpec(None, ('data',))
from repro.data.pipeline import raw_batches
batch = session.plan_batch(next(raw_batches(pipe)))
batch.pop('schedule_stats')
hlo = res['step_fn'].lower(res['params'], res['opt_state'],
                           batch).compile().as_text()
assert 'all-to-all' in hlo
print('OK', loss)
"""


def test_train_on_data_mesh_matches_global_sim():
    """train() on a 4-device ("data",) mesh: params and optimizer state
    sharded on the mesh, the CAD dispatch is the shard_map all-to-all, and
    the losses match the single-device global simulation of the same
    pool (4 fake CPU devices, in a subprocess so this process keeps one)."""
    import subprocess
    import sys
    from pathlib import Path
    r = subprocess.run([sys.executable, "-c", MESH_TRAIN_SCRIPT],
                       capture_output=True, text=True,
                       cwd=Path(__file__).resolve().parents[1], timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
