"""CAD core tests: scheduler invariants (hypothesis), plan properties,
dispatch equivalence (CAD == monolithic attention), gradients, ping-pong,
and the real shard_map path (subprocess with fake devices)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # dev extra; property tests only
from hypothesis import given, settings, strategies as st

from repro.core import (CADConfig, CADContext, CommModel, cad_attention,
                        identity_plan, imbalance, per_document_cp_plan,
                        plan_from_schedule, ref_attention, schedule)
from repro.core.dispatch import _global_sim
from repro.core.plan import ca_pair_bound
from repro.kernels.packed_flash import kernel as K
from repro.parallel import ParallelContext, ShardingRules

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
            # occasionally leave padding (short doc not filling its blocks)
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


def plan_coverage(plan, cfg, segs):
    """Every real q-block appears exactly once (home or exactly one send)."""
    d, nb = cfg.n_servers, cfg.nb
    seen = np.zeros((d, nb), np.int64)
    for r in range(d):
        for i in plan["q_home_idx"][r]:
            if i >= 0:
                seen[r, i] += 1
        for s_ in range(d):
            for i in plan["q_send_idx"][r, s_]:
                if i >= 0:
                    seen[r, i] += 1
    lead = segs.reshape(d, nb, cfg.blk)[:, :, 0]
    real = lead > 0
    assert (seen[real] == 1).all(), "real block not covered exactly once"
    assert (seen[~real] == 0).all(), "padding block dispatched"


@settings(max_examples=20, deadline=None)
@given(d=st.sampled_from([2, 4]), nbr=st.integers(4, 10),
       tol=st.sampled_from([0.02, 0.1, 0.3]), seed=st.integers(0, 10 ** 6))
def test_scheduler_properties(d, nbr, tol, seed):
    rng = np.random.default_rng(seed)
    s = nbr * BLK
    segs, _ = random_layout(rng, d, s)
    cfg = make_cfg(d, s)
    comm = CommModel(4, 32, 2)
    sch = schedule(segs, blk=BLK, n_servers=d, comm=comm, caps=cfg.caps(),
                   tolerance=tol)
    # conservation: assignment is a permutation-free total map
    assert sch.assign.shape == (d * cfg.nb,)
    assert ((sch.assign >= 0) & (sch.assign < d)).all()
    # loads consistent with assignment
    cost = np.where(sch.doc_of_block >= 0,
                    (sch.bi_of_block + 1) * float(BLK * BLK), 0.0)
    loads = np.array([cost[sch.assign == s_].sum() for s_ in range(d)])
    np.testing.assert_allclose(loads, sch.loads, rtol=1e-9)
    # scheduler never worsens the straggler
    home = (np.arange(d * cfg.nb) // cfg.nb)
    loads0 = np.array([cost[home == s_].sum() for s_ in range(d)])
    assert imbalance(sch.loads) <= imbalance(loads0) + 1e-9
    # plan builds without violating capacities, covers every block
    plan = plan_from_schedule(cfg, sch)
    plan_coverage(plan, cfg, segs)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10 ** 6), tol=st.sampled_from([0.05, 0.2]))
def test_dispatch_equivalence_property(seed, tol):
    """CAD(scheduled plan) == monolithic attention, for random layouts."""
    rng = np.random.default_rng(seed)
    d, s, hq, hkv, dh = 4, 8 * BLK, 4, 2, 32
    segs, poss = random_layout(rng, d, s)
    cfg = make_cfg(d, s)
    comm = CommModel(hq, dh, hkv)
    sch = schedule(segs, blk=BLK, n_servers=d, comm=comm, caps=cfg.caps(),
                   tolerance=tol)
    plan = jax.tree.map(jnp.asarray, plan_from_schedule(cfg, sch))
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (d, s, hq, dh))
    k = jax.random.normal(ks[1], (d, s, hkv, dh))
    v = jax.random.normal(ks[2], (d, s, hkv, dh))
    seg = jnp.asarray(segs)
    pos = jnp.asarray(poss)
    expected = ref_attention(q, k, v, seg, pos, seg, pos)
    cad = CADContext(cfg=cfg, plan=plan, kernel="xla", jmax=cfg.nkv)
    posm = jnp.where(seg > 0, pos, -1)
    out = _global_sim(q, k, v, posm, plan, cad, 0.0, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)


@pytest.mark.parametrize("plan_fn", [identity_plan, per_document_cp_plan])
def test_dispatch_equivalence_fixed_plans(plan_fn):
    rng = np.random.default_rng(3)
    d, s, hq, hkv, dh = 4, 8 * BLK, 4, 2, 32
    segs, poss = random_layout(rng, d, s)
    cfg = make_cfg(d, s)
    plan = jax.tree.map(jnp.asarray, plan_fn(cfg, segs))
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (d, s, hq, dh))
    k = jax.random.normal(ks[1], (d, s, hkv, dh))
    v = jax.random.normal(ks[2], (d, s, hkv, dh))
    seg, pos = jnp.asarray(segs), jnp.asarray(poss)
    expected = ref_attention(q, k, v, seg, pos, seg, pos)
    cad = CADContext(cfg=cfg, plan=plan, kernel="xla", jmax=cfg.nkv)
    ctx = ParallelContext(mesh=None, attn_impl="cad", cad=cad)
    out = cad_attention(q, k, v, seg, pos, seg, pos, ctx=ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)


@pytest.mark.parametrize("list_cells", [None, 64])
def test_dispatch_pallas_server(list_cells, monkeypatch):
    """The Pallas servers of the vmapped pool; with scalar memory for
    ``list_cells`` pairs their lists run as several calls, which gives
    the same bits."""
    rng = np.random.default_rng(5)
    d, s, hq, hkv, dh = 2, 6 * BLK, 2, 1, 64
    segs, poss = random_layout(rng, d, s)
    cfg = make_cfg(d, s)
    comm = CommModel(hq, dh, hkv)
    sch = schedule(segs, blk=BLK, n_servers=d, comm=comm, caps=cfg.caps(),
                   tolerance=0.05)
    plan = jax.tree.map(jnp.asarray, plan_from_schedule(cfg, sch))
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (d, s, hq, dh))
    k = jax.random.normal(ks[1], (d, s, hkv, dh))
    v = jax.random.normal(ks[2], (d, s, hkv, dh))
    seg, pos = jnp.asarray(segs), jnp.asarray(poss)
    expected = ref_attention(q, k, v, seg, pos, seg, pos)
    cad = CADContext(cfg=cfg, plan=plan, kernel="pallas", jmax=cfg.nkv)
    ctx = ParallelContext(mesh=None, attn_impl="cad", cad=cad)
    out = cad_attention(q, k, v, seg, pos, seg, pos, ctx=ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)
    if list_cells:
        monkeypatch.setattr(K, "LIST_CELLS", list_cells)
        assert K._chunking(K._list_lengths(cfg.n_tasks, cfg.nkv, cfg.nkv,
                                           ca_pair_bound(cfg, cfg.nkv))[0],
                           cfg.nkv)[0] > 1
        split = cad_attention(q, k, v, seg, pos, seg, pos, ctx=ctx)
        np.testing.assert_array_equal(np.asarray(split), np.asarray(out))


def test_dispatch_gradients():
    """d loss/d q,k,v through the CAD dispatch equals the monolithic
    gradient — the backward A2A mirror works by construction."""
    rng = np.random.default_rng(7)
    d, s, hq, hkv, dh = 2, 4 * BLK, 2, 2, 32
    segs, poss = random_layout(rng, d, s)
    cfg = make_cfg(d, s)
    comm = CommModel(hq, dh, hkv)
    sch = schedule(segs, blk=BLK, n_servers=d, comm=comm, caps=cfg.caps(),
                   tolerance=0.05)
    plan = jax.tree.map(jnp.asarray, plan_from_schedule(cfg, sch))
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (d, s, hq, dh))
    k = jax.random.normal(ks[1], (d, s, hkv, dh))
    v = jax.random.normal(ks[2], (d, s, hkv, dh))
    seg, pos = jnp.asarray(segs), jnp.asarray(poss)
    cad = CADContext(cfg=cfg, plan=plan, kernel="xla", jmax=cfg.nkv)
    ctx = ParallelContext(mesh=None, attn_impl="cad", cad=cad)

    def loss_cad(q_, k_, v_):
        return jnp.sum(cad_attention(q_, k_, v_, seg, pos, seg, pos,
                                     ctx=ctx) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(ref_attention(q_, k_, v_, seg, pos, seg, pos) ** 2)

    gc = jax.grad(loss_cad, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gc, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_pingpong_equivalence():
    """Two nano-batches with independent plans == one monolithic pass."""
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
    # per-nano plans: each nano is one row per rank here (rpr=2, half=1)
    nano_tokens = (rpr // 2) * s
    sub = CADConfig(n_servers=d, blk=BLK, nb=nano_tokens // BLK,
                    cq=nano_tokens // BLK, ckv=2 * nano_tokens // BLK,
                    nkv=4 * nano_tokens // BLK)
    comm = CommModel(hq, dh, hkv)
    plans = []
    for i in range(2):
        # rank-major rows: rank r owns rows [r*rpr, (r+1)*rpr)
        rows = [segs_rows[r * rpr + i] for r in range(d)]
        seg_i = np.stack(rows)
        sch = schedule(seg_i, blk=BLK, n_servers=d, comm=comm,
                       caps=sub.caps(), tolerance=0.05)
        plans.append(jax.tree.map(jnp.asarray,
                                  plan_from_schedule(sub, sch)))
    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, hq, dh))
    k = jax.random.normal(ks[1], (b, s, hkv, dh))
    v = jax.random.normal(ks[2], (b, s, hkv, dh))
    seg, pos = jnp.asarray(segs_rows), jnp.asarray(poss_rows)
    expected = ref_attention(q, k, v, seg, pos, seg, pos)
    cad = CADContext(cfg=sub, plan=tuple(plans), kernel="xla",
                     jmax=sub.nkv, pingpong=True)
    ctx = ParallelContext(mesh=None, attn_impl="cad", cad=cad)
    out = cad_attention(q, k, v, seg, pos, seg, pos, ctx=ctx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)


SHARD_MAP_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys; sys.path.insert(0, 'src')
import numpy as np, jax, jax.numpy as jnp
from repro.core import (CADConfig, CADContext, CommModel, cad_attention,
                        plan_from_schedule, ref_attention, schedule)
from repro.parallel import ParallelContext, ShardingRules

rng = np.random.default_rng(0)
D, S, blk, Hq, Hkv, dh = 8, 512, 64, 4, 2, 32
nb = S // blk
segs = np.zeros((D, S), np.int32); poss = np.zeros((D, S), np.int32); sid = 1
for r in range(D):
    t = 0
    while t < S:
        dl = min(int(rng.integers(1, 6)) * blk, S - t)
        segs[r, t:t+dl] = sid; poss[r, t:t+dl] = np.arange(dl)
        sid += 1; t += dl
cfg = CADConfig(n_servers=D, blk=blk, nb=nb, cq=nb, ckv=2*nb, nkv=4*nb)
comm = CommModel(Hq, dh, Hkv)
sch = schedule(segs, blk=blk, n_servers=D, comm=comm, caps=cfg.caps(),
               tolerance=0.05)
plan = jax.tree.map(jnp.asarray, plan_from_schedule(cfg, sch))
mesh = jax.make_mesh((8,), ('data',))
rules = ShardingRules(batch=('data',), cad_axis=('data',))
key = jax.random.PRNGKey(0); ks = jax.random.split(key, 3)
q = jax.random.normal(ks[0], (D, S, Hq, dh))
k = jax.random.normal(ks[1], (D, S, Hkv, dh))
v = jax.random.normal(ks[2], (D, S, Hkv, dh))
seg, pos = jnp.asarray(segs), jnp.asarray(poss)
expected = ref_attention(q, k, v, seg, pos, seg, pos)
cad = CADContext(cfg=cfg, plan=plan, kernel='xla', jmax=nb)
ctx = ParallelContext(mesh=mesh, rules=rules, attn_impl='cad', cad=cad)
out = jax.jit(lambda *a: cad_attention(*a, ctx=ctx))(q, k, v, seg, pos,
                                                     seg, pos)
err = float(jnp.max(jnp.abs(out - expected)))
assert err < 2e-5, err
print('OK', err)
"""


def test_shard_map_dispatch_subprocess():
    """The real distributed path on 8 fake XLA host devices (isolated in a
    subprocess so the main session keeps a single device)."""
    r = subprocess.run([sys.executable, "-c", SHARD_MAP_SCRIPT],
                       capture_output=True, text=True, cwd="/root/repo",
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
