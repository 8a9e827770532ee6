"""Multi-tenant attention fabric (DESIGN.md §10).

Covers the tenant/priority model and SLO-aware admission (FCFS
head-of-line blocking, best-fit placement, forced admission after
``max_wait_rounds``), the serve workload's fixed task sequence and
fused-batch builder, the FabricExecutor's isolation contract (training
outputs bit-identical with serve backfilling vs a dedicated pool),
speculation preemption, kill-mid-decode recovery with deterministic
replay, serve-scheduler snapshot-provider repricing, and the
``repro.launch.serve`` HTTP daemon.
"""
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.cad import CADConfig, CADSession
from repro.core.cost_model import (CalibrationSnapshot, CommModel,
                                   CostModel)
from repro.core.plan import ca_pair_bound
from repro.fabric import (LATENCY, SERVE, THROUGHPUT, TRAIN,
                          AdmissionPolicy, FabricExecutor, ServeWorkload,
                          TenantClass, admit_serve)
from repro.fabric.tenancy import ServeTaskReq
from repro.runtime import ElasticExecutor, FaultSchedule, ServerPool

BLK = 16
D, NB = 4, 8


def make_segs(d=D, nb=NB, seed=0, max_doc_blocks=4):
    rng = np.random.default_rng(seed)
    segs = np.zeros((d, nb * BLK), np.int32)
    sid = 1
    for r in range(d):
        t = 0
        while t < nb:
            dbl = int(rng.integers(1, min(max_doc_blocks, nb - t) + 1))
            segs[r, t * BLK:(t + dbl) * BLK] = sid
            sid += 1
            t += dbl
    return segs


def make_session(drained=()):
    cfg = CADConfig(n_servers=D, blk=BLK, nb=NB, cq=2 * NB, ckv=4 * NB,
                    nkv=4 * NB)
    sess = CADSession(cfg=cfg, comm=CommModel(2, 8, 2), tolerance=0.05,
                      jmax=NB, prefetch=0)
    pool = ServerPool(D)
    for s in drained:
        pool.drain(s)
    return sess.with_pool(pool)


def make_workload(arrivals, seed=7, slots=4):
    return ServeWorkload(arrivals, n_heads=2, head_dim=8, blk=BLK,
                         slots=slots, seed=seed)


def run_fabric(arrivals, steps, *, drained=(), allowed=None, faults=None,
               interval=1e-3, speculate_pct=0.0, max_steps=None,
               seed=0):
    wl = make_workload(arrivals)
    ex = FabricExecutor(
        make_session(drained=drained), wl,
        faults=FaultSchedule.parse(faults) if faults else None,
        policy=AdmissionPolicy(allowed=allowed),
        speculate_pct=speculate_pct)
    digests, reports = [], []
    step = 0
    while step < steps or (max_steps and step < max_steps
                           and not wl.all_done()):
        segs = make_segs(seed=step)
        pos = np.broadcast_to(np.arange(segs.shape[1]), segs.shape).copy()
        q, k, v, pos = ex.synth_inputs(segs, pos, seed=seed + step)
        out, rep = ex.run_mixed_step(step, q, k, v, pos, segs,
                                     interval=interval)
        digests.append(np.asarray(out).tobytes())
        reports.append(rep)
        step += 1
    return wl, digests, reports


def snap_of(cm=None, speeds=(1.0,) * D, version=0):
    return CalibrationSnapshot(version=version,
                               cost_model=cm or CostModel.analytic(2, 8),
                               speeds=tuple(speeds))


def task(rid, q=BLK, kv=2 * BLK, seq=0, arrival=0):
    return ServeTaskReq(rid=rid, seq=seq, q_tokens=q, kv_tokens=kv,
                        arrival_step=arrival)


# ===================================================================
# tenancy: classes + admission
# ===================================================================

def test_tenant_classes():
    assert TRAIN.kind == THROUGHPUT and SERVE.kind == LATENCY
    assert TRAIN.priority < SERVE.priority
    assert SERVE.preempts_speculation and not TRAIN.preempts_speculation
    with pytest.raises(ValueError, match="tenant kind"):
        TenantClass(name="x", kind="bursty", priority=2)


def test_admission_backfills_idle_capacity():
    """Tasks land on the candidate with the most remaining idle; busy
    servers receive nothing they cannot fit."""
    cm = CostModel.analytic(2, 8)
    cost = float(cm.predict(BLK, 2 * BLK))
    interval = 4 * cost
    # server 0 fully busy, 1 half busy, 2 and 3 idle
    busy = {0: interval, 1: interval - 2 * cost, 2: 0.0, 3: 0.0}
    rnd = admit_serve([task(r) for r in range(6)], busy, interval,
                      snap_of(cm), None, candidates=(0, 1, 2, 3))
    assert rnd.n_admitted == 6 and not rnd.deferred
    assert 0 not in rnd.placements
    # best-fit max-idle, ties to the lowest slot: 2,3,2,3, then the
    # half-busy 1 ties with the drained-down 2/3 at 2*cost left
    placed = {s: len(t) for s, t in rnd.placements.items()}
    assert placed[2] + placed[3] >= 4
    assert sum(placed.values()) == 6
    assert all(v >= -1e-12 for v in rnd.idle_after.values())


def test_admission_fcfs_head_of_line_blocks():
    """The first unfittable task defers everything behind it, even
    tasks that would fit — deterministic FCFS, no reordering."""
    cm = CostModel.analytic(2, 8)
    cost = float(cm.predict(BLK, 2 * BLK))
    small = task(1, q=1, kv=BLK)
    big = task(0, q=BLK, kv=2 * BLK)
    rnd = admit_serve([big, small], {0: 0.0}, 0.5 * cost, snap_of(cm),
                      None, candidates=(0,))
    assert rnd.n_admitted == 0
    assert [t.rid for t in rnd.deferred] == [0, 1]


def test_admission_forced_after_max_wait():
    """A head-of-line task past ``max_wait_rounds`` goes through even
    with no idle budget left (the forward-progress guarantee), and
    admission continues behind it."""
    cm = CostModel.analytic(2, 8)
    cost = float(cm.predict(BLK, 2 * BLK))
    pol = AdmissionPolicy(max_wait_rounds=3)
    waits = {0: 3}
    rnd = admit_serve([task(0), task(1, q=1, kv=BLK)], {0: 0.0, 1: 0.0},
                      0.1 * cost, snap_of(cm), None, policy=pol,
                      candidates=(0, 1), waits=waits)
    assert rnd.forced == (0,)
    assert 0 in {t.rid for g in rnd.placements.values() for t in g}
    # without the wait history the same round defers everything
    rnd2 = admit_serve([task(0), task(1, q=1, kv=BLK)],
                       {0: 0.0, 1: 0.0}, 0.1 * cost, snap_of(cm), None,
                       policy=pol, candidates=(0, 1))
    assert rnd2.n_admitted == 0 and len(rnd2.deferred) == 2


def test_admission_allowed_partition_and_slo():
    """``policy.allowed`` confines serve placement (the static-partition
    baseline); deferred tasks older than ``slo_rounds`` count as
    misses; the round is stamped with the view's epoch."""
    cm = CostModel.analytic(2, 8)
    cost = float(cm.predict(BLK, 2 * BLK))
    pol = AdmissionPolicy(slo_rounds=2, allowed=(2, 3))
    rnd = admit_serve([task(r) for r in range(4)],
                      {s: 0.0 for s in range(4)}, 1.01 * cost,
                      snap_of(cm), None, policy=pol,
                      candidates=(0, 1, 2, 3), waits={2: 2, 3: 5})
    assert set(rnd.placements) <= {2, 3}
    assert rnd.n_admitted == 2 and len(rnd.deferred) == 2
    assert rnd.slo_misses == 2          # rids 2 and 3 both past the SLO
    assert rnd.pool_epoch == -1         # no view supplied

    view = make_session().pool.view()
    rnd2 = admit_serve([], {}, 1.0, snap_of(cm), view)
    assert rnd2.pool_epoch == view.epoch


# ===================================================================
# workload: task sequence + fused batch builder
# ===================================================================

def test_workload_task_sequence_is_fixed():
    """Prefill chunks of <= blk tokens, then one decode per round —
    content (hence output) of task ``seq`` never depends on timing."""
    wl = make_workload([(0, 3 * BLK + 4, 2)])
    r = wl.requests[0]
    seen = []
    while not r.done:
        seq, qt, kvt = r.next_task(BLK)
        seen.append((seq, qt, kvt))
        if r.n_prefilled < r.prompt_len:
            r.n_prefilled += qt
        else:
            r.n_decoded += 1
    assert seen == [(0, BLK, BLK), (1, BLK, 2 * BLK),
                    (2, BLK, 3 * BLK), (3, 4, 3 * BLK + 4),
                    (4, 1, 3 * BLK + 5), (5, 1, 3 * BLK + 6)]


def test_workload_build_batch_layout():
    wl = make_workload([(0, 2 * BLK, 1), (0, BLK // 2, 1)])
    tasks = wl.pending(0)
    assert [t.q_tokens for t in tasks] == [BLK, BLK // 2]
    inputs, plan = wl.build_batch(tasks)
    q_tasks, qpos, k_buf, v_buf, kpos = (np.asarray(a) for a in inputs)
    assert q_tasks.shape == (wl.slots, BLK, 2, 8)
    assert k_buf.shape[0] == wl.kv_blocks
    # dead q rows/pad kv rows carry position -1
    assert (np.asarray(qpos)[1, BLK // 2:] == -1).all()
    start = np.asarray(plan["task_kv_start"])
    ln = np.asarray(plan["task_kv_len"])
    assert ln[0] == 1 and ln[1] == 1 and start[1] == 1
    assert (np.asarray(kpos)[1, BLK // 2:] == -1).all()
    with pytest.raises(ValueError, match="slots"):
        wl.build_batch([task(0)] * (wl.slots + 1))


def test_workload_groups_fit_the_pair_bound():
    """Fused serve batches keep the placement order and hold at most
    ``slots`` tasks whose kv blocks (one pair each) sum to at most the
    bound the CA-server kernels' pair lists are sized by."""
    wl = make_workload([(0, 8, 1)], slots=4)
    tasks = [ServeTaskReq(rid=i, seq=0, q_tokens=1,
                          kv_tokens=blocks * BLK - 3, arrival_step=0)
             for i, blocks in enumerate([3, 1, 2, 1, 3, 2])]

    def sizes(bound):
        return [[-(-t.kv_tokens // BLK) for t in g]
                for g in wl.groups(tasks, bound)]

    assert sizes(4) == [[3, 1], [2, 1], [3], [2]]
    assert sizes(100) == [[3, 1, 2, 1], [3, 2]]
    ex = FabricExecutor(make_session(), wl)
    assert ex._serve_pairs == ca_pair_bound(make_session().cfg, wl.jmax)


def test_workload_rejects_empty_prompt_and_blk_mismatch():
    with pytest.raises(ValueError, match="empty prompt"):
        make_workload([(0, 0, 1)])
    with pytest.raises(ValueError, match="blk"):
        FabricExecutor(make_session(),
                       ServeWorkload([(0, 8, 1)], blk=128))


# ===================================================================
# fabric executor: isolation, preemption, recovery
# ===================================================================

def _train_only(steps, seed=0):
    ex = ElasticExecutor(make_session())
    digests = []
    for step in range(steps):
        segs = make_segs(seed=step)
        pos = np.broadcast_to(np.arange(segs.shape[1]), segs.shape).copy()
        q, k, v, pos = ex.synth_inputs(segs, pos, seed=seed + step)
        out, _rep = ex.run_step(step, q, k, v, pos, segs)
        digests.append(np.asarray(out).tobytes())
    return digests


def test_train_bit_identical_with_serve_backfill():
    """The isolation contract: training outputs with serve traffic
    backfilling the same pool match a dedicated-pool run bit-for-bit,
    and the serve tenant also completes."""
    arr = [(0, 2 * BLK, 2), (1, BLK, 1), (1, 3 * BLK, 2)]
    wl, digests, reps = run_fabric(arr, 8)
    assert digests == _train_only(8)
    assert wl.all_done()
    assert sum(r.executed for r in reps) \
        == sum(len(r.digests) for r in wl.requests)
    assert all(r.calib_version == reps[0].calib_version for r in reps)


def test_serve_preempts_speculation_not_primaries():
    """With serve tasks pending, the step's speculation budget goes to
    the latency tenant (spec_preempted, no backups run); once the
    workload drains, speculation resumes (a straggler in the late
    steps gets a backup).  Primary-task outputs are untouched
    throughout."""
    wl, digests, reps = run_fabric([(0, BLK, 1)], 6, speculate_pct=0.9,
                                   faults="slow:1x8@3-5")
    assert reps[0].spec_preempted
    assert reps[0].train.speculated == ()
    drained = [r for r in reps if r.admitted == 0 and r.deferred == 0]
    assert drained and not any(r.spec_preempted for r in drained)
    assert any(r.train.speculated for r in drained)
    assert digests == _train_only(6)


def test_kill_mid_decode_recovers_and_replays():
    """Killing a server mid-step loses its serve placements along with
    its train tasks: serve re-places onto least-loaded survivors in the
    same round, both tenants complete, and the whole run replays
    deterministically."""
    arr = [(0, 2 * BLK, 3)] * 8        # enough load to cover the victim
    kw = dict(steps=6, faults="kill:1@3", max_steps=30)
    wl1, d1, r1 = run_fabric(arr, **kw)
    wl2, d2, r2 = run_fabric(arr, **kw)
    kill = r1[3]
    assert kill.train.failed == (1,)
    assert kill.lost_serve > 0 and kill.readmitted == kill.lost_serve
    assert wl1.all_done()
    assert r1[-1].pool_epoch == 1
    # deterministic replay: train + serve outputs, completion, timing
    assert d1 == d2
    assert wl1.digest_map() == wl2.digest_map()
    assert wl1.completion() == wl2.completion()
    assert [r.step_seconds for r in r1] == [r.step_seconds for r in r2]
    # placement-independence: the kill run's per-request digests match
    # the fault-free run's (prefix — both ran the same task sequences)
    wl0, _d0, _r0 = run_fabric(arr, steps=6, max_steps=30)
    assert wl0.digest_map() == wl1.digest_map()


def test_partition_vs_shared_placement_independent():
    """Per-request serve digests agree between a shared pool and a
    static partition — outputs are pure functions of (request, task)."""
    arr = [(0, 2 * BLK, 2)] * 6
    shared, _d, _r = run_fabric(arr, 6, max_steps=30)
    part, _d2, _r2 = run_fabric(arr, 6, drained=(2, 3), allowed=(2, 3),
                                max_steps=30)
    assert shared.all_done() and part.all_done()
    assert shared.digest_map() == part.digest_map()


def test_admission_round_reports_budget_pressure():
    """A tight cadence defers work and stamps SLO misses; waits clear
    once a request's task finally runs."""
    arr = [(0, 2 * BLK, 1)] * 12
    wl, _d, reps = run_fabric(arr, 6, interval=1e-7, allowed=(3,),
                              max_steps=6)
    assert any(r.deferred > 0 for r in reps)
    assert any(r.slo_misses > 0 for r in reps[4:])
    assert not wl.all_done()


# ===================================================================
# session admission view + scheduler snapshot provider
# ===================================================================

def test_session_admission_view_fallback_and_provider():
    sess = make_session()
    snap, view = sess.admission_view()
    assert snap.version == -1                 # no calibrator: analytic
    assert len(snap.speeds) == D
    assert view.epoch == 0
    provider = sess.snapshot_provider()
    assert provider().version == snap.version


def test_scheduler_snapshot_provider_reprices_each_round():
    from repro.serve.scheduler import (ContinuousScheduler, Request,
                                       SchedulerConfig)
    calls = []

    def provider():
        calls.append(len(calls))
        return snap_of(version=len(calls))

    s = ContinuousScheduler(SchedulerConfig(
        n_slots=2, max_seq=256, admission="cost",
        snapshot_provider=provider))
    s.submit(Request(rid=0, prompt=np.arange(1, 9, dtype=np.int32),
                     max_new_tokens=2))
    assert [r.rid for r in s.admit()] == [0]
    assert calls == [0] and s.last_calib_version == 1   # one per round
    s.admit()
    assert len(calls) == 2 and s.last_calib_version == 2

    # cost admission needs SOME pricing source
    with pytest.raises(ValueError, match="cost_model or a "
                                         "snapshot_provider"):
        SchedulerConfig(n_slots=1, max_seq=64, admission="cost")


# ===================================================================
# HTTP daemon
# ===================================================================

def test_daemon_http_roundtrip():
    """submit/stream/health/drain through the real HTTP stack on an
    ephemeral port, with cost admission priced by the live calibrator."""
    from repro.launch import serve as L
    args = L.parse_args(["--slots", "2", "--max-seq", "64",
                         "--max-new", "4", "--admission", "cost",
                         "--calibrate"])
    daemon = L.EngineDaemon(L.build_engine(args), calibrate=True)
    srv = L.make_server(daemon, "127.0.0.1", 0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_port}"

    def post(path, obj):
        rq = urllib.request.Request(base + path,
                                    json.dumps(obj).encode())
        with urllib.request.urlopen(rq) as r:
            return json.loads(r.read())

    try:
        out = post("/generate", {"prompt": [3, 14, 15],
                                 "max_new_tokens": 3})
        assert len(out["tokens"]) == 3

        rq = urllib.request.Request(
            base + "/generate",
            json.dumps({"prompt": [1, 2], "stream": True}).encode())
        with urllib.request.urlopen(rq) as r:
            lines = [json.loads(ln) for ln in r]
        assert lines[-1]["done"] and len(lines[-1]["tokens"]) == 4
        assert [ln["token"] for ln in lines[:-1]] \
            == lines[-1]["tokens"][:-1]

        with urllib.request.urlopen(base + "/health") as r:
            h = json.loads(r.read())
        assert h["status"] == "ok" and h["done"] == 2 and h["rounds"] > 0
        # DESIGN.md §14: pool/queue fields come from the same registry
        # /metrics exports, so the two endpoints can never disagree
        assert h["queue_depth"] == 0 and h["pool_epoch"] >= 0
        assert h["calib_version"] >= -1
        with urllib.request.urlopen(base + "/metrics") as r:
            assert r.headers["Content-Type"] \
                == "text/plain; version=0.0.4"
            text = r.read().decode()
        assert "# TYPE serve_admitted_total counter" in text
        assert "# TYPE serve_rounds_total counter" in text
        assert "serve_queue_depth 0" in text

        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/generate", {"prompt": []})
        assert ei.value.code == 400
        ei.value.close()

        assert post("/drain", {})["draining"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/generate", {"prompt": [1]})
        assert ei.value.code == 503
        ei.value.close()
        with urllib.request.urlopen(base + "/health") as r:
            assert json.loads(r.read())["status"] == "drained"
    finally:
        daemon.stop()
        srv.shutdown()
        srv.server_close()
