"""CA-server grid counts (``kernels/packed_flash`` ``ca_grid_cells``,
summed into the plan's ``schedule_stats["grid"]`` by ``cad/session.py``
``grid_counts``): the cells each planned step's CA-server kernels
launch, and how many run a body, per server and in all, equal a brute-force
walk of the launched pair grids with the kernels' own liveness — the
token-level mask on the positions each server's task batch holds.  A
Pallas session sizes those grids by ``plan.ca_pair_bound``: a plan over
it raises ``PlanCapacityError``, and ProLong layouts never are."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.cad import CADConfig, CADSession, PingPongPlan, \
    PlanCapacityError
from repro.core.cost_model import CommModel
from repro.core.dispatch import CADContext, build_server_inputs
from repro.core.mask import mask_params, parse_mask
from repro.core.plan import ca_pair_bound
from repro.data.pipeline import PipelineConfig, raw_batches
from repro.kernels.packed_flash import kernel as K
from repro.kernels.packed_flash.kernel import _ca_mask, ca_grid_cells

BLK = 16
D, NB = 2, 8                    # servers, q/kv blocks per rank and half


def layout(seed, tokens):
    """[D, tokens] segment ids and in-document positions (-1: padding);
    documents start on block boundaries, some end mid-block."""
    rng = np.random.default_rng(seed)
    segs = np.zeros((D, tokens), np.int32)
    pos = -np.ones((D, tokens), np.int32)
    sid = 1
    for r in range(D):
        t = 0
        while t < tokens:
            span = min(int(rng.integers(1, 7)) * BLK, tokens - t)
            real = span - int(rng.integers(0, BLK // 2))
            segs[r, t:t + real] = sid
            pos[r, t:t + real] = np.arange(real)
            sid += 1
            t += span
    return segs, pos


def walk(rows, cols, n_real, live):
    """Cells of one launched list, and those ``live(row, col)`` for."""
    pairs = list(zip(np.asarray(rows).tolist(), np.asarray(cols).tolist()))
    return len(pairs), sum(p < int(n_real[0]) and live(*pair)
                           for p, pair in enumerate(pairs))


def brute_force(plan, pos, jmax, mask, max_pairs):
    """Walk every server's forward and dk/dv pair grids of one half, as
    the kernels launch them (their own pair lists, sized by
    ``max_pairs`` and split into calls), evaluating each cell as the
    kernels do."""
    cfg = CADConfig(n_servers=D, blk=BLK, nb=NB, cq=NB, ckv=2 * NB,
                    nkv=4 * NB)
    window, sink, rate = mask_params(mask)
    dense = not (window or sink or rate > 1)
    cad = CADContext(cfg=cfg, kernel="xla", jmax=jmax, mask=mask)
    x = jnp.zeros(pos.shape + (1, 1), jnp.float32)
    inputs, plans_r = build_server_inputs(cad, plan, x, x, x,
                                          jnp.asarray(pos))
    fwd = dkv = 0
    fwd_live, dkv_live = [], []
    for (_, q_pos, _, _, kv_pos), pr in zip(inputs, plans_r):
        q_pos, kv_pos = np.asarray(q_pos), np.asarray(kv_pos)
        start = np.asarray(pr["task_kv_start"])
        length = np.asarray(pr["task_kv_len"])
        n_tasks, n_kv = len(length), len(kv_pos)

        def any_live(t, n):
            return dense or bool(_ca_mask(q_pos[t][:, None],
                                          kv_pos[n][None, :], True,
                                          window, sink, rate, BLK).any())

        def live_f(t, j):
            n = min(int(start[t]) + j, n_kv - 1)
            return j < length[t] and any_live(t, n)

        def live_d(n, t):
            return 0 <= n - int(start[t]) < length[t] and any_live(t, n)

        room_f, room_d = K._list_lengths(n_tasks, n_kv, jmax, max_pairs)
        live = live_kv = 0
        for chunk in K._chunks(*K._task_pairs(jnp.asarray(length), jmax,
                                              room_f), jmax):
            cells, n_live = walk(*chunk, live_f)
            fwd, live = fwd + cells, live + n_live
        for chunk in K._chunks(*K._kv_pairs(jnp.asarray(start),
                                            jnp.asarray(length), n_kv,
                                            room_d), n_tasks):
            cells, n_live = walk(*chunk, live_d)
            dkv, live_kv = dkv + cells, live_kv + n_live
        fwd_live.append(live)
        dkv_live.append(live_kv)
    return fwd, fwd_live, dkv, dkv_live


@pytest.mark.parametrize("kernel", ["xla", "pallas", "pallas-split"])
@pytest.mark.parametrize("mask,pingpong", [
    (None, False),
    (None, True),
    ("sliding:window=20,sink=8", True),
    ("dilated:rate=2", False),
])
def test_grid_counts_match_a_walk_of_the_launched_grids(mask, pingpong,
                                                         kernel,
                                                         monkeypatch):
    """The XLA session counts the dense grids (no bound), the Pallas one
    the pair grids its kernels launch, sized by ``ca_pair_bound``; with
    scalar memory for 40 cells those lists run as several calls, and a
    call that redoes the last run counts its cells again."""
    if kernel == "pallas-split":
        monkeypatch.setattr(K, "LIST_CELLS", 40)
        kernel = "pallas"
    mask = parse_mask(mask) if mask else None
    halves = 2 if pingpong else 1
    tokens = halves * NB * BLK
    cfg = CADConfig(n_servers=D, blk=BLK, nb=NB, cq=NB, ckv=2 * NB,
                    nkv=4 * NB)
    jmax = 6
    session = CADSession(cfg=cfg, kernel=kernel, pingpong=pingpong,
                         tolerance=0.05, comm=CommModel(2, 8, 1),
                         jmax=jmax, prefetch=0, mask=mask)
    max_pairs = ca_pair_bound(cfg, jmax) if kernel == "pallas" else None
    segs, pos = layout(3, tokens)
    plan, stats = session.plan(segs)
    stats = stats["grid"]
    plans = list(plan) if pingpong else [plan]
    assert isinstance(plan, PingPongPlan) == pingpong
    assert len(plans) == halves

    want = [0, 0, 0, 0]
    half = tokens // halves
    for i, p in enumerate(plans):
        f, fl, k, kl = brute_force(p, pos[:, i * half:(i + 1) * half],
                                   jmax, mask, max_pairs)
        want = [a + b for a, b in zip(want, (f, sum(fl), k, sum(kl)))]
        # per server, as the kernels' module counts one half
        cells = ca_grid_cells(p["task_kv_start"], p["task_kv_len"],
                              np.asarray(p["kv_gather"]).shape[1], jmax,
                              BLK, mask, max_pairs)
        assert cells[1].tolist() == fl and cells[3].tolist() == kl
        assert cells[0] * D == f and cells[2] * D == k
    got = [stats[k] for k in ("ca_fwd_cells", "ca_fwd_cells_live",
                              "ca_dkv_cells", "ca_dkv_cells_live")]
    assert got == want
    # the walk found work, and less than the whole grid
    assert 0 < got[1] < got[0] and 0 < got[3] < got[2]


def test_grid_counts_leave_out_tasks_beyond_jmax():
    """A task whose kv range is longer than ``jmax`` runs only jmax
    forward cells; its dk/dv cells still cover its whole range."""
    cfg = CADConfig(n_servers=D, blk=BLK, nb=NB, cq=NB, ckv=2 * NB,
                    nkv=4 * NB)
    session = CADSession(cfg=cfg, kernel="xla", comm=CommModel(2, 8, 1),
                         jmax=2, prefetch=0, plan_policy="identity")
    segs = np.ones((D, NB * BLK), np.int32) * np.array([[1], [2]])
    stats = session.plan(segs)[1]["grid"]
    # identity: every rank serves its own document of NB blocks
    per_server = sum(min(b + 1, 2) for b in range(NB))
    assert stats["ca_fwd_cells_live"] == D * per_server
    assert stats["ca_dkv_cells_live"] == D * NB * (NB + 1) // 2


def test_plan_over_the_pair_bound_raises():
    """Documents longer than ``jmax`` blocks cover more pairs than the
    bound allows: the Pallas session refuses the plan, naming the server
    and the grid, where the kernels would drop the pairs past the end."""
    cfg = CADConfig(n_servers=D, blk=BLK, nb=NB, cq=NB, ckv=2 * NB,
                    nkv=4 * NB)
    session = CADSession(cfg=cfg, kernel="pallas", comm=CommModel(2, 8, 1),
                         jmax=2, prefetch=0, plan_policy="identity")
    segs = np.ones((D, NB * BLK), np.int32) * np.array([[1], [2]])
    with pytest.raises(PlanCapacityError) as ei:
        session.plan(segs)
    # identity: each server's own document of NB blocks covers
    # NB(NB+1)/2 (kv block, task) pairs, and its other 4NB - NB kv slots
    # one cell each, against W + N cells, W = D·ceil(NB·3/2)
    err = ei.value
    assert err.capacity == "ca_dkv_pairs" and err.server == 0
    assert err.src is None and err.dst is None
    assert err.needed == NB * (NB + 1) // 2 + 3 * NB
    assert err.available == D * -(-NB * 3 // 2) + 4 * NB
    assert "server 0" in str(err) and "plan half 0" in str(err)
    # the same layout within jmax plans, and the XLA server has no bound
    dataclasses.replace(session, jmax=NB).plan(segs)
    dataclasses.replace(session, kernel="xla").plan(segs)


@pytest.mark.parametrize("servers", [1, 4])
@pytest.mark.parametrize("max_doc_len", [4096, 8192])
def test_pair_bound_holds_on_prolong_layouts(max_doc_len, servers):
    """The benchmark cells' geometries (nano-batches of 32 blocks with
    documents up to 32 for smollm-360m, 64 and 64 for Mistral-7B), on 1
    and 4 servers with ping-pong: ProLong layouts fit the pair grids
    with room, and the bound holds for any assignment of the tasks."""
    pipe = PipelineConfig(distribution="prolong", max_doc_len=max_doc_len,
                          seq_len=max_doc_len, global_batch=2 * servers,
                          n_ranks=servers, seed=servers)

    class Model:
        n_heads, head_dim, n_kv_heads = 1, 1, 1

    session = CADSession.for_pipeline(Model, pipe, kernel="pallas",
                                      pingpong=True, prefetch=0)
    cfg, jmax = session.cfg, session.jmax
    assert (cfg.nb, jmax) == (max_doc_len // 128,) * 2
    bound = ca_pair_bound(cfg, jmax)
    assert bound == servers * -(-cfg.nb * (jmax + 1) // 2)
    batches = raw_batches(pipe)
    for _ in range(4):
        plan, stats = session.plan(next(batches)["segment_ids"].reshape(
            servers, -1))
        for p in plan:
            start, length = (np.asarray(p[k]) for k in
                             ("task_kv_start", "task_kv_len"))
            n_kv = np.asarray(p["kv_gather"]).shape[1]
            # the pool covers at most the bound, so every server does
            assert np.minimum(length, jmax).sum() <= bound
            g = ca_grid_cells(start, length, n_kv, jmax, 128,
                              max_pairs=bound)
            assert (g.fwd_need <= g.fwd_room).all()
            assert (g.dkv_need <= g.dkv_room).all()
        # one call a list at these sizes
        assert g.fwd == g.fwd_room
        assert stats["grid"]["ca_fwd_cells"] == 2 * servers * g.fwd
