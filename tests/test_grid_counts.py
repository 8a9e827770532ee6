"""CA-server grid counts (``kernels/packed_flash`` ``ca_grid_cells``,
summed into the plan's ``schedule_stats["grid"]`` by ``cad/session.py``
``grid_counts``): the cells each planned step's CA-server kernels
launch, and how many run a body, per server and in all, equal a brute-force
walk of the launched grids with the kernels' own liveness — the
token-level mask on the positions each server's task batch holds."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cad import CADConfig, CADSession, PingPongPlan
from repro.core.cost_model import CommModel
from repro.core.dispatch import CADContext, build_server_inputs
from repro.core.mask import mask_params, parse_mask
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


def brute_force(plan, pos, jmax, mask):
    """Walk every server's forward (T, jmax) and dk/dv (N, T) grids of
    one half, evaluating each cell as the kernels do."""
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

        live = live_kv = 0
        for t in range(n_tasks):
            for j in range(jmax):
                n = min(int(start[t]) + j, n_kv - 1)
                live += j < length[t] and any_live(t, n)
        for n in range(n_kv):
            for t in range(n_tasks):
                jrel = n - int(start[t])
                live_kv += 0 <= jrel < length[t] and any_live(t, n)
        fwd += n_tasks * jmax
        dkv += n_kv * n_tasks
        fwd_live.append(live)
        dkv_live.append(live_kv)
    return fwd, fwd_live, dkv, dkv_live


@pytest.mark.parametrize("mask,pingpong", [
    (None, False),
    (None, True),
    ("sliding:window=20,sink=8", True),
    ("dilated:rate=2", False),
])
def test_grid_counts_match_a_walk_of_the_launched_grids(mask, pingpong):
    mask = parse_mask(mask) if mask else None
    halves = 2 if pingpong else 1
    tokens = halves * NB * BLK
    cfg = CADConfig(n_servers=D, blk=BLK, nb=NB, cq=NB, ckv=2 * NB,
                    nkv=4 * NB)
    jmax = 6
    session = CADSession(cfg=cfg, kernel="xla", pingpong=pingpong,
                         tolerance=0.05, comm=CommModel(2, 8, 1),
                         jmax=jmax, prefetch=0, mask=mask)
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
                                   jmax, mask)
        want = [a + b for a, b in zip(want, (f, sum(fl), k, sum(kl)))]
        # per server, as the kernels' module counts one half
        cells = ca_grid_cells(p["task_kv_start"], p["task_kv_len"],
                              np.asarray(p["kv_gather"]).shape[1], jmax,
                              BLK, mask)
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
