"""Quickstart: core attention disaggregation in ~50 lines.

Builds a packed two-rank batch, plans CA-tasks through a ``CADSession``
(the attention-service entry point — plan policies are selected by
name), dispatches them through the CAD runtime (global simulation of the
attention-server pool on CPU), and checks the result equals monolithic
attention.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.cad import CADConfig, CADSession
from repro.core import cad_attention, ref_attention
from repro.core.cost_model import CommModel
from repro.launch.mesh import device_summary

print(f"device: {json.dumps(device_summary())}")

# --- a packed batch: 2 ranks x 1024 tokens, documents of 1-4 blocks ----
BLK, D, S = 128, 2, 1024
rng = np.random.default_rng(0)
segs = np.zeros((D, S), np.int32)
poss = np.zeros((D, S), np.int32)
sid = 1
for r in range(D):
    t = 0
    while t < S:
        dl = min(int(rng.integers(1, 5)) * BLK, S - t)
        segs[r, t:t + dl] = sid
        poss[r, t:t + dl] = np.arange(dl)
        sid += 1
        t += dl

# --- the attention service: pool geometry + plan policy by name --------
nb = S // BLK
session = CADSession(
    cfg=CADConfig(n_servers=D, blk=BLK, nb=nb, cq=nb, ckv=2 * nb,
                  nkv=4 * nb),
    kernel="xla", plan_policy="balanced", tolerance=0.05, jmax=nb,
    comm=CommModel(n_heads=4, head_dim=64, n_kv_heads=2))

plan, stats = session.plan(segs)          # one step's typed StepPlan
print(f"planner[{session.plan_policy}]: {stats['n_moves']} migrations, "
      f"{stats['comm_bytes']/2**20:.2f} MiB moved, "
      f"straggler x{stats['load_max_over_mean']:.3f}")

# --- dispatch through the CAD runtime, compare to monolithic CA --------
ctx = session.context()
ctx = ctx.cad.bind_plan(ctx, plan)        # bind this step's plan

key = jax.random.PRNGKey(0)
kq, kk, kv = jax.random.split(key, 3)
q = jax.random.normal(kq, (D, S, 4, 64))
k = jax.random.normal(kk, (D, S, 2, 64))
v = jax.random.normal(kv, (D, S, 2, 64))
seg, pos = jnp.asarray(segs), jnp.asarray(poss)

out_cad = cad_attention(q, k, v, seg, pos, seg, pos, ctx=ctx)
out_ref = ref_attention(q, k, v, seg, pos, seg, pos)
err = float(jnp.max(jnp.abs(out_cad - out_ref)))
print(f"CAD == monolithic attention: max |err| = {err:.2e}")
assert err < 1e-4
print("OK")
