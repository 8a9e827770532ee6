"""The comparison that decides ``correct``.

The program's first three training steps run through the window's own
step and feed.  From them the harness reads (see ``window``):

  * the loss of each of the three steps;
  * the first gradient as the optimizer got it (clipped), per tensor,
    from the optimizer's first moment after one update (m1 = (1-b1) g);
  * the change of each tensor after three updates.

The reference (``bench/references/<name>.py``) trains the same weights,
drawn from the seed, on the same three batches, computing in float32
and storing the weights in the configuration's parameter type.  Three
numbers are read; each that has a limit in ``bench/limits/<workload>.json``
is compared against it:

  loss_gap    max over the three steps of |loss - reference loss|
  grad_gap    worst tensor of | |g| - |g_ref| | / max(|g_ref|, median |g_ref|)
  change_gap  the same for the change after three updates, over the
              tensors whose reference gradient is at least a thousandth
              of the median tensor's (others move by round-off alone)

``rnd`` rounds every matrix-multiply operand of the reference: the
control rounds them to float8 (e4m3), one step below the configuration's
bfloat16.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parents[1]
NUMBERS = ("loss_gap", "grad_gap", "change_gap")
EXCLUDE_BELOW = 1e-3


def reference_module(c: Dict):
    path = BENCH / "references" / f"{c['reference']}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{c['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def limits(workload: str) -> Dict[str, float]:
    """The limits of the numbers a cell compares.  A number with no
    limit there is read and printed, not compared: it had no reading of
    the control or of a fault to set an upper end from."""
    return json.loads((BENCH / "limits" / f"{workload}.json").read_text())[
        "limits"]


def fp8(x):
    """The control's rounding: the forward value of every matrix-multiply
    operand in float8 e4m3, one step below bfloat16; gradients pass
    through it in float32, so only the arithmetic loses precision."""
    return x + jax.lax.stop_gradient(
        x.astype(jnp.float8_e4m3fn).astype(jnp.float32) - x)


# faults planted in the reference, to read what each number sees of them
def half_batch(batches: List[Dict]) -> List[Dict]:
    """Half of each batch's rows left out; the mean is over the rest."""
    return [{k: x[: max(1, x.shape[0] // 2)] for k, x in b.items()}
            for b in batches]


def zero_first_block(a):
    """One attention answer altered where it is made: the first 128-token
    query block of the first row gets a zero output in every layer."""
    return a.at[0, :128].set(0.0)


def _norms(tree: Dict[str, jnp.ndarray], scale: float = 1.0):
    return {k: float(v) * scale for k, v in jax.jit(
        lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                   for k, x in t.items()})(tree).items()}


# -------------------------------------------------------------- program
def program_first_grad(mu: Dict[str, jnp.ndarray], b1: float):
    """Per-tensor norm of the clipped first gradient, from m1."""
    return _norms(mu, 1.0 / (1.0 - b1))


def program_change(ref, c: Dict, params: Dict[str, jnp.ndarray], key):
    """Per-tensor norm of (parameters now - the seed's initial ones)."""
    def diff(p, k):
        w0 = ref.init(c, k)
        return {n: jnp.sqrt(jnp.sum(jnp.square(
            p[n].astype(jnp.float32) - w0[n]))) for n in p}
    return {k: float(v) for k, v in jax.jit(diff)(params, key).items()}


# ------------------------------------------------------------ reference
def _adamw_leaf(t, step):
    @jax.jit
    def upd(w, g, m, v, scale):
        g = g * scale
        m = t["b1"] * m + (1 - t["b1"]) * g
        v = t["b2"] * v + (1 - t["b2"]) * g * g
        mh = m / (1 - t["b1"] ** step)
        vh = v / (1 - t["b2"] ** step)
        return mh / (jnp.sqrt(vh) + t["eps"]), m, v, g
    return upd


def reference_readings(ref, c: Dict, key, batches: List[Dict],
                       rnd: Callable = lambda x: x,
                       alter: Optional[Callable] = None) -> Dict:
    """Three float32 AdamW steps of the reference on ``batches``.  The
    optimizer moments stay on the host between steps, so that only the
    weights, one step's gradients and its activations share the chip."""
    t = c["train"]
    # the weights are kept in the configuration's parameter type: each
    # update is computed in float32 and stored rounded to that type
    store = jax.jit(lambda x: ref.round_to(x, c["param_dtype"]))
    with jax.default_matmul_precision("highest"):
        w = jax.jit(lambda k: ref.init(c, k))(key)
        vg = jax.jit(jax.value_and_grad(
            lambda ww, b: ref.loss(c, ww, b, rnd, alter)))
        gnorm = jax.jit(lambda g: jnp.sqrt(sum(jnp.sum(x * x)
                                               for x in g.values())))
        m = {k: np.zeros(x.shape, np.float32) for k, x in w.items()}
        v = {k: np.zeros(x.shape, np.float32) for k, x in w.items()}
        losses, first_grad = [], None
        for n, b in enumerate(batches[:3], start=1):
            dev = {k: jnp.asarray(x) for k, x in b.items()}
            lval, g = vg(w, dev)
            losses.append(float(lval))
            scale = jnp.minimum(1.0, t["grad_clip"] / (gnorm(g) + 1e-9))
            lr = float(ref.lr_at(t, n))
            upd = _adamw_leaf(t, n)
            clipped = {}
            for k in list(w):
                delta, mk, vk, gk = upd(w[k], g.pop(k), jnp.asarray(m[k]),
                                        jnp.asarray(v[k]), scale)
                if k in ref.DECAYED:
                    delta = delta + t["weight_decay"] * w[k]
                w[k] = store(w[k] - lr * delta)
                m[k], v[k] = np.asarray(mk), np.asarray(vk)
                if n == 1:
                    clipped[k] = gk
                del delta, mk, vk, gk
            if n == 1:
                first_grad = _norms(clipped)
                del clipped
        change = program_change(ref, c, w, key)
    return {"losses": losses, "first_grad": first_grad, "change": change}


# --------------------------------------------------------------- numbers
def tensor_gaps(prog: Dict[str, float], ref: Dict[str, float],
                keep=None) -> Dict[str, float]:
    """| |x| - |x_ref| | / max(|x_ref|, median |x_ref|) of each tensor."""
    med = float(np.median([ref[k] for k in ref]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref
            if keep is None or k in keep}


def worst_tensor(prog: Dict[str, float], ref: Dict[str, float],
                 keep=None) -> float:
    return max(tensor_gaps(prog, ref, keep).values())


def moved(ref: Dict) -> set:
    """Tensors whose reference gradient is at least EXCLUDE_BELOW of the
    median tensor's."""
    g = ref["first_grad"]
    med = float(np.median(list(g.values())))
    return {k for k, x in g.items() if x >= EXCLUDE_BELOW * med}


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The three compared numbers of a program (or control) reading
    against the reference's."""
    g_ref = ref["first_grad"]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                    ref["losses"])),
        "grad_gap": worst_tensor(prog["first_grad"], g_ref),
        "change_gap": worst_tensor(prog["change"], ref["change"],
                                   moved(ref)),
    }


def worst_names(prog: Dict, ref: Dict) -> Dict[str, str]:
    """The tensor that sets each per-tensor number."""
    g = tensor_gaps(prog["first_grad"], ref["first_grad"])
    c = tensor_gaps(prog["change"], ref["change"], moved(ref))
    return {"grad_gap": max(g, key=g.get), "change_gap": max(c, key=c.get)}


def verdict(nums: Dict[str, float], lim: Dict[str, float]) -> bool:
    """Every number that has a limit is finite and within it."""
    return all(np.isfinite(nums[k]) and nums[k] <= lim[k] for k in lim)
