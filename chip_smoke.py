#!/usr/bin/env python3
"""Bring-up smoke: the CAD training step on TPU, through the entry points
``launch/train.py`` uses.

  python chip_smoke.py               one chip: full-width smollm-360m,
                                     CAD on two simulated attention
                                     servers, compiled Pallas kernels
  python chip_smoke.py --four-chips  four chips: the shard_map CAD
                                     dispatch (one attention server per
                                     chip) against colocated attention

Everything runs in this one process, which owns the chips; it starts no
child process.  The script exits nonzero, and prints no result line, when
JAX's first device is not a TPU or when any check fails.  On success the
last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The readings it prints on the way are bring-up readings, not benchmark
results.  ``JAX_COMPILATION_CACHE_DIR`` places the compilation cache
(default ``.jax_cache/`` in the checkout).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ARCH = "smollm-360m"
SEQ = 4096
STEPS = 5
SEED = 0
# |step-0 loss, CAD - colocated|.  Every path computes in bf16 and the
# paths order the attention sums differently, so they agree to bf16
# rounding carried through 32 layers, not bit for bit; 0.02 is about
# 0.2% of the ~10.8 nats of a random-init 49152-way softmax.
LOSS_TOL = 0.02
# the smoke must leave these as it found them (the chip machine sets
# LIBTPU_INIT_ARGS itself)
ENV_KEPT = ("XLA_FLAGS", "LIBTPU_INIT_ARGS", "ALLOW_MULTIPLE_LIBTPU_LOAD")


class SmokeFailure(RuntimeError):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def _compile_seconds():
    """A list that collects every XLA backend compile's duration."""
    import jax
    seconds = []

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seconds.append(duration)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seconds


def _first_batches(session, pipe):
    """The first pipeline batch, without and with its CAD plan — the batch
    ``train`` takes first, since the pipeline is seeded."""
    from repro.data.pipeline import raw_batches
    batches = raw_batches(pipe)
    raw = next(batches)
    batches.close()
    planned = session.plan_batch(raw)
    planned.pop("schedule_stats")
    return raw, planned


def _colocated_loss(cfg, params, batch, impl, mesh=None, rules=None):
    import jax
    from repro.parallel import ParallelContext, ShardingRules
    from repro.train.step import make_eval_step
    ctx = ParallelContext(mesh=mesh, rules=rules or ShardingRules(),
                          attn_impl=impl, remat=True)
    return float(jax.jit(make_eval_step(cfg, ctx))(params, batch)["loss"])


def run_cad_training(arch, *, seq, batch, ranks, steps, mesh=None,
                     colocated=("pallas", "xla")):
    """Train ``arch`` for ``steps`` steps with CAD (Pallas kernels,
    ping-pong, plan prefetch) and check it.  Returns the readings and the
    lowered text of the step that ran."""
    import jax
    import numpy as np
    from repro.cad import CADSession
    from repro.configs import get_config
    from repro.data.pipeline import PipelineConfig
    from repro.models import model as M
    from repro.parallel import make_rules
    from repro.train.trainer import TrainConfig, train

    compiles = _compile_seconds()
    cfg = get_config(arch)
    pipe = PipelineConfig(distribution="pretrain", max_doc_len=seq,
                          seq_len=seq, global_batch=batch, n_ranks=ranks,
                          vocab_size=cfg.vocab_size)
    rules = make_rules(mesh, cfg) if mesh is not None else None
    session = CADSession.for_pipeline(cfg, pipe, kernel="pallas",
                                      pingpong=True, prefetch=2, mesh=mesh,
                                      rules=rules)
    raw, planned = _first_batches(session, pipe)
    key = jax.random.PRNGKey(SEED)

    # colocated references on the same parameters and first batch, run
    # before training so their buffers are gone when the step peaks
    params = M.init(key, cfg)
    ref_loss = {impl: _colocated_loss(cfg, params, raw, impl, mesh, rules)
                for impl in colocated}
    del params
    n_ref_compiles = len(compiles)

    tc = TrainConfig(steps=steps, peak_lr=3e-4, warmup=1, log_every=1,
                     seed=SEED)
    # params re-made from the same key, so train() holds the only copy
    res = train(cfg, pipe, tc, params=M.init(key, cfg), session=session)
    hist = res["history"]
    losses = [h["loss"] for h in hist]
    wall = [h["wall_s"] for h in hist]
    steady = float(np.median(np.diff(wall))) if len(wall) > 1 else None
    lowered = res["step_fn"].lower(res["params"], res["opt_state"], planned)

    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    deltas = {impl: abs(losses[0] - loss) for impl, loss in ref_loss.items()}
    for impl, d in deltas.items():
        check(d <= LOSS_TOL, f"step-0 loss {losses[0]!r} vs colocated "
              f"{impl} {ref_loss[impl]!r}: |delta| {d!r} > {LOSS_TOL}")
    readings = {
        "arch": arch, "seq": seq, "global_batch": batch, "ranks": ranks,
        "tokens_per_step": batch * seq,
        "real_tokens_step0": int(hist[0]["n_tokens"]),
        "losses": losses,
        "colocated_step0_loss": ref_loss,
        "step0_loss_abs_delta": deltas,
        "loss_tol": LOSS_TOL,
        # the step is the one large program train() compiles
        "step_compile_s": max(compiles[n_ref_compiles:]),
        "compile_s_total": sum(compiles),
        "first_step_s": wall[0],
        "steady_step_s": steady,
        "step_wall_s": wall,
    }
    return readings, lowered


def one_chip(devices):
    readings, lowered = run_cad_training(ARCH, seq=SEQ, batch=4, ranks=2,
                                         steps=STEPS)
    check("tpu_custom_call" in lowered.as_text(),
          "no tpu_custom_call in the step: Pallas did not compile")
    readings["peak_bytes_in_use"] = \
        devices[0].memory_stats()["peak_bytes_in_use"]
    return readings


def four_chips(devices):
    from repro.launch.mesh import make_data_mesh
    check(len(devices) == 4, f"--four-chips needs 4 devices, "
          f"found {len(devices)}")
    mesh = make_data_mesh(devices)
    # 8 rows over 4 ranks: 2 rows per rank, one per ping-pong nano-batch;
    # GSPMD partitions the colocated blockwise-jnp attention by rows
    readings, lowered = run_cad_training(ARCH, seq=SEQ, batch=8, ranks=4,
                                         steps=STEPS, mesh=mesh,
                                         colocated=("xla",))
    hlo = lowered.compile().as_text()
    check("all-to-all" in hlo, "no all-to-all in the compiled step: the "
          "CAD dispatch did not take the shard_map path")
    check("tpu_custom_call" in hlo, "no tpu_custom_call in the step")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    check(min(peaks) > 2 ** 28, f"a device held under 256 MiB at peak: "
          f"{peaks}")
    readings["peak_bytes_in_use"] = peaks
    readings["all_to_all_mentions"] = hlo.count("all-to-all")
    return readings


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip shard_map dispatch phase")
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    sys.path.insert(0, str(src))
    try:
        from repro.launch import mesh
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError:
        mesh = None
    if mesh is None or src not in Path(mesh.__file__).resolve().parents:
        print(f"chip_smoke: no repro package beside this script, under "
              f"{src}", file=sys.stderr)
        return 2
    import jax

    env_before = {k: os.environ.get(k) for k in ENV_KEPT}
    device = mesh.device_summary()
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}); this smoke only runs on a chip",
              file=sys.stderr)
        return 1
    print(f"device: {json.dumps(device)}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    t0 = time.time()
    phase = four_chips if args.four_chips else one_chip
    try:
        readings = phase(jax.devices())
        check({k: os.environ.get(k) for k in ENV_KEPT} == env_before,
              f"the environment changed under the smoke: {ENV_KEPT}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    readings["phase"] = phase.__name__
    readings["wall_s"] = time.time() - t0
    print(f"readings: {json.dumps(readings)}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
