"""One run of one benchmark cell (see ``bench/run.py``)."""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_out" / "trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ spec
def load_spec(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: Dict, workload: str, root: Path = ROOT) -> Dict:
    """The cell ``workload`` with its configuration file, traffic mix and
    the metrics it reports, all found by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" /
                      f"{cell['traffic']}.json").read_text())

    def here(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if here(m)]
    shown = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if here(m) and m["moves"] in shown]
    return {"cell": cell, "config": config, "mix": mix, "end_to_end": e2e,
            "per_layer": per_layer}


# --------------------------------------------------------------- devices
def chips_for(n: int, allow_cpu: bool = False):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devs)}")
    return devs[:n]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` places it."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Times at which XLA executables were fetched (compiled or read from
    the persistent cache), and at which the cache served one."""

    def __init__(self):
        import jax
        self.fetches: List[float] = []
        self.hits: List[float] = []

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.fetches.append(time.perf_counter())

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits.append(time.perf_counter())
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, lo: float, hi: float) -> str:
        f = sum(lo <= t < hi for t in self.fetches)
        h = sum(lo <= t < hi for t in self.hits)
        return f"{f} ({f - h} compiled, {h} from the cache)"


# ------------------------------------------------------------------- run
def run_cell(r: Dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, limits: Dict[str, float], chips: int,
             allow_cpu: bool = False, log=sys.stderr,
             keep: Optional[Dict] = None) -> Dict:
    """Drive the trainer through warm-up and the window, read memory,
    compare with the reference, reduce the trace.  Returns the result
    line's object; ``keep``, when given, receives what the comparison
    used (for ``bench/tools/calibrate.py``)."""
    import jax
    import numpy as np

    from harness import compare, program, readings, window, xplane
    from harness.peaks import peaks
    from harness.traffic import Traffic, seed32
    from repro.data.pipeline import PipelineConfig
    from repro.obs import trace as obs_trace
    from repro.train.trainer import TrainConfig, train

    devices = chips_for(chips, allow_cpu)
    kind = devices[0].device_kind
    pk = peaks("TPU v5 lite" if allow_cpu else kind)
    c, mix = r["config"], r["mix"]
    t = c["train"]
    mc = program.model_config(c)
    ref = compare.reference_module(c)
    program.check_layout(mc, ref.shapes(c))
    counter = CompileCounter()

    key = jax.random.PRNGKey(seed32(seed, 3))
    traffic = Traffic(mix, ranks=chips, vocab=c["vocab_size"], seed=seed)
    pipe = PipelineConfig(distribution=mix["distribution"],
                          max_doc_len=int(mix["max_doc_len"]),
                          seq_len=traffic.seq_len,
                          global_batch=traffic.rows, n_ranks=chips,
                          vocab_size=c["vocab_size"])
    mesh = rules = None
    if chips > 1:
        from repro.launch.mesh import make_data_mesh
        from repro.parallel import make_rules
        mesh = make_data_mesh(devices)
        rules = make_rules(mesh, mc)

    hold = {"params": jax.jit(lambda k: program.to_program(
        ref.init(c, k), mc.pdtype))(key)}

    def first_update(opt_state):
        return {"first_grad": compare.program_first_grad(
                    program.from_program(opt_state.mu), t["b1"]),
                "opt_step": int(opt_state.step)}

    def third_update(params, history):
        return {"losses": [h["loss"] for h in history[:window.WARM_STEPS]],
                "change": compare.program_change(
                    ref, c, program.from_program(params), key)}

    profile = {}

    def on_open():
        if trace:
            obs_trace.enable_tracing()
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            profile["span"] = jax.profiler.TraceAnnotation("bench.window")
            profile["span"].__enter__()

    def on_close():
        if trace:
            profile["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            profile["spans"] = [e for e in obs_trace.get_recorder().events()
                                if e.ts >= win.t_open]
            obs_trace.disable_tracing()

    win = window.Window(traffic.stream(), seconds,
                        on_first_update=first_update,
                        on_third_update=third_update,
                        on_open=on_open, on_close=on_close)
    session = window.WindowedSession.for_pipeline(
        mc, pipe, kernel="pallas", pingpong=True, prefetch=2, mesh=mesh,
        rules=rules)
    session = dataclasses.replace(session, window=win)
    tc = TrainConfig(steps=int(t["total_steps"]), peak_lr=t["peak_lr"],
                     warmup=int(t["warmup"]),
                     weight_decay=t["weight_decay"], log_every=1, seed=0)
    try:
        train(mc, pipe, tc, params=hold.pop("params"), session=session)
        raise RuntimeError("the trainer stopped before the window closed")
    except window.WindowClosed:
        pass
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(st.get("peak_bytes_in_use", 0)) for st in stats)
    print(f"memory_stats after the window: {stats[0]}", file=log, flush=True)
    gc.collect()

    window_losses = win.losses[window.WARM_STEPS:]
    failed = int(sum(not np.isfinite(x) for x in window_losses))
    tokens = sum(int((b["labels"] >= 0).sum()) for b in win.window_batches)
    metrics = {
        "tokens_per_s_per_chip": {"value": tokens / win.window_s / chips,
                                  "unit": "tokens/s"},
        "peak_hbm_gib": {"value": peak / 2 ** 30, "unit": "GiB"},
        "setup_s": {"value": win.t_open - t_start, "unit": "s"},
    }
    print(f"window: {win.steps} steps in {win.window_s!r} s, {tokens} "
          f"label-bearing tokens; set-up {win.t_open - t_start!r} s; "
          f"executables fetched before the window: "
          f"{counter.between(0.0, win.t_open)}, inside it: "
          f"{counter.between(win.t_open, win.t_close)}",
          file=log, flush=True)

    # ---------------------------------------------------- correctness
    prog = {"losses": win.readings["losses"],
            "first_grad": win.readings["first_grad"],
            "change": win.readings["change"]}
    t_ref = time.perf_counter()
    ref_read = compare.reference_readings(ref, c, key, win.warm_batches)
    nums = compare.numbers(prog, ref_read)
    if keep is not None:
        keep.update(ref=ref, config=c, key=key, batches=win.warm_batches,
                    reference=ref_read, program=prog)
    ok = compare.verdict(nums, limits) and failed == 0 \
        and win.readings.get("opt_step") == 1
    print(f"reference: {time.perf_counter() - t_ref!r} s; program losses "
          f"{prog['losses']}, reference losses {ref_read['losses']}; "
          f"worst tensors {compare.worst_names(prog, ref_read)}; "
          f"not compared: {({k: v for k, v in nums.items() if k not in limits})}",
          file=log, flush=True)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(ok), "attempted": win.steps, "failed": failed}

    if trace:
        tr = xplane.load(str(next(TRACE_DIR.rglob("*.xplane.pb"))))
        span = xplane.host_span(tr, "bench.window")
        lo, hi = span
        ctx = readings.Context(
            config=c, peaks=pk, chips=chips, n_nano=2, steps=win.steps,
            window_s=win.window_s, batches=win.window_batches, trace=tr,
            window_ps=span, spans=profile.get("spans", []))
        values = {}
        for m in r["per_layer"]:
            v = readings.reader(m["name"]).read(ctx)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = values
        busy = [xplane.busy(d, lo, hi) for d in tr.devices]
        device["busy_s"] = sum(busy) / len(busy) / 1e12 if busy else 0.0
        device["window_s"] = (hi - lo) / 1e12
        out["device"] = device
        out["breakdown"] = {"device_ops": xplane.top_ops(tr, lo, hi),
                            "idle_gaps": xplane.labelled_gaps(tr, lo, hi)}
    else:
        out["metrics"] = {m["name"]: metrics[m["name"]]
                          for m in r["end_to_end"]}
        out["device"] = device
    out["checks"] = {k: {"value": nums[k], "limit": limits[k]}
                     for k in compare.NUMBERS if k in limits}
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is not beside the benchmark ({e})",
              file=sys.stderr)
        return 2
    r = resolve(load_spec(), args.workload)
    from harness import compare
    use_compile_cache()
    try:
        out = run_cell(r, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start,
                       limits=compare.limits(args.workload),
                       chips=int(r["cell"]["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
