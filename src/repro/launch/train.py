"""Production training launcher.

On real TPU hardware this runs the full mesh; on CPU it runs reduced
configs (the mesh flags are for the dry-run, see dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m-reduced \
      --steps 50 --seq 512 --batch 4 --ranks 2 --cad

Flags mirror the paper's system knobs: --cad (core attention
disaggregation on/off), --plan-policy (identity | per_doc_cp |
balanced | ring — the last is the DISTFLASHATTN-style context-parallel
baseline layout, DESIGN.md §13), --pingpong (nano-batch overlap),
--tolerance (scheduler
imbalance budget), --prefetch (async plan look-ahead; 0 = synchronous),
--strategy fixed|variable (packing baseline), --server-speeds
(heterogeneous pool: comma-separated per-rank speed factors, e.g.
"1,0.5" gives rank 1 half the FLOPs), --calibrate (runtime cost-model
calibration: per-server kernel timings are probed every
--calibrate-every steps and fed back so later batches are planned from
measured costs), --mask (attention task shape beyond dense causal:
"sliding:window=256,sink=16" or "dilated:rate=4" — planning prices
tasks by live blocks and the kernels apply the matching in-block mask,
DESIGN.md §12), --fault-schedule (elastic pool membership: a
deterministic FaultSchedule spec like "kill:1@5" or "flap:0@3+2,
slow:2x4@4-8" — killed/drained servers are excluded from subsequent
plans and flapped servers rejoin, DESIGN.md §9), --speculate-pct
(straggler-speculation percentile for the elastic executor paths).
"""
import argparse
import json

from repro.cad import CADSession, available_policies
from repro.configs import get_config
from repro.data.pipeline import PipelineConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import device_summary
from repro.obs import enable_tracing, get_recorder, get_registry
from repro.parallel import ParallelContext
from repro.train.trainer import TrainConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ranks", type=int, default=2)
    ap.add_argument("--max-doc", type=int, default=0)
    ap.add_argument("--dist", default="pretrain",
                    choices=["pretrain", "prolong"])
    ap.add_argument("--strategy", default="fixed",
                    choices=["fixed", "variable"])
    ap.add_argument("--cad", action="store_true")
    ap.add_argument("--plan-policy", default="balanced",
                    choices=list(available_policies()))
    ap.add_argument("--pingpong", action="store_true")
    ap.add_argument("--tolerance", type=float, default=0.1)
    ap.add_argument("--prefetch", type=int, default=2,
                    help="plan look-ahead depth (0 = synchronous)")
    ap.add_argument("--server-speeds", default="",
                    help="comma-separated per-rank speed factors "
                         "(heterogeneous pool), e.g. '1,0.5'")
    ap.add_argument("--server-hbm", default="",
                    help="comma-separated per-rank HBM budgets in "
                         "bytes; planning then treats endpoint memory "
                         "as a constraint next to modeled time")
    ap.add_argument("--stream-chunk", type=int, default=0,
                    help="kv blocks resident per streamed chunk; "
                         "lets dispatch serve tasks whose kv prefix "
                         "exceeds every --server-hbm budget (0 = off)")
    ap.add_argument("--mask", default="",
                    help="attention task shape (DESIGN.md §12): "
                         "'causal' (default), "
                         "'sliding:window=N[,sink=M]', "
                         "'dilated:rate=R'; live-block planning + "
                         "masked kernels")
    ap.add_argument("--calibrate", action="store_true",
                    help="runtime cost-model calibration: probe "
                         "per-server CA timings and replan from them")
    ap.add_argument("--calibrate-every", type=int, default=5,
                    help="steps between calibration probes")
    ap.add_argument("--fault-schedule", default="",
                    help="deterministic fault injection spec, e.g. "
                         "'kill:1@5' or 'flap:0@3+2,slow:2x4@4-8' "
                         "(elastic pool membership, DESIGN.md §9)")
    ap.add_argument("--speculate-pct", type=float, default=0.0,
                    help="straggler-speculation deadline percentile "
                         "(0 = off; task-level speculation runs in the "
                         "elastic executor)")
    ap.add_argument("--kernel", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "to this path (one track per attention server; "
                         "load in ui.perfetto.dev — DESIGN.md §14)")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="trace ring-buffer capacity (oldest events "
                         "are overwritten past it)")
    ap.add_argument("--metrics", default="",
                    help="write the metrics-registry JSON snapshot "
                         "(counters/gauges/histograms) to this path "
                         "at exit")
    args = ap.parse_args()
    print(f"device: {json.dumps(device_summary())}")
    enable_compile_cache()

    if args.trace:
        enable_tracing(capacity=args.trace_capacity)

    cfg = get_config(args.arch)
    print(f"arch={cfg.arch_id} params={cfg.n_params()/1e6:.1f}M "
          f"family={cfg.family}")
    pipe = PipelineConfig(
        distribution=args.dist, max_doc_len=args.max_doc or args.seq,
        seq_len=args.seq, global_batch=args.batch, n_ranks=args.ranks,
        vocab_size=cfg.vocab_size, strategy=args.strategy)
    speeds = None
    if args.server_speeds:
        speeds = tuple(float(s) for s in args.server_speeds.split(","))
        if len(speeds) != args.ranks:
            raise SystemExit(f"--server-speeds needs {args.ranks} "
                             f"entries, got {len(speeds)}")
    hbm = None
    if args.server_hbm:
        hbm = tuple(float(s) for s in args.server_hbm.split(","))
        if len(hbm) != args.ranks:
            raise SystemExit(f"--server-hbm needs {args.ranks} "
                             f"entries, got {len(hbm)}")
    session = None
    if args.cad and cfg.has_attention():
        session = CADSession.for_pipeline(
            cfg, pipe, kernel=args.kernel, pingpong=args.pingpong,
            tolerance=args.tolerance, plan_policy=args.plan_policy,
            prefetch=args.prefetch, server_speeds=speeds,
            server_hbm=hbm, stream_chunk=args.stream_chunk,
            calibrate=args.calibrate, mask=args.mask or None)
        ctx = None
    else:
        if args.cad:
            print(f"note: {cfg.arch_id} is attention-free; CAD is "
                  f"inapplicable (DESIGN.md §5) — training without it")
        if args.calibrate or speeds or args.fault_schedule or args.mask:
            print("note: --calibrate/--server-speeds/--fault-schedule/"
                  "--mask only apply to the CAD attention service — "
                  "ignored")
        ctx = ParallelContext(attn_impl="xla", remat=True)
    tc = TrainConfig(steps=args.steps, peak_lr=args.lr,
                     warmup=max(1, args.steps // 10),
                     log_every=max(1, args.steps // 20),
                     ckpt_every=args.ckpt_every,
                     ckpt_dir=args.ckpt_dir or "/tmp/repro_ckpt",
                     calibrate_every=args.calibrate_every
                     if args.calibrate else 0,
                     fault_schedule=args.fault_schedule
                     if session is not None else "",
                     speculate_pct=args.speculate_pct)
    res = train(cfg, pipe, tc, ctx=ctx, session=session)
    h = res["history"]
    print(f"done: loss {h[0]['loss']:.4f} -> {h[-1]['loss']:.4f}")
    if args.trace:
        rec = get_recorder()
        rec.save(args.trace)
        print(f"trace: {len(rec)} events -> {args.trace} "
              f"({rec.n_dropped} dropped)")
    if args.metrics:
        with open(args.metrics, "w") as f:
            json.dump(get_registry().to_dict(), f, indent=2)
        print(f"metrics: -> {args.metrics}")


if __name__ == "__main__":
    main()
