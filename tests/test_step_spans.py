"""The program's spans on the profiler's clock (DESIGN.md §14): a live
recorder's spans enter a ``jax.profiler.TraceAnnotation`` of the same
name, a disabled one never touches the profiler; the trainer narrates
each step's batch fetch and dispatch, with the plan's grid counts; the
planner and the prefetch worker tag their spans with the step they plan
for, and the worker sees a recorder switched on after it started."""
import threading

import jax
import numpy as np
import pytest

from repro.cad import CADConfig, CADSession, PlanPrefetcher
from repro.core.cost_model import CommModel
from repro.obs import (MetricsRegistry, TraceRecorder, enable_tracing,
                       get_recorder, get_registry, set_recorder,
                       set_registry)


@pytest.fixture(autouse=True)
def _isolate_globals():
    prev_rec, prev_reg = get_recorder(), get_registry()
    set_recorder(None)
    set_registry(MetricsRegistry())
    yield
    set_recorder(prev_rec)
    set_registry(prev_reg)


class FakeAnnotation:
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    return FakeAnnotation.log


def test_live_span_enters_a_trace_annotation_of_its_name(annotations):
    rec = TraceRecorder(capacity=8)
    with rec.span("plan.build", "planner", step=3, args={"policy": "x"}):
        assert annotations == [("enter", "plan.build")]
    assert annotations == [("enter", "plan.build"), ("exit", "plan.build")]
    (ev,) = rec.events()
    assert (ev.name, ev.step, ev.args) == ("plan.build", 3, {"policy": "x"})


def test_span_leaves_its_annotation_when_the_body_raises(annotations):
    rec = TraceRecorder(capacity=8)
    with pytest.raises(KeyError):
        with rec.span("train.fetch", "step"):
            raise KeyError("x")
    assert annotations == [("enter", "train.fetch"), ("exit", "train.fetch")]
    assert [e.name for e in rec.events()] == ["train.fetch"]


def test_disabled_span_never_calls_the_profiler(annotations):
    rec = TraceRecorder(capacity=8, enabled=False)
    with rec.span("plan.build", "planner"):
        pass
    rec.add_span("serve", "server/0", 0.0, 1.0)
    assert annotations == [] and len(rec) == 0


def test_explicit_spans_stay_off_the_profiler(annotations):
    rec = TraceRecorder(capacity=8)
    rec.add_span("serve", "server/0", 0.0, 1.0)
    assert annotations == [] and len(rec) == 1


def test_prefetch_worker_sees_a_recorder_enabled_after_it_started():
    release = threading.Event()

    def source():
        yield 0
        release.wait(5.0)
        yield from range(1, 4)

    pf = PlanPrefetcher(source(), lambda x: x, depth=1,
                        step_of=lambda x: x)
    try:
        assert next(pf) == 0
        live = enable_tracing(capacity=64)
        release.set()
        assert list(pf) == [1, 2, 3]
    finally:
        pf.close()
    assert [e.step for e in live.events() if e.name == "prefetch.plan"] \
        == [1, 2, 3]


@pytest.mark.parametrize("prefetch", [0, 2])
def test_plan_spans_carry_the_batch_index(prefetch):
    d, blk, nb = 2, 16, 4
    cfg = CADConfig(n_servers=d, blk=blk, nb=nb, cq=nb, ckv=2 * nb,
                    nkv=4 * nb)
    session = CADSession(cfg=cfg, kernel="xla", comm=CommModel(2, 8, 1),
                         jmax=nb, prefetch=prefetch)
    live = enable_tracing(capacity=256)
    segs = np.repeat(np.arange(1, d * nb + 1), blk).reshape(d, -1)
    out = list(session.attach_plans({"segment_ids": segs}
                                    for _ in range(4)))
    assert len(out) == 4
    names = ["plan.build"] + (["prefetch.plan"] if prefetch else [])
    for name in names:
        assert [e.step for e in live.events() if e.name == name] \
            == [0, 1, 2, 3]


def test_train_narrates_fetch_and_dispatch_per_step():
    from repro.configs import get_config
    from repro.data.pipeline import PipelineConfig
    from repro.train.trainer import TrainConfig, train
    cfg = get_config("smollm-360m").reduced()
    pipe = PipelineConfig(distribution="pretrain", max_doc_len=256,
                          seq_len=256, global_batch=4, n_ranks=2,
                          vocab_size=cfg.vocab_size, seed=3)
    session = CADSession.for_pipeline(cfg, pipe, plan_policy="balanced",
                                      pingpong=True)
    live = enable_tracing(capacity=4096)
    steps = 3
    res = train(cfg, pipe, TrainConfig(steps=steps, peak_lr=1e-3, warmup=1,
                                       log_every=1), session=session)
    evs = live.events()
    fetch = [e for e in evs if e.name == "train.fetch"]
    dispatch = [e for e in evs if e.name == "train.dispatch"]
    assert [e.step for e in fetch] == list(range(steps))
    assert [e.step for e in dispatch] == list(range(steps))
    assert {e.track for e in fetch + dispatch} == {"step"}
    for f, g, h in zip(fetch, dispatch, res["history"]):
        assert f.ts + f.dur <= g.ts
        args = g.args
        assert 0 < args["ca_fwd_cells_live"] < args["ca_fwd_cells"]
        assert 0 < args["ca_dkv_cells_live"] < args["ca_dkv_cells"]
        # the same counts reach the history as sched_grid
        assert h["sched_grid"] == args
    # a step's plan, fetch and dispatch share one id
    plans = [e.step for e in evs if e.name == "plan.build"]
    assert plans[:steps] == list(range(steps))
