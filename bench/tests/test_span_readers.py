"""The readers of the program's step spans on synthetic spans:
``fetch_ms`` and ``dispatch_ms`` take the median span, the grid shares
sum the plan's counts that the ``train.dispatch`` spans carry, and each
reads nothing (None) where the program recorded no such span, as a
program without these spans does."""
import pytest

from harness import readings
from repro.obs.trace import TraceEvent


def ctx(spans):
    return readings.Context(config={}, ref=None, peaks={}, chips=1,
                            n_nano=2, steps=3, window_s=1.0, batches=[],
                            spans=spans)


def dispatch(step, fwd, fwd_live, dkv, dkv_live, dur=0.008):
    return TraceEvent("X", "train.dispatch", "step", float(step), dur,
                      step=step,
                      args={"ca_fwd_cells": fwd, "ca_fwd_cells_live": fwd_live,
                            "ca_dkv_cells": dkv, "ca_dkv_cells_live": dkv_live})


def plan(d):
    return TraceEvent("X", "plan.build", "planner", 0.0, d)


@pytest.mark.parametrize("metric,name", [("fetch_ms", "train.fetch"),
                                         ("dispatch_ms", "train.dispatch")])
def test_step_span_medians(metric, name):
    spans = [TraceEvent("X", name, "step", 0.0, d, step=i)
             for i, d in enumerate((0.009, 0.007, 0.0002))] + [plan(1.0)]
    assert readings.reader(metric).read(ctx(spans)) == pytest.approx(7.0)


def test_grid_shares_sum_the_window_steps():
    spans = [dispatch(0, 100, 10, 400, 10), dispatch(1, 100, 30, 400, 30),
             plan(0.002)]
    fwd = readings.reader("ca_fwd_grid_live_pct").read(ctx(spans))
    dkv = readings.reader("ca_dkv_grid_live_pct").read(ctx(spans))
    assert fwd == pytest.approx(20.0) and dkv == pytest.approx(5.0)


def test_grid_shares_skip_dispatch_spans_without_counts():
    bare = TraceEvent("X", "train.dispatch", "step", 0.0, 0.008, step=0)
    spans = [bare, dispatch(1, 100, 25, 400, 25)]
    assert readings.reader("ca_fwd_grid_live_pct").read(ctx(spans)) == \
        pytest.approx(25.0)


@pytest.mark.parametrize("metric", ["fetch_ms", "dispatch_ms",
                                    "ca_fwd_grid_live_pct",
                                    "ca_dkv_grid_live_pct"])
def test_no_span_reads_nothing(metric):
    bare = TraceEvent("X", "train.dispatch", "step", 0.0, 0.008, step=0)
    assert readings.reader(metric).read(ctx([])) is None
    assert readings.reader(metric).read(ctx([plan(0.002)])) is None
    if metric.startswith("ca_"):
        assert readings.reader(metric).read(ctx([bare])) is None
