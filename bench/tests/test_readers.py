"""The per-layer readers on the trace recorded on a TPU v5e (see
``test_xplane``): one CA-server forward call and its two backward passes
per step, three steps, over a 384-token and a 128-token document with
2 q heads, 1 kv head of 64."""
from pathlib import Path

import numpy as np
import pytest

from harness import readings, xplane
from harness.peaks import peaks

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
CONFIG = {"num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 64,
          "num_hidden_layers": 1, "hidden_size": 128,
          "intermediate_size": 256, "vocab_size": 256}


def batch():
    seg = np.array([[1] * 384 + [2] * 128])
    pos = np.array([list(range(384)) + list(range(128))])
    return {"segment_ids": seg, "positions": pos,
            "labels": np.where(seg > 0, 1, -1)}


def ctx(**over):
    tr = xplane.load(str(DATA))
    lo = min(o.start for o in tr.devices[0].ops)
    hi = xplane.host_span(tr, "host.iter2")[1]
    kw = dict(config=CONFIG, peaks=peaks("TPU v5 lite"), chips=1, n_nano=1,
              steps=3, window_s=(hi - lo) / 1e12, batches=[batch()] * 3,
              trace=tr, window_ps=(lo, hi))
    kw.update(over)
    return readings.Context(**kw)


@pytest.mark.parametrize("metric", ["ca_fwd_roofline", "ca_bwd_roofline",
                                    "device_idle_pct", "step_mfu"])
def test_shares_are_percentages_of_something(metric):
    v = readings.reader(metric).read(ctx())
    assert v is not None and 0 < v < 100


def test_roofline_is_missing_not_zero_when_calls_do_not_divide():
    assert readings.reader("ca_fwd_roofline").read(ctx(steps=2)) is None
    assert readings.reader("ca_fwd_roofline").read(ctx(trace=None)) is None


def test_plan_ms_reads_the_program_spans():
    from repro.obs.trace import TraceEvent
    spans = [TraceEvent("X", "plan.build", "planner", 0.0, d)
             for d in (0.001, 0.003, 0.002)]
    assert readings.reader("plan_ms").read(ctx(spans=spans)) == \
        pytest.approx(2.0)
    assert readings.reader("plan_ms").read(ctx(spans=[])) is None
