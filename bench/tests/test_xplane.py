"""Trace reduction: synthetic intervals, and a trace recorded on a TPU v5e
(``data/small.xplane.pb``: three steps of a small jitted function with one
CA-server forward Pallas call and its two backward passes)."""
from pathlib import Path

import pytest

from harness import xplane as X

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def dev(*ops):
    d = X.Device("/device:TPU:0", [X.Op(s, e, n, kernel=k)
                                   for s, e, n, k in ops])
    X._mark_leaves(d.ops)
    return d


def test_union_merges_overlaps_and_nesting():
    assert X.union([(0, 10), (2, 5), (10, 12), (20, 30)]) == \
        [(0, 12), (20, 30)]
    assert X.total(X.union([(0, 10), (2, 5)])) == 10


def test_subtract_and_clip():
    assert X.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert X.subtract([(0, 10)], []) == [(0, 10)]
    assert X.subtract([(0, 10)], [(0, 10)]) == []
    assert X.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_nested_ops_busy_is_a_union_and_leaves_sum_once():
    d = dev((0, 100, "while.1", None), (10, 40, "fusion.2", None),
            (50, 90, "pallas.3", "pallas_fwd"), (120, 130, "copy.4", None))
    assert X.busy(d, 0, 200) == 110                 # not 100+30+40+10
    assert [o.leaf for o in d.ops] == [False, True, True, True]
    tr = X.Trace([d], [])
    top = dict(X.top_ops(tr, 0, 200))
    assert "while" not in top and top["pallas_fwd"] == pytest.approx(40e-12)
    assert X.kernel_time(d, "pallas_fwd", 0, 200) == (40, 1)
    assert X.gaps(d, 0, 200) == [(100, 120), (130, 200)]


def test_exposed_collective_is_what_compute_leaves_uncovered():
    d = dev((0, 100, "%all-to-all.1 = all-to-all(...)", None),
            (20, 50, "fusion.1", None), (80, 120, "fusion.2", None))
    assert X.exposed_collective(d, 0, 200) == 20 + 30


def test_idle_gaps_are_labelled_with_overlapping_host_events():
    d = dev((0, 10, "fusion.1", None), (50, 60, "fusion.2", None))
    tr = X.Trace([d], [X.HostEvent(5, 55, "plan.build", "python"),
                       X.HostEvent(0, 100, "$<unknown> __exit__", "python")])
    (label, sec), = X.labelled_gaps(tr, 0, 60)
    assert "plan.build" in label and "unknown" not in label
    assert sec == pytest.approx(40e-12)


@pytest.mark.parametrize("tf_op,name", [
    ("jit(step)/jvp()/while/body/closed_call/vmap()/pallas_call:",
     "pallas_fwd"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/vmap()/pallas_call:", "pallas_fwd"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/vmap()/"
     "pallas_call:", "pallas_bwd"),
    ("jit(step)/jvp()/ca_server_fwd/pallas_call:", "ca_server_fwd"),
    ("jit(step)/jvp()/dot_general:", None),
])
def test_kernel_names(tf_op, name):
    assert X.kernel_name(tf_op) == name


def test_recorded_chip_trace():
    tr = X.load(str(DATA))
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    d = tr.devices[0]
    # the device's clock in this trace runs about 0.75 ms ahead of the
    # host's: take the window from the first device op
    lo = min(o.start for o in d.ops)
    hi = X.host_span(tr, "host.iter2")[1]
    assert X.kernel_time(d, "pallas_fwd", lo, hi)[1] == 3
    assert X.kernel_time(d, "pallas_bwd", lo, hi)[1] == 6
    busy = X.busy(d, lo, hi)
    assert 0 < busy < hi - lo
    ops = dict(X.top_ops(tr, lo, hi))
    assert ops["pallas_bwd"] > ops["pallas_fwd"] > 0
    gaps = X.labelled_gaps(tr, lo, hi)
    assert gaps and all(s > 0 for _, s in gaps)
    assert sum(s for _, s in X.labelled_gaps(tr, lo, hi, n=10 ** 6)) == \
        pytest.approx((hi - lo - busy) / 1e12)
