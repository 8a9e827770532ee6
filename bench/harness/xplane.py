"""Reduction of a profiler trace (``.xplane.pb``) to device intervals.

The XSpace protocol buffer is read with a descriptor built here (field
numbers of ``tsl/profiler/protobuf/xplane.proto``), so nothing beyond
``protobuf`` is needed.  Every time is in picoseconds from the start of
the profile, the common timebase of host and device lines.

Per device plane (``/device:TPU:<n>``) the "XLA Ops" line holds nested
events (a ``while`` contains its body's ops).  From it:

* busy time is the union of all op intervals, never their sum;
* op time is summed over leaf events only (events that contain no other
  event), so that nothing is counted twice;
* a Pallas kernel is a custom call whose op name ends in
  ``pallas_call``.  Its name is the innermost ``jax.named_scope`` of
  its op name where the program gives one; the program gives none
  today, and its kernels' own names do not reach the trace, so the
  kernel is then named by its role in the step: ``pallas_fwd`` for a
  call of the forward pass (also when rematerialised in the backward)
  and ``pallas_bwd`` for a call of the backward pass;
* collective time not covered by compute is the part of the collective
  events' union that no other op's interval covers.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

Interval = Tuple[int, int]


def _messages():
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    T = descriptor_pb2.FieldDescriptorProto
    rep, opt = T.LABEL_REPEATED, T.LABEL_OPTIONAL

    def msg(name, fields):
        m = f.message_type.add(name=name) if isinstance(name, str) else name
        for fname, num, ftype, label, tname in fields:
            fd = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                fd.type_name = tname
        return m

    msg("XSpace", [("planes", 1, T.TYPE_MESSAGE, rep, ".bench_xplane.XPlane")])
    plane = msg("XPlane", [
        ("id", 1, T.TYPE_INT64, opt, None),
        ("name", 2, T.TYPE_STRING, opt, None),
        ("lines", 3, T.TYPE_MESSAGE, rep, ".bench_xplane.XLine"),
        ("event_metadata", 4, T.TYPE_MESSAGE, rep,
         ".bench_xplane.XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, T.TYPE_MESSAGE, rep,
         ".bench_xplane.XPlane.StatMetadataEntry"),
        ("stats", 6, T.TYPE_MESSAGE, rep, ".bench_xplane.XStat")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = plane.nested_type.add(name=entry)
        e.options.map_entry = True
        msg(e, [("key", 1, T.TYPE_INT64, opt, None),
                ("value", 2, T.TYPE_MESSAGE, opt, f".bench_xplane.{value}")])
    msg("XLine", [
        ("id", 1, T.TYPE_INT64, opt, None),
        ("name", 2, T.TYPE_STRING, opt, None),
        ("timestamp_ns", 3, T.TYPE_INT64, opt, None),
        ("events", 4, T.TYPE_MESSAGE, rep, ".bench_xplane.XEvent"),
        ("duration_ps", 9, T.TYPE_INT64, opt, None),
        ("display_id", 10, T.TYPE_INT64, opt, None),
        ("display_name", 11, T.TYPE_STRING, opt, None)])
    msg("XEvent", [
        ("metadata_id", 1, T.TYPE_INT64, opt, None),
        ("offset_ps", 2, T.TYPE_INT64, opt, None),
        ("duration_ps", 3, T.TYPE_INT64, opt, None),
        ("stats", 4, T.TYPE_MESSAGE, rep, ".bench_xplane.XStat"),
        ("num_occurrences", 5, T.TYPE_INT64, opt, None)])
    msg("XStat", [
        ("metadata_id", 1, T.TYPE_INT64, opt, None),
        ("double_value", 2, T.TYPE_DOUBLE, opt, None),
        ("uint64_value", 3, T.TYPE_UINT64, opt, None),
        ("int64_value", 4, T.TYPE_INT64, opt, None),
        ("str_value", 5, T.TYPE_STRING, opt, None),
        ("bytes_value", 6, T.TYPE_BYTES, opt, None),
        ("ref_value", 7, T.TYPE_UINT64, opt, None)])
    msg("XEventMetadata", [
        ("id", 1, T.TYPE_INT64, opt, None),
        ("name", 2, T.TYPE_STRING, opt, None),
        ("metadata", 3, T.TYPE_BYTES, opt, None),
        ("display_name", 4, T.TYPE_STRING, opt, None),
        ("stats", 5, T.TYPE_MESSAGE, rep, ".bench_xplane.XStat"),
        ("child_id", 6, T.TYPE_INT64, rep, None)])
    msg("XStatMetadata", [
        ("id", 1, T.TYPE_INT64, opt, None),
        ("name", 2, T.TYPE_STRING, opt, None),
        ("description", 3, T.TYPE_STRING, opt, None)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


XSpace = _messages()


@dataclass
class Op:
    start: int                    # ps from the profile's start
    end: int
    name: str                     # HLO instruction name, e.g. fusion.12
    kernel: Optional[str] = None  # Pallas kernel name (``kernel_name``)
    leaf: bool = True


@dataclass
class Device:
    name: str
    ops: List[Op] = field(default_factory=list)


@dataclass
class HostEvent:
    start: int
    end: int
    name: str
    thread: str


@dataclass
class Trace:
    devices: List[Device]
    host: List[HostEvent]


def _stats(stats, names: Dict[int, str]):
    """{stat name: value}; a reference value names a stat metadata."""
    out = {}
    for s in stats:
        key = names.get(s.metadata_id, "")
        val = None
        if s.str_value:
            val = s.str_value
        elif s.ref_value:
            val = names.get(s.ref_value)
        elif s.int64_value:
            val = s.int64_value
        elif s.uint64_value:
            val = s.uint64_value
        elif s.double_value:
            val = s.double_value
        out[key] = val
    return out


def kernel_name(tf_op: str) -> Optional[str]:
    """Name of the Pallas kernel of an op, from its op name (the JAX
    name stack), or None for an op that is no Pallas call."""
    parts = tf_op.rstrip(":").split("/")
    if not parts or parts[-1] != "pallas_call":
        return None
    scopes = [p for p in parts[1:-1] if re.fullmatch(r"[A-Za-z_]\w*", p)
              and p not in ("while", "body", "cond", "closed_call",
                            "checkpoint", "rematted_computation",
                            "shard_map", "remat")]
    if scopes:
        return scopes[-1]
    backward = "transpose(" in tf_op and "rematted_computation" not in tf_op
    return "pallas_bwd" if backward else "pallas_fwd"


def _mark_leaves(ops: List[Op]) -> None:
    """An op is a leaf when no other op lies inside its interval."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for o in ops:
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].leaf = False
        stack.append(o)


def parse(data: bytes) -> Trace:
    xs = XSpace()
    xs.ParseFromString(data)
    devices, host = [], []
    for pl in xs.planes:
        names = {k: v.name for k, v in pl.stat_metadata.items()}
        if pl.name.startswith("/device:TPU:") and not pl.name.endswith(
                "Megascale Trace"):
            meta = {}
            for k, md in pl.event_metadata.items():
                st = _stats(md.stats, names)
                meta[k] = (md.display_name or md.name,
                           kernel_name(str(st.get("tf_op") or "")))
            dev = Device(pl.name)
            for ln in pl.lines:
                if ln.name != "XLA Ops":
                    continue
                base = ln.timestamp_ns * 1000
                for e in ln.events:
                    name, kernel = meta.get(e.metadata_id, ("?", None))
                    s = base + e.offset_ps
                    dev.ops.append(Op(s, s + e.duration_ps, name, kernel))
            _mark_leaves(dev.ops)
            devices.append(dev)
        elif pl.name == "/host:CPU":
            md = {k: v.name for k, v in pl.event_metadata.items()}
            for ln in pl.lines:
                base = ln.timestamp_ns * 1000
                for e in ln.events:
                    s = base + e.offset_ps
                    host.append(HostEvent(s, s + e.duration_ps,
                                          md.get(e.metadata_id, "?"),
                                          ln.name))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return Trace(devices, host)


def load(path: str) -> Trace:
    with open(path, "rb") as f:
        return parse(f.read())


# ------------------------------------------------------------ intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of union ``a`` that union ``b`` does not cover."""
    out: List[Interval] = []
    starts = [s for s, _ in b]
    for s, e in a:
        cur = s
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(b) and b[i][0] < e:
            bs, be = b[i]
            if be > cur:
                if bs > cur:
                    out.append((cur, min(bs, e)))
                cur = max(cur, be)
            if cur >= e:
                break
            i += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy(dev: Device, lo: int, hi: int) -> int:
    return total(clip(union((o.start, o.end) for o in dev.ops), lo, hi))


def kernel_time(dev: Device, kernel: str, lo: int, hi: int) -> Tuple[int, int]:
    """(summed ps, number of events) of one Pallas kernel's calls."""
    ivs = [(o.start, o.end) for o in dev.ops
           if o.kernel == kernel and o.start >= lo and o.end <= hi]
    return total(ivs), len(ivs)


def is_collective(op: Op) -> bool:
    return bool(re.search(r"all-to-all|all-reduce|all-gather|"
                          r"collective-permute|reduce-scatter", op.name))


def exposed_collective(dev: Device, lo: int, hi: int,
                       pattern: str = "all-to-all") -> int:
    """ps of ``pattern`` collectives during which no other op runs."""
    coll = union((o.start, o.end) for o in dev.ops
                 if re.search(pattern, o.name))
    other = union((o.start, o.end) for o in dev.ops
                  if o.leaf and not is_collective(o))
    return total(clip(subtract(coll, other), lo, hi))


def gaps(dev: Device, lo: int, hi: int) -> List[Interval]:
    """Idle intervals of a device inside [lo, hi]."""
    b = clip(union((o.start, o.end) for o in dev.ops), lo, hi)
    return subtract([(lo, hi)], b)


def op_group(op: Op) -> str:
    """A stable name for summing ops: the kernel's function for a Pallas
    call, otherwise the HLO name without its instance number."""
    if op.kernel:
        return op.kernel
    return re.sub(r"[.\d]+$", "", op.name.lstrip("%").split(" ")[0]) or "?"


def top_ops(trace: Trace, lo: int, hi: int, n: int = 10):
    """[name, seconds] of the leaf ops that took most device time, summed
    over devices."""
    acc: Dict[str, int] = {}
    for dev in trace.devices:
        for o in dev.ops:
            if o.leaf and o.start >= lo and o.end <= hi:
                k = op_group(o)
                acc[k] = acc.get(k, 0) + (o.end - o.start)
    return [[k, v / 1e12] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


GENERIC_HOST = re.compile(r"^(\$<unknown>|ThreadpoolListener|Release|"
                          r"MemoryDeallocation)")


def labelled_gaps(trace: Trace, lo: int, hi: int, n: int = 10):
    """[label, seconds] of the longest idle gaps (over all devices), each
    labelled with the host events that overlap it most."""
    out = []
    for dev in trace.devices:
        for s, e in gaps(dev, lo, hi):
            over: Dict[str, int] = {}
            for h in trace.host:
                ov = min(e, h.end) - max(s, h.start)
                if ov > 0 and not GENERIC_HOST.match(h.name):
                    over[h.name] = over.get(h.name, 0) + ov
            names = [k for k, _ in sorted(over.items(),
                                          key=lambda kv: -kv[1])[:3]]
            label = f"{dev.name.split('/')[-1]} idle: " + (
                " | ".join(names) if names else "no host event")
            out.append((e - s, label))
    out.sort(key=lambda t: -t[0])
    return [[label[:200], d / 1e12] for d, label in out[:n]]


def host_span(trace: Trace, name: str) -> Optional[Interval]:
    """The first host event named ``name`` (a ``TraceAnnotation``)."""
    for h in trace.host:
        if h.name == name:
            return (h.start, h.end)
    return None
