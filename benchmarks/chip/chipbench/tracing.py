"""Reduction of a profiler trace to device busy time, idle gaps, time per
named scope and time per kernel.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane. An event's name is the text of its HLO
instruction (``%fusion.462 = bf16[...] fusion(...), ...``) and its stats
hold only times, so the named-scope path an op was traced under is read
from the ``op_name`` metadata of the same instruction in the compiled
program's HLO text (:func:`op_names`). A fusion carries the path of its
root instruction. Control-flow ops (a ``while`` and the scan around it)
appear as events that enclose the ops they run; only the innermost
events (leaves) count as device work. Host spans are the events the
benchmark wrote with ``jax.profiler.TraceAnnotation`` on the host plane;
the profiler puts both on one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"',
    re.M)
_HLO_KERNEL = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = [^\n]*custom_call_target="tpu_custom_call"',
    re.M)


@dataclasses.dataclass(frozen=True)
class Op:
    name: str       # HLO instruction name, e.g. "fusion.462"
    start_ns: float
    dur_ns: float
    scope: str      # named-scope path the op was traced under ("" if none)
    device: int
    leaf: bool = True    # encloses no other op of its device
    kernel: bool = False  # a Pallas (Mosaic) kernel call

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: list            # [Op] of every device
    spans: list          # [Span] host spans written by the benchmark
    n_devices: int

    def span(self, name: str) -> Optional[Span]:
        hits = [s for s in self.spans if s.name == name]
        return max(hits, key=lambda s: s.dur_ns) if hits else None

    def ops_in(self, lo: float, hi: float) -> list:
        """Leaf ops that overlap [lo, hi]."""
        return [o for o in self.ops
                if o.leaf and o.end_ns > lo and o.start_ns < hi]


def find_xplane(trace_dir: str) -> str:
    hits = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    if len(hits) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {hits}")
    return hits[0]


@dataclasses.dataclass(frozen=True)
class HloIndex:
    scopes: dict      # instruction name -> op_name metadata (scope path)
    kernels: frozenset  # instruction names of Pallas kernel calls

    @classmethod
    def of(cls, hlo_text: str) -> "HloIndex":
        return cls({m.group(1): m.group(2)
                    for m in _HLO_OP_NAME.finditer(hlo_text)},
                   frozenset(m.group(1) for m in _HLO_KERNEL.finditer(hlo_text)))


def mark_leaves(ops: list) -> list:
    """The same ops, with ``leaf`` False for each that wholly encloses
    another op of its device (events of one device nest as a tree)."""
    out = []
    for dev in sorted({o.device for o in ops}):
        stack, mine = [], sorted((o for o in ops if o.device == dev),
                                 key=lambda o: (o.start_ns, -o.dur_ns))
        parent = set()
        for i, o in enumerate(mine):
            while stack and mine[stack[-1]].end_ns <= o.start_ns:
                stack.pop()
            if stack and mine[stack[-1]].end_ns >= o.end_ns:
                parent.add(stack[-1])
            stack.append(i)
        out += [dataclasses.replace(o, leaf=i not in parent)
                for i, o in enumerate(mine)]
    return out


def load(path: str, span_names: Iterable[str], hlo: HloIndex) -> Trace:
    """Device ops and the named host spans of one ``.xplane.pb``, the ops
    named and scoped by the compiled program's ``hlo``."""
    from jax.profiler import ProfileData

    wanted = set(span_names)
    ops, spans, devices = [], [], set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = int(plane.name[len(DEVICE_PLANE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices.add(dev)
                for ev in line.events:
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    ops.append(Op(name, float(ev.start_ns),
                                  float(ev.duration_ns),
                                  hlo.scopes.get(name, ""), dev,
                                  kernel=name in hlo.kernels))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        spans.append(Span(ev.name, float(ev.start_ns),
                                          float(ev.duration_ns)))
    return Trace(mark_leaves(ops), spans, len(devices))


def union_ns(intervals: Iterable[tuple]) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Busy time inside [lo, hi], averaged over the devices."""
    per_dev = {}
    for o in trace.ops_in(lo, hi):
        per_dev.setdefault(o.device, []).append(
            (max(o.start_ns, lo), min(o.end_ns, hi)))
    if not per_dev:
        return 0.0
    return sum(union_ns(v) for v in per_dev.values()) / max(
        trace.n_devices, len(per_dev))


def idle_gaps(trace: Trace, lo: float, hi: float, device: int = 0) -> list:
    """(start, end) of every stretch in [lo, hi] with no op on ``device``."""
    ivs = sorted((max(o.start_ns, lo), min(o.end_ns, hi))
                 for o in trace.ops_in(lo, hi) if o.device == device)
    gaps, at = [], lo
    for s, e in ivs:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def in_scope(op: Op, scope: str) -> bool:
    """The op was traced under a named scope called ``scope``, also where
    a transform wraps the scope's name (``vmap(kkt_solve)``)."""
    return any(re.fullmatch(r"(?:[\w.]+\()*" + re.escape(scope) + r"\)*", part)
               for part in op.scope.split("/"))


def scope_ns(trace: Trace, scope: str, lo: float, hi: float) -> float:
    """Device time of the ops traced under ``scope``, summed over devices
    and divided by their number."""
    tot = sum(o.dur_ns for o in trace.ops_in(lo, hi) if in_scope(o, scope))
    return tot / max(trace.n_devices, 1)


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[name, seconds] of the n leaf ops that took the most device time;
    the name is the instruction and the tail of its scope path."""
    acc: dict = {}
    for o in trace.ops_in(lo, hi):
        key = o.name + (" " + "/".join(o.scope.split("/")[-3:])
                        if o.scope else "")
        acc[key] = acc.get(key, 0.0) + o.dur_ns
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / max(trace.n_devices, 1)] for k, v in best]


def host_span_at(trace: Trace, t: float, names: Iterable[str]) -> str:
    """Innermost benchmark host span (of ``names``) covering time t."""
    best = None
    for s in trace.spans:
        if s.name in names and s.start_ns <= t <= s.end_ns:
            if best is None or s.dur_ns < best.dur_ns:
                best = s
    return best.name if best else "outside"
