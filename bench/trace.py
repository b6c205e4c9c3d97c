"""Reduction of a profiler trace (``.xplane.pb``) to device times.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation that ran on the chip. Busy time is the union of the op intervals, so
overlapping ops are not counted twice. Ops are classed by name:

  * BMF kernels: the Pallas kernels of the factor step. A TPU trace
    names each op by its HLO instruction and carries no kernel name, so a
    kernel is a ``tpu_custom_call`` op, or the ``kCustom`` fusion that XLA
    wraps around one inside the row-stripe loop (instruction
    ``closed_call.N``); an op that names a BMF kernel function counts too;
  * collectives: all-gather, all-reduce, reduce-scatter, all-to-all and
    collective-permute ops.

Host spans written with ``jax.profiler.TraceAnnotation`` appear on the
host plane's threads and are used to say what the host was doing during
the longest device idle gaps.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

KERNEL_NAMES = ("_sweep_kernel", "_fused_kernel", "tpu_custom_call")
COLLECTIVE_NAMES = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute", "allgather",
                    "allreduce", "reducescatter")
OP_LINE = "XLA Ops"


@dataclass
class Op:
    name: str
    start: int                 # ns
    end: int
    text: str                  # name + string stats, lower case

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclass
class Device:
    name: str
    ops: List[Op] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[Device]
    host_spans: List[Tuple[str, int, int]]


def _text(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts).lower()


def load(path: str, host_span_prefix: str = "bench.") -> Trace:
    """Read one ``.xplane.pb`` (or the newest under a profile directory)."""
    import jax
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    dev.ops.append(Op(ev.name, s, s + int(ev.duration_ns),
                                      _text(ev)))
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_span_prefix):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    return Trace(devices=devices, host_spans=spans)


def union(intervals) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops) -> int:
    return sum(e - s for s, e in union((o.start, o.end) for o in ops))


def is_kernel(op: Op) -> bool:
    return any(k in op.text for k in KERNEL_NAMES) or (
        op.text.startswith("%closed_call.") and "kind=kcustom" in op.text)


_HLO = re.compile(r"^(%\S+) = (\S+?) ([\w-]+)\(")


def label(name: str) -> str:
    """Short label of an op: instruction, opcode[/custom-call target]
    [fusion kind], output shape."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    instr, shape, opcode = m.groups()
    t = re.search(r'custom_call_target="([^"]+)"', name)
    k = re.search(r"kind=(k\w+)", name)
    return " ".join([instr, opcode + (f"/{t.group(1)}" if t else "")]
                    + ([k.group(1)] if k else []) + [shape[:60]])


def is_collective(op: Op) -> bool:
    return any(k in op.text for k in COLLECTIVE_NAMES)


def class_ns(dev: Device, pred) -> int:
    return busy_ns([o for o in dev.ops if pred(o)])


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Device ops with the most time, seconds averaged over devices."""
    tot: Dict[str, int] = {}
    for d in trace.devices:
        for o in d.ops:
            lb = label(o.name)
            tot[lb] = tot.get(lb, 0) + o.dur
    nd = max(1, len(trace.devices))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd / 1e9] for k, v in best]


def idle_gaps(trace: Trace, lo: int, hi: int, n: int = 10):
    """Longest gaps in [lo, hi] (ns) between busy intervals on the first
    device, each named by the host span that covers its middle."""
    if not trace.devices:
        return []
    busy = union((o.start, o.end) for o in trace.devices[0].ops)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:n]
    out = []
    for s, e in gaps:
        mid = (s + e) // 2
        names = [nm for nm, a, b in trace.host_spans if a <= mid <= b]
        out.append([names[-1] if names else "host", (e - s) / 1e9])
    return out
