"""Reduce a profiler trace (``.xplane.pb``) to the numbers the readers use.

Device planes are ``/device:TPU:<n>``.  On each, the line ``XLA Ops`` holds
one event per operation that ran; ``XLA Modules`` one per program (jitted
step).  The host plane ``/host:CPU`` holds the benchmark's spans, written by
``jax.profiler.TraceAnnotation`` on the same clock.

* busy: the union of the operation intervals of each device, averaged over
  the devices; idle = window - busy.
* time per named kernel: the summed durations of the operations whose own
  instruction name starts with the kernel's (a Pallas call is named after
  its jitted wrapper: ``%axo_matmul_pallas.3``); ops that only take its
  output do not count.
* time per program: summed ``XLA Modules`` durations by program name.
* idle gaps: each stretch between busy intervals, named by the innermost
  benchmark span (``bench.*``) the host was in at its midpoint.  Device and
  host events share the trace's clock to about a millisecond.
* the breakdown leaves out control-flow ops (``while`` and the like), whose
  time is their body's.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


CONTAINERS = ("%while", "%conditional", "%call")  # hold other ops' time


@dataclass
class Op:
    name: str          # the HLO instruction's own name, e.g. %fusion.12
    start_ns: float
    dur_ns: float
    text: str          # the instruction, layouts dropped, for the breakdown


def _op(event) -> Op:
    name, _, rest = event.name.partition(" = ")
    text = re.sub(r"\{[^{}]*\}", "", event.name)
    return Op(name, event.start_ns, event.duration_ns, text[:160])


@dataclass
class TraceSummary:
    window_s: float                 # first to last event on any plane
    busy_s: float                   # union of op intervals, mean over devices
    n_devices: int
    ops: list = field(default_factory=list)          # Op, all devices
    modules: dict = field(default_factory=dict)      # program -> seconds
    module_counts: dict = field(default_factory=dict)
    module_events: list = field(default_factory=list)  # (name, start, end) ns
    busy_intervals: list = field(default_factory=list)  # merged, per device
    gaps: list = field(default_factory=list)         # (seconds, host span)

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s

    def _named(self, kernel: str) -> list:
        return [o for o in self.ops if o.name.startswith("%" + kernel)]

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of the operations named for ``kernel``, per device."""
        tot = sum(o.dur_ns for o in self._named(kernel))
        return tot / 1e9 / max(self.n_devices, 1)

    def kernel_count(self, kernel: str) -> int:
        return len(self._named(kernel))

    def gaps_by_span(self) -> dict:
        out: dict = defaultdict(float)
        for sec, span in self.gaps:
            out[span] += sec
        return dict(out)

    def breakdown(self, k: int = 10) -> dict:
        by_op: dict = defaultdict(float)
        for o in self.ops:
            if not o.name.startswith(CONTAINERS):
                by_op[o.text] += o.dur_ns / 1e9
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:k]
        gaps = sorted(self.gaps_by_span().items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, s / max(self.n_devices, 1)] for n, s in top],
                "idle_gaps": [[n, s / max(self.n_devices, 1)] for n, s in gaps]}


def _union_ns(intervals: list) -> tuple[float, list]:
    """(covered length, merged intervals) of (start, end) pairs."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _innermost(spans: list, t: float) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "outside any span"


def reduce_trace(path: str | Path, span_prefix: str = "bench.") -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: list = []
    modules: dict = defaultdict(float)
    counts: dict = defaultdict(int)
    module_events: list = []
    busy, devices, merged_all = 0.0, 0, []
    t_lo, t_hi = float("inf"), float("-inf")
    host_spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev_iv = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        ops.append(_op(ev))
                        dev_iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        name = re.sub(r"\(\d+\)$", "", ev.name)
                        modules[name] += ev.duration_ns / 1e9
                        counts[name] += 1
                        module_events.append(
                            (name, ev.start_ns, ev.start_ns + ev.duration_ns))
            if dev_iv:
                devices += 1
                covered, merged = _union_ns(dev_iv)
                busy += covered
                merged_all.append(merged)
                t_lo = min(t_lo, merged[0][0])
                t_hi = max(t_hi, merged[-1][1])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        host_spans.append((ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
                        t_lo = min(t_lo, ev.start_ns)
                        t_hi = max(t_hi, ev.start_ns + ev.duration_ns)
    gaps = []
    for merged in merged_all:
        edges = [(t_lo, t_lo)] + [tuple(m) for m in merged] + [(t_hi, t_hi)]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps.append(((s1 - e0) / 1e9, _innermost(host_spans, (s1 + e0) / 2)))
    window = (t_hi - t_lo) / 1e9 if t_hi > t_lo else 0.0
    return TraceSummary(window_s=window, busy_s=busy / 1e9 / max(devices, 1),
                        n_devices=devices, ops=ops, modules=dict(modules),
                        module_counts=dict(counts),
                        module_events=sorted(module_events, key=lambda m: m[1]),
                        busy_intervals=merged_all, gaps=gaps)
