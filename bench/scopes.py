"""Device time of the serving program by layer, from the named scopes in a
profiler trace (``.xplane.pb``).

The program names its layers with ``jax.named_scope``.  The names this
file attributes time to (``SCOPES``) are the files ``scope_names/<scope>.txt``
beside it, each a line on where the program opens that scope, so a scope
that a new mechanism adds is one new file.  XLA
keeps each operation's name stack in its metadata, and the profiler writes
it, with the program's name, as the ``tf_op`` stat of the operation's event
metadata on a device plane: ``jit(decode_step)/while/body/closed_call/
attn.proj/axo.gather/gather``.  ``jax.profiler.ProfileData`` does not expose
the stats of event metadata, so this file reads the XPlane protobuf itself,
with the standard library alone: planes, lines, events, event metadata and
its stats, stat metadata.

For each ``decode_step`` program event (``XLA Modules`` line) of a device
plane, the operations that start inside it (``XLA Ops`` line; control flow
left out, as its time is its body's) are summed by the innermost scope of
``SCOPES`` that is a component of their ``tf_op``; the rest is ``other``.
Steps of one program run the same operations, so a step with another count
than the window's most common one has lost events: each reading is a median
over the complete steps alone.

    python3 bench/scopes.py <trace.xplane.pb> [program]

prints each scope's median milliseconds a step as one JSON object.  The
functions named for per-layer metrics at the end take a benchmark reader's
``ctx`` and return None where the trace holds nothing to read.
"""

from __future__ import annotations

import functools
import json
import re
import statistics
import struct
import sys
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path

from trace_reduce import CONTAINERS, MODULES_LINE, OPS_LINE

SCOPES = tuple(sorted(p.name.removesuffix(".txt") for p in
                     Path(__file__).with_name("scope_names").glob("*.txt")))
OTHER = "other"


# -- the XPlane protobuf, field numbers from tsl/profiler/protobuf/xplane.proto

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of each field in ``buf[i:end]``; a
    length-delimited value is its (start, end) offsets."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire} at {i}")
        yield key >> 3, val


def _str(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span: tuple) -> tuple:
    key, val = 0, (span[0], span[0])
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


@dataclass
class Plane:
    name: str
    lines: dict = field(default_factory=dict)   # name -> [(meta id, start ps, dur ps)]
    event_names: dict = field(default_factory=dict)   # meta id -> name
    event_stats: dict = field(default_factory=dict)   # meta id -> {stat: value}


def _stat(buf: bytes, span: tuple, stat_names: dict) -> tuple:
    sid, val = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            sid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            val = v
        elif f == 5:
            val = _str(buf, v)
        elif f == 6:
            val = buf[v[0]:v[1]]
        elif f == 7:                       # a reference to a stat's name
            val = stat_names.get(v, "")
    return sid, val


def _line(buf: bytes, span: tuple) -> tuple:
    name, t0_ns, events = "", 0, []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            t0_ns = v
        elif f == 4:
            mid = off = dur = 0
            for ef, ev in _fields(buf, *v):
                if ef == 1:
                    mid = ev
                elif ef == 2:
                    off = ev
                elif ef == 3:
                    dur = ev
            events.append((mid, off, dur))
    t0_ps = t0_ns * 1000
    return name, [(m, t0_ps + off, dur) for m, off, dur in events]


def plane_spans(buf: bytes):
    """(name, (start, end)) of each plane serialized in an XSpace."""
    for f, v in _fields(buf, 0, len(buf)):
        if f == 1:
            yield next((_str(buf, x) for g, x in _fields(buf, *v) if g == 2), ""), v


def _plane(buf: bytes, span: tuple, name: str) -> Plane:
    plane, meta, stat_names = Plane(name), [], {}
    for f, v in _fields(buf, *span):
        if f == 3:
            line, events = _line(buf, v)
            plane.lines.setdefault(line, []).extend(events)
        elif f == 4:
            meta.append(_map_entry(buf, v)[1])
        elif f == 5:
            sid, val = _map_entry(buf, v)
            for sf, sv in _fields(buf, *val):
                if sf == 2:
                    stat_names[sid] = _str(buf, sv)
    for val in meta:              # stat names first: stats refer to them
        mid, event, stats = 0, "", {}
        for f, v in _fields(buf, *val):
            if f == 1:
                mid = v
            elif f == 2:
                event = _str(buf, v)
            elif f == 5:
                sid, sval = _stat(buf, v, stat_names)
                stats[stat_names.get(sid, str(sid))] = sval
        plane.event_names[mid] = event
        plane.event_stats[mid] = stats
    return plane


def read_planes(path: str | Path, want=lambda name: True) -> list[Plane]:
    """The planes of an ``.xplane.pb`` whose name ``want`` accepts."""
    buf = Path(path).read_bytes()
    return [_plane(buf, span, name) for name, span in plane_spans(buf)
            if want(name)]


def device_planes(path: str | Path) -> list[Plane]:
    return read_planes(path, lambda name: name.startswith("/device:TPU:"))


# -- device time by scope --------------------------------------------------

def tf_op(stats: dict) -> str:
    """The operation's name stack (``tf_op`` ends in ':' and its type)."""
    op, colon, rest = str(stats.get("tf_op", "")).rpartition(":")
    return op if colon else rest


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` that is a component of ``op_name``."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return OTHER


@dataclass
class Step:
    """One program event on a device and the operations inside it."""

    start_ps: int
    end_ps: int
    n_ops: int = 0
    seconds: dict = field(default_factory=dict)   # scope -> device seconds

    @property
    def busy_s(self) -> float:
        return sum(self.seconds.values())

    @property
    def named_s(self) -> float:
        return self.busy_s - self.seconds.get(OTHER, 0.0)


def program_steps(planes: list[Plane], program: str = "decode_step") -> list[Step]:
    """Every event of ``jit_<program>`` on each device, with the device
    seconds of the operations that start inside it, by scope."""
    steps = []
    for plane in planes:
        scope = {mid: scope_of(tf_op(st)) for mid, st in plane.event_stats.items()}
        leaf = {mid: not name.startswith(CONTAINERS)
                for mid, name in plane.event_names.items()}
        ops = sorted(plane.lines.get(OPS_LINE, []), key=lambda e: e[1])
        mods = sorted((e for e in plane.lines.get(MODULES_LINE, [])
                       if re.sub(r"\(\d+\)$", "",
                                 plane.event_names.get(e[0], "")) == f"jit_{program}"),
                      key=lambda e: e[1])
        j = 0
        for _, start, dur in mods:
            step = Step(start, start + dur)
            while j < len(ops) and ops[j][1] < start:
                j += 1
            while j < len(ops) and ops[j][1] < step.end_ps:
                mid, _, op_dur = ops[j]
                step.n_ops += 1
                if leaf.get(mid, True):
                    s = scope.get(mid, OTHER)
                    step.seconds[s] = step.seconds.get(s, 0.0) + op_dur / 1e12
                j += 1
            steps.append(step)
    return steps


def split_complete(steps: list[Step]) -> tuple[list[Step], list[Step]]:
    """(complete, incomplete): a complete step holds the most common count
    of operations of the window."""
    if not steps:
        return [], []
    mode = Counter(s.n_ops for s in steps).most_common(1)[0][0]
    return ([s for s in steps if s.n_ops == mode],
            [s for s in steps if s.n_ops != mode])


@dataclass
class ScopeTimes:
    program: str
    complete: list
    incomplete: list

    def median_ms(self, *scopes: str) -> float | None:
        """Median over complete steps of the milliseconds under ``scopes``."""
        if not self.complete:
            return None
        return 1e3 * statistics.median(
            sum(s.seconds.get(k, 0.0) for k in scopes) for s in self.complete)

    @property
    def named_share(self) -> float:
        busy = sum(s.busy_s for s in self.complete)
        return sum(s.named_s for s in self.complete) / busy if busy else 0.0

    def summary(self) -> str:
        ops = self.complete[0].n_ops if self.complete else 0
        return (f"scopes: {self.program} steps complete {len(self.complete)} "
                f"incomplete {len(self.incomplete)} ops_per_step {ops} "
                f"named_share {100 * self.named_share:.2f}%")

    def medians(self) -> dict:
        return {k: self.median_ms(k) for k in SCOPES + (OTHER,)}


@functools.lru_cache(maxsize=2)
def scope_times(path: str, program: str = "decode_step") -> ScopeTimes:
    """Read a trace once; print the reader's one line to stderr."""
    steps = program_steps(device_planes(path), program)
    times = ScopeTimes(program, *split_complete(steps))
    print(times.summary(), file=sys.stderr)
    return times


# -- per-layer metrics: each takes a benchmark reader's ctx ------------------

def _decode_ms(ctx, *scopes: str) -> float | None:
    path = getattr(ctx["run"], "trace_path", None)
    if path is None:
        return None
    times = scope_times(str(path))
    if not any(s.seconds.keys() - {OTHER} for s in times.complete):
        return None          # a program without the named scopes
    return times.median_ms(*scopes)


def axo_glue_ms_per_step(ctx) -> float | None:
    """Device ms a decode step spends quantizing and gathering AxO
    activations (``axo.quantize`` + ``axo.gather``)."""
    if not ctx["layer"].get("axo"):
        return None
    return _decode_ms(ctx, "axo.quantize", "axo.gather")


def mlp_ms_per_step(ctx) -> float | None:
    """Device ms a decode step spends in the MLP (``mlp``)."""
    return _decode_ms(ctx, "mlp")


def kv_update_ms_per_step(ctx) -> float | None:
    """Device ms a decode step spends writing the KV cache
    (``attn.kv_update``)."""
    return _decode_ms(ctx, "attn.kv_update")


def axo_deploy_s(ctx) -> float | None:
    """Host seconds of the program's last ``axo.deploy`` span (set-up runs
    before a traced window opens, so the span is read from the program's
    process-wide telemetry, not from the trace)."""
    from repro import obs

    spans = [s for s in list(obs.GLOBAL.spans) if s.name == "axo.deploy"]
    return spans[-1].duration_s if spans else None


if __name__ == "__main__":
    if len(sys.argv) not in (2, 3):
        sys.exit(__doc__)
    times = scope_times(sys.argv[1], *sys.argv[2:])
    print(json.dumps({"complete": len(times.complete),
                      "incomplete": len(times.incomplete),
                      "named_share": times.named_share,
                      "median_ms": times.medians()}))
