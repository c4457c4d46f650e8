"""From a profiler trace to numbers: the one reduction every PR shares.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
(with nothing but jax) into a small model: per device the events of its
"XLA Ops" line (name, the op's name path from the HLO metadata, start,
end), and the host threads' events (TraceAnnotation spans among them).
All planes of one profile share one clock.  ``save_json``/``load_json``
keep the same model as a compact file, which is what the recorded trace
under ``benchmarks/tests/data/`` is.

The reduction, over a window [lo, hi] in the trace's seconds:

  busy          union of the intervals in which an op ran on a device
  self time     an op's duration minus the ops nested inside it (a
                ``while`` spans its body's ops on the same line), so
                sums by name never count an interval twice
  idle during   the idle time that falls inside given host spans
  idle gaps     the complement of busy, every instant of it named
                after the shortest host event that covers it

Device numbers are averaged over the devices in the trace.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import re
from typing import Dict, List, Optional, Sequence

# where a chip's ops are: (plane, line) patterns.  The CPU pair lets a
# --rehearse run walk the same code; it is never a measurement
TPU_OPS = (r"^/device:TPU:(\d+)$", r"^XLA Ops$")
CPU_OPS = (r"^/host:(CPU)$", r"^tf_XLAPjRtCpuClient")
MODULES_LINE = "XLA Modules"
# a pause shorter than this is not the host's doing
GAP_FLOOR_S = 20e-6


@dataclasses.dataclass
class Event:
    name: str
    path: str
    start: float
    end: float
    thread: str = ""

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Event]]
    host: List[Event]

    def span(self, name: str) -> Optional[Event]:
        """The first host event of that name (a TraceAnnotation)."""
        return next((e for e in self.host if e.name == name), None)


# ---- loading ---------------------------------------------------------

def _wire_fields(buf):
    """(field number, value) pairs of one protobuf message, decoded
    from the wire format: varints as ints, length-delimited fields as
    memoryviews.  Enough to read the HLO name paths a trace carries,
    with no protobuf classes."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return value

    while i < n:
        tag = varint()
        kind = tag & 7
        if kind == 0:
            yield tag >> 3, varint()
        elif kind == 2:
            size = varint()
            yield tag >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            yield tag >> 3, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {kind} in a trace file")


def _first(message, number):
    return next((v for n, v in _wire_fields(message) if n == number), None)


def hlo_op_names(space: bytes) -> Dict[str, Dict[str, str]]:
    """{module: {instruction: op name path}} from the HLO protos the
    profiler keeps in the ``/host:metadata`` plane of a serialized
    trace.  A module is named as on the "XLA Modules" line (``jit_train_step(<id>)``); an
    op's name path is what ``jax.named_scope`` and the call stack wrote
    (``jit(train_step)/.../attention/dot_general``).  Field numbers:
    XSpace.planes 1; XPlane.name 2, .event_metadata 4 (a map: value 2);
    XEventMetadata.name 2, .stats 5; XStat.bytes_value 6;
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2."""
    space = memoryview(space)
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _wire_fields(space):
        if number != 1 or bytes(_first(plane, 2) or b"") != b"/host:metadata":
            continue
        for number, entry in _wire_fields(plane):
            if number != 4:
                continue
            meta = _first(entry, 2)
            module = bytes(_first(meta, 2)).decode()
            for number, stat in _wire_fields(meta):
                proto = _first(stat, 6) if number == 5 else None
                if proto is None:
                    continue
                table = out.setdefault(module, {})
                for number, comp in _wire_fields(_first(proto, 1)):
                    if number != 3:
                        continue
                    for number, instr in _wire_fields(comp):
                        if number != 2:
                            continue
                        op_meta = _first(instr, 7)
                        op_name = _first(op_meta, 2) if op_meta else None
                        table[bytes(_first(instr, 1)).decode()] = \
                            bytes(op_name).decode() if op_name else ""
    return out


def _instruction(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: on a TPU
    an op event is named by its whole HLO line."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def load_xplane(path: str, ops=TPU_OPS) -> Trace:
    import bisect

    import jax

    with open(path, "rb") as f:
        space = f.read()
    data = jax.profiler.ProfileData.from_serialized_xspace(space)
    op_names = hlo_op_names(space)
    plane_re, line_re = (re.compile(p) for p in ops)
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        on_device = plane_re.match(plane.name)
        if not on_device and not plane.name.startswith("/host:"):
            continue
        lines = {line.name: line for line in plane.lines}
        # an op belongs to the module (the program) that runs around it
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in lines[MODULES_LINE].events) \
            if on_device and MODULES_LINE in lines else []
        starts = [m[0] for m in modules]
        for line in plane.lines:
            is_ops = bool(on_device and line_re.match(line.name))
            if not is_ops and not plane.name.startswith("/host:"):
                continue  # a device's other lines
            events = []
            for ev in line.events:
                name, path_ = ev.name, ""
                if is_ops:
                    name = _instruction(ev.name)
                    at = bisect.bisect_right(starts, ev.start_ns) - 1
                    if at >= 0 and ev.start_ns < modules[at][1]:
                        path_ = op_names.get(modules[at][2], {}).get(name, "")
                start = ev.start_ns * 1e-9
                events.append(Event(name, path_, start,
                                    start + ev.duration_ns * 1e-9,
                                    "" if is_ops else line.name))
            if is_ops:
                group = on_device.group(1)
                devices.setdefault(int(group) if group.isdigit() else 0,
                                   []).extend(events)
            else:
                host.extend(e for e in events if e.seconds > 0)
    return Trace(devices, host)


def save_json(trace: Trace, path: str) -> None:
    doc = {"devices": {str(d): [[e.name, e.path, e.start, e.end]
                                for e in evs]
                       for d, evs in trace.devices.items()},
           "host": [[e.name, e.thread, e.start, e.end]
                    for e in trace.host]}
    with gzip.open(path, "wt") as f:
        json.dump(doc, f, separators=(",", ":"))


def load_json(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return Trace(
        {int(d): [Event(n, p, s, e) for n, p, s, e in evs]
         for d, evs in doc["devices"].items()},
        [Event(n, "", s, e, th) for n, th, s, e in doc["host"]])


def clip(trace: Trace, lo: float, hi: float) -> Trace:
    """The part of the trace inside [lo, hi]; events are cut at the
    window's edges."""
    def cut(events):
        return [dataclasses.replace(e, start=max(e.start, lo),
                                    end=min(e.end, hi))
                for e in events if e.end > lo and e.start < hi]
    return Trace({d: cut(evs) for d, evs in trace.devices.items()},
                 cut(trace.host))


# ---- interval arithmetic ---------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        elif hi > lo:
            out.append([lo, hi])
    return out


def total(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def subtract(a, b) -> list:
    """The points of union ``a`` that union ``b`` does not cover."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, at = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > at:
                out.append([at, b[k][0]])
            at = max(at, b[k][1])
            k += 1
        if at < hi:
            out.append([at, hi])
    return out


def self_times(events: Sequence[Event]) -> list:
    """[(event, self seconds, is_leaf)]: nesting is by containment on
    one line, as the profiler draws it."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    rows = [[e, e.seconds, True] for e in order]
    stack = []
    for row in rows:
        e = row[0]
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        if stack and e.end <= stack[-1][0].end + 1e-12:
            stack[-1][1] -= e.seconds
            stack[-1][2] = False
        stack.append(row)
    return [(e, max(s, 0.0), leaf) for e, s, leaf in rows]


def matcher(patterns: Sequence[str], field: str = "any"):
    """Events whose name or path (``field``: name | path | any)
    matches any of the regular expressions."""
    regs = [re.compile(p) for p in patterns]

    def match(e: Event) -> bool:
        texts = {"name": (e.name,), "path": (e.path,),
                 "any": (e.name, e.path)}[field]
        return any(r.search(t) for r in regs for t in texts)
    return match


# ---- the reduction ---------------------------------------------------

class Reduction:
    """Everything the per-layer readers ask of one traced window."""

    def __init__(self, trace: Trace, lo: float, hi: float):
        self.trace = clip(trace, lo, hi)
        self.lo, self.hi = lo, hi
        self.window_s = hi - lo
        self.n_devices = len(self.trace.devices)
        self._rows = {d: self_times(evs)
                      for d, evs in self.trace.devices.items()}
        self._busy = {d: union((e.start, e.end) for e in evs)
                      for d, evs in self.trace.devices.items()}

    def _mean(self, per_device: dict) -> float:
        return (sum(per_device.values()) / len(per_device)
                if per_device else 0.0)

    @property
    def busy_s(self) -> float:
        return self._mean({d: total(u) for d, u in self._busy.items()})

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds(self, match) -> float:
        """Self time of the matching ops, mean over devices."""
        return self._mean({
            d: sum(s for e, s, _ in rows if match(e))
            for d, rows in self._rows.items()})

    def count(self, match) -> float:
        """Matching events per device."""
        return self._mean({d: float(sum(1 for e, _, _ in rows if match(e)))
                           for d, rows in self._rows.items()})

    def idle_seconds_during(self, match) -> float:
        """Idle time that falls inside the matching host events."""
        spans = union((e.start, e.end) for e in self.trace.host
                      if match(e))
        per_device = {}
        for d, busy in self._busy.items():
            idle = subtract([[self.lo, self.hi]], busy)
            per_device[d] = total(idle) - total(subtract(idle, spans))
        return self._mean(per_device)

    def top_ops(self, n: int = 10, label=None) -> list:
        """[[label, seconds]]: self time by label, mean over devices."""
        label = label or op_label
        sums: Dict[str, float] = {}
        for rows in self._rows.values():
            for e, s, _ in rows:
                key = label(e)
                sums[key] = sums.get(key, 0.0) + s / self.n_devices
        return [[k, v] for k, v in sorted(sums.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """[[host event name, seconds]]: idle time by what the host was
        doing, mean over devices.  Every instant of a gap goes to the
        shortest host event (of any thread) that covers it; events
        longer than half the window explain no particular gap and are
        left out; gaps under GAP_FLOOR_S are the device's own pauses
        between ops and are summed apart."""
        host = sorted((e for e in self.trace.host
                       if e.seconds <= 0.5 * self.window_s),
                      key=lambda e: e.start)
        sums: Dict[str, float] = {}

        def add(key, seconds):
            sums[key] = sums.get(key, 0.0) + seconds / self.n_devices

        for busy in self._busy.values():
            i, active = 0, []
            for lo, hi in subtract([[self.lo, self.hi]], busy):
                if hi - lo < GAP_FLOOR_S:
                    add(f"(gaps under {GAP_FLOOR_S * 1e6:.0f} us)", hi - lo)
                    continue
                while i < len(host) and host[i].start < hi:
                    active.append(host[i])
                    i += 1
                active = [e for e in active if e.end > lo]
                cuts = sorted({lo, hi} | {t for e in active
                                          for t in (e.start, e.end)
                                          if lo < t < hi})
                for a, b in zip(cuts, cuts[1:]):
                    over = [e for e in active
                            if e.start <= a and e.end >= b]
                    add(min(over, key=lambda e: e.seconds).name if over
                        else "(no host event)", b - a)
        return [[k, v] for k, v in sorted(sums.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def op_label(e: Event) -> str:
    """A readable name for an op: the tail of its name path where it
    has one (``.../attention/dot_general``), else the HLO name without
    its number (``fusion.123`` -> ``fusion``)."""
    if e.path:
        parts = [p for p in e.path.rstrip(":").split("/") if p]
        return "/".join(parts[-2:])
    return re.sub(r"[.\d]+$", "", e.name)


def describe(path: str, limit: int = 12) -> str:
    """What a trace holds, for a reader who has not seen one: planes,
    lines, and a few events of each with their stats."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            seen = set()
            for ev in events:
                if ev.name in seen:
                    continue
                seen.add(ev.name)
                if len(seen) > limit:
                    break
                stats = {k: (str(v)[:120]) for k, v in ev.stats}
                out.append(f"    {ev.name!r} start_ns={ev.start_ns} "
                           f"dur_ns={ev.duration_ns} {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2
                   else 12))
