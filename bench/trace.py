"""Reduction of a profiler trace (``*.xplane.pb``) to device metrics.

Read with `jax.profiler.ProfileData` alone.  The device planes are the
TPU cores (``/device:TPU:<n>``); their ``XLA Ops`` line holds one event per
operation run, their ``XLA Modules`` line one per program run.  The host plane carries the benchmark's own
``jax.profiler.TraceAnnotation`` spans (``bench.*``), on the same clock.

* busy: the union of the operations' intervals inside the traced window
  (the ``bench.window`` span), averaged over the devices;
* operation time by name, for the kernels' roofline shares;
* device-busy time inside the runs of one program, by its module name;
* idle gaps: the stretches of the window in which no operation ran,
  split by the innermost ``bench.*`` span that was open on the host.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
PREFIX = "bench."


@dataclasses.dataclass
class Reduction:
    window_ns: Tuple[float, float]
    n_devices: int
    busy_ns: float                       # mean over devices
    op_ns: Dict[str, float]              # summed over devices
    op_count: Dict[str, int]
    idle_by_span: Dict[str, float]       # mean over devices
    spans: Dict[str, List[Tuple[float, float]]]
    busy_union: List[List[float]]        # first device's busy intervals
    modules: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)            # first device's program runs

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def busy_within(self, intervals) -> float:
        """Device-busy seconds inside host ``intervals``."""
        return sum(_overlap(self.busy_union, a, b)
                   for a, b in intervals) * 1e-9

    def module_runs(self, pattern: str) -> List[Tuple[float, float]]:
        """The runs, inside the window, of the programs whose module name
        matches ``pattern``."""
        rx = re.compile(pattern)
        return [(a, b) for n, a, b in self.modules if rx.search(n)]


def find_trace(root: str) -> str:
    found = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(union, a, b) -> float:
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in union)


def events(pd):
    """-> (device ops per device [(name, start, end)], host bench spans
    [(name, start, end)], the first device's program runs
    [(module, start, end)])."""
    devices, spans, modules = [], [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    mods.extend((e.name, e.start_ns, e.end_ns)
                                for e in line.events)
            devices.append(ops)
            modules = mods if modules is None else modules
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(PREFIX))
    return devices, spans, modules or []


def reduce(devices, spans, modules=()) -> Reduction:
    """Reduce the `events` of one trace over its ``bench.window`` span."""
    windows = [(a, b) for n, a, b in spans if n == WINDOW]
    if not windows or not devices:
        raise ValueError("trace has no bench.window span or no device "
                         "plane")
    t0, t1 = windows[0]
    inner = sorted(((n, a, b) for n, a, b in spans
                    if n != WINDOW and b > t0 and a < t1),
                   key=lambda s: s[1])
    timeline = _timeline(inner, t0, t1)
    cuts = [x for x, _, _ in timeline]
    busy, op_ns, op_count, idle = 0.0, {}, {}, {}
    union_all = None
    for ops in devices:
        clipped = [(n, max(a, t0), min(b, t1)) for n, a, b in ops
                   if b > t0 and a < t1]
        for n, a, b in clipped:
            op_ns[n] = op_ns.get(n, 0.0) + (b - a)
            op_count[n] = op_count.get(n, 0) + 1
        union = _union((a, b) for _, a, b in clipped)
        union_all = union if union_all is None else union_all
        busy += sum(b - a for a, b in union)
        edges = [t0] + [x for iv in union for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            for label, x, y in _pieces(timeline, cuts, a, b):
                idle[label] = idle.get(label, 0.0) + (y - x)
    k = len(devices)
    return Reduction(window_ns=(t0, t1), n_devices=k, busy_ns=busy / k,
                     op_ns=op_ns, op_count=op_count,
                     idle_by_span={n: v / k for n, v in idle.items()},
                     spans=_by_name(inner), busy_union=union_all or [],
                     modules=[(n, max(a, t0), min(b, t1))
                              for n, a, b in modules if b > t0 and a < t1])


def _open_span(spans, starts, t: float) -> str:
    """Innermost (latest-starting) bench span open at ``t``."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[i][2] > t:
            return spans[i][0]
    return "outside bench spans"


def _timeline(spans, t0, t1):
    """[(start, end, innermost open span)] pieces covering t0..t1."""
    starts = [a for _, a, _ in spans]
    cuts = sorted({t0, t1} | {x for _, a, b in spans for x in (a, b)
                              if t0 < x < t1})
    return [(a, b, _open_span(spans, starts, (a + b) / 2))
            for a, b in zip(cuts, cuts[1:])]


def _pieces(timeline, cuts, a, b):
    """The parts of a..b under each host span."""
    i = max(0, bisect.bisect_right(cuts, a) - 1)
    while i < len(timeline) and timeline[i][0] < b:
        x, y, label = timeline[i]
        if min(y, b) > max(x, a):
            yield label, max(x, a), min(y, b)
        i += 1


def _by_name(spans):
    out: Dict[str, List[Tuple[float, float]]] = {}
    for n, a, b in spans:
        out.setdefault(n, []).append((a, b))
    return out


def load(trace_dir: str) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_trace(trace_dir))
    return reduce(*events(pd))


def op_seconds(red: Reduction, pattern: str) -> Tuple[float, int]:
    """Device seconds and count of the operations whose name matches."""
    hits = op_names(red, pattern)
    return (sum(red.op_ns[n] for n in hits) * 1e-9 / red.n_devices,
            sum(red.op_count[n] for n in hits) // red.n_devices)


def op_names(red: Reduction, pattern: str) -> List[str]:
    """The distinct operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return [n for n in red.op_ns if rx.search(n)]


OPCODE = re.compile(r"(?<![A-Za-z0-9_.])([a-z][a-z0-9-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def short_name(text: str) -> str:
    """``%fusion.48 fusion`` from the HLO text the trace names an op by."""
    lhs, _, rhs = text.partition(" = ")
    m = OPCODE.search(rhs)
    return f"{lhs} {m.group(1)}" if m else lhs[:80]


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The operations that took most device time (control-flow ops, which
    enclose others, left out) and the idle time by host span."""
    ops = sorted(((short_name(n), v) for n, v in red.op_ns.items()
                  if short_name(n).rsplit(" ", 1)[-1] not in CONTAINERS),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(red.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, v * 1e-9 / red.n_devices] for n, v in ops],
            "idle_gaps": [[n, v * 1e-9] for n, v in gaps]}
