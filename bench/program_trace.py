"""The program's own spans and name scopes in a profiler trace.

`bench.trace` reduces a trace by the benchmark's ``bench.*`` spans.  This
module reads, from the same ``*.xplane.pb``, what the program writes
itself (`repro.obs`):

* its host spans (``fl.prepare``, ``fl.round``, ``fl.host_sync``,
  ``fl.eval``, ... and the compile counter's ``fl.lowering`` markers),
  on the same clock as the device ops;
* each device op's name scope: the ``op_name`` of its HLO metadata,
  ``jax.named_scope`` components (``fl.local_sgd``, ``fl.trust``, ...)
  included.  It is the ``tf_op`` stat of the device planes' event
  metadata, which `jax.profiler.ProfileData` does not expose, so
  `op_scopes` reads it from the file with protobuf.

`of(ctx)` gives the per-layer readers a `ProgramTrace` of the run's
trace: the ``bench.window`` reduction over the ``bench.*`` and ``fl.*``
spans together (so its idle gaps go to the innermost ``fl.*`` span), and
the first device's busy intervals by ``fl.*`` scope.  A trace from a
program without these spans gives a view with no ``fl.*`` span and no
scope, on which the readers read nothing.

    python -m bench.program_trace <trace dir or .xplane.pb>

prints that view's idle gaps and the busy time by scope.
"""
from __future__ import annotations

import dataclasses
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

from bench import trace as bench_trace

PROGRAM_PREFIX = "fl."
SCOPE_STAT = "tf_op"
SEGMENT = "bench.segment"
# an ``fl.*`` component of an op's name scope
SCOPE = re.compile(r"(?<![\w.])fl\.[A-Za-z0-9_]+")


@dataclasses.dataclass
class ProgramTrace:
    red: bench_trace.Reduction          # over bench.* and fl.* spans
    scoped: Dict[str, List[List[float]]]  # first device's busy by fl. scope

    def has_program_spans(self) -> bool:
        return any(n.startswith(PROGRAM_PREFIX) for n in self.red.spans)

    def spans_inside(self, name: str,
                     outer: str) -> List[Tuple[float, float]]:
        """The ``name`` spans that lie inside some ``outer`` span."""
        out = self.red.spans.get(outer, [])
        return [(a, b) for a, b in self.red.spans.get(name, [])
                if any(x <= a and b <= y for x, y in out)]

    def scope_busy_within(self, scope: str, intervals) -> float:
        """Device-busy seconds of the operations under name scope
        ``scope`` inside host ``intervals``."""
        union = self.scoped.get(scope, [])
        return sum(bench_trace._overlap(union, a, b)
                   for a, b in intervals) * 1e-9


def _xplane_messages():
    """The few fields of the profiler's ``XSpace`` proto that hold the
    operations' metadata stats (a map is a repeated key/value entry on
    the wire; every other field is skipped)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    T = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_scopes.proto", package="bench_xplane_scopes")

    repeated = {"planes", "event_metadata", "stat_metadata", "stats"}

    def message(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, number, ftype, ref in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=T.LABEL_REPEATED if fname in repeated
                            else T.LABEL_OPTIONAL)
            if ref:
                f.type_name = ".bench_xplane_scopes." + ref

    message("XSpace", ("planes", 1, T.TYPE_MESSAGE, "XPlane"))
    message("XPlane", ("name", 2, T.TYPE_STRING, None),
            ("event_metadata", 4, T.TYPE_MESSAGE, "EventEntry"),
            ("stat_metadata", 5, T.TYPE_MESSAGE, "StatEntry"))
    message("EventEntry", ("key", 1, T.TYPE_INT64, None),
            ("value", 2, T.TYPE_MESSAGE, "XEventMetadata"))
    message("StatEntry", ("key", 1, T.TYPE_INT64, None),
            ("value", 2, T.TYPE_MESSAGE, "XStatMetadata"))
    message("XEventMetadata", ("name", 2, T.TYPE_STRING, None),
            ("stats", 5, T.TYPE_MESSAGE, "XStat"))
    message("XStatMetadata", ("name", 2, T.TYPE_STRING, None))
    message("XStat", ("metadata_id", 1, T.TYPE_INT64, None),
            ("str_value", 5, T.TYPE_STRING, None),
            ("ref_value", 7, T.TYPE_UINT64, None))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane_scopes.XSpace"))


def op_scopes(path: str) -> Dict[str, str]:
    """{device op name: its name scope} from the ``tf_op`` stat of the
    device planes' event metadata.  Ops of two programs may share a name;
    such a name gets every scope it carries, joined by a space.  Empty
    where protobuf is missing."""
    try:
        space = _xplane_messages()()
    except ImportError:
        return {}
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    scopes: Dict[str, set] = {}
    for plane in space.planes:
        if not bench_trace.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if stat_names.get(st.metadata_id) != SCOPE_STAT:
                    continue
                value = st.str_value or stat_names.get(st.ref_value, "")
                scopes.setdefault(entry.value.name, set()).add(value)
    return {n: " ".join(sorted(v)) for n, v in scopes.items()}


def events(pd, scopes=None):
    """-> (device ops per device [(name, start, end, scope)], host bench
    and program spans [(name, start, end)], the first device's program
    runs [(module, start, end)]).  ``scope`` is the op's name scope from
    ``scopes`` (`op_scopes`), "" where it has none."""
    devices, _, modules = bench_trace.events(pd)
    scopes = scopes or {}
    devices = [[(n, a, b, scopes.get(n, "")) for n, a, b in ops]
               for ops in devices]
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith((bench_trace.PREFIX,
                                                   PROGRAM_PREFIX)))
    return devices, spans, modules


def reduce(devices, spans, modules=()) -> ProgramTrace:
    """`bench.trace.reduce` over the ``bench.*`` and ``fl.*`` spans, and
    the first device's busy intervals by ``fl.*`` scope inside the
    window.  A device op is (name, start, end) or (name, start, end,
    scope)."""
    red = bench_trace.reduce([[op[:3] for op in ops] for ops in devices],
                             spans, modules)
    t0, t1 = red.window_ns
    scoped: Dict[str, list] = {}
    for n, a, b, *scope in (devices[0] if devices else ()):
        if b <= t0 or a >= t1:
            continue
        for name in set(SCOPE.findall(scope[0] if scope else "")):
            scoped.setdefault(name, []).append((max(a, t0), min(b, t1)))
    return ProgramTrace(red, {n: bench_trace._union(iv)
                              for n, iv in scoped.items()})


def load(path: str) -> ProgramTrace:
    """The `ProgramTrace` of a trace file, or of the newest one under a
    directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = bench_trace.find_trace(path)
    return reduce(*events(ProfileData.from_file(path), op_scopes(path)))


def trace_dir(workload: str) -> str:
    """Where `bench/run.py` has a cell's profiler write its trace: the
    ``trace`` directory of the cell's work directory."""
    from bench import harness as h
    return os.path.join(h.WORK, workload, "trace")


def of(ctx) -> Optional[ProgramTrace]:
    """The run's `ProgramTrace`, kept in ``ctx["program"]`` for the next
    reader: read from the cell's trace directory where its window is the
    one `bench.trace` reduced (``ctx["trace"]``).  None without a trace,
    or where the file there is another run's."""
    if "program" in ctx:
        return ctx["program"]
    red, view = ctx.get("trace"), None
    where = trace_dir(ctx["workload"]) if ctx.get("workload") else None
    if red is not None and where and os.path.isdir(where):
        try:
            found = load(where)
        except (FileNotFoundError, ValueError):
            found = None
        if found is not None and found.red.window_ns == red.window_ns:
            view = found
    ctx["program"] = view
    return view


def ms_per_segment(view: Optional[ProgramTrace], names) -> Optional[float]:
    """Wall milliseconds of the ``names`` spans that lie inside the
    ``bench.segment`` spans, per segment; None where there are none."""
    if view is None:
        return None
    inside = [iv for n in names for iv in view.spans_inside(n, SEGMENT)]
    if not inside:
        return None
    return 1e-6 * sum(b - a for a, b in inside) / len(
        view.red.spans[SEGMENT])


def scope_ms_per_round(view: Optional[ProgramTrace], scope: str,
                       module: str, rounds: int) -> Optional[float]:
    """Device-busy milliseconds of the ops under name scope ``scope``
    inside the runs of the programs matching ``module``, per round; None
    where no op carries the scope or no such program ran."""
    if view is None or scope not in view.scoped or rounds <= 0:
        return None
    runs = view.red.module_runs(module)
    if not runs:
        return None
    return 1e3 * view.scope_busy_within(scope, runs) / rounds


def main(argv=None) -> int:
    path, = argv if argv is not None else sys.argv[1:]
    view = load(path)
    out = bench_trace.breakdown(view.red, top=20)
    print("idle gaps (s):")
    for n, v in out["idle_gaps"]:
        print(f"  {n} {v!r}")
    print("device busy by scope (s):")
    for n, iv in sorted(view.scoped.items()):
        print(f"  {n} {sum(b - a for a, b in iv) * 1e-9!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
