"""window_compiles: lowerings of a program inside the traced window: the
``fl.lowering`` markers the program's compile counter (`repro.obs`)
writes on the profiler's host plane, one per lowering.  Every shape the
window uses is warmed up in set-up, so it should read 0.  A trace with
no ``fl.*`` span at all comes from a program without the counter, and
reads nothing."""
from bench import program_trace

MARKER = "fl.lowering"


def read(ctx):
    view = program_trace.of(ctx)
    if view is None or not view.has_program_spans():
        return None
    return len(view.red.spans.get(MARKER, []))
