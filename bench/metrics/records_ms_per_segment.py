"""records_ms_per_segment: host wall time per segment of folding pending
energy and building the round records (the f64 energy tally, the queue
sync, telemetry, one `RoundRecord` a round): the program's ``fl.flush``
and ``fl.records`` spans inside each ``bench.segment`` (profiler
trace)."""
from bench import program_trace

NAMES = ("fl.flush", "fl.records")


def read(ctx):
    return program_trace.ms_per_segment(program_trace.of(ctx), NAMES)
