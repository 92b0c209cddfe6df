"""dispatch_ms_per_segment: host wall time per segment of preparing and
dispatching the scan: the program's ``fl.prepare`` (scan policy, argument
tuple, cache lookup) and ``fl.round`` (the call of the compiled scan)
spans inside each ``bench.segment`` (profiler trace)."""
from bench import program_trace

NAMES = ("fl.prepare", "fl.round")


def read(ctx):
    return program_trace.ms_per_segment(program_trace.of(ctx), NAMES)
