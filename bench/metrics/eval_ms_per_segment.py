"""eval_ms_per_segment: host wall time per segment of the evaluation of
the global model after the scan (``task.evaluate``): the program's
``fl.eval`` spans inside each ``bench.segment`` (profiler trace).  It
moves ``time_to_target_s`` as well: every segment of an episode pays it."""
from bench import program_trace

NAMES = ("fl.eval",)


def read(ctx):
    return program_trace.ms_per_segment(program_trace.of(ctx), NAMES)
