"""host_sync_ms_per_segment: host wall time per segment of the one sync
of the scan's stacked metrics (``jax.device_get``, which waits for the
scan): the program's ``fl.host_sync`` spans inside each ``bench.segment``
(profiler trace)."""
from bench import program_trace

NAMES = ("fl.host_sync",)


def read(ctx):
    return program_trace.ms_per_segment(program_trace.of(ctx), NAMES)
