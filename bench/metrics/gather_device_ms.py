"""gather_device_ms: device-busy time of the operations under the round's
``fl.gather`` name scope (the batch draw's reads of the fleet tables: the
partition rows, then each member's rows of ``x`` and ``y``), inside the
runs of the fused scan's program, over the rounds run in the traced
window (profiler trace).  A program without the scope reads nothing."""
from bench import program_trace
from bench.metrics.round_device_ms import SCAN_MODULE

SCOPE = "fl.gather"


def read(ctx):
    return program_trace.scope_ms_per_round(
        program_trace.of(ctx), SCOPE, SCAN_MODULE,
        len(ctx["window"]["rounds"]))
