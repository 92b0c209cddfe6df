"""flatten_device_ms: device-busy time of the operations under the
round's ``fl.flatten`` name scope (flattening the members' new and old
parameters into the (members, N) update matrix the trust chain reads),
inside the runs of the fused scan's program, over the rounds run in the
traced window (profiler trace)."""
from bench import program_trace
from bench.metrics.round_device_ms import SCAN_MODULE

SCOPE = "fl.flatten"


def read(ctx):
    return program_trace.scope_ms_per_round(
        program_trace.of(ctx), SCOPE, SCAN_MODULE,
        len(ctx["window"]["rounds"]))
