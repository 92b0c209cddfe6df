"""trust_device_ms: device-busy time of the operations under the round's
``fl.trust`` name scope (Eqns 4-5: learning quality, diversity, belief,
reputation, trust weights), inside the runs of the fused scan's program,
over the rounds run in the traced window (profiler trace)."""
from bench import program_trace
from bench.metrics.round_device_ms import SCAN_MODULE

SCOPE = "fl.trust"


def read(ctx):
    return program_trace.scope_ms_per_round(
        program_trace.of(ctx), SCOPE, SCAN_MODULE,
        len(ctx["window"]["rounds"]))
