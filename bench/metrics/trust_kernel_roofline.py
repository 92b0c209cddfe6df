"""trust_kernel_roofline: the fused Eqn 6 + 19 kernel's least time over its
measured time, in percent (profiler trace).

Per call on a round of cluster c with m real members, B clusters and N
parameters the kernel's work is 2*(m+B)*N FLOPs (a multiply and an add per
element) and 4*((m+B)*N + N) bytes (each f32 row read once, the global
row written once).  The least time is the larger of FLOPs over the peak
FLOP/s and bytes over the peak HBM bandwidth.  The padded rows the kernel
streams today are not the work and are not counted.
"""
from bench import trace as bench_trace

# the trace names a device op by its HLO text, which the Mosaic kernel's
# name begins: ``%trust_aggregate_global.<n> = ... custom-call(...)``
PATTERN = r"^%trust_aggregate_global(\.\d+)? = "


def n_params(dims: dict) -> int:
    return (dims["dim"] * dims["hidden"] + dims["hidden"]
            + dims["hidden"] * dims["n_classes"] + dims["n_classes"])


def kernel_flops(m: int, b: int, n: int) -> int:
    return 2 * (m + b) * n


def kernel_bytes(m: int, b: int, n: int) -> int:
    return 4 * ((m + b) * n + n)


def least_seconds(m: int, b: int, n: int, peaks: dict) -> float:
    return max(kernel_flops(m, b, n) / peaks["bf16_flops"],
               kernel_bytes(m, b, n) / peaks["hbm_bytes_per_s"])


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    names = bench_trace.op_names(red, PATTERN)
    if len(names) > 1:
        raise ValueError(f"{len(names)} device ops are named as the trust "
                         "kernel; one call a round is counted")
    seconds, calls = bench_trace.op_seconds(red, PATTERN)
    rounds = ctx["window"]["rounds"]
    if calls == 0 or seconds <= 0 or not rounds:
        return None
    b = ctx["spec"]["clustering"]["n_clusters"]
    n = n_params(ctx["dims"])
    per_round = [least_seconds(int(ctx["members"][c]), b, n, ctx["peaks"])
                 for c, _ in rounds]
    # one call per round; should the counts disagree, scale by the mean
    least = sum(per_round) / len(per_round) * calls
    return 100.0 * least / seconds
