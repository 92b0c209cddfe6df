"""round_device_ms: device-busy time inside the runs of the fused scan's
program (``jit(run_k)``, what `run_scanned` compiles) over the rounds run
in the traced window (profiler trace).  Evaluations, restores and every
other program are left out."""

# the scanned segment's program: `run_scanned` jits ``run_k``, and the
# trace names the program's module after it (``jit_run_k``, perhaps with
# the program's id appended)
SCAN_MODULE = r"(?<![A-Za-z0-9])run_k(?![A-Za-z0-9])"


def read(ctx):
    red, rounds = ctx["trace"], len(ctx["window"]["rounds"])
    if red is None or rounds == 0:
        return None
    busy = red.busy_within(red.module_runs(SCAN_MODULE))
    if busy <= 0:
        return None
    return 1e3 * busy / rounds
