"""Training episodes of scanned segments (``engine.run_scanned``).

Every episode starts from the set-up state and runs segments of
``segment_rounds`` rounds, each one ``lax.scan`` on the device, synced and
evaluated on the host at its end, until the global model reaches the
configuration's accuracy target (an episode done) or the round cap (an
episode failed).  The first segment of set-up is the check's first steps.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

from bench import harness as h


def setup(run: h.Run) -> None:
    h.take_snapshot(run)
    first_steps(run)                                     # compiles
    # the window runs as users run the scan: without telemetry (the
    # compiled scan stays in the engine's cache)
    run.engine.set_obs(None)


def first_steps(run: h.Run) -> None:
    """Episode 0's first segment, through the window's own call."""
    K = run.mix["segment_rounds"]
    h.restore(run, 0)
    trace = run.engine.run_scanned(K, eval_final=True)
    run.first = h.first_of(trace.records[:K], run, "f32")
    h.restore(run, 0)


def window(run: h.Run, seconds: float, clock) -> dict:
    K = run.mix["segment_rounds"]
    cap = run.mix["round_cap"]
    target = run.cfg["target_acc"]
    out = h.new_window()
    t_start = clock()
    while clock() - t_start < seconds:
        run.episode += 1
        with TraceAnnotation("bench.restore"):
            h.restore(run, run.episode)
        done = 0
        while True:
            t0 = clock()
            with TraceAnnotation("bench.segment"):
                trace = run.engine.run_scanned(K, eval_final=True)
            t1 = clock()
            out["segments_s"].append(t1 - t0)
            out["rounds"].extend((r.cluster, r.a) for r in trace.records[:K])
            out["evals"] += 1
            done += K
            if trace.records[-1].acc >= target:
                out["attempted"] += 1
                out["reached_s"].append(t1 - t_start)
                break
            if done >= cap:
                out["attempted"] += 1
                out["failed"] += 1
                break
            if t1 - t_start >= seconds:
                break               # cut by the window's end: not counted
    out["window_s"] = clock() - t_start
    return out
