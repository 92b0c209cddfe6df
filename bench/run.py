"""Run one benchmark cell once, on the accelerator it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's deployment from ``--seed`` through
``Federation.from_spec``, drives its first steps through the window's own
call (which compiles every shape the window uses), and keeps what they
produced for the check.  The window then drives the cell's driver for
``--seconds`` (``--trace 1``: a shorter traced window, see the traffic
file's ``trace_seconds``).  After it, the plain reference follows the
first steps and decides ``correct``.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, last, ``checks``: each compared number with its limit,
also printed as the last lines of standard error.

Off a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()      # set-up is timed from here

import argparse                # noqa: E402
import gc                      # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class NoAccelerator(RuntimeError):
    pass


def _device(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs


def _peak_bytes(devs) -> int:
    stats = [d.memory_stats() or {} for d in devs]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def _all_spans(spans):
    for sp in spans:
        yield sp
        yield from _all_spans(sp.children)


def peaks(kind: str) -> dict:
    from bench import harness as h
    table = h.load_json("peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             cfg=None, devices=None, clock=None,
             work_dir: str = "") -> dict:
    """One run of ``workload``.  ``devices`` None looks for the chips the
    cell asks for; tests pass the CPU devices (and a small ``cfg``, and a
    ``work_dir`` of their own)."""
    import jax

    from bench import check
    from bench import harness as h
    from bench import trace as bench_trace

    clock = clock or h.Clock()
    bench = h.benchmark()
    cell = h.cell(workload, bench)
    devs = devices if devices is not None else _device(cell["chips"])
    cfg = cfg or h.config(cell["config"])
    mix = h.traffic(cell["traffic"])
    run = h.Run(workload=workload, seed=seed, cfg=cfg, mix=mix,
                spec=h.spec_dict(cfg, mix), work_dir=work_dir)
    h.fresh_work_dir(run)
    drv = h.driver(mix["driver"])

    h.build(run)
    built_s = clock()
    drv.setup(run)
    setup_s = clock()
    compile_s = sum(sp.dur_s for sp in _all_spans(run.obs.spans.finished)
                    if sp.name == "compile")

    red = None
    if trace:
        tdir = os.path.join(run.work_dir, "trace")
        jax.profiler.start_trace(tdir)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                res = drv.window(run, min(seconds, mix["trace_seconds"]),
                                 clock)
        finally:
            jax.profiler.stop_trace()
    else:
        res = drv.window(run, seconds, clock)
    peak = _peak_bytes(devs)

    # what the check and the metrics need, then the program's state goes
    first, members = run.first, h.real_members(run)
    assign = run.engine.assign.copy()
    samples = h.samples(run, res["rounds"])
    run.fed = run.obs = run.snapshot = None
    run.drv.clear()
    gc.collect()
    if trace:
        red = bench_trace.load(tdir)

    t_ref = clock()
    ref = check.reference_for(first, run.spec, h.dims(cfg), run.data,
                              run.parts, assign, h.episode_key(seed, 0))
    values = check.numbers(first, ref, assign)
    print(f"set-up: data and build {built_s:.3f} s, first steps "
          f"{setup_s - built_s:.3f} s (compile {compile_s:.3f} s); "
          f"reference {clock() - t_ref:.3f} s ({devs[0].device_kind})",
          file=sys.stderr)
    ok, rows = check.judge(values, h.limits(workload))

    ctx = {"workload": workload, "cfg": cfg, "mix": mix, "spec": run.spec,
           "dims": h.dims(cfg), "window": res, "samples": samples,
           "members": members, "setup_s": setup_s, "compile_s": compile_s,
           "trace": red,
           "peaks": peaks(devs[0].device_kind) if trace else None}
    metrics = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        v = h.metric_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(ok), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if red is not None:
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = bench_trace.breakdown(red)
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    shutil.rmtree(run.work_dir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench.harness import Clock, enable_cache
    enable_cache()
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), clock=Clock(_T0))
    except NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    kind = out["device"]["kind"]
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"({kind})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
