"""Readings that the check's limits are set from (see PERF.md).

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control [N]]

For each seed: the cell's first steps on the program, on that seed's
stream, then the reference following them (the program's readings).  With
``--control`` (on the first ``N`` seeds, or on all) also the reference in
bfloat16 in the program's place (the control) and with each planted fault
(`FAULTS`; "untrained" only where the controller is a DQN).  The
program's readings need the chip; the others run anywhere.  One JSON line
per seed and run; the limits in ``limits/<cell>.json`` lie between the largest
program reading and the smallest control or fault reading.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


FAULTS = ("half_batch", "action", "schedule", "energy", "untrained")


def readings(workload: str, seeds, control: int):
    """Build the cell once (the deployment does not depend on the seed),
    then for each seed: the first steps on that seed's stream and the
    readings of the program, the control and the planted faults."""
    import jax.numpy as jnp

    from bench import check
    from bench import harness as h

    cell = h.cell(workload)
    cfg, mix = h.config(cell["config"]), h.traffic(cell["traffic"])
    run = h.Run(workload=workload, seed=seeds[0], cfg=cfg, mix=mix,
                spec=h.spec_dict(cfg, mix))
    h.fresh_work_dir(run)
    h.build(run)
    drv = h.driver(mix["driver"])
    drv.setup(run)
    assign = run.engine.assign.copy()
    dims = h.dims(cfg)
    faults = [f for f in FAULTS
              if f != "untrained" or run.spec["controller"]["kind"] == "dqn"]
    variants = ([("control", jnp.bfloat16, None)]
                + [(f, jnp.float32, f) for f in faults])
    for i, seed in enumerate(seeds):
        if seed != run.seed:
            run.seed = seed
            drv.first_steps(run)
        first, key = run.first, h.episode_key(seed, 0)
        ref = check.reference_for(first, run.spec, dims, run.data,
                                  run.parts, assign, key)
        yield {"run": "program", "seed": seed,
               **check.numbers(first, ref, assign)}
        for name, dtype, fault in variants if i < control else ():
            cfirst, cassign = check.control_first(
                run.spec, dims, run.data, run.parts, len(first["rounds"]),
                first["clock"], dtype, key, fault)
            cref = check.reference_for(cfirst, run.spec, dims, run.data,
                                       run.parts, cassign, key)
            yield {"run": name, "seed": seed,
                   **check.numbers(cfirst, cref, cassign)}
    shutil.rmtree(run.work_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, nargs="?", const=10 ** 9,
                    default=0, help="control and faults on the first N seeds")
    args = ap.parse_args(argv)
    from bench.harness import enable_cache
    enable_cache()
    import jax
    kind = jax.devices()[0].device_kind
    seeds = [int(s) for s in args.seeds.split(",")]
    for row in readings(args.workload, seeds, args.control):
        print(json.dumps({"workload": args.workload, "device": kind, **row}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
