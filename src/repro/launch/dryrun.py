import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers and compiles against the production meshes.

For each combination this lowers + compiles the step, prints
memory_analysis() (proves it fits) and cost_analysis() (FLOPs/bytes for
EXPERIMENTS.md §Roofline), parses collective traffic from the optimized HLO,
and appends a JSON record to --out.

Usage:
    python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all [--multi-pod]
"""
import argparse
import json
import time
import traceback

import jax

from ..configs import all_arch_ids
from .hlo_stats import analyze_module, op_histogram
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh, n_chips
from .plans import SHAPES, applicable, make_plan


def run_one(arch: str, shape: str, multi_pod: bool, verbose: bool = True):
    rec = {"arch": arch, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    skip = applicable(arch, shape)
    if skip:
        rec["status"] = skip
        return rec
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = make_plan(arch, shape, mesh)
    try:
        with mesh:
            jitted = jax.jit(plan.step_fn, in_shardings=plan.in_shardings,
                             out_shardings=plan.out_shardings,
                             donate_argnums=plan.donate)
            lowered = jitted.lower(*plan.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        stats = analyze_module(hlo)          # loop-aware (hlo_stats.py)
        coll = dict(stats.collectives)
        coll["total"] = sum(stats.collectives.values())
        coll["count"] = stats.n_collective_ops
        hist = op_histogram(hlo)

        chips = n_chips(mesh)
        flops = stats.flops
        bytes_accessed = stats.bytes_traffic
        rec.update({
            "status": "ok",
            "kind": plan.kind,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "chips": chips,
            # memory_analysis is per-device on the host backend
            "bytes_per_device": {
                "argument": mem.argument_size_in_bytes,
                "output": mem.output_size_in_bytes,
                "temp": mem.temp_size_in_bytes,
                "generated_code": mem.generated_code_size_in_bytes,
                "total": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                          + mem.temp_size_in_bytes),
            },
            # NOTE: all quantities are PER-DEVICE (SPMD module), loop-aware
            # via hlo_stats.analyze_module (XLA's own cost_analysis counts
            # while bodies once — verified — so it is kept only as a
            # reference field).  The §Roofline division by `chips` is thus
            # already applied: t = per_device_quantity / per_chip_rate.
            "hlo_flops_per_dev": flops,
            "hlo_bytes_per_dev": bytes_accessed,
            "xla_cost_flops_raw": float(cost.get("flops", 0.0)),
            "collectives": coll,
            "op_hist": hist,
            "t_compute": flops / PEAK_FLOPS_BF16,
            "t_memory": bytes_accessed / HBM_BW,
            "t_collective": coll["total"] / ICI_BW,
        })
        if verbose:
            print(f"[{rec['mesh']}] {arch} x {shape}: OK "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
            print("  memory_analysis:", rec["bytes_per_device"])
            print(f"  cost_analysis (per-dev): flops={flops:.3e} bytes={bytes_accessed:.3e}")
            print(f"  collectives: {coll}")
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["status"] = f"FAIL: {type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[{rec['mesh']}] {arch} x {shape}: FAILED — {e}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="dryrun_results.jsonl")
    args = ap.parse_args()

    archs = all_arch_ids() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]

    records = []
    for a in archs:
        for s in shapes:
            rec = run_one(a, s, args.multi_pod)
            records.append(rec)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    ok = sum(r["status"] == "ok" for r in records)
    skipped = sum(r["status"].startswith("skip") for r in records)
    print(f"\n{ok} ok / {skipped} skipped / "
          f"{len(records) - ok - skipped} failed of {len(records)}")


if __name__ == "__main__":
    main()
