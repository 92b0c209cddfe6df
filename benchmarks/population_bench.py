"""Population-engine throughput: B federations, one program vs B programs.

The sweep cost model the population engine attacks: a B-member sweep run
sequentially pays B engine builds, B scan compiles, and B dispatch
streams; `repro.pop.PopulationEngine` pays one (vmapped) build + compile
and runs all members in a single device program.  The curve sweeps
B = 1 -> 64 seed replicates of one small federation and records, per B:

* ``sequential_s``   sum of standalone ``Federation.from_spec(spec_b)
                     .run_scanned(K)`` wall-clocks (build + compile + run
                     per member — what a naive sweep costs)
* ``population_s``   `PopulationEngine(specs)` build + ``run_scanned(K)``
                     wall-clock (the same work, one program)
* ``steady_s``       a second ``run_scanned(K)`` with the compiled
                     program cached — the long-sweep marginal cost
* ``speedup``        sequential_s / population_s

The acceptance gate (printed + recorded): >= 4x speedup at B >= 16 on
one CPU host.

    PYTHONPATH=src python benchmarks/population_bench.py [--fast] [--out=F]

Writes BENCH_population.json next to the repo root.
"""
from __future__ import annotations

import json
import sys
import time


def _base_spec(seed=29):
    from repro.api import (AggregatorSpec, ClusteringSpec, ControllerSpec,
                           FederationSpec, FleetSpec, TaskSpec)
    return FederationSpec(
        fleet=FleetSpec(n_devices=8),
        clustering=ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 3}),
        aggregator=AggregatorSpec("trust"),
        task=TaskSpec("mlp", {"n_samples": 256, "dim": 16, "hidden": 16}),
        execution="scanned", rounds=8, sim_seconds=1e9,
        local_batch=16, seed=seed)


def run(fast: bool = False, out_path: str = "BENCH_population.json"):
    from repro.api import Federation
    from repro.pop import PopulationEngine, PopulationSpec

    K = 6 if fast else 8
    sizes = (1, 4, 16) if fast else (1, 4, 16, 64)
    base = _base_spec()
    # process warmup: one throwaway standalone run, so neither arm's
    # first timing absorbs backend init / common-subcomputation caches
    # (each later Federation/PopulationEngine still pays its own scan
    # compile — fresh engine objects never share a jit cache entry)
    Federation.from_spec(base).run_scanned(2)
    curve = []
    for B in sizes:
        specs = PopulationSpec(base=base, replicates=B).expand()

        t0 = time.perf_counter()
        pop = PopulationEngine(specs)
        traces = pop.run_scanned(K)
        t_pop = time.perf_counter() - t0
        t0 = time.perf_counter()
        pop.run_scanned(K)
        t_steady = time.perf_counter() - t0

        t0 = time.perf_counter()
        refs = [Federation.from_spec(s).run_scanned(K) for s in specs]
        t_seq = time.perf_counter() - t0

        # free bit-parity check on the first timed segment
        key = lambda r: (r.t, r.round, r.cluster, r.a, r.loss,  # noqa: E731
                         r.acc, r.energy, r.agg_count)
        for b, (tr, ref) in enumerate(zip(traces, refs)):
            assert [key(r) for r in tr.records] == \
                [key(r) for r in ref.records], \
                f"B={B} member {b} diverged from its standalone run"

        row = {"B": B, "rounds": K,
               "sequential_s": round(t_seq, 3),
               "population_s": round(t_pop, 3),
               "steady_s": round(t_steady, 3),
               "steady_member_rounds_per_sec":
                   round(B * K / max(t_steady, 1e-9), 1),
               "speedup": round(t_seq / max(t_pop, 1e-9), 2)}
        curve.append(row)
        print(f"population,B={B},{row['population_s']}s vs "
              f"{row['sequential_s']}s seq ({row['speedup']}x, steady "
              f"{row['steady_member_rounds_per_sec']} member-rounds/s)")

    gate_rows = [r for r in curve if r["B"] >= 16]
    gate = {"threshold": 4.0,
            "speedup_at_16plus": max((r["speedup"] for r in gate_rows),
                                     default=None),
            "pass": any(r["speedup"] >= 4.0 for r in gate_rows)}
    out = {"bench": "population", "fast": fast, "curve": curve,
           "gate": gate}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"population,gate,>=4x@B>=16: "
          f"{'PASS' if gate['pass'] else 'FAIL'} "
          f"({gate['speedup_at_16plus']}x)")
    print(f"wrote {out_path}")
    return out


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(fast="--fast" in sys.argv,
        out_path=next((a.split("=", 1)[1] for a in sys.argv
                       if a.startswith("--out=")),
                      "BENCH_population.json"))


if __name__ == "__main__":
    main()
