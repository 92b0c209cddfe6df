"""Robustness bench: (fault mode x aggregator) grid -> BENCH_robustness.json.

The paper claims trust-weighted aggregation (Eqns 4-6) "effectively
resists malicious attacks".  This bench injects declarative faults
(`FederationSpec.faults`) *inside* the jitted round and measures the final
metric with and without trust, on both workloads:

* ``mlp``                  non-IID classification; metric = accuracy
* ``autoencoder-anomaly``  reconstruction anomaly detection; metric = AUC
  (labels never enter the loss, so ``label_flip``-style attacks are
  no-ops — ``poison`` corrupts the *inputs*, the only attack surface)

Fault modes: ``clean`` (control), ``sign_flip`` / ``gaussian`` Byzantine
update corruption, and ``poison`` (additive input noise on a static
device subset).  Aggregators: ``trust`` vs ``fedavg`` — the grid's delta
column is the trust recovery the acceptance gate checks.  A cell whose
training diverges to NaN (fedavg frequently does under the strongest
attacks — that is the result) scores 0.0 with ``diverged: true``.

The trust/fedavg cells of each fault mode are structurally identical, so
they run as one B=2 `repro.pop.PopulationEngine` program (the aggregator
flag is a lifted per-member scalar); the sequential per-spec runs are
kept as the timing baseline and bit-parity check, and the per-cell
wall-clock delta lands in the output's ``timing`` table.

    PYTHONPATH=src python benchmarks/attack_bench.py [--fast] [--out F]

Prints ``attack,<workload>/<fault>/<agg>,<metric>`` rows and writes the
grid + per-fault recovery summary to BENCH_robustness.json.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time

# per-workload fault strengths: attacks are meaningful only relative to a
# workload's own gradient scale and fragility (the autoencoder diverges
# under magnitudes the classifier shrugs off), so each workload gets the
# strongest settings its training still survives *with* trust
FAULTS = {
    "mlp": {
        "clean":     {},
        "sign_flip": {"corrupt_mode": "sign_flip", "corrupt_frac": 0.25,
                      "corrupt_scale": 4.0},
        "gaussian":  {"corrupt_mode": "gaussian", "corrupt_frac": 0.25,
                      "corrupt_scale": 8.0},
        "poison":    {"poison_frac": 0.375, "poison_scale": 8.0},
    },
    "autoencoder-anomaly": {
        "clean":     {},
        "sign_flip": {"corrupt_mode": "sign_flip", "corrupt_frac": 0.25,
                      "corrupt_scale": 3.0},
        "gaussian":  {"corrupt_mode": "gaussian", "corrupt_frac": 0.25,
                      "corrupt_scale": 8.0},
        "poison":    {"poison_frac": 0.375, "poison_scale": 4.0},
    },
}
AGGREGATORS = ("trust", "fedavg")


def _same(a, b):
    # bitwise trace parity modulo NaN: a diverged member NaNs at the same
    # round in both arms, and NaN != NaN would mask that agreement
    return a == b or (a != a and b != b)


def _specs(fast: bool):
    from repro.api import (AggregatorSpec, ClusteringSpec, ControllerSpec,
                           FederationSpec, FleetSpec, TaskSpec)
    mlp = FederationSpec(
        fleet=FleetSpec(n_devices=16),
        clustering=ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 5}),
        aggregator=AggregatorSpec("trust"),
        execution="scanned", rounds=12 if fast else 40, sim_seconds=1e9,
        seed=11)
    ae = FederationSpec(
        fleet=FleetSpec(n_devices=16),
        clustering=ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 3}),
        aggregator=AggregatorSpec("trust"),
        task=TaskSpec("autoencoder-anomaly",
                      {"n_samples": 2048, "dim": 32,
                       "n_types": 8, "hidden": 64, "code": 8}),
        execution="scanned", rounds=16, sim_seconds=1e9,
        local_batch=32, lr=0.1, seed=11)
    return {"mlp": mlp, "autoencoder-anomaly": ae}


def run(fast: bool = False, out_path: str = "BENCH_robustness.json"):
    from repro.api import Federation
    from repro.faults import FaultSpec
    from repro.pop import PopulationEngine

    grid = []
    timing = []
    for workload, base in _specs(fast).items():
        for fault, fkw in FAULTS[workload].items():
            # the trust/fedavg cells of one fault mode are structurally
            # identical (the aggregator flag is a lifted scalar), so the
            # population engine runs the whole cell as ONE vmapped
            # program — one compile instead of one per aggregator
            specs = [dataclasses.replace(
                base,
                aggregator=dataclasses.replace(base.aggregator, kind=agg),
                faults=FaultSpec(**fkw)) for agg in AGGREGATORS]
            t0 = time.perf_counter()
            traces = PopulationEngine(specs).run_scanned(base.rounds)
            t_pop = time.perf_counter() - t0
            t0 = time.perf_counter()
            refs = [Federation.from_spec(s).run_scanned(s.rounds)
                    for s in specs]
            t_seq = time.perf_counter() - t0
            timing.append({"workload": workload, "fault": fault,
                           "members": len(specs),
                           "population_s": round(t_pop, 3),
                           "sequential_s": round(t_seq, 3),
                           "wall_clock_delta_s": round(t_seq - t_pop, 3),
                           "speedup": round(t_seq / max(t_pop, 1e-9), 2)})
            for agg, tr, ref in zip(AGGREGATORS, traces, refs):
                rec, rref = tr.records[-1], ref.records[-1]
                assert _same(rec.loss, rref.loss) and \
                    _same(rec.acc, rref.acc), \
                    f"population/{workload}/{fault}/{agg} diverged from " \
                    "the sequential reference"
                loss_f, acc_f = float(rec.loss), float(rec.acc)
                diverged = acc_f != acc_f or loss_f != loss_f
                row = {"workload": workload, "fault": fault,
                       "aggregator": agg, "rounds": base.rounds,
                       "final_metric": 0.0 if acc_f != acc_f else acc_f,
                       "final_loss": None if loss_f != loss_f else loss_f,
                       "diverged": diverged}
                grid.append(row)
                print(f"attack,{workload}/{fault}/{agg},"
                      f"{row['final_metric']:.4f}"
                      f"{' (diverged)' if diverged else ''}")

    by = {(r["workload"], r["fault"], r["aggregator"]): r["final_metric"]
          for r in grid}
    recovery = [
        {"workload": w, "fault": f,
         "trust": by[(w, f, "trust")], "fedavg": by[(w, f, "fedavg")],
         "trust_recovery": round(by[(w, f, "trust")]
                                 - by[(w, f, "fedavg")], 4)}
        for w in ("mlp", "autoencoder-anomaly")
        for f in FAULTS[w] if f != "clean"]
    out = {"bench": "robustness", "fast": fast, "grid": grid,
           "recovery": recovery, "timing": timing,
           "wall_clock_delta_s": round(sum(t["wall_clock_delta_s"]
                                           for t in timing), 3)}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    for r in recovery:
        print(f"attack,recovery/{r['workload']}/{r['fault']},"
              f"{r['trust_recovery']:+.4f}")
    for t in timing:
        print(f"attack,walltime/{t['workload']}/{t['fault']},"
              f"{t['population_s']:.2f}s vs {t['sequential_s']:.2f}s "
              f"seq ({t['speedup']}x)")
    print(f"wrote {out_path}")
    return out


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(fast="--fast" in sys.argv,
        out_path=next((a.split("=", 1)[1] for a in sys.argv
                       if a.startswith("--out=")),
                      "BENCH_robustness.json"))


if __name__ == "__main__":
    main()
