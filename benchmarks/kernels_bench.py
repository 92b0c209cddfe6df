"""Kernel micro-benchmarks: Pallas (interpret) vs jnp oracle wall-time and,
more importantly on this CPU container, HBM-traffic *models* for the TPU
target (the numbers the §Perf analysis uses)."""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from repro.kernels import ref, trust_aggregate
from .common import timed


def bench_trust_aggregate():
    key = jax.random.PRNGKey(0)
    for C, N in [(16, 1 << 20), (64, 1 << 20)]:
        x = jax.random.normal(key, (C, N), jnp.float32)
        w = jax.nn.softmax(jax.random.normal(key, (C,)))
        us_ref, _ = timed(jax.jit(ref.trust_aggregate_ref), x, w)
        print(f"kernels,trust_aggregate_ref_C{C}_us,{us_ref:.1f}")
        # analytic TPU traffic: kernel = C*N*4 + N*4 bytes single pass
        bytes_kernel = (C + 1) * N * 4
        print(f"kernels,trust_aggregate_traffic_GB_C{C},{bytes_kernel/1e9:.3f}")


def bench_trust_aggregate_vs_jnp(out_json: str = "BENCH_trust_aggregate.json"):
    """Pallas (interpret on CPU) vs jnp oracle at simulator-realistic shapes:
    C = cluster sizes seen by the device-scale engine, N up to 10M params.
    The biggest input is ~1.07 GB (C=256, N=1M); the interpret path takes
    minutes at the largest shapes (it is a correctness oracle, not a speed
    path), so this bench is meant for explicit runs, not the smoke script."""
    shapes = [(8, 1 << 17), (8, 1 << 20), (8, 10_000_000),
              (64, 1 << 17), (64, 1 << 20),
              (256, 1 << 17), (256, 1 << 20)]
    results = []
    key = jax.random.PRNGKey(0)
    for C, N in shapes:
        x = jax.random.normal(key, (C, N), jnp.float32)
        w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (C,)))
        us_jnp, want = timed(jax.jit(ref.trust_aggregate_ref), x, w)
        us_pl, got = timed(
            lambda a, b: trust_aggregate(a, b, interpret=True), x, w)
        err = float(jnp.max(jnp.abs(got - want)))
        row = {
            "C": C, "N": N,
            "jnp_us": round(us_jnp, 1),
            "pallas_interpret_us": round(us_pl, 1),
            "max_abs_err": err,
            # analytic single-pass HBM traffic on the TPU target
            "tpu_traffic_GB": round((C + 1) * N * 4 / 1e9, 4),
            "tpu_us_at_800GBps": round((C + 1) * N * 4 / 800e9 * 1e6, 1),
        }
        results.append(row)
        print(f"kernels,trust_agg_C{C}_N{N},jnp_us={row['jnp_us']},"
              f"pallas_us={row['pallas_interpret_us']},err={err:.2e}")
        del x
    payload = {
        "bench": "trust_aggregate pallas(interpret,CPU) vs jnp oracle",
        "note": ("interpret=True executes the kernel body through the Pallas "
                 "CPU interpreter — a correctness path, not a speed path; "
                 "tpu_us_at_800GBps is the bandwidth-bound roofline for the "
                 "single-pass kernel on a v5e-class part"),
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "device": str(jax.devices()[0]),
        "results": results,
    }
    with open(out_json, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"kernels,bench_json,{out_json}")


def bench_attention_traffic_model():
    """Flash vs unfused attention HBM bytes at prefill_32k geometry."""
    S, H, d, B = 32768, 16, 256, 2      # per-chip gemma-7b prefill slice
    unfused = (B * H * S * S * 4) * 2 + B * S * H * d * 2 * 3
    flash = B * S * H * d * 2 * 4
    print(f"kernels,attn_unfused_traffic_GB,{unfused/1e9:.1f}")
    print(f"kernels,attn_flash_traffic_GB,{flash/1e9:.1f}")
    print(f"kernels,attn_traffic_reduction_x,{unfused/flash:.0f}")


def main(full: bool = False):
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    bench_trust_aggregate()
    bench_attention_traffic_model()
    if full:
        # multi-minute: sweeps the Pallas interpreter up to (8, 10M) and a
        # 1.07 GB (256, 1M) input, writing BENCH_trust_aggregate.json
        bench_trust_aggregate_vs_jnp()


if __name__ == "__main__":
    import sys
    main(full="--full" in sys.argv)
