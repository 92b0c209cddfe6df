"""Benchmark driver: one function per paper table/figure plus the roofline
table from the dry-run artifacts.  Prints ``name,metric,value`` CSV."""
from __future__ import annotations

import sys


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    from . import attack_bench, figures, kernels_bench, roofline
    enable_compile_cache()
    quick = "--quick" in sys.argv
    print("benchmark,metric,value")
    if quick:
        figures.fig2_dqn_convergence(episodes=2)
        figures.fig3_dt_deviation(sim_seconds=4.0)
    else:
        for fn in figures.ALL:
            fn()
        attack_bench.main()
    kernels_bench.main()
    roofline.main()


if __name__ == "__main__":
    main()
