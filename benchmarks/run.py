"""Benchmark entrypoint: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV. Rounds are reduced by default;
raise --rounds for the full-fidelity sweep. Every selected benchmark runs
in this one process (a chip belongs to one process), over the devices it
sees, with the persistent compile cache placed by ``repro.launch.cache``."""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=12,
                    help="FL rounds per simulation benchmark")
    ap.add_argument("--only", type=str, default=None,
                    help="comma list: table1,table1b,fig3,fig4,fig5,fig7,"
                         "fig8,kernels,round_engine,sharded_engine")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    t0 = time.time()

    def want(name: str) -> bool:
        return only is None or name in only

    if want("fig5"):
        from benchmarks import fig5_shapley
        fig5_shapley.run()
    if want("kernels"):
        from benchmarks import kernels_bench
        kernels_bench.run()
    if want("fig3"):
        from benchmarks import fig3_cost
        fig3_cost.run(rounds=args.rounds)
    if want("table1"):
        from benchmarks import table1_attacks
        table1_attacks.run(rounds=args.rounds)
    if want("table1b"):
        from benchmarks import table1_attacks
        table1_attacks.run_adaptive(rounds=args.rounds)
    if want("fig4"):
        from benchmarks import fig4_robustness
        fig4_robustness.run(rounds=args.rounds)
    if want("fig7"):
        from benchmarks import fig7_lambda_table2
        fig7_lambda_table2.run(rounds=args.rounds)
    if want("fig8"):
        from benchmarks import fig8_compression_pareto
        fig8_compression_pareto.run(rounds=args.rounds)
    if want("round_engine"):
        from benchmarks import bench_round_engine
        bench_round_engine.run(rounds=args.rounds)
    if want("sharded_engine"):
        from benchmarks import bench_sharded_engine
        bench_sharded_engine.run(rounds=max(4, args.rounds // 2))

    print(f"# total_wall_s={time.time() - t0:.1f}", file=sys.stderr)


if __name__ == "__main__":
    main()
