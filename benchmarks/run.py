"""Benchmark harness: one function per paper table/figure (+ extensions).

Each prints a ``name,us_per_call,derived`` CSV line followed by detail
rows. Usage: PYTHONPATH=src python -m benchmarks.run [name ...]
"""

import sys


def main() -> None:
    from benchmarks import (
        analysis_bench,
        design_scale,
        design_service,
        engine_parity,
        fig4_fmmd_variants,
        fig5_training,
        gossip_traffic,
        lemma31_validation,
        phase_routing,
        priced_training,
        roofline_bench,
        rollout_scale,
        route_scale,
        sim_scale,
        stochastic_routing,
        table1_runtimes,
    )
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    all_benches = {
        "fig4_fmmd_variants": fig4_fmmd_variants.main,
        "table1_runtimes": table1_runtimes.main,
        "fig5_training": fig5_training.main,
        "priced_training": priced_training.main,
        "lemma31_validation": lemma31_validation.main,
        "roofline_bench": roofline_bench.main,
        "gossip_traffic": gossip_traffic.main,
        "sim_scale": sim_scale.main,
        "route_scale": route_scale.main,
        "phase_routing": phase_routing.main,
        "stochastic_routing": stochastic_routing.main,
        "engine_parity": engine_parity.main,
        "rollout_scale": rollout_scale.main,
        "design_scale": design_scale.main,
        # argv pinned: harness arguments are bench names, not flags
        "design_service": lambda: design_service.main([]),
        "analysis_bench": analysis_bench.main,
    }
    names = sys.argv[1:] or list(all_benches)
    for name in names:
        all_benches[name]()


if __name__ == "__main__":
    main()
