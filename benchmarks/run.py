"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per benchmark.

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run --quick    # shorter FL runs
  PYTHONPATH=src python -m benchmarks.run --only fig3,table5
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

from benchmarks import (
    active_bench,
    async_bench,
    codec_pareto,
    engine_bench,
    engine_roofline,
    ext_beyond_paper,
    hetero_bench,
    fig3_cache_sim,
    fig4_era_curves,
    fig5_era_vs_enhanced,
    fig8_comparison,
    fig11_caching_plugin,
    fig12_cache_duration,
    fig13_beta_ablation,
    fig16_partial_participation,
    fig18_convergence_proxy,
    kernels_bench,
    table4_centralized,
    table5_comm_costs,
)
from benchmarks._common import emit
from repro import compile_cache

SUITE = {
    "fig3": (fig3_cache_sim, {}),
    "fig4": (fig4_era_curves, {}),
    "table4": (table4_centralized, {}),
    "table5": (table5_comm_costs, {}),
    "fig5": (fig5_era_vs_enhanced, {"rounds": 60}),
    "fig8": (fig8_comparison, {"rounds": 60}),
    "fig11": (fig11_caching_plugin, {"rounds": 60}),
    "fig12": (fig12_cache_duration, {"rounds": 80}),
    "fig13": (fig13_beta_ablation, {"rounds": 50}),
    "fig16": (fig16_partial_participation, {"rounds": 50}),
    "fig18": (fig18_convergence_proxy, {"rounds": 80}),
    "kernels": (kernels_bench, {}),
    "engine": (engine_bench, {}),
    "active": (active_bench, {}),
    "async": (async_bench, {}),
    "engine_roofline": (engine_roofline, {}),
    "codec_pareto": (codec_pareto, {}),
    "hetero": (hetero_bench, {}),
    "ext": (ext_beyond_paper, {"rounds": 80}),
}

# benchmarks whose rows feed the perf-regression gate: --out-dir writes
# their BENCH_<file>.json next to each other (codec_pareto keeps the
# short "codec" document name)
BENCH_FILES = {
    "engine": "engine",
    "kernels": "kernels",
    "codec_pareto": "codec",
    "engine_roofline": "engine_roofline",
    "active": "active",
    "async": "async",
}

QUICK_ROUNDS = 25


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--out-dir", default="",
                    help="write BENCH_<name>.json per gated benchmark here")
    args = ap.parse_args()

    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(SUITE)
    print(f"# compile cache: {compile_cache.enable()}", file=sys.stderr)
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        mod, kw = SUITE[name]
        if args.quick and "rounds" in kw:
            kw = {**kw, "rounds": QUICK_ROUNDS}
        # modules with a dedicated smoke mode take quick= directly
        if args.quick and "quick" in inspect.signature(mod.run).parameters:
            kw = {**kw, "quick": True}
        t0 = time.time()
        try:
            rows = mod.run(**kw)
            emit(rows)
            if args.out_dir and name in BENCH_FILES:
                import os

                from benchmarks._common import write_bench

                os.makedirs(args.out_dir, exist_ok=True)
                doc = BENCH_FILES[name]
                write_bench(os.path.join(args.out_dir, f"BENCH_{doc}.json"),
                            doc, rows, quick=args.quick)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED:\n{traceback.format_exc()}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
