"""Benchmark harness entry point: one section per paper table/figure, plus
the kernel traffic bench and the dry-run roofline table.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only table1,fig4

Each section prints CSV rows ``name,us_per_call,derived`` (common.print_rows)
so downstream tooling can grep a stable format.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (
    fig2_bo_scan,
    fig3_asha_scan,
    fig4_quant_scan,
    kernel_bench,
    obs_bench,
    serve_bench,
    table1_models,
    table2_fifo,
    table3_fusion,
    table4_ad_opts,
    table5_latency_energy,
    table6_scenarios,
)

SECTIONS = {
    "table1": table1_models.run,
    "table2": table2_fifo.run,
    "table3": table3_fusion.run,
    "table4": table4_ad_opts.run,
    "table5": table5_latency_energy.run,
    "table6": table6_scenarios.run,
    "fig2": fig2_bo_scan.run,
    "fig3": fig3_asha_scan.run,
    "fig4": fig4_quant_scan.run,
    "kernels": kernel_bench.run,
    "serve": serve_bench.run,
    "obs": obs_bench.run,
}


def _roofline_section():
    """Render the dry-run roofline tables (paper-faithful baseline AND the
    beyond-paper optimized re-sweep) if artifacts exist."""
    import os

    from repro.launch.roofline import analyze, load_artifacts, render_table

    if not os.path.isdir("artifacts/dryrun"):
        print("roofline: no artifacts/dryrun — run repro.launch.dryrun first")
        return []
    rows = [analyze(r) for r in load_artifacts("artifacts/dryrun")]
    print("--- paper-faithful baseline ---")
    print(render_table(rows, mesh="single", tag="baseline"))
    if any(r.tag == "optimized" for r in rows):
        print("\n--- beyond-paper optimized (MoE combine-then-psum + causal "
              "block-packing) ---")
        print(render_table(rows, mesh="single", tag="optimized"))
    if any(r.tag == "serving" for r in rows):
        print("\n--- decode cells under the serving layout "
              "(tponly + int8 weights) ---")
        print(render_table(rows, mesh="single", tag="serving"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    names = list(SECTIONS) + ["roofline"]
    if args.only:
        names = [n.strip() for n in args.only.split(",")]

    t0 = time.time()
    failures = []
    for name in names:
        try:
            if name == "roofline":
                from benchmarks.common import banner

                banner("Roofline table (from dry-run artifacts)")
                _roofline_section()
            else:
                SECTIONS[name]()
        except Exception:  # noqa: BLE001 — report all sections
            failures.append(name)
            traceback.print_exc()
    print(f"\n[benchmarks] done in {time.time()-t0:.1f}s; "
          f"{len(names)-len(failures)}/{len(names)} sections ok")
    if failures:
        print(f"[benchmarks] FAILED sections: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
