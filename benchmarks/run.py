"""Benchmark runner: one section per paper table + engine micro-bench +
the machine probe.  Prints ``name,us_per_call,derived`` CSV lines per
row (scaffold contract) and writes results/bench/*.json."""
from __future__ import annotations

import json
import os
import sys
import time

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def _emit(section: str, rows: list[dict], time_key: str | None) -> None:
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, f"{section}.json"), "w") as f:
        json.dump(rows, f, indent=1, default=str)
    for r in rows:
        us = (r.get(time_key, 0.0) or 0.0) * 1e6 if time_key else 0.0
        derived = ";".join(
            f"{k}={v}" for k, v in r.items()
            if k != time_key and not isinstance(v, (list, dict))
        )
        print(f"{section}/{r.get('query', r.get('bench', r.get('arch', '?')))},"
              f"{us:.1f},{derived}")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    from . import kernels_bench, roofline, tables

    enable_compile_cache()

    sections = sys.argv[1:] or [
        "table2", "table3", "table4", "table5", "iterations",
        "kernels", "roofline",
    ]
    t0 = time.time()
    if "table2" in sections:
        _emit("table2_soi_vs_ma", tables.table2_soi_vs_ma(), "t_soi_dense")
    if "table3" in sections:
        _emit("table3_pruning", tables.table3_pruning(), "t_sparqlsim")
    if "table4" in sections:
        _emit("table4_rdfox_style", tables.table4_join_pruned_selectivity(),
              "t_db_pruned")
    if "table5" in sections:
        _emit("table5_virtuoso_style", tables.table5_join_pruned_syntactic(),
              "t_db_pruned")
    if "iterations" in sections:
        _emit("iterations_sect53", tables.iterations_analysis(), None)
    if "kernels" in sections:
        _emit("kernels_micro", kernels_bench.bitmm_micro(), "t_pallas_interpret")
        _emit("kernels_segor", kernels_bench.segor_micro(), "t_packed_words")
    if "roofline" in sections:
        # ERT-style machine probe (DESIGN.md 13.1): persists the MachineSpec
        # under results/machine/ for the calibrated cost model + perf gate,
        # and mirrors it into results/bench/ like every other section
        spec, _ = roofline.probe(fast=True)
        from repro.engine import machine as machine_mod

        machine_mod.save_spec(spec)
        _emit("machine_probe", [dict(bench="machine", **spec.to_json())], None)
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
