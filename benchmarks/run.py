"""Benchmark harness — one entry per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV lines (harness contract).

  table1_<profile>   — paper Table 1 analogue: modeled latency; derived =
                       accuracy%, modeled power, weight bytes
  fig3_<profile>     — accuracy-vs-energy Pareto points
  fig4_adaptive      — merged-engine overhead + battery simulation
  kernel_*           — Pallas kernel microbenches (interpret-validated)

Heavy QAT results are cached under artifacts/repro/ (delete to retrain).
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    rows: list[tuple] = []

    # --- paper tables (cached QAT) ---
    from benchmarks import repro_cnn
    t1 = repro_cnn.run_table1()
    for name, r in t1["rows"].items():
        rows.append((f"table1_{name}", r["latency_us"],
                     f"acc={r['accuracy_pct']}%;power_w={r['power_w_model']};"
                     f"w_bytes={r['weight_bytes']}"))
        rows.append((f"fig3_{name}", r["latency_us"],
                     f"acc={r['accuracy_pct']}%;energy_j={r['energy_j_model']:.3e}"))
    f4 = repro_cnn.run_fig4(t1)
    rows.append(("fig4_adaptive", t1["latency_us"],
                 f"overhead_vs_largest={f4['merge']['overhead_vs_largest']*100:.1f}%;"
                 f"power_saving={f4['power_saving_pct']}%;"
                 f"acc_drop={f4['accuracy_drop_pct']}%;"
                 f"extra_classifications={f4['battery']['extra_classifications_pct']}%"))

    # --- kernels ---
    from benchmarks import kernel_bench
    rows.extend(kernel_bench.bench_qmatmul())
    rows.extend(kernel_bench.bench_qkv_attention())

    # --- serving decode loop (fused scan vs per-token host loop) ---
    from benchmarks import serving_bench
    rows.extend(serving_bench.run(serving_bench.QUICK_POINTS, iters=2))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()
