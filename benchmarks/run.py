"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig5,tab4,...]
    PYTHONPATH=src python -m benchmarks.run --json BENCH_roundtime.json

Prints ``name,value,derived`` CSV rows (value units are in each name).
``--json`` runs the sequential/batched round-time + aggregation regression
suite and writes the numbers to the given path for ``scripts/check_bench.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

BENCHES = [
    ("loc", "benchmarks.bench_loc", "Table I + Table V: lines of code"),
    ("overhead", "benchmarks.bench_overhead", "Table VI: abstraction overhead"),
    ("greedyada", "benchmarks.bench_greedyada", "Fig. 5: GreedyAda speedup"),
    ("heterogeneity", "benchmarks.bench_heterogeneity",
     "Fig. 6/10/11: straggler variance"),
    ("scalability", "benchmarks.bench_scalability", "Fig. 7: scalability"),
    ("latency", "benchmarks.bench_latency", "Fig. 8: distribution latency"),
    ("noniid", "benchmarks.bench_noniid", "Table IV: IID vs non-IID"),
    ("fedreid", "benchmarks.bench_fedreid", "Fig. 9: FedReID case study"),
    ("compression", "benchmarks.bench_compression",
     "STC/int8 compression (Table V support)"),
    ("roundtime", "benchmarks.bench_batched",
     "Sequential vs batched execution + streaming aggregation"),
    ("fused", "benchmarks.bench_fused",
     "Fused whole-round program vs staged batched path + roofline budget"),
    ("distributed", "benchmarks.bench_distributed",
     "Mesh-sharded cohort (resources.distributed) per-shard round times"),
    ("async", "benchmarks.bench_async",
     "Async FedBuff event loop vs synchronous rounds (simulated wall-clock)"),
    ("faults", "benchmarks.bench_faults",
     "Fault injection: zero-overhead when off, degraded-round throughput"),
    ("llm", "benchmarks.bench_llm",
     "Federated LLM fine-tuning: LoRA vs full-delta round time + wire bytes"),
    ("roofline", "benchmarks.bench_roofline", "§Roofline table from dry-run"),
]


def run_json(path: str) -> None:
    """Regression mode: emit sequential/batched round-time, aggregation,
    and compressed in-program-vs-gathering round numbers as JSON
    (consumed by scripts/check_bench.py)."""
    from benchmarks import (bench_batched, bench_compression, bench_faults,
                            bench_fused, bench_llm, bench_scalability)
    data = bench_batched.collect()
    data.update(bench_compression.collect_rounds())
    data.update(bench_faults.collect())
    data.update(bench_fused.collect())
    data.update(bench_llm.collect())
    data.update(bench_scalability.collect())
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
    print(f"# wrote {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated bench keys to run")
    ap.add_argument("--json", default="", metavar="BENCH_roundtime.json",
                    help="write round-time regression numbers to PATH and exit")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.json:
        run_json(args.json)
        return
    only = {s.strip() for s in args.only.split(",") if s.strip()}

    print("name,value,derived")
    failures = 0
    for key, module, desc in BENCHES:
        if only and key not in only:
            continue
        print(f"# === {key}: {desc} ===")
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["main"])
            mod.main()
            print(f"# {key} done in {time.time() - t0:.1f}s")
        except Exception:
            failures += 1
            print(f"# {key} FAILED:")
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
