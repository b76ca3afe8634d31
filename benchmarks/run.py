"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes full tables to
experiments/tables/.  Budget via REPRO_BENCH_EPISODES (default 600/node;
paper budget 4,613 — see examples/llama_highperf_dse.py).

Each suite runs in its own process (``python -m benchmarks.run --suite
NAME``) and this parent never imports jax: a chip serves one process at
a time, and suites such as ``fleet`` spawn workers of their own.
"""
from __future__ import annotations

import argparse
import importlib
import subprocess
import sys
import time

SUITES = [
    ("table9", "tables", "table9_model_characteristics"),
    ("ceilings", "tables", "ceilings_eq21_24"),
    ("dse_throughput", "tables", "batch_eval_throughput"),
    ("table10_11", "tables", "tables10_11_per_node"),
    ("table12", "tables", "table12_power_breakdown"),
    ("table13", "tables", "table13_scaling_laws"),
    ("table15_16", "tables", "tables15_16_hetero"),
    ("table17_18", "tables", "tables17_18_cross_node"),
    ("table19", "tables", "table19_smolvlm"),
    ("table21", "tables", "table21_search_comparison"),
    ("roofline", "roofline", "bench_rows"),
    ("vec_env", "bench_vec_env", "bench_rows"),
    ("campaign", "bench_campaign", "bench_rows"),
    ("gated_campaign", "bench_gated_campaign", "bench_rows"),
    ("fleet", "bench_fleet", "bench_rows"),
    ("serve", "bench_serve", "bench_rows"),
    ("obs", "bench_obs", "bench_rows"),
    ("scenarios", "bench_scenarios", "bench_rows"),
]


def run_suite(name: str) -> None:
    """Run one suite in this process and print its CSV rows."""
    from benchmarks.common import emit
    module, fn = next((m, f) for n, m, f in SUITES if n == name)
    emit(getattr(importlib.import_module(f"benchmarks.{module}"), fn)())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", choices=[n for n, _, _ in SUITES],
                    help="run this one suite in-process")
    a = ap.parse_args()
    if a.suite:
        run_suite(a.suite)
        return
    print("# repro benchmarks")
    print("name,us_per_call,derived", flush=True)
    failures = 0
    t_start = time.time()
    for name, _, _ in SUITES:
        t0 = time.time()
        rc = subprocess.run([sys.executable, "-m", "benchmarks.run",
                             "--suite", name]).returncode
        if rc:
            failures += 1
            print(f"# {name} FAILED: exit {rc}", flush=True)
        else:
            print(f"# {name}: {time.time() - t0:.1f}s", flush=True)
    print(f"# total {time.time() - t_start:.1f}s, failures={failures}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
