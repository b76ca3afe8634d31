"""Benchmark: W-worker fleet vs W=1 on the same campaign grid/budget.

Runs the same (workload x node x mode) grid twice through the fleet
launcher — so both sides pay identical spawn/reconcile overhead — once
with ONE worker and once with ``REPRO_BENCH_FLEET_WORKERS`` (default 4)
workers, and reports cells/hour for both plus the speedup.  The grid is
packed into single-cell batches (``max_envs == lanes``) so the deal
stays balanced at any worker count.

A warmup fleet populates the checkout's persistent compile cache first
(``repro.launch.compile_cache``; every worker uses it), so both timed runs
measure steady-state search throughput rather than XLA compiles.  Both
legs run with the elastic supervisor and worker lease heartbeats
enabled (the production path), so the floor keeps those overheads
honest.

Floor (enforced by ``benchmarks.check_floors``): speedup >= 2.5x at
W=4 on a machine with >= 8 cores, scaled by the achievable parallelism
below that — ONE worker's search loop already pipelines host work with
async XLA dispatch and so saturates ~2 cores by itself (measured: W=1
busy/batch quadruples when 4 workers share 2 cores), so the fleet can
only multiply throughput by the number of ~2-core worker slots the
machine offers: ``floor = 2.5 * min(W, max(1, cores // 2)) / W``
(the ``max(1, ...)`` keeps a 1-core box gated at W=1-slot).  The table
records ``workers`` and ``cores`` so the gate is self-describing.
Writes ``experiments/tables/bench_fleet.json``.

The budget must keep the run compute-dominated: each worker process pays
a few seconds of interpreter/jax startup, so a tiny grid measures spawn
overhead, not search throughput (at the default 512 ep/cell the W=1 leg
runs minutes and startup is noise).  Run it on an otherwise idle machine:
both legs are wall-clock timed.

Run:  PYTHONPATH=src python -m benchmarks.bench_fleet
Knobs: REPRO_BENCH_FLEET_WORKERS (default 4), .._EPISODES (default 512),
       .._LANES (8), .._ARCH (smollm-135m), .._MODES (2).
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time

from repro.ppa.nodes import NODES

WORKERS = int(os.environ.get("REPRO_BENCH_FLEET_WORKERS", "4"))
EPISODES = int(os.environ.get("REPRO_BENCH_FLEET_EPISODES", "512"))
LANES = int(os.environ.get("REPRO_BENCH_FLEET_LANES", "8"))
ARCH = os.environ.get("REPRO_BENCH_FLEET_ARCH", "smollm-135m")
N_MODES = int(os.environ.get("REPRO_BENCH_FLEET_MODES", "2"))
FLEET_FLOOR = 2.5


def scaled_floor(workers: int, cores: int) -> float:
    """The committed floor, scaled by achievable parallelism.

    A single worker process already uses ~2 cores (host/device pipeline
    overlap), so a machine offers ``cores // 2`` full-speed worker slots:
    2.5x at W=4 needs >= 8 cores, a 4-core runner is gated at 1.25x, and
    a 2-core box cannot beat the pipelined W=1 baseline at all."""
    slots = max(1, cores // 2)
    return round(FLEET_FLOOR * min(workers, slots) / max(1, workers), 3)


def _spec(name: str, episodes: int = EPISODES):
    from repro.campaign import CampaignSpec
    return CampaignSpec(
        name=name, workloads=[ARCH], nodes=list(NODES),
        modes=["high_perf", "low_power"][:N_MODES], episodes=episodes,
        lanes=LANES, max_envs=LANES,      # single-cell batches: fair deal
        seed=0, checkpoint_every=0)


def bench_rows():
    from repro.launch.fleet import run_fleet

    spec = _spec("bench")
    n_cells = spec.n_cells
    tmp = tempfile.mkdtemp(prefix="bench_fleet_")
    try:
        # warmup: one single-worker fleet at a small budget compiles the
        # (B = lanes) step + learner update once into the shared cache;
        # every timed worker process then loads instead of compiling
        run_fleet(os.path.join(tmp, "warm"),
                  _spec("warm", episodes=max(2 * LANES, 64)), workers=1,
                  progress=lambda m: None)

        t0 = time.time()
        s1 = run_fleet(os.path.join(tmp, "w1"), spec, workers=1,
                       progress=lambda m: None)
        w1_s = time.time() - t0
        assert s1.all_done(), "W=1 fleet did not complete"

        t0 = time.time()
        sN = run_fleet(os.path.join(tmp, "wN"), spec, workers=WORKERS,
                       progress=lambda m: None)
        wN_s = time.time() - t0
        assert sN.all_done(), f"W={WORKERS} fleet did not complete"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    def busy(store):
        stats = store.manifest.get("fleet", {}).get("worker_stats", {})
        return round(sum(v.get("busy_s", 0.0) for v in stats.values()), 2)

    cph_1 = n_cells / (w1_s / 3600.0)
    cph_n = n_cells / (wN_s / 3600.0)
    speedup = cph_n / cph_1
    cores = os.cpu_count() or 1
    out_dir = os.environ.get("REPRO_BENCH_OUT", "experiments/tables")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "bench_fleet.json"), "w") as f:
        from repro.campaign.store import DEFAULT_LEASE_TTL_S
        json.dump({"n_cells": n_cells, "episodes_per_cell": EPISODES,
                   "lanes": LANES, "arch": ARCH, "workers": WORKERS,
                   "supervised": True, "lease_ttl_s": DEFAULT_LEASE_TTL_S,
                   "cores": cores, "w1_s": w1_s, "wN_s": wN_s,
                   "w1_busy_s": busy(s1), "wN_busy_s": busy(sN),
                   "cells_per_hour_w1": cph_1,
                   "cells_per_hour_fleet": cph_n,
                   "speedup": speedup,
                   "floor": scaled_floor(WORKERS, cores)}, f, indent=1)
    return [
        ("fleet_w1", 1e6 * w1_s / (n_cells * EPISODES),
         f"{cph_1:.1f} cells/h"),
        (f"fleet_w{WORKERS}", 1e6 * wN_s / (n_cells * EPISODES),
         f"{cph_n:.1f} cells/h"),
        ("fleet_speedup", 0.0, f"{speedup:.2f}x"),
    ]


def main() -> None:
    cores = os.cpu_count() or 1
    print(f"# fleet benchmark ({WORKERS} workers on {cores} cores, "
          f"{EPISODES} ep/cell, lanes={LANES})")
    print("name,us_per_call,derived")
    rows = bench_rows()
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
    speedup = float(rows[-1][2][:-1])
    floor = scaled_floor(WORKERS, cores)
    print(f"# speedup {speedup:.2f}x "
          f"({'PASS' if speedup >= floor else 'FAIL'}: floor {floor}x = "
          f"2.5 * min(W, max(1, cores//2))/W)")


if __name__ == "__main__":
    main()
