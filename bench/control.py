"""Readings that set the limits of ``correct``: the program's own, and those
of each control its driver names (``CONTROLS``: the plain reference one
precision step below the stated one, or a planted fault), put in the
program's place and judged by the driver's own ``check``.

    python3 -m bench.control --workload <name> --seeds 1,2,3 \\
        [--seconds 3] [--traffic '{"key": value}'] [--out readings.jsonl]

One set-up, then for each seed a window of ``--seconds`` and the checks.
``--traffic`` merges keys into the cell's traffic (for instance other
campaign seeds).  Prints one JSON line per seed: the program's numbers
and whether they pass, and each control's.  The benchmark's own runs
never run this; ``tests/bench`` runs it at a size the CPU holds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def readings(cell, seeds, work: str, seconds: float):
    """Yields, per seed, the numbers of the program and of each control."""
    from bench.run import Context
    drv = cell.driver()
    state = drv.setup(Context(cell=cell, seed=seeds[0], seconds=seconds,
                              work=work))
    try:
        for seed in seeds:
            state["ctx"] = Context(cell=cell, seed=seed, seconds=seconds,
                                   work=work)
            win = drv.window(state, seconds)
            out = dict(seed=seed, attempted=win.attempted, failed=win.failed)
            for name in (None,) + tuple(drv.CONTROLS):
                checks = drv.check(state, win, control=name)
                out[name or "program"] = dict(
                    correct=all(c.ok for c in checks) and win.failed == 0,
                    **{c.name: c.value for c in checks})
            yield out
    finally:
        drv.close(state)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--traffic", default="{}")
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    from bench import run as brun
    if not brun.use_program():
        return 2
    cell = brun.resolve(a.workload)
    cell.traffic = dict(cell.traffic, **json.loads(a.traffic))
    seeds = [int(s) for s in a.seeds.split(",")]
    work = os.path.join(brun.ROOT, ".bench_work", f"control-{cell.name}")
    os.makedirs(work, exist_ok=True)
    for rec in readings(cell, seeds, work, a.seconds):
        line = json.dumps(dict(workload=cell.name, **rec))
        print(line, flush=True)
        if a.out:
            with open(a.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
