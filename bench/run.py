"""Run one benchmark cell once and print its result as one JSON line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout.  Everything a cell needs is found by name
from ``BENCHMARK.json``: the cell's configuration file
(``bench/configs/<config>.json``), its traffic file
(``bench/traffic/<traffic>.json``), the module of the traffic's ``kind``
(``bench/drivers/<kind>.py``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``).

A run sets up (imports, warm-up of every shape the traffic uses), measures
for ``--seconds`` with nothing compiling, checks what the timed path
produced against the plain reference in ``bench/reference``, and prints
the numbers compared, each beside its limit, as the last lines of
standard error and under ``checks`` in the result.  With ``--trace 0`` the
result's metrics are the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the JAX profiler and the metrics are the per-layer
ones.  Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit; it
    passes when ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclasses.dataclass
class Cell:
    """A cell of ``BENCHMARK.json`` with everything it names, resolved."""
    root: str
    workload: Dict
    config: Dict            # the configuration file's contents
    traffic: Dict           # the traffic file's contents
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def driver(self):
        return importlib.import_module(f"bench.drivers.{self.traffic['kind']}")


def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(workload: str, root: str = ROOT) -> Cell:
    """Find a cell and its files by name; KeyError if it is not there."""
    manifest = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    return Cell(
        root=root, workload=w,
        config=_read_json(os.path.join(root, cfg["file"])),
        traffic=_read_json(os.path.join(root, "bench", "traffic",
                                        f"{w['traffic']}.json")),
        end_to_end=[m for m in manifest["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in manifest["per_layer"]
                   if _applies(m, workload)])


def reader(name: str, root: str = ROOT):
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's seed and window, and a work
    directory inside the checkout."""
    cell: Cell
    seed: int
    seconds: float
    work: str


@dataclasses.dataclass
class Window:
    """What a driver's measured window returns.  ``t0``/``t1`` are host
    clock; ``e2e`` holds the end-to-end metrics it measured; ``counts``
    and ``spans`` feed the per-layer readers."""
    t0: float
    t1: float
    attempted: int
    failed: int
    e2e: Dict[str, float]
    counts: Dict = dataclasses.field(default_factory=dict)
    spans: List[Dict] = dataclasses.field(default_factory=list)
    labels: List = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Run:
    """Everything a per-layer reader may read about one run."""
    window: Window
    compiles: tuple            # (count, seconds, cache hits) in the window
    registry: Dict             # program metrics registry: window deltas
    trace: Optional[object]    # bench.xtrace.DeviceTrace, when traced
    peaks: Dict[str, float]


def registry_delta(before: Dict, after: Dict) -> Dict:
    """Counters and histograms of the program's metrics registry, as the
    window changed them (keyed by name and sorted label pairs)."""
    def key(row):
        return (row["name"], tuple(sorted(row.get("labels", {}).items())))
    b = {key(r): r for r in before.get("counters", [])}
    out = {"counters": {}, "histograms": {}}
    for r in after.get("counters", []):
        out["counters"][key(r)] = r["value"] - b.get(key(r), {}).get(
            "value", 0.0)
    bh = {key(r): r for r in before.get("histograms", [])}
    for r in after.get("histograms", []):
        p = bh.get(key(r), {})
        out["histograms"][key(r)] = dict(
            sum=r["sum"] - p.get("sum", 0.0),
            count=r["count"] - p.get("count", 0))
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True) -> Dict:
    """Set up, measure, check; returns the result dictionary.  Raises
    ``bench.device.NoChip`` before any work without the chip."""
    from bench import device as dev
    info = dev.describe(cell.chips, require_tpu=require_tpu)
    from bench.compile_watch import CompileWatch
    from repro.obs import metrics as obs_metrics
    watch = CompileWatch()
    work = os.path.join(cell.root, ".bench_work", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, work=work)
    driver = cell.driver()
    state = driver.setup(ctx)
    reg = obs_metrics.global_registry()
    try:
        before_reg, before_cc = reg.snapshot(), watch.mark()
        profile_dir = os.path.join(work, "profile")
        setup_s = time.time() - T_PROCESS
        if trace:
            import jax
            # device and annotation events only: the Python tracer would
            # record every call of the host loop and slow it
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench_window"):
                    t_mark = time.time()
                    win = driver.window(state, seconds)
            finally:
                jax.profiler.stop_trace()
        else:
            win = driver.window(state, seconds)
        compiles = watch.since(before_cc)
        registry = registry_delta(before_reg, reg.snapshot())
        info["memory_peak_bytes"] = dev.memory_peak_bytes(cell.chips)
        checks = driver.check(state, win)
    finally:
        driver.close(state)
    result = dict(correct=all(c.ok for c in checks) and win.failed == 0,
                  attempted=win.attempted, failed=win.failed)
    breakdown = None
    if trace:
        metrics, breakdown = _per_layer(cell, win, compiles, registry,
                                        profile_dir, info, t_mark)
    else:
        metrics = {"setup_s": dict(value=setup_s, unit="s")}
        for m in cell.end_to_end:
            if m["name"] in win.e2e:
                metrics[m["name"]] = dict(value=win.e2e[m["name"]],
                                          unit=m["unit"])
    result["metrics"] = metrics
    result["device"] = info
    if breakdown is not None:
        result["breakdown"] = breakdown
    # a number that could not be read (a call the window never made) is
    # written as text, since JSON has no infinity
    result["checks"] = {c.name: dict(value=(c.value if math.isfinite(c.value)
                                            else str(c.value)),
                                     limit=c.limit)
                        for c in checks}
    result["checks"]["failed_attempts"] = dict(value=win.failed, limit=0)
    return result


def _per_layer(cell, win, compiles, registry, profile_dir, info, t_mark):
    from bench import device as dev
    from bench import xtrace
    tr = xtrace.load(profile_dir, n_devices=cell.chips)
    offset = 0.0
    if tr is not None:
        offset = tr.window[0] - t_mark
        info["busy_s"] = tr.busy_s()
        info["window_s"] = tr.window_s
    run = Run(window=win, compiles=compiles, registry=registry, trace=tr,
              peaks=dev.peaks(info["kind"]) if info["platform"] == "tpu"
              else {})
    metrics = {}
    for m in cell.per_layer:
        value = reader(m["name"], cell.root)(run)
        if value is not None:
            metrics[m["name"]] = dict(value=value, unit=m["unit"])
    breakdown = None
    if tr is not None:
        labels = [(name, a + offset, b + offset)
                  for name, a, b in win.labels]
        breakdown = dict(device_ops=tr.top_ops(10),
                         idle_gaps=tr.idle_gaps(labels, 10))
    return metrics, breakdown


def use_program(root: str = ROOT) -> bool:
    """Put the checkout's program on the path, its compile cache at a
    fixed path inside the checkout, and the TPU runtime's logs nowhere;
    False when the checkout holds no program."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return False
    sys.path.insert(0, src)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache = os.path.join(root, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        cell = resolve(a.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    if not use_program():
        print(f"[bench] no program under {ROOT}/src", file=sys.stderr)
        return 2
    from bench.device import NoChip
    try:
        result = run_cell(cell, a.seed, a.seconds, bool(a.trace))
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"[bench] check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
