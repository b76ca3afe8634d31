"""The readers of the program's own instruments (``bench/program.py`` and
the per-layer metrics on it): on synthetic registries and traces with
known answers, None where the program has no such instrument, and in a
traced run of a tiny campaign cell on the CPU."""
import json
import os
import shutil

import pytest

from bench import program
from bench import run as brun
from bench import xtrace

ROOT = brun.ROOT
SEARCH = ("act_ms", "env_step_ms", "archive_ms", "learn_ms",
          "loop_untraced_share", "checkpoint_fsync_share",
          "gc_pause_share.campaign")
SERVER = ("lock_wait_ms", "lock_hold_ms", "score_dispatch_ms",
          "gc_pause_share.recommend")


def _run(hists=(), seconds=10.0, trace=None):
    registry = {"counters": {}, "histograms": {
        (name, tuple(sorted(labels.items()))): dict(sum=s, count=n)
        for name, labels, s, n in hists}}
    win = brun.Window(t0=0.0, t1=seconds, attempted=1, failed=0, e2e={})
    return brun.Run(window=win, compiles=(0, 0.0, 0), registry=registry,
                    trace=trace, peaks={})


def _read(name, run):
    return brun.reader(name)(run)


LOOP = [("dispatch_seconds", {}, 2.0, 20),
        ("search_phase_seconds", {"phase": "act"}, 0.2, 20),
        ("search_phase_seconds", {"phase": "env_step"}, 0.1, 20),
        ("search_phase_seconds", {"phase": "archive"}, 0.5, 20),
        ("search_phase_seconds", {"phase": "learn"}, 1.1, 20),
        ("search_phase_seconds", {"phase": "telemetry"}, 0.05, 20),
        ("checkpoint_fsync_seconds", {}, 0.25, 4),
        ("gc_pause_seconds", {"gen": "0"}, 0.1, 50),
        ("gc_pause_seconds", {"gen": "2"}, 0.15, 1),
        ("serve_lock_wait_seconds", {}, 4.5, 100),
        ("serve_lock_hold_seconds", {}, 0.3, 100),
        ("serve_score_dispatch_seconds", {}, 0.06, 30)]


def test_readers_on_a_synthetic_registry():
    run = _run(LOOP)
    want = {"act_ms": 10.0, "env_step_ms": 5.0, "archive_ms": 25.0,
            "learn_ms": 55.0, "loop_untraced_share": 5.0,
            "checkpoint_fsync_share": 2.5,
            "gc_pause_share.campaign": 2.5,
            "gc_pause_share.recommend": 2.5,
            "lock_wait_ms": 45.0, "lock_hold_ms": 3.0,
            "score_dispatch_ms": 2.0}
    for name, value in want.items():
        assert _read(name, run) == pytest.approx(value), name


@pytest.mark.parametrize("name", SEARCH + SERVER
                         + ("idle_named_share.campaign",))
def test_readers_read_nothing_without_the_instrument(name):
    assert _read(name, _run()) is None
    # an instrument that saw nothing in the window: no mean to take, and
    # no time to share out
    empty = [(n, lb, 0.0, 0) for n, lb, _, _ in LOOP]
    want = 0.0 if name.endswith(("fsync_share", "campaign",
                                 "recommend")) else None
    if name.startswith("idle_named"):
        want = None
    assert _read(name, _run(empty)) == want


def test_untraced_share_needs_every_phase():
    assert _read("loop_untraced_share", _run(LOOP[:3])) is None


def _trace(host, window=(0.0, 10.0)):
    # device busy at 1-2 and 6-7: idle 0-1, 2-6 and 7-10
    ops = [("fusion.1", 1.0, 2.0), ("fusion.2", 6.0, 7.0)]
    return xtrace.DeviceTrace(window=window, devices=[ops], modules=[[]],
                              host=[("bench_window",) + window] + host)


HOST = [("repro.run_search_cells", 0.0, 10.0),
        ("repro.dispatch", 0.5, 5.0),
        ("repro.act", 0.5, 1.5),           # idle 0.5-1
        ("repro.learn", 2.5, 4.5),         # idle 2.5-4.5, a container
        ("repro.learn.update", 3.0, 4.0),  # idle 3-4
        ("repro.gc", 4.1, 4.3),            # inside learn: idle 0.2
        ("repro.checkpoint.fsync", 8.0, 9.0),
        ("PjitFunction(update)", 7.5, 9.5)]  # the runtime's: not read


def test_idle_time_is_named_by_the_innermost_leaf():
    idle = program.idle_by_name(_trace(HOST))
    assert sum(idle.values()) == pytest.approx(8.0)
    assert idle["repro.act"] == pytest.approx(0.5)
    assert idle["repro.learn.update"] == pytest.approx(1.0)
    assert idle["repro.gc"] == pytest.approx(0.2)
    assert idle["repro.checkpoint.fsync"] == pytest.approx(1.0)
    # under the dispatch or learn alone, under run_search_cells alone, or
    # (0-0.5) under it alone too
    assert idle[None] == pytest.approx(8.0 - 2.7)
    assert "repro.learn" not in idle and "repro.dispatch" not in idle
    share = _read("idle_named_share.campaign", _run(trace=_trace(HOST)))
    assert share == pytest.approx(100.0 * 2.7 / 8.0)


def test_a_gc_pause_makes_no_container():
    host = [("repro.act", 0.0, 1.0), ("repro.gc", 0.2, 0.4)]
    idle = program.idle_by_name(_trace(host))
    assert idle["repro.act"] == pytest.approx(0.8)
    assert idle["repro.gc"] == pytest.approx(0.2)


def test_no_program_annotation_reads_nothing():
    tr = _trace([("PjitFunction(update)", 0.0, 1.0)])
    assert program.idle_by_name(tr) is None
    assert _read("idle_named_share.campaign", _run(trace=tr)) is None


# ------------------------------------------------ traced tiny runs
TINY_CAMPAIGN = {"kind": "campaign", "mode": "high_perf", "nodes": [3, 7],
                 "lanes": 4, "episodes": 240, "checkpoint_every": 4,
                 "campaign_seeds": [0, 1]}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout with a tiny campaign cell that reports the metrics of
    the paper-hp cell."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (root / "bench" / "traffic" / "tiny-campaign.json").write_text(
        json.dumps(TINY_CAMPAIGN))
    manifest["workloads"].append(dict(
        name="smollm-135m.tiny-campaign", config="smollm-135m",
        traffic="tiny-campaign", chips=1, why="tiny rehearsal"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "minicpm3-4b.paper-hp" in m.get("workloads", []):
            m["workloads"].append("smollm-135m.tiny-campaign")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


def test_traced_campaign_reads_the_loop_phases(checkout):
    m = brun.run_cell(brun.resolve("smollm-135m.tiny-campaign",
                                   root=checkout), 2**31 + 11, 0.5, True,
                      require_tpu=False)["metrics"]
    assert set(SEARCH) <= set(m)
    assert all(m[n]["value"] > 0 for n in ("act_ms", "env_step_ms",
                                           "archive_ms", "learn_ms"))
    assert 0 <= m["loop_untraced_share"]["value"] <= 5
    assert 0 < m["checkpoint_fsync_share"]["value"] < 100
    # the CPU has no device plane to find idle time on
    assert "idle_named_share.campaign" not in m

