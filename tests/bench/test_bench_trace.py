"""The reduction from a profiler trace to busy time, idle share, kernel
time and idle gaps: on synthetic events with known answers, and on a
small trace recorded on a TPU v5e (``fixtures/recommend.xplane.pb``:
two seconds of the recommend cell's window)."""
import os

import pytest

from bench import intervals as iv
from bench import xtrace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "recommend.xplane.pb")


def test_interval_union_and_gaps():
    busy = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert iv.merge(busy) == [(0.0, 2.0), (3.0, 4.0)]
    assert iv.covered(busy, 1.0, 3.5) == pytest.approx(1.5)
    assert iv.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]


def _synthetic():
    ops = [("fusion.1", 1.0, 1.5), ("actor_forward.1", 2.0, 2.25),
           ("fusion.2", 2.2, 2.5), ("copy.3", 9.0, 9.5)]
    mods = [("jit_update", 1.0, 1.5), ("jit_policy_act_batch", 2.0, 2.5)]
    host = [("bench_window", 0.0, 4.0), ("checkpoint", 2.5, 3.5),
            ("campaign", 0.0, 4.0)]
    return xtrace.DeviceTrace(window=(0.0, 4.0), devices=[ops],
                              modules=[mods], host=host)


def test_busy_union_idle_share_and_kernel_time():
    tr = _synthetic()
    assert tr.window_s == 4.0
    # 1.0-1.5 and 2.0-2.5 overlap-merged; 9.0 lies outside the window
    assert tr.busy_s() == pytest.approx(1.0)
    assert tr.op_seconds("actor_forward") == (pytest.approx(0.25), 1)
    assert tr.module_runs("jit_update") == (pytest.approx(0.5), 1)
    assert [n for n, _ in tr.top_ops()] == ["jit_update",
                                            "jit_policy_act_batch"]


def test_idle_gaps_are_named_by_the_innermost_host_interval():
    tr = _synthetic()
    gaps = tr.idle_gaps([(n, a, b) for n, a, b in tr.host
                         if n != "bench_window"])
    assert gaps[0] == ["checkpoint", pytest.approx(1.5)]
    assert {g[0] for g in gaps} == {"checkpoint", "campaign"}
    assert sum(g[1] for g in gaps) == pytest.approx(3.0)


def test_recorded_tpu_trace():
    """Against a plain pass over the same file's raw events."""
    from jax.profiler import ProfileData
    tr = xtrace.load(FIXTURE)
    assert tr is not None and 0 < tr.window_s < 10
    pd = ProfileData.from_file(FIXTURE)
    (dev,) = [p for p in pd.planes if p.name == "/device:TPU:0"]
    (line,) = [ln for ln in dev.lines if ln.name == "XLA Ops"]
    lo, hi = tr.window
    spans = sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                   for e in line.events)
    busy, end = 0.0, lo
    for a, b in spans:
        a, b = max(a, end), min(b, hi)
        if b > a:
            busy += b - a
            end = b
    assert tr.busy_s() == pytest.approx(busy, rel=1e-9)
    assert 0.0 < tr.busy_s() < tr.window_s
    # every operation runs inside a program; programs also hold the
    # short waits between their operations
    seconds, calls = tr.module_runs("jit_")
    assert calls > 0 and tr.busy_s() <= seconds
    assert tr.module_runs("jit_score_query_batch")[1] > 0
    gaps = tr.idle_gaps([])
    assert len(gaps) == 10 and all(g[0] == "other" for g in gaps)
