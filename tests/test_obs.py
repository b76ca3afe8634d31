"""Telemetry layer (repro.obs): span serde + torn-tail tolerance, the
Chrome trace exporter, deterministic histogram bucketing and snapshot
merge, lease-metrics piggyback round-trip, the fleet ``--status`` view,
Prometheus text rendering + the serve ``/metrics`` endpoint, the
structured-400 regression, supervision-event formatting, and the
contract that tracing never perturbs search results (bitwise)."""
import contextlib
import gc
import glob
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.search import PHASES, SearchConfig, run_search_cells
from repro.obs import export as obs_export
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.workload.extract import extract

ARCH = "smollm-135m"


# ------------------------------------------------------- tracing + serde
def test_span_serde_and_torn_tail(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = obs_trace.Tracer(path, proc="t0")
    obs_trace.install_tracer(tr)
    try:
        with obs_trace.span("work", cat="test", n=3) as sp:
            sp.set(extra=1)
        obs_trace.instant("tick", cat="test")
        obs_trace.counter("load", a=1.0, b=2.0)
        obs_trace.complete("measured", 12.0, 0.5, cat="test")
    finally:
        obs_trace.install_tracer(None)
        tr.close()
    with open(path, "a") as f:          # torn tail from a crash mid-append
        f.write('{"ph": "X", "name": "to')
    recs = obs_trace.read_trace(path)
    assert [r["ph"] for r in recs] == ["M", "X", "i", "C", "X"]
    x = recs[1]
    assert x["name"] == "work" and x["args"] == {"n": 3, "extra": 1}
    assert x["dur"] >= 0.0
    assert recs[3]["args"] == {"a": 1.0, "b": 2.0}
    assert recs[4]["ts"] == 12.0 and recs[4]["dur"] == 0.5


def test_span_records_error_and_null_span_without_tracer(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = obs_trace.Tracer(path)
    obs_trace.install_tracer(tr)
    try:
        with pytest.raises(RuntimeError):
            with obs_trace.span("boom"):
                raise RuntimeError("no")
    finally:
        obs_trace.install_tracer(None)
        tr.close()
    recs = obs_trace.read_trace(path)
    assert recs[-1]["args"]["error"].startswith("RuntimeError")
    # with no tracer installed the API is a no-op, not an error
    assert obs_trace.current_tracer() is None
    with obs_trace.span("ignored") as sp:
        sp.set(x=1)
    obs_trace.instant("ignored")


def test_chrome_export(tmp_path):
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "worker-0"))
    tr = obs_trace.Tracer(os.path.join(root, "trace.jsonl"), proc="fleet")
    tr.close()
    tw = obs_trace.Tracer(
        os.path.join(root, "worker-0", obs_trace.TRACE_NAME),
        proc="worker-0")
    tw.complete("dispatch", 100.0, 0.25, cat="search")
    tw.close()
    out = obs_export.export_run(root)
    assert out == os.path.join(root, "report", "trace.json")
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    assert all(e["ph"] in ("X", "i", "C", "M") for e in evs)
    # two processes -> two distinct pid lanes, each named by its source
    names = {e["pid"]: e["args"]["name"]
             for e in evs if e["ph"] == "M"}
    assert sorted(names.values()) == ["main", "worker-0"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and xs[0]["dur"] == pytest.approx(0.25e6)  # microseconds
    assert all(e["ts"] >= 0 for e in evs if "ts" in e)   # relative timebase


# ----------------------------------------------------------- metrics
def test_histogram_deterministic_and_merge():
    def build():
        r = obs_metrics.MetricsRegistry()
        h = r.histogram("lat", edges=(0.001, 0.01, 0.1))
        for v in (0.0005, 0.005, 0.05, 0.5):
            h.observe(v)
        r.counter("n").inc(2)
        r.gauge("g").set(10.0)
        return r.snapshot()
    a, b = build(), build()
    assert a == b                       # fixed edges -> identical snapshots
    m = obs_metrics.merge_snapshots([a, b])
    hist = obs_metrics.snapshot_value(m, "histograms", "lat")
    assert hist["counts"] == [2, 2, 2, 2]          # elementwise ADD
    assert hist["sum"] == pytest.approx(2 * (0.0005 + 0.005 + 0.05 + 0.5))
    assert obs_metrics.snapshot_value(m, "counters", "n") == 4   # ADD
    assert obs_metrics.snapshot_value(m, "gauges", "g") == 10.0  # AVERAGE
    bad = build()
    bad["histograms"][0]["edges"] = [1.0, 2.0]
    with pytest.raises(ValueError):
        obs_metrics.merge_snapshots([a, bad])


def test_snapshot_value_labels_and_default():
    r = obs_metrics.MetricsRegistry()
    r.counter("req", labels={"route": "/a"}).inc()
    r.counter("req", labels={"route": "/b"}).inc(5)
    s = r.snapshot()
    assert obs_metrics.snapshot_value(s, "counters", "req",
                                      {"route": "/b"}) == 5
    assert obs_metrics.snapshot_value(s, "counters", "nope",
                                      default=-1) == -1
    assert obs_metrics.snapshot_value(None, "gauges", "x") is None


def test_render_prometheus_text_format():
    r = obs_metrics.MetricsRegistry()
    r.counter("req", labels={"route": "/x"}).inc(3)
    r.gauge("up").set(1.0)
    h = r.histogram("lat", edges=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = obs_metrics.render_prometheus(r.snapshot())
    lines = text.strip().split("\n")
    for ln in lines:                    # every line parses as the v0.0.4
        if ln.startswith("#"):          # exposition grammar
            assert ln.startswith("# TYPE ")
            continue
        name_part, val = ln.rsplit(" ", 1)
        float(val)                      # value is a number (or +Inf count)
        assert name_part.startswith("repro_")
    assert "# TYPE repro_req counter" in text
    assert 'repro_req{route="/x"} 3' in text
    # histogram: cumulative buckets ending at +Inf, plus _sum/_count
    assert 'repro_lat_bucket{le="0.1"} 1' in text
    assert 'repro_lat_bucket{le="+Inf"} 2' in text
    assert "repro_lat_count 2" in text


# ------------------------------------------- lease piggyback + --status
def test_lease_metrics_roundtrip(tmp_path):
    from repro.campaign.distrib import Heartbeat
    from repro.campaign.store import read_lease, write_lease

    wdir = str(tmp_path / "worker-0")
    os.makedirs(wdir)
    reg = obs_metrics.MetricsRegistry()
    reg.counter("env_steps_total").inc(128)
    reg.gauge("env_steps_per_s").set(42.5)
    hb = Heartbeat(wdir, 0, ttl_s=30.0, registry=reg)
    hb.start()
    try:
        hb.beat("b0003")
    finally:
        hb.stop(done=False)
    lease = read_lease(wdir)
    assert lease["batch"] == "b0003"
    snap = lease["metrics"]
    assert obs_metrics.snapshot_value(snap, "counters",
                                      "env_steps_total") == 128
    assert obs_metrics.snapshot_value(snap, "gauges",
                                      "env_steps_per_s") == 42.5
    # registry-less heartbeats stay lean: no metrics field requirement
    write_lease(wdir, worker=0, batch=None, ttl_s=30.0, done=True)
    assert read_lease(wdir)["done"]


def test_fleet_status_reads_leases_without_jax(tmp_path):
    from repro.campaign.store import write_lease
    from repro.launch.fleet import fleet_status, render_status

    root = str(tmp_path)
    with open(os.path.join(root, "manifest.json"), "w") as f:
        json.dump({"name": "statrun",
                   "cells": {"a": {"status": "done"},
                             "b": {"status": "pending"}},
                   "fleet": {"lease_ttl_s": 20.0,
                             "assignments": {"b0002": 1},
                             "events": []}}, f)
    w0 = os.path.join(root, "worker-0")
    os.makedirs(w0)
    os.makedirs(os.path.join(root, "worker-1"))
    reg = obs_metrics.MetricsRegistry()
    reg.gauge("env_steps_per_s").set(99.0)
    reg.counter("env_steps_total").inc(1000)
    write_lease(w0, worker=0, batch="b0001", ttl_s=20.0,
                metrics=reg.snapshot())
    st = fleet_status(root)
    assert (st["name"], st["cells_done"], st["cells_total"],
            st["pending_batches"]) == ("statrun", 1, 2, 1)
    by = {r["worker"]: r for r in st["workers"]}
    assert by["worker-0"]["state"] == "live"
    assert by["worker-0"]["env_steps_s"] == 99.0
    assert by["worker-0"]["env_steps"] == 1000
    assert by["worker-1"]["state"] == "no-lease"
    txt = render_status(st)
    assert "worker-0" in txt and "live" in txt
    assert "99 env-steps/s over 1 live worker(s)" in txt
    assert "no-lease" in txt
    # stale detection: same lease observed far in the future
    st2 = fleet_status(root, now=__import__("time").time() + 1e4)
    assert {r["worker"]: r["state"] for r in st2["workers"]}[
        "worker-0"] == "stale"


# ------------------------------------------------------ structured log
def test_jsonl_logger_bind_mirror_and_torn_tail(tmp_path):
    path = str(tmp_path / "log.jsonl")
    mirror = str(tmp_path / "worker.log")
    with open(mirror, "w") as mf:
        lg = obs_log.JsonlLogger(path, mirror=mf, context={"worker": 1})
        lg.info("worker up", ttl=15)
        lg.bind(batch_id="b0001").error("cell failed", cell_id="c3")
        lg.close()
    recs = obs_log.read_log(path)
    assert recs[0]["msg"] == "worker up" and recs[0]["worker"] == 1
    assert recs[1]["level"] == "error" and recs[1]["batch_id"] == "b0001"
    assert recs[1]["worker"] == 1       # bound context inherited
    text = open(mirror).read()
    assert "worker up" in text and "ERROR" in text and "b0001" in text
    with open(path, "a") as f:
        f.write('{"torn')
    assert len(obs_log.read_log(path)) == 2


# ------------------------------------------- serve /metrics + 400 fix
class _StubIndex:
    cells, candidates, seq_len, batch = {}, [], 2048, 3


class _StubRec:
    index = _StubIndex()
    n_dispatches = n_exact = n_surrogate = 0

    def recommend_batch(self, queries):
        raise AssertionError("malformed requests must not reach the "
                             "recommender")


@pytest.fixture()
def srv_port():
    from repro.launch.serve import recommend_server

    obs_metrics.global_registry().clear()
    ready, box = threading.Event(), {}

    def _up(s):
        box["srv"] = s
        ready.set()

    t = threading.Thread(
        target=lambda: recommend_server([], port=0, recommender=_StubRec(),
                                        on_ready=_up),
        daemon=True)
    t.start()
    assert ready.wait(30)
    yield box["srv"].server_port
    box["srv"].shutdown()
    t.join(30)


def _post(port, body: bytes):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/recommend", data=body,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.load(r)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


def test_malformed_recommend_is_structured_400(srv_port):
    # regression: these used to surface as empty-body 500s
    for body in (b"{not json",                        # invalid JSON
                 b"[1, 2]",                           # valid JSON, non-dict
                 b'{"queries": 5}',                   # non-list queries
                 b'{"queries": [7]}',                 # non-object query
                 b'{"queries": []}'):                 # no queries
        code, payload = _post(srv_port, body)
        assert code == 400, body
        assert payload["error"]["type"] and payload["error"]["message"]


def test_metrics_endpoint_prometheus_text(srv_port):
    _post(srv_port, b"{not json")        # one bad request on the books
    health = json.load(urllib.request.urlopen(
        f"http://127.0.0.1:{srv_port}/healthz", timeout=30))
    assert health["uptime_s"] >= 0
    assert health["index"]["seq_len"] == 2048
    assert health["index"]["answered_exact"] == 0
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv_port}/metrics", timeout=30) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    assert "# TYPE repro_serve_bad_requests_total counter" in text
    assert "repro_serve_bad_requests_total 1" in text
    assert 'repro_serve_requests_total{route="/recommend"} 1' in text
    assert 'repro_serve_requests_total{route="/healthz"} 1' in text
    assert 'repro_serve_request_seconds_bucket{le="+Inf"}' in text


# -------------------------------------------------- event formatting
def test_format_event_human_readable():
    from repro.campaign.report import format_event

    ev = format_event(dict(kind="evict", ts=1700000000.0, worker=2,
                           reason="lease-expired", returncode=-9,
                           pending=["b0004", "b0005"]))
    assert "**evict**" in ev and "worker 2" in ev
    assert "`b0004`, `b0005`" in ev and "lease-expired" in ev
    assert "{" not in ev                # no raw dict rendering
    rd = format_event(dict(kind="redeal", ts=1700000100.0,
                           batches=["b0004"], from_worker=2, to_worker=3,
                           reason="lease-expired"))
    assert "re-dealt from worker 2 to fresh slot 3" in rd
    unk = format_event(dict(kind="mystery", ts=0.0, foo=1, bar="x"))
    assert "**mystery**" in unk and "bar=x" in unk and "foo=1" in unk


# ------------------------------------- tracing never perturbs results
def test_tracing_on_off_bitwise_identical_search(tmp_path):
    wl = extract(get_config(ARCH), seq_len=256, batch=1)
    sc = SearchConfig(episodes=64, warmup=24, batch_size=32, seed=0)

    def fp(results):
        out = []
        for r in results:
            out.append((
                None if r.best_cfg is None
                else np.asarray(r.best_cfg, np.float64).tobytes(),
                r.best_score, r.episodes_run, r.feasible_count,
                r.unique_configs, r.screened, r.evaluated,
                sorted(e.objectives().tobytes()
                       for e in r.archive.entries)))
        return out

    obs_metrics.global_registry().clear()
    assert obs_trace.current_tracer() is None
    off = fp(run_search_cells(wl, [3, 7], search=sc, lanes_per_cell=4))

    tr = obs_trace.Tracer(str(tmp_path / "trace.jsonl"), proc="test")
    obs_trace.install_tracer(tr)
    try:
        on = fp(run_search_cells(wl, [3, 7], search=sc, lanes_per_cell=4))
        # phases timed and annotated on a profile being taken, too
        with _profile(tmp_path / "profile"):
            profiled = fp(run_search_cells(wl, [3, 7], search=sc,
                                           lanes_per_cell=4))
    finally:
        obs_trace.install_tracer(None)
        tr.close()
    assert on == off
    assert profiled == off
    assert obs_metrics.global_registry().histogram(
        "search_phase_seconds", labels={"phase": "act"}).count > 0
    # and the traced run actually produced spans
    names = {r["name"] for r in obs_trace.read_trace(
        str(tmp_path / "trace.jsonl"))}
    assert "run_search_cells" in names and "first_dispatch" in names


# ------------------------------------- phases, profile annotations, GC
@contextlib.contextmanager
def _profile(log_dir):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # annotations only, as bench/run.py
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        yield


def _host_events(log_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats) if e.name.startswith("repro.") else {})
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _small_search(**kw):
    wl = extract(get_config(ARCH), seq_len=256, batch=1)
    sc = SearchConfig(episodes=64, warmup=24, batch_size=32, seed=0)
    return run_search_cells(wl, [3, 7], search=sc, lanes_per_cell=4, **kw)


def test_search_phases_account_for_the_dispatch():
    reg = obs_metrics.global_registry()
    reg.clear()
    _small_search()
    snap = reg.snapshot()
    disp = obs_metrics.snapshot_value(snap, "histograms", "dispatch_seconds")
    phases = {p: obs_metrics.snapshot_value(
        snap, "histograms", "search_phase_seconds", {"phase": p})
        for p in PHASES}
    assert disp["count"] == 16
    assert all(h["count"] == disp["count"] and h["sum"] > 0
               for h in phases.values())
    inside = sum(phases[p]["sum"] for p in PHASES if p != "telemetry")
    assert 0.9 * disp["sum"] <= inside <= disp["sum"]
    # the unread gauges are gone; the read ones stay
    gauges = {row["name"] for row in snap["gauges"]}
    assert {"env_steps_per_s", "gate_open_frac", "search_eps",
            "best_score"} <= gauges
    assert not gauges & {"sac_entropy", "per_max_priority", "per_size",
                         "per_beta"}


def test_search_counts_pareto_offers_and_keeps():
    reg = obs_metrics.global_registry()
    reg.clear()
    results = _small_search()
    snap = reg.snapshot()
    offered = obs_metrics.snapshot_value(snap, "counters",
                                         "pareto_offered_total")
    kept = obs_metrics.snapshot_value(snap, "counters", "pareto_kept_total")
    assert 0 < kept <= offered
    assert offered == sum(r.archive.n_offered for r in results)
    assert obs_metrics.snapshot_value(
        snap, "gauges", "pareto_frontier_max") == max(
            len(r.archive) for r in results)


def test_profile_holds_phases_inside_the_dispatch(tmp_path):
    _small_search()                     # compiled outside the profile
    with _profile(tmp_path):
        _small_search(checkpoint_dir=str(tmp_path / "ckpt"),
                      checkpoint_every=4)
    events = _host_events(tmp_path)
    names = {e[0] for e in events}
    disp = [e for e in events
            if e[0] in ("repro.dispatch", "repro.first_dispatch")]
    assert len(disp) == 16
    assert "repro.run_search_cells" in names
    for p in PHASES + ("learn.sample", "learn.update", "learn.priorities",
                       "learn.wm"):
        inner = [e for e in events if e[0] == "repro." + p]
        assert inner, p
        assert all(any(d[1] <= e[1] and e[2] <= d[2] for d in disp)
                   for e in inner), p
    for p in ("gather", "serialize", "fsync", "publish"):
        assert "repro.checkpoint." + p in names
    # every program annotation carries the program's prefix; the
    # runtime's own never do
    assert not {n for n in names if n.startswith("repro")
                and not n.startswith("repro.")}


def test_gc_pause_is_recorded_once_per_collection():
    obs_trace.watch_gc()
    obs_trace.watch_gc()
    assert gc.callbacks.count(obs_trace._on_gc) == 1
    h = obs_metrics.global_registry().histogram(
        obs_trace.GC_HIST, labels={"gen": "2"})
    n, total = h.count, h.sum
    gc.collect()
    assert h.count == n + 1 and h.sum > total


def test_phase_without_profile_or_histogram_is_the_null_span():
    assert not obs_trace.profiling()
    assert obs_trace.phase("x") is obs_trace.NULL_SPAN
    h = obs_metrics.MetricsRegistry().histogram("t")
    with obs_trace.phase("x", h, req=3):
        pass
    assert h.count == 1 and h.sum >= 0.0


def test_tagged_meta_reaches_every_annotation_of_the_thread(tmp_path):
    import jax  # noqa: F401  (annotations need jax loaded)
    with _profile(tmp_path):
        with obs_trace.tagged(req=7), obs_trace.phase("request"):
            with obs_trace.phase("inner", step_num=1):
                pass
        with obs_trace.phase("untagged"):
            pass
    stats = {e[0]: e[3] for e in _host_events(tmp_path)
             if e[0].startswith("repro.")}
    assert stats["repro.request"] == {"req": 7}
    assert stats["repro.inner"] == {"req": 7, "step_num": 1}
    assert stats["repro.untagged"] == {}


# ------------------------------------------- extraction and feasibility
def test_extract_span_carries_the_scenario_and_graph_size(tmp_path):
    path = str(tmp_path / obs_trace.TRACE_NAME)
    tracer = obs_trace.Tracer(path, proc="t")
    prev = obs_trace.install_tracer(tracer)
    try:
        wl = extract(get_config("deepseek-v2-lite"), seq_len=512, batch=8,
                     phase="prefill", dtype="fp8")
    finally:
        obs_trace.install_tracer(prev)
        tracer.close()
    (rec,) = [r for r in obs_trace.read_trace(path)
              if r.get("name") == "extract"]
    assert rec["cat"] == "workload" and rec["dur"] >= 0
    assert rec["args"] == dict(arch="deepseek-v2-lite", phase="prefill",
                               dtype="fp8", n_ops=wl.graph.n_ops,
                               moe_layers=26)


def test_infeasible_counter_recounts_from_the_dispatch_rows(monkeypatch):
    from repro.core import env as env_mod
    from repro.ppa import config_space as cs
    from repro.ppa.analytic import INFEASIBLE_REASONS, M_IDX
    rows = []
    original = env_mod.VecDSEEnv.step

    def step(self, a_cont, a_disc):
        out = original(self, a_cont, a_disc)
        rows.append((out[2].metrics.copy(), out[2].cfg.copy()))
        return out
    monkeypatch.setattr(env_mod.VecDSEEnv, "step", step)
    wl = extract(get_config("deepseek-v2-lite"), seq_len=4096, batch=8)
    reg = obs_metrics.global_registry()
    reg.clear()
    run_search_cells(wl, [3, 28], lanes_per_cell=4,
                     search=SearchConfig(episodes=64, warmup=24,
                                         batch_size=32, seed=0))
    snap = reg.snapshot()
    got = {r: obs_metrics.snapshot_value(snap, "counters",
                                         "env_infeasible_total",
                                         {"reason": r})
           for r in INFEASIBLE_REASONS}
    m = np.concatenate([r[0] for r in rows])
    cfg = np.concatenate([r[1] for r in rows]).astype(np.float64)
    broken = {k: int((m[:, M_IDX[k]] < 0.5).sum())
              for k in ("wmem_ok", "dmem_ok", "power_ok", "area_ok")}
    scratch = cfg[:, cs.IDX["dmem_kb"]] * np.maximum(
        0.0, 1.0 - cfg[:, cs.IDX["dmem_in_frac"]]
        - cfg[:, cs.IDX["dmem_out_frac"]])
    assert len(m) == 16 * 8
    assert got["wmem"] == broken["wmem_ok"] > 0
    assert got["power"] == broken["power_ok"]
    assert got["area"] == broken["area_ok"]
    assert got["dmem"] == int((scratch < 2048 * 4 / 1024).sum())
    assert got["dmem"] + got["kv_spill"] == broken["dmem_ok"]


def test_wmem_reject_share_reads_the_window_counters():
    from types import SimpleNamespace

    from bench import run as brun
    read = brun.reader("wmem_reject_share")
    counters = {("env_infeasible_total", (("reason", "wmem"),)): 300.0,
                ("env_infeasible_total", (("reason", "area"),)): 50.0,
                ("env_steps_total", ()): 1200.0}
    run = SimpleNamespace(registry=dict(counters=counters, histograms={}))
    assert read(run) == 25.0
    del counters[("env_infeasible_total", (("reason", "wmem"),))]
    assert read(run) is None
    run.registry["counters"] = {("env_steps_total", ()): 0.0}
    assert read(run) is None
