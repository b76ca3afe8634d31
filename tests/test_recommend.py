"""Pareto-as-a-service recommendation path (repro.launch.recommend).

The correctness contract under test:

* in-grid queries are EXACT — the served config is bitwise identical to
  the cell archive's scalarized ``select()`` winner, metrics verbatim;
* out-of-grid queries fall back to the index surrogate, marked
  ``source == "surrogate"`` with provenance to the mined cell;
* a mixed query batch fuses every surrogate fallback into ONE jit
  dispatch (counter + jit trace-cache asserted);
* the HTTP endpoint (serve.recommend_server) answers the same batch.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import repro.ppa.surrogate as sur_mod
from repro.campaign import CampaignSpec, CampaignStore, run_campaign
from repro.launch.recommend import (MODE_WEIGHTS, ArchiveIndex, Query,
                                    Recommender, main as recommend_main,
                                    split_cell_id)

ARCH = "smollm-135m"
IN_NODE, IN_NODE2, OUT_NODE = 3, 7, 14


@pytest.fixture(scope="module")
def campaign_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("recsvc") / "camp")
    spec = CampaignSpec(name="recsvc", workloads=[ARCH],
                        nodes=[IN_NODE, IN_NODE2], modes=["high_perf"],
                        episodes=32, lanes=4, max_envs=8, seed=0,
                        seq_len=256, batch=1, checkpoint_every=2)
    run_campaign(root, spec, progress=lambda m: None)
    return root


@pytest.fixture(scope="module")
def rec(campaign_root):
    return Recommender.build([campaign_root])


# ------------------------------------------------------------- queries
def test_query_validation():
    with pytest.raises(ValueError, match="exactly one"):
        Query(node_nm=IN_NODE)                        # neither arch/features
    with pytest.raises(ValueError, match="exactly one"):
        Query(node_nm=IN_NODE, arch=ARCH, features=np.zeros(30))
    with pytest.raises(ValueError, match="unknown arch"):
        Query(node_nm=IN_NODE, arch="not-a-model")
    with pytest.raises(ValueError, match="process node"):
        Query(node_nm=4, arch=ARCH)
    with pytest.raises(ValueError, match="unknown mode"):
        Query(node_nm=IN_NODE, arch=ARCH, mode="turbo")
    with pytest.raises(ValueError, match="unknown query key"):
        Query.from_dict({"node_nm": IN_NODE, "arch": ARCH, "speed": 9})
    with pytest.raises(ValueError, match="node_nm"):
        Query.from_dict({"arch": ARCH})
    with pytest.raises(ValueError, match="unknown workload feature"):
        Query(node_nm=IN_NODE, features={"not_a_field": 1.0})
    q = Query.from_dict({"node_nm": IN_NODE, "arch": ARCH})
    assert q.weights == MODE_WEIGHTS["high_perf"]
    q2 = Query(node_nm=IN_NODE, arch=ARCH, w_perf=1.0, w_power=0.5,
               w_area=0.25)
    assert q2.weights == (1.0, 0.5, 0.25)


def test_split_cell_id_roundtrips_double_underscore_arch():
    assert split_cell_id("a__b__5nm__low_power") == ("a__b", 5, "low_power")


# ---------------------------------------------------------- exact path
def test_in_grid_answer_bitwise_matches_archive_select(campaign_root, rec):
    store = CampaignStore.open(campaign_root)
    for node in (IN_NODE, IN_NODE2):
        cid = f"{ARCH}__{node}nm__high_perf"
        ref = store.load_archive(cid).select(*MODE_WEIGHTS["high_perf"])
        ans = rec.recommend(Query(arch=ARCH, node_nm=node))
        assert ans.source == "archive" and ans.cell_id == cid
        assert np.array_equal(ans.cfg, ref.cfg)          # bitwise
        assert ans.power_mw == ref.power_mw
        assert ans.perf_gops == ref.perf_gops
        assert ans.area_mm2 == ref.area_mm2
        assert ans.tok_s == ref.tok_s
        assert ans.ppa_score == ref.ppa_score
        assert ans.within_budget


def test_budget_filters_archive_answer(rec):
    ar = rec.index.cells[f"{ARCH}__{IN_NODE}nm__high_perf"]
    powers = sorted(e.power_mw for e in ar.entries)
    assert len(powers) > 1
    budget = (powers[0] + powers[1]) / 2.0  # admits exactly the frugalest
    ans = rec.recommend(Query(arch=ARCH, node_nm=IN_NODE,
                              power_budget_mw=budget))
    assert ans.source == "archive"
    assert ans.power_mw == powers[0] and ans.power_mw <= budget


def test_impossible_budget_falls_back_to_surrogate(rec):
    ar = rec.index.cells[f"{ARCH}__{IN_NODE}nm__high_perf"]
    floor = min(e.power_mw for e in ar.entries)
    ans = rec.recommend(Query(arch=ARCH, node_nm=IN_NODE,
                              power_budget_mw=floor * 1e-6))
    assert ans.source == "surrogate"   # no archived point satisfies it


# ------------------------------------------------------ surrogate path
def test_out_of_grid_node_uses_surrogate(rec):
    ans = rec.recommend(Query(arch=ARCH, node_nm=OUT_NODE))
    assert ans.source == "surrogate"
    assert ans.cell_id in rec.index.cells            # provenance
    assert np.isfinite([ans.power_mw, ans.perf_gops, ans.area_mm2]).all()
    assert ans.power_mw > 0 and ans.perf_gops > 0 and ans.area_mm2 > 0
    assert ans.tok_s is None and ans.ppa_score is None
    cfgs = [c.entry.cfg for c in rec.index.candidates]
    assert any(np.array_equal(ans.cfg, c) for c in cfgs)


def test_raw_feature_query_uses_surrogate(rec):
    ans = rec.recommend(Query(node_nm=IN_NODE,
                              features={"flops_per_token": 3e8,
                                        "weight_mb": 64.0, "seq_len": 512,
                                        "batch": 1, "d_model": 512}))
    assert ans.source == "surrogate"
    assert np.isfinite([ans.power_mw, ans.perf_gops, ans.area_mm2]).all()


def test_mixed_batch_is_one_fused_dispatch(rec):
    # three surrogate fallbacks + one exact hit in one recommend_batch call
    # must cost exactly one score_query_batch dispatch — the counter counts
    # calls, the jit trace cache proves a single (Q, C) shape was traced
    sur_mod.score_query_batch.clear_cache()
    before = rec.n_dispatches
    queries = [Query(arch=ARCH, node_nm=IN_NODE),            # exact
               Query(arch=ARCH, node_nm=OUT_NODE),           # surrogate
               Query(arch=ARCH, node_nm=OUT_NODE, mode="low_power"),
               Query(node_nm=IN_NODE, features={"weight_mb": 8.0})]
    answers = rec.recommend_batch(queries)
    assert [a.source for a in answers] == [
        "archive", "surrogate", "surrogate", "surrogate"]
    assert rec.n_dispatches - before == 1
    assert sur_mod.score_query_batch._cache_size() == 1


def test_all_exact_batch_costs_zero_dispatches(rec):
    before = rec.n_dispatches
    answers = rec.recommend_batch(
        [Query(arch=ARCH, node_nm=IN_NODE),
         Query(arch=ARCH, node_nm=IN_NODE2)])
    assert all(a.source == "archive" for a in answers)
    assert rec.n_dispatches == before


# ------------------------------------------------------------ index
def test_archive_index_build_and_candidates(campaign_root):
    idx = ArchiveIndex.build([campaign_root])
    assert sorted(idx.cells) == [f"{ARCH}__{IN_NODE}nm__high_perf",
                                 f"{ARCH}__{IN_NODE2}nm__high_perf"]
    total = sum(len(a) for a in idx.cells.values())
    assert 0 < len(idx.candidates) <= total
    x, y = idx.training_set()
    assert x.shape == (total, idx.query_context(
        idx.wl_features(ARCH), IN_NODE, "high_perf").shape[0]
        + idx.cand_matrix().shape[1])
    assert y.shape == (total, 3)
    assert np.isfinite(x).all() and np.isfinite(y).all()


def test_index_requires_campaign(tmp_path):
    with pytest.raises((ValueError, OSError)):
        ArchiveIndex.build([str(tmp_path / "nope")])
    with pytest.raises(ValueError):
        ArchiveIndex.build([])


def test_answer_to_dict_is_json_ready(rec):
    ans = rec.recommend(Query(arch=ARCH, node_nm=OUT_NODE))
    d = json.loads(json.dumps(ans.to_dict()))
    assert d["source"] == "surrogate" and isinstance(d["cfg"], list)


# --------------------------------------------------------- CLI + report
def test_cli_answers_and_writes_index_report(campaign_root, capsys):
    recommend_main(["--root", campaign_root, "--node", str(IN_NODE),
                    "--arch", ARCH, "--report"])
    out = capsys.readouterr().out
    ans = json.loads(out.strip().splitlines()[-1])
    assert ans["source"] == "archive"
    assert ans["query"] == {"arch": ARCH, "node_nm": IN_NODE,
                            "mode": "high_perf"}
    report = json.load(open(f"{campaign_root}/report/index.json"))
    assert [r["cell_id"] for r in report] == sorted(
        f"{ARCH}__{n}nm__high_perf" for n in (IN_NODE, IN_NODE2))
    assert all(r["frontier"] > 0 and np.isfinite(r["power_mw"])
               for r in report)


# -------------------------------------------------------- HTTP endpoint
def test_http_server_serves_fused_batch(campaign_root, rec):
    ready = threading.Event()
    box = {}

    def _go():
        from repro.launch.serve import recommend_server
        recommend_server([campaign_root], port=0, recommender=rec,
                         poll=True, on_ready=lambda s: (
                             box.update(port=s.server_port), ready.set()))

    t = threading.Thread(target=_go, daemon=True)
    t.start()
    assert ready.wait(30)
    req = urllib.request.Request(
        f"http://127.0.0.1:{box['port']}/recommend",
        data=json.dumps({"queries": [
            {"arch": ARCH, "node_nm": IN_NODE},
            {"arch": ARCH, "node_nm": OUT_NODE},
        ]}).encode(), headers={"Content-Type": "application/json"})
    r = json.load(urllib.request.urlopen(req, timeout=30))
    t.join(30)
    assert [a["source"] for a in r["answers"]] == ["archive", "surrogate"]
    assert r["dispatches"] == 1
    # archive leg of the HTTP answer carries the exact select() metrics
    store = CampaignStore.open(campaign_root)
    ref = store.load_archive(f"{ARCH}__{IN_NODE}nm__high_perf").select(
        *MODE_WEIGHTS["high_perf"])
    assert r["answers"][0]["power_mw"] == ref.power_mw
    assert r["answers"][0]["cfg"] == np.asarray(
        ref.cfg, np.float64).tolist()


def test_http_healthz_and_bad_query(campaign_root, rec):
    ready = threading.Event()
    box = {}

    def _go():
        from repro.launch.serve import recommend_server
        srv = [None]

        def _up(s):
            srv[0] = s
            box.update(port=s.server_port)
            ready.set()

        # two polls: healthz then the invalid POST
        recommend_server([campaign_root], port=0, recommender=rec,
                         poll=True, on_ready=_up)

    t = threading.Thread(target=_go, daemon=True)
    t.start()
    assert ready.wait(30)
    h = json.load(urllib.request.urlopen(
        f"http://127.0.0.1:{box['port']}/healthz", timeout=30))
    t.join(30)
    assert h["status"] == "ok" and h["cells"] == 2 and h["candidates"] > 0


@pytest.mark.parametrize("nodes,dispatches",
                         [((IN_NODE,), 0), ((IN_NODE, OUT_NODE), 1)],
                         ids=["in_grid", "with_fallback"])
def test_http_request_times_lock_and_dispatch(campaign_root, rec, nodes,
                                              dispatches):
    from repro.obs import metrics as obs_metrics
    reg = obs_metrics.global_registry()
    # each is observed before the reply goes out (serve_request_seconds
    # only after it, on a handler thread the test cannot join)
    names = ("serve_lock_wait_seconds", "serve_lock_hold_seconds",
             "serve_score_dispatch_seconds")
    before = {n: reg.histogram(n).count
              for n in names + ("serve_request_seconds",)}
    before_sum = {n: reg.histogram(n).sum
                  for n in names + ("serve_request_seconds",)}
    ready = threading.Event()
    box = {}

    def _go():
        from repro.launch.serve import recommend_server
        recommend_server([campaign_root], port=0, recommender=rec,
                         poll=True, on_ready=lambda s: (
                             box.update(port=s.server_port), ready.set()))

    t = threading.Thread(target=_go, daemon=True)
    t.start()
    assert ready.wait(30)
    req = urllib.request.Request(
        f"http://127.0.0.1:{box['port']}/recommend",
        data=json.dumps({"queries": [{"arch": ARCH, "node_nm": n}
                                     for n in nodes]}).encode(),
        headers={"Content-Type": "application/json"})
    r = json.load(urllib.request.urlopen(req, timeout=30))
    t.join(30)
    assert not t.is_alive()
    assert r["dispatches"] == dispatches
    added = {n: reg.histogram(n).count - before[n] for n in names}
    assert added == {"serve_lock_wait_seconds": 1,
                     "serve_lock_hold_seconds": 1,
                     "serve_score_dispatch_seconds": dispatches}
    # the request's own time, observed once its handler returns, holds
    # its wait and hold
    served = reg.histogram("serve_request_seconds")
    deadline = time.time() + 30
    while served.count == before["serve_request_seconds"] \
            and time.time() < deadline:
        time.sleep(0.01)
    assert served.count == before["serve_request_seconds"] + 1
    inner = sum(reg.histogram(n).sum for n in names[:2]) \
        - sum(before_sum[n] for n in names[:2])
    assert 0 < inner <= served.sum - before_sum["serve_request_seconds"]
