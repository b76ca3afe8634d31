"""Traffic kind ``recommend_closed``: the recommend service under a closed
loop of clients.

Set-up runs one campaign of the cell's configuration (the traffic's
``campaign`` parameters, its first campaign seed), builds the
``Recommender`` over it, serves it with ``launch.serve.recommend_server``
on localhost, and warms the service: every fallback arch's workload
features and every fallback count a request can carry.  The window is a
child process (``bench.loadgen``) whose ``clients`` each send a request
when the last one was answered.

What decides ``correct`` (limits from the readings in PERF.md), over the
answers of a seeded sample of the window's requests:
- ``grid_mismatch``: in-grid answers that differ from a plain selection
  over the same campaign's frontier (``bench/reference/archive.py``):
  the budget filter and the scalarized pick, with every served number
  equal (exact);
- ``fallback_gap``: surrogate fallbacks against the plain forward of the
  serving surrogate (``bench/reference/nets.py``) over the plain
  candidate pool, at the stated precision, request by request: the
  widest gap of the served power, perf and area at the served pick, or
  of the served pick's score above the best (``fallback_readings``);
- ``sample_shortfall``: in-grid answers, and fallbacks, checked short of
  ``SAMPLE_MIN`` each.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np

from bench.reference import archive as ref_archive
from bench.reference import nets as ref_nets
from bench.reference import ppa as ref_ppa
from bench.drivers import campaign as campaign_driver
from bench.run import Check, Window

# in-grid answers, and fallbacks, a run has to check at the least
SAMPLE_MIN = 50
FALLBACK_GAP_LIMIT = 2e-2
MODE_WEIGHTS = ref_ppa.MODE_WEIGHTS
# what ``check(..., control=...)`` puts in the service's place
CONTROLS = ("bf16",)


def _post(url: str, queries) -> dict:
    req = urllib.request.Request(
        url + "/recommend", data=json.dumps({"queries": queries}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _frontiers(root: str):
    with open(os.path.join(root, "manifest.json")) as f:
        cells = sorted(json.load(f)["cells"])
    return {cid: ref_archive.frontier(ref_archive.read_cell(root, cid)[0])
            for cid in cells}


def setup(ctx):
    from repro.launch.recommend import Recommender
    from repro.launch.serve import recommend_server
    t = ctx.cell.traffic
    root = os.path.join(ctx.work, "index")
    campaign_driver.run_one(campaign_driver.spec_for(
        ctx.cell.config, t["campaign"], "bench-index", 0,
        t["campaign"]["campaign_seeds"][0]), root)
    fronts = _frontiers(root)
    rec = Recommender.build([root])
    box, ready = {}, threading.Event()

    def on_ready(srv):
        box["srv"] = srv
        ready.set()

    def serve():
        try:
            recommend_server([root], port=0, recommender=rec,
                             on_ready=on_ready)
        except Exception as e:      # raised again in the caller below
            box["error"] = e
            ready.set()

    th = threading.Thread(target=serve, daemon=True)
    th.start()
    ready.wait(300)
    if "error" in box:
        raise box["error"]
    url = f"http://127.0.0.1:{box['srv'].server_port}"
    mix = dict(t["mix"], grid_arch=ctx.cell.config["arch"])
    # warm-up: every fallback arch's features, every fallback count
    node, mode = mix["nodes"][0], mix["mode"]
    for arch in mix["fallback_archs"]:
        _post(url, [dict(arch=arch, node_nm=node, mode=mode)])
    for k in range(1, mix["max_queries"] + 1):
        _post(url, [dict(arch=mix["fallback_archs"][0], node_nm=node,
                         mode=mode)] * k)
        _post(url, [dict(arch=mix["grid_arch"], node_nm=node,
                         mode=mode)] * k)
    cid = lambda n: f"{mix['grid_arch']}__{n}nm__{mode}"
    power_range = {str(n): [min(p["power_mw"] for p in fronts[cid(n)]),
                            max(p["power_mw"] for p in fronts[cid(n)])]
                   for n in mix["nodes"]}
    return dict(ctx=ctx, root=root, rec=rec, srv=box["srv"], thread=th,
                url=url, fronts=fronts, power_range=power_range, mix=mix)


def window(state, seconds: float) -> Window:
    ctx = state["ctx"]
    t = ctx.cell.traffic
    plan = dict(url=state["url"], clients=t["clients"], seconds=seconds,
                seed=ctx.seed, mix=state["mix"], timeout_s=t["timeout_s"],
                sample_share=t["sample_share"],
                power_range=state["power_range"])
    path = os.path.join(ctx.work, "plan.json")
    with open(path, "w") as f:
        json.dump(plan, f)
    rec = state["rec"]
    disp0 = rec.n_dispatches
    child = subprocess.run([sys.executable, "-m", "bench.loadgen", path],
                           cwd=ctx.cell.root, capture_output=True, text=True,
                           timeout=seconds + 2 * t["timeout_s"] + 60)
    if child.returncode != 0:
        raise RuntimeError(f"load generator failed: {child.stderr[-2000:]}")
    out = json.loads(child.stdout.strip().splitlines()[-1])
    reqs = out["requests"]
    ok = [r for r in reqs if r[2] == 200]
    lat = sorted(r[1] - r[0] for r in reqs)
    answered = sum(r[3] for r in ok)
    state["sample"] = out["sample"]
    win = Window(t0=out["t0"], t1=out["t1"], attempted=len(reqs),
                 failed=len(reqs) - len(ok),
                 e2e=dict(recommend_qps=answered / (out["t1"] - out["t0"])),
                 counts=dict(queries=answered,
                             dispatches=rec.n_dispatches - disp0),
                 labels=[("request", r[0], r[1]) for r in reqs])
    if lat:
        win.e2e["recommend_p99_ms"] = 1e3 * percentile(lat, 99)
    return win


def percentile(sorted_values, p: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    k = max(0, math.ceil(p / 100.0 * len(sorted_values)) - 1)
    return float(sorted_values[k])


# -------------------------------------------------------------- checking
def grid_mismatch(fronts, mode: str, pairs, control=None) -> int:
    """In-grid (query, answer) pairs whose answer is not the plain pick;
    with ``control`` (a rounding), pairs where the selection with its
    objectives and scores so rounded picks another point."""
    bad = 0
    fields = ("power_mw", "perf_gops", "area_mm2", "tok_s", "ppa_score")
    for q, a in pairs:
        cid = f"{q['arch']}__{q['node_nm']}nm__{q['mode']}"
        pts = ref_archive.in_grid(fronts[cid],
                                  q.get("power_budget_mw", math.inf))
        if not pts:
            bad += int(a["source"] != "surrogate")
            continue
        w = MODE_WEIGHTS[q["mode"]]
        j = int(np.argmin(ref_archive.select_scores(pts, w)))
        if control is not None:
            low = [dict(p, **{k: float(control(p[k]))
                              for k in ("power_mw", "perf_gops", "area_mm2")})
                   for p in pts]
            bad += int(int(np.argmin(control(
                ref_archive.select_scores(low, w)))) != j)
            continue
        want = pts[j]
        same = (a["source"] == "archive" and a["cell_id"] == cid
                and a["cfg"] == want["cfg"]
                and all(a[k] == want[k] for k in fields))
        bad += int(not same)
    return bad


def query_context(rec, qq) -> np.ndarray:
    """A fallback's serving context as the service forms it: log1p of the
    arch's workload features and of the plain node constants, in
    float32."""
    feats = np.asarray(rec.index.wl_features(qq["arch"]), np.float32)
    node = ref_ppa.node_vector(qq["node_nm"], qq["mode"])
    return np.concatenate([np.log1p(np.maximum(feats, np.float32(0.0))),
                           np.log1p(node).astype(np.float32)]
                          ).astype(np.float64)


def fallback_readings(rec, fronts, requests, control=None):
    """Gaps of the served fallbacks from the plain surrogate forward at the
    stated precision over the plain candidate pool, in log1p units: the
    widest gap of a served prediction at the served pick, or by which the
    served pick's score lies above the best.  ``requests`` holds each
    sampled request's fallback (query, answer) pairs: the service scores
    them in one dispatch, whose products the reference follows.
    ``control`` (a rounding) puts the reference so rounded throughout, at
    its own picks, in the service's place."""
    pool = ref_archive.pool(fronts)
    cand = ref_ppa.round_f32(np.log1p(np.maximum(
        np.array([p["cfg"] for p in pool], np.float64), 0.0)))
    keys = {tuple(np.asarray(p["cfg"], np.float32).tolist()): j
            for j, p in enumerate(pool)}
    params = {k: {kk: np.asarray(vv, np.float64) for kk, vv in v.items()}
              for k, v in rec.surrogate.params.items()}
    mq = campaign_driver.matmul_operands()
    worst = 0.0
    for pairs in requests:
        if not pairs:
            continue
        ctx = np.stack([query_context(rec, qq) for qq, _ in pairs])
        want = ref_nets.surrogate_log_pred(params, ctx, cand,
                                           q=ref_ppa.round_f32, mq=mq)
        w = np.array([MODE_WEIGHTS[qq["mode"]] for qq, _ in pairs])
        budget = np.array([qq.get("power_budget_mw", np.inf)
                           for qq, _ in pairs])
        zero = np.zeros(len(pairs))
        best = ref_nets.pick(want, w, budget, zero)
        rows = np.arange(len(pairs))
        if control is None:
            j = [keys.get(tuple(np.asarray(a["cfg"], np.float32).tolist()))
                 if a["source"] == "surrogate" else None for _, a in pairs]
            if any(x is None for x in j):
                return math.inf
            got = np.log1p(np.array([[a["power_mw"], a["perf_gops"],
                                      a["area_mm2"]] for _, a in pairs]))
        else:
            low = ref_nets.surrogate_log_pred(params, ctx, cand, q=control,
                                              mq=control)
            c = ref_nets.pick(low, w, budget, zero)
            j = np.argmin(np.where(c["within"][:, None],
                                   np.where(c["ok"], c["score"], np.inf),
                                   c["score"]), axis=1)
            got = low[rows, j]
        worst = max(worst, float(np.abs(got - want[rows, j]).max()),
                    float(np.max(best["score"][rows, j] - best["best"])))
    return worst


def fallbacks_by_request(sample, grid_arch: str):
    """Each sampled request's fallback (query, answer) pairs."""
    return [[(qq, a) for qq, a in zip(item["queries"], item["answers"])
             if qq["arch"] != grid_arch] for item in sample]


def split_pairs(sample, grid_arch: str):
    grid, fall = [], []
    for item in sample:
        for qq, a in zip(item["queries"], item["answers"]):
            (grid if qq["arch"] == grid_arch else fall).append((qq, a))
    return grid, fall


def check(state, win: Window, control=None):
    """The numbers compared, each beside its limit.  ``control`` (one of
    ``CONTROLS``) puts the selection and the surrogate forward in bfloat16
    in the service's place."""
    mix = state["mix"]
    rounding = ref_ppa.round_bf16 if control == "bf16" else None
    grid, fall = split_pairs(state["sample"], mix["grid_arch"])
    gap = fallback_readings(
        state["rec"], state["fronts"],
        fallbacks_by_request(state["sample"], mix["grid_arch"]),
        control=rounding)
    return [Check("grid_mismatch",
                  grid_mismatch(state["fronts"], mix["mode"], grid,
                                control=rounding), 0),
            Check("fallback_gap", gap, FALLBACK_GAP_LIMIT),
            Check("sample_shortfall",
                  max(0, SAMPLE_MIN - len(grid))
                  + max(0, SAMPLE_MIN - len(fall)), 0)]


def close(state):
    srv = state.get("srv")
    if srv is not None:
        srv.shutdown()
        state["thread"].join(60)
