"""Smoke run of the design-space explorer on a TPU chip.

Drives the system's normal path once, in one process, at the paper's own
campaign size: ``smolvlm`` (28 layers, d_model 768) at all 7 process
nodes, seq_len 512, batch 1, 4613 episodes per cell (paper Table 14), 64
lanes per cell, so one dispatch carries 7 x 64 = 448 lanes.  The paper
runs smolvlm in low-power mode; at this budget and seed that mode finds
no feasible design on the chip, so the grid adds high-performance mode,
whose frontiers the reference and recommend phases check.

Phases, in order (any failed check raises, and the script exits non-zero):

1. device check: jax version, platform, device kind and count; anything
   but a TPU stops the run;
2. campaign: ``repro.campaign.run_campaign`` into ``experiments/smoke/``;
   per cell the best design, feasible count, gate and screening counters
   and wall seconds, with compile time reported apart as set-up;
3. determinism: the same campaign again in this process; its
   ``distrib.fingerprint`` must equal the first run's, bit for bit;
4. reference: each cell's final design, and a seeded batch of designs,
   re-evaluated by the same analytic PPA evaluator on the host CPU, and
   both Pallas search kernels against their jnp references at B = 448,
   K = 4, within the tolerances below;
5. recommend: ``serve.recommend_server`` on localhost over the first run,
   in a thread; in-grid answers must equal the archive's ``select()`` bit
   for bit, an out-of-grid answer must be finite, and ``/metrics`` must
   serve;
6. the last line of stdout is ``{"ok": true, "device": {...}}``.

``--chips 4`` runs only the campaign sharded over four chips
(``devices=4``) and unsharded, in one process, and requires equal
fingerprints and a step whose arrays span all four chips.

Run from the checkout root:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
RUN_ROOT = os.path.join(HERE, "experiments", "smoke")

# The paper's own campaign (one cell per process node and mode; the
# planner packs each mode's 7 cells into one 448-lane batch).
PAPER = dict(workloads=["smolvlm"], modes=["low_power", "high_perf"],
             seq_len=512, batch=1, episodes=4613, lanes=64, seed=0)

# Host-CPU re-evaluation vs the chip (relative, on power, perf, area,
# tok/s and ppa_score): the evaluator is elementwise f32 with no matmul,
# so the two backends differ only by the rounding of their
# transcendental and divide implementations (3.5e-7 measured on a v5e).
REEVAL_RTOL = 1e-4
# Pallas kernels vs their jnp references on the same chip, relative to
# the output scale (max(1, max |ref|)).  The jnp path's f32 matmuls run
# at the default precision; on a v5e the two agree to 7.5e-8, so this
# bound leaves room for summation order, not for a wrong kernel.
KERNEL_TOL = 1e-4


def _log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def paper_spec(name: str, **over):
    from repro.campaign import CampaignSpec
    from repro.ppa.nodes import NODES
    kw = dict(PAPER, name=name, nodes=list(NODES))
    kw.update(over)
    kw["max_envs"] = kw["lanes"] * len(kw["nodes"])
    return CampaignSpec(**kw)


# ------------------------------------------------------------- 1. device
def device_info() -> dict:
    import jax
    devs = jax.devices()
    info = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))
    _log(f"jax {jax.__version__}: {info['count']} x {info['kind']} "
         f"({info['platform']})")
    return info


class CompileWatch:
    """Backend compiles (count, seconds) and persistent compile-cache hits
    that jax reports in this process from construction on."""

    def __init__(self):
        import jax
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def since(self, before: tuple) -> tuple:
        """(count, seconds, cache hits) since ``before = watch.mark()``."""
        now = self.mark()
        return tuple(n - b for n, b in zip(now, before))

    def mark(self) -> tuple:
        return (self.count, self.seconds, self.cache_hits)


# ----------------------------------------------------------- 2. campaign
def run_campaign_phase(spec, root: str, label: str, watch: CompileWatch):
    """One campaign through the normal runner; prints per-cell results
    and the set-up/search split of its wall time."""
    from repro.campaign import run_campaign
    shutil.rmtree(root, ignore_errors=True)
    before = watch.mark()
    t0 = time.time()
    store = run_campaign(root, spec, progress=lambda m: None)
    wall = time.time() - t0
    check(store.all_done(), f"{label}: campaign cells not all done")
    spans = {}
    with open(os.path.join(root, "trace.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("ph") == "X":
                spans.setdefault(rec["name"], []).append(rec["dur"])
    first = sum(spans.get("first_dispatch", []))
    n_comp, compile_s, hits = watch.since(before)
    _log(f"{label}: {spec.n_cells} cells in {wall:.2f} s wall; first "
         f"dispatch {first:.2f} s (set-up, includes its compiles); "
         f"{n_comp} compiles took {compile_s:.2f} s, {hits} "
         "persistent-cache hits")
    for cid in sorted(store.manifest["cells"]):
        s = store.load_summary(cid)
        score = s.get("ppa_score")
        _log(f"  {cid}: ppa_score={score} power_mw={s.get('power_mw')} "
             f"tok_s={s.get('tok_s')} feasible={s['feasible']} "
             f"gate_open_episode={s['gate_open_episode']} "
             f"screened/evaluated={s['screened']}/{s['evaluated']} "
             f"wall_s={s['wall_s']}")
    return store, dict(wall_s=wall, first_dispatch_s=first,
                       compile_s=compile_s)


# -------------------------------------------------------- 3. determinism
def determinism_phase(spec, store, root: str, watch: CompileWatch):
    from repro.campaign.distrib import fingerprint
    again, stats = run_campaign_phase(spec, root, "rerun", watch)
    same = fingerprint(again) == fingerprint(store)
    _log(f"determinism: fingerprints {'equal' if same else 'DIFFER'}")
    check(same, "the rerun's fingerprint differs from the first run's")
    return stats


# ---------------------------------------------------------- 4. reference
REEVAL_FIELDS = ("power_mw", "perf_gops", "area_mm2", "tok_s", "ppa_score")


def _rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)
                        / np.maximum(np.abs(want), 1e-30)))


def reevaluate_on_host(store, spec) -> float:
    """The analytic evaluator on the host CPU against the chip: each
    cell's final design against the metrics the chip run stored for it,
    and a seeded batch of designs (one per lane of a dispatch, so also
    for cells that found no feasible design) evaluated on both.  Returns
    the largest relative error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch.recommend import MODE_WEIGHTS, split_cell_id
    from repro.ppa import config_space as cs
    from repro.ppa.analytic import M_IDX, evaluate_vec_jit, node_vector
    from repro.ppa.nodes import node_params
    from repro.workload.extract import extract

    cpu = jax.devices("cpu")[0]
    cols = [M_IDX[f] for f in REEVAL_FIELDS]

    def on_host(cfgs, wl_vec, nodes):
        return np.asarray(evaluate_vec_jit(*(
            jax.device_put(np.asarray(x, np.float32), cpu)
            for x in (cfgs, wl_vec, nodes))))

    def node_row(node_nm, mode):
        return node_vector(node_params(node_nm, low_power=mode != "high_perf"),
                           high_perf=mode == "high_perf")

    worst = 0.0
    cells = sorted(store.manifest["cells"])
    for cid in cells:
        entries = store.load_archive(cid).entries
        if not entries:
            _log(f"  host re-eval {cid}: no feasible design to re-evaluate")
            continue
        arch, node_nm, mode = split_cell_id(cid)
        # the cell's final design: the archive's scalarized select(),
        # whose metrics the chip stored in the cell summary
        best = store.load_archive(cid).select(*MODE_WEIGHTS[mode])
        summary = store.load_summary(cid)
        wl = extract(get_config(arch), seq_len=spec.seq_len,
                     batch=spec.batch)
        m = on_host(np.asarray(cs.project(jnp.asarray(best.cfg)))[None],
                    wl.features, node_row(node_nm, mode)[None])
        check(m[0, M_IDX["feasible"]] > 0.5, f"{cid}: infeasible on the host")
        err = _rel_err(m[0, cols], [summary[f] for f in REEVAL_FIELDS])
        worst = max(worst, err)
        _log(f"  host re-eval {cid}: best design max rel err {err:.3e}")

    # a seeded batch of projected designs, one per lane, on both backends
    arch, _, mode = split_cell_id(cells[0])
    wl = extract(get_config(arch), seq_len=spec.seq_len, batch=spec.batch)
    rng = np.random.default_rng(spec.seed)
    b = spec.lanes * len(spec.nodes)
    cfgs = np.asarray(cs.project(jnp.asarray(
        cs.LO + rng.random((b, cs.DIM), np.float32) * (cs.HI - cs.LO))))
    nodes = np.stack([node_row(n, mode)
                      for n in np.repeat(spec.nodes, spec.lanes)])
    chip = np.asarray(evaluate_vec_jit(cfgs, jnp.asarray(wl.features),
                                       jnp.asarray(nodes, jnp.float32)))
    host = on_host(cfgs, wl.features, nodes)
    flips = int(np.sum(chip[:, M_IDX["feasible"]]
                       != host[:, M_IDX["feasible"]]))
    err = _rel_err(chip[:, cols], host[:, cols])
    worst = max(worst, err)
    _log(f"  host vs chip on {b} seeded designs: max rel err {err:.3e}, "
         f"{flips} feasibility flips")
    check(flips == 0, f"{flips} designs change feasibility on the host")
    check(worst <= REEVAL_RTOL,
          f"host re-evaluation differs by {worst:.3e} > {REEVAL_RTOL}")
    _log(f"reference: host CPU agrees with the chip to {worst:.3e} "
         f"(tolerance {REEVAL_RTOL})")
    return worst


def kernel_phase(batch: int, k: int, seed: int, *,
                 interpret: bool = False) -> dict:
    """Both search kernels against their jnp references at (B, K)."""
    import jax
    import numpy as np

    from repro.core import networks as nets
    from repro.core.actions import N_CONT
    from repro.core.state import SAC_STATE_DIM
    from repro.kernels import ops, ref
    from repro.ppa import surrogate as sur_mod

    rng = np.random.default_rng(seed)
    s = rng.normal(0.0, 1.0, (batch, SAC_STATE_DIM)).astype(np.float32)
    actor = nets.actor_init(jax.random.PRNGKey(seed))
    got = ops.actor_forward(actor, s, interpret=interpret)
    want = ref.actor_forward_reference(actor, s)
    errs = {}
    for name, g, w in zip(("disc", "mu", "log_std", "gate"), got, want):
        g, w = np.asarray(g), np.asarray(w)
        check(g.shape == w.shape and np.isfinite(g).all(),
              f"actor_moe {name}: shape {g.shape} vs {w.shape} or "
              "non-finite output")
        errs[f"actor_moe.{name}"] = (float(np.max(np.abs(g - w)))
                                     / max(1.0, float(np.max(np.abs(w)))))

    sur = sur_mod.init_params(jax.random.PRNGKey(seed + 1),
                              SAC_STATE_DIM + N_CONT)
    cand = rng.uniform(-1.0, 1.0, (batch, k, N_CONT)).astype(np.float32)
    w = rng.dirichlet(np.ones(3), batch).astype(np.float32)
    score = np.asarray(ops.screen_scores(sur, s, cand, w,
                                         interpret=interpret))
    score_ref = np.asarray(ref.screen_scores_reference(sur, s, cand, w))
    scale = max(1.0, float(np.max(np.abs(score_ref))))
    errs["screen_score"] = float(np.max(np.abs(score - score_ref))) / scale
    gate = np.ones(batch, bool)
    pick = np.asarray(ops.screen_batch(sur, s, cand, w, gate,
                                       interpret=interpret))
    pick_ref = np.asarray(sur_mod.screen_batch(sur, s, cand, w, gate))
    rows = np.arange(batch)
    # a differing pick is fine only between near-tied candidates
    errs["screen_pick"] = float(np.max(np.abs(
        score_ref[rows, pick] - score_ref[rows, pick_ref]))) / scale
    for name, e in errs.items():
        _log(f"  kernel {name}: max err / scale {e:.3e}")
    _log(f"kernels at B={batch}, K={k}: {int((pick != pick_ref).sum())} of "
         f"{batch} screening picks differ from the jnp path")
    worst = max(errs.values())
    check(worst <= KERNEL_TOL,
          f"kernel vs jnp reference differs by {worst:.3e} > {KERNEL_TOL}")
    return errs


# ---------------------------------------------------------- 5. recommend
def recommend_phase(store) -> int:
    """Serve the run's archives over HTTP on localhost and check the
    answers; returns the number of in-grid answers checked."""
    import numpy as np

    from repro.launch.recommend import MODE_WEIGHTS, split_cell_id
    from repro.launch.serve import recommend_server

    ready = threading.Event()
    box = {}

    def on_ready(srv):
        box["srv"] = srv
        ready.set()

    def serve():
        try:
            recommend_server([store.root], port=0, on_ready=on_ready)
        except Exception as e:   # re-raised below, in the main thread
            box["error"] = e
            ready.set()

    th = threading.Thread(target=serve, daemon=True)
    th.start()
    check(ready.wait(600), "recommend server did not start")
    if "error" in box:
        raise box["error"]
    srv = box["srv"]
    url = f"http://127.0.0.1:{srv.server_port}"

    def post(queries):
        req = urllib.request.Request(
            url + "/recommend", data=json.dumps({"queries": queries}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as r:
            return json.loads(r.read())

    try:
        cells = [c for c in sorted(store.manifest["cells"])
                 if store.load_archive(c).entries]
        queries = []
        for cid in cells:
            arch, node_nm, mode = split_cell_id(cid)
            queries.append(dict(arch=arch, node_nm=node_nm, mode=mode))
        out = post(queries)
        for cid, ans in zip(cells, out["answers"]):
            mode = split_cell_id(cid)[2]
            want = store.load_archive(cid).select(*MODE_WEIGHTS[mode])
            check(ans["source"] == "archive", f"{cid}: {ans['source']}")
            check(ans["cfg"] == np.asarray(want.cfg, np.float64).tolist()
                  and ans["ppa_score"] == want.ppa_score,
                  f"{cid}: served answer differs from the archive select()")
        _log(f"recommend: {len(cells)} in-grid answers equal select() "
             f"bit for bit ({out['dispatches']} dispatches)")
        node_nm = split_cell_id(cells[0])[1]
        oog = post([dict(arch="smollm-135m", node_nm=node_nm,
                         mode="low_power")])["answers"][0]
        vals = [oog["power_mw"], oog["perf_gops"], oog["area_mm2"]]
        check(oog["source"] == "surrogate"
              and all(math.isfinite(v) for v in vals),
              f"out-of-grid answer not a finite surrogate answer: {oog}")
        _log(f"recommend: out-of-grid smollm-135m@{node_nm}nm -> surrogate "
             f"power_mw={vals[0]:.4g} perf_gops={vals[1]:.4g} "
             f"area_mm2={vals[2]:.4g}")
        with urllib.request.urlopen(url + "/metrics", timeout=60) as r:
            text = r.read().decode()
        check("repro_serve_answers_total" in text, "/metrics lacks answers")
        _log(f"recommend: /metrics served {len(text.splitlines())} lines")
    finally:
        srv.shutdown()
        th.join(60)
    return len(cells)


# ------------------------------------------------------------ 4 chips
def multichip_phase(spec, n: int, root: str, watch: CompileWatch) -> None:
    """The campaign sharded over ``n`` devices and unsharded, in this
    process: equal fingerprints, and a step that spans all n devices."""
    import numpy as np

    from repro.campaign.distrib import fingerprint
    from repro.configs import get_config
    from repro.core import actions as act
    from repro.core.env import VecDSEEnv
    from repro.workload.extract import extract

    sharded, _ = run_campaign_phase(dataclasses.replace(spec, devices=n),
                                    os.path.join(root, f"devices{n}"),
                                    f"devices={n}", watch)
    plain, _ = run_campaign_phase(spec, os.path.join(root, "plain"),
                                  "devices=None", watch)
    same = fingerprint(sharded) == fingerprint(plain)
    _log(f"devices={n} vs None: fingerprints {'equal' if same else 'DIFFER'}")
    check(same, f"devices={n} campaign differs from the unsharded one")

    wl = extract(get_config(spec.workloads[0]), seq_len=spec.seq_len,
                 batch=spec.batch)
    b = spec.lanes * len(spec.nodes)
    env = VecDSEEnv(wl, np.repeat(spec.nodes, spec.lanes).tolist(),
                    high_perf=spec.modes[0] == "high_perf", devices=n)
    env.reset()
    env.step(np.zeros((b, act.N_CONT), np.float32),
             np.zeros((b, act.N_DISC), np.int32))
    shards = env.cfg.addressable_shards
    rows = sorted(sh.data.shape[0] for sh in shards)
    _log(f"sharded step: cfg on {len(env.cfg.sharding.device_set)} devices, "
         f"rows per shard {rows}")
    check(len(env.cfg.sharding.device_set) == n
          and rows == [b // n] * n, "the sharded step does not span "
          f"{n} devices evenly")


# --------------------------------------------------------------- main
def run_one_chip(spec, root: str, watch: CompileWatch, *,
                 interpret: bool = False) -> dict:
    """Phases 2-5 on the default device; returns per-phase seconds."""
    phases = {}
    t = time.time()
    store, first = run_campaign_phase(spec, os.path.join(root, "run1"),
                                      "campaign", watch)
    phases["campaign"] = time.time() - t
    t = time.time()
    again = determinism_phase(spec, store, os.path.join(root, "run2"),
                              watch)
    phases["determinism"] = time.time() - t
    t = time.time()
    reevaluate_on_host(store, spec)
    kernel_phase(spec.lanes * len(spec.nodes), spec.screen_k, spec.seed,
                 interpret=interpret)
    phases["reference"] = time.time() - t
    t = time.time()
    recommend_phase(store)
    phases["recommend"] = time.time() - t
    _log("phase seconds: " + ", ".join(f"{k} {v:.2f}"
                                       for k, v in phases.items()))
    _log(f"compile seconds: first run {first['compile_s']:.2f}, rerun "
         f"{again['compile_s']:.2f}")
    return phases


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the campaign sharded over four chips "
                         "against the unsharded one")
    a = ap.parse_args(argv)
    info = device_info()
    if info["platform"] != "tpu":
        sys.exit(f"[smoke] needs a TPU; JAX found {info['platform']}")
    if info["count"] < a.chips:
        sys.exit(f"[smoke] --chips {a.chips}: only {info['count']} "
                 "device(s) visible")
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    _log(f"compile cache: {enable_compile_cache()}")
    watch = CompileWatch()
    if a.chips > 1:
        multichip_phase(paper_spec("paper-smolvlm-multichip"), a.chips,
                        RUN_ROOT, watch)
    else:
        run_one_chip(paper_spec("paper-smolvlm"), RUN_ROOT, watch)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
