"""Traffic kind ``campaign_moe``: the ``campaign`` kind's campaigns, window
and checks over a decoder with leading dense layers, routed and shared
experts and latent attention, with the designs compared against
``bench/reference/ppa_moe.py``.

Set-up, the actor and learner checks, the digests and the controls are
the ``campaign`` kind's, by import.  The workload the designs are scored
on differs: ``ppa_moe.workload_features`` (routed-expert weight traffic,
routing imbalance, shared experts) in place of ``ppa.workload_features``,
which knows dense decoders alone.  And since most designs of such a
model break a constraint, and the archive keeps feasible designs alone,
the window keeps besides, by reference, the rows of one fused env step
(``_vec_step_analytic``) among its first ``DISPATCH_CALLS``: every design
the step evaluated, rejected or not, with its metrics.

What decides ``correct``: as for ``campaign``, with ``ppa_gap`` against
the MoE reference, ``learner_grad_gap`` at a limit of its own, set from
this kind's readings (PERF.md): the program's gradients read up to
2.93e-3 on DeepSeek-V2-Lite's states, over ``campaign``'s 2.5e-3, while
the bfloat16 control still fails ``ppa_gap`` on every seed.  And besides,
on the kept step's rows:

- ``dispatch_ppa_gap``: the widest relative gap over power, perf, area,
  tok/s and PPA score of every row against the reference;
- ``feasible_mismatch``: rows whose ``feasible`` or ``wmem_ok`` verdict
  (Eq. 14, the ROM holds the weights) differs from the reference's,
  where the reference's margin of that verdict lies beyond
  ``PPA_GAP_LIMIT`` of the boundary.

``best_ppa_score`` is reported only where every cell of the set's
campaigns archived a design the reference finds feasible.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from bench.drivers import campaign
from bench.drivers.campaign import CONTROLS, close, setup  # noqa: F401
from bench.reference import archive as ref_archive
from bench.reference import ppa as ref_ppa
from bench.reference import ppa_moe
from bench.run import Check

PPA_GAP_LIMIT = campaign.PPA_GAP_LIMIT
LEARNER_LIMITS = dict(campaign.LEARNER_LIMITS, learner_grad_gap=1e-2)
# the kept env step is drawn among the window's first ones, all in its
# first campaign (72 at the cell's grid)
DISPATCH_CALLS = campaign.ACT_CALLS


def window(state, seconds: float):
    """``campaign.window``, keeping besides the arguments and results of
    one fused env step drawn from the seed."""
    from repro.core import env as env_mod
    pick = int(np.random.default_rng([state["ctx"].seed, 1])
               .integers(DISPATCH_CALLS))
    with campaign.Tap(env_mod, "_vec_step_analytic", [pick]) as tap:
        state["dispatch_tap"] = tap
        return campaign.window(state, seconds)


def wmem_margin(cfg: np.ndarray, wl: dict) -> np.ndarray:
    """Eq. 14 in the reference's terms: the ROM's bytes less the weights'
    at the design's precision, relative to the larger side; >= 0 where
    the weights fit."""
    c = ref_ppa.project(cfg)
    g = lambda name: c[:, ref_ppa.F[name]]  # noqa: E731
    have = np.round(g("mesh_w")) * np.round(g("mesh_h")) * g("wmem_kb") \
        * 1024.0
    need = wl["weight_mb"] * 1e6 * (1.0 - 0.5 * g("precision"))
    return (have - need) / np.maximum(np.maximum(have, need), 1e-30)


def kept_dispatch(tap):
    """(designs, node of each row, the program's metrics rows) of the kept
    env step, or None where the window made no such step."""
    if tap is None or not tap.kept:
        return None
    from repro.ppa.analytic import NODE_IDX
    (args, out), = tap.kept.values()
    return (np.asarray(out[0], np.float64),
            np.asarray(args[4])[:, NODE_IDX["node_nm"]].astype(int),
            np.asarray(out[1], np.float64))


def dispatch_readings(rows, model: dict, deployment: dict, mode: str,
                      control=None):
    """``dispatch_ppa_gap`` and ``feasible_mismatch`` of the kept step's
    rows (see the module's docstring).  With ``control`` (a rounding) the
    reference computed under it stands in for the program's metrics and
    verdicts."""
    from repro.ppa.analytic import M_IDX
    cfg, node_nm, metrics = rows
    wl = ppa_moe.workload_features(model, deployment["seq_len"],
                                   deployment["batch"])
    want = ref_ppa.evaluate(cfg, wl, ref_ppa.node_columns(node_nm, mode))
    want_wmem = wmem_margin(cfg, wl)
    if control is None:
        got = {k: metrics[:, M_IDX[k]] for k in ref_ppa.COMPARED}
        feasible = metrics[:, M_IDX["feasible"]] > 0.5
        wmem_ok = metrics[:, M_IDX["wmem_ok"]] > 0.5
    else:
        got = ref_ppa.evaluate(cfg, wl, ref_ppa.node_columns(node_nm, mode),
                               q=control)
        feasible = got["margin"] >= 0.0
        wmem_ok = want_wmem >= 0.0
    wrong = (((feasible != (want["margin"] >= 0.0))
              & (np.abs(want["margin"]) > PPA_GAP_LIMIT))
             | ((wmem_ok != (want_wmem >= 0.0))
                & (np.abs(want_wmem) > PPA_GAP_LIMIT)))
    return dict(dispatch_ppa_gap=float(ref_ppa.relative_gap(got, want).max()),
                feasible_mismatch=int(wrong.sum()))


def ppa_readings(root: str, model: dict, deployment: dict, mode: str,
                 control=None):
    """``campaign.ppa_readings`` with the designs scored on the MoE
    reference's workload features."""
    wl = ppa_moe.workload_features(model, deployment["seq_len"],
                                   deployment["batch"])
    out = {}
    with open(os.path.join(root, "manifest.json")) as f:
        cell_ids = sorted(json.load(f)["cells"])
    for cid in cell_ids:
        node_nm = int(cid.split("__")[1][:-2])
        points, summary = ref_archive.read_cell(root, cid)
        if not points:
            out[cid] = dict(gap=0.0, best=None, n=0)
            continue
        cfg = np.array([p["cfg"] for p in points], np.float64)
        node = ref_ppa.node_columns([node_nm] * len(points), mode)
        want = ref_ppa.evaluate(cfg, wl, node)
        if control is None:
            got = {k: np.array([p[k] for p in points]) for k in
                   ref_ppa.COMPARED}
            margin = want["margin"]
        else:
            got = ref_ppa.evaluate(cfg, wl, node, q=control)
            margin = got["margin"]
        err = np.maximum(ref_ppa.relative_gap(got, want),
                         np.maximum(0.0, -margin))
        # the final design: the frontier's scalarized pick, whose metrics
        # the cell summary reports
        front = ref_archive.frontier(points)
        pick = front[int(np.argmin(ref_archive.select_scores(
            front, ref_ppa.MODE_WEIGHTS[mode])))]
        i = points.index(pick)
        final = ({k: np.array([summary[k]]) for k in ref_ppa.COMPARED}
                 if control is None else
                 {k: v[i:i + 1] for k, v in got.items()})
        final_gap = ref_ppa.relative_gap(
            final, {k: v[i:i + 1] for k, v in want.items()})
        feasible = want["margin"] >= 0.0
        out[cid] = dict(
            gap=float(max(err.max(), final_gap.max())), n=len(points),
            best=(float(want["ppa_score"][feasible].min())
                  if feasible.any() else None))
    return out


def check(state, win, control=None):
    """``campaign.check`` with ``ppa_gap`` and ``best_ppa_score`` from the
    MoE reference."""
    ctx = state["ctx"]
    cfg, t = ctx.cell.config, ctx.cell.traffic
    rounding = ref_ppa.round_bf16 if control == "bf16" else None
    gaps, best = [], []
    for seed, root in sorted(state["roots"].items()):
        cells = ppa_readings(root, cfg["model"], cfg["deployment"],
                             t["mode"], control=rounding)
        gaps += [c["gap"] for c in cells.values()]
        best += [c["best"] for c in cells.values()]
    if control is None and len(state["roots"]) == len(t["campaign_seeds"]):
        if all(b is not None for b in best):
            win.e2e["best_ppa_score"] = ref_ppa.geomean(best)
        else:
            print(f"[bench] {sum(b is None for b in best)} of {len(best)} "
                  f"cells archived no design the reference finds feasible",
                  file=sys.stderr)
    act = campaign.kept_act(state["taps"])
    actor = (np.inf if act is None else
             campaign.actor_gap(*act, control=rounding))
    kept = campaign.kept_steps(state["taps"])
    learner = (dict.fromkeys(LEARNER_LIMITS, np.inf) if kept is None else
               campaign.learner_readings(kept, control=control))
    rows = kept_dispatch(state.get("dispatch_tap"))
    disp = (dict(dispatch_ppa_gap=np.inf, feasible_mismatch=np.inf)
            if rows is None else
            dispatch_readings(rows, cfg["model"], cfg["deployment"],
                              t["mode"], control=rounding))
    differ = sum(d != ds[0] for ds in state["digests"].values() for d in ds)
    return ([Check("ppa_gap", max(gaps), PPA_GAP_LIMIT),
             Check("dispatch_ppa_gap", disp["dispatch_ppa_gap"],
                   PPA_GAP_LIMIT),
             Check("feasible_mismatch", disp["feasible_mismatch"], 0),
             Check("actor_gap", actor, campaign.ACTOR_GAP_LIMIT)]
            + [Check(name, learner[name], limit)
               for name, limit in LEARNER_LIMITS.items()]
            + [Check("campaign_digests_differ", differ, 0)])
