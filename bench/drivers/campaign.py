"""Traffic kind ``campaign``: whole design-space campaigns back to back.

The traffic names a fixed set of campaigns (``campaign_seeds``); the
run's ``--seed`` picks the order in which the window takes them.  Set-up
runs the set's first campaign, which compiles (or reads from the
persistent cache) every program the grid uses.  The window then runs the
set's campaigns in order, round and round, each into a fresh run
directory, in whole rounds of the set until ``--seconds`` have passed.
So every run does the same work in another order, and the rate and the
design quality do not swing with where one seed's search happens to go.

Traffic parameters: ``mode``, ``nodes``, ``lanes`` (environments per cell),
``episodes`` (per-cell budget), ``checkpoint_every`` (dispatches) and
``campaign_seeds``.

While the window runs, the driver keeps (by reference, with no copy and
no wait) the arguments and results of a few calls the search loop makes,
at call numbers drawn from the seed: one policy act (``policy_act_batch``)
among the first ``ACT_CALLS``, and three consecutive learner steps
(``sac.update``), the first among the first ``LEARNER_CALLS``.

What decides ``correct`` (limits from the readings in PERF.md):
- ``ppa_gap``: every design the window's campaigns archived, and each
  cell's final design, evaluated by the plain reference
  (``bench/reference/ppa.py``, float64 from published sizes): the widest
  relative gap over power, perf, area, tok/s and PPA score, or by which
  the reference finds a constraint violated;
- ``actor_gap``: the ``actor_moe`` kernel on the weights and states of
  the kept policy act against the plain actor forward at the stated
  precision (``bench/reference/nets.py``): per output the summed gap over
  the summed value, the worst output;
- ``learner_loss_gap``, ``learner_grad_gap``, ``learner_change_gap``: the
  three kept learner steps against the plain SAC update
  (``bench/reference/sac.py``) run from the first step's state on the
  same batches and keys: each step's four losses; the first step's
  gradient by leaf, as the optimizer's moments show it; the parameters'
  change over the three steps by leaf (``learner_readings``);
- ``campaign_digests_differ``: campaigns of the run whose archived
  designs and summaries differ from the first run of the same campaign
  seed (set-up included; exact).

``best_ppa_score`` is the geometric mean, over every cell of the set's
campaigns, of the lowest PPA score the reference gives a design the cell
archived and finds feasible.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys
import time

import numpy as np

from bench import counts
from bench.reference import archive as ref_archive
from bench.reference import nets as ref_nets
from bench.reference import ppa as ref_ppa
from bench.run import Check, Window

PPA_GAP_LIMIT = 1e-4
ACTOR_GAP_LIMIT = 3e-3
LEARNER_LIMITS = dict(learner_loss_gap=3e-4, learner_grad_gap=2.5e-3,
                      learner_change_gap=3e-3)
# the kept calls are drawn among the first ones of the window's first
# campaign, which at the paper's grid makes about 72 policy acts and 276
# learner steps
ACT_CALLS, LEARNER_CALLS, LEARNER_STEPS = 48, 96, 3
# leaves whose reference gradient is under this share of the median leaf's
# move by round-off alone and are left out of the compared change
STILL_LEAF = 1e-3
LOSSES = ("loss_q1", "loss_q2", "loss_actor", "loss_alpha")
SPANS = ("run_batch", "run_search_cells", "checkpoint", "complete_cell",
         "write_reports")
# what ``check(..., control=...)`` puts in the program's place
CONTROLS = ("bf16", "half_batch")


def order(seeds, run_seed: int):
    """The set's campaign seeds, rotated by the run's seed."""
    k = run_seed % len(seeds)
    return list(seeds[k:]) + list(seeds[:k])


def spec_for(config, traffic, name: str, checkpoint_every: int, seed: int):
    """The ``CampaignSpec`` of one campaign of a configuration under a
    traffic's grid."""
    from repro.campaign import CampaignSpec
    t, dep = traffic, config["deployment"]
    return CampaignSpec(
        name=name, workloads=[config["arch"]],
        nodes=list(t["nodes"]), modes=[t["mode"]], episodes=t["episodes"],
        lanes=t["lanes"], max_envs=t["lanes"] * len(t["nodes"]),
        seed=seed, seq_len=dep["seq_len"], batch=dep["batch"],
        checkpoint_every=checkpoint_every)


def run_one(spec, root: str):
    from repro.campaign import run_campaign
    shutil.rmtree(root, ignore_errors=True)
    store = run_campaign(root, spec, progress=lambda m: None)
    if not store.all_done():
        raise RuntimeError(f"campaign in {root} left cells undone")
    return store


def digest(root: str) -> str:
    """Hash of a run's archived designs and cell summaries, wall-clock
    fields left out."""
    h = hashlib.sha256()
    cells = os.path.join(root, "cells")
    for name in sorted(os.listdir(cells)):
        with open(os.path.join(cells, name)) as f:
            for line in f:
                if not line.strip():
                    continue
                rec = json.loads(line)
                rec.pop("wall_s", None)
                h.update(json.dumps(rec, sort_keys=True).encode())
    return h.hexdigest()


def read_spans(root: str):
    out = []
    with open(os.path.join(root, "trace.jsonl")) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("ph") == "X" and rec.get("name") in SPANS:
                out.append(rec)
    return out


class Tap:
    """Stands in for a module's function while the window runs and keeps
    the arguments and result of the calls numbered in ``picks``, by
    reference: it neither copies nor waits for the device."""

    def __init__(self, module, name: str, picks):
        self.module, self.name = module, name
        self.inner = getattr(module, name)
        self.picks, self.calls, self.kept = set(picks), 0, {}

    def __call__(self, *args, **kw):
        out = self.inner(*args, **kw)
        if self.calls in self.picks:
            self.kept[self.calls] = (args, out)
        self.calls += 1
        return out

    def __enter__(self):
        setattr(self.module, self.name, self)
        return self

    def __exit__(self, *exc):
        setattr(self.module, self.name, self.inner)


def setup(ctx):
    first = ctx.cell.traffic["campaign_seeds"][0]
    warm = os.path.join(ctx.work, "setup")
    cfg, t = ctx.cell.config, ctx.cell.traffic
    run_one(spec_for(cfg, t, "bench-setup", 0, first), warm)
    return dict(ctx=ctx, specs=[spec_for(cfg, t, "bench",
                                         t["checkpoint_every"], s)
                                for s in order(t["campaign_seeds"],
                                               ctx.seed)],
                digests={first: [digest(warm)]}, roots={})


def window(state, seconds: float) -> Window:
    from repro.core import sac as sac_mod
    from repro.kernels import ops as kernel_ops
    ctx, specs = state["ctx"], state["specs"]
    t = ctx.cell.traffic
    cells, lanes = len(t["nodes"]), t["lanes"]
    draw = np.random.default_rng(ctx.seed)
    act_pick = int(draw.integers(ACT_CALLS))
    k = int(draw.integers(LEARNER_CALLS))
    taps = [Tap(kernel_ops, "policy_act_batch", [act_pick]),
            Tap(sac_mod, "policy_act_batch", [act_pick]),
            Tap(sac_mod, "update", range(k, k + LEARNER_STEPS))]
    state["taps"] = taps
    steps, flops, dispatches, attempted, failed = 0, 0.0, 0, 0, 0
    spans, labels, runs = [], [], []
    # each window of a process writes run directories of its own
    state["windows"] = state.get("windows", 0) + 1
    for tap in taps:
        tap.__enter__()
    try:
        t0 = time.time()
        while True:
            spec = specs[attempted % len(specs)]
            root = os.path.join(
                ctx.work, f"window{state['windows']:02d}-{attempted:03d}")
            attempted += 1
            a = time.time()
            try:
                store = run_one(spec, root)
            except RuntimeError:
                failed += 1
                break
            labels.append(("campaign", a, time.time()))
            runs.append((spec.seed, root))
            for cid in store.manifest["cells"]:
                ep = store.load_summary(cid)["episodes"]
                steps += ep
            n_disp = ep // lanes
            dispatches += n_disp
            flops += counts.campaign_flops(n_disp, lanes, cells,
                                           t["episodes"])
            if time.time() - t0 >= seconds and attempted % len(specs) == 0:
                break
        t1 = time.time()
    finally:
        for tap in taps:
            tap.__exit__()
    for seed, root in runs:
        recs = read_spans(root)
        spans.extend(recs)
        labels.extend((r["name"], r["ts"], r["ts"] + r["dur"]) for r in recs)
        state["digests"].setdefault(seed, []).append(digest(root))
        if seed in state["roots"]:
            shutil.rmtree(root, ignore_errors=True)
        else:
            state["roots"][seed] = root
    return Window(t0=t0, t1=t1, attempted=attempted, failed=failed,
                  e2e=dict(env_steps_per_s=steps / (t1 - t0)),
                  counts=dict(flops=flops, dispatches=dispatches,
                              actor_rows=lanes * cells),
                  spans=spans, labels=labels)


# -------------------------------------------------------------- checking
def ppa_readings(root: str, model: dict, deployment: dict, mode: str,
                 control=None):
    """Per cell of the run at ``root``: the widest design error of its
    archived designs and final design against the reference, and the
    reference's best feasible PPA score.  With ``control`` (a rounding)
    the reference computed under it stands in for the program's
    metrics."""
    wl = ref_ppa.workload_features(model, deployment["seq_len"],
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


def as_tree(x):
    """A program pytree (named tuples, dicts) as nested dicts of numpy
    arrays keyed by field name."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(x)[0]:
        names = [getattr(p, "name", getattr(p, "key", getattr(p, "idx", p)))
                 for p in path]
        node = out
        for n in names[:-1]:
            node = node.setdefault(n, {})
        node[names[-1]] = np.asarray(leaf)
    return out


def leaves(tree, prefix: str = ""):
    """A nested dict of arrays as one flat dict keyed by the path,
    ``a/b/c``, in float64."""
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(leaves(tree[k], f"{prefix}/{k}" if prefix else k))
        return out
    return {prefix: np.asarray(tree, np.float64)}


def matmul_operands():
    """Rounding of matrix-product operands at the platform's default
    precision: bfloat16 on a TPU, float32 on the CPU."""
    import jax
    return (ref_ppa.round_bf16 if jax.default_backend() == "tpu"
            else ref_ppa.round_f32)


def kept_act(taps):
    """(actor weights, states) of the kept policy act."""
    for tap in taps:
        if tap.name == "policy_act_batch" and tap.kept:
            (args, _), = tap.kept.values()
            return (as_tree(args[0]),
                    np.asarray(args[1], np.float64))
    return None


def actor_gap(params, states, control=None) -> float:
    """Per output of the ``actor_moe`` kernel (or, with ``control``, of
    the reference rounded by it throughout), the summed gap from the
    reference at the stated precision over the summed value; the worst
    output."""
    want = ref_nets.actor(params, states, q=ref_ppa.round_f32,
                          mq=matmul_operands())
    if control is None:
        import jax
        from repro.kernels import ops as kernel_ops
        rows = states.shape[0]
        out = kernel_ops.actor_forward(
            jax.tree.map(lambda v: np.asarray(v, np.float32), params),
            states.astype(np.float32),
            interpret=jax.default_backend() != "tpu")
        got = dict(zip(("disc", "mu", "log_std", "gate"),
                       (np.asarray(o, np.float64).reshape(rows, -1)
                        for o in out)))
    else:
        got = ref_nets.actor(params, states, q=control, mq=control)
    return max(float(np.abs(got[k] - want[k]).sum())
               / max(float(np.abs(want[k]).sum()), 1e-30) for k in want)


def kept_steps(taps):
    """(state before the first kept learner step, batches, keys, the
    program's state and losses after each), or None when the window made
    fewer steps or they do not follow one another."""
    tap = next(t for t in taps if t.name == "update")
    calls = sorted(tap.kept)
    if len(calls) < LEARNER_STEPS:
        return None
    for a, b in zip(calls, calls[1:]):
        if tap.kept[b][0][0] is not tap.kept[a][1][0]:
            return None
    args = [tap.kept[c][0] for c in calls]
    outs = [tap.kept[c][1] for c in calls]
    return dict(
        before=as_tree(args[0][0]),
        batches=[as_tree(a[1]) for a in args],
        keys=[np.asarray(a[2]) for a in args],
        states=[as_tree(o[0]) for o in outs],
        losses=[{k: float(o[2][k]) for k in LOSSES} for o in outs])


def moment_grads(before, after):
    """The gradient each optimizer received in one step, worked out from
    its first moment before and after it, keyed as the reference's."""
    from bench.reference.sac import ADAM_B1
    out = {}
    for name, opt in (("actor", "actor"), ("q1", "q1"), ("q2", "q2"),
                      ("log_alpha", "alpha")):
        m0 = leaves(before["opt"][opt]["m"], name)
        m1 = leaves(after["opt"][opt]["m"], name)
        out.update({k: (v - ADAM_B1 * m0[k]) / (1 - ADAM_B1)
                    for k, v in m1.items()})
    return out


def _norm_gap(got, want, skip=()):
    """Worst leaf: the gap between the norms over the larger of the
    reference's norm of that leaf and of the median leaf."""
    n_want = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    scale = float(np.median([v for k, v in n_want.items() if k not in skip]))
    return max(abs(float(np.linalg.norm(got[k])) - n_want[k])
               / max(n_want[k], scale, 1e-30)
               for k in want if k not in skip)


def learner_readings(kept, control=None):
    """The three learner numbers of the kept steps (see the module's
    docstring); ``control`` puts the reference in bfloat16
    (``"bf16"``), or the reference on the first half of each batch
    (``"half_batch"``), in the program's place."""
    from bench.reference import sac as ref_sac
    rnd = (ref_sac.to_bf16 if matmul_operands() is ref_ppa.round_bf16
           else ref_sac.keep)
    before, batches, keys = kept["before"], kept["batches"], kept["keys"]
    ref_states, ref_losses, ref_grads = ref_sac.follow(
        before, batches, keys, rnd=rnd)
    if control is None:
        states, losses = kept["states"], kept["losses"]
        g_got = moment_grads(before, states[0])
    else:
        if control == "bf16":
            import jax.numpy as jnp
            states, losses, grads = ref_sac.follow(
                before, batches, keys, rnd=rnd, dtype=jnp.bfloat16)
        else:
            half = [{k: v[:len(v) // 2] for k, v in b.items()}
                    for b in batches]
            states, losses, grads = ref_sac.follow(before, half, keys,
                                                   rnd=rnd)
        g_got = leaves(grads)
    loss_gap = 0.0
    for got, want in zip(losses, ref_losses):
        scale = float(np.median([abs(float(want[k])) for k in LOSSES]))
        loss_gap = max(loss_gap, max(
            abs(float(got[k]) - float(want[k]))
            / max(abs(float(want[k])), scale, 1e-30) for k in LOSSES))
    g_ref = leaves(ref_grads)
    g_norm = {k: float(np.linalg.norm(v)) for k, v in g_ref.items()}
    still = {k for k, v in g_norm.items()
             if v < STILL_LEAF * float(np.median(list(g_norm.values())))}
    p0 = leaves(before["params"])
    d_ref = {k: v - p0[k] for k, v in leaves(ref_states[-1]["params"]).items()}
    d_got = {k: v - p0[k] for k, v in leaves(states[-1]["params"]).items()}
    # a target critic's leaf follows its critic's
    skip = {k for k in d_ref
            if k.replace("_targ/", "/") in still}
    return dict(learner_loss_gap=loss_gap,
                learner_grad_gap=_norm_gap(g_got, g_ref, still),
                learner_change_gap=_norm_gap(d_got, d_ref, skip),
                still_leaves=sorted(still))


def check(state, win: Window, control=None):
    """The numbers compared, each beside its limit.  ``control`` (one of
    ``CONTROLS``) puts the bfloat16 reference, or a planted fault, in the
    program's place wherever the cell has one."""
    ctx = state["ctx"]
    cfg, t = ctx.cell.config, ctx.cell.traffic
    rounding = ref_ppa.round_bf16 if control == "bf16" else None
    gaps, best = [], []
    for seed, root in sorted(state["roots"].items()):
        cells = ppa_readings(root, cfg["model"], cfg["deployment"],
                             t["mode"], control=rounding)
        gaps += [c["gap"] for c in cells.values()]
        best += [c["best"] for c in cells.values()]
    if control is None and len(state["roots"]) == len(t["campaign_seeds"]) \
            and all(b is not None for b in best):
        win.e2e["best_ppa_score"] = ref_ppa.geomean(best)
    act = kept_act(state["taps"])
    actor = (np.inf if act is None else
             actor_gap(*act, control=rounding))
    kept = kept_steps(state["taps"])
    learner = (dict.fromkeys(LEARNER_LIMITS, np.inf) if kept is None else
               learner_readings(kept, control=control))
    if learner.get("still_leaves"):
        print(f"[bench] leaves left out of the learner's change (gradient "
              f"under {STILL_LEAF} of the median leaf's): "
              f"{learner['still_leaves']}", file=sys.stderr)
    differ = sum(d != ds[0] for ds in state["digests"].values() for d in ds)
    return ([Check("ppa_gap", max(gaps), PPA_GAP_LIMIT),
             Check("actor_gap", actor, ACTOR_GAP_LIMIT)]
            + [Check(name, learner[name], limit)
               for name, limit in LEARNER_LIMITS.items()]
            + [Check("campaign_digests_differ", differ, 0)])


def close(state):
    state.pop("taps", None)
    return None
