"""Traffic kind ``campaign_wide``: the ``campaign`` kind, with the kept
policy act and learner steps drawn among those of the window's first
campaign.

``campaign`` draws its kept calls among fixed numbers of first calls
(``ACT_CALLS``, ``LEARNER_CALLS``) that suit the paper's grid.  At 512
lanes a cell a campaign makes 9 dispatches, so 9 policy acts and about
36 learner steps: a draw past the first campaign may need more campaigns
than the window runs, and three steps drawn across two campaigns do not
follow one another, which reads as no learner gap at all.  Here the
window keeps every policy act and learner step of its first campaign, by
reference, and draws among them from the seed once that campaign ends,
so the draw needs no count of the calls a campaign makes.  Set-up,
checks, controls and everything else are the ``campaign`` kind's, by
import.
"""
from __future__ import annotations

import os
import shutil
import time

import numpy as np

from bench import counts
from bench.drivers import campaign
from bench.drivers.campaign import CONTROLS, check, close, setup  # noqa: F401
from bench.run import Window


class FirstCampaignTap(campaign.Tap):
    """A ``campaign.Tap`` that keeps every call until ``draw`` picks the
    kept ones among them."""

    def __init__(self, module, name: str):
        super().__init__(module, name, ())
        self.keeping = True

    def __call__(self, *args, **kw):
        out = self.inner(*args, **kw)
        if self.keeping:
            self.kept[self.calls] = (args, out)
        self.calls += 1
        return out

    def draw(self, rng, run: int):
        """Stop keeping, and leave kept ``run`` consecutive calls drawn
        from ``rng`` (all of them where fewer were made)."""
        self.keeping = False
        calls = sorted(self.kept)
        k = int(rng.integers(max(1, len(calls) - run + 1)))
        self.kept = {c: self.kept[c] for c in calls[k:k + run]}


def window(state, seconds: float) -> Window:
    """``campaign.window`` with the kept calls drawn in its first
    campaign."""
    from repro.core import sac as sac_mod
    from repro.kernels import ops as kernel_ops
    ctx, specs = state["ctx"], state["specs"]
    t = ctx.cell.traffic
    cells, lanes = len(t["nodes"]), t["lanes"]
    draw = np.random.default_rng(ctx.seed)
    acts = [FirstCampaignTap(kernel_ops, "policy_act_batch"),
            FirstCampaignTap(sac_mod, "policy_act_batch")]
    steps_tap = FirstCampaignTap(sac_mod, "update")
    taps = acts + [steps_tap]
    state["taps"] = taps

    def pick():
        for tap in acts:
            tap.draw(draw, 1)
        steps_tap.draw(draw, campaign.LEARNER_STEPS)

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
                store = campaign.run_one(spec, root)
            except RuntimeError:
                failed += 1
                break
            labels.append(("campaign", a, time.time()))
            runs.append((spec.seed, root))
            if attempted == 1:
                pick()
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
        if steps_tap.keeping:
            pick()
    for seed, root in runs:
        recs = campaign.read_spans(root)
        spans.extend(recs)
        labels.extend((r["name"], r["ts"], r["ts"] + r["dur"]) for r in recs)
        state["digests"].setdefault(seed, []).append(campaign.digest(root))
        if seed in state["roots"]:
            shutil.rmtree(root, ignore_errors=True)
        else:
            state["roots"][seed] = root
    return Window(t0=t0, t1=t1, attempted=attempted, failed=failed,
                  e2e=dict(env_steps_per_s=steps / (t1 - t0)),
                  counts=dict(flops=flops, dispatches=dispatches,
                              actor_rows=lanes * cells),
                  spans=spans, labels=labels)
