"""The plain references agree with the program on the CPU, and their
controls, the same arithmetic in bfloat16, fail the limits that decide
``correct``."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import campaign as campaign_driver
from bench.reference import nets as ref_nets
from bench.reference import ppa as ref_ppa

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIGS = ("minicpm3-4b", "smollm-135m")


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_workload_features_match_the_extractor(name):
    from repro.configs import get_config
    from repro.workload.extract import extract
    from repro.workload.features import WL_IDX
    cfg = _config(name)
    dep = cfg["deployment"]
    prog = extract(get_config(cfg["arch"]), seq_len=dep["seq_len"],
                   batch=dep["batch"]).features
    ref = ref_ppa.workload_features(cfg["model"], dep["seq_len"],
                                    dep["batch"])
    for key, value in ref.items():
        assert prog[WL_IDX[key]] == pytest.approx(value, rel=1e-6), key


def _designs(n, seed=0):
    from bench.reference.ppa import HI, LO
    rng = np.random.default_rng(seed)
    return ref_ppa.project(LO + rng.random((n, len(LO))) * (HI - LO))


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("mode", ["high_perf", "low_power"])
def test_ppa_reference_matches_the_evaluator_and_its_control_fails(name,
                                                                   mode):
    from repro.configs import get_config
    from repro.ppa.analytic import M_IDX, evaluate_vec_jit, node_vector
    from repro.ppa.nodes import node_params
    from repro.workload.extract import extract
    cfg = _config(name)
    dep = cfg["deployment"]
    nodes = [3, 5, 7, 10, 14, 22, 28] * 64
    designs = _designs(len(nodes))
    wl = extract(get_config(cfg["arch"]), seq_len=dep["seq_len"],
                 batch=dep["batch"]).features
    node_mat = np.stack([node_vector(node_params(n, low_power=mode
                                                 != "high_perf"),
                                     high_perf=mode == "high_perf")
                         for n in nodes])
    m = np.asarray(evaluate_vec_jit(jnp.asarray(designs, jnp.float32),
                                    jnp.asarray(wl),
                                    jnp.asarray(node_mat)))
    got = {k: m[:, M_IDX[k]] for k in ref_ppa.COMPARED}
    wlr = ref_ppa.workload_features(cfg["model"], dep["seq_len"],
                                    dep["batch"])
    node = ref_ppa.node_columns(nodes, mode)
    want = ref_ppa.evaluate(designs, wlr, node)
    gap = ref_ppa.relative_gap(got, want)
    assert gap.max() < campaign_driver.PPA_GAP_LIMIT
    feasible = m[:, M_IDX["feasible"]] > 0.5
    clear = np.abs(want["margin"]) > 1e-5
    assert np.array_equal(feasible[clear], (want["margin"] >= 0)[clear])
    ctl = ref_ppa.evaluate(designs, wlr, node, q=ref_ppa.round_bf16)
    assert ref_ppa.relative_gap(ctl, want).max() \
        > 3 * campaign_driver.PPA_GAP_LIMIT


def _actor_params(seed=0, scale=30.0):
    from repro.core import networks as nets
    p = nets.actor_init(jax.random.PRNGKey(seed))
    # trained heads reach O(1) outputs; init heads are scaled by 1e-2
    for head in ("disc", "mu", "log_std"):
        p[head] = dict(w=p[head]["w"] * scale, b=p[head]["b"])
    return jax.tree.map(np.asarray, p)


def test_actor_reference_matches_the_kernel_and_its_control_fails():
    params = _actor_params()
    states = np.random.default_rng(3).normal(0.0, 1.0, (64, 52))
    sound = campaign_driver.actor_gap(params, states)
    control = campaign_driver.actor_gap(params, states,
                                        control=ref_ppa.round_bf16)
    assert sound < campaign_driver.ACTOR_GAP_LIMIT < control


def _sac_batch(rng, rows=256):
    return dict(s=rng.normal(size=(rows, 52)).astype(np.float32),
                a_cont=rng.uniform(-1, 1, (rows, 30)).astype(np.float32),
                a_disc=rng.integers(0, 5, (rows, 4)).astype(np.int32),
                r=rng.normal(size=rows).astype(np.float32),
                s2=rng.normal(size=(rows, 52)).astype(np.float32),
                done=np.zeros(rows, np.float32),
                is_w=rng.uniform(0.5, 1.0, rows).astype(np.float32))


def test_sac_reference_follows_the_update_and_its_controls_fail():
    from repro.core import sac
    rng = np.random.default_rng(0)
    state = sac.create(3)
    for i in range(3):      # give the optimizers moments of their own
        state, _, _ = sac.update(state, sac.Batch(**_sac_batch(rng)),
                                 jax.random.PRNGKey(100 + i))
    kept = dict(before=campaign_driver.as_tree(state), batches=[], keys=[],
                states=[], losses=[])
    for i in range(3):
        batch, key = _sac_batch(rng), np.asarray(jax.random.PRNGKey(i))
        state, _, met = sac.update(state, sac.Batch(**batch), key)
        kept["batches"].append(batch)
        kept["keys"].append(key)
        kept["states"].append(campaign_driver.as_tree(state))
        kept["losses"].append({k: float(met[k])
                               for k in campaign_driver.LOSSES})
    sound = campaign_driver.learner_readings(kept)
    for name, limit in campaign_driver.LEARNER_LIMITS.items():
        assert sound[name] < limit / 100, name
    for control in campaign_driver.CONTROLS:
        bad = campaign_driver.learner_readings(kept, control=control)
        assert any(bad[n] > limit for n, limit
                   in campaign_driver.LEARNER_LIMITS.items()), control


def test_surrogate_reference_matches_the_served_scoring():
    from repro.ppa import surrogate as sur
    params = sur.init_params(jax.random.PRNGKey(9), 52 + 30,
                             sur.SERVE_HIDDEN)
    rng = np.random.default_rng(1)
    ctx = rng.uniform(0, 5, (4, 52)).astype(np.float32)
    cand = rng.uniform(0, 5, (40, 30)).astype(np.float32)
    w = np.asarray([[0.4, 0.4, 0.2]] * 4, np.float32)
    idx, pred, within = jax.device_get(sur.score_query_batch(
        params, ctx, cand, w, np.full(4, np.inf, np.float32),
        np.zeros(4, np.float32)))
    p64 = {k: {kk: np.asarray(vv, np.float64) for kk, vv in v.items()}
           for k, v in params.items()}
    want = ref_nets.surrogate_log_pred(p64, ctx.astype(np.float64),
                                       cand.astype(np.float64))
    choice = ref_nets.pick(want, w.astype(np.float64), np.full(4, np.inf),
                           np.zeros(4))
    rows = np.arange(4)
    assert np.max(np.abs(np.log1p(pred) - want[rows, idx])) < 1e-5
    assert np.max(choice["score"][rows, idx] - choice["best"]) < 1e-5
    ctl = ref_nets.surrogate_log_pred(p64, ctx, cand, q=ref_ppa.round_bf16,
                                      mq=ref_ppa.round_bf16)
    assert np.max(np.abs(ctl - want)) > 3e-3
