"""The MoE reference (``bench/reference/ppa_moe.py``) and the two campaign
kinds added beside ``campaign``: the reference equals the dense one at
dense settings, agrees with the program's evaluator on DeepSeek-V2-Lite,
and planted faults in the program's workload, or the bfloat16 control,
break ``ppa_gap``; ``campaign_moe`` and ``campaign_wide`` cells added as
data files run at a tiny size."""
import json
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as brun
from bench.drivers import campaign as campaign_driver
from bench.drivers import campaign_wide
from bench.reference import ppa as ref_ppa
from bench.reference import ppa_moe

ROOT = brun.ROOT
MOE = "deepseek-v2-lite"


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["minicpm3-4b", "smollm-135m"])
def test_moe_reference_equals_the_dense_one_on_dense_models(name):
    cfg = _config(name)
    dep = cfg["deployment"]
    dense = ref_ppa.workload_features(cfg["model"], dep["seq_len"],
                                      dep["batch"])
    moe = ppa_moe.workload_features(cfg["model"], dep["seq_len"],
                                    dep["batch"])
    assert {k: moe[k] for k in dense} == dense


def test_routing_imbalance_of_the_cell():
    dep = _config(MOE)["deployment"]
    wl = ppa_moe.workload_features(_config(MOE)["model"], dep["seq_len"],
                                   dep["batch"])
    assert wl["moe_imbalance"] == pytest.approx(3.1703, abs=1e-4)
    assert ppa_moe.routing_imbalance(64, 6, 1e9) < 1e-3
    assert ppa_moe.routing_imbalance(64, 64, 8) == 0.0


def _shared_left_out(cfg, wl, fields):
    import dataclasses

    from repro.workload.extract import extract
    return extract(dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_shared=0)),
        seq_len=fields["seq_len"], batch=fields["batch"]).features


def _imbalance_left_out(cfg, wl, fields):
    from repro.workload.features import WL_IDX
    out = wl.copy()
    out[WL_IDX["moe_imbalance"]] = 0.0
    return out


# Charging all 64 routed experts as traffic is no such fault: at this
# deployment every design is bound by compute, never by weight traffic
# (PERF.md), so no compared metric moves.
@pytest.mark.parametrize("fault", [None, _shared_left_out,
                                   _imbalance_left_out],
                         ids=["sound", "shared_experts_left_out",
                              "routing_imbalance_left_out"])
def test_moe_reference_matches_the_evaluator_and_faults_fail(fault):
    from repro.configs import get_config
    from repro.ppa.analytic import M_IDX, evaluate_vec_jit, node_vector
    from repro.ppa.nodes import node_params
    from repro.workload.extract import extract
    conf = _config(MOE)
    dep = conf["deployment"]
    nodes = [3, 5, 7, 10, 14, 22, 28] * 64
    rng = np.random.default_rng(0)
    designs = ref_ppa.project(ref_ppa.LO + rng.random(
        (len(nodes), len(ref_ppa.LO))) * (ref_ppa.HI - ref_ppa.LO))
    cfg = get_config(conf["arch"])
    wl = extract(cfg, seq_len=dep["seq_len"], batch=dep["batch"]).features
    if fault is not None:
        wl = fault(cfg, wl, dep)
    node_mat = np.stack([node_vector(node_params(n), high_perf=True)
                         for n in nodes])
    m = np.asarray(evaluate_vec_jit(jnp.asarray(designs, jnp.float32),
                                    jnp.asarray(wl),
                                    jnp.asarray(node_mat)))
    got = {k: m[:, M_IDX[k]] for k in ref_ppa.COMPARED}
    wlr = ppa_moe.workload_features(conf["model"], dep["seq_len"],
                                    dep["batch"])
    want = ref_ppa.evaluate(designs, wlr,
                            ref_ppa.node_columns(nodes, "high_perf"))
    gap = ref_ppa.relative_gap(got, want).max()
    if fault is not None:
        assert gap > 3 * campaign_driver.PPA_GAP_LIMIT
        return
    assert gap < campaign_driver.PPA_GAP_LIMIT
    feasible = m[:, M_IDX["feasible"]] > 0.5
    clear = np.abs(want["margin"]) > 1e-5
    assert np.array_equal(feasible[clear], (want["margin"] >= 0)[clear])
    ctl = ref_ppa.evaluate(designs, wlr,
                           ref_ppa.node_columns(nodes, "high_perf"),
                           q=ref_ppa.round_bf16)
    assert ref_ppa.relative_gap(ctl, want).max() \
        > 3 * campaign_driver.PPA_GAP_LIMIT


def test_wide_window_draws_its_calls_in_the_first_campaign():
    """The wide window's taps keep every call of the first campaign and
    then keep a drawn run of consecutive ones among them alone."""
    import types
    module = types.SimpleNamespace(step=lambda x: x + 1)
    with campaign_wide.FirstCampaignTap(module, "step") as tap:
        for i in range(9):
            assert module.step(i) == i + 1
        tap.draw(np.random.default_rng(2**31 + 5),
                 campaign_driver.LEARNER_STEPS)
        for i in range(20):
            module.step(i)
    assert module.step(1) == 2 and tap.calls == 29
    kept = sorted(tap.kept)
    assert len(kept) == campaign_driver.LEARNER_STEPS
    assert kept == list(range(kept[0], kept[0] + len(kept)))
    assert kept[-1] < 9
    assert all(tap.kept[c] == ((c,), c + 1) for c in kept)
    # fewer calls than the run keeps them all; none keeps none
    few = campaign_wide.FirstCampaignTap(module, "step")
    with few:
        module.step(0)
        few.draw(np.random.default_rng(3), campaign_driver.LEARNER_STEPS)
    assert list(few.kept) == [0]
    none = campaign_wide.FirstCampaignTap(module, "step")
    none.draw(np.random.default_rng(3), 1)
    assert none.kept == {}


TINY = {"mode": "high_perf", "nodes": [3, 7], "episodes": 512,
        "checkpoint_every": 2, "campaign_seeds": [0, 1]}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout that adds a tiny ``campaign_moe`` cell over
    DeepSeek-V2-Lite and a tiny ``campaign_wide`` cell, by data files."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for name, config, traffic in (
            ("tiny-moe", MOE, dict(TINY, kind="campaign_moe", lanes=16)),
            ("tiny-wide", "minicpm3-4b",
             dict(TINY, kind="campaign_wide", lanes=128))):
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
        cell = f"{config}.{name}"
        manifest["workloads"].append(dict(name=cell, config=config,
                                          traffic=name, chips=1,
                                          why="tiny rehearsal"))
        like = ("deepseek-v2-lite.moe-decode" if name == "tiny-moe"
                else "minicpm3-4b.hp-wide")
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


def test_moe_cell_runs_and_reads_the_wmem_rejections(checkout):
    res = brun.run_cell(brun.resolve(f"{MOE}.tiny-moe", root=checkout),
                        2**31 + 7, 0.5, True, require_tpu=False)
    assert res["correct"] and res["failed"] == 0
    assert 0 < res["metrics"]["wmem_reject_share"]["value"] <= 100
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"], name


def test_wide_cell_runs_correct(checkout):
    res = brun.run_cell(brun.resolve("minicpm3-4b.tiny-wide", root=checkout),
                        2**31 + 11, 0.5, False, require_tpu=False)
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s", "env_steps_per_s"}
    assert res["checks"]["ppa_gap"]["value"] < 1e-4


def _doubled_weights(features):
    from repro.workload.features import WL_IDX
    out = features.copy()
    out[WL_IDX["weight_mb"]] *= 2.0
    return out


def _verdicts_flipped(metrics):
    from repro.ppa.analytic import M_IDX
    out = metrics.copy()
    out[:, M_IDX["feasible"]] = 1.0 - out[:, M_IDX["feasible"]]
    return out


@pytest.mark.parametrize("fault", [None, "weights_doubled",
                                   "verdicts_flipped"])
def test_moe_dispatch_rows_against_the_reference(fault):
    """Every design of a fused env step, rejected or not, against the
    reference: the sound evaluator passes both dispatch checks; weights
    counted twice break the metrics, flipped verdicts the mismatch."""
    from bench.drivers import campaign_moe
    from repro.configs import get_config
    from repro.ppa.analytic import evaluate_vec_jit, node_vector
    from repro.ppa.nodes import node_params
    from repro.workload.extract import extract
    conf = _config(MOE)
    dep = conf["deployment"]
    nodes = np.array([3, 5, 7, 10, 14, 22, 28] * 64)
    rng = np.random.default_rng(4)
    designs = ref_ppa.project(ref_ppa.LO + rng.random(
        (len(nodes), len(ref_ppa.LO))) * (ref_ppa.HI - ref_ppa.LO))
    wl = extract(get_config(conf["arch"]), seq_len=dep["seq_len"],
                 batch=dep["batch"]).features
    if fault == "weights_doubled":
        wl = _doubled_weights(wl)
    node_mat = np.stack([node_vector(node_params(int(n)), high_perf=True)
                         for n in nodes])
    m = np.asarray(evaluate_vec_jit(jnp.asarray(designs, jnp.float32),
                                    jnp.asarray(wl), jnp.asarray(node_mat)),
                   np.float64)
    if fault == "verdicts_flipped":
        m = _verdicts_flipped(m)
    got = campaign_moe.dispatch_readings(
        (designs, nodes, m), conf["model"], dep, "high_perf")
    limit = campaign_driver.PPA_GAP_LIMIT
    if fault is None:
        assert got == dict(dispatch_ppa_gap=got["dispatch_ppa_gap"],
                           feasible_mismatch=0)
        assert got["dispatch_ppa_gap"] < limit
        ctl = campaign_moe.dispatch_readings(
            (designs, nodes, m), conf["model"], dep, "high_perf",
            control=ref_ppa.round_bf16)
        assert ctl["dispatch_ppa_gap"] > 3 * limit
    elif fault == "weights_doubled":
        assert got["dispatch_ppa_gap"] > 3 * limit
        assert got["feasible_mismatch"] > 0
    else:
        assert got["dispatch_ppa_gap"] < limit
        assert got["feasible_mismatch"] > len(nodes) // 2


def test_moe_cell_reads_a_planted_weight_fault_as_not_correct(
        checkout, monkeypatch):
    """Weights counted twice in the program's workload, as a fault in the
    ROM sum would: the kept env step's rows read ``correct`` false."""
    from repro.campaign import runner
    extract = runner.extract

    def faulty(*args, **kw):
        wl = extract(*args, **kw)
        wl.features = _doubled_weights(wl.features)
        return wl
    monkeypatch.setattr(runner, "extract", faulty)
    res = brun.run_cell(brun.resolve(f"{MOE}.tiny-moe", root=checkout),
                        2**31 + 13, 0.5, False, require_tpu=False)
    assert not res["correct"]
    checks = res["checks"]
    assert checks["dispatch_ppa_gap"]["value"] > checks[
        "dispatch_ppa_gap"]["limit"]


def _sac_batch(rng, rows=256):
    return dict(s=rng.normal(size=(rows, 52)).astype(np.float32),
                a_cont=rng.uniform(-1, 1, (rows, 30)).astype(np.float32),
                a_disc=rng.integers(0, 5, (rows, 4)).astype(np.int32),
                r=rng.normal(size=rows).astype(np.float32),
                s2=rng.normal(size=(rows, 52)).astype(np.float32),
                done=np.zeros(rows, np.float32),
                is_w=rng.uniform(0.5, 1.0, rows).astype(np.float32))


def test_moe_learner_limits_still_fail_the_controls():
    """The MoE kind's own gradient limit passes the plain step and fails
    both controls, each by one of the learner numbers."""
    import jax

    from bench.drivers import campaign_moe
    from repro.core import sac
    assert campaign_moe.LEARNER_LIMITS == dict(
        campaign_driver.LEARNER_LIMITS, learner_grad_gap=1e-2)
    rng = np.random.default_rng(1)
    state = sac.create(5)
    for i in range(3):
        state, _, _ = sac.update(state, sac.Batch(**_sac_batch(rng)),
                                 jax.random.PRNGKey(200 + i))
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
    assert all(sound[n] < limit for n, limit
               in campaign_moe.LEARNER_LIMITS.items())
    for control in campaign_moe.CONTROLS:
        bad = campaign_driver.learner_readings(kept, control=control)
        assert any(bad[n] > limit for n, limit
                   in campaign_moe.LEARNER_LIMITS.items()), control
