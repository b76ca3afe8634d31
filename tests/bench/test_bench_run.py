"""The harness end to end on the CPU at a tiny size, through its test entry
(``bench.run.run_cell(..., require_tpu=False)``): a cell added as data
files alone runs; both traffic kinds report their metrics; a run with
its timed path broken comes out not correct; without a TPU, or without
the program, the command fails and prints no result."""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as brun

ROOT = brun.ROOT
# 60 dispatches of 8 rows: the learner starts once 256 rows are stored
# and makes 112 steps, enough for every call the check may keep
TINY_CAMPAIGN = {"kind": "campaign", "mode": "high_perf", "nodes": [3, 7],
                 "lanes": 4, "episodes": 240, "checkpoint_every": 4,
                 "campaign_seeds": [0, 1]}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout that adds a cell by data files only: a traffic file and
    a workload entry naming an existing configuration."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append(dict(
        name="smollm-135m.tiny-campaign", config="smollm-135m",
        traffic="tiny-campaign", chips=1, why="tiny rehearsal"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "minicpm3-4b.paper-hp" in m.get("workloads", []):
            m["workloads"].append("smollm-135m.tiny-campaign")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    (root / "bench" / "traffic" / "tiny-campaign.json").write_text(
        json.dumps(TINY_CAMPAIGN))
    recommend = json.loads((root / "bench" / "traffic" /
                            "recommend-closed.json").read_text())
    recommend["campaign"] = dict(TINY_CAMPAIGN, campaign_seeds=[0])
    recommend["clients"], recommend["sample_share"] = 2, 0.5
    recommend["mix"]["nodes"] = [3, 7]
    (root / "bench" / "traffic" / "tiny-recommend.json").write_text(
        json.dumps(recommend))
    manifest["workloads"].append(dict(
        name="smollm-135m.tiny-recommend", config="smollm-135m",
        traffic="tiny-recommend", chips=1, why="tiny rehearsal"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "smollm-135m.recommend-closed" in m.get("workloads", []):
            m["workloads"].append("smollm-135m.tiny-recommend")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(root)


def _run(checkout, cell, seconds=0.5, trace=False, seed=2**31 + 5):
    return brun.run_cell(brun.resolve(cell, root=checkout), seed, seconds,
                         trace, require_tpu=False)


def test_campaign_cell_added_as_data_runs(checkout):
    res = _run(checkout, "smollm-135m.tiny-campaign")
    assert res["correct"] and res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {"setup_s", "env_steps_per_s", "best_ppa_score"}
    assert m["env_steps_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert res["checks"]["ppa_gap"]["value"] < 1e-4
    for name in ("actor_gap", "learner_loss_gap", "learner_grad_gap",
                 "learner_change_gap"):
        assert res["checks"][name]["value"] < res["checks"][name]["limit"]


def test_campaign_trace_run_reports_span_and_counter_metrics(checkout):
    res = _run(checkout, "smollm-135m.tiny-campaign", trace=True)
    m = res["metrics"]
    assert {"runner_share", "dispatch_ms", "checkpoint_share",
            "window_compiles"} <= set(m)
    assert 0 < m["runner_share"]["value"] < 100
    assert m["window_compiles"]["value"] == 0


def test_recommend_cell_runs(checkout):
    res = _run(checkout, "smollm-135m.tiny-recommend", seconds=1.0)
    assert res["correct"] and res["attempted"] > 10
    assert set(res["metrics"]) == {"setup_s", "recommend_qps",
                                   "recommend_p99_ms"}
    assert res["checks"]["grid_mismatch"]["value"] == 0
    assert res["checks"]["fallback_gap"]["value"] \
        < res["checks"]["fallback_gap"]["limit"]


# ------------------------------------------------------------- faults
def _wrap_step(monkeypatch, change):
    from repro.core import env as env_mod
    original = env_mod.VecDSEEnv.step
    box = {}

    def step(self, a_cont, a_disc):
        obs, r, info = original(self, a_cont, a_disc)
        info.metrics = change(info.metrics.copy(), box)
        return obs, r, info
    monkeypatch.setattr(env_mod.VecDSEEnv, "step", step)


def _scale_power(m, box):
    from repro.ppa.analytic import M_IDX
    m[:, M_IDX["power_mw"]] *= 1.01
    return m


def _stale(m, box):
    prev = box.get("prev", m)
    box["prev"] = m
    return prev


def _half(m, box):
    h = m.shape[0] // 2
    m[h:2 * h] = m[:h]
    return m


@pytest.mark.parametrize("change", [_scale_power, _stale, _half],
                         ids=["answer_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_campaign_with_broken_env_step_is_not_correct(checkout, monkeypatch,
                                                      change):
    _wrap_step(monkeypatch, change)
    res = _run(checkout, "smollm-135m.tiny-campaign")
    assert not res["correct"]
    assert res["checks"]["ppa_gap"]["value"] \
        > res["checks"]["ppa_gap"]["limit"]


def test_campaign_with_altered_kernel_is_not_correct(checkout, monkeypatch):
    from repro.kernels import ops
    original = ops.actor_forward

    def altered(params, s, **kw):
        disc, mu, log_std, gate = original(params, s, **kw)
        return disc, mu * 1.01, log_std, gate
    monkeypatch.setattr(ops, "actor_forward", altered)
    res = _run(checkout, "smollm-135m.tiny-campaign")
    assert not res["correct"]
    assert res["checks"]["actor_gap"]["value"] \
        > res["checks"]["actor_gap"]["limit"]


def _unchanged(update, state, batch, key):
    _, td, met = update(state, batch, key)
    return state, td, met


def _half_batch(update, state, batch, key):
    half = type(batch)(*(v[:v.shape[0] // 2] for v in batch))
    new, td, met = update(state, half, key)
    return new, jnp.concatenate([td, td]), met


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch_left_out"])
def test_campaign_with_broken_learner_is_not_correct(checkout, monkeypatch,
                                                     fault):
    from repro.core import sac
    original = sac.update
    monkeypatch.setattr(sac, "update",
                        lambda s, b, k: fault(original, s, b, k))
    res = _run(checkout, "smollm-135m.tiny-campaign")
    assert not res["correct"]
    assert any(res["checks"][n]["value"] > res["checks"][n]["limit"]
               for n in ("learner_loss_gap", "learner_grad_gap",
                         "learner_change_gap"))


def test_recommend_with_altered_fallbacks_is_not_correct(checkout,
                                                         monkeypatch):
    from repro.launch import recommend
    original = recommend.Recommender.recommend_batch

    def altered(self, queries):
        answers = original(self, queries)
        for a in answers:
            if a.source == "surrogate":
                a.power_mw *= 1.05
        return answers
    monkeypatch.setattr(recommend.Recommender, "recommend_batch", altered)
    res = _run(checkout, "smollm-135m.tiny-recommend", seconds=1.0)
    assert not res["correct"]
    assert res["checks"]["fallback_gap"]["value"] \
        > res["checks"]["fallback_gap"]["limit"]


def test_recommend_with_altered_answers_is_not_correct(checkout,
                                                       monkeypatch):
    from repro.launch import recommend
    original = recommend.Recommender.recommend_batch

    def altered(self, queries):
        answers = original(self, queries)
        for a in answers:
            if a.source == "archive":
                a.power_mw *= 1.01
        return answers
    monkeypatch.setattr(recommend.Recommender, "recommend_batch", altered)
    res = _run(checkout, "smollm-135m.tiny-recommend", seconds=1.0)
    assert not res["correct"]


# -------------------------------------------------------- refusals
def _command(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "minicpm3-4b.paper-hp", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_a_cpu_and_prints_no_result():
    out = _command(ROOT, {"PYTHONPATH": os.path.join(ROOT, "src")})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = _command(str(tmp_path), {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_nearest_rank_percentile():
    from bench.drivers.recommend_closed import percentile
    values = sorted(np.arange(1, 101, dtype=float))
    assert percentile(values, 99) == 99.0
    assert percentile(values, 100) == 100.0
    assert percentile([5.0], 99) == 5.0


# ------------------------------------------------------------ controls
def test_campaign_control_fails_where_the_program_passes(checkout):
    from bench import control
    cell = brun.resolve("smollm-135m.tiny-campaign", root=checkout)
    work = os.path.join(checkout, ".bench_work", "control")
    # two windows in one process, as the readings on the chip take them
    for r in control.readings(cell, [2**31 + 9, 2**31 + 10], work, 0.5):
        assert r["program"]["correct"]
        assert not r["bf16"]["correct"] and not r["half_batch"]["correct"]
        assert r["bf16"]["ppa_gap"] > r["program"]["ppa_gap"]


def test_recommend_control_fails_where_the_program_passes(checkout):
    from bench import control
    cell = brun.resolve("smollm-135m.tiny-recommend", root=checkout)
    work = os.path.join(checkout, ".bench_work", "control-recommend")
    (r,) = control.readings(cell, [2**31 + 9], work, 1.0)
    assert r["program"]["correct"] and r["program"]["grid_mismatch"] == 0
    assert not r["bf16"]["correct"]
    assert r["bf16"]["fallback_gap"] > 3 * r["program"]["fallback_gap"]
