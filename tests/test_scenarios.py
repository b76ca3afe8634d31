"""Phase-split scenario engine: prefill/decode extraction, grouped MoE
graphs + routing imbalance, dtype axes, SLO-aware selection, and the
back-compat doctrine — the default scenario (decode/native, no SLO) of a
scenario grid must reproduce a plain no-axes campaign bitwise, run live in
the same environment."""
import dataclasses
import json
import os

import numpy as np
import pytest

import repro.campaign.runner as runner_mod
from repro.campaign.distrib import fingerprint
from repro.campaign.planner import (CampaignSpec, plan, scenario_suffix)
from repro.campaign.runner import run_campaign
from repro.configs import get_config, get_reduced
from repro.core.reward import (DEFAULT_SLOS, resolve_slo, slo_objective,
                               ttft_ms)
from repro.launch import dse
from repro.launch.recommend import (ArchiveIndex, Query, Recommender,
                                    split_cell_id, split_scenario)
from repro.workload.extract import (_PREC_BYTES, build_graph, extract,
                                    routing_imbalance)
from repro.workload.features import (WL_DIM, WL_DIM_LEGACY, WL_IDX,
                                     as_feature_vector)

MOE_ARCHS = ("mixtral-8x7b", "llama4-maverick-400b-a17b", "jamba-v0.1-52b")


def wlf(wl, name):
    return float(wl.features[WL_IDX[name]])


# ------------------------------------------------------------- extraction
def test_prec_bytes_has_fp8():
    # regression: the precision table silently defaulted unknown dtypes
    # before growing a real 1-byte fp8 datapath point
    assert _PREC_BYTES["fp8"] == 1
    assert _PREC_BYTES["float8"] == 1
    assert _PREC_BYTES["int8"] == 1


def test_dtype_axis_shrinks_weight_bytes():
    cfg = get_config("smollm-135m")  # bf16 -> 2 bytes/param
    base = extract(cfg, seq_len=256, batch=1)
    fp8 = extract(cfg, seq_len=256, batch=1, dtype="fp8")
    int8 = extract(cfg, seq_len=256, batch=1, dtype="int8")
    assert wlf(fp8, "weight_mb") == pytest.approx(
        0.5 * wlf(base, "weight_mb"))
    assert wlf(int8, "weight_mb") == pytest.approx(
        0.5 * wlf(base, "weight_mb"))
    assert wlf(fp8, "dtype_fp8") == 1.0 and wlf(fp8, "dtype_int8") == 0.0
    assert wlf(int8, "dtype_int8") == 1.0 and wlf(int8, "dtype_fp8") == 0.0
    assert wlf(base, "dtype_fp8") == 0.0 and wlf(base, "dtype_int8") == 0.0
    with pytest.raises(ValueError):
        extract(cfg, seq_len=256, batch=1, dtype="fp4")
    with pytest.raises(ValueError):
        extract(cfg, seq_len=256, batch=1, phase="chunked")


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_moe_graph_is_linear_in_layers(arch):
    # the grouped expert op keeps graphs O(layers): llama4-maverick would
    # otherwise emit 128 matmul nodes per MoE layer
    cfg = get_config(arch)
    g = build_graph(cfg, 256)
    assert g.n_ops <= 12 * cfg.n_layers
    # exactly ONE grouped expert op per MoE layer, never one per expert
    n_moe_layers = sum(cfg.moe_on_layer(li) for li in range(cfg.n_layers))
    grouped = [n for n in g.names if n.endswith(".experts")]
    assert len(grouped) == n_moe_layers
    assert not any("exp0" in n or "expert0" in n for n in g.names)


@pytest.mark.parametrize("arch", MOE_ARCHS)
def test_moe_weight_traffic_respects_activation(arch):
    cfg = get_config(arch)
    dec = extract(cfg, seq_len=256, batch=1)
    pre = extract(cfg, seq_len=256, batch=1, phase="prefill")
    # decode streams only the routed experts' weights; prefill (every
    # expert hit across the prompt) and the resident footprint see all
    assert 0 < wlf(dec, "weight_traffic_mb") < wlf(dec, "weight_mb")
    assert wlf(pre, "weight_traffic_mb") == wlf(pre, "weight_mb")
    assert wlf(dec, "weight_mb") == wlf(pre, "weight_mb")


def test_dense_weight_traffic_equals_footprint():
    wl = extract(get_config("smollm-135m"), seq_len=256, batch=1)
    assert wlf(wl, "weight_traffic_mb") == wlf(wl, "weight_mb")


def test_routing_imbalance_bounds():
    assert routing_imbalance(1, 1, 64) == 0.0       # dense
    assert routing_imbalance(8, 8, 64) == 0.0       # all experts active
    few = routing_imbalance(8, 2, 1)                # decode: 1 token
    many = routing_imbalance(8, 2, 4096)            # prefill: many tokens
    assert few > many > 0.0
    assert few <= 8 / 2 - 1                         # capped at worst case


def test_prefill_phase_semantics():
    cfg = get_config("mixtral-8x7b")
    dec = extract(cfg, seq_len=512, batch=2)
    pre = extract(cfg, seq_len=512, batch=2, phase="prefill")
    assert wlf(dec, "phase") == 0.0 and wlf(pre, "phase") == 1.0
    assert wlf(pre, "batch") == 2 * 512             # token-parallel
    assert wlf(dec, "batch") == 2
    assert wlf(pre, "spec_decode_ok") == 0.0
    assert wlf(pre, "moe_imbalance") < wlf(dec, "moe_imbalance")


def test_legacy_30dim_vector_zero_pads():
    v = as_feature_vector(np.ones(WL_DIM_LEGACY, np.float32))
    assert v.shape == (WL_DIM,)
    assert (v[:WL_DIM_LEGACY] == 1.0).all()
    assert (v[WL_DIM_LEGACY:] == 0.0).all()


# -------------------------------------------------------------- cell ids
def test_cell_id_scenario_roundtrip():
    assert scenario_suffix("native", "decode") == ""
    assert scenario_suffix("fp8", "prefill") == "__fp8-prefill"
    cid = "a__b__5nm__low_power"
    assert split_cell_id(cid) == ("a__b", 5, "low_power")
    assert split_scenario(cid) == (cid, "native", "decode")
    assert split_scenario(cid + "__fp8-prefill") == (cid, "fp8", "prefill")
    assert split_cell_id(cid + "__int8-decode") == ("a__b", 5, "low_power")


# ------------------------------------------------------------------- SLO
def test_slo_resolution_and_objective():
    # None -> the mode's defaults (campaigns gate on spec.slo is None
    # BEFORE resolving, so no-SLO runs never reach this path)
    assert resolve_slo(None, "high_perf") == DEFAULT_SLOS["high_perf"]
    flat = {"ttft_ms": 100.0, "tok_s": 5.0}
    assert resolve_slo(flat, "low_power") == flat
    per = resolve_slo(DEFAULT_SLOS, "low_power")
    assert per == DEFAULT_SLOS["low_power"]
    assert ttft_ms(1000.0, 512, 2) == pytest.approx(1024.0)
    meets = slo_objective(0.5, 50.0, 80.0, flat)
    misses = slo_objective(0.5, 2.0, 300.0, flat)
    assert meets == pytest.approx(0.5)              # no penalty when met
    assert misses > meets


def test_campaign_spec_scenario_validation():
    base = dict(name="x", workloads=["smollm-135m"])
    with pytest.raises(ValueError):
        CampaignSpec(**base, dtypes=["fp4"])
    with pytest.raises(ValueError):
        CampaignSpec(**base, phases=[])
    with pytest.raises(ValueError):
        CampaignSpec(**base, slo={"ttft_ms": -1.0})
    with pytest.raises(ValueError):
        CampaignSpec(**base, slo={"high_perf": {"nope": 1.0}})
    spec = CampaignSpec(**base, dtypes=["native", "fp8"],
                        phases=["decode", "prefill"], slo=DEFAULT_SLOS)
    assert spec.n_cells == len(spec.nodes) * len(spec.modes) * 4


def test_planner_scenario_grid_keeps_default_first():
    spec = CampaignSpec(name="g", workloads=["smollm-135m"], nodes=[7],
                        modes=["high_perf"], dtypes=["native", "fp8"],
                        phases=["decode", "prefill"])
    batches = plan(spec)
    assert [b.key for b in batches] == [
        "smollm-135m__high_perf__7nm",
        "smollm-135m__high_perf__7nm__native-prefill",
        "smollm-135m__high_perf__7nm__fp8-decode",
        "smollm-135m__high_perf__7nm__fp8-prefill"]
    # the default cell rides batch index 0 with an unsuffixed id, so its
    # seed (spec.seed + 1000*index) matches a plain no-axes grid
    assert batches[0].index == 0
    assert batches[0].cells[0].cell_id == "smollm-135m__7nm__high_perf"


# ------------------------------------------- default cell vs a plain run
DEFAULT_CELL = "smollm-135m__7nm__high_perf"


@pytest.fixture(scope="module")
def golden_run(tmp_path_factory):
    """A dtype x phase scenario grid and the plain (no-axes) campaign of
    the same spec, both run here: returns the scenario store and the
    plain run's fingerprint."""
    base = CampaignSpec(name="plain", workloads=["smollm-135m"], nodes=[7],
                        modes=["high_perf"], episodes=16, lanes=4,
                        max_envs=4, seed=0, seq_len=256, batch=1,
                        checkpoint_every=4)
    scen = dataclasses.replace(base, name="scenario",
                               dtypes=["native", "fp8"],
                               phases=["decode", "prefill"])
    tmp = tmp_path_factory.mktemp("golden")
    plain = run_campaign(str(tmp / "plain"), base, progress=lambda m: None)
    store = run_campaign(str(tmp / "scen"), scen, progress=lambda m: None)
    return store, json.loads(json.dumps(fingerprint(plain)))


def test_default_scenario_reproduces_pre_refactor_fingerprint(golden_run):
    # THE back-compat contract: decode/native with no SLO is bitwise the
    # plain pipeline — summaries, frontier floats, everything
    store, plain = golden_run
    got = json.loads(json.dumps(fingerprint(store)))
    assert len(got) == 4 and list(plain) == [DEFAULT_CELL]
    assert got[DEFAULT_CELL] == plain[DEFAULT_CELL]


def test_default_summary_has_no_scenario_keys(golden_run):
    store, _ = golden_run
    s = store.load_summary(DEFAULT_CELL)
    for k in ("dtype", "phase", "ttft_ms", "slo_ok"):
        assert k not in s


def test_wl_cache_keys_on_full_extraction_settings(golden_run):
    # regression: the cache was keyed on arch alone, so phase/dtype (and
    # multi-root seq_len/batch) lookups aliased to the first extraction
    store, _ = golden_run
    idx = ArchiveIndex.build([store.root])
    dec = idx.wl_features("smollm-135m")
    pre = idx.wl_features("smollm-135m", "prefill")
    fp8 = idx.wl_features("smollm-135m", "decode", "fp8")
    assert len(idx._wl_cache) == 3
    assert not np.array_equal(dec, pre)
    assert not np.array_equal(dec, fp8)
    assert np.array_equal(dec, idx.wl_features("smollm-135m"))


def test_query_scenario_validation():
    with pytest.raises(ValueError):
        Query(node_nm=7, arch="smollm-135m", phase="chunked")
    with pytest.raises(ValueError):
        Query(node_nm=7, arch="smollm-135m", dtype="fp4")
    with pytest.raises(ValueError):
        Query(node_nm=7, arch="smollm-135m", max_ttft_ms=0.0)


# ------------------------------------------- scenario campaign end-to-end
@pytest.fixture(scope="module")
def moe_scenario_run(tmp_path_factory):
    """Reduced-MoE campaign over the phase axis with per-mode SLOs."""
    real = runner_mod.get_config
    runner_mod.get_config = lambda a: get_reduced(a)
    try:
        spec = CampaignSpec(name="moe-scen", workloads=["mixtral-8x7b"],
                            nodes=[7], modes=["high_perf"], episodes=16,
                            lanes=4, max_envs=4, seed=0, seq_len=128,
                            batch=1, checkpoint_every=4,
                            phases=["decode", "prefill"], slo=DEFAULT_SLOS)
        root = str(tmp_path_factory.mktemp("moescen") / "run")
        return run_campaign(root, spec, progress=lambda m: None)
    finally:
        runner_mod.get_config = real


def test_scenario_campaign_adapts_across_phase_axis(moe_scenario_run):
    store = moe_scenario_run
    dec = store.load_summary("mixtral-8x7b__7nm__high_perf")
    pre = store.load_summary(
        "mixtral-8x7b__7nm__high_perf__native-prefill")
    assert dec["ppa_score"] is not None and pre["ppa_score"] is not None
    # the RL search lands on different configs per phase (the headline
    # adaptation claim, at test budget)
    cfg_cols = ("mesh", "fetch", "vlen", "wmem_kb", "dmem_kb", "imem_kb",
                "freq_frac")
    assert [dec[c] for c in cfg_cols] != [pre[c] for c in cfg_cols]
    # scenario keys only off the default point; SLO keys wherever an SLO
    # is in force
    assert "phase" not in dec and pre["phase"] == "prefill"
    for s in (dec, pre):
        assert s["ttft_ms"] > 0 and isinstance(s["slo_ok"], bool)


def test_scenario_report_groups_by_axis(moe_scenario_run):
    store = moe_scenario_run
    with open(os.path.join(store.root, "report", "adaptation.json")) as f:
        adapt = json.load(f)
    assert "mixtral-8x7b__high_perf" in adapt
    assert "mixtral-8x7b__high_perf__native-prefill" in adapt


def test_scenario_recommend_exact_with_ttft_cap(moe_scenario_run):
    store = moe_scenario_run
    rec = Recommender.build([store.root], fit_steps=10)
    a_dec = rec.recommend(Query(node_nm=7, arch="mixtral-8x7b"))
    a_pre = rec.recommend(Query(node_nm=7, arch="mixtral-8x7b",
                                phase="prefill", max_ttft_ms=1e9))
    assert a_dec.source == "archive"
    assert a_dec.cell_id == "mixtral-8x7b__7nm__high_perf"
    assert a_pre.source == "archive"
    assert a_pre.cell_id == "mixtral-8x7b__7nm__high_perf__native-prefill"
    # an impossible TTFT cap excludes every archived prefill point and
    # falls through to the surrogate
    a_miss = rec.recommend(Query(node_nm=7, arch="mixtral-8x7b",
                                 phase="prefill", max_ttft_ms=1e-6))
    assert a_miss.source == "surrogate"


# --------------------------------------------------------------- DSE CLI
def test_dse_cli_scenario_flags(tmp_path, capsys):
    out = str(tmp_path / "dse")
    dse.main(["--arch", "smollm-135m", "--nodes", "7", "--method",
              "random", "--episodes", "8", "--seq-len", "128",
              "--batch", "1", "--phase", "prefill", "--dtype", "fp8",
              "--out", out])
    rows = json.load(open(os.path.join(out,
                                       "smollm-135m__random_summary.json")))
    assert rows and rows[0]["node_nm"] == 7


def test_dse_cli_rejects_scenario_flags_with_campaign(tmp_path):
    grid = tmp_path / "g.json"
    grid.write_text(json.dumps(dict(name="x", workloads=["smollm-135m"])))
    with pytest.raises(SystemExit):
        dse.main(["--campaign", str(grid), "--phase", "prefill"])
