"""DeepSeek-V2-Lite through the extractor: leading dense layer, routed and
shared experts, MLA without a q LoRA.  Its features are compared with the
plain MoE reference (``bench/reference/ppa_moe.py``) at published and
reduced widths; every earlier arch's extraction stays bitwise as it was
pinned before shared-expert counts, leading dense layers and LoRA-free
queries existed (``tests/fixtures/extract_pinned.json``)."""
import hashlib
import json
import os

import numpy as np
import pytest

from bench.reference import ppa_moe
from repro.configs import get_config, get_reduced
from repro.workload.extract import extract, workload_fields
from repro.workload.features import wl_vector

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PINNED = os.path.join(ROOT, "tests", "fixtures", "extract_pinned.json")
ARCH = "deepseek-v2-lite"
# (seq_len, batch): the benchmark cell's deployment, one sequence, and a
# batch whose routing is smooth
SHAPES = [(4096, 8), (512, 1), (2048, 64)]


def _bench_model():
    with open(os.path.join(ROOT, "bench", "configs", f"{ARCH}.json")) as f:
        return json.load(f)["model"]


def _model_of(cfg):
    """The reference's description of an ``ArchConfig`` with MLA and
    experts."""
    m, e = cfg.mla, cfg.moe
    return dict(
        n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, d_ff=cfg.d_ff,
        vocab=cfg.vocab, mlp_gated=cfg.mlp_gated,
        tie_embeddings=cfg.tie_embeddings, qkv_bias=cfg.qkv_bias,
        param_dtype=cfg.param_dtype,
        mla=dict(kv_lora_rank=m.kv_lora_rank, q_lora_rank=m.q_lora_rank,
                 qk_nope_head_dim=m.qk_nope_head_dim,
                 qk_rope_head_dim=m.qk_rope_head_dim,
                 v_head_dim=m.v_head_dim),
        moe=dict(n_routed_experts=e.n_experts, experts_per_token=e.top_k,
                 d_ff_expert=e.d_ff_expert, n_shared_experts=e.n_shared,
                 first_k_dense=e.first_k_dense))


@pytest.mark.parametrize("width", ["published", "reduced"])
@pytest.mark.parametrize("seq_len,batch", SHAPES)
def test_extractor_matches_the_moe_reference(width, seq_len, batch):
    if width == "published":
        cfg, model = get_config(ARCH), _bench_model()
    else:
        cfg = get_reduced(ARCH)
        model = _model_of(cfg)
    ref = ppa_moe.workload_features(model, seq_len, batch)
    graph, fields = workload_fields(cfg, seq_len=seq_len, batch=batch,
                                    phase="decode", dtype="native")
    for key, want in ref.items():
        assert fields[key] == pytest.approx(want, rel=1e-12, abs=0.0), key
    assert graph.n_ops == ref["n_ops"]
    # the float32 vector is these numbers rounded once
    vec = extract(cfg, seq_len=seq_len, batch=batch).features
    assert np.array_equal(vec, wl_vector(**fields))


def test_published_widths_and_graph_shape():
    cfg = get_config(ARCH)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab) \
        == (27, 2048, 16, 102400)
    assert cfg.mla.q_lora_rank is None
    assert (cfg.mla.kv_lora_rank, cfg.mla.qk_nope_head_dim,
            cfg.mla.qk_rope_head_dim, cfg.mla.v_head_dim) \
        == (512, 128, 64, 128)
    assert (cfg.d_ff, cfg.moe.d_ff_expert, cfg.moe.n_experts,
            cfg.moe.top_k, cfg.moe.n_shared, cfg.moe.first_k_dense) \
        == (10944, 1408, 64, 6, 2, 1)
    assert not cfg.tie_embeddings
    pc = cfg.param_counts()
    assert 15.6e9 < pc["total"] < 15.8e9 and 2.6e9 < pc["active"] < 2.8e9
    g = extract(cfg, seq_len=4096, batch=8).graph
    names = set(g.names)
    assert "L0.ffn_up" in names and "L0.router" not in names
    assert {"L1.router", "L1.experts", "L1.shared_exp", "L1.shared_exp_1",
            "L1.moe_combine", "L1.q_proj"} <= names
    assert not any(n.endswith((".q_down", ".q_up")) for n in names)
    assert sum(n.endswith(".experts") for n in g.names) == 26


def test_reduced_keeps_the_pattern():
    cfg = get_reduced(ARCH)
    kinds = [cfg.moe_on_layer(i) for i in range(cfg.n_layers)]
    assert kinds[0] is False and all(kinds[1:]) and len(kinds) >= 3
    assert cfg.mla.q_lora_rank is None and cfg.moe.n_shared == 2
    assert cfg.moe.n_experts >= 8 and cfg.moe.top_k > 1


def _pinned():
    with open(PINNED) as f:
        return json.load(f)


def _digest(graph):
    out = {}
    for name in ("kind", "flops", "weight_bytes", "out_bytes", "layer",
                 "edges"):
        a = np.ascontiguousarray(getattr(graph, name))
        out[name] = hashlib.sha256(
            a.tobytes() + str(a.dtype).encode() + str(a.shape).encode()
        ).hexdigest()[:16]
    out["names"] = hashlib.sha256(
        "\n".join(graph.names).encode()).hexdigest()[:16]
    return out


@pytest.mark.parametrize("case", sorted(_pinned()["cases"]))
def test_earlier_archs_extract_bitwise_as_pinned(case):
    pinned = _pinned()
    arch, phase, dtype = case.split("/")
    wl = extract(get_config(arch), seq_len=pinned["seq_len"],
                 batch=pinned["batch"], phase=phase, dtype=dtype)
    want = pinned["cases"][case]
    assert np.array_equal(wl.features,
                          np.asarray(want["features"], np.float32))
    assert _digest(wl.graph) == want["graph"]
