"""Plain reference of the analytic PPA model and of the workload features
it reads, in numpy float64, written from the model's equations (paper
Eqs. 14-33 and 62-64) and the published sizes in ``bench/configs``.

Imports nothing of the program.  ``q`` is applied to every intermediate
result: the identity gives the float64 reference, ``round_bf16`` gives the
control, the same arithmetic rounded to bfloat16 after every operation.
"""
from __future__ import annotations

import json
import math
import os
from typing import Callable, Dict

import ml_dtypes
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# design vector: (name, lo, hi, quantisation step or 0 for continuous)
FIELDS = [
    ("mesh_w", 2, 64, 1), ("mesh_h", 2, 64, 1), ("sc_x", 1, 8, 1),
    ("sc_y", 1, 8, 1), ("fetch", 1, 16, 1), ("stanum", 1, 32, 1),
    ("vlen", 128, 2048, 128), ("dmem_kb", 16, 512, 16),
    ("wmem_kb", 256, 131072, 256), ("imem_kb", 1, 128, 1),
    ("dflit", 64, 8192, 64), ("xr_wp", 1, 16, 1), ("vr_wp", 1, 16, 1),
    ("xdpnum", 1, 16, 1), ("vdpnum", 1, 16, 1), ("freq_frac", 0.01, 1.0, 0),
    ("precision", 0.0, 1.0, 0), ("dmem_in_frac", 0.10, 0.80, 0),
    ("dmem_out_frac", 0.05, 0.50, 0), ("lb_alpha", 0.0, 1.0, 0),
    ("lb_beta", 0.0, 1.0, 0), ("rho_matmul", 0.0, 1.0, 0),
    ("rho_conv", 0.0, 1.0, 0), ("rho_general", 0.0, 1.0, 0),
    ("stream_in", 0.0, 1.0, 0), ("stream_out", 0.0, 1.0, 0),
    ("sub_matmul", 0.0, 1.0, 0), ("allreduce_frac", 0.0, 1.0, 0),
    ("kv_quant", 0, 2, 1), ("kv_window_frac", 0.05, 1.0, 0),
]
F = {name: i for i, (name, *_r) in enumerate(FIELDS)}
LO = np.array([f[1] for f in FIELDS], np.float64)
HI = np.array([f[2] for f in FIELDS], np.float64)
STEP = np.array([f[3] for f in FIELDS], np.float64)

# model constants (paper Tables 10/11 fit, Eq. 21)
ETA_A, ETA_B, ETA_IMB = 1.288e-3, 4.03e-5, 0.05
ALPHA_SPEC, TM_FP16 = 1.56, 128.0
PERF_NORM_MESH = 48.0 * 48.0
MODE_WEIGHTS = {"high_perf": (0.4, 0.4, 0.2), "low_power": (0.2, 0.6, 0.2)}
PARAM_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}
# order of the node constant vector in the serving context
NODE_FIELDS = ["node_nm", "f_max_hz", "vdd", "a_scale", "kappa_p",
               "e_mac_pj", "e_rom_mw_per_mb", "e_sram_pj_per_byte",
               "e_noc_pj_per_byte_hop", "leak_core_mw",
               "leak_sram_mw_per_mb", "a_logic_mm2", "a_rom_mm2_per_mb",
               "a_sram_mm2_per_mb", "power_budget_mw", "area_budget_mm2",
               "high_perf"]
COMPARED = ("power_mw", "perf_gops", "area_mm2", "tok_s", "ppa_score")

Round = Callable[[np.ndarray], np.ndarray]


def exact(x):
    return np.asarray(x, np.float64)


def round_f32(x):
    return np.asarray(x, np.float64).astype(np.float32).astype(np.float64)


def round_bf16(x):
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def node_constants(node_nm: int, mode: str) -> Dict[str, float]:
    """One node's constants, derived from the plain table as the paper's
    calibration defines them."""
    with open(os.path.join(HERE, "nodes.json")) as f:
        t = json.load(f)
    n = str(node_nm)
    kappa = t["e_mac_pj"][n] / t["e_mac_pj"]["28"]
    kappa3 = t["e_mac_pj"]["3"] / t["e_mac_pj"]["28"]
    low = mode != "high_perf"
    return dict(
        node_nm=float(node_nm), f_max_hz=t["f_max_hz"][n], vdd=t["vdd"][n],
        a_scale=t["a_scale"][n], kappa_p=kappa, e_mac_pj=t["e_mac_pj"][n],
        e_rom_mw_per_mb=t["e_rom_mw_per_mb"][n],
        e_sram_pj_per_byte=t["e_sram_pj_per_byte_3nm"] * kappa / kappa3,
        e_noc_pj_per_byte_hop=t["e_noc_pj_per_byte_hop_3nm"] * kappa / kappa3,
        leak_core_mw=t["leak_core_mw"][n],
        leak_sram_mw_per_mb=t["leak_sram_mw_per_mb"][n],
        a_logic_mm2=t["a_logic_mm2"],
        a_rom_mm2_per_mb=t["a_rom_mm2_per_mb"][n],
        a_sram_mm2_per_mb=t["sram_over_rom_area"] * t["a_rom_mm2_per_mb"][n],
        power_budget_mw=(t["power_budget_low_mw"] if low
                         else t["power_budget_mw"][n]),
        area_budget_mm2=(t["area_budget_low_mm2"] if low
                         else t["area_budget_mm2"])[n],
        high_perf=0.0 if low else 1.0)


def node_vector(node_nm: int, mode: str) -> np.ndarray:
    c = node_constants(node_nm, mode)
    return np.array([c[k] for k in NODE_FIELDS], np.float64)


# ------------------------------------------------------- workload features
def _attn_shapes(m: Dict):
    """(matmul (d_in, d_out) list, attention flops per context token,
    attention output width, number of graph edges into the attention op)
    of one attention block of a dense decoder, MLA or grouped-query."""
    d, h = m["d_model"], m["n_heads"]
    if m.get("mla"):
        a = m["mla"]
        qk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
        mats = [(d, a["q_lora_rank"]), (a["q_lora_rank"], h * qk),
                (d, a["kv_lora_rank"] + a["qk_rope_head_dim"]),
                (a["kv_lora_rank"], h * (a["qk_nope_head_dim"]
                                         + a["v_head_dim"])),
                (h * a["v_head_dim"], d)]
        return mats, 4.0 * h * qk, 2
    hd, hk = m["head_dim"], m["n_kv_heads"]
    mats = [(d, h * hd), (d, hk * hd), (d, hk * hd), (h * hd, d)]
    return mats, 4.0 * h * hd, 3


def workload_features(model: Dict, seq_len: int, batch: int) -> Dict:
    """The decode-phase workload features the PPA model reads, for a dense
    decoder (every layer attention + gated or plain MLP, no experts, no
    sliding window, no cross-attention), from published sizes."""
    d, L, V = model["d_model"], model["n_layers"], model["vocab"]
    dff = model["d_ff"]
    by = PARAM_BYTES[model["param_dtype"]]
    mats, attn_per_ctx, attn_deps = _attn_shapes(model)
    n_mats = 3 if model["mlp_gated"] else 2
    attn_params = sum(i * o for i, o in mats)
    if model.get("qkv_bias") and not model.get("mla"):
        attn_params += model["n_heads"] * model["head_dim"] \
            + 2 * model["n_kv_heads"] * model["head_dim"]
    layer_params = 2 * d + attn_params + n_mats * d * dff
    params = V * d * (1 if model["tie_embeddings"] else 2) + L * layer_params
    weight_mb = params * by / 1e6
    # FLOPs per decoded token: norms, projections, attention over the
    # whole cached context, residual add, MLP, final norm, output head
    layer_flops = (4.0 * d + sum(2.0 * i * o for i, o in mats)
                   + attn_per_ctx * seq_len + d + 4.0 * d
                   + 2.0 * d * (n_mats - 1) * dff + 2.0 * dff * d)
    flops = L * layer_flops + 4.0 * d + 2.0 * d * V
    if model.get("mla"):
        a = model["mla"]
        kv_bytes = L * (a["kv_lora_rank"] + a["qk_rope_head_dim"]) * 2.0
    else:
        kv_bytes = L * 2 * model["n_kv_heads"] * model["head_dim"] * 2.0
    # graph: embed, final norm, head + per layer (norm1, projections,
    # attention, add, norm2, up, down); mean fan-out = edges / ops
    n_ops = 3 + L * (len(mats) + 6)
    n_edges = 2 + L * (1 + (len(mats) - 1) + attn_deps + 1 + 2 + 3)
    ilp = min(1.0, max(0.05, n_edges / n_ops / 2.0))
    return dict(weight_mb=weight_mb, weight_traffic_mb=weight_mb,
                flops_per_token=flops, kv_bytes_per_token=kv_bytes,
                act_bytes_per_token=40.0 * L * d * 2.0, seq_len=seq_len,
                batch=batch, d_model=d, xtile_base_bytes=2.0 * d * 2.0 * L,
                ilp=ilp, moe_imbalance=0.0, dtype_fp8=0.0, dtype_int8=0.0,
                spec_decode_ok=1.0)


# ------------------------------------------------------------- evaluator
def project(cfg: np.ndarray) -> np.ndarray:
    c = np.clip(np.asarray(cfg, np.float64), LO, HI)
    stepped = np.where(STEP > 0, np.round(c / np.where(STEP > 0, STEP, 1.0))
                       * np.where(STEP > 0, STEP, 1.0), c)
    return np.clip(stepped, LO, HI)


def evaluate(cfg: np.ndarray, wl: Dict, node: Dict[str, np.ndarray],
             q: Round = exact) -> Dict[str, np.ndarray]:
    """Metrics of N designs ``cfg`` (N, 30) for one workload; ``node``
    holds one array of N per node constant (rows may differ by node)."""
    c = project(cfg)
    g = lambda name: q(c[:, F[name]])
    w = {k: q(v) for k, v in wl.items()}
    n = {k: q(np.asarray(v, np.float64)) for k, v in node.items()}
    mesh_w, mesh_h = q(np.round(g("mesh_w"))), q(np.round(g("mesh_h")))
    n_cores = q(mesh_w * mesh_h)
    f = q(g("freq_frac") * n["f_max_hz"])
    hp = n["high_perf"]

    bisect = q(q(q(np.minimum(mesh_w, mesh_h) * g("dflit")) * f) / 8.0)
    hbar = q((mesh_w + mesh_h) / 3.0)
    eta = q(1.0 / q(1.0 + q(ETA_A * hbar) + q(ETA_B * n_cores)))
    eta = q(eta / q(1.0 + ETA_IMB * w["moe_imbalance"]))

    kv_quant = np.round(g("kv_quant"))
    b_quant = q(16.0 / 2.0 ** kv_quant)
    kappa = q(q(16.0 / b_quant) * q(1.0 / g("kv_window_frac")))
    kv_total_mb = q(q(q(w["seq_len"] * w["kv_bytes_per_token"]) / kappa)
                    / 1e6)
    kv_bt_eff = q(w["kv_bytes_per_token"] / kappa)

    lanes = q(np.minimum(TM_FP16, q(g("vlen") / 16.0)))
    int8_boost = q(1.0 + g("precision"))
    dtype_boost = q(1.0 + w["dtype_fp8"] + w["dtype_int8"])
    alpha = q(1.0 + q(q((ALPHA_SPEC - 1.0) * w["spec_decode_ok"]) * hp))
    macs = q(q(q(q(q(n_cores * lanes) * int8_boost) * dtype_boost) * f)
             * eta)
    tok_comp = q(q(q(2.0 * macs) * alpha) / w["flops_per_token"])

    batch = q(np.maximum(1.0, w["batch"]))
    weight_bytes = q(w["weight_mb"] * 1e6)
    prec_shrink = q(1.0 - 0.5 * g("precision"))
    dmem_in_kb = q(g("dmem_kb") * g("dmem_in_frac"))
    act_in_kb = q(q(q(w["d_model"] * 2.0 * batch) / 1024.0)
                  * q(1.0 - 0.8 * g("stream_in")))
    kv_cap_mb = q(q(n_cores * np.maximum(0.0, dmem_in_kb - act_in_kb))
                  / 1024.0)
    headroom = q(np.maximum(0.0, q(q(n_cores * g("wmem_kb")) / 1024.0)
                            - q(q(weight_bytes * prec_shrink) / 1e6)))
    spill_mb = q(np.maximum(0.0, kv_total_mb - kv_cap_mb))
    spill_frac = q(spill_mb / np.maximum(kv_total_mb, 1e-6))
    wtraf = q(w["weight_traffic_mb"] * 1e6)
    wtraf = np.where(wtraf > 0.0, wtraf, weight_bytes)
    bytes_tok = q(q(q(wtraf * prec_shrink) / batch)
                  + q(kv_bt_eff * q(1.0 + 3.0 * spill_frac))
                  + w["act_bytes_per_token"])
    rom_bw = q(q(g("vlen") / 8.0) * f)
    sram_bw = q(q(q(g("vr_wp") + g("xr_wp")) / 4.0) * rom_bw)
    bw_eff = q(n_cores * np.minimum(q(rom_bw + sram_bw), q(2.0 * rom_bw)))
    tok_mem = q(bw_eff / bytes_tok)
    relief = q(1.0 - 0.25 * q(q(g("stream_in") + g("stream_out")) / 2.0))
    xtile = q(q(q(q(w["xtile_base_bytes"] * q(np.sqrt(n_cores))) / 4.0)
                * q(0.6 + 0.8 * g("allreduce_frac"))) * relief)
    tok_noc = q(bisect / xtile)
    tok_s = q(np.minimum(tok_comp, np.minimum(tok_mem, tok_noc)))
    util = q(tok_s / np.maximum(tok_comp, 1e-9))
    perf = q(q(q(q(2.0 * macs) * alpha) * util) / 1e9)

    p_compute = q(q(q(macs * util) * n["e_mac_pj"]) * 1e-9)
    sram_traffic = q(q(w["act_bytes_per_token"] + kv_bt_eff) * tok_s)
    p_sram = q(q(sram_traffic * n["e_sram_pj_per_byte"]) * 1e-9)
    rom_act = q(q(eta * util) * g("freq_frac"))
    p_rom = q(q(q(w["weight_mb"] * prec_shrink) * n["e_rom_mw_per_mb"])
              * rom_act)
    p_noc = q(q(q(q(xtile * tok_s) * hbar) * n["e_noc_pj_per_byte_hop"])
              * 1e-9)
    sram_mb = q(q(n_cores * q(g("dmem_kb") + g("imem_kb"))) / 1024.0)
    p_leak = q(q(n_cores * n["leak_core_mw"])
               + q(sram_mb * n["leak_sram_mw_per_mb"]))
    power = q(q(q(q(p_compute + p_sram) + p_rom) + p_noc) + p_leak)

    wmem_total_mb = q(q(n_cores * g("wmem_kb")) / 1024.0)
    area = q(q(q(q(n_cores * n["a_logic_mm2"]) * n["a_scale"])
               + q(wmem_total_mb * n["a_rom_mm2_per_mb"]))
             + q(sram_mb * n["a_sram_mm2_per_mb"]))

    wmem_bytes = q(q(n_cores * g("wmem_kb")) * 1024.0)
    wmem_need = q(weight_bytes * prec_shrink)
    scr_kb = q(g("dmem_kb") * np.maximum(
        0.0, 1.0 - g("dmem_in_frac") - g("dmem_out_frac")))
    scratch_need = q(q(w["d_model"] * 4.0) / 1024.0)
    # constraint margins, each relative to the larger side: >= 0 where
    # met, down to -1 where violated outright
    def margin(have, need):
        return (have - need) / np.maximum(np.maximum(np.abs(have),
                                                     np.abs(need)), 1e-30)
    margins = [margin(wmem_bytes, wmem_need), margin(headroom, spill_mb),
               margin(scr_kb, scratch_need),
               margin(n["power_budget_mw"], power),
               margin(n["area_budget_mm2"], area)]

    perf_range = q(q(q(q(q(PERF_NORM_MESH * 2.0 * TM_FP16) * n["f_max_hz"])
                       * 0.85) * q(1.0 + (ALPHA_SPEC - 1.0) * hp)) / 1e9)
    wp = q(0.4 * hp + 0.2 * (1.0 - hp))
    wpw = q(0.4 * hp + 0.6 * (1.0 - hp))
    score = q(q(q(wp * q(1.0 - q(perf / perf_range)))
                + q(wpw * q(power / n["power_budget_mw"])))
              + q(0.2 * q(area / n["area_budget_mm2"])))
    return dict(power_mw=power, perf_gops=perf, area_mm2=area, tok_s=tok_s,
                ppa_score=score, margin=np.min(np.stack(margins), axis=0))


def node_columns(node_nms, mode: str) -> Dict[str, np.ndarray]:
    """Node constants as one column per constant for designs at
    ``node_nms`` (one entry per design)."""
    rows = [node_constants(int(nm), mode) for nm in node_nms]
    return {k: np.array([r[k] for r in rows], np.float64) for k in rows[0]}


def relative_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
                 ) -> np.ndarray:
    """Per design, the widest relative gap over the compared metrics."""
    gaps = [np.abs(np.asarray(got[k], np.float64) - want[k])
            / np.maximum(np.abs(want[k]), 1e-30) for k in COMPARED]
    return np.max(np.stack(gaps), axis=0)


def geomean(values) -> float:
    v = np.asarray(values, np.float64)
    return float(math.exp(np.mean(np.log(v))))
