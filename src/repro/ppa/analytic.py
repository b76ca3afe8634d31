"""Analytic PPA evaluator — paper Eqs. 14-33 (memory/NoC/throughput/KV) and
Eqs. 62-64 (power/perf/area surrogate heads), fully in ``jnp``.

Everything is a pure function of
  (cfg [30]  — design vector, repro.ppa.config_space layout,
   wl  [30]  — workload features, repro.workload.features layout,
   node [...] — process-node constants, NODE_VEC layout below)
returning a metrics vector (METRIC layout below).  ``evaluate_batch`` is the
vmap'd + jit'd entry used by the RL loop, MPC planner and the population-
parallel distributed search (DESIGN.md §3 adaptation note 1).

Node calibration constants live in ``repro.ppa.nodes`` and are documented
there; the parallel-efficiency constants below are fit to the paper's
Tables 10/11 (see DESIGN.md §9 faithfulness ledger).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.ppa import config_space as cs
from repro.ppa.nodes import NodeParams
from repro.workload.features import WL_IDX

# ---------------------------------------------------------------------------
# node constant vector (jit-friendly mirror of NodeParams)
NODE_FIELDS = [
    "node_nm", "f_max_hz", "vdd", "a_scale", "kappa_p", "e_mac_pj",
    "e_rom_mw_per_mb", "e_sram_pj_per_byte", "e_noc_pj_per_byte_hop",
    "leak_core_mw", "leak_sram_mw_per_mb", "a_logic_mm2",
    "a_rom_mm2_per_mb", "a_sram_mm2_per_mb", "power_budget_mw",
    "area_budget_mm2", "high_perf",
]
NODE_IDX = {n: i for i, n in enumerate(NODE_FIELDS)}
NODE_DIM = len(NODE_FIELDS)


def node_vector(p: NodeParams, *, high_perf: bool = True) -> np.ndarray:
    v = np.zeros((NODE_DIM,), np.float32)
    for name in NODE_FIELDS[:-1]:
        v[NODE_IDX[name]] = getattr(p, name)
    v[NODE_IDX["high_perf"]] = 1.0 if high_perf else 0.0
    return v


# ---------------------------------------------------------------------------
# metrics vector layout
METRICS = [
    "power_mw", "perf_gops", "area_mm2", "tok_s", "ppa_score",
    "feasible", "wmem_ok", "dmem_ok", "power_ok", "area_ok",
    "mem_overuse_mb", "pressure", "hazard",
    "tok_comp", "tok_mem", "tok_noc",
    "bisect_bytes_s", "hbar", "eta_par", "noc_latency_cyc",
    "p_compute_mw", "p_sram_mw", "p_rom_mw", "p_noc_mw", "p_leak_mw",
    "util", "kv_total_mb", "kappa_compact", "xtile_bytes_tok",
    "n_cores", "f_hz", "load_balance", "scratch_ok",
]
M_IDX = {n: i for i, n in enumerate(METRICS)}
M_DIM = len(METRICS)

# parallel-efficiency fit to paper Tables 10/11 (DESIGN.md §ppa):
#   eta_par = 1 / (1 + ETA_A*hbar + ETA_B*n_cores)
ETA_A = 1.288e-3
ETA_B = 4.03e-5
# expert-routing load imbalance degrades parallel efficiency:
#   eta_par /= 1 + ETA_IMB * moe_imbalance   (identity for dense workloads)
ETA_IMB = 0.05
ALPHA_SPEC = 1.56        # paper §4.13.1: speculative decode ~1.56x
TM_FP16 = 128            # Eq. 21: tensor-multiplier cap per TCC
L_HOP_CYC = 2.0          # NoC per-hop latency (cycles), Eq. 19
L_SETUP_CYC = 12.0       # routing header overhead, Eq. 19
PERF_NORM_MESH = 48 * 48  # score normalisation reference mesh (node ceiling)


def _g(cfg, name):
    return cfg[..., cs.IDX[name]]


def _w(wl, name):
    return wl[..., WL_IDX[name]]


def _n(node, name):
    return node[..., NODE_IDX[name]]


def evaluate(cfg: jnp.ndarray, wl: jnp.ndarray, node: jnp.ndarray) -> jnp.ndarray:
    """Single design point -> metrics vector.  Pure jnp; vmap over cfg."""
    cfg = cs.project(cfg)

    mesh_w = jnp.round(_g(cfg, "mesh_w"))
    mesh_h = jnp.round(_g(cfg, "mesh_h"))
    n_cores = mesh_w * mesh_h
    f = _g(cfg, "freq_frac") * _n(node, "f_max_hz")
    high_perf = _n(node, "high_perf")

    # ---------------- NoC model (Eqs. 18-19) ------------------------------
    dflit = _g(cfg, "dflit")
    bisect_bytes_s = jnp.minimum(mesh_w, mesh_h) * dflit * f / 8.0     # Eq. 18
    hbar = (mesh_w + mesh_h) / 3.0                                     # Eq. 19
    sc_express = 1.0 / (1.0 + 0.1 * (_g(cfg, "sc_x") + _g(cfg, "sc_y") - 2.0))
    noc_latency = hbar * sc_express * L_HOP_CYC + L_SETUP_CYC          # Eq. 19

    eta_par = 1.0 / (1.0 + ETA_A * hbar + ETA_B * n_cores)
    # expert-routing imbalance stalls tiles waiting on the hot expert;
    # moe_imbalance == 0 (dense / prefill-smoothed) divides by exactly 1.0,
    # keeping the default scenario bitwise identical
    eta_par = eta_par / (1.0 + ETA_IMB * _w(wl, "moe_imbalance"))

    # ---------------- KV-cache compaction (Eqs. 25-33) --------------------
    kv_bt = _w(wl, "kv_bytes_per_token")                                # Eq. 25
    kv_quant = jnp.round(_g(cfg, "kv_quant"))
    b_quant = 16.0 / (2.0 ** kv_quant)          # 16 / 8 / 4 bits
    window_frac = _g(cfg, "kv_window_frac")
    kappa = (16.0 / b_quant) * (1.0 / window_frac)                      # Eq. 32
    seq_len = _w(wl, "seq_len")
    kv_total_mb = seq_len * kv_bt / kappa / 1e6                         # Eq. 26/30
    kv_bt_eff = kv_bt / kappa

    # ---------------- throughput ceilings (Eqs. 21-24) --------------------
    lanes = jnp.minimum(TM_FP16, _g(cfg, "vlen") / 16.0)                # M_i
    int8_boost = 1.0 + _g(cfg, "precision")      # INT8 mix doubles MACs
    # real fp8/int8 datapath points on the precision axis: narrow operands
    # double MAC throughput per lane (1.0 at the native-dtype default)
    dtype_boost = 1.0 + _w(wl, "dtype_fp8") + _w(wl, "dtype_int8")
    alpha_spec = 1.0 + (ALPHA_SPEC - 1.0) * _w(wl, "spec_decode_ok") * high_perf
    flops_tok = _w(wl, "flops_per_token")
    macs_capacity = n_cores * lanes * int8_boost * dtype_boost * f * eta_par
    tok_comp = 2.0 * macs_capacity * alpha_spec / flops_tok             # Eq. 21

    batch = jnp.maximum(1.0, _w(wl, "batch"))
    weight_bytes = _w(wl, "weight_mb") * 1e6
    prec_shrink = 1.0 - 0.5 * _g(cfg, "precision")   # INT8 mix halves weights

    # KV slices live in DMEM-in; overflow spills to WMEM headroom and is
    # re-read through the slower tier (paper §3.9) -> extra memory traffic.
    dmem_in_kb = _g(cfg, "dmem_kb") * _g(cfg, "dmem_in_frac")
    act_in_kb = (_w(wl, "d_model") * 2.0 * batch / 1024.0
                 * (1.0 - 0.8 * _g(cfg, "stream_in")))
    kv_dmem_cap_mb = n_cores * jnp.maximum(0.0, dmem_in_kb - act_in_kb) / 1024.0
    wmem_headroom_mb = jnp.maximum(
        0.0, n_cores * _g(cfg, "wmem_kb") / 1024.0
        - weight_bytes * prec_shrink / 1e6)
    kv_spill_mb = jnp.maximum(0.0, kv_total_mb - kv_dmem_cap_mb)
    spill_frac = kv_spill_mb / jnp.maximum(kv_total_mb, 1e-6)

    # weights actually streamed per step (MoE decode touches only routed
    # experts; prefill streams the full bank).  Legacy vectors carry 0 here
    # and fall back to the resident footprint; the default dense scenario
    # writes weight_traffic_mb by the same expression as weight_mb, so the
    # select is bitwise transparent.
    wtraf_bytes = _w(wl, "weight_traffic_mb") * 1e6
    wtraf_bytes = jnp.where(wtraf_bytes > 0.0, wtraf_bytes, weight_bytes)
    bytes_tok = (wtraf_bytes * prec_shrink / batch
                 + kv_bt_eff * (1.0 + 3.0 * spill_frac)
                 + _w(wl, "act_bytes_per_token"))                       # Eq. 33
    rom_bw_tile = (_g(cfg, "vlen") / 8.0) * f                           # Eq. 16 BW_pk
    sram_bw_tile = (_g(cfg, "vr_wp") + _g(cfg, "xr_wp")) / 4.0 * rom_bw_tile
    bw_eff = n_cores * jnp.minimum(rom_bw_tile + sram_bw_tile, 2.0 * rom_bw_tile)
    tok_mem = bw_eff / bytes_tok                                        # Eq. 22

    stream_relief = 1.0 - 0.25 * (_g(cfg, "stream_in") + _g(cfg, "stream_out")) / 2.0
    xtile_tok = (_w(wl, "xtile_base_bytes") * jnp.sqrt(n_cores) / 4.0
                 * (0.6 + 0.8 * _g(cfg, "allreduce_frac")) * stream_relief)
    tok_noc = bisect_bytes_s / xtile_tok                                # Eq. 23

    tok_s = jnp.minimum(tok_comp, jnp.minimum(tok_mem, tok_noc))        # Eq. 24
    util = tok_s / jnp.maximum(tok_comp, 1e-9)

    # realised performance (GOps/s of FP16 MACs, paper Table 10 metric)
    perf_gops = 2.0 * macs_capacity * alpha_spec * util / 1e9

    # ---------------- power (Eq. 62 + Table 12 decomposition) -------------
    p_compute = (macs_capacity * util) * _n(node, "e_mac_pj") * 1e-9    # mW
    sram_traffic = (_w(wl, "act_bytes_per_token") + kv_bt_eff) * tok_s
    p_sram = sram_traffic * _n(node, "e_sram_pj_per_byte") * 1e-9
    rom_activity = eta_par * util * _g(cfg, "freq_frac")
    p_rom = _w(wl, "weight_mb") * prec_shrink * _n(node, "e_rom_mw_per_mb") * rom_activity
    p_noc = xtile_tok * tok_s * hbar * _n(node, "e_noc_pj_per_byte_hop") * 1e-9
    sram_mb = n_cores * (_g(cfg, "dmem_kb") + _g(cfg, "imem_kb")) / 1024.0
    p_leak = (n_cores * _n(node, "leak_core_mw")
              + sram_mb * _n(node, "leak_sram_mw_per_mb"))
    power_mw = p_compute + p_sram + p_rom + p_noc + p_leak

    # ---------------- area (Eq. 64) ---------------------------------------
    wmem_total_mb = n_cores * _g(cfg, "wmem_kb") / 1024.0
    area = (n_cores * _n(node, "a_logic_mm2") * _n(node, "a_scale")
            + wmem_total_mb * _n(node, "a_rom_mm2_per_mb")
            + sram_mb * _n(node, "a_sram_mm2_per_mb"))

    # ---------------- feasibility (Eqs. 14-17, 27-28) ---------------------
    wmem_bytes = n_cores * _g(cfg, "wmem_kb") * 1024.0
    wmem_need = weight_bytes * prec_shrink
    wmem_ok = wmem_bytes >= wmem_need                                   # Eq. 14
    dmem_scr_kb = _g(cfg, "dmem_kb") * jnp.maximum(
        0.0, 1.0 - _g(cfg, "dmem_in_frac") - _g(cfg, "dmem_out_frac"))
    scratch_need_kb = _w(wl, "d_model") * 2.0 * 2.0 / 1024.0
    kv_per_tile_kb = kv_total_mb * 1024.0 / n_cores
    scratch_ok = dmem_scr_kb >= scratch_need_kb                         # Eq. 28
    dmem_ok = jnp.logical_and(
        kv_spill_mb <= wmem_headroom_mb, scratch_ok)                     # Eq. 27
    power_ok = power_mw <= _n(node, "power_budget_mw")
    area_ok = area <= _n(node, "area_budget_mm2")
    feasible = (wmem_ok & dmem_ok & power_ok & area_ok).astype(jnp.float32)

    mem_overuse_mb = (jnp.maximum(0.0, wmem_need - wmem_bytes)
                      + jnp.maximum(0.0, (kv_per_tile_kb + act_in_kb - dmem_in_kb)
                                    * n_cores * 1024.0)) / 1e6
    pressure = (wmem_need / jnp.maximum(wmem_bytes, 1.0)
                + 0.5 * (kv_per_tile_kb + act_in_kb)
                / jnp.maximum(dmem_in_kb, 1e-3))                        # Eq. 17

    # hazard proxy (Table 2 idx 37-44 source; penalises starved issue/ports)
    hazard = jnp.clip(
        0.5 * _w(wl, "ilp") / (1.0 + _g(cfg, "stanum"))
        + 0.3 * jnp.maximum(0.0, 1.0 - (_g(cfg, "vr_wp") + _g(cfg, "vdpnum")) / 8.0)
        + 0.2 * jnp.maximum(0.0, 1.0 - _g(cfg, "fetch") / 8.0), 0.0, 1.0)

    # load balance proxy: sub-matmul partitioning evens per-tile load
    load_balance = jnp.clip(0.5 + 0.5 * _g(cfg, "sub_matmul")
                            - 0.2 * hazard, 0.0, 1.0)

    # ---------------- composite PPA score (cost, lower = better) ----------
    perf_range = (PERF_NORM_MESH * 2.0 * TM_FP16 * _n(node, "f_max_hz")
                  * 0.85 * (1.0 + (ALPHA_SPEC - 1.0) * high_perf)) / 1e9
    p_norm = perf_gops / perf_range
    pw_norm = power_mw / _n(node, "power_budget_mw")
    a_norm = area / _n(node, "area_budget_mm2")
    w_perf, w_power, w_area = score_weights(high_perf)
    ppa_score = w_perf * (1.0 - p_norm) + w_power * pw_norm + w_area * a_norm

    out = jnp.zeros((M_DIM,), jnp.float32)
    vals = dict(
        power_mw=power_mw, perf_gops=perf_gops, area_mm2=area, tok_s=tok_s,
        ppa_score=ppa_score, feasible=feasible,
        wmem_ok=wmem_ok.astype(jnp.float32), dmem_ok=dmem_ok.astype(jnp.float32),
        power_ok=power_ok.astype(jnp.float32), area_ok=area_ok.astype(jnp.float32),
        mem_overuse_mb=mem_overuse_mb, pressure=pressure, hazard=hazard,
        tok_comp=tok_comp, tok_mem=tok_mem, tok_noc=tok_noc,
        bisect_bytes_s=bisect_bytes_s, hbar=hbar, eta_par=eta_par,
        noc_latency_cyc=noc_latency,
        p_compute_mw=p_compute, p_sram_mw=p_sram, p_rom_mw=p_rom,
        p_noc_mw=p_noc, p_leak_mw=p_leak, util=util,
        kv_total_mb=kv_total_mb, kappa_compact=kappa,
        xtile_bytes_tok=xtile_tok, n_cores=n_cores, f_hz=f,
        load_balance=load_balance, scratch_ok=scratch_ok,
    )
    for k, v in vals.items():
        out = out.at[M_IDX[k]].set(v.astype(jnp.float32))
    return out


# constraints a design can break, as ``env_infeasible_total{reason}`` counts
INFEASIBLE_REASONS = ("wmem", "dmem", "power", "area", "kv_spill")


def infeasible_counts(metrics: np.ndarray) -> Dict[str, int]:
    """Designs of a batch that break each constraint, from its metrics rows
    (already on the host): WMEM capacity (Eq. 14), the DMEM scratch
    partition (Eq. 28), the KV spill over the WMEM headroom (Eq. 27:
    ``dmem_ok`` broken with the scratch partition met), and the power and
    area budgets.  A design may break several."""
    broken = {k: metrics[:, M_IDX[k]] < 0.5
              for k in ("wmem_ok", "dmem_ok", "scratch_ok", "power_ok",
                        "area_ok")}
    return dict(wmem=int(broken["wmem_ok"].sum()),
                dmem=int(broken["scratch_ok"].sum()),
                power=int(broken["power_ok"].sum()),
                area=int(broken["area_ok"].sum()),
                kv_spill=int((broken["dmem_ok"]
                              & ~broken["scratch_ok"]).sum()))


def score_weights(high_perf):
    """PPA weight triplet (paper §3.13): (0.4,0.4,0.2) high-perf,
    (0.2,0.6,0.2) low-power."""
    w_perf = 0.4 * high_perf + 0.2 * (1.0 - high_perf)
    w_power = 0.4 * high_perf + 0.6 * (1.0 - high_perf)
    w_area = 0.2 + 0.0 * high_perf
    return w_perf, w_power, w_area


@functools.partial(jax.jit, static_argnames=())
def evaluate_jit(cfg, wl, node):
    return evaluate(cfg, wl, node)


evaluate_batch = jax.jit(jax.vmap(evaluate, in_axes=(0, None, None)))

# Batched over (cfg, node) pairs: one compiled evaluator serves every process
# node in the same dispatch (node constants are traced, not baked in) — the
# evaluation path of the vectorized DSE engine (repro.core.env.VecDSEEnv).
evaluate_vec = jax.vmap(evaluate, in_axes=(0, None, 0))
evaluate_vec_jit = jax.jit(evaluate_vec)


def node_matrix(nodes, *, high_perf: bool = True) -> np.ndarray:
    """Stack per-element node constant vectors: nodes is a sequence of
    ``NodeParams`` -> (B, NODE_DIM) float32."""
    return np.stack([node_vector(p, high_perf=high_perf) for p in nodes])


def metrics_dict(m: jnp.ndarray) -> Dict[str, float]:
    arr = np.asarray(m, np.float64)
    return {name: float(arr[..., i]) for name, i in M_IDX.items()}
