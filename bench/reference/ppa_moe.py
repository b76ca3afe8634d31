"""Plain reference of the decode-phase workload features of a decoder with
leading dense layers, routed and shared experts, and latent attention
(with or without a q LoRA) or grouped-query attention, in numpy float64,
written from the published equations and the sizes in ``bench/configs``.

Imports nothing of the program.  Designs are scored with
``bench.reference.ppa.evaluate``; at dense settings (no ``moe`` group) the
features here equal ``ppa.workload_features``.

Equations, per decoded token at context ``seq_len`` and ``batch``
sequences a step:

- parameters: embeddings (and an untied head), per layer two norms and the
  attention projections, then a dense FFN of ``n_mats * d * d_ff`` on the
  ``first_k_dense`` leading layers and, on the others, ``E`` routed experts
  and ``n_shared`` shared experts of ``n_mats * d * d_ff_expert`` each.
  The router's ``d * E`` weights are left out of the count, as the PPA
  model's parameter count leaves them out for every MoE model (0.02% of
  DeepSeek-V2-Lite's weights); its FLOPs are counted;
- weights streamed a step (``weight_traffic_mb``): every parameter outside
  the routed experts plus ``top_k`` routed experts per MoE layer;
- FLOPs: two per multiply-add of every projection, the router, the
  ``top_k`` routed and every shared expert, ``4 * H * qk`` per cached
  token of attention, ``4 d`` a norm, ``d`` the residual add and
  ``d * (top_k + n_shared)`` the expert combine;
- MLA caches ``kv_lora_rank + qk_rope_head_dim`` values a layer, 2 bytes
  each;
- routing imbalance: a token picks an expert with probability
  ``p = top_k / E``, so over ``batch`` tokens an expert's load is
  Binomial(batch, p), of relative spread ``sqrt((1 - p) / (batch p))``;
  the largest of ``E`` such loads lies about ``sqrt(2 ln E)`` spreads over
  the mean (the Gumbel tail), capped where every token lands on the same
  ``top_k`` experts, ``E / top_k - 1``;
- ``ilp``: the operator graph's edges over its ops, halved, in
  [0.05, 1], with one op per norm, projection, attention, residual add,
  router, grouped routed experts, shared expert and combine.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

PARAM_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def attention(m: Dict) -> Tuple[List[Tuple[int, int]], float, int]:
    """(projections as (d_in, d_out), attention FLOPs per cached token,
    graph edges into the attention op) of one attention block."""
    d, h = m["d_model"], m["n_heads"]
    a = m.get("mla")
    if a:
        qk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
        if a.get("q_lora_rank") is None:
            q = [(d, h * qk)]
        else:
            q = [(d, a["q_lora_rank"]), (a["q_lora_rank"], h * qk)]
        mats = q + [(d, a["kv_lora_rank"] + a["qk_rope_head_dim"]),
                    (a["kv_lora_rank"],
                     h * (a["qk_nope_head_dim"] + a["v_head_dim"])),
                    (h * a["v_head_dim"], d)]
        return mats, 4.0 * h * qk, 2
    hd, hk = m["head_dim"], m["n_kv_heads"]
    return ([(d, h * hd), (d, hk * hd), (d, hk * hd), (h * hd, d)],
            4.0 * h * hd, 3)


def routing_imbalance(n_experts: int, top_k: int, tokens: float) -> float:
    if n_experts <= 1 or top_k >= n_experts:
        return 0.0
    p = top_k / n_experts
    spread = math.sqrt((1.0 - p) / (max(tokens, 1.0) * p))
    return min(spread * math.sqrt(2.0 * math.log(n_experts)),
               n_experts / top_k - 1.0)


def workload_features(model: Dict, seq_len: int, batch: int) -> Dict:
    """The decode-phase workload features the PPA model reads, with the
    parameter counts and the graph's op count besides."""
    d, L, V = model["d_model"], model["n_layers"], model["vocab"]
    dff = model["d_ff"]
    by = PARAM_BYTES[model["param_dtype"]]
    mats, attn_per_ctx, attn_deps = attention(model)
    n_mats = 3 if model["mlp_gated"] else 2
    moe = model.get("moe")
    n_dense = moe["first_k_dense"] if moe else L
    n_moe = L - n_dense
    E = moe["n_routed_experts"] if moe else 0
    k = moe["experts_per_token"] if moe else 0
    shared = moe["n_shared_experts"] if moe else 0
    expert = n_mats * d * (moe["d_ff_expert"] if moe else 0)

    attn_params = sum(i * o for i, o in mats)
    if model.get("qkv_bias") and not model.get("mla"):
        attn_params += model["n_heads"] * model["head_dim"] \
            + 2 * model["n_kv_heads"] * model["head_dim"]
    dense_layer = 2 * d + attn_params + n_mats * d * dff
    moe_outside = 2 * d + attn_params + shared * expert
    embed = V * d * (1 if model["tie_embeddings"] else 2)
    params = embed + n_dense * dense_layer + n_moe * (moe_outside + E * expert)
    active = embed + n_dense * dense_layer + n_moe * (moe_outside + k * expert)
    weight_mb = params * by / 1e6
    traffic_mb = active * by / 1e6 if n_moe else weight_mb

    common = (4.0 * d + sum(2.0 * i * o for i, o in mats)
              + attn_per_ctx * seq_len + d + 4.0 * d)
    dense_flops = common + 2.0 * d * (n_mats - 1) * dff + 2.0 * dff * d
    moe_flops = (common + 2.0 * d * E + 2.0 * expert * k
                 + 2.0 * expert * shared + d * (k + shared))
    flops = n_dense * dense_flops + n_moe * moe_flops + 4.0 * d + 2.0 * d * V

    if model.get("mla"):
        a = model["mla"]
        kv_bytes = L * (a["kv_lora_rank"] + a["qk_rope_head_dim"]) * 2.0
    else:
        kv_bytes = L * 2 * model["n_kv_heads"] * model["head_dim"] * 2.0

    # ops: embed, final norm, head; a layer: norm1, projections,
    # attention, add, norm2, then up and down, or router, routed experts,
    # each shared expert and the combine
    attn_ops = len(mats) + 4
    attn_edges = 1 + (len(mats) - 1) + attn_deps + 1 + 2 + 1
    n_ops = (3 + n_dense * (attn_ops + 2)
             + n_moe * (attn_ops + 3 + shared))
    n_edges = (2 + n_dense * (attn_edges + 2)
               + n_moe * (attn_edges + 3 + 2 * shared))
    ilp = min(1.0, max(0.05, n_edges / n_ops / 2.0))
    return dict(params_total=float(params), params_active=float(active),
                weight_mb=weight_mb, weight_traffic_mb=traffic_mb,
                flops_per_token=flops, kv_bytes_per_token=kv_bytes,
                act_bytes_per_token=40.0 * L * d * 2.0, seq_len=seq_len,
                batch=batch, d_model=d, xtile_base_bytes=2.0 * d * 2.0 * L,
                n_ops=n_ops, ilp=ilp,
                moe_imbalance=(routing_imbalance(E, k, float(batch))
                               if n_moe else 0.0),
                dtype_fp8=0.0, dtype_int8=0.0, spec_decode_ok=1.0)
