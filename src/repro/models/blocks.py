"""Transformer-family blocks: GQA/MLA/cross/SWA attention, dense & MoE FFN,
Mamba (selective scan), xLSTM (mLSTM chunked linear-attention form + sLSTM
recurrence).

Each block kind provides:
  init(key, cfg, moe_on)            -> params
  apply(params, cfg, kind, moe_on, x, ...)        full-sequence (train/prefill)
  decode(params, cfg, kind, moe_on, x_t, cache, pos, ...)  single token
  init_cache(cfg, kind, batch, cache_len, dtype)  -> cache pytree

Memory discipline (dry-run provable):
  * attention is chunked-online-softmax (never [S,S]);
  * Mamba uses a remat-chunked time scan: only chunk-boundary states are
    saved for backward (inner 128-step scans recompute);
  * mLSTM uses the chunked linear-attention formulation (inter-chunk matrix
    state + intra-chunk decay-masked scores), sigmoid-stabilised gating
    (deviation from xLSTM's exponential gating noted in DESIGN.md);
  * sLSTM is a true recurrence (lax.scan over time).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, MambaConfig, XLSTMConfig
from repro.models import attention as attn
from repro.models import layers as L

MAMBA_CHUNK = 128
MLSTM_CHUNK = 128
MOE_CAPACITY = 1.25
KV_TAIL = 64   # two-tier decode cache: local ring-tail capacity


def _xlstm_dims(cfg: ArchConfig) -> Tuple[int, int, int]:
    xc = cfg.xlstm or XLSTMConfig()
    quant = 16 * cfg.n_heads
    di = max(quant, int(cfg.d_model * xc.proj_factor) // quant * quant)
    dqk = max(quant, int(di * xc.d_qk_factor) // quant * quant)
    return di, dqk, cfg.n_heads


# ===========================================================================
# init
# ===========================================================================
def block_init(key, cfg: ArchConfig, kind: str, moe_on: bool) -> Dict:
    d, dt = cfg.d_model, L.dtype_of(cfg.param_dtype)
    hd, H, Hk = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    ks = list(jax.random.split(key, 24))
    p: Dict = dict(norm1=L.rmsnorm_init(d, dt))

    if kind in ("attn", "xattn"):
        if cfg.mla is not None:
            m = cfg.mla
            qk_d = m.qk_nope_head_dim + m.qk_rope_head_dim
            if m.q_lora_rank is None:
                p.update(wq=L.linear_init(ks[0], d, H * qk_d, dt))
            else:
                p.update(
                    wdq=L.linear_init(ks[0], d, m.q_lora_rank, dt),
                    q_norm=L.rmsnorm_init(m.q_lora_rank, dt),
                    wuq=L.linear_init(ks[1], m.q_lora_rank, H * qk_d, dt))
            p.update(
                wdkv=L.linear_init(ks[2], d,
                                   m.kv_lora_rank + m.qk_rope_head_dim, dt),
                kv_norm=L.rmsnorm_init(m.kv_lora_rank, dt),
                wukv=L.linear_init(ks[3], m.kv_lora_rank,
                                   H * (m.qk_nope_head_dim + m.v_head_dim), dt),
                wo=L.linear_init(ks[4], H * m.v_head_dim, d, dt),
            )
        else:
            p.update(
                wq=L.linear_init(ks[0], d, H * hd, dt, bias=cfg.qkv_bias),
                wk=L.linear_init(ks[1], d, Hk * hd, dt, bias=cfg.qkv_bias),
                wv=L.linear_init(ks[2], d, Hk * hd, dt, bias=cfg.qkv_bias),
                wo=L.linear_init(ks[3], H * hd, d, dt),
            )
        if kind == "xattn":   # cross-attention onto context tokens
            p.update(
                x_norm=L.rmsnorm_init(d, dt),
                x_wq=L.linear_init(ks[5], d, H * hd, dt),
                x_wk=L.linear_init(ks[6], d, Hk * hd, dt),
                x_wv=L.linear_init(ks[7], d, Hk * hd, dt),
                x_wo=L.linear_init(ks[8], H * hd, d, dt),
                x_gate=jnp.zeros((d,), dt),
            )
    elif kind == "mamba":
        mc = cfg.mamba or MambaConfig()
        di = mc.expand * d
        p.update(
            in_proj=L.linear_init(ks[0], d, 2 * di, dt),
            conv_w=(jax.random.normal(ks[1], (mc.d_conv, di)) * 0.1).astype(dt),
            conv_b=jnp.zeros((di,), dt),
            x_proj=L.linear_init(ks[2], di, 2 * mc.d_state + 1, dt),
            dt_bias=jnp.zeros((di,), jnp.float32),
            dt_w=L.linear_init(ks[3], 1, di, dt),  # broadcast dt -> channels
            a_log=jnp.log(jnp.tile(jnp.arange(1, mc.d_state + 1,
                                              dtype=jnp.float32), (di, 1))),
            d_skip=jnp.ones((di,), jnp.float32),
            out_proj=L.linear_init(ks[4], di, d, dt),
        )
    elif kind == "mlstm":
        di, dqk, Hx = _xlstm_dims(cfg)
        p.update(
            up=L.linear_init(ks[0], d, 2 * di, dt),
            wq=L.linear_init(ks[1], di, dqk, dt),
            wk=L.linear_init(ks[2], di, dqk, dt),
            wv=L.linear_init(ks[3], di, di, dt),
            gates=L.linear_init(ks[4], di, 2 * Hx, dt),  # i, f per head
            ln=L.rmsnorm_init(di, dt),
            down=L.linear_init(ks[5], di, d, dt),
        )
    elif kind == "slstm":
        di, _, _ = _xlstm_dims(cfg)
        p.update(
            up=L.linear_init(ks[0], d, di, dt),
            wx=L.linear_init(ks[1], di, 4 * di, dt),
            wr=L.linear_init(ks[2], di, 4 * di, dt, scale=0.02),
            ln=L.rmsnorm_init(di, dt),
            down=L.linear_init(ks[3], di, d, dt),
        )
    else:
        raise ValueError(kind)

    # ---- FFN / MoE --------------------------------------------------------
    if cfg.d_ff > 0 and kind not in ("mlstm", "slstm"):
        p["norm2"] = L.rmsnorm_init(d, dt)
        if moe_on:
            m = cfg.moe
            eff = m.d_ff_expert or cfg.d_ff
            n_mats = 3 if cfg.mlp_gated else 2
            p["router"] = L.linear_init(ks[9], d, m.n_experts, dt, scale=0.02)
            sc = 1.0 / np.sqrt(d)
            p["e_gate"] = (jax.random.normal(ks[10], (m.n_experts, d, eff)) * sc).astype(dt) \
                if n_mats == 3 else None
            p["e_up"] = (jax.random.normal(ks[11], (m.n_experts, d, eff)) * sc).astype(dt)
            p["e_down"] = (jax.random.normal(ks[12], (m.n_experts, eff, d))
                           * (1.0 / np.sqrt(eff))).astype(dt)
            if p["e_gate"] is None:
                del p["e_gate"]
            if m.n_shared:
                # the shared experts as one SwiGLU of n_shared * eff
                # (DeepSeek-V2 and Llama 4 build them so)
                sf = m.n_shared * eff
                p["s_gate"] = L.linear_init(ks[13], d, sf, dt)
                p["s_up"] = L.linear_init(ks[14], d, sf, dt)
                p["s_down"] = L.linear_init(ks[15], sf, d, dt)
        else:
            if cfg.mlp_gated:
                p["w_gate"] = L.linear_init(ks[9], d, cfg.d_ff, dt)
            p["w_up"] = L.linear_init(ks[10], d, cfg.d_ff, dt)
            p["w_down"] = L.linear_init(ks[11], cfg.d_ff, d, dt)
    return p


# ===========================================================================
# FFN / MoE forward
# ===========================================================================
def _ffn(p: Dict, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    if "router" in p:
        return x + _moe(p, cfg, h)
    if cfg.mlp_gated:
        z = L.swiglu(L.linear(p["w_gate"], h), L.linear(p["w_up"], h))
    else:
        z = jax.nn.gelu(L.linear(p["w_up"], h).astype(jnp.float32)).astype(h.dtype)
    return x + L.linear(p["w_down"], z)


def _moe(p: Dict, cfg: ArchConfig, h: jnp.ndarray) -> jnp.ndarray:
    """Capacity-based dense dispatch (GShard-style): correct active-FLOPs on
    the compiled graph — experts see [E, C, d] buffers, not all tokens."""
    m = cfg.moe
    B, S, d = h.shape
    T = B * S
    ht = h.reshape(T, d)
    logits = L.linear(p["router"], ht).astype(jnp.float32)      # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, m.top_k)               # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    if S == 1:
        # decode: exact dense-gather path (capacity dispatch would drop
        # tokens at tiny T); gathers only the top-k experts' weights.
        def one_tok(x_t, idx_t, g_t):
            up_w = jnp.take(p["e_up"], idx_t, axis=0)        # [k,d,f]
            dn_w = jnp.take(p["e_down"], idx_t, axis=0)      # [k,f,d]
            if "e_gate" in p:
                gt_w = jnp.take(p["e_gate"], idx_t, axis=0)
                z = L.swiglu(jnp.einsum("d,kdf->kf", x_t, gt_w),
                             jnp.einsum("d,kdf->kf", x_t, up_w))
            else:
                z = jax.nn.gelu(jnp.einsum("d,kdf->kf", x_t, up_w)
                                .astype(jnp.float32)).astype(x_t.dtype)
            y = jnp.einsum("kf,kfd->kd", z, dn_w)
            return jnp.einsum("k,kd->d", g_t.astype(y.dtype), y)
        out = jax.vmap(one_tok)(ht, idx, gate_vals)
        if "s_up" in p:
            z = L.swiglu(L.linear(p["s_gate"], ht), L.linear(p["s_up"], ht))
            out = out + L.linear(p["s_down"], z)
        return out.reshape(B, S, d)
    if T <= 512:
        # smoke-test scale: exact dropless dense-masked compute (E/k x more
        # FLOPs, bit-consistent with the decode path).  Dry-run/production
        # shapes take the capacity path below.
        w = jnp.zeros((T, m.n_experts), jnp.float32)
        w = jnp.einsum("tke,tk->te",
                       jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32),
                       gate_vals)
        if "e_gate" in p:
            z = L.swiglu(jnp.einsum("td,edf->tef", ht, p["e_gate"]),
                         jnp.einsum("td,edf->tef", ht, p["e_up"]))
        else:
            z = jax.nn.gelu(jnp.einsum("td,edf->tef", ht, p["e_up"])
                            .astype(jnp.float32)).astype(ht.dtype)
        ye = jnp.einsum("tef,efd->ted", z, p["e_down"]).astype(jnp.float32)
        out = jnp.einsum("ted,te->td", ye, w).astype(ht.dtype)
        if "s_up" in p:
            zs = L.swiglu(L.linear(p["s_gate"], ht), L.linear(p["s_up"], ht))
            out = out + L.linear(p["s_down"], zs)
        return out.reshape(B, S, d)
    # --- grouped capacity dispatch (GShard-style) -------------------------
    # The one-hot dispatch tensor is O(T_group * E * cap) = O(T_group^2);
    # at 1M tokens a single group is quadratic-in-T and explodes HBM, so we
    # process ~8192-token groups sequentially (lax.map + remat): one group's
    # dispatch buffers live at a time, which is also how the paper's tiled
    # WMEM/DMEM hierarchy would stream the expert batches.
    g = max(1, min(S, 8192 // max(1, B)))
    while S % g:
        g -= 1
    n_groups = S // g
    tg = B * g
    cap = max(1, int(MOE_CAPACITY * m.top_k * tg / m.n_experts))

    # §Perf note: hoisting the expert-weight gather via replication hints
    # was tried and REFUTED (wire 7.3 -> 17.2 TiB: the hint forces per-
    # group re-reshards in backward).  The effective lever is group COUNT:
    # each group iteration costs one weight-grad partial reduction, so
    # fewer/bigger groups amortise it (dispatch stays token-sharded).
    e_up = p["e_up"]
    e_down = p["e_down"]
    e_gate = p.get("e_gate")

    def group_fn(hgrp):
        """hgrp: [B, g, d] -> [B, g, d] (router recomputed in-group)."""
        ht = hgrp.reshape(tg, d)
        logits = L.linear(p["router"], ht).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gv, ix = jax.lax.top_k(probs, m.top_k)
        gv = gv / jnp.maximum(gv.sum(-1, keepdims=True), 1e-9)
        onehot = jax.nn.one_hot(ix, m.n_experts, dtype=jnp.float32)
        pos = jnp.cumsum(onehot.sum(1), axis=0) - onehot.sum(1)
        keep = (pos < cap).astype(jnp.float32)
        pos_k = jnp.einsum("tke,te->tk", onehot, pos)
        keep_k = jnp.einsum("tke,te->tk", onehot, keep)
        disp = jnp.einsum("tke,tkc->tec", onehot * keep_k[..., None],
                          jax.nn.one_hot(pos_k, cap, dtype=jnp.float32))
        xe = jnp.einsum("td,tec->ecd", ht.astype(jnp.float32),
                        disp).astype(ht.dtype)
        if e_gate is not None:
            z = L.swiglu(jnp.einsum("ecd,edf->ecf", xe, e_gate),
                         jnp.einsum("ecd,edf->ecf", xe, e_up))
        else:
            z = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, e_up)
                            .astype(jnp.float32)).astype(xe.dtype)
        ye = jnp.einsum("ecf,efd->ecd", z, e_down)
        comb = jnp.einsum("tec,tk,tke->tec", disp,
                          gv.astype(jnp.float32), onehot)
        out = jnp.einsum("ecd,tec->td", ye.astype(jnp.float32), comb)
        return out.astype(ht.dtype).reshape(B, g, d)

    if n_groups == 1:
        out = group_fn(h)
    else:
        hg = h.reshape(B, n_groups, g, d).swapaxes(0, 1)   # [G, B, g, d]
        out = jax.lax.map(jax.checkpoint(group_fn), hg)
        out = out.swapaxes(0, 1).reshape(B, S, d)
    out = out.reshape(B, S, d)
    if "s_up" in p:
        ht = h.reshape(T, d)
        z = L.swiglu(L.linear(p["s_gate"], ht), L.linear(p["s_up"], ht))
        out = out + L.linear(p["s_down"], z).reshape(B, S, d)
    return out.reshape(B, S, d)


# ===========================================================================
# attention blocks (full sequence)
# ===========================================================================
def _mla_q(p: Dict, cfg: ArchConfig, h: jnp.ndarray) -> jnp.ndarray:
    """MLA queries [..., H*qk_d]: through the q LoRA (down, norm, up), or
    one projection where the arch has none."""
    if cfg.mla.q_lora_rank is None:
        return L.linear(p["wq"], h)
    return L.linear(p["wuq"], L.rmsnorm(p["q_norm"], L.linear(p["wdq"], h),
                                        cfg.norm_eps))


def _attn_qkv(p: Dict, cfg: ArchConfig, h: jnp.ndarray, positions):
    B, S, d = h.shape
    hd, H, Hk = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    if cfg.mla is not None:
        m = cfg.mla
        qk_d = m.qk_nope_head_dim + m.qk_rope_head_dim
        q = _mla_q(p, cfg, h).reshape(B, S, H, qk_d)
        ckv = L.linear(p["wdkv"], h)
        c, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
        c = L.rmsnorm(p["kv_norm"], c, cfg.norm_eps)
        kv = L.linear(p["wukv"], c).reshape(
            B, S, H, m.qk_nope_head_dim + m.v_head_dim)
        k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
        q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
        q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
        k_rope = L.apply_rope(k_rope.reshape(B, S, 1, m.qk_rope_head_dim),
                              positions, cfg.rope_theta)
        k_rope_b = jnp.broadcast_to(k_rope, (B, S, H, m.qk_rope_head_dim))
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        return q_full, k_full, v, dict(ckv=c, krope=k_rope)
    q = L.linear(p["wq"], h).reshape(B, S, H, hd)
    k = L.linear(p["wk"], h).reshape(B, S, Hk, hd)
    v = L.linear(p["wv"], h).reshape(B, S, Hk, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v, dict(k=k, v=v)


def _attn_apply(p: Dict, cfg: ArchConfig, kind: str, x: jnp.ndarray,
                ctx: Optional[jnp.ndarray], positions, causal: bool,
                collect: bool):
    B, S, d = x.shape
    hd, H, Hk = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    q, k, v, cache = _attn_qkv(p, cfg, h, positions)
    o = attn.chunked_attention(q, k, v, causal=causal,
                               window=cfg.sliding_window,
                               q_chunk=min(512, S))
    vd = o.shape[-1]
    x = x + L.linear(p["wo"], o.reshape(B, S, H * vd))
    if kind == "xattn" and ctx is not None:
        hx = L.rmsnorm(p["x_norm"], x, cfg.norm_eps)
        Sc = ctx.shape[1]
        qx = L.linear(p["x_wq"], hx).reshape(B, S, H, hd)
        kx = L.linear(p["x_wk"], ctx).reshape(B, Sc, Hk, hd)
        vx = L.linear(p["x_wv"], ctx).reshape(B, Sc, Hk, hd)
        ox = attn.chunked_attention(qx, kx, vx, causal=False,
                                    q_chunk=min(512, S))
        gate = jnp.tanh(p["x_gate"].astype(jnp.float32)).astype(x.dtype)
        x = x + gate * L.linear(p["x_wo"], ox.reshape(B, S, H * hd))
        if collect:
            cache = dict(cache, xk=kx, xv=vx)
    if not collect:
        cache = None
    return x, cache


# ===========================================================================
# Mamba (remat-chunked selective scan)
# ===========================================================================
def _mamba_scan_chunk(h0, dt, B_in, C_in, xz, a):
    """Sequential inner scan over one chunk.
    h0 [B,di,ds]; dt [B,T,di]; B_in/C_in [B,T,ds]; xz [B,T,di]; a [di,ds]."""
    def step(h, inp):
        dt_t, b_t, c_t, x_t = inp
        decay = jnp.exp(dt_t[..., None] * a)           # [B,di,ds]
        h = decay * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = (h * c_t[:, None, :]).sum(-1)              # [B,di]
        return h, y
    h, ys = jax.lax.scan(step, h0,
                         (jnp.moveaxis(dt, 1, 0), jnp.moveaxis(B_in, 1, 0),
                          jnp.moveaxis(C_in, 1, 0), jnp.moveaxis(xz, 1, 0)))
    return h, jnp.moveaxis(ys, 0, 1)


def _mamba_apply(p: Dict, cfg: ArchConfig, x: jnp.ndarray, collect: bool):
    mc = cfg.mamba or MambaConfig()
    B, S, d = x.shape
    di, ds = mc.expand * d, mc.d_state
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    xz = L.linear(p["in_proj"], h)
    xm_raw, z = jnp.split(xz, 2, axis=-1)               # [B,S,di] each
    # depthwise causal conv1d
    pad = jnp.pad(xm_raw, ((0, 0), (mc.d_conv - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S] * p["conv_w"][i] for i in range(mc.d_conv))
    xm = jax.nn.silu((conv + p["conv_b"]).astype(jnp.float32)).astype(x.dtype)
    proj = L.linear(p["x_proj"], xm).astype(jnp.float32)
    dt_in, B_in, C_in = jnp.split(proj, [1, 1 + ds], axis=-1)
    dt = jax.nn.softplus(L.linear(p["dt_w"], dt_in).astype(jnp.float32)
                         + p["dt_bias"])                # [B,S,di]
    a = -jnp.exp(p["a_log"])                            # [di,ds]
    xf = xm.astype(jnp.float32)

    n_chunks = max(1, S // MAMBA_CHUNK) if S % MAMBA_CHUNK == 0 else 1
    ch = S // n_chunks
    h0 = jnp.zeros((B, di, ds), jnp.float32)

    def outer(h_carry, chunk_idx):
        sl = lambda arr: jax.lax.dynamic_slice_in_dim(arr, chunk_idx * ch, ch, 1)
        hN, ys = jax.remat(_mamba_scan_chunk)(
            h_carry, sl(dt), sl(B_in), sl(C_in), sl(xf), a)
        return hN, ys

    h_final, ys = jax.lax.scan(outer, h0, jnp.arange(n_chunks))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, di)
    y = y + p["d_skip"] * xf
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = x + L.linear(p["out_proj"], y)
    cache = None
    if collect:
        # conv state = last (d_conv-1) PRE-conv inputs
        conv_state = pad[:, S:S + mc.d_conv - 1] if mc.d_conv > 1 else \
            xm_raw[:, :0]
        cache = dict(conv=conv_state.astype(x.dtype), ssm=h_final)
    return out, cache


def _mamba_decode(p: Dict, cfg: ArchConfig, x_t: jnp.ndarray, cache: Dict):
    mc = cfg.mamba or MambaConfig()
    B, _, d = x_t.shape
    di, ds = mc.expand * d, mc.d_state
    h = L.rmsnorm(p["norm1"], x_t, cfg.norm_eps)
    xz = L.linear(p["in_proj"], h)[:, 0]                # [B, 2di]
    xm, z = jnp.split(xz, 2, axis=-1)
    hist = jnp.concatenate([cache["conv"], xm[:, None]], axis=1)  # [B,dc,di]
    conv = (hist * p["conv_w"][None]).sum(1) + p["conv_b"]
    xc = jax.nn.silu(conv.astype(jnp.float32)).astype(x_t.dtype)
    proj = L.linear(p["x_proj"], xc).astype(jnp.float32)
    dt_in, B_in, C_in = jnp.split(proj, [1, 1 + ds], axis=-1)
    dt = jax.nn.softplus(L.linear(p["dt_w"], dt_in).astype(jnp.float32)
                         + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    decay = jnp.exp(dt[..., None] * a)
    hs = decay * cache["ssm"] + (dt * xc.astype(jnp.float32))[..., None] \
        * B_in[:, None, :]
    y = (hs * C_in[:, None, :]).sum(-1) + p["d_skip"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x_t.dtype)
    out = x_t + L.linear(p["out_proj"], y)[:, None]
    return out, dict(conv=hist[:, 1:].astype(x_t.dtype), ssm=hs)


# ===========================================================================
# xLSTM blocks
# ===========================================================================
def _mlstm_apply(p: Dict, cfg: ArchConfig, x: jnp.ndarray, collect: bool):
    """Chunked linear-attention form of mLSTM (sigmoid-stabilised gates)."""
    B, S, d = x.shape
    di, dqk, H = _xlstm_dims(cfg)
    dqk_h, dv_h = dqk // H, di // H
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    up = L.linear(p["up"], h)
    u, z = jnp.split(up, 2, axis=-1)                    # [B,S,di]
    q = L.linear(p["wq"], u).reshape(B, S, H, dqk_h)
    k = L.linear(p["wk"], u).reshape(B, S, H, dqk_h) / np.sqrt(dqk_h)
    v = L.linear(p["wv"], u).reshape(B, S, H, dv_h)
    gts = L.linear(p["gates"], u).astype(jnp.float32).reshape(B, S, 2, H)
    ig = jax.nn.sigmoid(gts[:, :, 0])                   # [B,S,H]
    fg = jax.nn.sigmoid(gts[:, :, 1] + 4.0)             # forget bias -> ~1

    n_chunks = max(1, S // MLSTM_CHUNK) if S % MLSTM_CHUNK == 0 else 1
    ch = S // n_chunks
    qc = q.reshape(B, n_chunks, ch, H, dqk_h)
    kc = k.reshape(B, n_chunks, ch, H, dqk_h)
    vc = v.reshape(B, n_chunks, ch, H, dv_h)
    ic = ig.reshape(B, n_chunks, ch, H)
    fc = fg.reshape(B, n_chunks, ch, H)

    def chunk(carry, idx):
        C = carry                                        # [B,H,dqk,dv]
        qi, ki, vi = qc[:, idx], kc[:, idx], vc[:, idx]
        ii, fi = ic[:, idx], fc[:, idx]
        logf = jnp.log(jnp.maximum(fi, 1e-6))            # [B,ch,H]
        cum = jnp.cumsum(logf, axis=1)                   # inclusive
        # intra-chunk: D[t,s] = exp(cum_t - cum_s) * i_s  for s <= t
        dmask = (cum[:, :, None] - cum[:, None, :])      # [B,t,s,H]
        tri = jnp.tril(jnp.ones((ch, ch), bool))
        dmat = jnp.where(tri[None, :, :, None],
                         jnp.exp(dmask) * ii[:, None, :, :], 0.0)
        scores = jnp.einsum("bthd,bshd->btsh", qi.astype(jnp.float32),
                            ki.astype(jnp.float32))
        o_intra = jnp.einsum("btsh,bshe->bthe", scores * dmat,
                             vi.astype(jnp.float32))
        # inter-chunk: q_t decayed to chunk start @ C
        o_inter = jnp.einsum("bthd,bhde->bthe",
                             qi.astype(jnp.float32) * jnp.exp(cum)[..., None],
                             C)
        # state update: C' = F_total*C + sum_s exp(cum_end-cum_s) i_s k_s v_s^T
        f_tot = jnp.exp(cum[:, -1])                      # [B,H]
        w = jnp.exp(cum[:, -1:, :] - cum) * ii           # [B,ch,H]
        C_new = (f_tot[:, :, None, None] * C
                 + jnp.einsum("bshd,bshe->bhde",
                              ki.astype(jnp.float32) * w[..., None],
                              vi.astype(jnp.float32)))
        return C_new, (o_intra + o_inter).astype(x.dtype)

    C0 = jnp.zeros((B, H, dqk_h, dv_h), jnp.float32)
    C_final, outs = jax.lax.scan(chunk, C0, jnp.arange(n_chunks))
    o = jnp.moveaxis(outs, 0, 1).reshape(B, S, di)
    o = L.rmsnorm(p["ln"], o, cfg.norm_eps)
    o = o * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = x + L.linear(p["down"], o)
    cache = dict(C=C_final) if collect else None
    return out, cache


def _mlstm_decode(p: Dict, cfg: ArchConfig, x_t: jnp.ndarray, cache: Dict):
    B, _, d = x_t.shape
    di, dqk, H = _xlstm_dims(cfg)
    dqk_h, dv_h = dqk // H, di // H
    h = L.rmsnorm(p["norm1"], x_t, cfg.norm_eps)
    up = L.linear(p["up"], h)[:, 0]
    u, z = jnp.split(up, 2, axis=-1)
    q = L.linear(p["wq"], u).reshape(B, H, dqk_h).astype(jnp.float32)
    k = (L.linear(p["wk"], u).reshape(B, H, dqk_h) / np.sqrt(dqk_h)).astype(jnp.float32)
    v = L.linear(p["wv"], u).reshape(B, H, dv_h).astype(jnp.float32)
    gts = L.linear(p["gates"], u).astype(jnp.float32).reshape(B, 2, H)
    ig = jax.nn.sigmoid(gts[:, 0])
    fg = jax.nn.sigmoid(gts[:, 1] + 4.0)
    C = fg[..., None, None] * cache["C"] \
        + ig[..., None, None] * jnp.einsum("bhd,bhe->bhde", k, v)
    o = jnp.einsum("bhd,bhde->bhe", q, C).reshape(B, di)
    o = L.rmsnorm(p["ln"], o.astype(x_t.dtype), cfg.norm_eps)
    o = o * jax.nn.silu(z.astype(jnp.float32)).astype(x_t.dtype)
    out = x_t + L.linear(p["down"], o)[:, None]
    return out, dict(C=C)


def _slstm_apply(p: Dict, cfg: ArchConfig, x: jnp.ndarray, collect: bool):
    B, S, d = x.shape
    di, _, _ = _xlstm_dims(cfg)
    hin = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    u = L.linear(p["up"], hin)                          # [B,S,di]
    wx = L.linear(p["wx"], u).astype(jnp.float32)       # [B,S,4di]

    def step(carry, wx_t):
        h_prev, c_prev = carry
        pre = wx_t + (h_prev.astype(x.dtype) @ p["wr"]["w"]).astype(jnp.float32)
        i, f, zg, o = jnp.split(pre, 4, axis=-1)
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f + 2.0)
        c = f * c_prev + i * jnp.tanh(zg)
        hcur = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (hcur, c), hcur

    init = (jnp.zeros((B, di), jnp.float32), jnp.zeros((B, di), jnp.float32))
    (hN, cN), hs = jax.lax.scan(step, init, jnp.moveaxis(wx, 1, 0))
    y = jnp.moveaxis(hs, 0, 1).astype(x.dtype)
    y = L.rmsnorm(p["ln"], y, cfg.norm_eps)
    out = x + L.linear(p["down"], y)
    cache = dict(h=hN, c=cN) if collect else None
    return out, cache


def _slstm_decode(p: Dict, cfg: ArchConfig, x_t: jnp.ndarray, cache: Dict):
    B, _, d = x_t.shape
    hin = L.rmsnorm(p["norm1"], x_t, cfg.norm_eps)
    u = L.linear(p["up"], hin)[:, 0]
    wx = L.linear(p["wx"], u).astype(jnp.float32)
    pre = wx + (cache["h"].astype(x_t.dtype) @ p["wr"]["w"]).astype(jnp.float32)
    i, f, zg, o = jnp.split(pre, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f + 2.0)
    c = f * cache["c"] + i * jnp.tanh(zg)
    hcur = jax.nn.sigmoid(o) * jnp.tanh(c)
    y = L.rmsnorm(p["ln"], hcur.astype(x_t.dtype), cfg.norm_eps)
    out = x_t + L.linear(p["down"], y)[:, None]
    return out, dict(h=hcur, c=c)


# ===========================================================================
# unified block API
# ===========================================================================
def block_apply(params: Dict, cfg: ArchConfig, kind: str, moe_on: bool,
                x: jnp.ndarray, *, ctx=None, positions=None,
                causal: bool = True, collect_cache: bool = False):
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    if kind in ("attn", "xattn"):
        x, cache = _attn_apply(params, cfg, kind, x, ctx, positions, causal,
                               collect_cache)
    elif kind == "mamba":
        x, cache = _mamba_apply(params, cfg, x, collect_cache)
    elif kind == "mlstm":
        x, cache = _mlstm_apply(params, cfg, x, collect_cache)
    elif kind == "slstm":
        x, cache = _slstm_apply(params, cfg, x, collect_cache)
    else:
        raise ValueError(kind)
    if cfg.d_ff > 0 and kind not in ("mlstm", "slstm"):
        x = _ffn(params, cfg, x)
    return x, cache


def block_decode(params: Dict, cfg: ArchConfig, kind: str, moe_on: bool,
                 x_t: jnp.ndarray, cache: Dict, pos, *, ctx=None):
    """x_t: [B,1,d]; pos: scalar int (current length)."""
    B = x_t.shape[0]
    hd, H, Hk = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    if kind in ("attn", "xattn"):
        h = L.rmsnorm(params["norm1"], x_t, cfg.norm_eps)
        positions = jnp.full((B, 1), pos)
        # two-tier cache: `plen` tokens live in the (sequence-sharded)
        # frozen prefix; the newest (pos - plen + 1) tokens live in the
        # small replicated ring tail.  Writes touch only the tail, so no
        # traced-index update ever hits a sharded dimension.
        plen = cache["plen"]
        tpos = jnp.maximum(pos - plen, 0) % KV_TAIL
        if cfg.mla is not None:
            # MLA decode with absorbed projections: only the compressed
            # latent (kv_lora_rank + rope_dim per token) is cached.
            m = cfg.mla
            r = m.kv_lora_rank
            qk_d = m.qk_nope_head_dim + m.qk_rope_head_dim
            q = _mla_q(params, cfg, h).reshape(B, 1, H, qk_d)
            q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
            q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
            ckv_t = L.linear(params["wdkv"], h)            # [B,1,r+rope]
            c_t, krope_t = jnp.split(ckv_t, [r], axis=-1)
            c_t = L.rmsnorm(params["kv_norm"], c_t, cfg.norm_eps)
            krope_t = L.apply_rope(
                krope_t.reshape(B, 1, 1, m.qk_rope_head_dim), positions,
                cfg.rope_theta)
            ckv_tail = jax.lax.dynamic_update_slice_in_dim(
                cache["ckv_tail"], c_t.astype(cache["ckv_tail"].dtype),
                tpos, axis=1)
            krope_tail = jax.lax.dynamic_update_slice_in_dim(
                cache["krope_tail"],
                krope_t.astype(cache["krope_tail"].dtype), tpos, axis=1)
            wukv = params["wukv"]["w"].reshape(
                r, H, m.qk_nope_head_dim + m.v_head_dim)
            w_uk = wukv[..., :m.qk_nope_head_dim]          # [r,H,nope]
            w_uv = wukv[..., m.qk_nope_head_dim:]          # [r,H,v]
            q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                               w_uk.astype(jnp.float32))
            scale = 1.0 / jnp.sqrt(jnp.asarray(qk_d, jnp.float32))

            def mla_stats(ckv_seg, krope_seg, length):
                # bf16 operands + f32 accumulation: avoid hoisted f32
                # copies of the stacked latent cache (see attention.py)
                s = (L.einsum_f32("bqhr,bsr->bhqs",
                                  q_lat.astype(ckv_seg.dtype), ckv_seg)
                     + L.einsum_f32("bqhn,bsxn->bhqs",
                                    q_rope.astype(krope_seg.dtype),
                                    krope_seg[:, :, 0:1]))
                s = s * scale
                s = L.shard_hint(s, "__dp__", None, None, "model")
                valid = jnp.arange(ckv_seg.shape[1])[None, :] < length
                s = jnp.where(valid[:, None, None, :], s, attn.NEG_INF)
                mm = jnp.max(s, axis=-1)
                p = jnp.exp(s - mm[..., None])
                ll = jnp.sum(p, axis=-1)
                ctx = L.einsum_f32("bhqs,bsr->bqhr",
                                   p.astype(ckv_seg.dtype), ckv_seg)
                return ctx, mm, ll

            pre = mla_stats(cache["ckv"], cache["krope"],
                            jnp.minimum(plen, cache["ckv"].shape[1]))
            tail = mla_stats(ckv_tail, krope_tail, tpos + 1)
            ctx_lat = attn.merge_attention([pre, tail], jnp.float32)
            o = jnp.einsum("bqhr,rhv->bqhv", ctx_lat,
                           w_uv.astype(jnp.float32)).astype(x_t.dtype)
            x_t = x_t + L.linear(params["wo"],
                                 o.reshape(B, 1, H * m.v_head_dim))
            cache = dict(cache, ckv_tail=ckv_tail, krope_tail=krope_tail)
        else:
            q = L.linear(params["wq"], h).reshape(B, 1, H, hd)
            k = L.linear(params["wk"], h).reshape(B, 1, Hk, hd)
            v = L.linear(params["wv"], h).reshape(B, 1, Hk, hd)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            S = cache["k"].shape[1]
            kt, vt = attn.cache_update(cache["k_tail"], cache["v_tail"],
                                       k, v, tpos)
            # prefix: a ring of the last <=S tokens (== the SWA window for
            # sliding-window archs); tail: the newest tpos+1 tokens
            pre = attn.decode_attention_stats(q, cache["k"], cache["v"],
                                              jnp.minimum(plen, S))
            tail = attn.decode_attention_stats(q, kt, vt, tpos + 1)
            o = attn.merge_attention([pre, tail], x_t.dtype)
            x_t = x_t + L.linear(params["wo"], o.reshape(B, 1, H * hd))
            cache = dict(cache, k_tail=kt, v_tail=vt)
        if kind == "xattn" and "xk" in cache:
            hx = L.rmsnorm(params["x_norm"], x_t, cfg.norm_eps)
            qx = L.linear(params["x_wq"], hx).reshape(B, 1, H, hd)
            ox = attn.decode_attention(qx, cache["xk"], cache["xv"],
                                       cache["xk"].shape[1])
            gate = jnp.tanh(params["x_gate"].astype(jnp.float32)).astype(x_t.dtype)
            x_t = x_t + gate * L.linear(params["x_wo"],
                                        ox.reshape(B, 1, H * hd))
    elif kind == "mamba":
        x_t, cache = _mamba_decode(params, cfg, x_t, cache)
    elif kind == "mlstm":
        x_t, cache = _mlstm_decode(params, cfg, x_t, cache)
    elif kind == "slstm":
        x_t, cache = _slstm_decode(params, cfg, x_t, cache)
    if cfg.d_ff > 0 and kind not in ("mlstm", "slstm"):
        x_t = _ffn(params, cfg, x_t)
    return x_t, cache


def init_cache(cfg: ArchConfig, kind: str, batch: int, cache_len: int, dtype
               ) -> Dict:
    hd, Hk = cfg.head_dim, cfg.n_kv_heads
    if kind in ("attn", "xattn"):
        S = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
        if cfg.mla is not None:
            m = cfg.mla
            c = dict(ckv=jnp.zeros((batch, S, m.kv_lora_rank), dtype),
                     krope=jnp.zeros((batch, S, 1, m.qk_rope_head_dim), dtype),
                     ckv_tail=jnp.zeros((batch, KV_TAIL, m.kv_lora_rank),
                                        dtype),
                     krope_tail=jnp.zeros(
                         (batch, KV_TAIL, 1, m.qk_rope_head_dim), dtype),
                     plen=jnp.zeros((), jnp.int32))
        else:
            c = dict(k=jnp.zeros((batch, S, Hk, hd), dtype),
                     v=jnp.zeros((batch, S, Hk, hd), dtype),
                     k_tail=jnp.zeros((batch, KV_TAIL, Hk, hd), dtype),
                     v_tail=jnp.zeros((batch, KV_TAIL, Hk, hd), dtype),
                     plen=jnp.zeros((), jnp.int32))
        if kind == "xattn":
            c["xk"] = jnp.zeros((batch, cfg.n_context_tokens, Hk, hd), dtype)
            c["xv"] = jnp.zeros((batch, cfg.n_context_tokens, Hk, hd), dtype)
        return c
    if kind == "mamba":
        mc = cfg.mamba or MambaConfig()
        di = mc.expand * cfg.d_model
        return dict(conv=jnp.zeros((batch, mc.d_conv - 1, di), dtype),
                    ssm=jnp.zeros((batch, di, mc.d_state), jnp.float32))
    if kind == "mlstm":
        di, dqk, H = _xlstm_dims(cfg)
        return dict(C=jnp.zeros((batch, H, dqk // H, di // H), jnp.float32))
    if kind == "slstm":
        di, _, _ = _xlstm_dims(cfg)
        return dict(h=jnp.zeros((batch, di), jnp.float32),
                    c=jnp.zeros((batch, di), jnp.float32))
    raise ValueError(kind)
