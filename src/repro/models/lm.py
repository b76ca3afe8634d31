"""Full language-model assembly for every assigned architecture.

Depth is organised as (n_periods x period) where `period` is the smallest
repeating block pattern (dense: 1; Jamba: 8 = 1 attn + 7 mamba; Llama-3.2
vision: 5 = 4 self + 1 cross; xLSTM: 8 = 7 mLSTM + 1 sLSTM).  Parameters of
each position-in-period are stacked over periods and the decoder runs as a
`lax.scan` over periods with a remat'd body — HLO size is O(period), not
O(depth), which keeps 512-device dry-run compiles fast (DESIGN.md §7).

Whisper (enc-dec) runs an encoder scan over the (stub) frame embeddings and
gives every decoder layer a cross-attention block ("xattn" kinds).

Leading layers outside the pattern (DeepSeek-V2's ``first_k_dense`` dense
layers before the MoE stack) run before the scan, one block each, under
``params["lead"]["l<i>"]`` and cache ``l<i>``, stacked over one period so
that they share the scanned blocks' layout.

Public entry points:
  init_params(key, cfg)
  forward(params, cfg, tokens, ctx=None)            -> logits
  loss_fn(params, cfg, tokens, labels, ctx=None)    -> scalar loss
  prefill(params, cfg, tokens, ctx=None)            -> (last_logits, caches)
  decode_step(params, cfg, token, caches, pos, ctx) -> (logits, caches)
  init_caches(cfg, batch, cache_len)                -> cache pytree
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import blocks as blk
from repro.models import layers as L


# --------------------------------------------------------------- structure
def decoder_kinds(cfg: ArchConfig) -> Tuple[str, ...]:
    if cfg.is_encdec:
        return ("xattn",) * cfg.n_layers
    return cfg.layer_kinds()


def lead_of(cfg: ArchConfig) -> int:
    """Leading layers that run before the repeating pattern."""
    return cfg.moe.first_k_dense if cfg.moe is not None else 0


def period_of(cfg: ArchConfig) -> int:
    if cfg.is_encdec:
        return 1
    if cfg.family == "ssm" and cfg.xlstm is not None:
        p = cfg.xlstm.slstm_every
    elif cfg.attn_period > 0:
        p = cfg.attn_period
    elif cfg.cross_attn_every > 0:
        p = cfg.cross_attn_every
    else:
        p = 1
    if cfg.moe is not None and cfg.moe.every > 1:
        import math
        p = p * cfg.moe.every // math.gcd(p, cfg.moe.every)
    n = cfg.n_layers - lead_of(cfg)
    return p if n % p == 0 else n


def _layout(cfg: ArchConfig) -> Tuple[int, int, List[Tuple[str, bool]]]:
    """(period, periods, slots) of the layers after the leading ones."""
    kinds = decoder_kinds(cfg)
    lead, p = lead_of(cfg), period_of(cfg)
    n_periods = (cfg.n_layers - lead) // p
    slots = [(kinds[lead + j], cfg.moe_on_layer(lead + j)) for j in range(p)]
    # verify the pattern really repeats
    for i in range(lead, cfg.n_layers):
        assert kinds[i] == slots[(i - lead) % p][0], (cfg.name, i)
        assert cfg.moe_on_layer(i) == slots[(i - lead) % p][1], (cfg.name, i)
    return p, n_periods, slots


def _lead_slots(cfg: ArchConfig) -> List[Tuple[str, bool]]:
    kinds = decoder_kinds(cfg)
    return [(kinds[i], cfg.moe_on_layer(i)) for i in range(lead_of(cfg))]


def _unstack(tree):
    return jax.tree.map(lambda a: a[0], tree)


def _stack(tree):
    return jax.tree.map(lambda a: a[None], tree)


# ------------------------------------------------------------------- init
def init_params(key, cfg: ArchConfig) -> Dict:
    dt = L.dtype_of(cfg.param_dtype)
    p_len, n_periods, slots = _layout(cfg)
    keys = jax.random.split(key, 8)
    params: Dict = dict(embed=L.embed_init(keys[0], cfg.vocab, cfg.d_model, dt))
    blocks = {}
    for j, (kind, moe_on) in enumerate(slots):
        ks = jax.random.split(jax.random.fold_in(keys[1], j), n_periods)
        blocks[f"p{j}"] = jax.vmap(
            lambda k: blk.block_init(k, cfg, kind, moe_on))(ks)
    params["blocks"] = blocks
    lead = _lead_slots(cfg)
    if lead:
        params["lead"] = {
            f"l{i}": jax.vmap(lambda k: blk.block_init(k, cfg, kind, moe_on))(
                jax.random.split(jax.random.fold_in(keys[5], i), 1))
            for i, (kind, moe_on) in enumerate(lead)}
    params["final_norm"] = L.rmsnorm_init(cfg.d_model, dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.linear_init(keys[2], cfg.d_model, cfg.vocab, dt)
    if cfg.is_encdec:
        ks = jax.random.split(keys[3], cfg.enc_layers)
        params["enc"] = dict(
            blocks=jax.vmap(
                lambda k: blk.block_init(k, cfg, "attn", False))(ks),
            norm=L.rmsnorm_init(cfg.d_model, dt),
            pos=(jax.random.normal(keys[4], (cfg.n_audio_frames,
                                             cfg.d_model)) * 0.02).astype(dt))
    return params


# ------------------------------------------------------------------ encoder
def _encode_ctx(params: Dict, cfg: ArchConfig, ctx: jnp.ndarray) -> jnp.ndarray:
    """Whisper encoder over stub frame embeddings (bidirectional)."""
    x = ctx + params["enc"]["pos"][None, :ctx.shape[1]]

    def body(x, layer_params):
        y, _ = blk.block_apply(layer_params, cfg, "attn", False, x,
                               causal=False)
        return y.astype(x.dtype), None

    x, _ = jax.lax.scan(jax.checkpoint(body, prevent_cse=False), x,
                        params["enc"]["blocks"])
    return L.rmsnorm(params["enc"]["norm"], x, cfg.norm_eps)


def _embed_inputs(params, cfg, tokens, ctx):
    x = L.embed(params["embed"], tokens)
    if (cfg.family == "vlm" and cfg.cross_attn_every == 0 and ctx is not None):
        # prefix-VLM (SmolVLM): image embeddings replace the first positions
        n = min(cfg.n_context_tokens, ctx.shape[1], x.shape[1])
        x = jnp.concatenate([ctx[:, :n].astype(x.dtype), x[:, n:]], axis=1)
    return x


# ------------------------------------------------------------------ forward
def forward(params: Dict, cfg: ArchConfig, tokens: jnp.ndarray,
            ctx: Optional[jnp.ndarray] = None, *, collect_caches: bool = False,
            cache_len: int = 0, return_hidden: bool = False):
    """tokens [B,S] -> logits [B,S,V] (+ caches when collecting)."""
    p_len, n_periods, slots = _layout(cfg)
    if cfg.is_encdec:
        assert ctx is not None, "enc-dec needs frame embeddings"
        ctx = _encode_ctx(params, cfg, ctx)
    x = _embed_inputs(params, cfg, tokens, ctx)
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    lead_caches = {}
    for i, (kind, moe_on) in enumerate(_lead_slots(cfg)):
        x, c = blk.block_apply(_unstack(params["lead"][f"l{i}"]), cfg, kind,
                               moe_on, x, ctx=ctx, positions=positions,
                               collect_cache=collect_caches)
        x = x.astype(L.dtype_of(cfg.param_dtype))
        if collect_caches:
            lead_caches[f"l{i}"] = _stack(c)

    def body(x, period_params):
        # re-pin the scan carry's sharding: without this Shardy may leave
        # the carry replicated, un-sharding the whole batch inside the loop.
        # Sequence-parallel storage (seq over "model") additionally shards
        # the per-layer carry stack the scan saves for backward — 16x less
        # HBM for the residuals at production shapes.
        x = L.shard_hint(x, "__dp__", "model", None)
        caches = {}
        for j, (kind, moe_on) in enumerate(slots):
            x, c = blk.block_apply(period_params[f"p{j}"], cfg, kind, moe_on,
                                   x, ctx=ctx, positions=positions,
                                   collect_cache=collect_caches)
            if collect_caches:
                caches[f"p{j}"] = c
        return x.astype(L.dtype_of(cfg.param_dtype)), caches

    # prevent_cse=False: inside scan the CSE-prevention barriers are
    # unnecessary (jax docs) and they materialise an f32 copy of the
    # whole saved-carry stack (~5 GiB/device at 70B scale, §Perf)
    x, caches = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), x, params["blocks"])
    caches = {**lead_caches, **caches}
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return (x, caches) if collect_caches else x
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["w"].T
    else:
        logits = L.linear(params["lm_head"], x)
    logits = L.shard_hint(logits, "__dp__", None, "model")
    if collect_caches:
        return logits, caches
    return logits


def loss_fn(params: Dict, cfg: ArchConfig, tokens: jnp.ndarray,
            labels: jnp.ndarray, ctx: Optional[jnp.ndarray] = None,
            ce_chunk: int = 512):
    """Chunked cross-entropy: the head matmul + softmax run per sequence
    chunk (remat'd lax.map), so only one [B, chunk, V] logits block is live
    at a time — the full [B, S, V] f32 block was ~40% of train-cell peak
    HBM (§Perf train hillclimb)."""
    B, S = tokens.shape
    x = forward(params, cfg, tokens, ctx, return_hidden=True)
    if cfg.tie_embeddings:
        head_w = params["embed"]["w"].T
    else:
        head_w = params["lm_head"]["w"]
    if S % ce_chunk or S <= ce_chunk:
        logits = L.shard_hint(x @ head_w, "__dp__", None, "model")
        return L.cross_entropy(logits, labels)
    n = S // ce_chunk
    xc = jnp.moveaxis(x.reshape(B, n, ce_chunk, -1), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, ce_chunk), 1, 0)

    def chunk_sum(args):
        xs, ls = args
        logits = L.shard_hint(xs @ head_w, "__dp__", None, "model")
        logits = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        onehot = ls[..., None] == jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, logits.ndim - 1)
        gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        return jnp.sum(logz - gold)

    parts = jax.lax.map(jax.checkpoint(chunk_sum), (xc, lc))
    return parts.sum() / (B * S)


# ------------------------------------------------------------------- decode
def init_caches(cfg: ArchConfig, batch: int, cache_len: int) -> Dict:
    dt = L.dtype_of(cfg.param_dtype)
    p_len, n_periods, slots = _layout(cfg)

    def stack(tree):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_periods,) + a.shape), tree)

    caches = {f"l{i}": _stack(blk.init_cache(cfg, kind, batch, cache_len,
                                             dt))
              for i, (kind, _) in enumerate(_lead_slots(cfg))}
    caches.update({f"p{j}": stack(blk.init_cache(cfg, kind, batch, cache_len,
                                                 dt))
                   for j, (kind, _) in enumerate(slots)})
    return caches


def prefill(params: Dict, cfg: ArchConfig, tokens: jnp.ndarray,
            ctx: Optional[jnp.ndarray] = None):
    """Run the prompt; returns (last-token logits, caches at prompt length)."""
    logits, caches = forward(params, cfg, tokens, ctx, collect_caches=True)
    return logits[:, -1:], caches


_SEQ_CACHE_KEYS = ("k", "v", "ckv", "krope")


def extend_caches(caches: Dict, cfg: ArchConfig, new_len: int) -> Dict:
    """Prepare prefill caches for decoding: pad the (sequence-indexed)
    prefix to `new_len`, attach empty ring tails and set plen to the
    prompt length (two-tier decode cache, see models.blocks).  Stacked
    layout: arrays are [n_periods, B, S, ...] — sequence axis 2."""
    out = {}
    for pj, c in caches.items():
        nc = {}
        prompt_len = None
        for name, arr in c.items():
            if name in _SEQ_CACHE_KEYS and not name.startswith("x"):
                prompt_len = arr.shape[2]
                cap = min(new_len, cfg.sliding_window) \
                    if cfg.sliding_window else new_len
                pad = cap - arr.shape[2]
                if pad > 0:
                    widths = [(0, 0)] * arr.ndim
                    widths[2] = (0, pad)
                    arr = jnp.pad(arr, widths)
                elif pad < 0:
                    arr = arr[:, :, arr.shape[2] - cap:]  # SWA: keep last W
            nc[name] = arr
        if prompt_len is not None:   # attention cache: add tail + plen
            n_per = nc[next(iter(nc))].shape[0]
            for name in list(nc):
                if name in _SEQ_CACHE_KEYS:
                    tail_shape = list(nc[name].shape)
                    tail_shape[2] = blk.KV_TAIL
                    nc[name + "_tail"] = jnp.zeros(tuple(tail_shape),
                                                   nc[name].dtype)
            nc["plen"] = jnp.full((n_per,), prompt_len, jnp.int32)
        out[pj] = nc
    return out


def flush_tails(caches: Dict, cfg: ArchConfig) -> Dict:
    """Merge full ring tails into the sharded prefix.  Amortised: the
    serving loop calls this every KV_TAIL decode steps, so the traced-index
    update into the sequence-sharded prefix happens 1/KV_TAIL as often as a
    naive per-step cache write (requires prefix capacity % KV_TAIL == 0 for
    ring wrap)."""
    out = {}
    for pj, c in caches.items():
        if "plen" not in c:
            out[pj] = c
            continue
        nc = dict(c)
        for name in _SEQ_CACHE_KEYS:
            if name not in c:
                continue
            S = c[name].shape[2]

            def write(pre, tl, pl):
                return jax.lax.dynamic_update_slice_in_dim(
                    pre, tl.astype(pre.dtype), pl % S, axis=1)

            nc[name] = jax.vmap(write)(c[name], c[name + "_tail"], c["plen"])
        nc["plen"] = c["plen"] + blk.KV_TAIL
        out[pj] = nc
    return out


def decode_step(params: Dict, cfg: ArchConfig, token: jnp.ndarray,
                caches: Dict, pos, ctx: Optional[jnp.ndarray] = None):
    """token [B,1] int; caches from init_caches/prefill; pos = current
    length (scalar).  Returns (logits [B,1,V], new caches)."""
    p_len, n_periods, slots = _layout(cfg)
    # cross-attention KV (vision / encoder memory) is already cached from
    # prefill (cache["xk"/"xv"]); ctx is not re-encoded at decode time.
    del ctx
    ctx = None
    x = L.embed(params["embed"], token)
    lead_caches = {}
    for i, (kind, moe_on) in enumerate(_lead_slots(cfg)):
        x, c = blk.block_decode(_unstack(params["lead"][f"l{i}"]), cfg, kind,
                                moe_on, x, _unstack(caches[f"l{i}"]), pos,
                                ctx=ctx)
        x = x.astype(L.dtype_of(cfg.param_dtype))
        lead_caches[f"l{i}"] = _stack(c)

    def body(x, scanned):
        period_params, period_cache = scanned
        new_cache = {}
        for j, (kind, moe_on) in enumerate(slots):
            x, c = blk.block_decode(period_params[f"p{j}"], cfg, kind, moe_on,
                                    x, period_cache[f"p{j}"], pos, ctx=ctx)
            new_cache[f"p{j}"] = c
        return x.astype(L.dtype_of(cfg.param_dtype)), new_cache

    x, new_caches = jax.lax.scan(
        body, x, (params["blocks"],
                  {k: v for k, v in caches.items() if k not in lead_caches}))
    new_caches = {**lead_caches, **new_caches}
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["w"].T
    else:
        logits = L.linear(params["lm_head"], x)
    logits = L.shard_hint(logits, "__dp__", None, "model")
    return logits, new_caches
