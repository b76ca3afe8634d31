"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def attention_reference(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: int = 0) -> jnp.ndarray:
    """q: [B,H,Sq,hd]; k/v: [B,Hk,Sk,hd] -> [B,H,Sq,hd] (f32 softmax)."""
    B, H, Sq, hd = q.shape
    Hk, Sk = k.shape[1], k.shape[2]
    k = jnp.repeat(k, H // Hk, axis=1)
    v = jnp.repeat(v, H // Hk, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (hd ** 0.5)
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask = mask & (kp <= qp)
    if window > 0:
        mask = mask & (kp > qp - window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def ssm_scan_reference(dt: jnp.ndarray, b_in: jnp.ndarray, c_in: jnp.ndarray,
                       x: jnp.ndarray, a: jnp.ndarray,
                       h0: jnp.ndarray | None = None) -> jnp.ndarray:
    """Sequential selective-scan oracle.

    dt/x: [B,S,D]; b_in/c_in: [B,S,N]; a: [D,N]; h0: [B,D,N] or None.
    y[t] = C_t . h_t,  h_t = exp(dt_t*A) h_{t-1} + (dt_t*x_t) B_t.
    Returns (y [B,S,D], h_final [B,D,N]) in f32.
    """
    B, S, D = x.shape
    N = a.shape[1]
    if h0 is None:
        h0 = jnp.zeros((B, D, N), jnp.float32)

    def step(h, inp):
        dt_t, b_t, c_t, x_t = inp
        decay = jnp.exp(dt_t[..., None] * a)
        h = decay * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = (h * c_t[:, None, :]).sum(-1)
        return h, y

    h, ys = jax.lax.scan(
        step, h0,
        (jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
         jnp.moveaxis(b_in.astype(jnp.float32), 1, 0),
         jnp.moveaxis(c_in.astype(jnp.float32), 1, 0),
         jnp.moveaxis(x.astype(jnp.float32), 1, 0)))
    return jnp.moveaxis(ys, 0, 1), h


def fused_mlp_reference(x: jnp.ndarray, w1, b1, w2, b2, w3, b3) -> jnp.ndarray:
    """GELU-MLP stack oracle: gelu(gelu(x@w1+b1)@w2+b2)@w3+b3 (f32)."""
    h = jax.nn.gelu(x.astype(jnp.float32) @ w1.astype(jnp.float32) + b1)
    h = jax.nn.gelu(h @ w2.astype(jnp.float32) + b2)
    return (h @ w3.astype(jnp.float32) + b3).astype(x.dtype)


def screen_scores_reference(params, s: jnp.ndarray, cand: jnp.ndarray,
                            weights: jnp.ndarray) -> jnp.ndarray:
    """The score half of ``repro.ppa.surrogate.screen_batch``: scalarized
    log1p PPA proxy per candidate (lower = better), before the argmin/gate
    select.  s: [B,S]; cand: [B,K,C]; weights: [B,3] -> [B,K]."""
    from repro.ppa.surrogate import predict
    bsz, k = cand.shape[0], cand.shape[1]
    x = jnp.concatenate(
        [jnp.broadcast_to(s[:, None, :], (bsz, k, s.shape[-1])), cand],
        axis=-1)
    pred = predict(params, x)
    return (weights[:, None, 1] * pred[..., 0]
            + weights[:, None, 2] * pred[..., 2]
            - weights[:, None, 0] * pred[..., 1])


def actor_forward_reference(params, s: jnp.ndarray):
    """The live MoE actor forward (``repro.core.networks.actor_forward``)."""
    from repro.core.networks import actor_forward
    return actor_forward(params, s)

