"""Pallas TPU kernel: fused MoE actor forward (paper Fig. 2, Eq. 54).

``sac.policy_act_batch`` runs the 4-expert MoE actor over every env state on
every engine dispatch — K expert [52->256->256] GELU trunks plus three
gate-blended heads, the single largest per-step network in the search loop.
The reference path (``repro.core.networks.actor_forward``) materialises the
[B, K, 256] expert activations in HBM between einsums; this kernel keeps
ALL expert weights (~1.6 MB) resident in VMEM, tiles only the state batch,
and accumulates the gate-blended head outputs across the (static) expert
loop, so intermediates never leave the core.

Outputs mirror ``actor_forward`` exactly: flat discrete logits, tanh'd
means, clamped log-stds, gate probabilities — sampling (RNG) stays in jnp
(``repro.kernels.ops.policy_act_batch``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_B = 256
LOG_STD_MIN, LOG_STD_MAX = -20.0, 2.0   # networks.py clamp (Eq. 5)


def _dot(a, b):
    return jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _actor_kernel(s_ref, gw_ref, l1w_ref, l1b_ref, l2w_ref, l2b_ref,
                  dw_ref, db_ref, mw_ref, mb_ref, lw_ref, lb_ref,
                  disc_ref, mu_ref, ls_ref, gate_ref):
    s = s_ref[...].astype(jnp.float32)                       # (bb, S)
    g = jax.nn.softmax(_dot(s, gw_ref[...]), axis=-1)        # (bb, K) Eq. 54
    n_exp = gw_ref.shape[-1]
    disc = jnp.zeros((s.shape[0], db_ref.shape[-1]), jnp.float32)
    mu = jnp.zeros((s.shape[0], mb_ref.shape[-1]), jnp.float32)
    ls = jnp.zeros((s.shape[0], lb_ref.shape[-1]), jnp.float32)
    for k in range(n_exp):                                   # static unroll
        h1 = jax.nn.gelu(_dot(s, l1w_ref[k]) + l1b_ref[k])
        h2 = jax.nn.gelu(_dot(h1, l2w_ref[k]) + l2b_ref[k])
        gk = g[:, k:k + 1]
        disc = disc + gk * (_dot(h2, dw_ref[k]) + db_ref[k])
        mu = mu + gk * (_dot(h2, mw_ref[k]) + mb_ref[k])
        ls = ls + gk * (_dot(h2, lw_ref[k]) + lb_ref[k])
    disc_ref[...] = disc.astype(disc_ref.dtype)
    mu_ref[...] = jnp.tanh(mu).astype(mu_ref.dtype)
    ls_ref[...] = jnp.clip(ls, LOG_STD_MIN, LOG_STD_MAX).astype(ls_ref.dtype)
    gate_ref[...] = g.astype(gate_ref.dtype)


def actor_forward_pallas(s: jnp.ndarray, gate_w, l1w, l1b, l2w, l2b,
                         dw, db, mw, mb, lw, lb, *,
                         block_b: int = DEFAULT_BLOCK_B,
                         interpret: bool = False):
    """s: [B, S]; gate_w: [S, K]; l*/d*/m*/lw/lb: stacked per-expert dense
    params [K, ...].  Returns (disc_logits [B, n_disc_out], mu [B, n_cont],
    log_std [B, n_cont], gate [B, K]) — the flat-head view of
    ``networks.actor_forward``.  Pads B to the batch tile."""
    B = s.shape[0]
    n_exp = gate_w.shape[-1]
    n_disc, n_cont = db.shape[-1], mb.shape[-1]
    block_b = min(block_b, max(8, B))
    pad = (-B) % block_b
    if pad:
        s = jnp.pad(s, ((0, pad), (0, 0)))
    Bp = s.shape[0]

    whole = lambda arr: pl.BlockSpec(arr.shape, lambda i: (0,) * arr.ndim)
    blk = lambda d: pl.BlockSpec((block_b, d), lambda i: (i, 0))
    out = pl.pallas_call(
        _actor_kernel,
        grid=(Bp // block_b,),
        in_specs=[blk(s.shape[1])] + [whole(a) for a in (
            gate_w, l1w, l1b, l2w, l2b, dw, db, mw, mb, lw, lb)],
        out_specs=[blk(n_disc), blk(n_cont), blk(n_cont), blk(n_exp)],
        out_shape=[jax.ShapeDtypeStruct((Bp, d), jnp.float32)
                   for d in (n_disc, n_cont, n_cont, n_exp)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(s, gate_w, l1w, l1b, l2w, l2b, dw, db, mw, mb, lw, lb)
    return tuple(o[:B] for o in out)
