"""Pallas TPU kernel: surrogate K-candidate screening scores (Eq. 67 path).

Once a cell's surrogate gate opens, every env-step proposes K candidate
actions and ``repro.ppa.surrogate.screen_batch`` scores all of them with the
(128, 64) surrogate MLP — B x K forward passes per dispatch, the hottest
surrogate call in the campaign engine.  This kernel keeps the whole
surrogate stack (< 50 KB) resident in VMEM and tiles only the env batch, so
one grid pass scores every candidate with zero intermediate HBM traffic.

The first layer is split by input rows: ``[s, a] @ w1 = s @ w1[:S] +
a @ w1[S:]``, so the state half runs once per env instead of once per
candidate, and the kernel body needs no in-kernel concatenate or reshape.
Candidates arrive candidate-major ([K, B, C]), so each one is a plain
leading-axis load, and the scalarization weights arrive as per-row
coefficients over the three predicted targets, so the score is a product
and a lane sum.  The kernel emits the scalarized log1p PPA proxy scores
(B, K) — lower = better, mirroring ``ppa_score``; the argmin/gate select
stays in jnp (it is O(B*K) scalar work).  Tiling: grid = (B / block_b,);
weights use whole-array BlockSpecs (the ``policy_mlp`` idiom), biases are
passed as (1, H) rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_B = 256


def _dot(a, b):
    return jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _screen_kernel(s_ref, cand_ref, coef_ref, w1s_ref, w1c_ref, b1_ref,
                   w2_ref, b2_ref, w3_ref, b3_ref, score_ref):
    hs = _dot(s_ref[...], w1s_ref[...]) + b1_ref[...]      # (bb, H1)
    coef = coef_ref[...].astype(jnp.float32)               # (bb, 3)
    n_cand = cand_ref.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n_cand), 1)
    score = jnp.zeros(score_ref.shape, jnp.float32)        # (bb, K)
    for k in range(n_cand):                                # static unroll
        h = jax.nn.gelu(hs + _dot(cand_ref[k], w1c_ref[...]))
        h = jax.nn.gelu(_dot(h, w2_ref[...]) + b2_ref[...])
        pred = _dot(h, w3_ref[...]) + b3_ref[...]          # (bb, 3)
        sk = jnp.sum(coef * pred, axis=-1, keepdims=True)  # (bb, 1)
        score = jnp.where(col == k, sk, score)
    score_ref[...] = score.astype(score_ref.dtype)


def screen_scores_pallas(s: jnp.ndarray, cand: jnp.ndarray,
                         weights: jnp.ndarray, w1, b1, w2, b2, w3, b3, *,
                         block_b: int = DEFAULT_BLOCK_B,
                         interpret: bool = False) -> jnp.ndarray:
    """s: [B, S]; cand: [B, K, C]; weights: [B, 3] (w_perf, w_power,
    w_area); wi/bi: surrogate MLP stack over [S+C] inputs.  Returns [B, K]
    scalarized screening scores.  Pads B to the batch tile."""
    B, K, C = cand.shape
    S = s.shape[1]
    # score = w_power * pred[0] - w_perf * pred[1] + w_area * pred[2]
    coef = jnp.stack([weights[:, 1], -weights[:, 0], weights[:, 2]], axis=1)
    cand = jnp.transpose(cand, (1, 0, 2))                  # (K, B, C)
    block_b = min(block_b, max(8, B))
    pad = (-B) % block_b
    if pad:
        s = jnp.pad(s, ((0, pad), (0, 0)))
        cand = jnp.pad(cand, ((0, 0), (0, pad), (0, 0)))
        coef = jnp.pad(coef, ((0, pad), (0, 0)))
    Bp = s.shape[0]
    params = (w1[:S], w1[S:], b1.reshape(1, -1), w2, b2.reshape(1, -1),
              w3, b3.reshape(1, -1))

    whole = lambda arr: pl.BlockSpec(arr.shape, lambda i: (0,) * arr.ndim)
    return pl.pallas_call(
        _screen_kernel,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, S), lambda i: (i, 0)),
            pl.BlockSpec((K, block_b, C), lambda i: (0, i, 0)),
            pl.BlockSpec((block_b, 3), lambda i: (i, 0)),
        ] + [whole(p) for p in params],
        out_specs=pl.BlockSpec((block_b, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, K), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(s, cand, coef, *params)[:B]
