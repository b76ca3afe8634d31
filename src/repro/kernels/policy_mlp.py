"""Pallas TPU fused GELU-MLP stack — the MPC/world-model/surrogate hot loop.

The paper's MPC planner evaluates K x H = 320 rollout steps of small MLPs
per decision (§3.16); the DSE plane batches thousands of candidate
configurations (DESIGN.md §3 note 1).  This kernel keeps ALL layer weights
resident in VMEM (the whole [82->128->64->52] world-model + surrogate stack
is < 100 KB) and tiles only the candidate batch, so one grid pass evaluates
the full batch with zero intermediate HBM traffic.

Tiling: grid = (B / block_b,); weights use trivial (whole-array) BlockSpecs;
intermediate activations live in registers/VMEM within the kernel body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_B = 256


def _mlp_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref,
                y_ref):
    x = x_ref[...].astype(jnp.float32)
    h = jax.nn.gelu(
        jax.lax.dot_general(x, w1_ref[...].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + b1_ref[...])
    h = jax.nn.gelu(
        jax.lax.dot_general(h, w2_ref[...].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + b2_ref[...])
    y = jax.lax.dot_general(h, w3_ref[...].astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) + b3_ref[...]
    y_ref[...] = y.astype(y_ref.dtype)


def fused_mlp_pallas(x: jnp.ndarray, w1, b1, w2, b2, w3, b3, *,
                     block_b: int = DEFAULT_BLOCK_B,
                     interpret: bool = False) -> jnp.ndarray:
    """x: [B, d_in]; weights wi: [d_{i-1}, d_i], bi: [d_i].  Pads B to the
    batch tile.  Returns [B, d_out] in x.dtype."""
    B, d_in = x.shape
    d_out = w3.shape[1]
    block_b = min(block_b, max(8, B))
    pad = (-B) % block_b
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    Bp = x.shape[0]

    whole = lambda arr: pl.BlockSpec(arr.shape, lambda i: (0,) * arr.ndim)
    y = pl.pallas_call(
        _mlp_kernel,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, d_in), lambda i: (i, 0)),
            whole(w1), whole(b1), whole(w2), whole(b2), whole(w3), whole(b3),
        ],
        out_specs=pl.BlockSpec((block_b, d_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, d_out), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w1, b1, w2, b2, w3, b3)
    return y[:B]
