"""Pallas TPU chunked selective-scan (Mamba / linear-recurrence hot loop).

Tiling: grid = (batch, D/block_d, S/chunk) with the chunk axis innermost and
sequential; the recurrent state h [block_d, N] lives in f32 VMEM scratch and
carries across chunk iterations.  Inside a chunk the scan is a fori_loop over
time steps entirely in VMEM — the HBM traffic is exactly one read of
(dt, B, C, x) and one write of y per element, which is the roofline minimum
for this memory-bound op (arithmetic intensity ~ O(N)).

TPU adaptation note (DESIGN.md §3): CUDA Mamba kernels use warp-level
parallel scans; on TPU the VPU prefers a short sequential inner loop over a
VMEM-resident state with chunk-level grid parallelism over (batch, d_inner).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_D = 512
DEFAULT_CHUNK = 128


def _ssm_kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, y_ref, h_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)            # [bd, N]

    def step(t, h):
        dt_t = dt_ref[0, t].astype(jnp.float32)   # [bd]
        x_t = x_ref[0, t].astype(jnp.float32)     # [bd]
        b_t = b_ref[0, t].astype(jnp.float32)     # [N]
        c_t = c_ref[0, t].astype(jnp.float32)     # [N]
        decay = jnp.exp(dt_t[:, None] * a)        # [bd, N]
        h = decay * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_ref[0, t] = (h * c_t[None, :]).sum(-1).astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def ssm_scan_pallas(dt: jnp.ndarray, b_in: jnp.ndarray, c_in: jnp.ndarray,
                    x: jnp.ndarray, a: jnp.ndarray, *,
                    block_d: int = DEFAULT_BLOCK_D,
                    chunk: int = DEFAULT_CHUNK,
                    interpret: bool = False) -> jnp.ndarray:
    """dt/x: [B,S,D]; b_in/c_in: [B,S,N]; a: [D,N] -> y [B,S,D] (f32)."""
    B, S, D = x.shape
    N = a.shape[1]
    block_d = min(block_d, D)
    chunk = min(chunk, S)
    assert D % block_d == 0 and S % chunk == 0
    n_d, n_c = D // block_d, S // chunk

    kernel = functools.partial(_ssm_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, n_d, n_c),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((block_d, N), lambda b, d, c: (d, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dt, b_in, c_in, x, a)
