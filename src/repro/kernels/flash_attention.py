"""Pallas TPU flash attention (blocked online-softmax), GQA + causal +
sliding-window.

Tiling: grid = (batch, q_heads, Sq/block_q, Sk/block_k); the kv dimension is
the innermost (sequential) grid axis, with f32 VMEM scratch accumulators
(acc [block_q, hd], running max m and sum l [block_q, 1]) carried across kv
steps.  block_q = block_k = 128 matches the MXU tile; K/V blocks for GQA are
indexed h -> h * Hk // H so each query-head group reads its shared KV head.

On TPU the pallas_call lowers natively; the CPU tests run it with
``interpret=True`` (Python emulation) and assert allclose against
``ref.attention_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int, block_q: int,
                  block_k: int, n_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # [bq, hd]
    k = k_ref[0, 0].astype(jnp.float32)          # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)          # [bk, hd]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window > 0:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                          # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: int = 0,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = False) -> jnp.ndarray:
    """q: [B, H, Sq, hd]; k/v: [B, Hk, Sk, hd].  Returns [B, H, Sq, hd]."""
    B, H, Sq, hd = q.shape
    Hk, Sk = k.shape[1], k.shape[2]
    assert H % Hk == 0 and Sq % block_q == 0 and Sk % block_k == 0
    n_q, n_k = Sq // block_q, Sk // block_k
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki, Hk=Hk, H=H:
                         (b, h * Hk // H, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, qi, ki, Hk=Hk, H=H:
                         (b, h * Hk // H, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
