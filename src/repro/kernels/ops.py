"""Jit'd public wrappers around the Pallas kernels.

Every wrapper lowers its kernel natively, which only a TPU backend can do.
``interpret=True`` runs the same kernel in the Pallas interpreter instead;
only tests pass it, to check a kernel against its ``repro.kernels.ref``
oracle on the CPU.  The model zoo's default path is the pure-jnp reference
(repro.models.attention / repro.kernels.ref), which is what the dry-run
lowers.

The DSE search loop routes through ``screen_batch`` / ``policy_act_batch``
when :func:`kernels_enabled` says so: the platform alone decides, so a TPU
runs the native kernels and the CPU runs the jnp reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.actor_moe import actor_forward_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.policy_mlp import fused_mlp_pallas
from repro.kernels.screen_score import screen_scores_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas


def kernels_enabled() -> bool:
    """Route the search hot loop through the Pallas kernels?  True on a
    TPU backend, False on the CPU (the jnp reference path); any other
    backend has no kernel path and is an error."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"no search-loop kernel path for the {backend!r} backend: the "
            "Pallas kernels lower for TPU and the CPU runs the jnp "
            "reference")
    return backend == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: [B,H,Sq,hd]; k/v: [B,Hk,Sk,hd] -> [B,H,Sq,hd]."""
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_d", "chunk",
                                             "interpret"))
def ssm_scan(dt, b_in, c_in, x, a, *, block_d: int = 512, chunk: int = 128,
             interpret: bool = False):
    """Selective scan: dt/x [B,S,D], b/c [B,S,N], a [D,N] -> y [B,S,D]."""
    return ssm_scan_pallas(dt, b_in, c_in, x, a, block_d=block_d,
                           chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fused_mlp(x, w1, b1, w2, b2, w3, b3, *, block_b: int = 256,
              interpret: bool = False):
    """Fused 3-layer GELU MLP with VMEM-resident weights."""
    return fused_mlp_pallas(x, w1, b1, w2, b2, w3, b3, block_b=block_b,
                            interpret=interpret)


# --------------------------------------------------------------------------
# DSE search-loop kernels (drop-in for the pure-jnp hot-path references)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def screen_scores(params, s, cand, weights, *, block_b: int = 256,
                  interpret: bool = False):
    """[B, K] scalarized surrogate screening scores (lower = better)."""
    return screen_scores_pallas(
        s, cand, weights,
        params["l1"]["w"], params["l1"]["b"],
        params["l2"]["w"], params["l2"]["b"],
        params["head"]["w"], params["head"]["b"],
        block_b=block_b, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def screen_batch(params, s, cand, weights, open_mask, *,
                 interpret: bool = False):
    """Kernel-backed drop-in for ``repro.ppa.surrogate.screen_batch``:
    scores via the Pallas kernel, picks argmin where the gate is open."""
    score = screen_scores(params, s, cand, weights, interpret=interpret)
    return jnp.where(open_mask, jnp.argmin(score, axis=1), 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def actor_forward(params, s, *, interpret: bool = False):
    """Kernel-backed drop-in for ``repro.core.networks.actor_forward``:
    (disc_logits [B, N_DISC, N_DISC_OPTIONS], mu, log_std, gate)."""
    from repro.core.actions import N_DISC, N_DISC_OPTIONS
    disc, mu, log_std, gate = actor_forward_pallas(
        s, params["gate"],
        params["l1"]["w"], params["l1"]["b"],
        params["l2"]["w"], params["l2"]["b"],
        params["disc"]["w"], params["disc"]["b"],
        params["mu"]["w"], params["mu"]["b"],
        params["log_std"]["w"], params["log_std"]["b"],
        interpret=interpret)
    return (disc.reshape(s.shape[0], N_DISC, N_DISC_OPTIONS),
            mu, log_std, gate)


@functools.partial(jax.jit, static_argnames=("interpret",))
def policy_act_batch(actor_params, s, key, *, interpret: bool = False):
    """Kernel-backed drop-in for ``repro.core.sac.policy_act_batch``.

    The MoE forward runs in the Pallas kernel; sampling stays in jnp with
    the exact key-split structure of ``networks.sample_actions`` (kc for
    the Gaussian, kd for the categorical), so for identical forward
    outputs the sampled actions are identical too."""
    kc, kd = jax.random.split(key)
    disc_logits, mu, log_std, _ = actor_forward(actor_params, s,
                                                interpret=interpret)
    a = jnp.tanh(mu + jnp.exp(log_std) * jax.random.normal(kc, mu.shape))
    a_d = jax.random.categorical(kd, disc_logits, axis=-1)
    return a, a_d
