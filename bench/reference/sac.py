"""Plain reference of the search loop's learner step: the soft actor-critic
update with twin critics, entropy temperature and PER weights (paper
section 3.11, Table 5/6 hyperparameters), written in jax.numpy from the
paper's equations and importing nothing of the program.

A state is a nested dict of arrays: ``params`` holds ``actor``, ``q1``,
``q2``, ``q1_targ``, ``q2_targ`` and ``log_alpha``; ``opt`` holds one Adam
state (``m``, ``v``, ``t``) for ``actor``, ``q1``, ``q2`` and ``alpha``.  A
batch holds ``s``, ``a_cont``, ``a_disc``, ``r``, ``s2``, ``done`` and
``is_w``.  The step draws its noise from the step's key as the search
defines it: the key splits into the target's and the actor's, each of
those into a Gaussian and a categorical stream.

Precision: ``step(..., rnd=...)`` computes in float32 with the operands of
every matrix product rounded by ``rnd``, forward and backward, and
accumulated at full float32 precision, except products with a vector side
(see ``bench/reference/nets.py``); ``dtype=jnp.bfloat16`` computes the
whole step in bfloat16 (the control).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

LR = 3e-4
GAMMA = 0.99
TAU = 0.005
N_CONT = 30
TARGET_ENTROPY = -float(N_CONT)
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
GRAD_CLIP = 10.0
LOG_STD_MIN, LOG_STD_MAX = -20.0, 2.0
N_DISC, N_DISC_OPTIONS = 4, 5
MOE_LB_COEF = 1e-2
HIGHEST = jax.lax.Precision.HIGHEST


def keep(x):
    return x


def to_bf16(x):
    return x.astype(jnp.bfloat16).astype(x.dtype)


def _vector_side(spec: str, a, b) -> bool:
    """Whether a product has a side of one row or column, or contracts
    over one element: XLA computes such a product as a multiply and a sum
    at the arithmetic's own precision."""
    ins, out = spec.split("->")
    la, lb = ins.split(",")
    size = dict(zip(la, a.shape))
    size.update(zip(lb, b.shape))
    free_a = np.prod([size[c] for c in la if c not in lb], dtype=int)
    free_b = np.prod([size[c] for c in lb if c not in la], dtype=int)
    inner = np.prod([size[c] for c in la if c in lb and c not in out],
                    dtype=int)
    return min(free_a, free_b, inner) == 1


def product(rnd: Callable = keep):
    """``einsum`` whose operands are rounded by ``rnd``, in the forward
    product and in both backward ones, and accumulated at full precision;
    a product with a vector side keeps its operands."""
    def one(spec, a, b):
        r = keep if _vector_side(spec, a, b) else rnd
        return jnp.einsum(spec, r(a), r(b), precision=HIGHEST)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def mm(spec, a, b):
        return one(spec, a, b)

    def fwd(spec, a, b):
        return one(spec, a, b), (a, b)

    def bwd(spec, res, g):
        a, b = res
        ins, out = spec.split("->")
        la, lb = ins.split(",")
        return (one(f"{out},{lb}->{la}", g, b).astype(a.dtype),
                one(f"{la},{out}->{lb}", a, g).astype(b.dtype))

    mm.defvjp(fwd, bwd)
    return mm


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def actor_forward(p: Dict, s, mm):
    """MoE actor (Fig. 2, Eq. 54): a softmax gate blends the outputs of K
    expert trunks of two GELU layers and three heads."""
    g = jax.nn.softmax(mm("bs,sk->bk", s, p["gate"]), axis=-1)
    h1 = gelu(mm("bs,kso->bko", s, p["l1"]["w"]) + p["l1"]["b"])
    h2 = gelu(mm("bkh,kho->bko", h1, p["l2"]["w"]) + p["l2"]["b"])

    def head(name):
        out = mm("bkh,kho->bko", h2, p[name]["w"]) + p[name]["b"]
        return mm("bk,bko->bo", g, out)
    disc = head("disc").reshape(s.shape[0], N_DISC, N_DISC_OPTIONS)
    mu = jnp.tanh(head("mu"))
    log_std = jnp.clip(head("log_std"), LOG_STD_MIN, LOG_STD_MAX)
    return disc, mu, log_std, g


def sample(p: Dict, s, key, mm):
    """Tanh-squashed Gaussian action with its log-density (the discrete
    draw uses the second stream and enters no loss)."""
    kc, _ = jax.random.split(key)
    disc, mu, log_std, gate = actor_forward(p, s, mm)
    eps = jax.random.normal(kc, mu.shape).astype(mu.dtype)
    a = jnp.tanh(mu + jnp.exp(log_std) * eps)
    logp = (-0.5 * eps ** 2 - log_std - 0.5 * np.log(2 * np.pi)).sum(-1)
    logp = logp - jnp.log(1 - a ** 2 + 1e-6).sum(-1)
    return a, logp, gate, disc


def critic(p: Dict, s, a, mm):
    x = jnp.concatenate([s, a], axis=-1)
    h = gelu(mm("bi,io->bo", x, p["l1"]["w"]) + p["l1"]["b"])
    h = gelu(mm("bi,io->bo", h, p["l2"]["w"]) + p["l2"]["b"])
    return (mm("bi,io->bo", h, p["out"]["w"]) + p["out"]["b"])[:, 0]


def clip_by_norm(grads, limit: float):
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves)
                    + 1e-12)
    scale = jnp.minimum(1.0, limit / norm)
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)


def adam(params, grads, st: Dict):
    t = st["t"] + 1
    m = jax.tree.map(lambda mu, g: ADAM_B1 * mu + (1 - ADAM_B1) * g,
                     st["m"], grads)
    v = jax.tree.map(lambda nu, g: ADAM_B2 * nu + (1 - ADAM_B2) * g * g,
                     st["v"], grads)
    bc1 = 1.0 - ADAM_B1 ** t.astype(jnp.float32)
    bc2 = 1.0 - ADAM_B2 ** t.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, mu, nu: (p - LR * (mu / bc1) / (jnp.sqrt(nu / bc2)
                                                   + ADAM_EPS)).astype(p.dtype),
        params, m, v)
    return new, dict(m=m, v=v, t=t)


def step(state: Dict, batch: Dict, key, *, rnd: Callable = keep,
         dtype=jnp.float32):
    """One SAC step.  Returns (new state, losses, the clipped gradients
    the optimizers received)."""
    cast = lambda x: (x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
                      else x)
    state = jax.tree.map(cast, state)
    b = {k: cast(v) for k, v in batch.items()}
    mm = product(rnd)
    p = state["params"]
    k1, k2 = jax.random.split(key)
    alpha = jnp.exp(p["log_alpha"])

    a2, logp2, _, _ = sample(p["actor"], b["s2"], k1, mm)
    q_next = jnp.minimum(critic(p["q1_targ"], b["s2"], a2, mm),
                         critic(p["q2_targ"], b["s2"], a2, mm))
    ret = b["r"] + GAMMA * (1.0 - b["done"]) * (q_next - alpha * logp2)
    y = jax.lax.stop_gradient(ret)

    def critic_loss(q):
        td = critic(q, b["s"], b["a_cont"], mm) - y
        return jnp.mean(b["is_w"] * td ** 2)

    l_q1, g1 = jax.value_and_grad(critic_loss)(p["q1"])
    l_q2, g2 = jax.value_and_grad(critic_loss)(p["q2"])
    g1, g2 = clip_by_norm(g1, GRAD_CLIP), clip_by_norm(g2, GRAD_CLIP)
    q1, opt_q1 = adam(p["q1"], g1, state["opt"]["q1"])
    q2, opt_q2 = adam(p["q2"], g2, state["opt"]["q2"])

    def actor_loss(ap):
        a, logp, gate, disc = sample(ap, b["s"], k2, mm)
        q_pi = jnp.minimum(critic(q1, b["s"], a, mm),
                           critic(q2, b["s"], a, mm))
        loss_cont = jnp.mean(alpha * logp - q_pi)
        logsm = jax.nn.log_softmax(disc, -1)
        logp_stored = jnp.take_along_axis(
            logsm, b["a_disc"][..., None], -1)[..., 0].sum(-1)
        v_s = jax.lax.stop_gradient(q_pi - alpha * logp)
        adv = jax.lax.stop_gradient(ret - v_s)
        loss_disc = -jnp.mean(b["is_w"] * logp_stored * adv)
        entropy = -jnp.mean(jnp.sum(jnp.exp(logsm) * logsm, axis=(-2, -1)))
        balance = MOE_LB_COEF * gate.shape[1] * jnp.sum(
            gate.mean(axis=0) ** 2)
        return (loss_cont + 0.5 * loss_disc - 1e-3 * entropy + balance,
                logp)

    (l_actor, logp), ga = jax.value_and_grad(actor_loss, has_aux=True)(
        p["actor"])
    ga = clip_by_norm(ga, GRAD_CLIP)
    actor, opt_a = adam(p["actor"], ga, state["opt"]["actor"])

    def alpha_loss(la):
        return -jnp.mean(jnp.exp(la)
                         * jax.lax.stop_gradient(logp + TARGET_ENTROPY))

    l_alpha, g_alpha = jax.value_and_grad(alpha_loss)(p["log_alpha"])
    g_alpha = jnp.clip(g_alpha, -1.0, 1.0)
    log_alpha, opt_al = adam(p["log_alpha"], g_alpha, state["opt"]["alpha"])
    log_alpha = jnp.clip(log_alpha, -10.0, 10.0)

    polyak = lambda t, s: jax.tree.map(lambda x, y: (1 - TAU) * x + TAU * y,
                                       t, s)
    new = dict(params=dict(actor=actor, q1=q1, q2=q2,
                           q1_targ=polyak(p["q1_targ"], q1),
                           q2_targ=polyak(p["q2_targ"], q2),
                           log_alpha=log_alpha),
               opt=dict(actor=opt_a, q1=opt_q1, q2=opt_q2, alpha=opt_al))
    losses = dict(loss_q1=l_q1, loss_q2=l_q2, loss_actor=l_actor,
                  loss_alpha=l_alpha)
    grads = dict(actor=ga, q1=g1, q2=g2, log_alpha=g_alpha)
    return new, losses, grads


def follow(state: Dict, batches, keys, *, rnd: Callable = keep,
           dtype=jnp.float32, device=None):
    """Steps through ``batches`` and ``keys`` from ``state`` on ``device``
    (the host CPU by default), every array as float64-free numpy in and
    out.  Returns (state after each step, losses of each step, first
    step's gradients)."""
    device = device or jax.devices("cpu")[0]
    fn = jax.jit(functools.partial(step, rnd=rnd, dtype=dtype))
    put = lambda tree: jax.device_put(tree, device)
    to_np = lambda tree: jax.tree.map(
        lambda x: np.asarray(x, np.float64 if jnp.issubdtype(
            x.dtype, jnp.floating) else x.dtype), tree)
    states, losses, grads = [], [], None
    cur = put(state)
    for batch, key in zip(batches, keys):
        cur, loss, g = fn(cur, put(batch), put(key))
        states.append(to_np(cur))
        losses.append(to_np(loss))
        if grads is None:
            grads = to_np(g)
    return states, losses, grads
