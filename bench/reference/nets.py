"""Plain numpy references of the two networks whose compiled programs the
cells drive: the MoE actor (paper Fig. 2, Eq. 54) that the ``actor_moe``
kernel computes, and the serving surrogate that scores recommendation
fallbacks.  ``q`` rounds the result of every operation and ``mq`` the
operands of every matrix product with two sides wider than one.  The
configurations state float32 networks at the platform's default matmul
precision.  On a TPU that rounds the operands of a product to bfloat16 and
accumulates in float32, except where one side is a single row or column:
XLA computes that product as a multiply and a sum in float32 (measured on
the chip: the surrogate's context product of a one-query request).  On the
CPU it keeps float32.  So the reference is ``q = ppa.round_f32`` with
``mq = ppa.round_bf16`` on a TPU and ``ppa.round_f32`` on the CPU; the
control is ``ppa.round_bf16`` for both.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.reference.ppa import exact

LOG_STD_MIN, LOG_STD_MAX = -20.0, 2.0
HEADS = ("disc", "mu", "log_std")


def gelu(x):
    """tanh approximation, as the networks define it."""
    return 0.5 * x * (1.0 + np.tanh(np.sqrt(2.0 / np.pi)
                                    * (x + 0.044715 * x ** 3)))


def softmax(z):
    z = z - z.max(axis=-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(axis=-1, keepdims=True)


def _mm(a, b, q, mq):
    """``a @ b``; operands rounded by ``mq``, or by ``q`` where a side is
    a single row or column."""
    r = q if min(a.shape[-2], b.shape[-1]) == 1 else mq
    return q(r(a) @ r(b))


def actor(params: Dict, s: np.ndarray, q=exact, mq=exact
          ) -> Dict[str, np.ndarray]:
    """MoE actor forward: a softmax gate over K expert trunks of two GELU
    layers, each expert's three heads blended by the gate.  ``params`` are
    arrays keyed as the actor's weights (``gate``, ``l1``, ``l2`` and the
    heads, each dense layer a ``w``/``b`` pair stacked over experts)."""
    p = {k: ({kk: q(vv) for kk, vv in v.items()} if isinstance(v, dict)
             else q(v)) for k, v in params.items()}
    s = q(s)
    g = q(softmax(_mm(s, p["gate"], q, mq)))
    out = {h: 0.0 for h in HEADS}
    for k in range(g.shape[1]):
        h1 = q(gelu(q(_mm(s, p["l1"]["w"][k], q, mq) + p["l1"]["b"][k])))
        h2 = q(gelu(q(_mm(h1, p["l2"]["w"][k], q, mq) + p["l2"]["b"][k])))
        for h in HEADS:
            y = q(_mm(h2, p[h]["w"][k], q, mq) + p[h]["b"][k])
            out[h] = q(out[h] + q(g[:, k:k + 1] * y))
    return dict(disc=out["disc"], mu=q(np.tanh(out["mu"])),
                log_std=np.clip(out["log_std"], LOG_STD_MIN, LOG_STD_MAX),
                gate=g)


def surrogate_log_pred(params: Dict, ctx: np.ndarray, cand: np.ndarray,
                       q=exact, mq=exact) -> np.ndarray:
    """(Q, C, 3) log1p (power, perf, area) of every candidate design
    ``cand`` (C, D) under every query context ``ctx`` (Q, F) of one
    request: the serving surrogate, an MLP over [context || design] with
    two GELU layers, clamped at 0 (targets are log1p of non-negative
    values).  The first layer is the sum of the context's product and the
    design's, so a one-query request has a one-row context product."""
    p = {k: {kk: q(vv) for kk, vv in v.items()} for k, v in params.items()}
    f = ctx.shape[1]
    a = _mm(q(ctx), p["l1"]["w"][:f], q, mq)
    b = _mm(q(cand), p["l1"]["w"][f:], q, mq)
    h = q(gelu(q(q(a[:, None, :] + b[None]) + p["l1"]["b"])))
    h = q(gelu(q(_mm(h, p["l2"]["w"], q, mq) + p["l2"]["b"])))
    return np.maximum(q(_mm(h, p["head"]["w"], q, mq) + p["head"]["b"]),
                      0.0)


def pick(log_pred: np.ndarray, weights: np.ndarray, power_budget,
         min_perf) -> Dict[str, np.ndarray]:
    """The fallback's choice per query: the lowest scalarized log score
    among candidates whose predicted power and perf meet the budgets, or
    among all when none does."""
    w = weights / np.maximum(weights.sum(axis=1, keepdims=True), 1e-9)
    score = (w[:, None, 1] * log_pred[..., 0]
             + w[:, None, 2] * log_pred[..., 2]
             - w[:, None, 0] * log_pred[..., 1])
    ok = ((np.expm1(log_pred[..., 0]) <= np.asarray(power_budget)[:, None])
          & (np.expm1(log_pred[..., 1]) >= np.asarray(min_perf)[:, None]))
    within = ok.any(axis=1)
    masked = np.where(ok, score, np.inf)
    best = np.where(within, masked.min(axis=1), score.min(axis=1))
    return dict(score=score, ok=ok, within=within, best=best)
