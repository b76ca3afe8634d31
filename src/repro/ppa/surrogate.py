"""Learned PPA surrogate with node-dependent heads (paper §3.15, Eq. 61-67).

A small MLP maps (state, action/config, node-constants) -> (power, perf,
area) estimates.  Trained online from evaluated transitions (Eq. 65), with
the uncertainty gate of Eq. 66-67: predictions are *accepted* (used in place
of a full evaluation, e.g. inside MPC rollouts) only when the running
residual variance is below tau_sur.

Pure JAX; the train step is jit'd and the predict path is vmap-able so the
MPC planner can score K*H candidates in one fused call.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ppa.analytic import M_IDX, NODE_DIM

SUR_HIDDEN = (128, 64)
N_TARGETS = 3  # power, perf, area  (Eq. 61)
TARGET_NAMES = ("power_mw", "perf_gops", "area_mm2")
# log1p-scaled targets; weights w_q of Eq. 65
TARGET_WEIGHTS = np.array([1.0, 1.0, 1.0], np.float32)
TAU_SUR_DEFAULT = 0.05


def init_params(rng: jax.Array, in_dim: int,
                hidden: Tuple[int, int] = SUR_HIDDEN) -> Dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    h1, h2 = hidden

    def dense(key, n_in, n_out):
        return dict(w=jax.random.normal(key, (n_in, n_out)) * jnp.sqrt(2.0 / n_in),
                    b=jnp.zeros((n_out,)))

    return dict(l1=dense(k1, in_dim, h1), l2=dense(k2, h1, h2),
                head=dense(k3, h2, N_TARGETS))


def predict(params: Dict, x: jnp.ndarray) -> jnp.ndarray:
    """x: [..., in_dim] -> [..., 3] log1p-space (power, perf, area)."""
    h = jax.nn.gelu(x @ params["l1"]["w"] + params["l1"]["b"])
    h = jax.nn.gelu(h @ params["l2"]["w"] + params["l2"]["b"])
    return h @ params["head"]["w"] + params["head"]["b"]


def targets_from_metrics(metrics: jnp.ndarray) -> jnp.ndarray:
    """Extract (power, perf, area) in log1p space from a metrics batch."""
    cols = jnp.stack([metrics[..., M_IDX[n]] for n in TARGET_NAMES], axis=-1)
    return jnp.log1p(jnp.maximum(cols, 0.0))


def loss_fn(params: Dict, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    pred = predict(params, x)
    return jnp.mean(jnp.sum(TARGET_WEIGHTS * (pred - y) ** 2, axis=-1))  # Eq. 65


@jax.jit
def train_step(params: Dict, opt_state: Dict, x: jnp.ndarray, y: jnp.ndarray,
               lr: float = 1.5e-4) -> Tuple[Dict, Dict, jnp.ndarray]:
    """One Adam step on the surrogate loss (half the critic LR, §3.16)."""
    loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
    m = jax.tree.map(lambda mu, g: 0.9 * mu + 0.1 * g, opt_state["m"], grads)
    v = jax.tree.map(lambda nu, g: 0.999 * nu + 0.001 * g * g, opt_state["v"], grads)
    t = opt_state["t"] + 1
    mhat = jax.tree.map(lambda mu: mu / (1 - 0.9 ** t), m)
    vhat = jax.tree.map(lambda nu: nu / (1 - 0.999 ** t), v)
    params = jax.tree.map(lambda p, mu, nu: p - lr * mu / (jnp.sqrt(nu) + 1e-8),
                          params, mhat, vhat)
    return params, dict(m=m, v=v, t=t), loss


def init_opt(params: Dict) -> Dict:
    z = jax.tree.map(jnp.zeros_like, params)
    return dict(m=z, v=jax.tree.map(jnp.zeros_like, params), t=jnp.zeros(()))


@dataclasses.dataclass
class Surrogate:
    """Stateful convenience wrapper with the Eq. 66-67 uncertainty gate."""
    params: Dict
    opt_state: Dict
    tau_sur: float = TAU_SUR_DEFAULT
    resid_var: float = float("inf")   # sigma_psi^2, running (Eq. 66)
    n_updates: int = 0

    @classmethod
    def create(cls, in_dim: int, seed: int = 0,
               tau_sur: float = TAU_SUR_DEFAULT,
               hidden: Tuple[int, int] = SUR_HIDDEN) -> "Surrogate":
        p = init_params(jax.random.PRNGKey(seed), in_dim, hidden=hidden)
        return cls(params=p, opt_state=init_opt(p), tau_sur=tau_sur)

    def update(self, x: np.ndarray, metrics: np.ndarray) -> float:
        y = targets_from_metrics(jnp.asarray(metrics))
        self.params, self.opt_state, loss = train_step(
            self.params, self.opt_state, jnp.asarray(x), y)
        loss = float(loss)
        # running residual variance (Eq. 66), EMA over batches.  Mirrors
        # ScreenGate.observe's non-finite guard: a NaN/inf batch loss (a
        # diverged step, or an inf analytic metric on a degenerate design)
        # is skipped rather than folded in — folding it would poison the
        # EMA permanently and `accepted` could never open.  The isfinite
        # first-update check also covers a NaN-seeded resid_var, which the
        # old `== inf` comparison silently missed.
        var = loss / N_TARGETS
        if np.isfinite(var):
            self.resid_var = var if not np.isfinite(self.resid_var) else (
                0.95 * self.resid_var + 0.05 * var)
        self.n_updates += 1
        return loss

    @property
    def accepted(self) -> bool:
        """Eq. 67: 1[sigma^2 < tau_sur]."""
        return self.resid_var < self.tau_sur

    def __call__(self, x: np.ndarray) -> np.ndarray:
        """Predict (power_mw, perf_gops, area_mm2) in linear space."""
        return np.asarray(jnp.expm1(predict(self.params, jnp.asarray(x))))


def surrogate_reward(pred_log: jnp.ndarray) -> jnp.ndarray:
    """r_sur = P_perf - 0.3 P_pwr - 0.2 P_area (paper §3.16 MPC reward),
    on log1p-scaled heads for stability."""
    return pred_log[..., 1] - 0.3 * pred_log[..., 0] - 0.2 * pred_log[..., 2]


# ---------------------------------------------------------------------------
# Surrogate-gated candidate screening (campaign search path)
# ---------------------------------------------------------------------------

@jax.jit
def screen_batch(params: Dict, s: jnp.ndarray, cand: jnp.ndarray,
                 weights: jnp.ndarray, open_mask: jnp.ndarray) -> jnp.ndarray:
    """Score K candidate actions per env and pick the surrogate-best.

    s: (B, S) states; cand: (B, K, N_CONT) candidate continuous actions
    (candidate 0 is the action the ungated path would take); weights: (B, 3)
    normalized (w_perf, w_power, w_area); open_mask: (B,) bool per-env gate.

    The score is the surrogate's scalarized PPA proxy in log1p space
    (lower = better, mirroring ppa_score's direction):
    w_power * log1p(power) + w_area * log1p(area) - w_perf * log1p(perf).
    Where the gate is closed the base candidate (index 0) is returned, so a
    closed gate is exactly the ungated action stream.
    """
    bsz, k = cand.shape[0], cand.shape[1]
    x = jnp.concatenate(
        [jnp.broadcast_to(s[:, None, :], (bsz, k, s.shape[-1])), cand],
        axis=-1)
    pred = predict(params, x)                                   # (B, K, 3)
    score = (weights[:, None, 1] * pred[..., 0]
             + weights[:, None, 2] * pred[..., 2]
             - weights[:, None, 0] * pred[..., 1])
    return jnp.where(open_mask, jnp.argmin(score, axis=1), 0)


@jax.jit
def calib_errors(params: Dict, x: jnp.ndarray,
                 metrics: jnp.ndarray) -> jnp.ndarray:
    """Per-sample surrogate residual (Eq. 66 numerator) on evaluated points.

    x: (B, in_dim) [state||action]; metrics: (B, M_DIM) analytic outcomes.
    Returns (B,) mean-squared error over the 3 log1p targets — the online
    calibration signal the per-cell Eq.-67 gate integrates.
    """
    pred = predict(params, x)
    y = targets_from_metrics(metrics)
    return jnp.mean((pred - y) ** 2, axis=-1)


# ---------------------------------------------------------------------------
# Pareto-as-a-service: fused query-batch scoring over an archive index
# ---------------------------------------------------------------------------

SERVE_HIDDEN = (32, 16)  # serving-sized net: the index surrogate
# interpolates dozens-to-hundreds of archive points, and at query time
# its layer-2 GEMM runs Q x C times inside score_query_batch — the
# online search surrogate's (128, 64) would dominate the fused dispatch
# for no accuracy gain at index scale


def fit_index_surrogate(x: np.ndarray, y_log: np.ndarray, *,
                        steps: int = 400, seed: int = 0,
                        minibatch: int = 4096,
                        hidden: Tuple[int, int] = SERVE_HIDDEN) -> Surrogate:
    """Fit a fresh surrogate to an archive index's (context, PPA) pairs.

    ``x``: (N, in_dim) serving contexts (log1p-scaled workload features ||
    node constants || design vector); ``y_log``: (N, 3) log1p-space
    (power, perf, area) — the objectives the archive measured for those
    designs.  Reuses the online :func:`train_step` (one jit, ``steps``
    dispatches at index-build time, zero at query time); datasets larger
    than ``minibatch`` are subsampled per step with a seed-deterministic
    stream so two builds of the same index fit identical surrogates.
    """
    x = np.asarray(x, np.float32)
    y = np.asarray(y_log, np.float32)
    if x.ndim != 2 or y.shape != (x.shape[0], N_TARGETS):
        raise ValueError(f"fit_index_surrogate: bad shapes {x.shape} / "
                         f"{y.shape}")
    sur = Surrogate.create(x.shape[1], seed=seed, hidden=hidden)
    rng = np.random.default_rng(seed)
    xd, yd = jnp.asarray(x), jnp.asarray(y)
    for _ in range(steps):
        if x.shape[0] > minibatch:
            pick = rng.integers(0, x.shape[0], size=minibatch)
            xb, yb = jnp.asarray(x[pick]), jnp.asarray(y[pick])
        else:
            xb, yb = xd, yd
        sur.params, sur.opt_state, _ = train_step(
            sur.params, sur.opt_state, xb, yb)
        sur.n_updates += 1
    # the reported calibration must cover the FULL dataset, not whichever
    # minibatch happened to come last — serve/transfer compare resid_var
    # across index builds, and a subsampled tail makes that comparison
    # noise.  Same per-sample residual as calib_errors, but on the already
    # log1p-scaled targets.
    sur.resid_var = float(jnp.mean(_calib_errors_log(sur.params, xd, yd)))
    return sur


@jax.jit
def _calib_errors_log(params: Dict, x: jnp.ndarray,
                      y_log: jnp.ndarray) -> jnp.ndarray:
    """:func:`calib_errors` for targets already in log1p space — the
    index/transfer datasets store (context, log1p PPA) pairs directly."""
    return jnp.mean((predict(params, x) - y_log) ** 2, axis=-1)


@jax.jit
def score_query_batch(params: Dict, q: jnp.ndarray, cand: jnp.ndarray,
                      weights: jnp.ndarray, power_budget: jnp.ndarray,
                      min_perf: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Score every index candidate for every query in ONE fused dispatch.

    The serving-side sibling of :func:`screen_batch`: where screen_batch
    scores K candidate *actions* per environment inside the search loop,
    this scores the archive index's C candidate *designs* for Q concurrent
    recommendation queries — Q x C surrogate evaluations ride one jit call,
    so thousands of queries cost one dispatch.

    q: (Q, F) per-query context (log1p workload features || node consts);
    cand: (C, D) log1p candidate design vectors; weights: (Q, 3) normalized
    (w_perf, w_power, w_area); power_budget: (Q,) mW cap (inf = none);
    min_perf: (Q,) GOPS floor (0 = none).

    Score is the scalarized log1p PPA proxy of screen_batch (lower =
    better); candidates whose *predicted* power/perf violate the query's
    budget are masked to +inf, falling back to the unmasked argmin when a
    budget excludes every candidate (best-effort answer, flagged by the
    returned ``within_budget``).  Returns (best_idx (Q,), pred (Q, 3)
    linear-space (power, perf, area) of the winner, within_budget (Q,)).
    """
    # layer 1 split along the input: gelu([q||cand] @ W1) decomposes as
    # gelu(q @ W1[:F] + cand @ W1[F:]) — the (Q, C, F+D) concat is never
    # materialized and the big (Q*C, F+D, H1) contraction collapses to two
    # small GEMMs + a broadcast add, ~2.5x off the dispatch (predict()'s
    # summation grouping differs, so predictions can drift by float eps
    # from a concat-then-predict; the surrogate path is an estimate, only
    # archive answers are bitwise)
    w1, b1 = params["l1"]["w"], params["l1"]["b"]
    f = q.shape[-1]
    h = jax.nn.gelu((q @ w1[:f])[:, None, :]
                    + (cand @ w1[f:])[None, :, :] + b1)         # (Q, C, H1)
    h = jax.nn.gelu(h @ params["l2"]["w"] + params["l2"]["b"])
    pred = h @ params["head"]["w"] + params["head"]["b"]        # (Q, C, 3)
    # targets are log1p(max(v, 0)) >= 0 by construction — clamp so an
    # underfit head can't serve negative power/perf/area through expm1
    pred = jnp.maximum(pred, 0.0)
    score = (weights[:, None, 1] * pred[..., 0]
             + weights[:, None, 2] * pred[..., 2]
             - weights[:, None, 0] * pred[..., 1])
    ok = ((jnp.expm1(pred[..., 0]) <= power_budget[:, None])
          & (jnp.expm1(pred[..., 1]) >= min_perf[:, None]))
    within = ok.any(axis=1)
    idx = jnp.where(within,
                    jnp.argmin(jnp.where(ok, score, jnp.inf), axis=1),
                    jnp.argmin(score, axis=1))
    sel = jnp.take_along_axis(pred, idx[:, None, None], axis=1)[:, 0]
    return idx, jnp.expm1(sel), within


@dataclasses.dataclass
class ScreenGate:
    """Per-cell Eq.-66/67 gate state for surrogate-gated screening.

    Tracks one running residual variance per search cell (EMA of the
    surrogate's calibration error on that cell's analytically evaluated
    points).  A cell's gate *opens* — and stays open — the first time its
    residual variance drops below ``tau`` (Eq. 67, per cell); from then on
    candidate actions for that cell are screened through the surrogate and
    only the survivor pays a full analytic evaluation.

    ``screened`` counts candidates considered (K per env-step once open,
    1 before), ``evaluated`` counts full analytic evaluations; their ratio
    is the "effective episodes per analytic evaluation" multiplier that
    ``benchmarks/bench_gated_campaign`` regresses on.
    """
    tau: float
    resid_var: np.ndarray      # (n_cells,) EMA residual variance, init inf
    open_at: np.ndarray        # (n_cells,) env-step the gate opened; -1 closed
    screened: np.ndarray       # (n_cells,) candidates scored
    evaluated: np.ndarray      # (n_cells,) full analytic evaluations
    ema: float = 0.95          # same EMA horizon as Surrogate.update

    @classmethod
    def create(cls, n_cells: int, tau: float = TAU_SUR_DEFAULT
               ) -> "ScreenGate":
        return cls(tau=float(tau),
                   resid_var=np.full(n_cells, np.inf, np.float64),
                   open_at=np.full(n_cells, -1, np.int64),
                   screened=np.zeros(n_cells, np.int64),
                   evaluated=np.zeros(n_cells, np.int64))

    @property
    def open(self) -> np.ndarray:
        """(n_cells,) bool — which cells' gates are open."""
        return self.open_at >= 0

    def observe(self, err_per_cell: np.ndarray, t_env: int) -> None:
        """Fold one dispatch's per-cell calibration error into the EMA and
        open any cell whose variance just passed below tau (Eq. 67).

        Non-finite errors (a NaN/inf loss from a diverged surrogate batch,
        or an inf analytic metric on a degenerate design) are skipped for
        that cell: folding them in would poison the EMA permanently — a
        NaN seed never compares below tau, so the gate could never open,
        and an inf seed NaN-propagates through the EMA.  The cell keeps
        its previous variance (inf until the first finite error) and its
        gate stays closed, which is the safe direction: closed means every
        candidate still pays the exact analytic evaluation."""
        err = np.asarray(err_per_cell, np.float64)
        finite = np.isfinite(err)
        first = ~np.isfinite(self.resid_var)
        upd = np.where(first, err,
                       self.ema * self.resid_var + (1.0 - self.ema) * err)
        self.resid_var = np.where(finite, upd, self.resid_var)
        newly = (~self.open) & (self.resid_var < self.tau)
        self.open_at[newly] = t_env

    def count(self, lanes: int, k: int) -> None:
        """Account one dispatch: every env pays one analytic evaluation;
        open cells screened k candidates per lane, closed cells one."""
        self.evaluated += lanes
        self.screened += np.where(self.open, lanes * k, lanes)

    # ------------------------------------------------- checkpoint (de)serde
    def to_dict(self) -> Dict:
        return dict(tau=self.tau, ema=self.ema,
                    resid_var=[float(v) for v in self.resid_var],
                    open_at=self.open_at.tolist(),
                    screened=self.screened.tolist(),
                    evaluated=self.evaluated.tolist())

    @classmethod
    def from_dict(cls, d: Dict) -> "ScreenGate":
        return cls(tau=float(d["tau"]), ema=float(d["ema"]),
                   resid_var=np.array([float(v) for v in d["resid_var"]],
                                      np.float64),
                   open_at=np.asarray(d["open_at"], np.int64),
                   screened=np.asarray(d["screened"], np.int64),
                   evaluated=np.asarray(d["evaluated"], np.int64))
