"""The hardware-design MDP environment (paper §3.1).

One environment = (workload, process node, optimization mode).  Steps apply
mixed discrete/continuous actions to the design vector, re-partition the
operator graph when the mesh changes (or periodically), evaluate the
analytic PPA model, and emit the Table-2 state + Eq.-34 reward.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import actions as act
from repro.core import reward as rw
from repro.core import state as st
from repro.core.partition import PartitionResult, partition, stats_vec
from repro.core.reward import RewardModel, adaptive_weights
from repro.ppa import config_space as cs
from repro.ppa.analytic import (M_IDX, evaluate_jit, evaluate_vec,
                                evaluate_vec_jit, node_matrix, node_vector)
from repro.ppa.nodes import node_params
from repro.workload.features import Workload


@dataclasses.dataclass
class StepInfo:
    metrics: np.ndarray
    cfg: np.ndarray
    reward_parts: Dict[str, float]
    feasible: bool
    partition_stats: np.ndarray


class DSEEnv:
    """Single-workload, single-node design-space exploration environment."""

    def __init__(self, workload: Workload, node_nm: int, *,
                 high_perf: bool = True, seed: int = 0,
                 partition_period: int = 25,
                 w_perf: Optional[float] = None,
                 w_power: Optional[float] = None,
                 w_area: Optional[float] = None):
        self.workload = workload
        self.node_nm = node_nm
        self.high_perf = high_perf
        self.node = node_params(node_nm, low_power=not high_perf)
        self.node_vec = jnp.asarray(node_vector(self.node, high_perf=high_perf))
        self.wl_vec = jnp.asarray(workload.features)
        self.rng = np.random.default_rng(seed)
        self.partition_period = partition_period
        # PPA weight profiles (paper §5.4): high-perf (.4,.4,.2),
        # low-power (.2,.6,.2)
        if w_perf is None:
            w_perf, w_power, w_area = ((0.4, 0.4, 0.2) if high_perf
                                       else (0.2, 0.6, 0.2))
        self.reward_model = RewardModel(
            power_budget_mw=self.node.power_budget_mw,
            area_budget_mm2=self.node.area_budget_mm2,
            w_perf=w_perf, w_power=w_power, w_area=w_area)
        self.cfg: np.ndarray = cs.default_config()
        self._part: Optional[PartitionResult] = None
        self._part_cache: Dict[tuple, PartitionResult] = {}
        self._steps_since_partition = 10 ** 9
        self._t = 0

    # ------------------------------------------------------------------ api
    def reset(self, jitter: float = 0.15) -> np.ndarray:
        cfg = cs.default_config()
        noise = self.rng.normal(0.0, jitter, cfg.shape).astype(np.float32)
        cfg = cfg + noise * (cs.HI - cs.LO) * 0.1
        self.cfg = np.asarray(cs.project(jnp.asarray(cfg)))
        self._steps_since_partition = 10 ** 9
        self._repartition()
        metrics = self._evaluate(self.cfg)
        self._t = 0
        return self._encode(metrics)

    def step(self, a_cont: np.ndarray, a_disc: np.ndarray
             ) -> Tuple[np.ndarray, float, StepInfo]:
        old_mesh = (self.cfg[cs.IDX["mesh_w"]], self.cfg[cs.IDX["mesh_h"]])
        self.cfg = act.apply_action(self.cfg, a_cont, a_disc)
        new_mesh = (self.cfg[cs.IDX["mesh_w"]], self.cfg[cs.IDX["mesh_h"]])
        self._steps_since_partition += 1
        if (new_mesh != old_mesh
                or self._steps_since_partition >= self.partition_period):
            self._repartition()
        metrics = self._evaluate(self.cfg)
        r, parts = self.reward_model(metrics)
        s2 = self._encode(metrics)
        self._t += 1
        info = StepInfo(metrics=metrics, cfg=self.cfg.copy(),
                        reward_parts=parts,
                        feasible=bool(metrics[M_IDX["feasible"]] > 0.5),
                        partition_stats=self._part_stats())
        return s2, r, info

    def evaluate_config(self, cfg: np.ndarray) -> np.ndarray:
        """Evaluate an arbitrary design vector (search baselines)."""
        return self._evaluate(np.asarray(cs.project(jnp.asarray(cfg))))

    # -------------------------------------------------------------- internals
    def _evaluate(self, cfg: np.ndarray) -> np.ndarray:
        m = evaluate_jit(jnp.asarray(cfg, jnp.float32), self.wl_vec,
                         self.node_vec)
        return np.asarray(m)

    def _repartition(self) -> None:
        # cache keyed by the placement-relevant fields (mesh + ratios + lb
        # weights, coarsely quantised); mesh deltas happen nearly every step
        # and re-running the full placement would dominate episode cost.
        key = (int(self.cfg[cs.IDX["mesh_w"]]), int(self.cfg[cs.IDX["mesh_h"]]),
               round(float(self.cfg[cs.IDX["rho_matmul"]]), 1),
               round(float(self.cfg[cs.IDX["rho_conv"]]), 1),
               round(float(self.cfg[cs.IDX["rho_general"]]), 1),
               round(float(self.cfg[cs.IDX["lb_alpha"]]), 1),
               round(float(self.cfg[cs.IDX["lb_beta"]]), 1))
        hit = self._part_cache.get(key)
        if hit is None:
            hit = partition(self.workload.graph, self.cfg)
            if len(self._part_cache) > 512:
                self._part_cache.pop(next(iter(self._part_cache)))
            self._part_cache[key] = hit
        self._part = hit
        self._steps_since_partition = 0

    def _part_stats(self) -> np.ndarray:
        return (self._part.stats if self._part is not None
                else np.zeros(8, np.float32))

    def _encode(self, metrics: np.ndarray) -> np.ndarray:
        s73 = st.encode(np.asarray(self.wl_vec), self.cfg, metrics,
                        np.asarray(self.node_vec), self._part_stats())
        return st.sac_state(s73)

    @property
    def partition_result(self) -> Optional[PartitionResult]:
        return self._part


# ===========================================================================
# Batched vectorized environment
# ===========================================================================

@dataclasses.dataclass
class VecStepInfo:
    """Batched mirror of :class:`StepInfo` — every field gains a leading
    batch axis; reward_parts becomes a dict of (B,) arrays."""
    metrics: np.ndarray          # (B, M_DIM)
    cfg: np.ndarray              # (B, 30)
    reward_parts: Dict[str, np.ndarray]
    feasible: np.ndarray         # (B,) bool
    partition_stats: np.ndarray  # (B, 8)


def _step_core_fn(cfg, delta_cont, a_disc, wl, node, ranges, weights):
    """The fused device step: action application + projection + analytic PPA
    + Eq.-34 reward over the whole batch in one dispatch.  Node constants are
    traced inputs, so one compiled step serves every process node."""
    new_cfg = act.apply_action_vec(cfg, delta_cont, a_disc)
    metrics = evaluate_vec(new_cfg, wl, node)
    r, new_ranges, parts = rw.reward_step(metrics, ranges, node, weights)
    return new_cfg, metrics, r, new_ranges, parts


def _encode_fn(wl, cfg, metrics, node, part_stats):
    """Batched Table-2 encoding + SAC 52-dim subset gather, one dispatch."""
    return st.sac_state_vec(st.encode_vec(wl, cfg, metrics, node, part_stats))


def _step_analytic_fn(cfg, delta_cont, a_disc, wl, node, ranges, weights):
    """The FULLY fused step (partition_mode="analytic"): action application,
    clamping/projection, analytic partition-stat refresh, analytic PPA and
    Eq.-34 reward + Table-2 encoding — one device dispatch for B env-steps."""
    new_cfg = act.apply_action_vec(cfg, delta_cont, a_disc)
    metrics = evaluate_vec(new_cfg, wl, node)
    r, new_ranges, parts = rw.reward_step(metrics, ranges, node, weights)
    part_stats = stats_vec(new_cfg, wl)
    obs = st.sac_state_vec(st.encode_vec(wl, new_cfg, metrics, node,
                                         part_stats))
    return new_cfg, metrics, r, new_ranges, parts, part_stats, obs


def _reset_eval_analytic_fn(cfg, wl, node):
    """Reset-time evaluation + encoding for the analytic-stats mode."""
    metrics = evaluate_vec(cfg, wl, node)
    part_stats = stats_vec(cfg, wl)
    obs = st.sac_state_vec(st.encode_vec(wl, cfg, metrics, node, part_stats))
    return part_stats, obs


_vec_step_core = jax.jit(_step_core_fn)
_vec_encode = jax.jit(_encode_fn)
_vec_step_analytic = jax.jit(_step_analytic_fn)
_vec_reset_eval_analytic = jax.jit(_reset_eval_analytic_fn)


@functools.lru_cache(maxsize=8)
def _sharded_step_fns(mesh):
    """jit(shard_map(...)) versions of the fused step/reset/encode over a
    1-D batch mesh (``repro.distributed.sharding.batch_mesh``).

    Every fused-step computation is purely element-wise over the batch axis
    (reward running-ranges are per-element (B, 6) rows — see
    ``repro.core.reward.reward_step``), so sharding introduces NO
    collectives and the sharded step is bitwise identical to the unsharded
    one at equal B (test-enforced).  The workload feature vector is the one
    replicated operand.  Cached per mesh so every env on the same mesh
    shares one compiled step.
    """
    from jax.sharding import PartitionSpec as P
    pb = P(mesh.axis_names[0])
    rep = P()

    def sm(fn, in_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=pb))

    step_analytic = sm(_step_analytic_fn,
                       (pb, pb, pb, rep, pb, pb, pb))
    reset_eval = sm(_reset_eval_analytic_fn, (pb, rep, pb))
    step_core = sm(_step_core_fn, (pb, pb, pb, rep, pb, pb, pb))
    encode = sm(_encode_fn, (rep, pb, pb, pb, pb))
    return step_analytic, reset_eval, step_core, encode


# partition-cache key fields (must match DSEEnv._repartition's key)
_PART_KEY_FIELDS = ("mesh_w", "mesh_h", "rho_matmul", "rho_conv",
                    "rho_general", "lb_alpha", "lb_beta")
_PART_KEY_IDX = np.array([cs.IDX[n] for n in _PART_KEY_FIELDS])


class VecDSEEnv:
    """B design-space-exploration environments stepped in lockstep.

    Semantically B independent :class:`DSEEnv` instances with seeds
    ``seed .. seed+B-1`` (the parity tests assert element-wise agreement),
    but the hot path — action application, constraint projection, partition-
    stat refresh, analytic PPA evaluation, Eq.-34 reward and the Table-2
    encoding — runs as ONE jit-compiled vmap dispatch per batch step instead
    of B host-side loops.

    partition_mode:
      * "analytic" (default) — the 8 load-distribution state features come
        from the closed-form ``repro.core.partition.stats_vec`` inside the
        fused step; the host placement algorithm never runs.  PPA metrics,
        reward and feasibility are untouched by this choice (they never
        read partition stats) and stay element-wise identical to the scalar
        env; only those 8 observation dims differ.
      * "exact" — runs the scalar env's host partitioner with per-element
        refresh triggers and caches; the full 73-dim state then matches
        ``DSEEnv`` bitwise (the parity-suite oracle mode), at roughly
        scalar-loop cost per env-step when meshes move every step.

    ``node_nm`` may be a single process node or a length-B sequence: node
    constants enter the compiled step as traced vectors (``node_vector``),
    so mixed-node batches and sequential per-node sweeps reuse the same
    compiled step (see ``repro.core.search.search_all_nodes``).

    ``devices``: shard the batch axis over the first ``devices`` visible
    accelerators via ``shard_map`` (mesh built by
    ``repro.distributed.sharding.batch_mesh``); ``batch`` must divide
    evenly.  The fused step is purely element-wise over the batch, so the
    sharded engine is bitwise identical to the default single-device one at
    equal B — and ``devices=1`` is the degenerate 1-device mesh.  Per-lane
    RNG streams stay folded from the global seed (``seed + lane``), so
    shard layout never perturbs reset noise.  ``devices=None`` (default)
    keeps today's unsharded jit path.
    """

    def __init__(self, workload: Workload, node_nm: Union[int, Sequence[int]],
                 *, batch: int = 64, high_perf: bool = True, seed: int = 0,
                 partition_period: int = 25, partition_mode: str = "analytic",
                 w_perf: Optional[float] = None,
                 w_power: Optional[float] = None,
                 w_area: Optional[float] = None,
                 devices: Optional[int] = None):
        if partition_mode not in ("analytic", "exact"):
            raise ValueError(f"unknown partition_mode {partition_mode!r}")
        self.partition_mode = partition_mode
        if isinstance(node_nm, (int, np.integer)):
            node_nms = [int(node_nm)] * batch
        else:
            node_nms = [int(n) for n in node_nm]
            batch = len(node_nms)
        if batch < 1:
            raise ValueError(f"VecDSEEnv needs batch >= 1, got {batch}")
        self.batch = batch
        self.devices = devices
        self.mesh = None
        if devices is None:
            self._step_analytic = _vec_step_analytic
            self._reset_eval_analytic = _vec_reset_eval_analytic
            self._step_core = _vec_step_core
            self._encode = _vec_encode
        else:
            from repro.distributed.sharding import batch_mesh
            n = int(devices)
            if batch % max(n, 1):
                raise ValueError(
                    f"VecDSEEnv batch ({batch}) must divide evenly over "
                    f"devices ({n})")
            self.mesh = batch_mesh(n)   # raises if n > jax.device_count()
            (self._step_analytic, self._reset_eval_analytic,
             self._step_core, self._encode) = _sharded_step_fns(self.mesh)
        self.workload = workload
        self.node_nms = node_nms
        self.high_perf = high_perf
        self.nodes = [node_params(n, low_power=not high_perf)
                      for n in node_nms]
        self.node_mat = jnp.asarray(node_matrix(self.nodes,
                                                high_perf=high_perf))
        self.wl_vec = jnp.asarray(workload.features)
        self.partition_period = partition_period
        self.rngs = [np.random.default_rng(seed + i) for i in range(batch)]
        if w_perf is None:
            w_perf, w_power, w_area = ((0.4, 0.4, 0.2) if high_perf
                                       else (0.2, 0.6, 0.2))
        self.w_perf, self.w_power, self.w_area = w_perf, w_power, w_area
        self.weights = jnp.broadcast_to(
            jnp.asarray(adaptive_weights(w_perf, w_power, w_area),
                        jnp.float32), (batch, 3))
        self.ranges = rw.init_ranges(self.node_mat)
        self.cfg = jnp.broadcast_to(jnp.asarray(cs.default_config()),
                                    (batch, cs.DIM))
        # host-side partition state (per element, mirrors DSEEnv exactly)
        self._part_caches: List[Dict[tuple, PartitionResult]] = [
            {} for _ in range(batch)]
        self._part_memo: Dict[tuple, PartitionResult] = {}
        self._parts: List[Optional[PartitionResult]] = [None] * batch
        self._part_stats = np.zeros((batch, 8), np.float32)
        self._steps_since = np.full(batch, 10 ** 9, np.int64)
        self._last_mesh = np.zeros((batch, 2), np.float32)
        self._t = 0

    # ------------------------------------------------------------------ api
    def reset(self, jitter: float = 0.15) -> np.ndarray:
        base = cs.default_config()
        cfgs = np.empty((self.batch, cs.DIM), np.float32)
        for i, rng in enumerate(self.rngs):
            noise = rng.normal(0.0, jitter, base.shape).astype(np.float32)
            cfgs[i] = base + noise * (cs.HI - cs.LO) * 0.1
        self.cfg = cs.project(jnp.asarray(cfgs))
        self._t = 0
        if self.partition_mode == "analytic":
            stats, obs = self._reset_eval_analytic(self.cfg, self.wl_vec,
                                                   self.node_mat)
            self._part_stats = np.asarray(stats)
            return np.asarray(obs)
        cfg_np = np.asarray(self.cfg)
        self._steps_since[:] = 10 ** 9
        self._refresh_partitions(cfg_np, np.ones(self.batch, bool))
        self._last_mesh = cfg_np[:, _PART_KEY_IDX[:2]].copy()
        metrics = evaluate_vec_jit(self.cfg, self.wl_vec, self.node_mat)
        obs = self._encode(self.wl_vec, self.cfg, metrics, self.node_mat,
                           jnp.asarray(self._part_stats))
        return np.asarray(obs)

    def step(self, a_cont: np.ndarray, a_disc: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray, VecStepInfo]:
        """a_cont: (B, 30) in [-1,1]; a_disc: (B, 4) int in [0,5)."""
        delta = jnp.asarray(act.cont_delta(np.asarray(a_cont)))
        a_d = jnp.asarray(a_disc, jnp.int32)
        if self.partition_mode == "analytic":
            (new_cfg, metrics, r, new_ranges, parts, stats,
             obs) = self._step_analytic(self.cfg, delta, a_d, self.wl_vec,
                                        self.node_mat, self.ranges,
                                        self.weights)
            self.cfg = new_cfg
            self.ranges = new_ranges
            self._part_stats = np.asarray(stats)
            self._t += 1
            metrics_np = np.asarray(metrics)
            info = VecStepInfo(
                metrics=metrics_np, cfg=np.asarray(new_cfg),
                reward_parts={k: np.asarray(v) for k, v in parts.items()},
                feasible=metrics_np[:, M_IDX["feasible"]] > 0.5,
                partition_stats=self._part_stats.copy())
            return np.asarray(obs), np.asarray(r), info
        new_cfg, metrics, r, new_ranges, parts = self._step_core(
            self.cfg, delta, a_d, self.wl_vec, self.node_mat,
            self.ranges, self.weights)
        cfg_np = np.asarray(new_cfg)
        mesh = cfg_np[:, _PART_KEY_IDX[:2]]
        mesh_changed = np.any(mesh != self._last_mesh, axis=1)
        self._steps_since += 1
        need = mesh_changed | (self._steps_since >= self.partition_period)
        self._refresh_partitions(cfg_np, need)
        self._last_mesh = mesh.copy()
        self.cfg = new_cfg
        self.ranges = new_ranges
        obs = self._encode(self.wl_vec, new_cfg, metrics, self.node_mat,
                           jnp.asarray(self._part_stats))
        self._t += 1
        metrics_np = np.asarray(metrics)
        info = VecStepInfo(
            metrics=metrics_np, cfg=cfg_np.copy(),
            reward_parts={k: np.asarray(v) for k, v in parts.items()},
            feasible=metrics_np[:, M_IDX["feasible"]] > 0.5,
            partition_stats=self._part_stats.copy())
        return np.asarray(obs), np.asarray(r), info

    def evaluate_configs(self, cfgs: np.ndarray) -> np.ndarray:
        """Evaluate (N, 30) arbitrary design vectors in one dispatch.

        N == batch pairs cfgs with per-element nodes; any other N evaluates
        every cfg on element 0's node (single-node envs only)."""
        proj = cs.project(jnp.asarray(cfgs, jnp.float32))
        if proj.ndim == 1:
            proj = proj[None]
        if proj.shape[0] == self.batch:
            return np.asarray(evaluate_vec_jit(proj, self.wl_vec,
                                               self.node_mat))
        if len(set(self.node_nms)) > 1:
            raise ValueError("cfg batch size must match env batch for "
                             "mixed-node VecDSEEnv")
        from repro.ppa.analytic import evaluate_batch
        return np.asarray(evaluate_batch(proj, self.wl_vec,
                                         self.node_mat[0]))

    # -------------------------------------------------------------- internals
    def _refresh_partitions(self, cfg_np: np.ndarray,
                            need: np.ndarray) -> None:
        for i in np.nonzero(need)[0]:
            row = cfg_np[i]
            key = (int(row[cs.IDX["mesh_w"]]), int(row[cs.IDX["mesh_h"]]),
                   round(float(row[cs.IDX["rho_matmul"]]), 1),
                   round(float(row[cs.IDX["rho_conv"]]), 1),
                   round(float(row[cs.IDX["rho_general"]]), 1),
                   round(float(row[cs.IDX["lb_alpha"]]), 1),
                   round(float(row[cs.IDX["lb_beta"]]), 1))
            cache = self._part_caches[i]
            hit = cache.get(key)
            if hit is None:
                # share the actual placement compute across elements whose
                # partition-relevant fields coincide exactly (deterministic)
                memo_key = tuple(row[_PART_KEY_IDX].tolist())
                hit = self._part_memo.get(memo_key)
                if hit is None:
                    hit = partition(self.workload.graph, row)
                    if len(self._part_memo) > 4096:
                        self._part_memo.pop(next(iter(self._part_memo)))
                    self._part_memo[memo_key] = hit
                if len(cache) > 512:
                    cache.pop(next(iter(cache)))
                cache[key] = hit
            self._parts[i] = hit
            self._part_stats[i] = hit.stats
            self._steps_since[i] = 0

    @property
    def partition_results(self) -> List[Optional[PartitionResult]]:
        return self._parts
