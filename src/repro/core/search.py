"""Algorithm 1: Unified RL-based hardware-aware compilation loop.

Per process node: epsilon-greedy SAC with PER, online world-model training,
MPC refinement during exploitation (eps < 0.15), Pareto archiving of every
feasible configuration, and post-convergence scalarized selection.  Also
implements the random-search and grid-search baselines of Table 21.
"""
from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import manager as ckpt_mod
from repro.core import actions as act
from repro.core import mpc as mpc_mod
from repro.core import sac as sac_mod
from repro.core import world_model as wm_mod
from repro.core.env import DSEEnv, VecDSEEnv
from repro.core.exploration import EpsilonSchedule
from repro.core.hetero import HeteroConfig, derive
from repro.core.pareto import (ArchiveEntry, ParetoArchive, pack_archives,
                               unpack_archives)
from repro.core.partition import partition
from repro.core.replay import PERBuffer
from repro.core.state import SAC_STATE_DIM
from repro.kernels import ops as kernel_ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.ppa import config_space as cs
from repro.ppa import surrogate as sur_mod
from repro.ppa.analytic import (INFEASIBLE_REASONS, M_DIM, M_IDX,
                                evaluate_batch, evaluate_vec_jit,
                                infeasible_counts)
from repro.workload.features import Workload


@dataclasses.dataclass
class SearchConfig:
    episodes: int = 4613          # paper Table 14 per-node budget
    warmup: int = 1000            # SAC warmup (Table 6)
    batch_size: int = 256
    eps0: float = 0.5
    eps_min: float = 0.1
    mpc_eps_gate: float = 0.15    # MPC active when eps < 0.15 (§3.16)
    reset_period: int = 500
    seed: int = 0
    early_stop_patience: int = 1500   # "Bayesian early stopping" proxy
    update_every: int = 1
    wm_batch: int = 256
    surrogate_every: int = 8
    verbose: bool = False
    # vectorized engine (run_search): SAC updates per batched env dispatch.
    # The scalar loop updates once per env-step; one dispatch advances
    # n_envs env-steps, so this trades update density for env throughput.
    updates_per_dispatch: int = 4
    # surrogate-gated screening (vectorized engine only): once a cell's
    # calibrated surrogate residual variance passes the Eq.-67 gate, every
    # env proposes screen_k candidate actions per step, the shared surrogate
    # scores them in the fused step, and only the top-1 survivor pays the
    # full analytic evaluation.  Before the gate opens (and with
    # surrogate_gate=False) the path is bitwise identical to the ungated
    # engine.
    surrogate_gate: bool = True
    screen_k: int = 4
    gate_threshold: float = sur_mod.TAU_SUR_DEFAULT


@dataclasses.dataclass
class TracePoint:
    episode: int
    reward: float
    best_score: float
    eps: float
    entropy: float
    unique_configs: int
    feasible_count: int
    tok_s: float

    @classmethod
    def from_dict(cls, d: Dict) -> "TracePoint":
        """Inverse of ``dataclasses.asdict`` after a JSON round trip, in
        which a non-finite float came back as text ("inf")."""
        return cls(**{f.name: (float if f.type == "float" else int)(
            d[f.name]) for f in dataclasses.fields(cls)})


@dataclasses.dataclass
class SearchResult:
    method: str
    node_nm: int
    best_cfg: Optional[np.ndarray]
    best_metrics: Optional[np.ndarray]
    best_score: float
    archive: ParetoArchive
    trace: List[TracePoint]
    hetero: Optional[HeteroConfig]
    episodes_run: int
    feasible_count: int
    unique_configs: int
    wall_s: float
    # surrogate-gate accounting (vectorized engine; see SearchConfig):
    # env-step at which this cell's Eq.-67 gate opened (None = never),
    # candidates screened and full analytic evaluations spent.
    gate_open_episode: Optional[int] = None
    screened: int = 0
    evaluated: int = 0
    # SLO-aware scenario selection (set only when run_search_cells got a
    # ``scenario``): prefill-phase TTFT of the chosen design and whether it
    # met both SLO targets.
    ttft_ms: Optional[float] = None
    slo_ok: Optional[bool] = None

    def metric(self, name: str) -> float:
        if self.best_metrics is None:
            return float("nan")
        return float(self.best_metrics[M_IDX[name]])


def _cfg_key(cfg: np.ndarray) -> tuple:
    return tuple(np.round(np.asarray(cfg, np.float64), 3).tolist())


def _update_best(best, metrics, cfg, archive, episode):
    """paper line 15: if PPA < s* and feasible -> keep."""
    score = float(metrics[M_IDX["ppa_score"]])
    feas = metrics[M_IDX["feasible"]] > 0.5
    if feas:
        archive.insert(ArchiveEntry(
            cfg=cfg.copy(), power_mw=float(metrics[M_IDX["power_mw"]]),
            perf_gops=float(metrics[M_IDX["perf_gops"]]),
            area_mm2=float(metrics[M_IDX["area_mm2"]]),
            tok_s=float(metrics[M_IDX["tok_s"]]),
            ppa_score=score, episode=episode))
        if score < best[0]:
            return (score, cfg.copy(), metrics.copy()), True
    return best, feas


def run_sac(workload: Workload, node_nm: int, *, high_perf: bool = True,
            search: Optional[SearchConfig] = None) -> SearchResult:
    """The paper's production flow: SAC + MoE + PER + world model + MPC."""
    sc = search or SearchConfig()
    t0 = time.time()
    env = DSEEnv(workload, node_nm, high_perf=high_perf, seed=sc.seed)
    rng = np.random.default_rng(sc.seed)
    key = jax.random.PRNGKey(sc.seed)

    sac_state = sac_mod.create(sc.seed)
    wm_state = wm_mod.create(sc.seed + 1)
    surrogate = sur_mod.Surrogate.create(SAC_STATE_DIM + act.N_CONT,
                                         seed=sc.seed + 2)
    buf = PERBuffer(SAC_STATE_DIM, act.N_CONT, act.N_DISC, seed=sc.seed)
    eps_sched = EpsilonSchedule(sc.eps0, sc.eps_min, sc.episodes)
    archive = ParetoArchive()
    trace: List[TracePoint] = []
    seen: set = set()
    best = (np.inf, None, None)
    feasible_count = 0
    last_entropy = 0.0
    no_improve = 0

    sur_x: List[np.ndarray] = []
    sur_y: List[np.ndarray] = []

    s = env.reset()
    for t in range(sc.episodes):
        key, k_act, k_upd, k_mpc = jax.random.split(key, 4)
        # ---- action selection: eps-greedy over SAC policy (Alg. 1 l.6) ----
        if rng.random() < eps_sched.eps:
            a_c, a_d = act.random_action(rng)
        else:
            a_c, a_d = sac_mod.policy_act(sac_state.params.actor,
                                          jnp.asarray(s), k_act)
            a_c, a_d = np.asarray(a_c), np.asarray(a_d)
            # MPC refinement during exploitation (Alg. 1 l.14)
            if (eps_sched.eps < sc.mpc_eps_gate and surrogate.accepted
                    and wm_mod.trained(wm_state)):
                a_mpc = mpc_mod.plan(sac_state.params.actor, wm_state.params,
                                     surrogate.params, jnp.asarray(s), k_mpc)
                a_c = np.asarray(mpc_mod.refine(jnp.asarray(a_c), a_mpc))
        # ---- env transition (Alg. 1 l.7-10) -------------------------------
        s2, r, info = env.step(a_c, a_d)
        buf.add(s, a_c, a_d, r, s2, 0.0)
        sur_x.append(np.concatenate([s, a_c]).astype(np.float32))
        sur_y.append(info.metrics.astype(np.float32))
        prev_best_score = best[0]
        best, feas = _update_best(best, info.metrics, info.cfg, archive, t)
        feasible_count += int(feas)
        seen.add(_cfg_key(info.cfg))
        no_improve = 0 if best[0] < prev_best_score else no_improve + 1
        # ---- learn (Alg. 1 l.12-13) ---------------------------------------
        if buf.size >= max(sc.batch_size, min(sc.warmup, sc.episodes // 4)) \
                and t % sc.update_every == 0:
            batch_np, idx = buf.sample(sc.batch_size)
            batch = sac_mod.Batch(**{k: jnp.asarray(v)
                                     for k, v in batch_np.items()})
            sac_state, td_abs, met = sac_mod.update(sac_state, batch, k_upd)
            buf.update_priorities(idx, np.asarray(td_abs))
            last_entropy = float(met["entropy"])
            wmb = buf.recent(sc.wm_batch)
            wm_state, _ = wm_mod.train_step(
                wm_state, jnp.asarray(wmb["s"]), jnp.asarray(wmb["a_cont"]),
                jnp.asarray(wmb["s2"]))
            if t % sc.surrogate_every == 0 and len(sur_x) >= 64:
                pick = rng.integers(0, len(sur_x), size=min(256, len(sur_x)))
                surrogate.update(np.stack([sur_x[i] for i in pick]),
                                 np.stack([sur_y[i] for i in pick]))
                if len(sur_x) > 20_000:   # bound host memory
                    sur_x = sur_x[-10_000:]
                    sur_y = sur_y[-10_000:]
        # ---- epsilon decay (Eq. 9) ----------------------------------------
        eps_sched.step(found_feasible=feasible_count > 0)
        if t % 50 == 0 or t == sc.episodes - 1:
            trace.append(TracePoint(
                episode=t, reward=r, best_score=float(best[0]),
                eps=eps_sched.eps, entropy=last_entropy,
                unique_configs=len(seen), feasible_count=feasible_count,
                tok_s=float(info.metrics[M_IDX["tok_s"]])))
            if sc.verbose:
                print(f"  ep {t:5d} r={r:+.3f} best={best[0]:.4f} "
                      f"eps={eps_sched.eps:.3f} feas={feasible_count}")
        if t % sc.reset_period == sc.reset_period - 1:
            s = env.reset()
        else:
            s = s2
        if (no_improve > sc.early_stop_patience
                and eps_sched.eps <= sc.eps_min + 1e-6):
            break

    # ---- final selection: Pareto-scalarized (paper §3.10) ----------------
    sel = archive.select(env.reward_model.w_perf, env.reward_model.w_power,
                         env.reward_model.w_area)
    best_cfg = sel.cfg if sel is not None else best[1]
    best_metrics = (env.evaluate_config(best_cfg)
                    if best_cfg is not None else None)
    hetero = None
    if best_cfg is not None:
        env.cfg = best_cfg.copy()
        env._repartition()
        hetero = derive(best_cfg, env.partition_result,
                        weight_bytes_total=workload.f("weight_mb") * 1e6)
    return SearchResult(
        method="sac", node_nm=node_nm, best_cfg=best_cfg,
        best_metrics=best_metrics,
        best_score=(float(best_metrics[M_IDX["ppa_score"]])
                    if best_metrics is not None else float("inf")),
        archive=archive, trace=trace, hetero=hetero, episodes_run=t + 1,
        feasible_count=feasible_count, unique_configs=len(seen),
        wall_s=time.time() - t0, screened=t + 1, evaluated=t + 1)


# --------------------------------------------------------------------------
# Vectorized engine: B environments per device dispatch (VecDSEEnv)
# --------------------------------------------------------------------------

# the phases of one dispatch of the batched loop, each timed into
# ``search_phase_seconds{phase=...}``: the first four make up
# ``dispatch_seconds``, the telemetry feed follows it
PHASES = ("act", "env_step", "archive", "learn", "telemetry")

_plan_batch = jax.jit(jax.vmap(mpc_mod.plan,
                               in_axes=(None, None, None, 0, 0)))


def _restore_np_rng(state: Dict) -> np.random.Generator:
    g = np.random.default_rng()
    g.bit_generator.state = state
    return g


def _unflatten_from(flat: Dict[str, np.ndarray], prefix: str, template):
    """Rebuild a device pytree from a ``restore_flat`` dict by leaf name."""
    leaves, treedef = jax.tree_util.tree_flatten(template)
    names = ckpt_mod.leaf_names(template)
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(flat[f"{prefix}/{n}"]) for n in names])


def _save_search_ckpt(ckpt_dir: str, step: int, tree: Dict, extra: Dict,
                      *, keep: int = 2) -> str:
    """Checkpoint hook: atomic save of the full search loop state.

    Module-level so the kill/resume tests can wrap it; the campaign runner
    points ``checkpoint_dir`` at its per-batch directory."""
    return ckpt_mod.save(tree, ckpt_dir, step, keep=keep, extra=extra)


# what a search checkpoint writes of the PER buffer, one row a transition
PER_FIELDS = ("s", "a_cont", "a_disc", "r", "s2", "done")
# bucket edges of ``search_checkpoint_bytes`` (arrays.npz + manifest.json)
CKPT_BYTES_EDGES = (1e5, 1e6, 3e6, 1e7, 3e7, 1e8, 3e8, 1e9)


class SeenKeys:
    """The distinct designs each cell has seen, as :func:`_cfg_key` values.

    Per cell a set answers membership, and the same keys are kept as
    float64 rows in first-seen order, so a checkpoint writes them as one
    array and no key is converted again."""

    def __init__(self, n_cells: int):
        self.sets: List[set] = [set() for _ in range(n_cells)]
        self._rows: List[List[np.ndarray]] = [[] for _ in range(n_cells)]

    def add(self, cfg: np.ndarray, lanes: int) -> None:
        """Record a dispatch's designs, ``lanes`` consecutive rows a cell."""
        keys = np.round(np.asarray(cfg, np.float64), 3)
        for c, s in enumerate(self.sets):
            rows = keys[c * lanes:(c + 1) * lanes]
            new = []
            for i, k in enumerate(map(tuple, rows.tolist())):
                if k not in s:
                    s.add(k)
                    new.append(i)
            if new:
                self._rows[c].append(rows[new])

    def rows(self, c: int) -> np.ndarray:
        """Cell ``c``'s keys in first-seen order, ``(n, DIM)`` float64."""
        if len(self._rows[c]) != 1:
            self._rows[c] = [np.concatenate(self._rows[c]) if self._rows[c]
                             else np.zeros((0, cs.DIM))]
        return self._rows[c][0]

    def to_arrays(self) -> tuple:
        """Every cell's keys, cell after cell, and each row's cell."""
        rows = [self.rows(c) for c in range(len(self.sets))]
        return (np.concatenate(rows),
                np.repeat(np.arange(len(rows), dtype=np.int64),
                          [len(r) for r in rows]))

    @classmethod
    def from_arrays(cls, keys: np.ndarray, cell: np.ndarray,
                    n_cells: int) -> "SeenKeys":
        out = cls(n_cells)
        for c in range(n_cells):
            rows = np.asarray(keys[cell == c], np.float64)
            out.sets[c] = set(map(tuple, rows.tolist()))
            out._rows[c] = [rows]
        return out


def _host_checkpoint(buf: PERBuffer, archives: List[ParetoArchive],
                     seen: SeenKeys, traces: List[List[TracePoint]],
                     best: List[tuple]) -> tuple:
    """The search's record as a checkpoint writes it: ``(arrays,
    manifest entries)``.

    Sized to what the loop holds: the PER rows up to the buffer's fill
    (the rows past it were never written, also once the ring wraps) with
    the whole sum tree, the Pareto archives as columns
    (:func:`pack_archives`), each cell's seen keys, incumbent and trace."""
    n_cells = len(archives)
    arrays = {f"per_{n}": getattr(buf, n)[:buf.size] for n in PER_FIELDS}
    arrays["per_tree"] = buf.tree.tree
    cols, meta = pack_archives(archives, cs.DIM)
    arrays.update({f"archive_{k}": v for k, v in cols.items()})
    arrays["seen_keys"], arrays["seen_cell"] = seen.to_arrays()
    arrays["best_cfg"] = np.stack([
        best[c][1] if best[c][1] is not None
        else np.zeros(cs.DIM, np.float32) for c in range(n_cells)])
    arrays["best_metrics"] = np.stack([
        best[c][2] if best[c][2] is not None
        else np.zeros(M_DIM, np.float32) for c in range(n_cells)])
    extra = dict(
        archive_meta=meta,
        traces=[[dataclasses.asdict(tp) for tp in tr] for tr in traces],
        best_has=[best[c][1] is not None for c in range(n_cells)],
        best_score=[float(best[c][0]) for c in range(n_cells)])
    return arrays, extra


def _host_restore(flat: Dict[str, np.ndarray], ex: Dict,
                  buf: PERBuffer) -> tuple:
    """Inverse of :func:`_host_checkpoint` from a ``restore_flat`` dict
    and the manifest's ``extra``: fills ``buf``'s rows and sum tree and
    returns ``(archives, seen, traces, best)``.  Also reads the earlier
    layout, with every PER row up to capacity and the archives as JSON
    in the manifest."""
    n_cells = len(ex["best_has"])
    for n in PER_FIELDS:
        arr = flat[f"host/per_{n}"]
        getattr(buf, n)[:len(arr)] = arr
    buf.tree.tree[...] = flat["host/per_tree"]
    if "archives" in ex:
        archives = [ParetoArchive.from_dict(d) for d in ex["archives"]]
    else:
        pre = "host/archive_"
        archives = unpack_archives(
            {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)},
            ex["archive_meta"])
    seen = SeenKeys.from_arrays(flat["host/seen_keys"],
                                flat["host/seen_cell"], n_cells)
    traces = [[TracePoint.from_dict(tp) for tp in tr] for tr in ex["traces"]]
    best: List[tuple] = [(np.inf, None, None) for _ in range(n_cells)]
    for c in range(n_cells):
        if ex["best_has"][c]:
            best[c] = (float(ex["best_score"][c]),
                       flat["host/best_cfg"][c].copy(),
                       flat["host/best_metrics"][c].copy())
    return archives, seen, traces, best


def run_search_cells(workload: Workload, node_nms: Sequence[int], *,
                     high_perf: bool = True,
                     search: Optional[SearchConfig] = None,
                     lanes_per_cell: int = 64,
                     checkpoint_dir: Optional[str] = None,
                     checkpoint_every: int = 0,
                     resume: bool = False,
                     devices: Optional[int] = None,
                     warm_start: Optional[Dict] = None,
                     save_weights_to: Optional[str] = None,
                     scenario: Optional[Dict] = None
                     ) -> List[SearchResult]:
    """Algorithm 1 on the batched engine over a mixed-node *cell batch*.

    Each entry of ``node_nms`` is one search cell; every cell gets
    ``lanes_per_cell`` parallel environments, so one fused jit dispatch
    advances ``len(node_nms) * lanes_per_cell`` env-steps.  Node constants
    are traced vectors inside the compiled step (``VecDSEEnv``), so
    heterogeneous cells share ONE compiled step AND one SAC policy / PER
    buffer / world model — the paper's "one RL loop adapts across nodes"
    claim, operationalised: per dispatch the learner pays one update block
    regardless of cell count, which is where the campaign engine's
    cells/hour advantage over sequential single-cell runs comes from.

    Per-cell state (Pareto archive, incumbent, trace, feasible/unique
    counters) is tracked separately and one :class:`SearchResult` is
    returned per cell, in ``node_nms`` order.  ``sc.episodes`` is the
    PER-CELL env-step budget.

    Surrogate-gated screening (``sc.surrogate_gate``, on by default): the
    shared surrogate's residual variance is calibrated online PER CELL
    (Eq. 66); once a cell passes the Eq.-67 gate (``sc.gate_threshold``),
    each of its envs proposes ``sc.screen_k`` candidate actions per step,
    the surrogate scores them inside one fused call, and only the top-1
    survivor pays the full analytic evaluation — multiplying explored
    candidates per analytic evaluation by up to K.  Candidate 0 is always
    the exact action the ungated path would take and the extra-candidate
    streams are dedicated RNGs, so before any gate opens (or with
    ``surrogate_gate=False``) results are bitwise identical to the ungated
    engine (test-enforced).  Per-cell ``gate_open_episode`` and
    screened/evaluated counters are reported on each ``SearchResult``.

    Checkpoint/restore: with ``checkpoint_dir`` set and ``checkpoint_every
    > 0``, the complete loop state — SAC/world-model/surrogate parameters
    and optimizers, PER buffer (its filled rows) + sum-tree priorities,
    per-cell Pareto archives (as arrays) and incumbents, epsilon
    schedule, Eq.-67 gate state (per-cell residual variance, open
    episodes, screened/evaluated counters) and every host/device RNG
    (including the dedicated screen streams) — is atomically checkpointed
    every ``checkpoint_every`` dispatches.
    ``resume=True`` restarts from the latest checkpoint and is exact: a
    killed-and-resumed run reproduces the uninterrupted run bit-for-bit
    (test-enforced).

    ``devices``: shard the B = cells x lanes batch axis of the fused env
    step over a ``batch_mesh(devices)`` device mesh (``shard_map``; see
    :class:`VecDSEEnv`).  The step is element-wise over the batch, so a
    sharded search is bitwise identical to the single-device run at equal
    B — ``devices`` only buys wall-clock, which is why checkpoints and
    campaign fingerprints carry no device count and a checkpoint written
    at one mesh size resumes exactly at another.

    ``warm_start`` (cross-campaign transfer; see
    ``repro.campaign.transfer``): seeds the fresh loop state before the
    first dispatch — ``warm_start["flat"]`` holds donor SAC/surrogate
    parameter leaves (keys ``sac/<leaf>`` / ``sur_params/<leaf>``, the
    layout :func:`repro.checkpoint.manager.restore_flat` returns for a
    weights snapshot), and ``warm_start["cells"][c]`` optionally carries
    ``entries`` (ArchiveEntry seeds, re-evaluated for THIS cell) and
    ``best`` (an ``(score, cfg, metrics)`` incumbent).  Applied ONLY on a
    fresh start: a checkpoint resume restores the already-warmed state,
    so kill/resume of a warm-started run stays bit-exact for free.

    ``save_weights_to``: after the final dispatch, snapshot the final
    SAC + surrogate parameters there (atomic, ``keep=1``) so a later
    campaign can warm-start from this batch.

    ``scenario`` (SLO-aware phase combination): a dict with ``aux_wl``
    (the prefill-phase :class:`Workload` paired with the decode search
    workload), ``slo`` (resolved ``{"ttft_ms", "tok_s"}`` targets),
    ``seq_len`` and ``batch``.  Final selection then minimises
    ``reward.slo_objective`` over the Pareto archive — TTFT from the
    prefill evaluation, tokens/s from decode — instead of the plain
    scalarisation, and the returned results carry ``ttft_ms``/``slo_ok``.
    Strictly post-loop: ``scenario=None`` is byte-identical to the
    pre-scenario engine.
    """
    with obs_trace.phase("run_search_cells"):
        return _search_cells(
            workload, node_nms, high_perf=high_perf, search=search,
            lanes_per_cell=lanes_per_cell, checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, resume=resume,
            devices=devices, warm_start=warm_start,
            save_weights_to=save_weights_to, scenario=scenario)


def _search_cells(workload, node_nms, *, high_perf, search, lanes_per_cell,
                  checkpoint_dir, checkpoint_every, resume, devices,
                  warm_start, save_weights_to, scenario):
    """:func:`run_search_cells`, inside its profiler annotation."""
    sc = search or SearchConfig()
    n_cells = len(node_nms)
    if n_cells < 1:
        raise ValueError("run_search_cells needs >= 1 cell")
    lanes = lanes_per_cell
    b = n_cells * lanes
    t0 = time.time()
    env = VecDSEEnv(workload, np.repeat(node_nms, lanes).tolist(),
                    high_perf=high_perf, seed=sc.seed, devices=devices)
    # Pallas hot-path kernels on a TPU backend: actor sampling + surrogate
    # K-candidate screening run through repro.kernels; the CPU runs the jnp
    # reference.
    on_tpu = kernel_ops.kernels_enabled()
    _policy_act = (kernel_ops.policy_act_batch if on_tpu
                   else sac_mod.policy_act_batch)
    _screen = kernel_ops.screen_batch if on_tpu else sur_mod.screen_batch
    rng = np.random.default_rng(sc.seed)
    key = jax.random.PRNGKey(sc.seed)

    sac_state = sac_mod.create(sc.seed)
    wm_state = wm_mod.create(sc.seed + 1)
    surrogate = sur_mod.Surrogate.create(SAC_STATE_DIM + act.N_CONT,
                                         seed=sc.seed + 2)
    buf = PERBuffer(SAC_STATE_DIM, act.N_CONT, act.N_DISC, seed=sc.seed)
    eps_sched = EpsilonSchedule(sc.eps0, sc.eps_min, sc.episodes)
    # Surrogate-gated screening state.  The extra-candidate streams are
    # DEDICATED rngs/keys (never the main ones): the base action stream must
    # stay aligned with the ungated path, so a run whose gates never open is
    # bitwise identical to surrogate_gate=False (test-enforced).
    gate = sur_mod.ScreenGate.create(n_cells, sc.gate_threshold)
    gate_on = bool(sc.surrogate_gate) and sc.screen_k > 1
    screen_rng = np.random.default_rng(sc.seed + 7919)
    screen_key = jax.random.PRNGKey(sc.seed + 7919)
    archives = [ParetoArchive() for _ in range(n_cells)]
    traces: List[List[TracePoint]] = [[] for _ in range(n_cells)]
    seen = SeenKeys(n_cells)
    best: List[tuple] = [(np.inf, None, None) for _ in range(n_cells)]
    feasible_count = np.zeros(n_cells, np.int64)
    last_entropy = 0.0
    no_improve = 0
    # surrogate minibatch source: only the last 4 dispatches are ever read
    sur_x: deque = deque(maxlen=4)
    sur_y: deque = deque(maxlen=4)

    n_steps = max(1, sc.episodes // lanes)
    reset_every = max(1, sc.reset_period)
    trace_every = max(1, 50 // lanes)
    start_t = 0
    t_env = 0            # per-cell env-steps completed
    resumed = False

    if resume and checkpoint_dir and ckpt_mod.latest_step(checkpoint_dir):
        flat, manifest = ckpt_mod.restore_flat(checkpoint_dir)
        ex = manifest["extra"]
        if (list(ex["node_nms"]) != [int(n) for n in node_nms]
                or ex["lanes"] != lanes or ex["episodes"] != sc.episodes
                or bool(ex["high_perf"]) != bool(high_perf)
                or int(ex["seed"]) != sc.seed):
            raise ValueError(
                f"checkpoint in {checkpoint_dir} was written for cells "
                f"{ex['node_nms']} x{ex['lanes']} lanes @{ex['episodes']} ep "
                f"(high_perf={ex['high_perf']}, seed={ex['seed']}); got "
                f"{list(node_nms)} x{lanes} @{sc.episodes} "
                f"(high_perf={high_perf}, seed={sc.seed})")
        gc = ex.get("gate_cfg")
        if gc is not None and (
                bool(gc["surrogate_gate"]) != bool(sc.surrogate_gate)
                or int(gc["screen_k"]) != sc.screen_k
                or float(gc["gate_threshold"]) != sc.gate_threshold):
            raise ValueError(
                f"checkpoint in {checkpoint_dir} was written with gate "
                f"settings {gc}; got surrogate_gate={sc.surrogate_gate}, "
                f"screen_k={sc.screen_k}, gate_threshold="
                f"{sc.gate_threshold} — resuming with different gate "
                "settings would break bit-exact resume")
        sac_state = _unflatten_from(flat, "device/sac", sac_state)
        wm_state = _unflatten_from(flat, "device/wm", wm_state)
        surrogate.params = _unflatten_from(flat, "device/sur_params",
                                           surrogate.params)
        surrogate.opt_state = _unflatten_from(flat, "device/sur_opt",
                                              surrogate.opt_state)
        surrogate.resid_var = float(ex["sur_resid_var"])
        surrogate.n_updates = int(ex["sur_n_updates"])
        key = jnp.asarray(flat["device/key"])
        archives, seen, traces, best = _host_restore(flat, ex, buf)
        buf.pos, buf.size = int(ex["buf_pos"]), int(ex["buf_size"])
        buf.max_priority = float(ex["buf_max_priority"])
        buf.beta = float(ex["buf_beta"])
        buf.rng = _restore_np_rng(ex["buf_rng"])
        rng = _restore_np_rng(ex["rng"])
        env.rngs = [_restore_np_rng(st) for st in ex["env_rngs"]]
        env.cfg = jnp.asarray(flat["host/env_cfg"])
        env.ranges = jnp.asarray(flat["host/env_ranges"])
        s = flat["host/obs"]
        for k in range(int(ex["sur_len"])):
            sur_x.append(flat["host/sur_x"][k])
            sur_y.append(flat["host/sur_y"][k])
        feasible_count = np.asarray(ex["feasible_count"], np.int64)
        no_improve = int(ex["no_improve"])
        last_entropy = float(ex["last_entropy"])
        eps_sched.eps = float(ex["eps"])
        if "gate" in ex:
            gate = sur_mod.ScreenGate.from_dict(ex["gate"])
            screen_rng = _restore_np_rng(ex["screen_rng"])
            screen_key = jnp.asarray(flat["device/screen_key"])
        else:
            # legacy (pre-gate) checkpoint: the original run was ungated,
            # and ungated == gated-with-closed-gates bitwise — finish the
            # run ungated so resume stays bit-exact with that run
            gate_on = False
        start_t = int(manifest["step"])
        t_env = start_t * lanes
        resumed = True
    if not resumed:
        if warm_start is not None:
            ws_flat = warm_start.get("flat")
            if ws_flat:
                sac_state = _unflatten_from(ws_flat, "sac", sac_state)
                surrogate.params = _unflatten_from(ws_flat, "sur_params",
                                                   surrogate.params)
            for c, seed_cell in enumerate(warm_start.get("cells") or []):
                if c >= n_cells or not seed_cell:
                    continue
                archives[c].insert_batch(list(seed_cell.get("entries")
                                              or []))
                sb = seed_cell.get("best")
                if sb is not None:
                    best[c] = (float(sb[0]),
                               np.asarray(sb[1], np.float32).copy(),
                               np.asarray(sb[2], np.float32).copy())
        s = env.reset()      # (B, 52)

    # ---- telemetry: read-only taps on the loop's own state ---------------
    # Handles hoisted out of the hot loop (one lock+dict hit at creation,
    # attribute access per dispatch).  Everything below only READS clocks
    # and counters the loop already maintains — never RNG streams or
    # checkpoint contents — so results are bitwise identical with
    # telemetry on or off (test-enforced).
    _reg = obs_metrics.global_registry()
    _m_steps = _reg.counter("env_steps_total")
    _m_screened = _reg.counter("screened_total")
    _m_evaluated = _reg.counter("evaluated_total")
    _m_sps = _reg.gauge("env_steps_per_s")
    _m_gate = _reg.gauge("gate_open_frac")
    _m_eps = _reg.gauge("search_eps")
    _m_best = _reg.gauge("best_score")
    _m_offered = _reg.counter("pareto_offered_total")
    _m_kept = _reg.counter("pareto_kept_total")
    _m_front = _reg.gauge("pareto_frontier_max")
    _m_disp = _reg.histogram("dispatch_seconds")
    _m_infeasible = {r: _reg.counter("env_infeasible_total",
                                     labels={"reason": r})
                     for r in INFEASIBLE_REASONS}
    # act, env_step, archive and learn cover a dispatch_seconds interval;
    # telemetry follows it, inside the dispatch's annotation
    _m_phase = {p: _reg.histogram("search_phase_seconds",
                                  labels={"phase": p})
                for p in PHASES}
    obs_trace.watch_gc()
    # screened/evaluated are cumulative in the gate (and survive resume):
    # counters track the delta per dispatch so fleet aggregation sums
    _prev_scr = float(gate.screened.sum())
    _prev_ev = float(gate.evaluated.sum())
    _prev_off = sum(a.n_offered for a in archives)
    _m_ckpt_bytes = _reg.histogram("search_checkpoint_bytes",
                                   CKPT_BYTES_EDGES)

    def _checkpoint(t_next: int) -> None:
        with obs_trace.phase("checkpoint.gather"):
            tree, extra = _checkpoint_state()
        final = _save_search_ckpt(checkpoint_dir, t_next, tree, extra)
        _m_ckpt_bytes.observe(sum(
            os.path.getsize(os.path.join(final, f))
            for f in ("arrays.npz", "manifest.json")))

    def _checkpoint_state():
        xdim = SAC_STATE_DIM + act.N_CONT
        host, host_extra = _host_checkpoint(buf, archives, seen, traces,
                                            best)
        tree = dict(
            device=dict(sac=sac_state, wm=wm_state,
                        sur_params=surrogate.params,
                        sur_opt=surrogate.opt_state, key=np.asarray(key),
                        screen_key=np.asarray(screen_key)),
            host=dict(
                host,
                env_cfg=np.asarray(env.cfg), env_ranges=np.asarray(env.ranges),
                obs=np.asarray(s),
                sur_x=(np.stack(list(sur_x)) if sur_x
                       else np.zeros((0, b, xdim), np.float32)),
                sur_y=(np.stack(list(sur_y)) if sur_y
                       else np.zeros((0, b, 1), np.float32))))
        extra = dict(
            node_nms=[int(n) for n in node_nms], lanes=lanes,
            episodes=sc.episodes, high_perf=high_perf, seed=sc.seed,
            eps=eps_sched.eps, rng=rng.bit_generator.state,
            buf_rng=buf.rng.bit_generator.state,
            env_rngs=[g.bit_generator.state for g in env.rngs],
            buf_pos=buf.pos, buf_size=buf.size,
            buf_max_priority=buf.max_priority, buf_beta=buf.beta,
            sur_resid_var=surrogate.resid_var,
            sur_n_updates=surrogate.n_updates, sur_len=len(sur_x),
            feasible_count=feasible_count.tolist(), no_improve=no_improve,
            last_entropy=last_entropy, gate=gate.to_dict(),
            gate_cfg=dict(surrogate_gate=bool(sc.surrogate_gate),
                          screen_k=sc.screen_k,
                          gate_threshold=sc.gate_threshold),
            screen_rng=screen_rng.bit_generator.state, **host_extra)
        return tree, extra

    for t in range(start_t, n_steps):
        # the first dispatch pays jit compilation: its annotation carries
        # the name of its JSONL span
        with obs_trace.step("first_dispatch" if t == start_t
                            else "dispatch", t):
            _dt0, _pc0 = time.time(), time.perf_counter()
            with obs_trace.phase("act", _m_phase["act"]):
                key, k_act, k_upd, k_mpc = jax.random.split(key, 4)
                # ---- action selection: per-element eps-greedy (l.6) ----
                a_c_rand, a_d_rand = act.random_action_batch(rng, b)
                a_c_pol, a_d_pol = _policy_act(
                    sac_state.params.actor, jnp.asarray(s), k_act)
                a_c_pol, a_d_pol = np.asarray(a_c_pol), np.asarray(a_d_pol)
                if (eps_sched.eps < sc.mpc_eps_gate and surrogate.accepted
                        and wm_mod.trained(wm_state)):
                    a_mpc = np.asarray(_plan_batch(
                        sac_state.params.actor, wm_state.params,
                        surrogate.params, jnp.asarray(s),
                        jax.random.split(k_mpc, b)))
                    blend = (mpc_mod.BLEND_MPC * a_mpc
                             + (1.0 - mpc_mod.BLEND_MPC) * a_c_pol)
                    a_c_pol[:, :mpc_mod.TCC_ACTION_DIMS] = \
                        blend[:, :mpc_mod.TCC_ACTION_DIMS]
                explore = rng.random(b) < eps_sched.eps
                a_c = np.where(explore[:, None], a_c_rand,
                               a_c_pol).astype(np.float32)
                a_d = np.where(explore[:, None], a_d_rand,
                               a_d_pol).astype(np.int32)
                # ---- surrogate-gated screening (Eq. 67): K candidates
                # per env, surrogate scores them in one fused call, the
                # top-1 survivor gets the analytic evaluation.  Candidate
                # 0 is the exact ungated action; extra candidates draw
                # from the dedicated screen streams, so cells whose gate
                # is closed keep the ungated action stream untouched.
                if gate_on and gate.open.any():
                    kk = sc.screen_k
                    cand_c = np.empty((b, kk, act.N_CONT), np.float32)
                    cand_d = np.empty((b, kk, act.N_DISC), np.int32)
                    cand_c[:, 0], cand_d[:, 0] = a_c, a_d
                    screen_key, k_scr = jax.random.split(screen_key)
                    p_c, p_d = _policy_act(
                        sac_state.params.actor,
                        jnp.asarray(np.repeat(s, kk - 1, axis=0)), k_scr)
                    r_c, r_d = act.random_action_batch(screen_rng,
                                                       b * (kk - 1))
                    expl = screen_rng.random(b * (kk - 1)) < eps_sched.eps
                    cand_c[:, 1:] = np.where(
                        expl[:, None], r_c,
                        np.asarray(p_c)).reshape(b, kk - 1, -1)
                    cand_d[:, 1:] = np.where(
                        expl[:, None], r_d,
                        np.asarray(p_d)).reshape(b, kk - 1, -1)
                    pick = np.asarray(_screen(
                        surrogate.params, jnp.asarray(s),
                        jnp.asarray(cand_c), env.weights,
                        jnp.asarray(np.repeat(gate.open, lanes))))
                    a_c = cand_c[np.arange(b), pick]
                    a_d = cand_d[np.arange(b), pick]
            # ---- env transition: one fused dispatch for B env-steps ----
            with obs_trace.phase("env_step", _m_phase["env_step"]):
                s2, r, info = env.step(a_c, a_d)
            with obs_trace.phase("archive", _m_phase["archive"]):
                buf.add_batch(s, a_c, a_d, r, s2, np.zeros(b, np.float32))
                sur_x.append(np.concatenate([s, a_c],
                                            axis=1).astype(np.float32))
                sur_y.append(info.metrics.astype(np.float32))
                # ---- per-cell best tracking + batched Pareto insert ----
                improved = False
                kept = 0
                scores = info.metrics[:, M_IDX["ppa_score"]]
                for c in range(n_cells):
                    lo, hi = c * lanes, (c + 1) * lanes
                    feas_idx = lo + np.nonzero(info.feasible[lo:hi])[0]
                    kept += archives[c].insert_batch([
                        ArchiveEntry.from_metrics(
                            info.cfg[i], info.metrics[i],
                            episode=t_env + int(i) - lo)
                        for i in feas_idx])
                    if feas_idx.size:
                        j = int(feas_idx[np.argmin(scores[feas_idx])])
                        if float(scores[j]) < best[c][0]:
                            best[c] = (float(scores[j]), info.cfg[j].copy(),
                                       info.metrics[j].copy())
                            improved = True
                    feasible_count[c] += int(info.feasible[lo:hi].sum())
                seen.add(info.cfg, lanes)
                t_env += lanes
                no_improve = 0 if improved else no_improve + lanes
                # ---- gate accounting + online calibration (Eq. 66) ----
                if gate_on:
                    gate.count(lanes, sc.screen_k)
                    # calibration only matters while some gate can still
                    # open (the gate is monotone): skip the dead work once
                    # all are open
                    if surrogate.n_updates > 0 and not gate.open.all():
                        errs = np.asarray(sur_mod.calib_errors(
                            surrogate.params, jnp.asarray(sur_x[-1]),
                            jnp.asarray(info.metrics)))
                        gate.observe(errs.reshape(n_cells,
                                                  lanes).mean(axis=1),
                                     t_env)
                else:
                    gate.count(lanes, 1)
            # ---- learn (Alg. 1 l.12-13) ----------------------------------
            with obs_trace.phase("learn", _m_phase["learn"]):
                if buf.size >= max(sc.batch_size,
                                   min(sc.warmup, sc.episodes // 4)):
                    for _ in range(sc.updates_per_dispatch):
                        with obs_trace.phase("learn.sample"):
                            batch_np, idx = buf.sample(sc.batch_size)
                            batch = sac_mod.Batch(**{
                                k: jnp.asarray(v)
                                for k, v in batch_np.items()})
                        with obs_trace.phase("learn.update"):
                            key, k_upd = jax.random.split(key)
                            sac_state, td_abs, met = sac_mod.update(
                                sac_state, batch, k_upd)
                        with obs_trace.phase("learn.priorities"):
                            buf.update_priorities(idx, np.asarray(td_abs))
                            last_entropy = float(met["entropy"])
                    with obs_trace.phase("learn.wm"):
                        wmb = buf.recent(sc.wm_batch)
                        wm_state, _ = wm_mod.train_step(
                            wm_state, jnp.asarray(wmb["s"]),
                            jnp.asarray(wmb["a_cont"]),
                            jnp.asarray(wmb["s2"]))
                    if (t % max(1, sc.surrogate_every // lanes) == 0
                            and len(sur_x)):
                        with obs_trace.phase("learn.surrogate"):
                            xs = np.concatenate(list(sur_x), axis=0)
                            ys = np.concatenate(list(sur_y), axis=0)
                            pick = rng.integers(0, len(xs),
                                                size=min(256, len(xs)))
                            surrogate.update(xs[pick], ys[pick])
            _td = time.perf_counter() - _pc0
            # ---- telemetry feed: clocks + loop counters only -------------
            with obs_trace.phase("telemetry", _m_phase["telemetry"]):
                _m_disp.observe(_td)
                _m_steps.inc(b)
                _m_sps.set(b / _td if _td > 0 else 0.0)
                _m_gate.set(float(np.mean(gate.open)))
                _m_eps.set(eps_sched.eps)
                _bb = min(best[c][0] for c in range(n_cells))
                if np.isfinite(_bb):
                    _m_best.set(float(_bb))
                _scr = float(gate.screened.sum())
                _ev = float(gate.evaluated.sum())
                _m_screened.inc(_scr - _prev_scr)
                _m_evaluated.inc(_ev - _prev_ev)
                _prev_scr, _prev_ev = _scr, _ev
                _off = sum(a.n_offered for a in archives)
                _m_offered.inc(_off - _prev_off)
                _prev_off = _off
                _m_kept.inc(kept)
                _m_front.set(max(len(a) for a in archives))
                for _r, _n in infeasible_counts(info.metrics).items():
                    _m_infeasible[_r].inc(_n)
                if t == start_t:
                    obs_trace.complete("first_dispatch", _dt0, _td,
                                       cat="search", cells=n_cells,
                                       lanes=lanes)
                # ---- epsilon decay: one per per-cell env-step (Eq. 9) ----
                found = bool(feasible_count.sum() > 0)
                for _ in range(lanes):
                    eps_sched.step(found_feasible=found)
                if t % trace_every == 0 or t == n_steps - 1:
                    for c in range(n_cells):
                        lo, hi = c * lanes, (c + 1) * lanes
                        traces[c].append(TracePoint(
                            episode=t_env, reward=float(np.mean(r[lo:hi])),
                            best_score=float(best[c][0]), eps=eps_sched.eps,
                            entropy=last_entropy,
                            unique_configs=len(seen.sets[c]),
                            feasible_count=int(feasible_count[c]),
                            tok_s=float(np.mean(
                                info.metrics[lo:hi, M_IDX["tok_s"]]))))
                    obs_trace.counter(
                        "search",
                        env_steps_s=(b / _td if _td > 0 else 0.0),
                        eps=eps_sched.eps,
                        gate_open_frac=float(np.mean(gate.open)),
                        feasible=float(feasible_count.sum()))
                    if sc.verbose:
                        bb = min(float(best[c][0]) for c in range(n_cells))
                        print(f"  step {t:5d} (ep {t_env}) "
                              f"r={float(np.mean(r)):+.3f} best={bb:.4f} "
                              f"eps={eps_sched.eps:.3f} "
                              f"feas={int(feasible_count.sum())}")
        if t % reset_every == reset_every - 1:
            s = env.reset()
        else:
            s = s2
        if (no_improve > sc.early_stop_patience
                and eps_sched.eps <= sc.eps_min + 1e-6):
            break
        # checkpoint only live continuations (after the early-stop check:
        # a resumed run must never execute dispatches the original skipped)
        if checkpoint_dir and checkpoint_every > 0 \
                and (t + 1) % checkpoint_every == 0 and t + 1 < n_steps:
            with obs_trace.span("checkpoint", cat="search", step=t + 1):
                _checkpoint(t + 1)

    if save_weights_to:
        # final-weights snapshot for cross-campaign warm-starts; plain
        # ckpt_mod.save (NOT _save_search_ckpt — that hook is the
        # kill/resume tests' checkpoint counter) and derived purely from
        # loop state, so a resumed finish re-writes identical bytes
        ckpt_mod.save(dict(sac=sac_state, sur_params=surrogate.params),
                      save_weights_to, max(1, t_env), keep=1,
                      extra=dict(kind="batch_weights",
                                 node_nms=[int(n) for n in node_nms],
                                 seed=sc.seed, high_perf=bool(high_perf)))

    # ---- final selection per cell: Pareto-scalarized (paper §3.10) -------
    results = []
    wall = time.time() - t0
    obs_trace.complete("run_search_cells", t0, wall, cat="search",
                       cells=n_cells, lanes=lanes, episodes=sc.episodes,
                       env_steps=t_env * n_cells)
    for c, node_nm in enumerate(node_nms):
        sel = archives[c].select(env.w_perf, env.w_power, env.w_area)
        best_cfg = sel.cfg if sel is not None else best[c][1]
        ttft = slo_ok = None
        # SLO-aware scenario selection: re-evaluate the cell's Pareto
        # archive under the paired prefill workload and pick the entry
        # minimising the combined objective (decode ppa_score + SLO hinge
        # penalties, repro.core.reward.slo_objective).  Runs strictly after
        # the search loop, so checkpoints and the scenario=None path are
        # untouched.
        if scenario is not None and archives[c].entries:
            from repro.core import reward as rwd
            ents = archives[c].entries
            pre = np.asarray(evaluate_batch(
                cs.project(jnp.asarray(np.stack([e.cfg for e in ents]),
                                       jnp.float32)),
                jnp.asarray(scenario["aux_wl"].features),
                env.node_mat[c * lanes]))
            slo = scenario["slo"]
            ttfts = [rwd.ttft_ms(pre[i, M_IDX["tok_s"]],
                                 scenario["seq_len"], scenario["batch"])
                     for i in range(len(ents))]
            objs = [rwd.slo_objective(e.ppa_score, e.tok_s, t, slo)
                    for e, t in zip(ents, ttfts)]
            pick = int(np.argmin(objs))
            best_cfg = ents[pick].cfg
            ttft = float(ttfts[pick])
            slo_ok = bool(
                (not slo.get("tok_s") or ents[pick].tok_s >= slo["tok_s"])
                and (not slo.get("ttft_ms") or ttft <= slo["ttft_ms"]))
        best_metrics = None
        hetero = None
        if best_cfg is not None:
            best_metrics = np.asarray(evaluate_vec_jit(
                cs.project(jnp.asarray(best_cfg, jnp.float32))[None],
                env.wl_vec, env.node_mat[c * lanes][None]))[0]
            part = partition(workload.graph, best_cfg)
            hetero = derive(best_cfg, part,
                            weight_bytes_total=workload.f("weight_mb") * 1e6)
        results.append(SearchResult(
            method="sac-vec", node_nm=int(node_nm), best_cfg=best_cfg,
            best_metrics=best_metrics,
            best_score=(float(best_metrics[M_IDX["ppa_score"]])
                        if best_metrics is not None else float("inf")),
            archive=archives[c], trace=traces[c], hetero=hetero,
            episodes_run=t_env, feasible_count=int(feasible_count[c]),
            unique_configs=len(seen.sets[c]), wall_s=wall,
            gate_open_episode=(int(gate.open_at[c])
                               if gate.open_at[c] >= 0 else None),
            screened=int(gate.screened[c]),
            evaluated=int(gate.evaluated[c]),
            ttft_ms=ttft, slo_ok=slo_ok))
    return results


def run_search(workload: Workload, node_nm: int, *, high_perf: bool = True,
               search: Optional[SearchConfig] = None, n_envs: int = 64,
               checkpoint_dir: Optional[str] = None,
               checkpoint_every: int = 0, resume: bool = False,
               devices: Optional[int] = None) -> SearchResult:
    """Algorithm 1 on the batched engine: ``n_envs`` parallel episodes per
    device dispatch (the single-cell view of :func:`run_search_cells`).

    The env hot path (action application, projection, analytic PPA, Eq.-34
    reward) is one fused jit step over the whole batch; transitions land in
    the PER buffer via one ``add_batch`` and feasible configurations reach
    the Pareto archive via one ``insert_batch`` per dispatch.  SAC/world-
    model updates run ``sc.updates_per_dispatch`` times per dispatch (the
    scalar loop updates per env-step; see SearchConfig).  ``sc.episodes``
    is the TOTAL env-step budget, matching the scalar driver.
    """
    return run_search_cells(
        workload, [node_nm], high_perf=high_perf, search=search,
        lanes_per_cell=n_envs, checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every, resume=resume,
        devices=devices)[0]


def search_all_nodes(workload: Workload, nodes: Sequence[int], *,
                     high_perf: bool = True,
                     search: Optional[SearchConfig] = None,
                     n_envs: int = 64) -> Dict[int, SearchResult]:
    """Algorithm 1 outer loop on the batched engine (Eq. 50).

    Because the fused step traces the node constant vector instead of baking
    it in, the 7 per-node searches share ONE compiled step (and one compiled
    evaluator/encoder): only the first node pays compilation.
    """
    out = {}
    for n in nodes:
        out[n] = run_search(workload, n, high_perf=high_perf, search=search,
                            n_envs=n_envs)
    return out


# --------------------------------------------------------------------------
def run_random(workload: Workload, node_nm: int, *, high_perf: bool = True,
               episodes: int = 4613, seed: int = 0) -> SearchResult:
    """Random-search baseline (Table 21)."""
    t0 = time.time()
    env = DSEEnv(workload, node_nm, high_perf=high_perf, seed=seed)
    rng = np.random.default_rng(seed)
    archive = ParetoArchive()
    best = (np.inf, None, None)
    feas_count = 0
    seen = set()
    trace = []
    for t in range(episodes):
        cfg = cs.random_config(rng)
        m = env.evaluate_config(cfg)
        best, feas = _update_best(best, m, cfg, archive, t)
        feas_count += int(feas)
        seen.add(_cfg_key(cfg))
        if t % 50 == 0:
            trace.append(TracePoint(t, 0.0, float(best[0]), 1.0, 0.0,
                                    len(seen), feas_count,
                                    float(m[M_IDX["tok_s"]])))
    return SearchResult("random", node_nm, best[1], best[2], float(best[0]),
                        archive, trace, None, episodes, feas_count,
                        len(seen), time.time() - t0,
                        screened=episodes, evaluated=episodes)


def run_grid(workload: Workload, node_nm: int, *, high_perf: bool = True,
             episodes: int = 4613, seed: int = 0) -> SearchResult:
    """Grid-search baseline (Table 21): lattice over the dominant axes."""
    t0 = time.time()
    env = DSEEnv(workload, node_nm, high_perf=high_perf, seed=seed)
    archive = ParetoArchive()
    best = (np.inf, None, None)
    feas_count = 0
    seen = set()
    trace = []
    # lattice sized to the episode budget
    meshes = np.unique(np.linspace(2, 64, 14).astype(int))
    vlens = np.array([256, 512, 1024, 1536, 2048])
    wmems = np.array([1024, 4096, 9800, 16384, 32768, 65536])
    freqs = np.array([0.25, 0.5, 1.0])
    t = 0
    for mw in meshes:
        for vl in vlens:
            for wm in wmems:
                for fq in freqs:
                    if t >= episodes:
                        break
                    cfg = cs.default_config()
                    cfg[cs.IDX["mesh_w"]] = mw
                    cfg[cs.IDX["mesh_h"]] = mw
                    cfg[cs.IDX["vlen"]] = vl
                    cfg[cs.IDX["wmem_kb"]] = wm
                    cfg[cs.IDX["freq_frac"]] = fq
                    m = env.evaluate_config(cfg)
                    best, feas = _update_best(best, m, cfg, archive, t)
                    feas_count += int(feas)
                    seen.add(_cfg_key(cfg))
                    if t % 50 == 0:
                        trace.append(TracePoint(
                            t, 0.0, float(best[0]), 0.0, 0.0, len(seen),
                            feas_count, float(m[M_IDX["tok_s"]])))
                    t += 1
    return SearchResult("grid", node_nm, best[1], best[2], float(best[0]),
                        archive, trace, None, t, feas_count, len(seen),
                        time.time() - t0, screened=t, evaluated=t)


def run_all_nodes(workload: Workload, nodes: Sequence[int], *,
                  high_perf: bool = True,
                  search: Optional[SearchConfig] = None
                  ) -> Dict[int, SearchResult]:
    """Algorithm 1 outer loop: sequential per-node optimisation (Eq. 50)."""
    out = {}
    for n in nodes:
        out[n] = run_sac(workload, n, high_perf=high_perf, search=search)
    return out
