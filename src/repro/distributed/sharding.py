"""Sharding rules: FSDP (+pod) x TP layouts for every architecture family.

Rules are name-based over parameter tree paths; every rule degrades to
replication when a dimension is not divisible by the mesh axis size, so the
same rule set serves the 16x16 production pod, the 2x16x16 multi-pod mesh
and the tiny CPU test meshes.

Conventions (see DESIGN.md §5):
  * fsdp axes: ("data",) single-pod / ("pod","data") multi-pod for the
    largest archs — weights are fully sharded, gathered per-layer by GSPMD.
  * tp axis: "model" — attention heads / FFN inner / vocab.
  * scanned-stack leading axis (n_periods) is never sharded.
  * KV caches shard sequence over "model" (flash-decode style) and batch
    over the data axes; recurrent states shard their channel dim.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Any  # str | tuple[str, ...] | None


def _axsize(mesh: Mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis]
    return int(np.prod([mesh.shape[a] for a in axis]))


def _flat(*axes) -> Tuple[str, ...]:
    """Flatten possibly-tuple axis specs into one compound tuple."""
    out = []
    for a in axes:
        if a is None:
            continue
        if isinstance(a, str):
            out.append(a)
        else:
            out.extend(a)
    return tuple(out)


def _fit(mesh: Mesh, spec_axes, shape) -> P:
    """Drop axes that don't divide the corresponding dim."""
    fixed = []
    for dim, ax in zip(shape, spec_axes):
        if ax is not None and dim % _axsize(mesh, ax) == 0 and dim > 0:
            fixed.append(ax)
        else:
            fixed.append(None)
    return P(*fixed)


# parameter-name classes
_COL = ("wq", "wk", "wv", "wuq", "wukv", "x_wq", "x_wk", "x_wv", "w_gate",
        "w_up", "in_proj", "up", "qkv", "s_gate", "s_up", "gates", "wx")
_ROW = ("wo", "x_wo", "w_down", "out_proj", "down", "s_down")
_REP = ("norm1", "norm2", "x_norm", "q_norm", "kv_norm", "ln", "norm",
        "final_norm", "dt_bias", "d_skip", "x_gate", "conv_b", "pos",
        "dt_w", "router", "wdq", "wdkv")


def param_spec(path: str, shape: Tuple[int, ...], mesh: Mesh,
               fsdp: Axis, tp: Axis, *, serve: bool = False) -> P:
    """Sharding rule for one parameter leaf (path is '/'-joined keys).

    serve=False (training): FSDP x TP — every matrix 2-D sharded; GSPMD
    re-gathers layer weights per step (amortised over the big train batch).
    serve=True (decode): Megatron column/row TP over `tp` only — weights
    are STATIONARY (replicated over the data axes) and each layer costs two
    activation-sized psums; at decode batch sizes the activations are ~MB
    while weight gathers would be ~GB (the §Perf decode hillclimb)."""
    parts = path.split("/")
    name = parts[-2] if parts[-1] in ("w", "b") else parts[-1]
    stacked = parts[0] in ("blocks", "lead") or (
        len(parts) > 1 and parts[1] in ("blocks", "lead"))
    lead = (None,) if stacked else ()
    row_in = fsdp if not serve else None     # contracting-dim shard (train)

    def spec(*axes):
        return _fit(mesh, lead + axes, shape)

    if name == "embed" or (len(parts) >= 2 and parts[-2] == "embed"):
        return _fit(mesh, (tp, None if serve else fsdp), shape)
    if name == "lm_head":
        return _fit(mesh, (None if serve else fsdp, tp), shape)
    if parts[-1] == "b":  # bias: follows the out dim of its matrix
        if name in _COL:
            return spec(tp)
        return spec(None)
    if name in _REP:
        return spec(*([None] * (len(shape) - len(lead))))
    if name in _COL:
        return spec(row_in, tp)
    if name in _ROW:
        return spec(tp, row_in)
    if name == "conv_w":
        return spec(None, tp)
    if name in ("a_log", "x_proj"):
        return spec(tp, None)
    if name == "wr":  # sLSTM recurrent matrix
        return spec(None, tp)
    if name in ("e_gate", "e_up"):       # [E, d, f]
        E = shape[len(lead)]
        if E % _axsize(mesh, tp) == 0:
            return spec(tp, row_in, None)   # expert-parallel
        # small-E MoE: 2-D shard (d over fsdp, ff over tp).  A compound
        # (fsdp,tp) ff-only shard was tried in §Perf and REFUTED: the
        # dispatched activations then move more than the expert weights.
        return spec(None, row_in, tp)
    if name == "e_down":                  # [E, f, d]
        E = shape[len(lead)]
        if E % _axsize(mesh, tp) == 0:
            return spec(tp, None, row_in)
        return spec(None, tp, row_in)
    # default: replicate
    return spec(*([None] * (len(shape) - len(lead))))


def _path_str(kp) -> str:
    out = []
    for k in kp:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return "/".join(out)


def param_shardings(params, mesh: Mesh, *, fsdp: Axis = "data",
                    tp: Axis = "model", serve: bool = False):
    """Tree of NamedShardings matching `params` (works on ShapeDtypeStructs
    as well as real arrays)."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(
            mesh, param_spec(_path_str(kp), leaf.shape, mesh, fsdp, tp,
                             serve=serve)),
        params)


# ------------------------------------------------------------------ caches
def cache_spec(path: str, shape, mesh: Mesh, dp: Axis, tp: Axis,
               *, shard_seq: bool = True) -> P:
    """KV/state cache rule.  Stacked layout [n_periods, B, ...]."""
    name = path.split("/")[-1]
    if name in ("k_tail", "v_tail", "ckv_tail", "krope_tail"):
        # ring tail: small, batch-sharded only — traced-index writes stay
        # shard-local (two-tier decode cache, §Perf)
        rest = [None] * (len(shape) - 2)
        return _fit(mesh, (None, dp, *rest), shape)
    if name == "plen":
        return _fit(mesh, tuple([None] * len(shape)), shape)
    if name in ("k", "v", "ckv", "krope", "xk", "xv"):
        # [P, B, S, ...]: batch over dp, seq over tp (flash-decode)
        seq_ax = tp if shard_seq else None
        rest = [None] * (len(shape) - 3)
        return _fit(mesh, (None, dp, seq_ax, *rest), shape)
    if name == "ssm":      # [P, B, di, N]
        return _fit(mesh, (None, dp, tp, None), shape)
    if name == "conv":     # [P, B, dc-1, di]
        return _fit(mesh, (None, dp, None, tp), shape)
    if name == "C":        # [P, B, H, dqk, dv]
        return _fit(mesh, (None, dp, None, None, tp), shape)
    if name in ("h", "c", "n", "m"):   # [P, B, di]
        return _fit(mesh, (None, dp, tp), shape)
    return _fit(mesh, tuple([None] * len(shape)), shape)


def cache_shardings(caches, mesh: Mesh, *, dp: Axis = "data",
                    tp: Axis = "model", shard_seq: bool = True):
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: NamedSharding(
            mesh, cache_spec(_path_str(kp), leaf.shape, mesh, dp, tp,
                             shard_seq=shard_seq)),
        caches)


def batch_sharding(mesh: Mesh, dp: Axis, *, extra_dims: int = 1):
    return NamedSharding(mesh, P(dp, *([None] * extra_dims)))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ------------------------------------------------------- batch-axis meshes
BATCH_AXIS = "batch"


def batch_mesh(devices: Optional[int] = None, *,
               axis: str = BATCH_AXIS) -> Mesh:
    """1-D device mesh over the DSE engine's environment-batch axis.

    ``devices=None`` takes every visible device; ``devices=n`` takes the
    first ``n``.  A mesh of 1 is the degenerate case (``shard_map`` over it
    is the identity partitioning), so callers can treat single- and multi-
    device runs uniformly.  Raises ``ValueError`` when more devices are
    requested than ``jax.device_count()`` provides — CLI layers should
    surface that before any compile (see ``repro.launch.dse``).
    """
    n = jax.device_count() if devices is None else int(devices)
    if n < 1:
        raise ValueError(f"batch_mesh needs >= 1 device (got {n})")
    check_visible_devices(n)
    return Mesh(np.asarray(jax.devices()[:n]), (axis,))


def check_visible_devices(n: int) -> None:
    """Raise ``ValueError`` unless this process sees at least ``n``
    devices.  The message names the platform and the count found; only on
    the CPU does it suggest emulating more devices with ``XLA_FLAGS``."""
    devs = jax.devices()
    if n <= len(devs):
        return
    platform = devs[0].platform
    hint = (f"; emulate host devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}"
            if platform == "cpu" else "")
    raise ValueError(f"{n} devices requested but only {len(devs)} "
                     f"{platform} device(s) visible{hint}")


def shard_keys(key: jax.Array, n_shards: int) -> jax.Array:
    """(n_shards, 2) per-shard PRNG keys folded from one global key.

    ``fold_in(key, shard_index)`` gives every shard an independent stream
    that is a pure function of the global seed and the shard's position —
    the same recipe the vec engine uses host-side (``seed + lane_index``),
    so re-sharding the same global seed re-derives identical streams.
    """
    if n_shards < 1:
        raise ValueError(f"shard_keys needs >= 1 shard (got {n_shards})")
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(n_shards))
