"""The device a run measures: what JAX reports, its published peaks, and
its peak memory."""
from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def describe(chips: int, *, require_tpu: bool = True) -> Dict:
    """Platform, kind and count of JAX's devices; raises ``NoChip`` unless
    they are ``chips`` or more TPUs (``require_tpu=False`` is for the
    CPU rehearsal in the tests)."""
    import jax
    devs = jax.devices()
    info = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"needs a TPU; JAX found {info['count']} "
                     f"{info['platform']} device(s) ({info['kind']})")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chip(s); JAX found "
                     f"{info['count']}")
    return info


def peaks(kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


def memory_peak_bytes(n_chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``n_chips`` devices
    (0 where the backend keeps no such statistic, as the CPU does)."""
    import jax
    best = 0
    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best
