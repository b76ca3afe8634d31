"""Plain reference of what the recommend service answers from a campaign
run directory: each cell's Pareto frontier read from the run's JSONL
records, the in-grid scalarized selection, and the fallback candidate
pool.  Reads the files directly and imports nothing of the program.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, List

import numpy as np

# scalarization grid whose per-cell winners form the fallback pool
POOL_WEIGHTS = ((0.8, 0.1, 0.1), (0.6, 0.3, 0.1), (0.4, 0.4, 0.2),
                (0.33, 0.34, 0.33), (0.2, 0.6, 0.2), (0.1, 0.8, 0.1),
                (0.1, 0.3, 0.6))


def read_cell(run_dir: str, cell_id: str):
    """(design points, last summary) a run recorded for one cell."""
    points, summary = [], None
    with open(os.path.join(run_dir, "cells", f"{cell_id}.jsonl")) as f:
        for line in f:
            if line.strip():
                rec = json.loads(line)
                if rec.get("kind") == "point":
                    points.append(rec)
                elif rec.get("kind") == "summary":
                    summary = rec
    return points, summary


def frontier(points: List[Dict]) -> List[Dict]:
    """Non-dominated points over (power, -perf, area), duplicates of a
    design or of an objective vector dropped (the first one kept), in the
    order they were recorded."""
    seen, uniq = set(), []
    for p in points:
        k = (tuple(np.round(np.asarray(p["cfg"], np.float32), 6).tolist()),
             p["power_mw"], p["perf_gops"], p["area_mm2"])
        if k not in seen:
            seen.add(k)
            uniq.append(p)
    obj = np.array([[p["power_mw"], -p["perf_gops"], p["area_mm2"]]
                    for p in uniq], np.float64).reshape(-1, 3)
    le = np.all(obj[:, None, :] <= obj[None, :, :], axis=-1)
    lt = np.any(obj[:, None, :] < obj[None, :, :], axis=-1)
    dominated = (le & lt).any(axis=0)
    out, objs = [], set()
    for p, o, dom in zip(uniq, obj, dominated):
        if not dom and tuple(o) not in objs:
            objs.add(tuple(o))
            out.append(p)
    return out


def select_scores(points: List[Dict], weights) -> np.ndarray:
    """Scalarized scores on frontier-normalized objectives (lower wins)."""
    def norm(x):
        return (x - x.min()) / max(x.max() - x.min(), 1e-9)
    perf = np.array([p["perf_gops"] for p in points])
    power = np.array([p["power_mw"] for p in points])
    area = np.array([p["area_mm2"] for p in points])
    w_perf, w_power, w_area = weights
    return (w_perf * (1.0 - norm(perf)) + w_power * norm(power)
            + w_area * norm(area))


def in_grid(points: List[Dict], power_budget: float = math.inf
            ) -> List[Dict]:
    """The points of a cell's frontier that meet the budget (empty when
    the query is out of grid)."""
    return [p for p in points if p["power_mw"] <= power_budget]


def pool(frontiers: Dict[str, List[Dict]]) -> List[Dict]:
    """Each cell's winners over ``POOL_WEIGHTS``, deduplicated by design,
    cells in sorted order."""
    out, seen = [], set()
    for cid in sorted(frontiers):
        pts = frontiers[cid]
        if not pts:
            continue
        for w in POOL_WEIGHTS:
            p = pts[int(np.argmin(select_scores(pts, w)))]
            k = tuple(np.round(np.asarray(p["cfg"], np.float64), 6).tolist())
            if k not in seen:
                seen.add(k)
                out.append(dict(p, cell_id=cid))
    return out
