"""Pareto archive + scalarized final selection (paper §3.10, §5.4).

Objectives: (power [min], -perf [min], area [min]).  Every feasible
configuration is inserted; the archive maintains the non-dominated frontier.
After convergence the final design is selected by scalarizing frontier-
normalized objectives with the user PPA weights — guaranteeing the returned
configuration is Pareto-optimal among everything explored.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class ArchiveEntry:
    cfg: np.ndarray
    power_mw: float
    perf_gops: float
    area_mm2: float
    tok_s: float
    ppa_score: float
    episode: int

    def objectives(self) -> np.ndarray:
        return np.array([self.power_mw, -self.perf_gops, self.area_mm2])

    @classmethod
    def from_metrics(cls, cfg: np.ndarray, metrics: np.ndarray,
                     episode: int) -> "ArchiveEntry":
        """Build an entry from an analytic-PPA metrics vector."""
        from repro.ppa.analytic import M_IDX
        return cls(cfg=np.array(cfg, copy=True),
                   power_mw=float(metrics[M_IDX["power_mw"]]),
                   perf_gops=float(metrics[M_IDX["perf_gops"]]),
                   area_mm2=float(metrics[M_IDX["area_mm2"]]),
                   tok_s=float(metrics[M_IDX["tok_s"]]),
                   ppa_score=float(metrics[M_IDX["ppa_score"]]),
                   episode=episode)

    def to_dict(self) -> Dict:
        """JSON-safe dict; float64 reprs round-trip cfg exactly."""
        d = dataclasses.asdict(self)
        d["cfg"] = np.asarray(self.cfg, np.float64).tolist()
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "ArchiveEntry":
        return cls(cfg=np.asarray(d["cfg"], np.float32),
                   power_mw=float(d["power_mw"]),
                   perf_gops=float(d["perf_gops"]),
                   area_mm2=float(d["area_mm2"]), tok_s=float(d["tok_s"]),
                   ppa_score=float(d["ppa_score"]),
                   episode=int(d["episode"]))


def _dominates(a: np.ndarray, b: np.ndarray) -> bool:
    return bool(np.all(a <= b) and np.any(a < b))


class ParetoArchive:
    """Non-dominated frontier over (power, -perf, area).

    ``entries`` is the frontier in insertion order.  Next to it the archive
    caches their objectives as one float64 ``(F, 3)`` matrix, so an insert
    is a few whole-matrix comparisons instead of a scan per entry.  Assign
    ``entries`` to replace the frontier (the matrix is rebuilt on first
    use); the list itself is never mutated in place."""

    def __init__(self, max_size: int = 2048):
        self.entries: List[ArchiveEntry] = []
        self.max_size = max_size
        self.n_inserted = 0
        # candidates that reached the per-entry insert (telemetry only:
        # not serialized, restarts at 0 on load)
        self.n_offered = 0

    @property
    def entries(self) -> List[ArchiveEntry]:
        return self._entries

    @entries.setter
    def entries(self, entries: List[ArchiveEntry]) -> None:
        self._entries = entries
        self._objs: Optional[np.ndarray] = None

    def _objectives(self) -> np.ndarray:
        """The frontier's objectives, one float64 row per entry, in order."""
        if self._objs is None:
            self._objs = np.array([e.objectives() for e in self._entries],
                                  np.float64).reshape(-1, 3)
        return self._objs

    def insert(self, entry: ArchiveEntry) -> bool:
        """Insert if non-dominated; evict newly-dominated entries.

        An entry whose objective vector exactly equals an existing one is
        rejected as a duplicate (the first-seen entry wins): equal vectors
        are mutually non-dominating, so without the check every
        ``merge``/``insert_batch`` of overlapping archives would
        accumulate copies on the frontier — bloating archives and zeroing
        the crowd-prune pairwise distances."""
        return self._insert(entry, np.asarray(entry.objectives(), np.float64))

    def _insert(self, entry: ArchiveEntry, obj: np.ndarray) -> bool:
        self.n_inserted += 1
        self.n_offered += 1
        objs = self._objectives()
        # a row <= obj everywhere either dominates obj or equals it; a NaN
        # compares False, so it neither rejects nor evicts
        if (objs <= obj).all(axis=1).any():
            return False
        # no row equals obj, so obj <= row everywhere means obj dominates it
        keep = ~(obj <= objs).all(axis=1)
        if keep.all():
            entries = self._entries + [entry]
        else:
            entries = [e for e, k in zip(self._entries, keep) if k]
            entries.append(entry)
            objs = objs[keep]
        objs = np.concatenate([objs, obj[None]])
        if len(entries) > self.max_size:  # crowd-prune: drop densest
            span = objs.max(0) - objs.min(0) + 1e-9
            normed = (objs - objs.min(0)) / span
            d = np.linalg.norm(normed[:, None] - normed[None, :], axis=-1)
            np.fill_diagonal(d, np.inf)
            i = int(np.argmin(d.min(1)))
            entries.pop(i)
            objs = np.delete(objs, i, axis=0)
        self._entries, self._objs = entries, objs
        return True

    def insert_batch(self, entries: Sequence[ArchiveEntry]) -> int:
        """Insert B entries at once; returns how many reached the frontier.

        Pre-filters the batch to its own non-dominated subset with one
        vectorized pairwise pass (O(B^2) numpy instead of O(B) frontier
        updates for entries a batch-mate already dominates), then runs the
        usual per-entry frontier update.  The resulting archive equals
        sequential insertion (up to crowd-pruning order at max_size).
        """
        if not entries:
            return 0
        objs = np.array([e.objectives() for e in entries], np.float64)
        le = np.all(objs[:, None, :] <= objs[None, :, :], axis=-1)
        lt = np.any(objs[:, None, :] < objs[None, :, :], axis=-1)
        dominated = (le & lt).any(axis=0)
        self.n_inserted += int(dominated.sum())
        inserted = 0
        for e, o, dom in zip(entries, objs, dominated):
            if not dom:
                inserted += int(self._insert(e, o))
        return inserted

    def select(self, w_perf: float = 0.4, w_power: float = 0.4,
               w_area: float = 0.2) -> Optional[ArchiveEntry]:
        """Scalarized selection on frontier-normalized objectives."""
        if not self.entries:
            return None
        perf = np.array([e.perf_gops for e in self.entries])
        power = np.array([e.power_mw for e in self.entries])
        area = np.array([e.area_mm2 for e in self.entries])

        def norm(x):
            return (x - x.min()) / max(x.max() - x.min(), 1e-9)

        score = (w_perf * (1.0 - norm(perf)) + w_power * norm(power)
                 + w_area * norm(area))
        return self.entries[int(np.argmin(score))]

    def to_dict(self) -> Dict:
        """JSON-ready snapshot of the full archive state."""
        return dict(max_size=self.max_size, n_inserted=self.n_inserted,
                    entries=[e.to_dict() for e in self.entries])

    @classmethod
    def from_dict(cls, d: Dict) -> "ParetoArchive":
        """Exact inverse of :meth:`to_dict` — entries are restored verbatim
        (no re-insertion), so a save→load round trip preserves the frontier
        bit-for-bit including entry order."""
        ar = cls(max_size=int(d.get("max_size", 2048)))
        ar.entries = [ArchiveEntry.from_dict(e) for e in d.get("entries", [])]
        ar.n_inserted = int(d.get("n_inserted", len(ar.entries)))
        return ar

    def merge(self, other: "ParetoArchive") -> int:
        """Union another archive's frontier into this one with dominance
        filtering (the campaign-store merge across resumed/parallel runs);
        returns how many of ``other``'s entries reached the frontier."""
        return self.insert_batch([dataclasses.replace(e, cfg=e.cfg.copy())
                                  for e in other.entries])

    def frontier(self) -> Dict[str, np.ndarray]:
        return dict(
            power_mw=np.array([e.power_mw for e in self.entries]),
            perf_gops=np.array([e.perf_gops for e in self.entries]),
            area_mm2=np.array([e.area_mm2 for e in self.entries]),
            tok_s=np.array([e.tok_s for e in self.entries]),
        )

    def __len__(self) -> int:
        return len(self.entries)


# an entry's metric fields, as the columns of :func:`pack_archives`
METRIC_FIELDS = ("power_mw", "perf_gops", "area_mm2", "tok_s", "ppa_score")


def pack_archives(archives: Sequence[ParetoArchive], cfg_dim: int
                  ) -> tuple[Dict[str, np.ndarray], List[Dict]]:
    """Several archives as columns and per-archive scalars.

    The columns hold one row per entry, archive after archive, each in
    entry order: ``cfg`` float32, the metric fields float64, ``episode``
    int64 and ``cell``, the index of the row's archive.  The scalars are
    each archive's ``max_size`` and ``n_inserted``.  These are the values
    :meth:`ParetoArchive.from_dict` restores from :meth:`ParetoArchive.
    to_dict`, so :func:`unpack_archives` gives the same archives bit for
    bit."""
    entries = [e for a in archives for e in a.entries]
    cols = {f: np.array([getattr(e, f) for e in entries], np.float64)
            for f in METRIC_FIELDS}
    cols["cfg"] = (np.stack([np.asarray(e.cfg, np.float32) for e in entries])
                   if entries else np.zeros((0, cfg_dim), np.float32))
    cols["episode"] = np.array([e.episode for e in entries], np.int64)
    cols["cell"] = np.repeat(np.arange(len(archives), dtype=np.int64),
                             [len(a) for a in archives])
    meta = [dict(max_size=a.max_size, n_inserted=a.n_inserted)
            for a in archives]
    return cols, meta


def unpack_archives(cols: Dict[str, np.ndarray], meta: Sequence[Dict]
                    ) -> List[ParetoArchive]:
    """Exact inverse of :func:`pack_archives`."""
    bounds = np.searchsorted(cols["cell"], np.arange(len(meta) + 1))
    values = {f: cols[f].tolist() for f in METRIC_FIELDS + ("episode",)}
    out = []
    for c, m in enumerate(meta):
        ar = ParetoArchive(max_size=int(m["max_size"]))
        ar.entries = [
            ArchiveEntry(cfg=np.array(cols["cfg"][i], np.float32),
                         **{f: v[i] for f, v in values.items()})
            for i in range(bounds[c], bounds[c + 1])]
        ar.n_inserted = int(m["n_inserted"])
        out.append(ar)
    return out
