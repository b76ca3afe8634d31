"""The Pareto archive's matrix insert against the per-entry scan it
replaced: same entries in the same order, same ``n_inserted``, same
return values, for single inserts, batches, NaN objectives, exact
duplicates, multi-entry evictions and crowd-pruning."""
import dataclasses

import numpy as np
import pytest

from repro.core.pareto import ArchiveEntry, ParetoArchive, _dominates


class _ScanArchive:
    """The per-entry scan, verbatim: the reference for the matrix insert."""

    def __init__(self, max_size: int = 2048):
        self.entries = []
        self.max_size = max_size
        self.n_inserted = 0

    def insert(self, entry: ArchiveEntry) -> bool:
        self.n_inserted += 1
        obj = entry.objectives()
        keep = []
        for e in self.entries:
            eo = e.objectives()
            if _dominates(eo, obj) or np.array_equal(eo, obj):
                return False          # dominated by (or duplicate of) an
                                      # existing entry
            if not _dominates(obj, eo):
                keep.append(e)
        keep.append(entry)
        if len(keep) > self.max_size:  # crowd-prune: drop densest
            objs = np.stack([e.objectives() for e in keep])
            span = objs.max(0) - objs.min(0) + 1e-9
            normed = (objs - objs.min(0)) / span
            d = np.linalg.norm(normed[:, None] - normed[None, :], axis=-1)
            np.fill_diagonal(d, np.inf)
            keep.pop(int(np.argmin(d.min(1))))
        self.entries = keep
        return True

    def insert_batch(self, entries) -> int:
        if not entries:
            return 0
        objs = np.stack([e.objectives() for e in entries])
        le = np.all(objs[:, None, :] <= objs[None, :, :], axis=-1)
        lt = np.any(objs[:, None, :] < objs[None, :, :], axis=-1)
        dominated = (le & lt).any(axis=0)
        self.n_inserted += int(dominated.sum())
        inserted = 0
        for e, dom in zip(entries, dominated):
            if not dom:
                inserted += int(self.insert(e))
        return inserted


def _entry(power, perf, area, i):
    return ArchiveEntry(cfg=np.full(4, float(i), np.float32),
                        power_mw=float(power), perf_gops=float(perf),
                        area_mm2=float(area), tok_s=float(i), ppa_score=0.5,
                        episode=i)


def _stream(kind, rng, n):
    """Objective triples that exercise one part of the insert."""
    if kind == "uniform":      # mostly dominated by the frontier
        objs = rng.uniform(1, 100, (n, 3))
    elif kind == "grid":       # ties and exact duplicates; perf 0 gives -0.0
        objs = rng.integers(0, 5, (n, 3)).astype(np.float64)
    elif kind == "nan":        # NaN objectives neither reject nor evict
        objs = rng.integers(0, 6, (n, 3)).astype(np.float64)
        objs[rng.random((n, 3)) < 0.1] = np.nan
    elif kind == "improving":  # each point tends to evict several
        t = np.linspace(0, 1, n)
        objs = np.stack([100 - 90 * t, 10 + 80 * t, 50 - 40 * t], 1)
        objs += rng.normal(0, 8, (n, 3))
    elif kind == "front":      # a trade-off curve: most points are kept
        u = rng.uniform(0, 1, n)
        objs = np.stack([u, u + rng.normal(0, 0.05, n),
                         rng.uniform(0, 1, n)], 1)
    else:
        raise ValueError(kind)
    return [_entry(p, g, a, i) for i, (p, g, a) in enumerate(objs)]


def _same(ar, ref):
    assert [id(e) for e in ar.entries] == [id(e) for e in ref.entries]
    assert ar.n_inserted == ref.n_inserted


KINDS = ["uniform", "grid", "nan", "improving", "front"]


@pytest.mark.parametrize("max_size", [8, 2048])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_insert_matches_the_scan(kind, max_size, seed):
    rng = np.random.default_rng(seed)
    ar, ref = ParetoArchive(max_size), _ScanArchive(max_size)
    for e in _stream(kind, rng, 300):
        assert ar.insert(e) == ref.insert(e)
    _same(ar, ref)


@pytest.mark.parametrize("max_size", [8, 2048])
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_insert_batch_matches_the_scan(kind, max_size, seed):
    rng = np.random.default_rng(100 + seed)
    es = _stream(kind, rng, 400)
    ar, ref = ParetoArchive(max_size), _ScanArchive(max_size)
    lo = 0
    while lo < len(es):
        hi = lo + int(rng.integers(0, 40))
        assert ar.insert_batch(es[lo:hi]) == ref.insert_batch(es[lo:hi])
        _same(ar, ref)
        lo = hi


@pytest.mark.parametrize("kind", KINDS)
def test_insert_after_from_dict(kind):
    rng = np.random.default_rng(7)
    es = _stream(kind, rng, 300)
    ar, ref = ParetoArchive(16), _ScanArchive(16)
    ar.insert_batch(es[:150])
    ref.insert_batch(es[:150])
    loaded = ParetoArchive.from_dict(ar.to_dict())
    ref.entries = list(loaded.entries)   # the restored objects, as loaded
    for e in es[150:]:
        assert loaded.insert(e) == ref.insert(e)
    _same(loaded, ref)


@pytest.mark.parametrize("kind", KINDS)
def test_insert_after_entries_are_set(kind):
    """As the recommend index does: a filtered frontier assigned to a
    fresh archive, then inserted into."""
    rng = np.random.default_rng(8)
    es = _stream(kind, rng, 300)
    ar = ParetoArchive()
    ar.insert_batch(es[:200])
    ar.insert(es[200])                   # the matrix is built and cached
    sub = [e for i, e in enumerate(ar.entries) if i % 2 == 0]
    ar.entries = sub
    ref = _ScanArchive()
    ref.entries = list(sub)
    assert ar.insert_batch(es[201:]) == ref.insert_batch(es[201:])
    assert [id(e) for e in ar.entries] == [id(e) for e in ref.entries]


def test_duplicates_within_a_batch_and_against_the_frontier():
    # objectives are (power, -perf, area)
    ar = ParetoArchive()
    assert ar.insert_batch([_entry(1, 5, 3, 0), _entry(2, 6, 1, 1)]) == 2
    # a copy of entry 0 (a batch-mate dominates it), a point evicting
    # entry 0, its exact twin, and a copy of entry 1
    batch = [_entry(1, 5, 3, 2), _entry(0, 5, 3, 3), _entry(0, 5, 3, 4),
             _entry(2, 6, 1, 5)]
    assert ar.insert_batch(batch) == 1
    assert [e.episode for e in ar.entries] == [1, 3]
    assert ar.insert(_entry(0, 9, 0, 6))      # evicts both
    assert [e.episode for e in ar.entries] == [6]
    assert ar.n_inserted == 7 and ar.n_offered == 6


def test_merge_hands_over_copies_and_matches_the_scan():
    rng = np.random.default_rng(9)
    a, b = ParetoArchive(), ParetoArchive()
    a.insert_batch(_stream("front", rng, 200))
    b.insert_batch(_stream("front", rng, 200))
    ref = _ScanArchive()
    ref.insert_batch(list(a.entries))
    n = a.merge(b)
    assert n == ref.insert_batch([dataclasses.replace(e, cfg=e.cfg.copy())
                                  for e in b.entries])
    assert [e.to_dict() for e in a.entries] == \
        [e.to_dict() for e in ref.entries]


def test_entries_list_is_replaced_not_mutated():
    """A list read from ``entries`` keeps its contents after later
    inserts, as it did when every insert built a new list."""
    ar = ParetoArchive()
    ar.insert(_entry(5, 5, 5, 0))
    held = ar.entries
    ar.insert(_entry(6, 6, 4, 1))
    ar.insert(_entry(1, 9, 1, 2))
    assert [e.episode for e in held] == [0]
    assert [e.episode for e in ar.entries] == [2]


try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # optional (requirements-dev.txt): the cases above stand
    st = None

if st is not None:
    _value = st.one_of(st.integers(0, 4).map(float), st.just(float("nan")),
                       st.floats(-1e3, 1e3, allow_nan=False))

    @settings(max_examples=80, deadline=None)
    @given(objs=st.lists(st.tuples(_value, _value, _value), max_size=60),
           cuts=st.lists(st.integers(0, 60), max_size=6),
           max_size=st.sampled_from([3, 8, 2048]))
    def test_batches_match_the_scan_property(objs, cuts, max_size):
        es = [_entry(p, g, a, i) for i, (p, g, a) in enumerate(objs)]
        ar, ref = ParetoArchive(max_size), _ScanArchive(max_size)
        bounds = sorted({0, len(es), *[min(c, len(es)) for c in cuts]})
        for lo, hi in zip(bounds, bounds[1:]):
            assert ar.insert_batch(es[lo:hi]) == ref.insert_batch(es[lo:hi])
        _same(ar, ref)
