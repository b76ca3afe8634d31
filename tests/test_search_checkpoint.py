"""Search checkpoints hold the live loop state as arrays: PER rows up to
the buffer's fill, the Pareto archives as columns, every cell's seen keys
in first-seen order.  A restore gives back that state exactly, and a
checkpoint in the earlier layout (PER at full capacity, archives as JSON
in the manifest) still resumes bit for bit."""
import copy
import dataclasses
import os
import shutil

import numpy as np
import pytest

import repro.core.search as search_mod
from repro.checkpoint import manager as ckpt_mod
from repro.configs import get_config
from repro.core import actions as act
from repro.core.pareto import (ArchiveEntry, ParetoArchive, pack_archives,
                               unpack_archives)
from repro.core.replay import PERBuffer
from repro.core.search import (PER_FIELDS, SearchConfig, SeenKeys, TracePoint,
                               run_search_cells)
from repro.core.state import SAC_STATE_DIM
from repro.obs import metrics as obs_metrics
from repro.ppa import config_space as cs
from repro.ppa.analytic import M_DIM
from repro.workload.extract import extract

NODES, LANES, EVERY = [3, 7], 4, 8
# 24 dispatches: checkpoints after 8 and 16, learning from the 4th
SC = SearchConfig(episodes=96, warmup=32, batch_size=32, surrogate_every=4,
                  seed=0)


@pytest.fixture(scope="module")
def wl():
    return extract(get_config("smollm-135m"), seq_len=256, batch=1)


def _ckpt_hist():
    for h in obs_metrics.global_registry().snapshot()["histograms"]:
        if h["name"] == "search_checkpoint_bytes":
            return h["sum"], h["count"]
    return 0.0, 0


def _run(wl, d, **kw):
    return run_search_cells(wl, NODES, search=SC, lanes_per_cell=LANES,
                            checkpoint_dir=d, checkpoint_every=EVERY, **kw)


@pytest.fixture(scope="module")
def reference(wl, tmp_path_factory):
    """The uninterrupted run, and the checkpoint histogram's change."""
    d = str(tmp_path_factory.mktemp("ref") / "ck")
    s0, n0 = _ckpt_hist()
    res = _run(wl, d)
    s1, n1 = _ckpt_hist()
    return res, d, (s1 - s0, n1 - n0)


@pytest.fixture(scope="module")
def killed(wl, tmp_path_factory):
    """A run killed right after its first checkpoint, with a copy of the
    live state that checkpoint encoded."""
    d = str(tmp_path_factory.mktemp("killed") / "ck")
    live = {}
    real_host, real_save = (search_mod._host_checkpoint,
                            search_mod._save_search_ckpt)

    def spy(buf, archives, seen, traces, best):
        if not live:
            live.update(copy.deepcopy(dict(
                buf=buf, archives=archives, seen=seen.sets, traces=traces,
                best=best)))
        return real_host(buf, archives, seen, traces, best)

    def killing_save(*args, **kw):
        real_save(*args, **kw)
        raise KeyboardInterrupt("simulated kill after checkpoint")

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(search_mod, "_host_checkpoint", spy)
        mp.setattr(search_mod, "_save_search_ckpt", killing_save)
        with pytest.raises(KeyboardInterrupt):
            _run(wl, d)
    return d, live


def _fresh_buffer(capacity=None):
    kw = {} if capacity is None else dict(capacity=capacity)
    return PERBuffer(SAC_STATE_DIM, act.N_CONT, act.N_DISC, seed=0, **kw)


def _assert_archives_equal(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert (a.max_size, a.n_inserted) == (b.max_size, b.n_inserted)
        assert len(a.entries) == len(b.entries)
        for ea, eb in zip(a.entries, b.entries):
            assert ea.cfg.dtype == eb.cfg.dtype
            assert np.array_equal(ea.cfg, eb.cfg)
            da, db = dataclasses.asdict(ea), dataclasses.asdict(eb)
            for k in da:
                if k != "cfg":
                    assert type(da[k]) is type(db[k]), k
                    assert np.array_equal(da[k], db[k], equal_nan=True), k
        assert np.array_equal(a._objectives(), b._objectives(),
                              equal_nan=True)


def _assert_restored(flat, ex, live):
    buf = _fresh_buffer()
    archives, seen, traces, best = search_mod._host_restore(flat, ex, buf)
    want = live["buf"]
    for n in PER_FIELDS:
        assert np.array_equal(getattr(buf, n), getattr(want, n)), n
    assert np.array_equal(buf.tree.tree, want.tree.tree)
    _assert_archives_equal(archives, [ParetoArchive.from_dict(a.to_dict())
                                      for a in live["archives"]])
    assert seen.sets == live["seen"]
    for c, keys in enumerate(live["seen"]):
        assert set(map(tuple, seen.rows(c).tolist())) == keys
    assert traces == live["traces"]
    for (sa, ca, ma), (sb, cb, mb) in zip(best, live["best"]):
        assert sa == sb
        assert (ca is None) == (cb is None)
        if ca is not None:
            assert np.array_equal(ca, cb) and np.array_equal(ma, mb)


def test_mid_campaign_checkpoint_holds_the_live_rows(killed):
    d, live = killed
    flat, man = ckpt_mod.restore_flat(d)
    ex = man["extra"]
    assert man["step"] == EVERY
    size = live["buf"].size
    assert size == ex["buf_size"] == EVERY * LANES * len(NODES)
    for n in PER_FIELDS:
        assert flat[f"host/per_{n}"].shape[0] == size, n
    assert "archives" not in ex
    assert sum(len(a) for a in live["archives"]) > 0
    assert len(flat["host/seen_keys"]) == sum(map(len, live["seen"]))
    _assert_restored(flat, ex, live)


def _to_parent_layout(d):
    """Rewrite the latest checkpoint in ``d`` as the earlier layout wrote
    it: PER rows padded to capacity, each archive as ``to_dict`` JSON in
    ``extra["archives"]``, seen keys in another order."""
    step = ckpt_mod.latest_step(d)
    flat, man = ckpt_mod.restore_flat(d, step)
    ex = man["extra"]
    cap = len(flat["host/per_tree"]) // 2
    for n in PER_FIELDS:
        arr = flat[f"host/per_{n}"]
        full = np.zeros((cap,) + arr.shape[1:], arr.dtype)
        full[:len(arr)] = arr
        flat[f"host/per_{n}"] = full
    pre = "host/archive_"
    cols = {k[len(pre):]: flat.pop(k) for k in list(flat)
            if k.startswith(pre)}
    ex["archives"] = [
        dict(max_size=m["max_size"], n_inserted=m["n_inserted"],
             entries=[dict(cfg=cols["cfg"][i].astype(np.float64).tolist(),
                           episode=int(cols["episode"][i]),
                           **{f: float(cols[f][i]) for f in (
                               "power_mw", "perf_gops", "area_mm2", "tok_s",
                               "ppa_score")})
                      for i in np.nonzero(cols["cell"] == c)[0]])
        for c, m in enumerate(ex.pop("archive_meta"))]
    flat["host/seen_keys"] = flat["host/seen_keys"][::-1].copy()
    flat["host/seen_cell"] = flat["host/seen_cell"][::-1].copy()
    shutil.rmtree(os.path.join(d, f"step_{step:08d}"))
    ckpt_mod.save(flat, d, step, keep=2, extra=ex)


@pytest.mark.parametrize("layout", ["current", "parent"])
def test_resume_finishes_bitwise_equal(wl, reference, killed, layout,
                                       tmp_path):
    ref, _, _ = reference
    d = str(tmp_path / "ck")
    shutil.copytree(killed[0], d)
    if layout == "parent":
        _to_parent_layout(d)
        ex = ckpt_mod.manifest_of(d)["extra"]
        assert ex["archives"] and "archive_meta" not in ex
    got = _run(wl, d, resume=True)
    for a, b in zip(got, ref):
        assert a.best_score == b.best_score
        assert np.array_equal(a.best_cfg, b.best_cfg)
        assert np.array_equal(a.best_metrics, b.best_metrics)
        assert a.trace == b.trace
        assert (a.episodes_run, a.feasible_count, a.unique_configs) == \
            (b.episodes_run, b.feasible_count, b.unique_configs)
        assert (a.screened, a.evaluated, a.gate_open_episode) == \
            (b.screened, b.evaluated, b.gate_open_episode)
        _assert_archives_equal([a.archive], [b.archive])


def test_checkpoint_bytes_histogram_counts_every_checkpoint(reference):
    _, d, (total, n) = reference
    steps = ckpt_mod.all_steps(d)
    assert steps == [EVERY, 2 * EVERY] and n == len(steps)
    on_disk = sum(os.path.getsize(os.path.join(d, f"step_{s:08d}", f))
                  for s in steps for f in ("arrays.npz", "manifest.json"))
    assert total == on_disk


def _synthetic_archives(rng):
    """Three archives: one with non-finite metrics, one empty, one with a
    crowd limit."""
    out = []
    for c, n in enumerate((5, 0, 7)):
        ar = ParetoArchive(max_size=2048 if c != 2 else 6)
        ar.insert_batch([ArchiveEntry(
            cfg=rng.random(cs.DIM).astype(np.float32),
            power_mw=float(rng.random()), perf_gops=float(rng.random()),
            area_mm2=float(rng.random()),
            tok_s=float("inf") if c == 0 and i == 0 else float(rng.random()),
            ppa_score=float(rng.random()), episode=int(i))
            for i in range(n)])
        out.append(ar)
    return out


@pytest.mark.parametrize("rows", [0, 40, 130])
def test_host_state_round_trip(rows, tmp_path):
    """Buffers empty, part full and wrapped round their ring (capacity
    100 here): the checkpoint holds ``size`` rows and the restore gives
    back every row, the sum tree, the archives, seen keys, incumbents and
    traces, an infinite best score among them."""
    rng = np.random.default_rng(rows)
    buf = _fresh_buffer(capacity=100)
    for n in (rows // 2, rows - rows // 2):
        buf.add_batch(rng.random((n, SAC_STATE_DIM), np.float32),
                      rng.random((n, act.N_CONT), np.float32),
                      rng.integers(0, 3, (n, act.N_DISC)).astype(np.int32),
                      rng.random(n).astype(np.float32),
                      rng.random((n, SAC_STATE_DIM), np.float32),
                      np.zeros(n, np.float32))
    buf.update_priorities(np.arange(min(rows, 10)), rng.random(min(rows, 10)))
    archives = _synthetic_archives(rng)
    seen = SeenKeys(3)
    for _ in range(3):
        cfg = rng.integers(0, 3, (12, cs.DIM)).astype(np.float32) / 7
        seen.add(cfg, 4)
    traces = [[TracePoint(4 * k, 0.1, float("inf") if c else 0.5, 0.4,
                          0.0, k, 0, 12.0) for k in range(3)]
              for c in range(3)]
    best = [(0.25, rng.random(cs.DIM).astype(np.float32),
             rng.random(M_DIM).astype(np.float32)),
            (np.inf, None, None), (np.inf, None, None)]
    arrays, extra = search_mod._host_checkpoint(buf, archives, seen, traces,
                                                best)
    assert all(len(arrays[f"per_{n}"]) == min(rows, 100) for n in PER_FIELDS)
    live = copy.deepcopy(dict(buf=buf, archives=archives, seen=seen.sets,
                              traces=traces, best=best))
    # through the checkpoint's own files and JSON manifest
    ckpt_mod.save(dict(host=arrays), str(tmp_path), 1, extra=extra)
    flat, man = ckpt_mod.restore_flat(str(tmp_path))
    buf2 = _fresh_buffer(capacity=100)
    archives2, seen2, traces2, best2 = search_mod._host_restore(
        flat, man["extra"], buf2)
    for n in PER_FIELDS:
        assert np.array_equal(getattr(buf2, n), getattr(live["buf"], n)), n
    assert np.array_equal(buf2.tree.tree, live["buf"].tree.tree)
    _assert_archives_equal(archives2, [ParetoArchive.from_dict(a.to_dict())
                                       for a in live["archives"]])
    assert seen2.sets == live["seen"]
    assert traces2 == live["traces"]
    assert best2[1:] == live["best"][1:] and best2[0][0] == 0.25
    assert np.array_equal(best2[0][1], live["best"][0][1])


def test_pack_archives_is_the_inverse_of_unpack():
    archives = _synthetic_archives(np.random.default_rng(3))
    cols, meta = pack_archives(archives, cs.DIM)
    assert cols["cfg"].dtype == np.float32 and cols["cfg"].shape[1] == cs.DIM
    assert cols["episode"].dtype == np.int64
    assert list(cols["cell"]) == sorted(cols["cell"])
    _assert_archives_equal(unpack_archives(cols, meta), archives)
    empty, meta = pack_archives([ParetoArchive()], cs.DIM)
    assert empty["cfg"].shape == (0, cs.DIM)
    assert [len(a) for a in unpack_archives(empty, meta)] == [0]


def test_seen_keys_match_cfg_key():
    """The rows the vectorized add keeps are ``_cfg_key``'s values, each
    distinct key once, in first-seen order."""
    rng = np.random.default_rng(5)
    cfg = (rng.integers(0, 4, (24, cs.DIM)) / 3.0 + 1e-4).astype(np.float32)
    cfg[5] = cfg[1]                     # a repeat inside one dispatch
    seen = SeenKeys(2)
    seen.add(cfg, 12)
    seen.add(cfg[::-1].copy(), 12)      # the same designs, other cells
    for c in range(2):
        want = []
        for row in list(cfg[c * 12:(c + 1) * 12]) + list(
                cfg[::-1][c * 12:(c + 1) * 12]):
            k = search_mod._cfg_key(row)
            if k not in want:
                want.append(k)
        assert [tuple(r) for r in seen.rows(c).tolist()] == want
        assert seen.sets[c] == set(want)
