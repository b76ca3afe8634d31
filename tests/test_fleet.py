"""Fleet (multi-worker) campaigns: deterministic order-independent
sharding, W=2 fleet == W=1 run equivalence, chaos SIGKILL + fleet
--resume bitwise exactness, reconciler idempotency/crash-safety, the
per-worker utilization report, and CLI routing."""
import dataclasses
import glob
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.campaign import CampaignSpec, CampaignStore, run_campaign
from repro.campaign.distrib import (create_fleet, fingerprint,
                                    pending_batches, reconcile,
                                    shard_batches, worker_root)
from repro.campaign.planner import plan
from repro.campaign.store import STATUS_DONE
from repro.core.pareto import ArchiveEntry
from repro.launch import dse
from repro.launch import fleet as fleet_mod
from repro.launch.compile_cache import CACHE_DIR_ENV, enable_compile_cache

ARCH = "smollm-135m"
GRID = os.path.join(os.path.dirname(__file__), os.pardir,
                    "examples", "grids", "ci_smoke.json")
_silent = lambda m: None


def smoke_spec(name, **kw):
    """The ci_smoke grid (2 single-cell batches), optionally re-budgeted."""
    return dataclasses.replace(CampaignSpec.from_file(GRID),
                               name=name, **kw)


@pytest.fixture(scope="session")
def fleet_cache(tmp_path_factory):
    """Session-shared persistent compile cache: the first search pays the
    XLA compile, every later in-process run and worker subprocess reuses
    it (workers inherit JAX_COMPILATION_CACHE_DIR)."""
    cache = str(tmp_path_factory.mktemp("jax_compile_cache"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(CACHE_DIR_ENV, cache)
        assert enable_compile_cache() == cache
        yield cache


# ---------------------------------------------------------------- sharding
def test_shard_deterministic_order_independent_balanced():
    spec = CampaignSpec(name="s", workloads=[ARCH],
                        nodes=[3, 5, 7, 10, 14], modes=["high_perf",
                                                        "low_power"],
                        episodes=8, lanes=4, max_envs=4)
    batches = plan(spec)          # 10 single-cell batches
    assert len(batches) == 10
    for w in (1, 2, 3, 4, 7, 10, 16):
        deal = shard_batches(batches, w)
        dealt = [b.batch_id for bs in deal.values() for b in bs]
        # complete + disjoint
        assert sorted(dealt) == sorted(b.batch_id for b in batches)
        # balanced to within one batch among workers that got work
        sizes = [len(bs) for bs in deal.values()]
        assert max(sizes) - min(sizes) <= 1
        assert len(deal) == min(w, len(batches))
        # order-independent: the deal is a function of the batch SET
        shuffled = shard_batches(list(reversed(batches)), w)
        assert {k: [b.batch_id for b in bs] for k, bs in deal.items()} == \
               {k: [b.batch_id for b in bs] for k, bs in shuffled.items()}
    with pytest.raises(ValueError, match="workers"):
        shard_batches(batches, 0)


# ----------------------------------------------- reconciler (no search)
def _mk_entries(vals, cfg_fill=0.0):
    return [ArchiveEntry(cfg=np.full(30, cfg_fill, np.float32),
                         power_mw=float(p), perf_gops=float(g),
                         area_mm2=float(a), tok_s=1.0, ppa_score=0.5,
                         episode=i)
            for i, (p, g, a) in enumerate(vals)]


def test_reconcile_idempotent_and_crash_safe(tmp_path, monkeypatch):
    """Reconcile merges worker results once, re-running adds nothing, and
    a crash mid-manifest-write leaves the previous manifest valid."""
    spec = smoke_spec("rec")
    root = str(tmp_path / "rec")
    store = create_fleet(root, spec, workers=2)
    batches = plan(spec)
    assert [store.manifest["fleet"]["assignments"][b.batch_id]
            for b in batches] == [0, 1]

    # fabricate worker-1's completed cell (worker-0 never started)
    cell = batches[1].cells[0]
    wroot = worker_root(root, 1)
    os.makedirs(os.path.join(wroot, "cells"))
    w = CampaignStore(wroot, dict(name="rec/worker-1", spec=spec.to_dict(),
                                  worker=dict(index=1, busy_s=2.0),
                                  cells={cell.cell_id:
                                         dict(status="pending")}))
    w.complete_cell(cell, dict(cell_id=cell.cell_id, ppa_score=0.7,
                               episodes=48, wall_s=1.0),
                    _mk_entries([(10, 50, 1), (5, 40, 1), (10, 50, 2)]))

    # crash mid-reconcile: the manifest flip never lands, but the JSONL
    # appends are dedup-safe and the OLD manifest still opens
    real_save = CampaignStore.save_manifest
    monkeypatch.setattr(CampaignStore, "save_manifest",
                        lambda self: (_ for _ in ()).throw(
                            OSError("simulated crash")))
    with pytest.raises(OSError, match="simulated crash"):
        reconcile(CampaignStore.open(root))
    monkeypatch.setattr(CampaignStore, "save_manifest", real_save)
    store = CampaignStore.open(root)
    assert store.status(cell) != STATUS_DONE, \
        "interrupted reconcile must not have published a torn manifest"

    # completed reconcile: cell done, archive dominance-filtered
    newly = reconcile(store)
    assert newly == [cell.cell_id]
    store = CampaignStore.open(root)
    assert store.status(cell) == STATUS_DONE
    objs = sorted((e.power_mw, e.perf_gops)
                  for e in store.load_archive(cell.cell_id).entries)
    assert objs == [(5.0, 40.0), (10.0, 50.0)]
    assert store.load_summary(cell.cell_id)["ppa_score"] == 0.7
    # completed batches drop out of the outstanding deal
    assert batches[1].batch_id not in \
        store.manifest["fleet"]["assignments"]

    # idempotent: a second reconcile changes neither state nor the JSONL
    fp = fingerprint(store)
    size = os.path.getsize(store._cell_path(cell.cell_id))
    assert reconcile(store) == []
    store = CampaignStore.open(root)
    assert fingerprint(store) == fp
    assert os.path.getsize(store._cell_path(cell.cell_id)) == size


def test_run_campaign_refuses_fleet_scope_resume(tmp_path):
    spec = smoke_spec("guard")
    root = str(tmp_path / "guard")
    create_fleet(root, spec, workers=2)
    with pytest.raises(ValueError, match="fleet"):
        run_campaign(root, resume=True, progress=_silent)


# ------------------------------------------------- equivalence (W=2 == W=1)
def test_fleet_w2_matches_w1_bitwise(tmp_path, fleet_cache):
    """Determinism equivalence: a 2-worker fleet and the single-process
    campaign on the same grid/seed produce identical per-cell best-PPA and
    frontier sets (batch seeds derive from the global batch index, so the
    shard is order-independent)."""
    spec = smoke_spec("eq")
    ref = run_campaign(str(tmp_path / "w1"), spec, progress=_silent)
    store = fleet_mod.run_fleet(str(tmp_path / "w2"), spec, workers=2,
                                progress=_silent)
    assert store.all_done()
    assert fingerprint(store) == fingerprint(ref)

    # per-worker utilization table: one row per worker, busy time recorded
    with open(os.path.join(store.root, "report", "workers.json")) as f:
        report = json.load(f)
    rows = report["workers"]
    assert report["events"] == []        # healthy fleet: no supervision
    assert [r["worker"] for r in rows] == ["worker-0", "worker-1"]
    assert sum(r["cells"] for r in rows) == spec.n_cells
    assert all(r["busy_s"] > 0 and r["util_pct"] > 0 for r in rows)
    md = open(os.path.join(store.root, "report", "workers.md")).read()
    assert "| worker |" in md and "worker-1" in md


# ------------------------------------------------------- chaos kill/resume
def _wait_for_ckpt(h, root, victim, deadline_s=300):
    """Block until the victim worker has an in-flight checkpoint (so a
    kill provably interrupts mid-batch), or it exits."""
    ckpts = os.path.join(worker_root(root, victim), "ckpt", "*", "step_*")
    deadline = time.time() + deadline_s
    while time.time() < deadline and not glob.glob(ckpts) \
            and h.procs[victim].poll() is None:
        time.sleep(0.02)
    assert h.procs[victim].poll() is None and glob.glob(ckpts), \
        "victim finished before the kill window; raise spec.episodes"


def test_chaos_sigkill_worker_resume_bitwise_exact(tmp_path, fleet_cache):
    """Start a 2-worker fleet on the ci_smoke grid, SIGKILL one worker
    mid-batch, fleet --resume with the single survivor: the final merged
    manifest + frontiers must be bitwise identical to an uninterrupted
    run with the same seeds (checkpoint relocated to the survivor).
    ``supervise=False`` keeps the supervisor from healing the kill —
    this is the manual-recovery path."""
    spec = smoke_spec("chaos", episodes=240, checkpoint_every=4)
    ref = run_campaign(str(tmp_path / "ref"), spec, progress=_silent)

    root = str(tmp_path / "fleet")
    h = fleet_mod.launch_fleet(root, spec, workers=2, progress=_silent)
    victim = 1
    _wait_for_ckpt(h, root, victim)
    h.kill(victim, signal.SIGKILL)
    with pytest.raises(fleet_mod.FleetError, match="--resume"):
        h.wait(supervise=False)

    # the kill really interrupted work: the victim's batch is still
    # pending and stays dealt in the manifest
    store = CampaignStore.open(root)
    assert not store.all_done()
    pend = pending_batches(store)
    assert pend and all(
        b.batch_id in store.manifest["fleet"]["assignments"] for b in pend)

    # resume with ONE surviving worker: the dead worker's batch is
    # re-dealt, its in-flight checkpoint relocated, nothing re-run
    store = fleet_mod.run_fleet(root, workers=1, resume=True,
                                progress=_silent)
    assert store.all_done()
    assert fingerprint(store) == fingerprint(ref)
    # the relocated checkpoint was consumed + cleared on batch completion
    assert not glob.glob(os.path.join(root, "worker-*", "ckpt", "*"))


# ------------------------------------------- chaos: supervisor self-heal
def test_chaos_supervisor_redeals_sigkilled_worker(tmp_path, fleet_cache):
    """SIGKILL a worker mid-batch while the SUPERVISOR is running: its
    pending batch must be re-dealt to a fresh worker slot automatically
    (no parent restart, no manual --resume) and the final merged
    fingerprint must be bitwise identical to an uninterrupted run —
    the relocated checkpoint restores exactly where the victim died."""
    spec = smoke_spec("heal", episodes=240, checkpoint_every=4)
    ref = run_campaign(str(tmp_path / "ref"), spec, progress=_silent)

    root = str(tmp_path / "fleet")
    h = fleet_mod.launch_fleet(root, spec, workers=2, lease_ttl_s=3.0,
                               progress=_silent)
    victim = 1
    _wait_for_ckpt(h, root, victim)
    h.kill(victim, signal.SIGKILL)
    store = h.wait()                     # heals in-flight: NO FleetError
    assert store.all_done()
    assert fingerprint(store) == fingerprint(ref)

    # the eviction + re-deal left an audit trail: fleet events in the
    # manifest, the fresh slot in the report's worker table
    events = store.manifest["fleet"]["events"]
    redeals = [e for e in events if e["kind"] == "redeal"]
    assert redeals and redeals[0]["from_worker"] == victim
    fresh = redeals[0]["to_worker"]
    assert fresh not in (0, victim) and fresh in h.procs
    assert any(e["kind"] == "evict" and e["worker"] == victim
               for e in events)
    with open(os.path.join(store.root, "report", "workers.json")) as f:
        rep = json.load(f)
    assert any(e["kind"] == "redeal" for e in rep["events"])
    assert f"worker-{fresh}" in {r["worker"] for r in rep["workers"]}
    # the fresh worker's final lease reads done (clean exit)
    lease = json.load(open(os.path.join(worker_root(root, fresh),
                                        "lease.json")))
    assert lease["done"] and lease["batch"] is not None


# -------------------------------------------------------------------- CLI
def test_cli_rejects_bad_workers(capsys):
    with pytest.raises(SystemExit):
        dse.main(["--campaign", GRID, "--workers", "0"])
    assert "--workers must be >= 1" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        dse.main(["--workers", "2"])
    assert "--campaign" in capsys.readouterr().err


def test_cli_fleet_end_to_end(tmp_path, fleet_cache):
    """--campaign --workers 2 runs a fleet; --resume routes a fleet
    manifest back to fleet scope (a finished fleet resume is a no-op)."""
    grid = tmp_path / "grid.json"
    payload = json.loads(open(GRID).read())
    payload.update(name="clifleet", episodes=16)
    grid.write_text(json.dumps(payload))
    dse.main(["--campaign", str(grid), "--workers", "2",
              "--campaign-root", str(tmp_path / "runs")])
    root = str(tmp_path / "runs" / "clifleet")
    store = CampaignStore.open(root)
    assert store.all_done()
    assert store.manifest["fleet"]["workers"] == 2
    assert store.manifest["fleet"]["assignments"] == {}
    assert os.path.isfile(os.path.join(root, "report", "workers.json"))
    # resume of the finished fleet: reconcile + report only, no workers
    dse.main(["--resume", root])
    assert CampaignStore.open(root).all_done()


def test_fleet_warm_start_w2_matches_w1_bitwise(tmp_path, fleet_cache):
    """A warm-started (--transfer-from) fleet must fingerprint identically
    to the W=1 warm run: every worker mirrors the top-level manifest's
    transfer record verbatim, and the priority-LPT deal only changes
    WHERE batches run (seeds derive from the global batch index)."""
    from repro.campaign import transfer as transfer_mod
    donor = run_campaign(str(tmp_path / "donor"), smoke_spec("wdonor"),
                         progress=_silent)
    tspec = transfer_mod.with_transfer(smoke_spec("weq"), [donor.root])
    assert tspec.priorities is not None
    ref = run_campaign(str(tmp_path / "w1"), tspec, progress=_silent)
    store = fleet_mod.run_fleet(str(tmp_path / "w2"), tspec, workers=2,
                                progress=_silent)
    assert store.all_done()
    assert fingerprint(store) == fingerprint(ref)
    top = store.manifest["transfer"]
    assert top["donors"] and top == ref.manifest["transfer"]
    mirrored = 0
    for wr in glob.glob(os.path.join(store.root, "worker-*")):
        if os.path.isfile(os.path.join(wr, "manifest.json")):
            assert CampaignStore.open(wr).manifest["transfer"] == top
            mirrored += 1
    assert mirrored == 2


# ------------------------------------------------------ one process per chip
def test_fleet_parent_initializes_no_jax_backend(tmp_path):
    """The fleet parent must not claim the chip its workers need: run the
    CLI's fleet path in a fresh process and look at JAX's backends at the
    moment the first worker would be spawned."""
    script = textwrap.dedent(f"""
        import json, sys
        from jax._src import xla_bridge
        from repro.launch import dse, fleet

        def spawn(self, root, idx, env=None):
            print(json.dumps(dict(
                initialized=xla_bridge.backends_are_initialized())))
            sys.exit(0)

        fleet.LocalLauncher.spawn = spawn
        dse.main(["--campaign", {GRID!r}, "--workers", "2",
                  "--campaign-root", {str(tmp_path)!r}])
        """)
    out = subprocess.run([sys.executable, "-c", script],
                         env=fleet_mod._worker_env(), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout.splitlines()[-1]) == {"initialized": False}


def test_local_fleet_refuses_to_share_one_chip(tmp_path, monkeypatch):
    """W > 1 local workers on an accelerator host would contend for one
    chip: the launch fails before any worker is spawned."""
    spawned = []
    monkeypatch.setattr(fleet_mod, "_worker_platform", lambda env: "tpu")
    monkeypatch.setattr(fleet_mod.LocalLauncher, "spawn",
                        lambda self, *a, **k: spawned.append(a))
    with pytest.raises(fleet_mod.FleetError, match="share this host's tpu"):
        fleet_mod.launch_fleet(str(tmp_path / "f"), smoke_spec("chip"),
                               workers=2, progress=_silent)
    assert spawned == []
