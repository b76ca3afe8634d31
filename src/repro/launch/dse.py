"""DSE driver — the paper's compiler flow (Algorithm 1) as a CLI.

Runs the RL-based hardware search for a workload architecture across
process nodes, emits the per-TCC JSON artifacts, Pareto archive and
convergence trace the paper's tables/figures are generated from.

``--distributed`` runs population-parallel exploration: E environments
stepped per round with one shared policy; candidate evaluation is the
vmapped analytic PPA (on TPU this shards over the mesh via jit — the
1.4M evals/s batch evaluator; DESIGN.md §3 adaptation note 2).

``--campaign grid.yaml`` runs a persistent multi-workload x multi-node
campaign (``repro.campaign``) instead of a single search; ``--resume
<run-dir>`` continues a killed campaign from its last completed chunk.

``--screen-k`` / ``--gate-threshold`` / ``--no-surrogate-gate`` control
surrogate-gated candidate screening (vec engine + campaigns): once a
cell's surrogate calibration passes the Eq.-67 gate, K candidates are
proposed per env-step and only the surrogate's top-1 survivor pays a full
analytic PPA evaluation.
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List, Optional

import numpy as np

from repro.configs import get_config
from repro.core.search import (SearchConfig, SearchResult, run_grid,
                               run_random, run_sac, run_search)
from repro.launch.compile_cache import enable_compile_cache
from repro.ppa.analytic import M_IDX
from repro.ppa.nodes import NODES
from repro.workload.extract import DTYPES, PHASES, extract


def result_row(res: SearchResult) -> Dict:
    m = lambda n: res.metric(n)
    return dict(
        node_nm=res.node_nm, method=res.method,
        mesh=f"{int(np.round(res.best_cfg[0]))}x{int(np.round(res.best_cfg[1]))}"
        if res.best_cfg is not None else "-",
        cores=float(m("n_cores")), power_mw=float(m("power_mw")),
        perf_gops=float(m("perf_gops")), area_mm2=float(m("area_mm2")),
        tok_s=float(m("tok_s")), ppa_score=float(m("ppa_score")),
        freq_mhz=float(m("f_hz")) / 1e6,
        episodes=res.episodes_run, feasible=res.feasible_count,
        unique=res.unique_configs, wall_s=round(res.wall_s, 1),
        p_compute_mw=float(m("p_compute_mw")), p_sram_mw=float(m("p_sram_mw")),
        p_rom_mw=float(m("p_rom_mw")), p_noc_mw=float(m("p_noc_mw")),
        p_leak_mw=float(m("p_leak_mw")),
    )


def run(arch: str, *, nodes: List[int], mode: str, episodes: int,
        method: str, out_dir: str, seed: int = 0, seq_len: int = 2048,
        batch: int = 3, update_every: int = 1, verbose: bool = False,
        engine: str = "scalar", n_envs: int = 64,
        surrogate_gate: bool = True, screen_k: Optional[int] = None,
        gate_threshold: Optional[float] = None,
        devices: Optional[int] = None, phase: str = "decode",
        dtype: str = "native") -> List[Dict]:
    cfg = get_config(arch)
    high_perf = mode == "high-performance"
    wl = extract(cfg, seq_len=seq_len, batch=batch, phase=phase, dtype=dtype)
    os.makedirs(out_dir, exist_ok=True)
    # None = SearchConfig's defaults own the gate settings
    gate_kw = dict(surrogate_gate=surrogate_gate)
    if screen_k is not None:
        gate_kw["screen_k"] = screen_k
    if gate_threshold is not None:
        gate_kw["gate_threshold"] = gate_threshold
    rows = []
    for node in nodes:
        if method == "sac":
            sc = SearchConfig(episodes=episodes, seed=seed,
                              update_every=update_every, verbose=verbose,
                              **gate_kw)
            if engine == "vec":
                res = run_search(wl, node, high_perf=high_perf, search=sc,
                                 n_envs=n_envs, devices=devices)
            else:
                res = run_sac(wl, node, high_perf=high_perf, search=sc)
        elif method == "random":
            res = run_random(wl, node, high_perf=high_perf,
                             episodes=episodes, seed=seed)
        else:
            res = run_grid(wl, node, high_perf=high_perf,
                           episodes=episodes, seed=seed)
        row = result_row(res)
        rows.append(row)
        print(f"[dse] {arch} {node}nm [{method}]: mesh {row['mesh']} "
              f"tok/s {row['tok_s']:.1f} power {row['power_mw']:.1f} mW "
              f"area {row['area_mm2']:.0f} mm2 score {row['ppa_score']:.3f} "
              f"({row['wall_s']}s)")
        # artifacts: per-TCC JSON (Tables 15/16 source), trace, frontier
        tag = f"{arch}__{node}nm__{method}"
        if res.hetero is not None:
            res.hetero.to_json(os.path.join(out_dir, tag + "_tcc.json"))
        with open(os.path.join(out_dir, tag + "_trace.json"), "w") as f:
            json.dump([t.__dict__ for t in res.trace], f)
        fr = res.archive.frontier()
        with open(os.path.join(out_dir, tag + "_pareto.json"), "w") as f:
            json.dump({k: v.tolist() for k, v in fr.items()}, f)
    with open(os.path.join(out_dir, f"{arch}__{method}_summary.json"),
              "w") as f:
        json.dump(rows, f, indent=1)
    return rows


def _read_manifest(root: str) -> Dict:
    with open(os.path.join(root, "manifest.json")) as f:
        return json.load(f)


def _parse_hosts(s: Optional[str]) -> Optional[List[str]]:
    """--hosts comma list -> cleaned host names (None if flag absent)."""
    if s is None:
        return None
    return [h.strip() for h in s.split(",") if h.strip()]


def _resolve_devices(ap: argparse.ArgumentParser, a: argparse.Namespace,
                     *, fleet: bool) -> Optional[int]:
    """--mesh/--devices -> mesh device count (None = plain jit).

    A single-process run validates against the *visible* JAX device set
    here, before anything traces or compiles: a count larger than
    ``jax.device_count()`` dies with a one-line ``ap.error`` instead of a
    shard_map traceback deep in the engine, and ``--mesh auto`` takes
    every visible device.  A fleet parent never asks JAX for devices (a
    chip serves one process, and the workers need it): each worker checks
    the count it sees when it starts.
    """
    if a.mesh is not None and a.devices is not None:
        ap.error("--mesh and --devices are aliases; pass exactly one")
    spec = a.mesh if a.mesh is not None else a.devices
    if spec is None:
        return None
    if spec == "auto":
        if fleet:
            ap.error("--mesh auto counts this process's devices, and a "
                     "fleet parent leaves them to its workers; pass "
                     "--devices N with --workers")
        import jax
        return jax.device_count()
    try:
        n = int(spec)
    except ValueError:
        ap.error(f"--mesh must be 'auto' or a device count (got {spec!r})")
    if n < 1:
        ap.error(f"--devices must be >= 1 (got {n})")
    if not fleet:
        from repro.distributed.sharding import check_visible_devices
        try:
            check_visible_devices(n)
        except ValueError as e:
            ap.error(f"--devices {n}: {e}")
    return n


def validate_args(ap: argparse.ArgumentParser,
                  a: argparse.Namespace) -> None:
    """Reject invalid flag combinations up front with a one-line error
    (instead of a deep traceback later in the engine)."""
    if a.n_envs < 1:
        ap.error(f"--n-envs must be >= 1 (got {a.n_envs})")
    if a.engine == "scalar" and a.n_envs != ap.get_default("n_envs"):
        ap.error(f"--n-envs {a.n_envs} only applies to --engine vec; the "
                 "scalar engine steps one environment (drop --n-envs or "
                 "pass --engine vec)")
    if a.engine == "vec" and a.method != "sac":
        ap.error(f"--engine vec only drives the SAC search loop; "
                 f"--method {a.method} runs on the scalar evaluator "
                 "(drop --engine vec)")
    gate_flags = [n for n, v in (("--screen-k", a.screen_k),
                                 ("--gate-threshold", a.gate_threshold))
                  if v is not None]
    if a.no_surrogate_gate:
        gate_flags.append("--no-surrogate-gate")
    if a.screen_k is not None and a.screen_k < 1:
        ap.error(f"--screen-k must be >= 1 (got {a.screen_k})")
    if a.gate_threshold is not None and a.gate_threshold < 0:
        ap.error(f"--gate-threshold must be >= 0 (got {a.gate_threshold})")
    if gate_flags and a.resume:
        ap.error(f"{'/'.join(gate_flags)}: a resumed campaign keeps the "
                 "gate settings recorded in its manifest; start a new "
                 "campaign to change them")
    if gate_flags and not a.campaign and a.engine != "vec":
        ap.error(f"{'/'.join(gate_flags)} applies to --engine vec or "
                 "--campaign runs; the scalar engine has no surrogate "
                 "screening gate")
    mesh_flags = [n for n, v in (("--devices", a.devices),
                                 ("--mesh", a.mesh)) if v is not None]
    if mesh_flags and a.resume:
        ap.error(f"{'/'.join(mesh_flags)}: a resumed campaign keeps the "
                 "mesh recorded in its manifest; start a new campaign to "
                 "change it")
    if mesh_flags and not a.campaign and a.engine != "vec":
        ap.error(f"{'/'.join(mesh_flags)} shard the batched engine's env "
                 "batch over accelerators; pass --engine vec or --campaign "
                 "with them")
    if a.workers is not None and a.workers < 1:
        ap.error(f"--workers must be >= 1 (got {a.workers})")
    if a.workers is not None and not (a.campaign or a.resume):
        ap.error("--workers shards a campaign across worker processes; "
                 "pass --campaign (or --resume) with it")
    fleet_flags = [n for n, v in (("--hosts", a.hosts),
                                  ("--launch-template", a.launch_template),
                                  ("--lease-ttl", a.lease_ttl))
                   if v is not None]
    if a.no_supervise:
        fleet_flags.append("--no-supervise")
    if fleet_flags and a.workers is None and not a.resume:
        ap.error(f"{'/'.join(fleet_flags)} configure fleet campaigns; "
                 "pass --workers (or --resume) with them")
    if a.lease_ttl is not None and a.lease_ttl <= 0:
        ap.error(f"--lease-ttl must be > 0 seconds (got {a.lease_ttl})")
    if a.hosts is not None and not _parse_hosts(a.hosts):
        ap.error(f"--hosts must be a comma list of host names "
                 f"(got {a.hosts!r})")
    if a.launch_template is not None and (
            "{root}" not in a.launch_template
            or "{worker}" not in a.launch_template):
        ap.error("--launch-template must reference {root} and {worker} "
                 f"(got {a.launch_template!r})")
    if a.launch_template is not None and "{host}" in a.launch_template \
            and a.hosts is None:
        ap.error("--launch-template references {host}; pass --hosts too")
    scen_flags = [n for n, v, d in (("--phase", a.phase, "decode"),
                                    ("--dtype", a.dtype, "native"))
                  if v != d]
    if scen_flags and (a.campaign or a.resume):
        ap.error(f"{'/'.join(scen_flags)} select the single-search "
                 "scenario; campaign grids sweep these as 'phases'/"
                 "'dtypes' axes in the spec file")
    if a.campaign and a.resume:
        ap.error("--campaign starts a new run and --resume continues an "
                 "existing one; pass exactly one")
    if a.transfer_from and a.resume:
        ap.error("--transfer-from: a resumed campaign keeps the warm-start "
                 "donors recorded in its manifest; start a new campaign to "
                 "change them")
    if a.transfer_from and not a.campaign:
        ap.error("--transfer-from warm-starts a campaign from completed "
                 "run directories; pass --campaign with it")
    for r in a.transfer_from or []:
        if not os.path.isfile(os.path.join(r, "manifest.json")):
            ap.error(f"--transfer-from: no campaign manifest under {r}")
    if a.campaign and not os.path.isfile(a.campaign):
        ap.error(f"--campaign grid file not found: {a.campaign}")
    if a.resume and not os.path.isfile(os.path.join(a.resume,
                                                    "manifest.json")):
        ap.error(f"--resume: no campaign manifest under {a.resume}")


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.1-8b")
    ap.add_argument("--mode", default="high-performance",
                    choices=["high-performance", "low-power"])
    ap.add_argument("--nodes", default="all",
                    help="comma list of nm values or 'all'")
    ap.add_argument("--episodes", type=int, default=4613)
    ap.add_argument("--method", default="sac",
                    choices=["sac", "random", "grid"])
    ap.add_argument("--out", default="experiments/dse")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=3)
    ap.add_argument("--phase", default="decode", choices=list(PHASES),
                    help="inference phase to extract the workload for: "
                         "'decode' is the per-token steady state, 'prefill' "
                         "the seq-parallel prompt pass (campaign grids take "
                         "a 'phases' list in the spec instead)")
    ap.add_argument("--dtype", default="native", choices=list(DTYPES),
                    help="datapath dtype override: 'fp8'/'int8' re-extract "
                         "the workload at a 1-byte weight format (campaign "
                         "grids take a 'dtypes' list in the spec instead)")
    ap.add_argument("--update-every", type=int, default=1)
    ap.add_argument("--engine", default="scalar", choices=["scalar", "vec"],
                    help="'vec' runs the batched VecDSEEnv engine: n-envs "
                         "parallel episodes per jit dispatch")
    ap.add_argument("--n-envs", type=int, default=64,
                    help="environments per dispatch for --engine vec")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the env batch over this many accelerator "
                         "devices (vec engine / campaigns); must divide the "
                         "batch and be <= jax.device_count().  Sharded runs "
                         "are bitwise identical to single-device runs")
    ap.add_argument("--mesh", default=None, metavar="N|auto",
                    help="alias for --devices; 'auto' takes every visible "
                         "device.  Emulate devices on CPU with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--screen-k", type=int, default=None,
                    help="candidate actions proposed per env-step once the "
                         "surrogate gate opens; only the surrogate's top-1 "
                         "survivor gets a full analytic evaluation "
                         "(default 4)")
    ap.add_argument("--gate-threshold", type=float, default=None,
                    help="Eq.-67 per-cell residual-variance threshold below "
                         "which surrogate screening activates (default 0.05)")
    ap.add_argument("--no-surrogate-gate", action="store_true",
                    help="disable surrogate-gated screening: every candidate "
                         "pays a full analytic evaluation (pre-gate behavior "
                         "is identical either way)")
    ap.add_argument("--campaign", default="",
                    help="grid spec (.yaml/.json): run a full multi-workload"
                         " x multi-node campaign instead of a single search")
    ap.add_argument("--resume", default="",
                    help="existing campaign run directory to resume "
                         "(fleet campaigns resume at fleet scope: "
                         "completed cells are reconciled and skipped, "
                         "unfinished batches are re-dealt)")
    ap.add_argument("--campaign-root", default="experiments/campaigns",
                    help="parent directory for new campaign run dirs")
    ap.add_argument("--transfer-from", action="append", default=None,
                    metavar="RUN_DIR",
                    help="completed campaign run directory whose archives "
                         "and weights warm-start this campaign and train "
                         "its persistent cost model (repeatable; see "
                         "repro.campaign.transfer).  Batches are then "
                         "packed by predicted cost so workers drain "
                         "together")
    ap.add_argument("--workers", type=int, default=None,
                    help="shard the campaign's cell batches across this "
                         "many shared-nothing worker processes "
                         "(repro.launch.fleet); with --resume, overrides "
                         "the manifest's recorded worker count")
    ap.add_argument("--hosts", default=None,
                    help="comma list of hosts for fleet workers (slot i "
                         "runs on hosts[i %% len]); implies the ssh "
                         "launch template unless --launch-template is "
                         "given; the grid file's 'hosts' key is the "
                         "fallback")
    ap.add_argument("--launch-template", default=None,
                    help="command template spawning one fleet worker, "
                         "e.g. 'ssh {host} python -m repro.launch.fleet "
                         "--root {root} --worker {worker}'; {python} "
                         "expands to the local interpreter")
    ap.add_argument("--lease-ttl", type=float, default=None,
                    help="fleet worker lease TTL in seconds (default 15): "
                         "a worker silent for longer is presumed dead and "
                         "its pending batches are re-dealt mid-run")
    ap.add_argument("--no-supervise", action="store_true",
                    help="disable the elastic fleet supervisor: dead "
                         "workers' batches are NOT re-dealt mid-run; "
                         "recover manually with --resume")
    ap.add_argument("--verbose", action="store_true")
    a = ap.parse_args(argv)
    validate_args(ap, a)
    enable_compile_cache()
    fleet = a.workers is not None or bool(
        a.resume and _read_manifest(a.resume).get("fleet"))
    if fleet:
        from repro.launch.fleet import keep_off_chip
        keep_off_chip()
    devices = _resolve_devices(ap, a, fleet=fleet)
    if devices is not None and not a.campaign and a.n_envs % devices:
        ap.error(f"--n-envs {a.n_envs} must divide evenly over "
                 f"--devices {devices}")
    if a.campaign or a.resume:
        import dataclasses
        from repro.campaign import CampaignSpec, run_campaign
        hosts = _parse_hosts(a.hosts)
        fleet_kw = dict(lease_ttl_s=a.lease_ttl,
                        supervise=not a.no_supervise)
        if a.launch_template or hosts:
            from repro.launch.fleet import make_launcher
            fleet_kw["launcher"] = make_launcher(a.launch_template, hosts)
        if a.resume:
            if fleet:
                from repro.launch.fleet import run_fleet
                run_fleet(a.resume, workers=a.workers, resume=True,
                          **fleet_kw)
            else:
                if hosts or a.launch_template or a.lease_ttl is not None \
                        or a.no_supervise:
                    ap.error(f"{a.resume} is a single-process campaign; "
                             "fleet flags need --workers N to upgrade it "
                             "to a fleet on resume")
                run_campaign(a.resume, resume=True)
        else:
            try:
                spec = CampaignSpec.from_file(a.campaign)
            except (ValueError, TypeError, RuntimeError, OSError) as e:
                ap.error(f"--campaign {a.campaign}: {e}")
            overrides = {}
            if a.screen_k is not None:
                overrides["screen_k"] = a.screen_k
            if a.gate_threshold is not None:
                overrides["gate_threshold"] = a.gate_threshold
            if a.no_surrogate_gate:
                overrides["surrogate_gate"] = False
            if devices is not None:
                overrides["devices"] = devices
            if overrides:
                spec = dataclasses.replace(spec, **overrides)
            if a.transfer_from:
                from repro.campaign import transfer as transfer_mod
                try:
                    spec = transfer_mod.with_transfer(spec, a.transfer_from)
                except (ValueError, FileNotFoundError) as e:
                    ap.error(f"--transfer-from: {e}")
            root = os.path.join(a.campaign_root, spec.name)
            if a.workers is not None:
                # any explicit --workers (including 1) runs the fleet
                # layout, matching what --resume --workers produces
                from repro.launch.fleet import run_fleet
                run_fleet(root, spec, workers=a.workers, **fleet_kw)
            else:
                run_campaign(root, spec)
        done_root = a.resume or root
        print(f"[dse] campaign archives are queryable: python -m "
              f"repro.launch.recommend --root {done_root} --node <nm> "
              f"--mode <high_perf|low_power> [--arch <zoo-id>] "
              f"(or --serve for the HTTP endpoint)")
        return
    nodes = list(NODES) if a.nodes == "all" else [
        int(x) for x in a.nodes.split(",")]
    run(a.arch, nodes=nodes, mode=a.mode, episodes=a.episodes,
        method=a.method, out_dir=a.out, seed=a.seed, seq_len=a.seq_len,
        batch=a.batch, update_every=a.update_every, verbose=a.verbose,
        engine=a.engine, n_envs=a.n_envs,
        surrogate_gate=not a.no_surrogate_gate,
        screen_k=a.screen_k, gate_threshold=a.gate_threshold,
        devices=devices, phase=a.phase, dtype=a.dtype)


if __name__ == "__main__":
    main()
