import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# This is the ONLY entrypoint that fakes 512 devices (multi-pod dry-run);
# tests and benchmarks see the real device count.

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax               # noqa: E402

from repro.configs import ARCH_IDS, get_config          # noqa: E402
from repro.launch import shapes as shp                   # noqa: E402
from repro.launch.hlo_analysis import analyze_collectives  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import build_prefill, build_serve, build_train  # noqa: E402

DEFAULT_OUT = "experiments/dryrun"
ASSIGNED = [a for a in ARCH_IDS if a not in ("llama3.1-8b", "smolvlm")]


def _mem_dict(ma) -> Dict[str, float]:
    return dict(
        argument_bytes=float(ma.argument_size_in_bytes),
        output_bytes=float(ma.output_size_in_bytes),
        temp_bytes=float(ma.temp_size_in_bytes),
        alias_bytes=float(ma.alias_size_in_bytes),
        generated_code_bytes=float(ma.generated_code_size_in_bytes),
        peak_bytes=float(ma.argument_size_in_bytes
                         + ma.output_size_in_bytes
                         + ma.temp_size_in_bytes
                         - ma.alias_size_in_bytes),
    )


def model_flops_analytic(cfg, shape: str) -> Dict[str, float]:
    """MODEL_FLOPS per §Roofline: 6·N·D train (N = active non-embedding
    params, D = tokens), 2·N·D decode/prefill."""
    info = shp.SHAPES[shape]
    pc = cfg.param_counts()
    n_embed = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    n_active = max(pc["active"] - n_embed, 1.0)
    if info["kind"] == "train":
        tokens = info["seq_len"] * info["global_batch"]
        return dict(model_flops=6.0 * n_active * tokens, tokens=tokens)
    if info["kind"] == "prefill":
        tokens = info["seq_len"] * info["global_batch"]
        return dict(model_flops=2.0 * n_active * tokens, tokens=tokens)
    tokens = info["global_batch"]
    return dict(model_flops=2.0 * n_active * tokens, tokens=tokens)


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             save_hlo: bool = True) -> Dict:
    cfg = get_config(arch)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: Dict = dict(arch=arch, shape=shape, mesh=mesh_name)
    ok, reason = shp.cell_supported(cfg, shape)
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape}__{mesh_name}"
    if not ok:
        rec.update(status="SKIP", reason=reason)
        _write(out_dir, tag, rec)
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        kind = shp.SHAPES[shape]["kind"]
        with jax.set_mesh(mesh):
            if kind == "train":
                fn, sds, in_sh, out_sh = build_train(cfg, mesh, shape)
                state_sds, batch_sds = sds
                jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                                 donate_argnums=(0,))
                lowered = jitted.lower(state_sds, batch_sds)
            elif kind == "prefill":
                fn, sds, in_sh, out_sh = build_prefill(cfg, mesh, shape)
                p_sds, inputs = sds
                args = [p_sds, inputs["tokens"]]
                shard_args = [in_sh[0], in_sh[1]["tokens"]]
                if "ctx" in inputs:
                    args.append(inputs["ctx"])
                    shard_args.append(in_sh[1]["ctx"])
                jitted = jax.jit(fn, in_shardings=tuple(shard_args),
                                 out_shardings=out_sh)
                lowered = jitted.lower(*args)
            else:
                fn, sds, in_sh, out_sh = build_serve(cfg, mesh, shape)
                p_sds, cache_sds, inputs = sds
                jitted = jax.jit(
                    fn,
                    in_shardings=(in_sh[0], in_sh[1], in_sh[2]["token"],
                                  in_sh[2]["pos"]),
                    out_shardings=out_sh, donate_argnums=(1,))
                lowered = jitted.lower(p_sds, cache_sds, inputs["token"],
                                       inputs["pos"])
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        n_dev = mesh.size
        colls = analyze_collectives(hlo, n_devices=n_dev)
        rec.update(
            status="OK",
            n_devices=n_dev,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory=_mem_dict(ma),
            cost=dict(flops_per_device=float(ca.get("flops", 0.0)),
                      bytes_per_device=float(ca.get("bytes accessed", 0.0)),
                      transcendentals=float(ca.get("transcendentals", 0.0))),
            collectives=colls.summary(),
            analytic=model_flops_analytic(cfg, shape),
            hlo_chars=len(hlo),
        )
        if save_hlo:
            with open(os.path.join(out_dir, tag + ".hlo.txt"), "w") as f:
                f.write(hlo)
        print(f"[OK]   {tag}: compile {t_compile:.1f}s, "
              f"peak/dev {rec['memory']['peak_bytes']/2**30:.2f} GiB, "
              f"wire/dev {colls.total_wire_bytes/2**30:.3f} GiB")
    except Exception as e:  # a failure here is a bug in our sharding
        rec.update(status="FAIL", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
    _write(out_dir, tag, rec)
    return rec


def _write(out_dir: str, tag: str, rec: Dict) -> None:
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all",
                    help=f"one of {ASSIGNED} or 'all'")
    ap.add_argument("--shape", default="all",
                    help=f"one of {list(shp.SHAPES)} or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--no-hlo", action="store_true")
    args = ap.parse_args()

    archs = ASSIGNED if args.arch == "all" else [args.arch]
    cells = list(shp.SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    n_fail = 0
    for arch in archs:
        for shape in cells:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, args.out,
                               save_hlo=not args.no_hlo)
                n_fail += rec["status"] == "FAIL"
    print(f"done; failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
