import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The first two lines above MUST run before any other import (jax locks the
device count on first init). Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
        --shape train_4k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json

Per cell it prints/records compiled.memory_analysis() (fits-in-HBM proof),
compiled.cost_analysis() (FLOPs/bytes, kept beside the roofline terms),
and the collective byte breakdown parsed from the HLO.
"""

import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs.base import (
    ALL_SHAPES,
    ARCH_IDS,
    get_config,
    get_shape,
    get_train_config,
)
from repro.launch.fabric import design_mixing_matrix
from repro.launch.mesh import make_production_mesh, num_agents
from repro.launch.train import build_train_artifacts
from repro.models import model as M
from repro.roofline import analysis as roofline


def _memory_summary(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for key in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "peak_memory_in_bytes",
    ):
        val = getattr(ma, key, None)
        if val is not None:
            out[key] = int(val)
    return out


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    gossip: str = "auto",
    verbose: bool = True,
) -> dict:
    cfg = get_config(arch)
    tcfg = get_train_config(arch)
    if gossip != "auto":
        import dataclasses as _dc

        tcfg = _dc.replace(tcfg, gossip=gossip)
    shape = get_shape(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"

    t0 = time.perf_counter()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
    }
    try:
        with jax.set_mesh(mesh):
            m = num_agents(mesh, tcfg.agent_layout)
            w = None
            if m > 1:
                # κ = per-agent parameter bytes shipped per gossip
                # exchange (bf16 params / TP shards).
                kappa = M.parameter_count(cfg) * 2 / mesh.shape["model"]
                w, _ = design_mixing_matrix(
                    m, pods=(2 if multi_pod else 1), kappa_bytes=kappa
                )
            art = build_train_artifacts(cfg, tcfg, shape, mesh, w)
            lowered = art.lower()
            record["num_agents"] = m
            record["gossip_mode"] = tcfg.gossip

            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower

            cost = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            gossip_edges = 0
            if m > 1:
                w_off = w - np.diag(np.diag(w))
                gossip_edges = int(np.count_nonzero(np.abs(w_off) > 1e-12))
            rep = roofline.report(
                arch=arch,
                shape=shape,
                cfg=cfg,
                mesh_name=mesh_name,
                chips=chips,
                cost=cost,
                hlo_text=hlo,
                num_agents=m,
                remat=True,
                tcfg=tcfg,
                mesh_shape={a: mesh.shape[a] for a in mesh.axis_names},
                gossip_directed_edges=gossip_edges,
            )
            record.update(
                status="ok",
                lower_s=round(t_lower, 1),
                compile_s=round(t_compile, 1),
                memory=_memory_summary(compiled),
                cost_flops=float(cost.get("flops", 0.0) or 0.0),
                cost_bytes=float(cost.get("bytes accessed", 0.0) or 0.0),
                roofline=rep.to_dict(),
            )
            if verbose:
                mem = record["memory"]
                print(
                    f"[ok] {arch} × {shape_name} × {mesh_name}: "
                    f"lower {t_lower:.0f}s compile {t_compile:.0f}s  "
                    f"dominant={rep.dominant} bound={rep.bound_s*1e3:.2f}ms "
                    f"roofline={rep.roofline_fraction:.2%} "
                    f"coll={rep.collective_bytes_per_chip/1e6:.0f}MB/chip "
                    f"temp={mem.get('temp_size_in_bytes', 0)/1e9:.1f}GB"
                )
    except Exception as e:
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[ERR] {arch} × {shape_name} × {mesh_name}: {record['error']}")
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=[s.name for s in ALL_SHAPES])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="all archs × shapes")
    ap.add_argument("--gossip", default="auto",
                    choices=["auto", "sparse", "dense", "allreduce"])
    ap.add_argument("--out", default=None, help="append JSON records here")
    args = ap.parse_args()

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = (
        [s.name for s in ALL_SHAPES]
        if (args.all or not args.shape)
        else [args.shape]
    )
    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]
    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                records.append(run_cell(arch, shape, mp, gossip=args.gossip))
    if args.out:
        existing = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                existing = json.load(f)
        # de-dup on (arch, shape, mesh): new records win
        keys = {(r["arch"], r["shape"], r["mesh"]) for r in records}
        existing = [
            r for r in existing
            if (r["arch"], r["shape"], r["mesh"]) not in keys
        ]
        with open(args.out, "w") as f:
            json.dump(existing + records, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"cells: {n_ok} ok, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
