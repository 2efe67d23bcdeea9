import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # virtual host devices; never the TPU
# The lines above MUST run before any jax import (jax locks the device
# count at first init).  REPRO_DRYRUN_DEVICES overrides for local debugging.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware: the sharding config is coherent
(SPMD partitioner accepts it), the per-device memory fits the v5e budget
(memory_analysis), and it yields the FLOP/byte/collective numbers the
roofline analysis (EXPERIMENTS.md §Roofline) consumes.

Usage:
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--mesh both] [--outdir results/dryrun]
"""
import argparse
import json
import time
import traceback
from typing import Dict, List, Optional, Tuple

import jax

from repro.config import SHAPES, OptimizerConfig, replace
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.launch.mesh import (
    arch_parallel_config, arch_rules, make_production_mesh,
)
from repro.launch.steps import build_setup


def applicable_shapes(arch: str) -> List[Tuple[str, str]]:
    """[(shape_name, kind)] for an arch; long_500k only for sub-quadratic."""
    cfg = get_config(arch)
    cells = [("train_4k", "train"), ("prefill_32k", "prefill"),
             ("decode_32k", "decode")]
    if cfg.supports_long_context:
        cells.append(("long_500k", "decode"))
    return cells


def arch_optimizer(arch: str) -> OptimizerConfig:
    if arch in ("grok-1-314b", "granite-34b", "llava-next-34b"):
        return OptimizerConfig(name="sgdm", lr=1e-2, momentum=0.9)
    return OptimizerConfig(name="adamw", lr=3e-4)


def run_cell(arch: str, shape_name: str, mesh_kind: str, outdir: str, *,
             save_hlo: bool = True, overrides: Optional[Dict] = None) -> Dict:
    """Lower + compile one cell; returns (and writes) the result record."""
    multi_pod = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    kind = dict(applicable_shapes(arch)).get(shape_name)
    if kind is None:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skip(full-attn)"}
    overrides = overrides or {}
    parallel = arch_parallel_config(
        arch, optimized=overrides.get("optimized", False))
    if "parallel" in overrides:
        parallel = replace(parallel, **overrides["parallel"])
    if overrides.get("tp_pad_heads"):
        from repro.launch.mesh import mesh_axis_size
        cfg = replace(cfg, tp_pad_heads=mesh_axis_size(mesh, "model"))
    rules = arch_rules(cfg, mesh, parallel, multi_pod=multi_pod,
                       decode=(kind == "decode"), batch=shape.global_batch,
                       tp_pad_heads=overrides.get("tp_pad_heads", False))
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                 "kind": kind, "devices": int(mesh.devices.size),
                 "params": cfg.param_count(),
                 "params_active": cfg.param_count(active_only=True)}
    t0 = time.time()
    try:
        with mesh:
            setup = build_setup(kind, cfg, shape, rules, parallel,
                                arch_optimizer(arch),
                                **overrides.get("setup_kw", {}))
            # donate the persistent state (train state / kv cache) so XLA
            # aliases the update in place instead of double-buffering
            donate = (0,) if kind == "train" else (1,)
            jitted = jax.jit(setup.step_fn,
                             in_shardings=setup.in_shardings,
                             out_shardings=setup.out_shardings,
                             donate_argnums=donate)
            lowered = jitted.lower(*setup.abstract_args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        rec.update(status="ok", lower_s=round(t_lower, 1),
                   compile_s=round(t_compile, 1))
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(ma, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes")
                if hasattr(ma, k)
            }
        except Exception as e:  # CPU backend may not expose everything
            rec["memory"] = {"error": str(e)}
        try:
            ca = compiled.cost_analysis()
            rec["cost"] = {k: float(v) for k, v in ca.items()
                           if isinstance(v, (int, float))}
        except Exception as e:
            rec["cost"] = {"error": str(e)}
        if save_hlo:
            os.makedirs(outdir, exist_ok=True)
            hlo_path = os.path.join(
                outdir, f"{arch}__{shape_name}__{mesh_kind}.hlo.txt")
            with open(hlo_path, "w") as f:
                f.write(compiled.as_text())
            rec["hlo_file"] = hlo_path
    except Exception as e:
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    rec["total_s"] = round(time.time() - t0, 1)
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(
            outdir, f"{arch}__{shape_name}__{mesh_kind}.json"), "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--outdir", default="results/dryrun")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply the §Perf hillclimb settings (head padding, "
                         "microbatching) on top of the current code")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells: List[Tuple[str, str, str]] = []
    if args.all:
        for arch in ASSIGNED_ARCHS:
            for shape_name, _ in applicable_shapes(arch):
                for m in meshes:
                    cells.append((arch, shape_name, m))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        for m in meshes:
            cells.append((args.arch, args.shape, m))

    ok = fail = 0
    for arch, shape_name, m in cells:
        out = os.path.join(args.outdir)
        path = os.path.join(out, f"{arch}__{shape_name}__{m}.json")
        if args.all and os.path.exists(path):
            with open(path) as f:
                prev = json.load(f)
            if prev.get("status") == "ok":
                print(f"[cached] {arch} {shape_name} {m}")
                ok += 1
                continue
        ov = None
        if args.optimized:
            ov = {"tp_pad_heads": True, "optimized": True}
        rec = run_cell(arch, shape_name, m, out, save_hlo=not args.no_hlo,
                       overrides=ov)
        tag = rec["status"]
        ok += tag == "ok"
        fail += tag == "fail"
        print(f"[{tag}] {arch} {shape_name} {m} "
              f"compile={rec.get('compile_s', '-')}s "
              f"{rec.get('error', '')}", flush=True)
        if rec.get("memory") and "temp_size_in_bytes" in rec.get("memory", {}):
            mm = rec["memory"]
            print(f"        mem: args={mm['argument_size_in_bytes']/2**30:.2f}GiB "
                  f"temp={mm['temp_size_in_bytes']/2**30:.2f}GiB "
                  f"out={mm['output_size_in_bytes']/2**30:.2f}GiB", flush=True)
    print(f"dry-run complete: {ok} ok, {fail} fail")


if __name__ == "__main__":
    main()
