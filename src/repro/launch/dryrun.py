import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the jitted
step (the SAME object the trainer/server runs) is lowered with
ShapeDtypeStruct inputs, compiled for the production mesh, and its
memory_analysis / cost_analysis / collective schedule are recorded for the
roofline (EXPERIMENTS.md §Dry-run, §Roofline).

Usage:
    python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh pod
    python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
    python -m repro.launch.dryrun --all --jobs 4      # subprocess per cell

The XLA_FLAGS and JAX_PLATFORMS lines above MUST stay the first
statements — jax locks the platform and the device count at first init.
The CPU pin keeps this tool (and every --all child, which inherits the
environment) off an attached accelerator, whose few devices could not
hold the production mesh anyway. Smoke tests / benches never import this
module.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import List, Optional, Tuple

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def model_flops_for(cfg, sp) -> float:
    """MODEL_FLOPS: 6·N·D train (3 matmul passes), 2·N·D forward-only.
    MoE: active params only."""
    n = cfg.active_param_count()
    if sp.kind == "train":
        return 6.0 * n * sp.global_batch * sp.seq_len
    if sp.kind == "prefill":
        return 2.0 * n * sp.global_batch * sp.seq_len
    return 2.0 * n * sp.global_batch          # decode: one token


def run_cell(arch: str, shape: str, mesh_name: str,
             recipe_override: Optional[str] = None,
             extra: Optional[dict] = None,
             grad_reduce_dtype: Optional[str] = None,
             microbatches: int = 0) -> dict:
    import jax
    from ..configs import SHAPES, get_config, input_specs
    from ..roofline import analyze_compiled
    from ..train import TrainConfig, make_decode_step, make_prefill_step, \
        make_train_step
    from .mesh import make_production_mesh

    cfg = get_config(arch)
    if extra:
        cfg = cfg.replace(**{k: v for k, v in extra.items()
                             if hasattr(cfg, k)})
    sp = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    specs = input_specs(cfg, shape)
    t0 = time.perf_counter()

    with jax.set_mesh(mesh):
        if sp.kind == "train":
            tcfg = TrainConfig(recipe=recipe_override,
                               grad_reduce_dtype=grad_reduce_dtype,
                               microbatches=microbatches)
            bundle = make_train_step(cfg, tcfg,
                                     mesh, sp.global_batch, sp.seq_len)
            import jax.numpy as jnp
            from ..models import init_params
            from ..optim import init_opt_state
            pshape = jax.eval_shape(
                lambda: init_params(cfg, jax.random.PRNGKey(0)))
            oshape = jax.eval_shape(lambda: init_opt_state(
                jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), pshape)))
            lowered = bundle.fn.lower(pshape, oshape, specs)
        elif sp.kind == "prefill":
            bundle = make_prefill_step(cfg, mesh, sp.global_batch,
                                       sp.seq_len, recipe_name=recipe_override)
            pshape = bundle.abstract_inputs[0]
            args = [pshape, specs["tokens"]]
            if cfg.n_prefix_embeds:
                args.append(specs["prefix_embeds"])
            lowered = bundle.fn.lower(*args)
        else:  # decode
            bundle = make_decode_step(cfg, mesh, sp.global_batch,
                                      sp.seq_len, recipe_name=recipe_override)
            pshape = bundle.abstract_inputs[0]
            lowered = bundle.fn.lower(pshape, specs["cache"],
                                      specs["tokens"], specs["pos"])
        compiled = lowered.compile()

    dt = time.perf_counter() - t0
    res = analyze_compiled(
        compiled, arch=arch, shape=shape, mesh_name=mesh_name,
        recipe=(recipe_override or bundle.recipe.name),
        model_flops=model_flops_for(cfg, sp),
        n_devices=mesh.devices.size, compile_seconds=dt)
    print(compiled.memory_analysis())
    d = res.to_json()
    d["ok"] = True
    return d


def cells(mesh_sel: str) -> List[Tuple[str, str, str]]:
    from ..configs import ARCH_IDS, applicable_shapes, get_config
    meshes = {"pod": ["pod"], "multipod": ["multipod"],
              "both": ["pod", "multipod"]}[mesh_sel]
    out = []
    for arch in ARCH_IDS:
        for shape in applicable_shapes(get_config(arch)):
            for m in meshes:
                out.append((arch, shape, m))
    return out


def result_path(arch: str, shape: str, mesh_name: str, tag: str = "") -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    return os.path.join(RESULTS_DIR,
                        f"{arch}__{shape}__{mesh_name}{suffix}.json")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--recipe", default=None)
    ap.add_argument("--tag", default="", help="result filename suffix")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (ints only)")
    ap.add_argument("--grad-reduce-dtype", default=None)
    ap.add_argument("--microbatches", type=int, default=0)
    args = ap.parse_args()

    extra = {}
    for kv in args.set:
        k, v = kv.split("=")
        extra[k] = int(v) if v.lstrip("-").isdigit() else v
    if args.recipe:
        # the recipe name is part of the experiment identity
        pass

    if not args.all:
        assert args.arch and args.shape
        meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
        ok = True
        for m in meshes:
            path = result_path(args.arch, args.shape, m, args.tag)
            try:
                d = run_cell(args.arch, args.shape, m, args.recipe, extra,
                             grad_reduce_dtype=args.grad_reduce_dtype,
                             microbatches=args.microbatches)
            except Exception as e:
                d = {"arch": args.arch, "shape": args.shape, "mesh": m,
                     "ok": False, "error": f"{type(e).__name__}: {e}",
                     "trace": traceback.format_exc()[-2000:]}
            with open(path, "w") as f:
                json.dump(d, f, indent=1, default=str)
            status = "OK" if d.get("ok") else f"FAIL ({d.get('error')})"
            print(f"[dryrun] {args.arch} x {args.shape} x {m}: {status}")
            ok = ok and bool(d.get("ok"))
        return 0 if ok else 1

    # --all: one subprocess per cell (isolation + bounded memory)
    todo = cells(args.mesh)
    failures = []
    for arch, shape, m in todo:
        path = result_path(arch, shape, m, args.tag)
        if os.path.exists(path) and not args.force:
            with open(path) as f:
                if json.load(f).get("ok"):
                    print(f"[dryrun] {arch} x {shape} x {m}: cached")
                    continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--mesh", m]
        if args.recipe:
            cmd += ["--recipe", args.recipe]
        if args.tag:
            cmd += ["--tag", args.tag]
        r = subprocess.run(cmd, capture_output=True, text=True)
        if r.returncode != 0:
            print(r.stdout[-1500:], r.stderr[-1500:])
            failures.append((arch, shape, m))
        else:
            print(r.stdout.strip().splitlines()[-1])
    print(f"[dryrun] done: {len(todo) - len(failures)}/{len(todo)} OK")
    for f3 in failures:
        print("  FAILED:", f3)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
