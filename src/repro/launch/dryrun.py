import os
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    # append, never overwrite: a user-supplied XLA_FLAGS (tuning flags,
    # dump dirs) must survive; an explicit device count wins outright
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count"
                                 "=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture x input shape) cell
on the production meshes, proving the distribution config is coherent.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
        --shape train_4k --multi-pod --json out.json

The very first lines above force 512 host devices BEFORE any jax import —
jax locks the device count at first init (see system notes).  Do not move
them, and do not replicate this env var anywhere global.
"""
import argparse
import json
import re
import sys
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs import applicable_shapes, ARCH_NAMES, get_config, shape_by_name
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import Plan
from repro.launch import specs as S
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.models.moe import EPSpec
from repro.serving.step import cache_shape, make_decode_step, make_prefill_step
from repro.training.optimizer import OptConfig
from repro.training.step import make_train_step, train_state_shape

COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)


def _opt_config(cfg: ModelConfig) -> OptConfig:
    return OptConfig(state_dtype=cfg.optimizer_state_dtype)


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Build + lower one (arch, shape) cell on a mesh.  Returns lowered."""
    plan = Plan(mesh, cfg)
    ep = (EPSpec(mesh, batch_axes(mesh)) if cfg.moe is not None else None)
    if shape.kind == "train":
        oc = _opt_config(cfg)
        step = make_train_step(cfg, oc, constrain=plan.constrain, ep=ep)
        state_shape = train_state_shape(cfg, oc)
        state_sh = {
            "params": plan.param_shardings(state_shape["params"]),
            "opt": {
                "mu": plan.param_shardings(state_shape["opt"]["mu"]),
                "nu": plan.param_shardings(state_shape["opt"]["nu"]),
                "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            },
        }
        batch_shape = S.train_batch_specs(cfg, shape)
        batch_sh = plan.batch_shardings(batch_shape)
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     donate_argnums=(0,))
        return fn.lower(state_shape, batch_shape)

    params_shape = jax.eval_shape(
        lambda: __import__("repro.models", fromlist=["init_params"]).init_params(
            cfg, jax.random.key(0)))
    params_sh = plan.param_shardings(params_shape)

    if shape.kind == "prefill":
        step = make_prefill_step(cfg, max_len=shape.seq_len,
                                 constrain=plan.constrain, ep=ep)
        batch_shape = S.prefill_batch_specs(cfg, shape)
        batch_sh = plan.batch_shardings(batch_shape)
        fn = jax.jit(step, in_shardings=(params_sh, batch_sh))
        return fn.lower(params_shape, batch_shape)

    # decode
    step = make_decode_step(cfg, constrain=plan.constrain, ep=ep)
    cache = cache_shape(cfg, shape.global_batch, shape.seq_len,
                        enc_len=S.enc_len_for(cfg, shape))
    cache_sh = plan.cache_shardings(cache)
    tok = S.decode_token_specs(cfg, shape)
    tok_sh = plan.batch_shardings(tok)
    fn = jax.jit(step, in_shardings=(params_sh, cache_sh, tok_sh),
                 donate_argnums=(1,))
    return fn.lower(params_shape, cache, tok)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum per-device operand bytes of collective ops in SPMD HLO, with ring
    cost factors left to the caller."""
    out: Dict[str, float] = {}
    # lines look like: %all-reduce.5 = bf16[1024,512]{1,0} all-reduce(...)
    for m in re.finditer(
            r"= *([a-z0-9_]+)\[([0-9,]*)\][^ ]* (all-gather|all-reduce|"
            r"reduce-scatter|all-to-all|collective-permute)", hlo_text):
        dtype_s, dims_s, op = m.groups()
        bits = {"f32": 32, "bf16": 16, "f16": 16, "s32": 32, "u32": 32,
                "s8": 8, "u8": 8, "pred": 8, "f64": 64, "s64": 64,
                "u64": 64, "s16": 16, "u16": 16}.get(dtype_s, 32)
        n = 1
        if dims_s:
            for d in dims_s.split(","):
                n *= int(d)
        out[op] = out.get(op, 0.0) + n * bits / 8
    return out


def analyze(lowered, compile_also: bool = True) -> Dict[str, Any]:
    info: Dict[str, Any] = {}
    t0 = time.time()
    compiled = lowered.compile()
    info["compile_s"] = round(time.time() - t0, 1)
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    info["flops_per_device"] = float(ca.get("flops", 0.0))
    info["bytes_per_device"] = float(ca.get("bytes accessed", 0.0))
    ma = compiled.memory_analysis()
    info["arg_bytes"] = int(ma.argument_size_in_bytes)
    info["temp_bytes"] = int(ma.temp_size_in_bytes)
    info["out_bytes"] = int(ma.output_size_in_bytes)
    info["peak_bytes_per_device"] = (info["arg_bytes"] + info["temp_bytes"]
                                     + info["out_bytes"])
    hlo = compiled.as_text()
    info["collective_bytes"] = collective_bytes(hlo)
    info["n_collectives"] = len(COLLECTIVE_RE.findall(hlo))
    return info


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = shape_by_name(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": "x".join(str(s) for s in
                                            tuple(mesh.shape.values()))}
    t0 = time.time()
    with mesh:
        lowered = lower_cell(cfg, shape, mesh)
        rec["lower_s"] = round(time.time() - t0, 1)
        rec.update(analyze(lowered))
    rec["ok"] = True
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_NAMES))
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_NAMES)
    results = []
    failures = 0
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([shape_by_name(args.shape)] if args.shape
                  else applicable_shapes(cfg))
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape.name} x {'2x16x16' if mp else '16x16'}"
                try:
                    rec = run_cell(arch, shape.name, mp)
                    print(f"[ok] {tag}: lower={rec['lower_s']}s "
                          f"compile={rec['compile_s']}s "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"peak={rec['peak_bytes_per_device']/2**30:.2f}GiB "
                          f"colls={rec['n_collectives']}")
                except Exception as e:  # noqa: BLE001 - report and continue
                    failures += 1
                    rec = {"arch": arch, "shape": shape.name,
                           "multi_pod": mp, "ok": False, "error": repr(e)[:500]}
                    print(f"[FAIL] {tag}: {repr(e)[:300]}")
                results.append(rec)
                sys.stdout.flush()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    print(f"done: {len(results) - failures}/{len(results)} cells ok")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
