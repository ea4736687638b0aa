import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 host
platform devices stand in for two v5e-256 pods.  For each cell we jit the
real train_step / serve_step against ShapeDtypeStruct inputs with the
production shardings, ``.lower().compile()`` it, and record
``memory_analysis()`` / ``cost_analysis()`` plus the HLO collective mix
into a JSON.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cells, get_config
from repro.configs.base import TrainConfig
from repro.data.tokens import input_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.shardspecs import (batch_shardings, cache_shardings,
                                     state_shardings)
from repro.models import model as M
from repro.models import sharding
from repro.optim import adamw as O
from repro.train import steps as S

_COLL_RE = re.compile(
    r"(\w+)\[([0-9,]*)\][^=]*\b"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")

_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "f64": 8, "s64": 8, "f8e4m3fn": 1,
          "f8e5m2": 1, "s16": 2, "u16": 2}


def parse_collectives(hlo: str) -> dict:
    """Sum result-shape bytes of every collective op in (partitioned) HLO.

    Shapes in the partitioned module are per-device; ops inside while
    bodies are counted once per appearance.
    """
    out: dict[str, dict] = {}
    for m in _COLL_RE.finditer(hlo):
        dty, dims, kind = m.group(1), m.group(2), m.group(3)
        if dty not in _BYTES:
            continue
        n = _BYTES[dty]
        for d in dims.split(","):
            if d:
                n *= int(d)
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += n
    return out


# best-known beyond-paper flags per arch (see EXPERIMENTS.md §Perf);
# all exact except route_groups (routing-local variant)
OPTIMIZED = {
    "deepseek-v3-671b": {"ep2d": True, "ce_chunk": 512, "momentum": False,
                         "route_groups": 8, "route_top_groups": 4},
    "whisper-large-v3": {"vocab_pad": 256, "head_pad": 32, "ce_chunk": 512},
    "internvl2-1b": {"vocab_pad": 256, "ce_chunk": 512},
    "*": {"ce_chunk": 512},
}


def optimized_overrides(arch: str, kind: str) -> dict:
    over = dict(OPTIMIZED.get(arch, OPTIMIZED["*"]))
    if kind != "train":  # train-only knobs
        over.pop("ce_chunk", None)
        over.pop("momentum", None)
    return over


def _train_config(cfg, momentum: bool = True) -> TrainConfig:
    # adafactor for the 671B config (factored 2nd moment), adamw otherwise
    opt = "adafactor" if cfg.name.startswith("deepseek") else "adamw"
    return TrainConfig(optimizer=opt, b1=0.9 if momentum else 0.0)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               overrides: dict | None = None):
    """Build (lowered, mesh, cfg) for one cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    param_dtype = jnp.bfloat16
    over = dict(overrides or {})
    ep2d = over.pop("ep2d", False)
    momentum = over.pop("momentum", True)
    if shape.kind == "train":
        over.setdefault("remat", "full")
        over.setdefault("seq_shard", True)
    else:
        over.setdefault("remat", "none")
        over.setdefault("mtp", False)   # MTP head is train-only
    cfg = cfg.replace(**over)

    mesh = make_production_mesh(multi_pod=multi_pod)
    sharding.set_mesh(mesh)
    sharding.set_ep2d(ep2d)
    specs = input_specs(cfg, shape, dtype=param_dtype)
    b_sh = batch_shardings(cfg, mesh, specs)

    if shape.kind == "train":
        tc = _train_config(cfg, momentum=momentum)
        state_shape = jax.eval_shape(
            lambda: S.init_state(cfg, tc, jax.random.PRNGKey(0), param_dtype))
        st_sh = state_shardings(state_shape, mesh)
        step = S.build_train_step(cfg, tc)
        fn = jax.jit(step, in_shardings=(st_sh, b_sh), donate_argnums=(0,))
        args = (state_shape, specs)
    elif shape.kind == "prefill":
        params_shape = jax.eval_shape(
            lambda: M.init_params(cfg, jax.random.PRNGKey(0), param_dtype))
        p_sh = sharding.param_shardings(params_shape, mesh)
        fn = jax.jit(lambda params, batch: M.forward(params, cfg, batch),
                     in_shardings=(p_sh, b_sh))
        args = (params_shape, specs)
    else:
        B, L = shape.global_batch, shape.seq_len
        params_shape = jax.eval_shape(
            lambda: M.init_params(cfg, jax.random.PRNGKey(0), param_dtype))
        p_sh = sharding.param_shardings(params_shape, mesh)
        cache_shape = jax.eval_shape(
            lambda: M.init_cache(cfg, B, L, param_dtype))
        c_sh = cache_shardings(cfg, mesh, cache_shape, B, L)
        step = S.build_serve_step(cfg)
        tok_sh = b_sh["tokens"]
        pos_sh = NamedSharding(mesh, P())
        fn = jax.jit(step, in_shardings=(p_sh, c_sh, tok_sh, pos_sh),
                     donate_argnums=(1,))
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        args = (params_shape, cache_shape, specs["tokens"], pos)

    with mesh:
        lowered = fn.lower(*args)
    return lowered, mesh, cfg


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             overrides: dict | None = None) -> dict:
    t0 = time.time()
    lowered, mesh, cfg = lower_cell(arch, shape_name, multi_pod=multi_pod,
                                    overrides=overrides)
    t_lower = time.time() - t0
    with mesh:
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    colls = parse_collectives(compiled.as_text())
    rec = {
        "arch": arch, "shape": shape_name,
        "overrides": dict(overrides or {}),
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.devices.size,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": cost.get("flops", -1.0),
        "bytes_accessed_per_device": cost.get("bytes accessed", -1.0),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
        "collectives": colls,
        "ok": True,
    }
    sharding.set_mesh(None)
    sharding.set_ep2d(False)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell for the chosen mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply best-known per-arch flags (§Perf)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    if args.all:
        todo = [(a, s) for a in ARCHS for s in cells(a)]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        todo = [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    if args.out and os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results if r.get("ok")}

    for arch, shape in todo:
        for mp in meshes:
            meshname = "2x16x16" if mp else "16x16"
            if (arch, shape, meshname) in done:
                print(f"[skip] {arch} {shape} {meshname} (cached)")
                continue
            # drop stale failed records for this cell before re-running
            results = [r for r in results
                       if (r["arch"], r["shape"], r["mesh"])
                       != (arch, shape, meshname)]
            print(f"[dryrun] {arch} {shape} {meshname} ...", flush=True)
            over = (optimized_overrides(arch, SHAPES[shape].kind)
                    if args.optimized else None)
            try:
                rec = run_cell(arch, shape, multi_pod=mp, overrides=over)
                print(f"  ok: flops/dev={rec['flops_per_device']:.3e} "
                      f"peak={rec['peak_bytes']/2**30:.2f}GiB "
                      f"lower={rec['lower_s']}s compile={rec['compile_s']}s",
                      flush=True)
            except Exception as e:  # noqa: BLE001 — record and continue
                rec = {"arch": arch, "shape": shape, "mesh": meshname,
                       "ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(f"  FAIL: {rec['error']}", flush=True)
            results.append(rec)
            if args.out:
                json.dump(results, open(args.out, "w"), indent=1)
    n_ok = sum(r.get("ok", False) for r in results)
    print(f"done: {n_ok}/{len(results)} cells ok")


if __name__ == "__main__":
    main()
