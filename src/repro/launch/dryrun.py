import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST precede every other import (jax locks the
# device count on first init), so no `from __future__ import annotations`.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding rules produce a partitionable program (SPMD succeeds),
  * it fits (memory_analysis: per-device bytes),
  * and it yields the roofline inputs (cost_analysis FLOPs/bytes are
    PER-DEVICE post-partition on the CPU backend; collective bytes are
    parsed from the compiled HLO).

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
  python -m repro.launch.dryrun --all --out benchmarks/results/dryrun.json
  python -m repro.launch.dryrun --all --multi-pod        # 2x16x16 proof
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, SKIPS, get
from repro.dist import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.train.optimizer import AdamW, cosine_schedule, opt_state_specs
from repro.train import train_step as TS

# HLO collective ops whose operand bytes feed the roofline collective term.
_COLL_RE = re.compile(
    r"(\w+(?:\.\d+)?)\s*=\s*(\S+)\s+(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def collective_bytes(hlo_text: str):
    """Sum output-shape bytes of every collective op in the compiled HLO."""
    totals = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, op = m.group(2), m.group(3)
        size = 0
        for dt, dims in _SHAPE_RE.findall(shape_str):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            size += n * _DTYPE_BYTES[dt]
        totals[op] = totals.get(op, 0) + size
    totals["total"] = sum(v for k, v in totals.items() if k != "total")
    return totals


def _shard_batch(shapes, names, mesh):
    return jax.tree.map(
        lambda s, n: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=SH.act_sharding(s.shape, n, mesh)),
        shapes, names,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, tuple)))


def lower_cell(cfg: ModelConfig, shape_name: str, mesh, microbatches: int = 1):
    """Lower + compile one (arch, shape) cell on `mesh`."""
    seq_len, global_batch, kind = SHAPES[shape_name]
    act_rules, param_rules = SH.select_rules(cfg)
    with SH.axis_rules(mesh, act_rules=act_rules, param_rules=param_rules):
        if kind in ("train", "prefill"):
            inputs = M.input_specs(cfg, seq_len, global_batch, kind)
            in_names = M.input_spec_names(cfg, kind)
            batch_sds = _shard_batch(inputs, in_names, mesh)

            params_shape = jax.eval_shape(
                lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
            p_spec = SH.shard_tree(params_shape, M.param_specs(cfg), mesh)
            params_sds = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                params_shape, p_spec)

            if kind == "train":
                opt = AdamW(lr=cosine_schedule(3e-4, 100, 10000))
                opt_shape = jax.eval_shape(opt.init, params_shape)
                o_spec = SH.shard_tree(
                    opt_shape, opt_state_specs(M.param_specs(cfg)), mesh)
                opt_sds = jax.tree.map(
                    lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sh),
                    opt_shape, o_spec)
                state_sds = TS.TrainState(params_sds, opt_sds)
                step = TS.make_train_step(cfg, opt, microbatches=microbatches)
                fn = jax.jit(step, donate_argnums=(0,))
                lowered = fn.lower(state_sds, batch_sds)
            else:  # prefill: logits only (cache write shown by decode cells)
                fn = jax.jit(lambda p, b: M.forward(cfg, p, b)[0])
                lowered = fn.lower(params_sds, batch_sds)
        else:  # decode
            params_shape = jax.eval_shape(
                lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
            p_spec = SH.shard_tree(params_shape, M.param_specs(cfg), mesh)
            params_sds = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                params_shape, p_spec)
            cache_shape = M.cache_shapes(cfg, global_batch, seq_len)
            c_spec = SH.shard_tree(cache_shape, M.cache_specs(cfg), mesh,
                                   rules=act_rules)
            cache_sds = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                cache_shape, c_spec)
            tok_sds = _shard_batch(
                M.input_specs(cfg, seq_len, global_batch, "decode"),
                M.input_spec_names(cfg, "decode"), mesh)
            fn = jax.jit(
                lambda p, c, t: M.decode_step(cfg, p, c, t),
                donate_argnums=(1,))
            lowered = fn.lower(params_sds, cache_sds, tok_sds["tokens"])

        compiled = lowered.compile()
    return lowered, compiled


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             microbatches: int = 1, cfg_override=None):
    # hlo_cost lives in benchmarks/ (repo root on sys.path when run as
    # `python -m repro.launch.dryrun` from the repo).
    from benchmarks import hlo_cost

    cfg = cfg_override or get(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, compiled = lower_cell(cfg, shape_name, mesh, microbatches)
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    attributed = hlo_cost.analyze(hlo_text)   # trip-count-aware, per-device
    coll_naive = collective_bytes(hlo_text)   # body-once (sanity column)
    seq_len, global_batch, kind = SHAPES[shape_name]
    return {
        "arch": arch,
        "shape": shape_name,
        "kind": kind,
        "mesh": list(mesh.devices.shape),
        "multi_pod": multi_pod,
        "compile_s": round(compile_s, 1),
        # xla cost_analysis counts while bodies ONCE (see hlo_cost docstring)
        "xla_flops_body_once": cost.get("flops", 0.0),
        "xla_bytes_body_once": cost.get("bytes accessed", 0.0),
        "dot_flops_per_device": attributed["flops"],
        "collective_bytes_per_device": attributed["coll"],
        "collective_counts": attributed["counts"],
        "collective_bytes_total": attributed["coll_total"],
        "collective_bytes_naive": coll_naive["total"],
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "tokens": seq_len * global_batch if kind != "decode" else global_batch,
        "status": "ok",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--smoke", action="store_true",
                    help="lower the reduced smoke config instead of the "
                         "published one (CI: fast partitionability check "
                         "of the sharding rule tables on the 16x16 mesh)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        cells.append((args.arch, args.shape))

    results = []
    for arch, shape in cells:
        if (arch, shape) in SKIPS:
            results.append({"arch": arch, "shape": shape, "status": "skip",
                            "reason": SKIPS[(arch, shape)]})
            print(f"SKIP {arch} x {shape}: {SKIPS[(arch, shape)]}", flush=True)
            continue
        try:
            override = None
            if args.smoke:
                from repro.configs import get_smoke
                override = get_smoke(arch)
            r = run_cell(arch, shape, args.multi_pod, args.microbatches,
                         cfg_override=override)
            results.append(r)
            print(f"OK   {arch} x {shape}: "
                  f"{r['dot_flops_per_device']:.3e} dot-flops/dev, "
                  f"temp {r['memory']['temp_bytes']/2**30:.2f} GiB, "
                  f"coll {r['collective_bytes_total']/2**20:.1f} MiB, "
                  f"compile {r['compile_s']}s", flush=True)
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            results.append({"arch": arch, "shape": shape, "status": "fail",
                            "error": f"{type(e).__name__}: {e}"})
            print(f"FAIL {arch} x {shape}: {type(e).__name__}: {e}",
                  flush=True)
            traceback.print_exc()

    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")

    n_fail = sum(r.get("status") == "fail" for r in results)
    if n_fail:
        raise SystemExit(f"{n_fail}/{len(results)} cells failed")


if __name__ == "__main__":
    main()
