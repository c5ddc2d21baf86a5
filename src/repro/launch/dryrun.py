import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the 512
placeholder CPU devices let jax.make_mesh build the production meshes; the
compiled artifact yields memory_analysis (fits-per-device), cost_analysis
(FLOPs/bytes for §Roofline) and the post-SPMD HLO whose collective ops we
byte-count for the collective roofline term.

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k \
      [--mesh single|multi] [--smoke] [--out benchmarks/artifacts/dryrun]
  python -m repro.launch.dryrun --all [--mesh both]
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.launch import mesh as meshlib
from repro.launch.shapes import (SHAPES, TRAIN_OVERRIDES, cache_len_for,
                                 input_specs, runnable)
from repro.models.model import build_model
from repro.train.trainer import (TrainConfig, abstract_opt_state,
                                 make_train_step, opt_state_shardings)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def collective_bytes(hlo_text: str) -> dict:
    """Byte-count collective ops in post-SPMD (per-device) HLO text."""
    out = {c: 0 for c in COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(.+?)\s+(" + "|".join(COLLECTIVES)
                      + r")(-start|-done)?\(", line)
        if not m or (m.group(3) or "") == "-done":
            continue
        shapes_part, op = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in re.findall(r"([a-z]+[0-9]+|pred)\[([0-9,]*)\]",
                                   shapes_part):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES.get(dt, 4)
        out[op] += nbytes
        out["count"] += 1
    out["total"] = sum(out[c] for c in COLLECTIVES)
    return out


def _batch_shardings(mesh, specs):
    baxes = meshlib.batch_axes(mesh)
    nb = 1
    for a in baxes:
        nb *= mesh.shape[a]

    def shard(sds):
        if sds.shape and sds.shape[0] % nb == 0 and sds.shape[0] >= nb:
            return NamedSharding(mesh, P(baxes, *([None] *
                                                  (len(sds.shape) - 1))))
        return NamedSharding(mesh, P())
    return jax.tree.map(shard, specs)


def _cache_shardings(mesh, cache_specs):
    """Batch dim if divisible; else the first large seq/feature dim over
    'data' (sequence-parallel decode for batch=1 long-context)."""
    baxes = meshlib.batch_axes(mesh)
    nb = 1
    for a in baxes:
        nb *= mesh.shape[a]
    nd = mesh.shape["data"]

    def shard(sds):
        shape = sds.shape            # (n_periods, B, ...)
        dims = [None] * len(shape)
        if len(shape) >= 2 and shape[1] % nb == 0 and shape[1] >= nb:
            dims[1] = baxes
        else:
            for i in range(2, len(shape)):
                if shape[i] % nd == 0 and shape[i] >= nd:
                    dims[i] = "data"
                    break
        return NamedSharding(mesh, P(*dims))
    return jax.tree.map(shard, cache_specs)


OPT_REPLICATE_SERVE_PARAMS_GB = 8.0     # per-device bf16 budget for TP-only


def _apply_opt(cfg):
    import dataclasses
    return dataclasses.replace(cfg, attn_impl="chunked", gqa_grouped=True)


def _cost_fields(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll": coll["total"], "coll_by_op": coll}


def reconstruct_costs(cfg, shape_name, mesh, ctx, kind, specs, opt):
    """Differential cost reconstruction (see EXPERIMENTS.md §Roofline):
    XLA's cost_analysis counts While bodies once, so per-device totals are
    rebuilt from 1-period and 2-period lowerings:
      C(n) = C(1) + (n-1) * (C(2) - C(1))    per varied loop."""
    import dataclasses as dc
    base_kwargs = {"n_layers": cfg.period}
    loops = [("n_layers", cfg.period, cfg.n_periods)]
    if cfg.enc_dec:
        base_kwargs["n_enc_layers"] = 1
        loops.append(("n_enc_layers", 1, cfg.n_enc_layers))

    def lower_variant(**over):
        kw = dict(base_kwargs)
        kw.update(over)
        vcfg = dc.replace(cfg, **kw)
        if opt:
            vcfg = _apply_opt(vcfg)
        vmodel = build_model(vcfg)
        vkind, vspecs = input_specs(vcfg, shape_name, model=vmodel)
        return _lower(vcfg, vmodel, mesh, ctx, vkind, vspecs,
                      accum_override=1,
                      grad_shard=opt).compile()

    c_base = _cost_fields(lower_variant())
    out = dict(c_base)
    out["coll_by_op"] = dict(c_base["coll_by_op"])
    for field_name, step, actual in loops:
        c_double = _cost_fields(lower_variant(**{field_name: 2 * step}))
        mult = (actual - step) / step
        for f in ("flops", "bytes", "coll"):
            out[f] += mult * (c_double[f] - c_base[f])
        for op in COLLECTIVES:
            out["coll_by_op"][op] = out["coll_by_op"].get(op, 0) + mult * (
                c_double["coll_by_op"][op] - c_base["coll_by_op"][op])
    return out


def _lower(cfg, model, mesh, ctx, kind, specs, accum_override=None,
           grad_shard=False):
    p_abs = model.abstract_params()
    p_shard = meshlib.param_shardings(model, mesh)
    b_shard = _batch_shardings(mesh, specs["batch"])
    with mesh:
        if kind == "train":
            tov = dict(TRAIN_OVERRIDES.get(cfg.name, {}))
            # NOTE §Perf iteration 2 (refuted): reducing accum_steps 4x to
            # amortize FSDP gathers quadrupled per-microbatch activation
            # temps (64.9 -> 204 GB/device on arctic) — kept at baseline.
            if accum_override is not None:
                tov["accum_steps"] = accum_override
            tcfg = TrainConfig(**tov)
            step = make_train_step(
                model, tcfg, ctx,
                grad_shardings=p_shard if grad_shard else None)
            o_abs = abstract_opt_state(p_abs, tcfg)
            o_shard = opt_state_shardings(p_shard, mesh)
            fn = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))
            return fn.lower(p_abs, o_abs, specs["batch"])
        if kind == "prefill":
            def prefill(params, batch):
                return model.prefill(params, batch, ctx=ctx)
            fn = jax.jit(prefill, in_shardings=(p_shard, b_shard))
            return fn.lower(p_abs, specs["batch"])
        c_shard = _cache_shardings(mesh, specs["cache"])
        c_out = c_shard
        if grad_shard:          # opt mode: serve params TP-only if they fit
            per_dev_gb = cfg.param_count() * 2 / mesh.shape["model"] / 1e9
            if per_dev_gb <= OPT_REPLICATE_SERVE_PARAMS_GB:
                p_shard = meshlib.serve_param_shardings(model, mesh)
            # §Perf: let XLA choose a self-consistent cache layout across
            # steps (explicit replicated-over-model caches forced a
            # re-replication gather of the whole cache per step)
            c_shard = None
            c_out = None

        def serve(params, cache, batch):
            return model.serve_step(params, cache, batch, ctx=ctx)
        fn = jax.jit(serve, in_shardings=(p_shard, c_shard, b_shard),
                     out_shardings=(None, c_out), donate_argnums=(1,))
        return fn.lower(p_abs, specs["cache"], specs["batch"])


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               smoke: bool = False, opt: bool = False,
               reconstruct: bool = False):
    """Lower + compile one (arch x shape x mesh) cell.

    Returns (compiled, lowered, info dict)."""
    cfg = get_config(arch, smoke=smoke)
    if opt:
        cfg = _apply_opt(cfg)
    model = build_model(cfg)
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    ctx = meshlib.shard_ctx(mesh)
    kind, specs = input_specs(cfg, shape_name, model=model)
    if smoke:   # shrink shapes, keep the mesh
        sh = SHAPES[shape_name]
        b = max(32, 512 if multi_pod else 256)
        seq = 64
        from repro.launch.shapes import (train_batch_specs,
                                         decode_batch_specs)
        if kind in ("train", "prefill"):
            specs = {"batch": train_batch_specs(cfg, seq, b)}
        else:
            cache = model.cache_shapes(b, seq,
                                       enc_len=seq if cfg.enc_dec else 0)
            specs = {"batch": decode_batch_specs(cfg, b), "cache": cache}

    lowered = _lower(cfg, model, mesh, ctx, kind, specs,
                     accum_override=1 if smoke else None,
                     grad_shard=opt)

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")
            if hasattr(mem, k)}
    except Exception:
        mem_info = {}
    coll = collective_bytes(compiled.as_text())

    n_chips = 512 if multi_pod else 256
    info = {
        "arch": cfg.name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips, "kind": kind, "smoke": smoke, "opt": opt,
        "compile_s": round(compile_s, 2),
        "flops_per_device": cost.get("flops", -1.0),
        "bytes_accessed_per_device": cost.get("bytes accessed", -1.0),
        "memory": mem_info,
        "collectives": coll,
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    }
    if reconstruct and not smoke:
        info["reconstructed"] = reconstruct_costs(
            get_config(arch), shape_name, mesh, ctx, kind, specs, opt)
    return compiled, lowered, info


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="beyond-paper perf variant (chunked attention, "
                         "grouped GQA, sharded grad accum, TP-only serving)")
    ap.add_argument("--reconstruct", action="store_true",
                    help="differential HLO cost reconstruction (kept as a "
                         "documented negative result; see §Perf)")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = ARCHS if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        cfg = get_config(arch)
        for shape_name in shapes:
            ok, why = runnable(cfg, shape_name)
            if not ok:
                print(f"SKIP {arch} x {shape_name}: {why}")
                continue
            for multi in meshes:
                tag = (f"{cfg.name}_{shape_name}_"
                       f"{'multi' if multi else 'single'}"
                       f"{'_smoke' if args.smoke else ''}"
                       f"{'_opt' if args.opt else ''}")
                t0 = time.time()
                try:
                    _, _, info = lower_cell(
                        arch, shape_name, multi, smoke=args.smoke,
                        opt=args.opt, reconstruct=args.reconstruct)
                    info["total_s"] = round(time.time() - t0, 2)
                    (out_dir / f"{tag}.json").write_text(
                        json.dumps(info, indent=1))
                    print(f"OK   {tag}: compile={info['compile_s']}s "
                          f"flops/dev={info['flops_per_device']:.3e} "
                          f"coll={info['collectives']['total']/1e6:.1f}MB")
                except Exception as e:
                    failures += 1
                    print(f"FAIL {tag}: {e}")
                    traceback.print_exc()
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
