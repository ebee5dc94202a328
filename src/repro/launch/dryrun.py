"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST be the first two lines (before ANY other import): jax locks the device
count on first initialization, and the production meshes need 512 placeholder
host devices.
"""

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402
import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro import sharding as shd
from repro.configs import get_config, list_archs
from repro.launch import steps as steps_lib
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models import layers as layers_lib

# Pallas interpret-mode kernels cannot be SPMD-partitioned over 512 fake
# devices; lower the dry run with the XLA attention/SSD formulation (the
# Pallas kernels are the single-chip production path — DESIGN.md §2).
layers_lib.set_attn_impl("xla")
from repro.kernels import ops as kops  # noqa: E402
kops.set_use_pallas_ssd(False)

COLLECTIVE_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)",
)

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
               "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3": 1,
               "f8e5m2": 1, "s16": 2, "u16": 2}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-buffer bytes of every collective op in per-device HLO."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        out[kind] += n * DTYPE_BYTES[dtype]
        out["count"] += 1
    out["total"] = sum(out[k] for k in
                       ("all-gather", "all-reduce", "reduce-scatter",
                        "all-to-all", "collective-permute"))
    return out


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float) -> dict:
    compute_s = flops_per_dev / PEAK_FLOPS_BF16
    memory_s = bytes_per_dev / HBM_BW
    collective_s = coll_bytes_per_dev / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    terms["bottleneck"] = max(terms, key=lambda k: terms[k]
                              if k.endswith("_s") else -1)
    return terms


def _hybrid_args(cfg, gp, x) -> dict:
    """A hybrid group's extra inputs: its shared blocks and, standing in
    for the token embedding, an activation of the same shape."""
    if not cfg.hybrid_layers:
        return {}
    return {"emb": x, "shared": gp["shared"]}


def _body_costs(cfg, shape: str, mesh, rules) -> dict:
    """Per-trip cost of every scanned layer-group body.

    XLA's HloCostAnalysis counts a ``while`` body ONCE regardless of trip
    count (verified experimentally), so module-level cost analysis under-
    counts an R-layer scan by a factor of ~R.  We compile each group's body
    standalone — rep=1 group application (value_and_grad for train shapes so
    fwd+remat+bwd are included, matching the two whiles of the module) — and
    scale by (rep − 1) when combining.
    """
    import jax.numpy as jnp

    from repro.models import model as mdl
    from repro.models import params as pm2
    from repro.models import transformer as tfm
    from repro.models.transformer import cache_spec as cs_full
    from repro.models.transformer import group_spec, model_spec

    info = steps_lib.SHAPES[shape]
    kind = info["kind"]
    seq = info["seq"] if kind != "decode" else 1
    batch = info["batch"]
    d = cfg.d_model
    h_sds = jax.ShapeDtypeStruct((batch, seq, d), jnp.bfloat16)
    h_sh = shd.named_sharding(mesh, rules, ("batch", None, None),
                              h_sds.shape)
    groups = []
    all_blocks = [("g", gi, u, r) for gi, (u, r) in enumerate(cfg.blocks)]
    all_blocks += [("enc", gi, u, r)
                   for gi, (u, r) in enumerate(cfg.encoder_blocks)]

    positions = jnp.arange(seq)
    for prefix, gi, unit, rep in all_blocks:
        if rep <= 1:
            groups.append({"rep": rep, "flops": 0.0, "bytes": 0.0,
                           "coll": 0.0})
            continue
        gspec = group_spec(cfg, unit, 1)
        if cfg.hybrid_layers:    # the shared blocks, costed with each group
            gspec["shared"] = model_spec(cfg)["shared"]
        gp_abs = pm2.abstract(gspec)
        gp_sh = jax.tree.map(
            lambda s: shd.named_sharding(mesh, rules, s.axes, s.shape),
            gspec, is_leaf=pm2.is_spec)

        if kind == "train":
            def body(gp, x, _u=unit):
                y, _, aux = tfm.group_fwd(gp, x, _u, 1, cfg,
                                          positions=positions,
                                          **_hybrid_args(cfg, gp, x))
                return jnp.sum(y.astype(jnp.float32)) + aux
            fn = jax.grad(body, argnums=(0, 1))
            args = (gp_abs, h_sds)
            in_sh = (gp_sh, h_sh)
        else:
            # decode/prefill body with a cache slice (rep=1)
            cspec = {}
            for i, k2 in enumerate(unit):
                key = f"{i}:{k2}"
                cspec[key] = tfm.layer_cache_spec(cfg, k2, batch, info["seq"])
            cspec = pm2.stack_tree(cspec, 1)
            c_abs = pm2.abstract(cspec)
            c_sh = jax.tree.map(
                lambda s: shd.named_sharding(mesh, rules, s.axes, s.shape),
                cspec, is_leaf=pm2.is_spec)

            def body(gp, x, c, _u=unit):
                y, nc, _ = tfm.group_fwd(gp, x, _u, 1, cfg,
                                         positions=positions, caches=c,
                                         **_hybrid_args(cfg, gp, x))
                return y, nc
            fn = body
            args = (gp_abs, h_sds, c_abs)
            in_sh = (gp_sh, h_sh, c_sh)

        with mesh:
            comp = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
        cost = comp.cost_analysis()
        coll = collective_bytes(comp.as_text())
        groups.append({
            "rep": rep,
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(coll["total"]),
        })
    return {"groups": groups}


def model_flops(cfg, shape: str) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); D = tokens.

    Train counts fwd+bwd (6ND); prefill counts forward only (2ND); decode
    counts one token per sequence.
    """
    info = steps_lib.SHAPES[shape]
    n_active = cfg.active_param_count()
    if info["kind"] == "train":
        tokens = info["seq"] * info["batch"]
        return 6.0 * n_active * tokens
    if info["kind"] == "prefill":
        tokens = info["seq"] * info["batch"]
        return 2.0 * n_active * tokens
    return 2.0 * n_active * info["batch"]


def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             rules: shd.ShardingRules | None = None,
             remat: str | None = None, attn: str | None = None,
             ssm_chunk: int | None = None, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    if remat is not None:
        cfg = cfg.scaled(remat=remat)
    if ssm_chunk is not None:
        cfg = cfg.scaled(ssm_chunk=ssm_chunk)
    if attn is not None:
        layers_lib.set_attn_impl(attn)
    ok, reason = steps_lib.applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "status": "skipped",
                "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rules = rules or shd.DEFAULT_RULES
    info = steps_lib.SHAPES[shape]
    specs = steps_lib.input_specs(cfg, shape)
    in_sh, out_sh = steps_lib.cell_shardings(cfg, shape, mesh, rules)

    t0 = time.perf_counter()
    if info["kind"] == "train":
        p_spec, o_spec = steps_lib.train_state_specs(cfg)
        step = steps_lib.make_train_step(cfg)
        args = (p_spec, o_spec, specs["batch"])
    elif info["kind"] == "prefill":
        p_spec, _ = steps_lib.train_state_specs(cfg)
        step = steps_lib.make_prefill_step(cfg)
        args = (p_spec, specs["tokens"], specs["caches"], specs["extras"])
    else:
        p_spec, _ = steps_lib.train_state_specs(cfg)
        step = steps_lib.make_serve_step(cfg)
        args = (p_spec, specs["tokens"], specs["caches"])

    shd.set_active(mesh, rules)
    try:
        with mesh:
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            lowered = jitted.lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)

        # trip-count correction: module cost counts each scan body once; add
        # (rep - 1) × per-body cost from standalone body compiles
        bodies = _body_costs(cfg, shape, mesh, rules)
    finally:
        shd.set_active(None)
    extra_flops = sum((g["rep"] - 1) * g["flops"] for g in bodies["groups"])
    extra_bytes = sum((g["rep"] - 1) * g["bytes"] for g in bodies["groups"])
    extra_coll = sum((g["rep"] - 1) * g["coll"] for g in bodies["groups"])

    flops_dev = float(cost.get("flops", 0.0)) + extra_flops
    bytes_dev = float(cost.get("bytes accessed", 0.0)) + extra_bytes
    coll_total = coll["total"] + extra_coll
    terms = roofline_terms(flops_dev, bytes_dev, coll_total)
    mf = model_flops(cfg, shape)
    mf_dev = mf / chips

    result = {
        "arch": arch, "shape": shape, "status": "ok",
        "mesh": dict(mesh.shape), "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "hlo_flops_per_dev": flops_dev,
        "hlo_bytes_per_dev": bytes_dev,
        "collective_bytes_per_dev": coll_total,
        "collectives": {k: v for k, v in coll.items()
                        if k not in ("total",)},
        "terms": terms,
        "model_flops_per_dev": mf_dev,
        "useful_flops_ratio": (mf_dev / flops_dev) if flops_dev else None,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
    }
    arg_b = result["memory"]["argument_bytes"] or 0
    tmp_b = result["memory"]["temp_bytes"] or 0
    result["memory"]["total_per_dev_gb"] = round((arg_b + tmp_b) / 2**30, 3)
    result["fits_v5e_16gb"] = (arg_b + tmp_b) < 16 * 2**30
    if verbose:
        print(json.dumps(result, default=float))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="multi-pod dry run")
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None,
                    choices=[None, *steps_lib.SHAPES], help="default: all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--remat", default=None, choices=[None, "full", "dots", "none"])
    ap.add_argument("--attn", default=None, choices=[None, "xla", "xla_chunked"])
    ap.add_argument("--no-fsdp", action="store_true",
                    help="paper-faithful static baseline (no FSDP)")
    ap.add_argument("--serve-rules", action="store_true",
                    help="TP-only + seq-sharded-cache serving topology")
    ap.add_argument("--optimized", action="store_true",
                    help="beyond-paper optimized config: chunked attention "
                         "for train/prefill + SERVE_RULES for decode shapes")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(steps_lib.SHAPES)
    rules = shd.NO_FSDP_RULES if args.no_fsdp else shd.DEFAULT_RULES
    if args.serve_rules:
        rules = shd.SERVE_RULES

    failures = 0
    for arch in archs:
        for shape in shapes:
            cell_rules = rules
            attn = args.attn
            if args.optimized:
                attn = "xla_chunked"
                if steps_lib.SHAPES[shape]["kind"] == "decode":
                    # TP-only serving needs params bf16 to fit one model-axis
                    # shard (§Perf S3): above ~200B keep FSDP weight storage
                    # AND the jit-partitioned MoE path (EP would all-gather
                    # the FSDP'd experts every token)
                    from repro.models import moe as moe_lib
                    params_gb_tp = get_config(arch).param_count() * 2 / 16 / 2**30
                    if params_gb_tp < 12:
                        cell_rules = shd.SERVE_RULES
                        moe_lib.set_use_ep(True)
                    else:
                        # ≥200B decode: every "optimized" delta measured
                        # worse than the FSDP baseline here — run baseline
                        cell_rules = shd.DEFAULT_RULES
                        moe_lib.set_use_ep(False)
                        attn = "xla"
                else:
                    from repro.models import moe as moe_lib
                    moe_lib.set_use_ep(True)
            try:
                res = run_cell(arch, shape, multi_pod=args.multi_pod,
                               rules=cell_rules, remat=args.remat, attn=attn)
            except Exception as e:  # a failing cell is a bug — surface it
                failures += 1
                res = {"arch": arch, "shape": shape, "status": "error",
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
                print(json.dumps(res))
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(res, default=float) + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
