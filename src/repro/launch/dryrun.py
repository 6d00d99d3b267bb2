import os
# A CPU-only dry run: pin the platform before JAX is imported, so it never
# reaches for an accelerator that another process holds.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This proves the distribution config is coherent without hardware: 512
placeholder CPU devices stand in for 2 pods × 256 chips. For each cell we

    with jax.set_mesh(mesh):
        lowered  = jax.jit(step, in_shardings=...).lower(**input_specs(...))
        compiled = lowered.compile()
        compiled.memory_analysis()   # proves it fits 16 GB/chip
        compiled.cost_analysis()     # FLOPs/bytes for the roofline

and persist everything (plus the HLO collective inventory) to
``experiments/dryrun/<arch>__<cell>__<mesh>.json``, which §Roofline reads.

Usage:
    python -m repro.launch.dryrun --arch qwen3_8b --shape decode_32k
    python -m repro.launch.dryrun --arch qwen3_8b --shape decode_32k --multi-pod
    python -m repro.launch.dryrun --all            # every applicable cell
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, SHAPES_BY_NAME, cell_applicable, get_config
from ..configs.shapes import SHAPES, ShapeCell
from ..distributed.sharding import (
    ShardingConfig,
    build_cache_specs,
    build_param_specs,
    input_specs_for,
)
from ..models.layers import abstract_params, logical_specs
from ..models.registry import get_model
from ..train.optimizer import AdamWConfig, abstract_opt_state
from ..train.train_step import make_train_step
from .mesh import make_production_mesh
from .plan import WHISPER_CROSS_LEN, WHISPER_DECODER_PROMPT, plan_for

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


# --------------------------------------------------------------------------- #
# Collective inventory from the partitioned HLO                               #
# --------------------------------------------------------------------------- #
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}
_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_inventory(hlo_text: str) -> dict:
    """Per-kind {count, bytes} from the per-device partitioned HLO. Result
    buffer sizes are used (per-device bytes moved is proportional; the
    roofline divides by per-chip link bandwidth)."""
    inv = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        eq = s.find(" = ")
        if eq < 0:
            continue
        rhs = s[eq + 3 :]
        for kind in _COLLECTIVES:
            # match the op name right after the result shape, e.g.
            # "bf16[4,128]{1,0} all-gather(..." — avoids matching metadata
            m = re.match(r"^((?:\([^)]*\))|(?:[\w\[\],{}]+))\s+" + kind + r"(-start|-done)?\(", rhs)
            if m:
                if m.group(2) == "-done":
                    break  # bytes counted at -start
                inv[kind]["count"] += 1
                inv[kind]["bytes"] += _shape_bytes(m.group(1))
                break
    inv["total_bytes"] = sum(v["bytes"] for k, v in inv.items() if isinstance(v, dict))
    return inv


# --------------------------------------------------------------------------- #
# Cell construction                                                           #
# --------------------------------------------------------------------------- #
def build_cell(arch: str, cell: ShapeCell, mesh, plan: dict):
    """Returns (fn, args_abstract, in_shardings, out_shardings, donate).

    Explicit out_shardings matter: donated caches only alias when the output
    sharding matches the input's (GSPMD-propagated output shardings usually
    don't, which silently doubles the cache footprint)."""
    cfg = get_config(arch)
    model = get_model(cfg)
    scfg: ShardingConfig = plan["sharding"]
    defs = model.param_defs()
    aparams = abstract_params(defs)
    laxes = logical_specs(defs)
    pspecs = build_param_specs(aparams, laxes, mesh, scfg)
    b, s = cell.global_batch, cell.seq_len

    def logits_spec(batch_dim_size):
        spec = jax.sharding.PartitionSpec(
            scfg.dp_axes if batch_dim_size % _mesh_prod(mesh, scfg.dp_axes) == 0 else None,
            scfg.tp_axis if cfg.vocab_size % _mesh_prod(mesh, (scfg.tp_axis,)) == 0 else None,
        )
        return jax.sharding.NamedSharding(mesh, spec)

    if cell.kind == "train":
        opt_abs = abstract_opt_state(aparams)
        opt_specs = {
            "m": build_param_specs(opt_abs["m"], laxes, mesh, scfg),
            "v": build_param_specs(opt_abs["v"], laxes, mesh, scfg),
            "count": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        }
        in_specs, in_shards = input_specs_for(cfg, cell, mesh, scfg)
        step = make_train_step(
            model, AdamWConfig(), microbatches=plan["microbatches"], remat=plan["remat"]
        )
        args = (aparams, opt_abs, in_specs)
        shards = (pspecs, opt_specs, in_shards)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        metrics_out = {"grad_norm": rep, "lr": rep, "loss": rep}
        return step, args, shards, (pspecs, opt_specs, metrics_out), (0, 1)

    if cell.kind == "prefill":
        if cfg.family == "audio":
            dec_len = WHISPER_DECODER_PROMPT
            cache_abs = model.cache_shape(b, dec_len, enc_len=s)
            tokens = jax.ShapeDtypeStruct((b, dec_len), jnp.int32)
            frames = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
            cache_specs = build_cache_specs(cache_abs, mesh, scfg, cfg.n_kv_heads)
            _, in_shards = input_specs_for(cfg, cell, mesh, scfg)
            fn = lambda params, tokens, cache, frames: model.prefill(
                params, tokens, cache, patch_embeds=frames
            )
            args = (aparams, tokens, cache_abs, frames)
            tok_spec = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(scfg.dp_axes)
            )
            shards = (pspecs, tok_spec, cache_specs, in_shards["frames"])
            return fn, args, shards, (logits_spec(b), cache_specs), (2,)
        in_specs, in_shards = input_specs_for(cfg, cell, mesh, scfg)
        cache_abs = model.cache_shape(b, s)
        cache_specs = build_cache_specs(cache_abs, mesh, scfg, cfg.n_kv_heads)
        if cfg.family == "vlm":
            fn = lambda params, tokens, cache, patch_embeds: model.prefill(
                params, tokens, cache, patch_embeds=patch_embeds
            )
            args = (aparams, in_specs["tokens"], cache_abs, in_specs["patch_embeds"])
            shards = (pspecs, in_shards["tokens"], cache_specs, in_shards["patch_embeds"])
            return fn, args, shards, (logits_spec(b), cache_specs), (2,)
        fn = lambda params, tokens, cache: model.prefill(params, tokens, cache)
        args = (aparams, in_specs["tokens"], cache_abs)
        shards = (pspecs, in_shards["tokens"], cache_specs)
        return fn, args, shards, (logits_spec(b), cache_specs), (2,)

    if cell.kind == "decode":
        in_specs, in_shards = input_specs_for(cfg, cell, mesh, scfg)
        if cfg.family == "audio":
            cache_abs = model.cache_shape(b, s, enc_len=WHISPER_CROSS_LEN)
        else:
            cache_abs = model.cache_shape(b, s)
        cache_specs = build_cache_specs(cache_abs, mesh, scfg, cfg.n_kv_heads)
        fn = lambda params, tokens, cache: model.decode_step(params, tokens, cache)
        args = (aparams, in_specs["tokens"], cache_abs)
        shards = (pspecs, in_shards["tokens"], cache_specs)
        return fn, args, shards, (logits_spec(b), cache_specs), (2,)

    raise ValueError(cell.kind)


def _mesh_prod(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = 1
    for a in axes:
        out *= sizes.get(a, 1)
    return out


def input_specs(arch: str, shape: str, multi_pod: bool = False):
    """ShapeDtypeStruct stand-ins for every model input of a cell (public
    helper per the brief)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = SHAPES_BY_NAME[shape]
    plan = plan_for(arch, shape, multi_pod)
    _, args, _, _, _ = build_cell(arch, cell, mesh, plan)
    return args


# --------------------------------------------------------------------------- #
def run_cell(arch: str, shape: str, multi_pod: bool, overrides=None,
             save: bool = True, tag: str = "") -> dict:
    cell = SHAPES_BY_NAME[shape]
    cfg = get_config(arch)
    ok, why = cell_applicable(cfg, cell)
    if not ok:
        return {"arch": arch, "shape": shape, "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    plan = plan_for(arch, shape, multi_pod, overrides)
    t0 = time.time()
    fn, args, shards, out_shards, donate = build_cell(arch, cell, mesh, plan)
    result = {
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(n_chips),
        "microbatches": plan["microbatches"],
        "tag": tag,
    }
    try:
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                fn, in_shardings=shards, out_shardings=out_shards,
                donate_argnums=donate,
            )
            lowered = jitted.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        from .hlo_analysis import analyze_hlo

        totals = analyze_hlo(hlo)
        result.update(
            {
                "status": "ok",
                "lower_s": round(t_lower - t0, 2),
                "compile_s": round(t_compile - t_lower, 2),
                "memory": {
                    "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                    "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                    "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                    "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
                    "generated_code_bytes": int(
                        getattr(mem, "generated_code_size_in_bytes", 0)
                    ),
                },
                # raw XLA numbers (loop bodies counted ONCE — kept for
                # reference only; see hlo_analysis for the real accounting)
                "cost_analysis_raw": {
                    "flops": float(cost.get("flops", -1)) if cost else -1,
                    "bytes_accessed": float(cost.get("bytes accessed", -1)) if cost else -1,
                },
                # trip-count-aware per-device totals
                "cost": {
                    "flops": totals.flops,
                    "transcendentals": totals.transcendentals,
                },
                "collectives": totals.as_dict()["collectives"],
                "collective_bytes_total": totals.total_collective_bytes,
            }
        )
    except Exception as e:  # noqa: BLE001 — a failed cell is a recorded bug
        result.update(
            {
                "status": "failed",
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            }
        )
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        out = RESULTS_DIR / f"{arch}__{shape}__{result['mesh']}{suffix}.json"
        out.write_text(json.dumps(result, indent=2))
        result["saved_to"] = str(out)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, help="architecture id")
    ap.add_argument("--shape", choices=[s.name for s in SHAPES], help="shape cell")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 mesh")
    ap.add_argument("--all", action="store_true", help="run every applicable cell")
    ap.add_argument("--tag", default="", help="variant tag for perf experiments")
    ap.add_argument("--no-fsdp", action="store_true", help="replicate weights over dp")
    args = ap.parse_args()

    overrides = None
    if args.no_fsdp:
        from ..distributed.sharding import ShardingConfig

        overrides = {
            "sharding": ShardingConfig(
                dp_axes=("pod", "data") if args.multi_pod else ("data",),
                fsdp_weights=False,
            )
        }

    if args.all:
        failures = 0
        for arch in ARCH_IDS:
            for cell in SHAPES:
                r = run_cell(arch, cell.name, args.multi_pod, overrides, tag=args.tag)
                status = r["status"]
                extra = r.get("reason", r.get("error", ""))
                print(f"{arch:20s} {cell.name:12s} {status:8s} {extra}", flush=True)
                failures += status == "failed"
        return 1 if failures else 0

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    r = run_cell(args.arch, args.shape, args.multi_pod, overrides, tag=args.tag)
    print(json.dumps({k: v for k, v in r.items() if k != "traceback"}, indent=2))
    if r["status"] == "failed":
        print(r.get("traceback", ""), file=sys.stderr)
    return 1 if r["status"] == "failed" else 0


if __name__ == "__main__":
    sys.exit(main())
