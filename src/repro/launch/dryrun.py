import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices build the production meshes
(16×16 single-pod, 2×16×16 multi-pod); every step function must
``.lower().compile()`` under its shardings; ``memory_analysis()`` proves it
fits, ``cost_analysis()`` + HLO collective parsing feed §Roofline.

Usage:
    python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, get_config, list_archs, shapes_for
from ..pjit_utils import enable_spmd
from . import hlo_analysis, shardings, specs, steps
from .mesh import (HBM_BW, ICI_LINK_BW, PEAK_FLOPS_BF16, make_production_mesh,
                   mesh_counts)


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               extra_cfg: Optional[Dict[str, Any]] = None,
               variant: Optional[Dict[str, Any]] = None):
    """Lower + compile one cell; returns (compiled, lowered, meta).

    ``extra_cfg`` overrides ArchConfig fields (remat_policy, accum_steps,
    mla_absorbed, ...); ``variant`` toggles spec-level knobs:
    cache_seq_shard (flash-decode cache layout), fsdp_params (decode
    weights sharded over DP too)."""
    import dataclasses
    from ..models import layers as _layers
    variant = variant or {}
    _layers.FLASH_DECODE_ENABLED = bool(variant.get("flash_decode", False))
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    shape = SHAPES[shape_name]
    enable_spmd(True)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = steps.make_optimizer(cfg)
            inp = specs.input_specs(cfg, shape, opt)
            state_ps = shardings.train_state_pspecs(cfg, inp["state"], mesh)
            batch_ps = shardings.batch_pspecs(cfg, shape, mesh)
            fn = steps.make_train_step(cfg, opt)
            jitted = jax.jit(fn,
                             in_shardings=(_named(mesh, state_ps),
                                           _named(mesh, batch_ps)),
                             donate_argnums=(0,))
            lowered = jitted.lower(inp["state"], inp["batch"])
        elif shape.kind == "prefill":
            inp = specs.input_specs(cfg, shape)
            param_ps = shardings.param_pspecs(cfg, inp["params"], mesh)
            if cfg.param_count() >= shardings.FSDP_THRESHOLD:
                param_ps = shardings.shard_over_dp(param_ps, inp["params"], mesh)
            batch_ps = shardings.batch_pspecs(cfg, shape, mesh)
            fn = steps.make_prefill_step(cfg)
            jitted = jax.jit(fn, in_shardings=(_named(mesh, param_ps),
                                               _named(mesh, batch_ps)))
            lowered = jitted.lower(inp["params"], inp["batch"])
        else:  # decode
            inp = specs.input_specs(cfg, shape)
            param_ps = shardings.param_pspecs(cfg, inp["params"], mesh)
            if (cfg.param_count() >= shardings.FSDP_THRESHOLD
                    or variant.get("fsdp_params")):
                param_ps = shardings.shard_over_dp(param_ps, inp["params"], mesh)
            cache_ps = shardings.cache_pspecs(
                cfg, inp["cache"], shape.global_batch, mesh,
                seq_shard_model=variant.get("cache_seq_shard", False))
            tok_dp = shardings.batch_axes_for(shape.global_batch, cfg, mesh)
            tok_spec = P(tok_dp if len(tok_dp) != 1 else tok_dp[0], None) \
                if tok_dp else P(None, None)
            fn = steps.make_decode_step(cfg)
            jitted = jax.jit(fn,
                             in_shardings=(_named(mesh, param_ps),
                                           _named(mesh, cache_ps),
                                           NamedSharding(mesh, tok_spec),
                                           NamedSharding(mesh, P())),
                             donate_argnums=(1,))
            lowered = jitted.lower(inp["params"], inp["cache"],
                                   inp["tokens"], inp["pos"])
        compiled = lowered.compile()
    return compiled, lowered, {"mesh": mesh, "cfg": cfg, "shape": shape}


def analyze_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                 extra_cfg: Optional[Dict[str, Any]] = None,
                 variant: Optional[Dict[str, Any]] = None,
                 verbose: bool = True) -> Dict[str, Any]:
    t0 = time.time()
    compiled, lowered, meta = lower_cell(arch, shape_name,
                                         multi_pod=multi_pod,
                                         extra_cfg=extra_cfg,
                                         variant=variant)
    cfg, shape, mesh = meta["cfg"], meta["shape"], meta["mesh"]
    chips = mesh.devices.size

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    totals = hlo_analysis.analyze(hlo)      # loop-aware (scan bodies × trips)
    colls = totals.collectives
    coll_bytes = totals.collective_bytes

    flops = totals.flops                                   # per-device
    bytes_acc = totals.hbm_bytes                           # per-device

    # MODEL_FLOPS (global, useful): 6·N·tokens train; 2·N·tokens fwd-only
    n_act = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_act * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_act * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2.0 * n_act * tokens

    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = bytes_acc / HBM_BW
    collective_s = coll_bytes / ICI_LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    bottleneck = max(terms, key=terms.get)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "kind": shape.kind,
        "extra_cfg": {k: str(v) for k, v in (extra_cfg or {}).items()},
        "variant": {k: str(v) for k, v in (variant or {}).items()},
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll_bytes,
        "collectives": colls,
        "xla_cost_analysis_once": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        **terms,
        "bottleneck": bottleneck.replace("_s", ""),
        "model_flops_global": model_flops,
        "useful_flop_ratio": (model_flops / (flops * chips)
                              if flops else 0.0),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
        },
        "compile_s": round(time.time() - t0, 1),
    }
    if verbose:
        ma = rec["memory_analysis"]
        print(f"[{rec['mesh']}] {arch} × {shape_name}: "
              f"args={ma['argument_bytes']/2**30:.2f}GiB "
              f"temp={ma['temp_bytes']/2**30:.2f}GiB "
              f"flops/dev={flops:.3e} bytes/dev={bytes_acc:.3e} "
              f"coll/dev={coll_bytes:.3e}  bottleneck={rec['bottleneck']} "
              f"({rec['compile_s']}s)", flush=True)
    return rec


def run_all(multi_pod: bool, out_path: Optional[str] = None,
            archs=None) -> Dict[str, Any]:
    results, failures = [], []
    for arch in (archs or list_archs()):
        cfg = get_config(arch)
        for shape in shapes_for(cfg):
            try:
                results.append(analyze_cell(arch, shape.name,
                                            multi_pod=multi_pod))
            except Exception as e:               # a failure here is a bug
                traceback.print_exc()
                failures.append({"arch": arch, "shape": shape.name,
                                 "error": repr(e)})
    payload = {"multi_pod": multi_pod, "results": results,
               "failures": failures}
    if out_path:
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {out_path}: {len(results)} ok, {len(failures)} failed")
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default="train_4k",
                    choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.all:
        run_all(args.multi_pod, args.out, archs=[args.arch] if args.arch
                else None)
        return
    rec = analyze_cell(args.arch, args.shape, multi_pod=args.multi_pod)
    compiled, lowered, _ = lower_cell(args.arch, args.shape,
                                      multi_pod=args.multi_pod)
    print(compiled.memory_analysis())
    print({k: v for k, v in (compiled.cost_analysis() or {}).items()
           if k in ("flops", "bytes accessed")})
    print(json.dumps(rec, indent=1, default=str))


if __name__ == "__main__":
    main()
