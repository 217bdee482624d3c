import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (device count locks at
# first init).  Everything below is ordinary code.

"""Multi-pod dry-run: lower + compile every (arch x input shape) on the
production mesh, prove it fits, and extract the roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all
Options: --multi-pod (2x16x16 mesh), --algo feddane|fedavg|feddane_pipelined,
--out <dir> (JSON per pair), --remat full|dots|none.
"""
import argparse
import json
import sys
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHITECTURES, INPUT_SHAPES, get_arch, get_shape
from repro.launch import hloanalysis
from repro.launch import sharding as sh
from repro.launch import steps
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models import transformer
from repro.models.param import ParamSpec, param_shardings

def _sds_with_sharding(tree, shardings):
    return jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sp),
        tree, shardings)


def build_lowerable(cfg, shape, mesh, *, algo: str, remat: str,
                    dtype=jnp.bfloat16):
    """Returns (jitted_fn, abstract_args) for one (arch x shape x mesh)."""
    wrules = sh.weight_rules(mesh)
    pshard = param_shardings(transformer.model_specs(cfg), wrules, mesh)
    bspec = sh.batch_pspec(mesh, shape.global_batch)
    baxes = tuple(bspec)

    def shard_batch(tree):
        def f(s):
            spec = P(*(baxes + (None,) * (len(s.shape) - len(baxes)))) \
                if s.shape else P()
            return jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, spec))
        return jax.tree_util.tree_map(f, tree)

    if shape.kind == "train":
        state_specs = steps.train_state_specs(cfg, algo)
        # all train-state trees (params / anchor / g_t) share the weight
        # shardings
        state_sh = {k: pshard for k in state_specs}
        state_abs = jax.tree_util.tree_map(
            lambda s, spd: jax.ShapeDtypeStruct(s.shape, dtype, sharding=spd),
            state_specs, state_sh,
            is_leaf=lambda x: isinstance(x, ParamSpec))
        batch_abs = shard_batch(steps.train_batch_specs(cfg, shape, dtype))
        step = steps.STEP_BUILDERS[algo](cfg, remat=remat)
        fn = jax.jit(step, donate_argnums=(0,))
        return fn, (state_abs, batch_abs)

    params_abs = jax.tree_util.tree_map(
        lambda s, spd: jax.ShapeDtypeStruct(s.shape, dtype, sharding=spd),
        transformer.model_specs(cfg), pshard,
        is_leaf=lambda x: isinstance(x, ParamSpec))

    if shape.kind == "prefill":
        batch_abs = shard_batch(steps.prefill_batch_specs(cfg, shape, dtype))
        fn = jax.jit(steps.make_prefill_step(cfg))
        return fn, (params_abs, batch_abs)

    # decode
    crules = sh.cache_rules(mesh, shape)
    cache_specs = transformer.decode_cache_specs(
        cfg, shape.global_batch,
        transformer.effective_cache_len(cfg, shape.seq_len),
        shape.seq_len if cfg.encoder_decoder else 0)
    cache_sh = param_shardings(cache_specs, crules, mesh)
    cache_abs_plain = steps.abstract_decode_cache(cfg, shape, dtype)
    cache_abs = _sds_with_sharding(cache_abs_plain, cache_sh)
    batch_abs = shard_batch(steps.decode_batch_specs(cfg, shape))
    fn = jax.jit(steps.make_decode_step(cfg), donate_argnums=(2,))
    return fn, (params_abs, batch_abs, cache_abs)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D with N = active params (MoE: routed top-k)."""
    from repro.models.param import param_count
    total = param_count(transformer.model_specs(cfg))
    if cfg.is_moe:
        # subtract inactive expert params
        moe_blocks = sum(1 for k in cfg.layer_kinds if k.endswith("moe"))
        per_expert = 3 * cfg.d_model * cfg.d_ff
        total -= moe_blocks * (cfg.moe.num_experts - cfg.moe.top_k) \
            * per_expert
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    return float(mult) * total * tokens


def run_pair(arch: str, shape_name: str, *, multi_pod: bool, algo: str,
             remat: str, verbose: bool = True) -> Dict[str, Any]:
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "algo": algo, "remat": remat,
        "mesh": "2x16x16" if multi_pod else "16x16", "status": "skipped",
    }
    if shape.kind == "decode" and shape.seq_len > 40_000 \
            and not cfg.supports_subquadratic_decode:
        result["reason"] = ("long-context decode skipped: full-attention "
                            "enc-dec family has no sub-quadratic variant "
                            "(see DESIGN.md)")
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    with jax.set_mesh(mesh):
        fn, args = build_lowerable(cfg, shape, mesh, algo=algo, remat=remat)
        lowered = fn.lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        # older jax returns a one-element list of dicts, newer a dict
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        hlo = hloanalysis.analyze(compiled.as_text())

    # raw cost_analysis numbers (counts while-loop bodies once — recorded
    # for reference); the roofline terms use the loop-aware HLO accounting.
    flops_raw = float(cost.get("flops", 0.0)) if cost else 0.0
    bytes_raw = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    flops = hlo["dot_flops"]
    terms = {
        # per-device quantities (the module is SPMD-partitioned)
        "compute_s": flops / PEAK_FLOPS_BF16,
        "memory_s": hlo["traffic_bytes"] / HBM_BW,
        "collective_s": hlo["collective_bytes"] / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    result.update({
        "status": "ok",
        "chips": chips,
        "hlo_flops_per_device": flops,
        "hlo_traffic_bytes_per_device": hlo["traffic_bytes"],
        "collective_bytes_per_device": hlo["collectives"],
        "collective_bytes_total": hlo["collective_bytes"],
        "cost_analysis_raw": {"flops": flops_raw, "bytes": bytes_raw},
        "roofline_terms_s": terms,
        "dominant": dominant,
        "model_flops_total": mf,
        "useful_flops_ratio": mf / (flops * chips) if flops else 0.0,
        "memory_analysis": {
            k: getattr(mem, k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if mem is not None and hasattr(mem, k)},
    })
    if verbose:
        print(f"== {arch} x {shape_name} ({result['mesh']}, {algo}) ==")
        if mem is not None:
            print(f"  memory: args={result['memory_analysis'].get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
                  f"temp={result['memory_analysis'].get('temp_size_in_bytes', 0)/2**30:.2f}GiB")
        print(f"  flops/dev={flops:.3e} traffic/dev={hlo['traffic_bytes']:.3e} "
              f"coll/dev={hlo['collective_bytes']:.3e}")
        print(f"  terms: compute={terms['compute_s']*1e3:.2f}ms "
              f"memory={terms['memory_s']*1e3:.2f}ms "
              f"collective={terms['collective_s']*1e3:.2f}ms "
              f"-> {dominant}")
        print(f"  useful-flops ratio={result['useful_flops_ratio']:.3f}")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--algo", default="feddane",
                    choices=sorted(steps.STEP_BUILDERS))
    ap.add_argument("--remat", default="full",
                    choices=("full", "dots", "none"))
    ap.add_argument("--out", default=None, help="directory for JSON results")
    args = ap.parse_args(argv)

    archs = sorted(ARCHITECTURES) if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]

    failures = []
    for a in archs:
        for s in shapes:
            try:
                res = run_pair(a, s, multi_pod=args.multi_pod,
                               algo=args.algo, remat=args.remat)
            except Exception as e:  # a failure here is a bug in our system
                traceback.print_exc()
                res = {"arch": a, "shape": s, "algo": args.algo,
                       "mesh": "2x16x16" if args.multi_pod else "16x16",
                       "status": "error", "error": repr(e)}
                failures.append((a, s, repr(e)))
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                tag = f"{a}_{s}_{res['mesh']}_{args.algo}_{args.remat}"
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=2)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for a, s, e in failures:
            print(f"  {a} x {s}: {e}")
        sys.exit(1)
    print("\nall requested pairs lowered + compiled OK")


if __name__ == "__main__":
    main()
