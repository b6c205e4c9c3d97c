import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh).

For each combination this:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. jits the right step function (train / prefill / decode) with explicit
     in/out shardings from repro.sharding.partitioning,
  3. ``.lower(**ShapeDtypeStruct specs).compile()`` — NO allocation,
  4. records memory_analysis / cost_analysis / per-kind collective bytes
     into a JSON results file (incrementally, one entry per run).

Usage:
  python -m repro.launch.dryrun --arch llama3_8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out out.json]
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ARCH_IDS, INPUT_SHAPES, TrainConfig,
                                get_config, shape_supported)
from repro.launch.mesh import make_production_mesh
from repro.models import steps as STEPS
from repro.optim import adamw
from repro.roofline import analysis as ROOF
from repro.roofline import jaxpr_cost as JCOST
from repro.sharding import partitioning as PART

DEFAULT_OUT = Path(__file__).resolve().parents[3] / "benchmarks" / "dryrun_results.json"


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_one(arch_id: str, shape_name: str, multi_pod: bool,
              tcfg=None, verbose=True, extra_tags=None):
    cfg = get_config(arch_id)
    shape = INPUT_SHAPES[shape_name]
    ok, note = shape_supported(cfg, shape)
    if not ok:
        return {"arch": arch_id, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "note": note}

    # production default: 4 microbatches of 64 sequences (grad accumulation)
    tcfg = tcfg or TrainConfig(microbatches=4)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()

    params_s = STEPS.params_specs(cfg)
    p_sh = _named(mesh, PART.param_specs(params_s, cfg, mesh))
    win = STEPS.long_context_window(cfg, shape)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            batch_s = STEPS.batch_specs(cfg, shape)
            opt_s = STEPS.opt_specs(cfg)
            b_sh = _named(mesh, PART.batch_specs(batch_s, cfg, shape, mesh))
            o_sh = _named(mesh, PART.opt_specs(opt_s, params_s, cfg, mesh))
            step = STEPS.make_train_step(cfg, tcfg)
            jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
            traced = jitted.trace(params_s, opt_s, batch_s)
            tokens = shape.global_batch * shape.seq_len
            kind = "train"
        elif shape.kind == "prefill":
            batch_s = STEPS.batch_specs(cfg, shape)
            b_sh = _named(mesh, PART.batch_specs(batch_s, cfg, shape, mesh))
            step = STEPS.make_prefill_step(cfg, shape, window_override=win)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            traced = jitted.trace(params_s, batch_s)
            tokens = shape.global_batch * shape.seq_len
            kind = "prefill"
        else:  # decode
            kv_quant = bool(extra_tags and extra_tags.get("kv_quant"))
            cache_fn = STEPS.cache_specs_quant if kv_quant else STEPS.cache_specs
            cache_s = cache_fn(cfg, shape, window_override=win)
            c_sh = _named(mesh, PART.cache_specs(cache_s, cfg, shape, mesh))
            tok_s = STEPS.decode_token_specs(shape)
            t_sh = _named(mesh, PART.batch_specs(tok_s, cfg, shape, mesh))
            step = STEPS.make_serve_step(cfg, window_override=win)
            jitted = jax.jit(step, in_shardings=(p_sh, c_sh, t_sh),
                             out_shardings=(None, c_sh),
                             donate_argnums=(1,))  # cache updated in place
            traced = jitted.trace(params_s, cache_s, tok_s)
            tokens = shape.global_batch  # one new token per sequence
            kind = "decode"

        jcost = JCOST.jaxpr_cost(traced.jaxpr)
        t_lower = time.time() - t0
        lowered = traced.lower()
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
    except Exception as e:  # platform-dependent
        mem["error"] = str(e)

    hlo = compiled.as_text()
    n_chips = 512 if multi_pod else 256
    terms = ROOF.terms_from(jcost, hlo, n_chips)
    coll = ROOF.collective_bytes(hlo)

    n_active = cfg.active_param_count()
    model_flops_global = ROOF.model_flops_per_step(n_active, tokens, kind)
    model_flops_per_chip = model_flops_global / n_chips
    useful_ratio = (model_flops_per_chip / terms.flops) if terms.flops else 0.0

    rec = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok", "kind": kind,
        "swa_variant": bool(win),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem,
        "roofline": terms.as_dict(),
        "bytes_unfused_upper": jcost["bytes"] / n_chips,
        "dot_flops_frac": (jcost["dot_flops"] / jcost["flops"]) if jcost["flops"] else 0,
        "collectives": coll,
        "params": cfg.param_count(), "active_params": n_active,
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flops_ratio": useful_ratio,
        "tokens_per_step": tokens,
    }
    if extra_tags:
        rec.update(extra_tags)
    if verbose:
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "status", "compile_s")}))
        print("  memory:", mem)
        print("  roofline:", {k: (f"{v:.3e}" if isinstance(v, float) else v)
                              for k, v in rec["roofline"].items()})
    return rec


def append_result(rec, out_path: Path):
    out_path = Path(out_path)
    results = []
    if out_path.exists():
        results = json.loads(out_path.read_text())
    # replace same-key entry if present
    key = (rec["arch"], rec["shape"], rec["mesh"], rec.get("tag", ""))
    results = [r for r in results
               if (r["arch"], r["shape"], r["mesh"], r.get("tag", "")) != key]
    results.append(rec)
    out_path.write_text(json.dumps(results, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--tag", default="")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache for decode shapes (§Perf H2)")
    args = ap.parse_args()

    combos = []
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                for m in meshes:
                    combos.append((a, s, m))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        combos = [(args.arch, args.shape, m) for m in meshes]

    failures = 0
    extra = {"kv_quant": True} if args.kv_quant else None
    for a, s, m in combos:
        try:
            rec = lower_one(a, s, m, extra_tags=extra)
            if args.tag:
                rec["tag"] = args.tag
        except Exception:
            failures += 1
            rec = {"arch": a, "shape": s, "mesh": "multi" if m else "single",
                   "status": "error", "error": traceback.format_exc()[-2000:]}
            if args.tag:
                rec["tag"] = args.tag
            print(f"FAILED {a} {s} mesh={'multi' if m else 'single'}",
                  file=sys.stderr)
            print(rec["error"], file=sys.stderr)
        append_result(rec, Path(args.out))
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
